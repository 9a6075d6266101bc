"""The dense block computes what the harness computed before it became a
block, on a tiny configuration, against pins taken from the code before
the move (``dense_pins.json``, with the JAX version they were taken with):

* the weights equal the pinned digest bit for bit (threefry bits, then
  the same elementwise arithmetic);
* the float32 reference's logits, summarised at every read position by
  their largest value and by their projection on a seeded vector, lie
  within 1e-5 of each summary's scale of the pins.  A compiler that fuses
  or vectorises otherwise moves them by about 1e-6 of it; a changed
  operation or operand order moves them by far more;
* the float8 control lies as far from the float32 logits as it did (root
  mean square, within 1%): its rounding flips with the last bit of its
  input, so it is pinned by how much it loses, not bit for bit.
"""

import hashlib
import json
import pathlib

import numpy as np
import pytest

from bench import model
from bench.model import BENCH_DIR
from bench.weights import seed_key

PINS = json.loads((pathlib.Path(__file__).parent / "dense_pins.json")
                  .read_text())
DTYPES = ("bfloat16", "float32")


def _close(got, pinned):
    pinned = np.asarray(pinned)
    np.testing.assert_allclose(got, pinned, rtol=0,
                               atol=1e-5 * np.abs(pinned).max())


@pytest.mark.parametrize("dtype", DTYPES)
def test_dense_weights_and_logits_are_pinned(dtype):
    raw = json.loads((BENCH_DIR / "configs" / "stablelm3b.json").read_text())
    raw.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, intermediate_size=128,
               vocab_size=256, torch_dtype=dtype)
    cfg = model.parse(raw)
    m, block = cfg.model, cfg.block
    pins = PINS[dtype]
    w = block.make_weights(m, seed_key(2**31 + 7))
    h = hashlib.sha256()
    for k in sorted(w):
        h.update(np.asarray(w[k]).tobytes())
    assert h.hexdigest() == pins["weights"]

    toks = np.random.default_rng(5).integers(1, m.vocab, (4, 48)) \
        .astype(np.int32)
    read = np.tile(np.arange(20, 48, dtype=np.int32), (4, 1))
    f = np.asarray(block.logits_at(m, w, toks, read), np.float64)
    u = np.random.default_rng(11).standard_normal(m.vocab)
    _close(f.max(-1), pins["max"])
    _close(f @ u, pins["proj"])
    q = np.asarray(block.logits_at(m, w, toks, read, quant="fp8"),
                   np.float64)
    assert np.sqrt(np.mean((q - f) ** 2)) == \
        pytest.approx(pins["fp8_rms_gap"], rel=0.01)
