"""The sparse-expert windowed block (``bench/blocks/moe_window.py``):

* its weights, its float32 reference and its float8 control against pins
  taken when it was written (``moe_window_pins.json``, with the JAX
  version they were taken with), on the tiny configuration of
  ``bench/tests/tiny.py``: weights bit for bit, the reference's logits by
  their largest value and their projection on a seeded vector within
  1e-5 of each summary's scale, the control by its root-mean-square gap
  within 1% (its rounding flips with the last bit of its input);
* its counts, against the numbers worked out by hand at Mellum2's
  published widths;
* a whole run of a tiny Mellum2 cell on the CPU, and the ``.code``
  cell's readers.
"""

import hashlib
import json
import pathlib
import time

import numpy as np
import pytest

from bench import cell, flops, model, trace
from bench.blocks import moe_window
from bench.model import BENCH_DIR
from bench.tests import tiny
from bench.weights import seed_key

PINS = json.loads((pathlib.Path(__file__).parent / "moe_window_pins.json")
                  .read_text())
V5E = (197e12, 819e9)
LIMIT = 1e-3      # float32 program against the float32 reference


def _close(got, pinned):
    pinned = np.asarray(pinned)
    np.testing.assert_allclose(got, pinned, rtol=0,
                               atol=1e-5 * np.abs(pinned).max())


@pytest.mark.parametrize("dtype", ("bfloat16", "float32"))
def test_moe_window_weights_and_logits_are_pinned(dtype):
    cfg = model.parse(tiny.mellum(torch_dtype=dtype))
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


MELLUM = model.load("mellum2").model


def test_mellum_experts_reached_and_weights():
    # 16 held of 64, top-8: a token reaches each held expert w.p. 1/8
    assert moe_window.experts_reached(MELLUM, 1) == 16 * (1 - 56 / 64)
    assert moe_window.experts_reached(MELLUM, 1) == 2.0
    assert moe_window.experts_reached(MELLUM, 2) == 16 * (1 - (56 / 64) ** 2)
    assert moe_window.experts_reached(MELLUM, 2) == 3.75
    assert moe_window.held_pairs(MELLUM) == 2.0
    # q 4096, k,v 512 each, o 4096 x 2304; an expert 3 x 2304 x 896
    assert moe_window.attention_params(MELLUM) == 2304 * (4096 + 1024) \
        + 4096 * 2304 == 21_233_664
    assert moe_window.expert_params(MELLUM) == 6_193_152
    # bf16 attention and head, f32 router and RMSNorm scales, and the
    # 3.75 experts two rows reach, in each of 28 layers
    w = 2 * (28 * 21_233_664 + 2304 * 98304) + 4 * 28 * 2304 * 64 \
        + 4 * 57 * 2304 + 2 * 28 * 3.75 * 6_193_152
    assert moe_window.weight_bytes_per_step(MELLUM) == w
    assert 2.95e9 < w < 2.97e9


@pytest.mark.parametrize("ctx,window_keys", [(512, 512), (1024, 1024),
                                             (5000, 1024)])
def test_mellum_window_keys(ctx, window_keys):
    # 21 sliding layers attend min(ctx, 1024) keys, 7 full layers ctx
    assert moe_window.keys_attended(MELLUM, ctx) == \
        21 * window_keys + 7 * ctx
    # K and V of 4 heads of 128 at bfloat16, read, plus one written a layer
    assert moe_window.decode_token_bytes(MELLUM, ctx) == \
        2048 * (21 * window_keys + 7 * ctx + 28)


def test_mellum_prefill_counts():
    m = MELLUM
    token = 2 * 28 * (21_233_664 + 2304 * 64 + 2 * 6_193_152)
    # 448 tokens: all within the window, so every layer is causal-full
    keys = 28 * 448 * 449 // 2
    assert moe_window.prefill_flops(m, 448) == \
        448 * token + 2 * 2304 * 98304 + 4 * 32 * 128 * keys
    # 5376: sliding layers attend 1024 keys past the first 1024 positions
    slide = 1024 * 1025 // 2 + (5376 - 1024) * 1024
    full = 5376 * 5377 // 2
    assert moe_window.prefill_flops(m, 5376) == 5376 * token \
        + 2 * 2304 * 98304 + 4 * 32 * 128 * (21 * slide + 7 * full)
    # a wave of 2 x 448 reads every held expert: its operations and its
    # bytes take about as long (7.26 GB at 819 GB/s, 8.9 ms); at 2 x 5376
    # the operations take 5 times as long as the bytes
    def bounds(p):
        return (2 * moe_window.prefill_flops(m, p) / V5E[0],
                moe_window.prefill_bytes(m, p, 2) / V5E[1])

    ops, nbytes = bounds(448)
    assert 0.95 < ops / nbytes < 1.1
    assert 7.25e9 < moe_window.prefill_bytes(m, 448, 2) < 7.27e9
    ops, nbytes = bounds(5376)
    assert ops / nbytes > 5
    assert moe_window.prefill_bytes(m, 448, 2) - \
        moe_window.prefill_bytes(m, 448) == 448 * 28 * 2048


def test_mellum_decode_is_memory_bound_at_2_x_8192():
    cfg = model.load("mellum2")
    assert (cfg.max_batch, cfg.max_len) == (2, 8192)
    assert flops.memory_bound_always(cfg.block, cfg.model, 2, 8192, *V5E)


#: offline calls whose prompts (48, 160) run past the window of 32
MIX = {"mode": "offline", "requests_per_row": 2, "prompt_buckets": [48, 160],
       "prompt_weights": [0.5, 0.5], "bucket_block": 2,
       "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                  "max": 12}}


def _tiny_mellum():
    """The tiny Mellum2 configuration as the program's reduced one: 4
    layers (3 sliding, 1 full), window 32, 1 of 4 experts held, top-2."""
    raw = tiny.mellum(hidden_size=64, num_key_value_heads=4,
                      sliding_window=32, num_experts=1,
                      num_experts_published=4, num_experts_per_tok=2,
                      program_reduced=True)
    raw["serve"] = {"max_batch": 2, "max_len": 256, "tenants": [
        {"name": "t0", "pages": 4, "popularity": 0.5},
        {"name": "t1", "pages": 4, "popularity": 0.3},
        {"name": "t2", "pages": 4, "popularity": 0.2}]}
    return model.parse(raw)


def test_a_sound_mellum_run_is_correct_and_its_control_is_not():
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    r = cell.run(bench, "mellum2.code_offline", 5, 0.2, False,
                 time.perf_counter(), require_tpu=False,
                 config=_tiny_mellum(), mix=MIX,
                 limits={"max_logit_gap": LIMIT}, persist=False,
                 control=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] % 4 == 0
    assert set(r["metrics"]) == {"setup_s", "output_tok_per_s"}
    assert r["checks"]["max_logit_gap"]["value"] <= LIMIT
    assert r["control"]["correct"] is False


def _cell_readers(name):
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    return [(m["name"], m["unit"]) for m in bench["per_layer"]
            if name in m.get("workloads", ())]


@pytest.mark.parametrize("reader,unit", _cell_readers("mellum2.code_offline"))
def test_the_code_cells_readers_read(reader, unit):
    """Every per-layer metric listed for ``mellum2.code_offline`` reads
    over the ``moe_window`` block's counts: a share within (0, 100) and a
    time above 0 from a trace that holds the window's steps.  A trace that
    holds half of them reads on through ``prefill_roofline.code`` (means),
    and nothing through ``decode_step_roofline``, which reads only a
    trace that holds every step."""
    cfg = model.load("mellum2")
    served = [cell.Served(i, "t0", [1] * p, 4, [5, 6, 7, 8], 0.5)
              for i, p in enumerate((448, 448, 5376, 5376))]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    def ctx(modules):
        summary = trace.Summary(window_s=1.0, busy_s=0.5, devices=1,
                                modules=modules, top_ops=[], idle_gaps=[])
        return cell.Context(model=cfg.model, cfg=cfg, peaks=peaks,
                            served=served, decode_steps=12, trace=summary)

    read = cell.load_reader(reader)
    steps = {"jit_decode_step(1)": [0.012] * 12,
             "jit_prefill_step(2)": [0.05, 0.3]}
    whole = read(ctx(steps))
    assert whole > 0 and (unit != "%" or whole < 100)
    half = {k: v[:len(v) // 2] for k, v in steps.items()}
    if reader == "prefill_roofline.code":
        assert read(ctx(half)) > 0
        assert read(ctx({})) is None
    if reader == "decode_step_roofline":
        assert read(ctx(half)) is None
