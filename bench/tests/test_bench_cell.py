"""A whole run at a tiny size on the CPU, past the harness's look for a
chip: the program passes the check, the float8 control does not, a run
whose served path is broken underneath comes out not correct, and a new
block is one new file."""

import dataclasses
import json
import sys
import time

import pytest

from bench import blocks, cell, model, trace
from bench.model import BENCH_DIR

LIMIT = 1e-3      # float32 program against the float32 reference

MIX = {"mode": "offline", "requests_per_row": 2, "prompt_buckets": [16, 32],
       "prompt_weights": [0.5, 0.5], "bucket_block": 2,
       "output": {"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 2,
                  "max": 12}}


def _tiny(**keys):
    raw = json.loads((BENCH_DIR / "configs" / "stablelm3b.json").read_text())
    raw.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, head_dim=16, intermediate_size=128,
               vocab_size=256, torch_dtype="float32", program_reduced=True,
               **keys)
    raw["serve"] = {"max_batch": 4, "max_len": 256, "tenants": [
        {"name": "t0", "pages": 16, "popularity": 0.5},
        {"name": "t1", "pages": 8, "popularity": 0.3},
        {"name": "t2", "pages": 4, "popularity": 0.2}]}
    return model.parse(raw)


def _run(seed=3, config=None, **kw):
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    return cell.run(bench, "stablelm3b.chat_offline", seed, 0.2, False,
                    time.perf_counter(), require_tpu=False,
                    config=config or _tiny(), mix=MIX,
                    limits={"max_logit_gap": LIMIT}, persist=False, **kw)


def _break_decode(monkeypatch, fault):
    """Serve through a decode step with ``fault`` planted in it."""
    import repro.launch.serve as serve

    build = serve.build_trusted_serve_steps

    def broken(*a, **kw):
        steps = build(*a, **kw)
        inner = steps.decode_fn

        def decode_fn(arena, pool, params, meta, x, guard):
            arena2, pool2, (meta2, nxt) = inner(arena, pool, params, meta,
                                                x, guard)
            if fault == "token":
                nxt = (nxt + 1) % 256
            else:                           # the KV pool left unchanged
                pool2 = pool
            return arena2, pool2, (meta2, nxt)

        return dataclasses.replace(steps, decode_fn=decode_fn)

    monkeypatch.setattr(serve, "build_trusted_serve_steps", broken)


def test_sound_run_is_correct_and_the_control_is_not():
    r = _run(control=True)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] % 8 == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"setup_s", "output_tok_per_s"}
    assert r["device"]["platform"] == "cpu"
    assert r["checks"]["max_logit_gap"]["value"] <= LIMIT
    # the control's tokens, in the program's place, fail the same check
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["max_logit_gap"]["value"] > 3 * LIMIT
    assert r["device"]["memory_in_use_bytes"] >= 0


@pytest.mark.parametrize("fault", ["token", "state"])
def test_a_broken_served_path_is_not_correct(monkeypatch, fault):
    _break_decode(monkeypatch, fault)
    r = _run()
    assert not r["correct"]
    assert r["checks"]["max_logit_gap"]["value"] > LIMIT


def test_a_traced_window_closes_past_its_decode_step_budget(monkeypatch):
    """With ``--trace 1`` the window ends at the first call boundary past
    ``TRACE_DECODE_STEPS``, long before ``seconds``; the summary the
    readers see is stubbed, since the CPU has no device plane."""
    summary = trace.Summary(window_s=1.0, busy_s=0.5, devices=1,
                            modules={}, top_ops=[], idle_gaps=[])
    monkeypatch.setattr(cell, "TRACE_DECODE_STEPS", 1)
    monkeypatch.setattr(trace, "load", lambda _dir: [])
    monkeypatch.setattr(trace, "summarize", lambda _planes: summary)
    bench = json.loads((BENCH_DIR.parent / "BENCHMARK.json").read_text())
    t0 = time.perf_counter()
    r = cell.run(bench, "stablelm3b.chat_offline", 3, 600, True, t0,
                 require_tpu=False, config=_tiny(), mix=MIX,
                 limits={"max_logit_gap": LIMIT}, persist=False)
    assert time.perf_counter() - t0 < 300
    assert r["correct"] and r["attempted"] == 8     # one call of 2 x 4 rows
    assert r["device"]["window_s"] == 1.0
    # the stub holds no decode step: the loss shows in the result
    assert r["device"]["decode_steps_traced"] == 0 \
        < r["device"]["decode_steps"]


COUNTING_BLOCK = """
from bench.blocks import INTERFACE, dense

calls = {}


def _counted(name):
    def call(*args, **kw):
        calls[name] = calls.get(name, 0) + 1
        return getattr(dense, name)(*args, **kw)
    return call


globals().update({name: _counted(name) for name in INTERFACE})
"""


@pytest.fixture
def counting_block(tmp_path, monkeypatch):
    """A block that only the lookup can find: a file outside ``bench/``
    that wraps the dense block and counts the calls that reach it."""
    name = "counting_dense"
    (tmp_path / f"{name}.py").write_text(COUNTING_BLOCK)
    monkeypatch.setattr(blocks, "__path__", [str(tmp_path),
                                             *blocks.__path__])
    yield name
    sys.modules.pop(f"{blocks.__name__}.{name}", None)
    vars(blocks).pop(name, None)


def test_a_new_block_is_one_new_file(counting_block):
    cfg = _tiny(block=counting_block)
    calls = cfg.block.calls
    r = _run(config=cfg)
    assert r["correct"] and r["failed"] == 0
    for name in ("parse", "program_fields", "make_weights",
                 "to_program_tree", "logits_at"):
        assert calls.get(name, 0) >= 1, name
    # the per-layer readers take their counts from the same block
    summary = trace.Summary(window_s=1.0, busy_s=0.5, devices=1,
                            modules={"jit_decode_step(1)": [0.01] * 3},
                            top_ops=[], idle_gaps=[])
    served = [cell.Served(0, "t0", [1] * 16, 4, [5, 6, 7, 8], 0.5)]
    ctx = cell.Context(model=cfg.model, cfg=cfg, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        served=served, decode_steps=3, trace=summary)
    for reader in ("decode_step_roofline", "mfu.offline"):
        assert cell.load_reader(reader)(ctx) > 0
    for name in ("weight_bytes_per_step", "decode_token_flops",
                 "decode_token_bytes", "prefill_flops"):
        assert calls.get(name, 0) >= 1, name
