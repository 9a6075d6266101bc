"""Host spans and serve counters on the profiler's clock
(``Telemetry.span``, ``serve_continuous``), the request latency they
close on delivery, the bounded launch-phase samples, and the stable
names of the served step programs.

Proof obligations:

* **nesting** — a profiled ``serve_continuous`` writes every span of
  ``HOST_SPANS`` as ``guardian.<name>``, each inside its parent, one
  ``guardian.serve.cycle`` per counted ``serve_cycles``;
* **off-mode** — with telemetry off no ``guardian.*`` span is written and
  no serve counter moves;
* **delivery** — a served request's ``request_e2e_us`` runs to the return
  of the call that delivers its tokens, not to the enqueue of its last
  step;
* **bounded** — the launch-phase series keep a ring of the newest
  launches, and nothing with telemetry off;
* **names** — the fused multi-engine decode compiles under a name that
  holds ``decode_step``; program spans leave the benchmark's trace
  reduction unchanged.
"""

import os
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import FencePolicy, GuardianManager, SharingMode
from repro.core.manager import LaunchStats
from repro.core.telemetry import HOST_SPAN_PREFIX, HOST_SPANS
from repro.launch.serve import (
    ServeEngine,
    make_shared_manager,
    serve_continuous,
    serve_engines,
)
from repro.launch.steps import DECODE_STEP, PREFILL_STEP

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench import trace as bench_trace  # noqa: E402

CFG = get_config("stablelm-3b").reduced()
SERVE_COUNTERS = ("serve_cycles", "serve_host_cpu_ns",
                  "prefill_tokens_useful", "prefill_tokens_computed",
                  "kv_token_cycles_live")


def _prompts(n, plen=6):
    return [[(7 * i + 3 * j) % 211 + 1 for j in range(plen)]
            for i in range(n)]


def _engine(telemetry=True):
    eng = ServeEngine(CFG, max_batch=2, max_len=64, seed=0, paged=True,
                      telemetry=telemetry)
    eng.register_tenant("t", 2)
    return eng


def _profiled_call(eng, trace_dir, budgets=(1, 3, 2)):
    """Submit and serve under the profiler; the ``guardian.*`` host spans
    of the trace as (name, start, end), and the call's output."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        rids = [eng.submit("t", p[:4 + i], max_new=n)
                for i, (p, n) in enumerate(zip(_prompts(len(budgets)),
                                               budgets))]
        out = serve_continuous([eng], max_new_tokens=4)[0]
    finally:
        jax.profiler.stop_trace()
    spans = [(name, s, s + d)
             for plane in bench_trace.load(str(trace_dir))
             if not bench_trace.DEVICE_PLANE.match(plane["name"])
             for line in plane["lines"]
             for name, s, d in line["events"]
             if name.startswith(HOST_SPAN_PREFIX)]
    return spans, rids, out


def test_profiled_serve_spans_nest_under_their_parents(tmp_path):
    eng = _engine()
    spans, rids, out = _profiled_call(eng, tmp_path)
    assert sorted(out) == sorted(rids)
    by_name = {}
    for name, s, e in spans:
        by_name.setdefault(name[len(HOST_SPAN_PREFIX):], []).append((s, e))
    assert set(by_name) == {name for name, _ in HOST_SPANS}
    for name, parent in HOST_SPANS:
        if parent is None:
            continue
        for s, e in by_name[name]:
            assert any(ps <= s and e <= pe for ps, pe in by_name[parent]), \
                f"{name} [{s}, {e}] outside every {parent}"
    reg = eng.manager.telemetry.registry
    assert len(by_name["serve.call"]) == 1
    assert len(by_name["serve.submit"]) == len(rids)
    assert len(by_name["serve.cycle"]) == reg.counter("serve_cycles") > 0
    assert reg.counter("serve_host_cpu_ns") > 0
    # 3 prompts of 4, 5, 6 tokens on 2 rows: two join the first cycle
    # (one prefill of 2 x 5), the third when the 1-token request leaves
    # (one prefill of 2 x 6)
    assert reg.counter("prefill_tokens_useful") == 4 + 5 + 6
    assert reg.counter("prefill_tokens_computed") == 2 * 5 + 2 * 6
    assert reg.counter("kv_token_cycles_live") > 0
    assert reg.gauge("kv_bytes_per_token") == (
        2 * CFG.decoder_layers * CFG.n_kv_heads * CFG.head_dim
        * jnp.dtype(CFG.dtype).itemsize)
    assert reg.gauge("kv_pool_bytes") == sum(
        leaf.nbytes for leaf in jax.tree.leaves(eng._pool.buf))


def test_telemetry_off_writes_no_span_and_counts_nothing(tmp_path):
    eng = _engine(telemetry=False)
    spans, rids, out = _profiled_call(eng, tmp_path)
    assert sorted(out) == sorted(rids)
    assert spans == []
    reg = eng.manager.telemetry.registry
    assert all(reg.counter(n) == 0 for n in SERVE_COUNTERS)
    assert reg.gauge("kv_pool_bytes") is None
    assert all(len(series) == 0 for series in (
        eng.manager.launch_stats.lookup_ns,
        eng.manager.launch_stats.dispatch_ns))


def test_request_latency_runs_to_delivery():
    """A 1-token request leaves its row cycles before the 12-token one
    ends; its latency still runs until the call returns its tokens."""
    eng = _engine()
    tel = eng.manager.telemetry
    t0 = tel.trace.now_us()
    rids = [eng.submit("t", p, max_new=n)
            for p, n in zip(_prompts(3), (1, 12, 2))]
    serve_continuous([eng], max_new_tokens=12)
    t1 = tel.trace.now_us()
    spans = {sp.rid: sp for sp in tel.spans.closed}
    assert sorted(spans) == sorted(rids)
    for rid in rids:
        sp = spans[rid]
        assert sp.e2e_us >= (t1 - t0) - 2000.0, (rid, sp.e2e_us, t1 - t0)
        assert sum(sp.components().values()) == sp.e2e_cycles
    # the cycle stamps are those of the row's leave: the short request
    # finished in fewer cycles than the long one
    assert spans[rids[0]].e2e_cycles < spans[rids[1]].e2e_cycles
    hist = tel.registry.histogram("request_e2e_us", tenant="t")
    assert hist.count == len(rids)


def test_launch_stats_keep_a_bounded_ring():
    mgr = GuardianManager(total_slots=256, mode=SharingMode.TIME_SHARE,
                          policy=FencePolicy.BITWISE,
                          standalone_fast_path=False)
    c = mgr.register_tenant("a", 64)
    mgr.register_tenant("b", 64)

    def bump(arena, ptr, n):
        return arena.at[ptr + jnp.arange(n, dtype=jnp.int32)].add(1.0), None

    c.module_load("bump", bump)
    p = c.malloc(8)
    for _ in range(100_000):
        c.launch_kernel("bump", ptrs=[p], args=(8,))
    c.synchronize()
    stats = mgr.launch_stats
    for series in (stats.lookup_ns, stats.augment_ns, stats.dispatch_ns):
        assert len(series) == LaunchStats.KEEP
    assert stats.summary()["dispatch_ns"] > 0
    off = LaunchStats(enabled=False)
    off.dispatch_ns.append(1)
    assert len(off.dispatch_ns) == 0 and off.summary()["dispatch_ns"] == 0.0


def test_step_programs_carry_stable_names():
    """Solo steps jit as ``jit_prefill_step`` / ``jit_decode_step``; two
    engines' fused decode as ``jit_fused_decode_step``."""
    mgr = make_shared_manager(2, max_batch=2)
    engines = [ServeEngine(CFG, max_batch=2, max_len=16, seed=s,
                           manager=mgr) for s in (0, 1)]
    for e, t in zip(engines, ("a", "b")):
        e.register_tenant(t, 2)
        e.submit(t, _prompts(1)[0])
    serve_engines(engines, max_new_tokens=2)
    names = {fn.__name__ for key, fn in mgr.scheduler._fused_cache.items()
             if key[0] == "trusted"}
    assert names and all(DECODE_STEP in n or PREFILL_STEP in n
                         for n in names)
    assert f"fused_{DECODE_STEP}" in names
    solo = engines[0]._steps
    assert solo.prefill_fn.__name__ == PREFILL_STEP
    assert solo.decode_fn.__name__ == DECODE_STEP


def test_program_spans_leave_the_trace_reduction_unchanged():
    ms = 1e6
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ("bench.window", 0.0, 100 * ms),
        ("bench.serve_call", 10 * ms, 80 * ms),
    ]}]}
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_prefill_step(1)", 10 * ms, 20 * ms),
            ("jit_fused_decode_step(2)", 40 * ms, 10 * ms)]},
        {"name": "XLA Ops", "events": [
            ("fusion.1", 10 * ms, 20 * ms), ("gather", 40 * ms, 10 * ms)]},
    ]}
    program = [("guardian.serve.call", 10 * ms, 80 * ms),
               ("guardian.serve.cycle", 12 * ms, 30 * ms),
               ("guardian.manager.drain", 20 * ms, 25 * ms)]
    plain = bench_trace.summarize([host, device])
    host2 = {"name": host["name"], "lines": [
        {"name": "python3", "events": host["lines"][0]["events"] + program}]}
    traced = bench_trace.summarize([host2, device])
    assert traced == plain
    assert traced.module_seconds(DECODE_STEP) == (1, pytest.approx(0.010))
