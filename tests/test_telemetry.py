"""Flight recorder (core/telemetry.py) + operator dashboard
(launch/dashboard.py, repro.top) — DESIGN.md §Observability.

Covers: Histogram percentile semantics, MetricsRegistry per-tenant
series + the ``enabled=False`` no-op discipline, EventTrace ring buffer
and Chrome/Perfetto export (round-trips ``json.loads`` with per-track
monotonic timestamps), the manager-plane instrumentation (drain cycles,
queue age, quarantine gauges, lifecycle events), and the three headline
invariants:

* logical metrics are **bit-identical** between two serve runs of the
  same requests (wall-clock series are excluded via
  ``snapshot(include_timing=False)``);
* ``telemetry=False`` is byte-identical on the data plane and leaves
  the registry/trace empty;
* telemetry adds **zero device syncs** to fenced (BITWISE) traffic —
  the ViolationLog dirty-flag discipline is untouched.

Deterministic sweeps mirror every hypothesis property (tier-1 runs
without hypothesis; see tests/_hyp.py).
"""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import (
    EventTrace,
    FencePolicy,
    GuardianManager,
    Histogram,
    MetricsRegistry,
    ThresholdPolicy,
)
from repro.core.telemetry import (
    DRAIN_TRACK,
    GLOBAL,
    QUEUE_AGE_BOUNDS,
)
from repro.launch.dashboard import format_report, sparkline

TOTAL = 512


def bump(arena, ptr, n):
    idx = ptr + jnp.arange(n, dtype=jnp.int32)
    vals = jnp.take(arena, idx, axis=0)
    return arena.at[idx].set(vals + 1.0), None


def make_mgr(n_tenants=3, **kw):
    kw.setdefault("total_slots", TOTAL)
    kw.setdefault("standalone_fast_path", False)
    mgr = GuardianManager(**kw)
    clients, ptrs = [], []
    for i in range(n_tenants):
        c = mgr.register_tenant(f"t{i}", TOTAL // (2 * n_tenants))
        c.module_load("bump", bump)
        p = c.malloc(8)
        c.memcpy_h2d(p, np.zeros(8, np.float32))
        clients.append(c)
        ptrs.append(p)
    mgr.synchronize()
    return mgr, clients, ptrs


def drive(mgr, clients, ptrs, rounds=3):
    for _ in range(rounds):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(8,))
        mgr.run_queued()


# ---------------------------------------------------------------------------
# Histogram
# ---------------------------------------------------------------------------


def test_histogram_exact_on_edge_valued_ints():
    h = Histogram(QUEUE_AGE_BOUNDS)
    for v in (0, 1, 1, 2, 4):
        h.observe(v)
    assert h.count == 5 and h.mean == pytest.approx(1.6)
    assert h.percentile(50) == 1.0
    assert h.percentile(90) == 4.0
    assert h.percentile(99) == 4.0
    p = h.percentiles()
    assert p == {"p50": 1.0, "p90": 4.0, "p99": 4.0,
                 "count": 5.0, "mean": pytest.approx(1.6)}


def test_histogram_empty_and_overflow():
    h = Histogram((0, 1, 2))
    assert h.percentile(99) == 0.0 and h.mean == 0.0
    h.observe(10_000)                       # overflow bucket
    assert h.percentile(50) == 10_000.0     # exact observed max
    assert h.to_dict()["max"] == 10_000.0
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((2, 1))


def test_histogram_percentiles_monotonic_sweep():
    rng = np.random.default_rng(0)
    for _ in range(10):
        h = Histogram(QUEUE_AGE_BOUNDS)
        vals = rng.integers(0, 200, size=rng.integers(1, 40))
        for v in vals:
            h.observe(int(v))
        ps = [h.percentile(q) for q in (1, 25, 50, 75, 90, 99, 100)]
        assert ps == sorted(ps)
        assert h.percentile(100) >= vals.max() or \
            h.percentile(100) == float(vals.max())
        assert h.count == len(vals)


@given(st.lists(st.integers(min_value=0, max_value=300), min_size=1,
                max_size=40))
@settings(max_examples=30, deadline=None)
def test_histogram_percentiles_monotonic_property(vals):
    h = Histogram(QUEUE_AGE_BOUNDS)
    for v in vals:
        h.observe(v)
    ps = [h.percentile(q) for q in (1, 50, 90, 99, 100)]
    assert ps == sorted(ps)
    assert h.count == len(vals)
    # a percentile is never below the true minimum (bucket upper edges)
    assert ps[0] >= 0


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_registry_disabled_is_a_noop():
    reg = MetricsRegistry(enabled=False)
    reg.inc("x")
    reg.set_gauge("g", 1.0, tenant="a")
    reg.observe("h", 3.0)
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}
    assert reg.counter("x") == 0 and reg.gauge("g", tenant="a") is None
    assert reg.percentiles("h")["count"] == 0.0


def test_registry_per_tenant_series_and_forget():
    reg = MetricsRegistry()
    reg.inc("req", tenant="a")
    reg.inc("req", n=2, tenant="b")
    reg.inc("req")                           # global series
    reg.observe("queue_age_cycles", 3, tenant="a")
    reg.set_gauge("util", 0.5, tenant="a")
    assert reg.counter("req", tenant="a") == 1
    assert reg.counter("req", tenant="b") == 2
    assert reg.counter("req") == 1           # GLOBAL key is separate
    reg.forget_tenant("a")
    assert reg.counter("req", tenant="a") == 0
    assert reg.counter("req", tenant="b") == 2
    assert reg.gauge("util", tenant="a") is None
    assert reg.histogram("queue_age_cycles", tenant="a") is None


def test_registry_timing_series_excluded_from_logical_snapshot():
    reg = MetricsRegistry()
    reg.observe("drain_cycle_us", 123.0, timing=True)
    reg.observe("queue_age_cycles", 1)
    full = reg.snapshot(include_timing=True)["histograms"]
    logical = reg.snapshot(include_timing=False)["histograms"]
    assert "drain_cycle_us" in full
    assert "drain_cycle_us" not in logical
    assert "queue_age_cycles" in logical


def _feed(reg, ops):
    for kind, name, val, tenant in ops:
        if kind == 0:
            reg.inc(name, n=val, tenant=tenant)
        elif kind == 1:
            reg.set_gauge(name, float(val), tenant=tenant)
        else:
            reg.observe(name, float(val), tenant=tenant)


def test_registry_determinism_sweep():
    """Two registries fed the same op sequence are bit-identical — the
    substrate of the run-to-run serve metrics comparison."""
    rng = np.random.default_rng(1)
    ops = [(int(rng.integers(0, 3)), f"m{rng.integers(3)}",
            int(rng.integers(1, 9)),
            [None, "a", "b"][rng.integers(3)]) for _ in range(200)]
    a, b = MetricsRegistry(), MetricsRegistry()
    _feed(a, ops)
    _feed(b, ops)
    assert a.snapshot() == b.snapshot()
    assert a.to_prometheus() == b.to_prometheus()


@given(st.lists(st.tuples(st.integers(0, 2), st.sampled_from("xyz"),
                          st.integers(1, 9),
                          st.sampled_from([None, "a", "b"])),
                max_size=60))
@settings(max_examples=25, deadline=None)
def test_registry_determinism_property(ops):
    a, b = MetricsRegistry(), MetricsRegistry()
    _feed(a, ops)
    _feed(b, ops)
    assert a.snapshot() == b.snapshot()
    assert a.to_prometheus() == b.to_prometheus()


def test_prometheus_exposition_shape():
    reg = MetricsRegistry()
    reg.inc("requests", n=3, tenant="a")
    reg.observe("queue_age_cycles", 1, tenant="a")
    reg.observe("queue_age_cycles", 500, tenant="a")   # overflow
    reg.set_gauge("util", 0.25)
    text = reg.to_prometheus()
    assert text.endswith("\n")
    lines = text.splitlines()
    assert '# TYPE guardian_requests_total counter' in lines
    assert 'guardian_requests_total{tenant="a"} 3' in lines
    assert "guardian_util 0.25" in lines
    # histogram triple: cumulative buckets, +Inf == count, sum
    inf = [l for l in lines if '+Inf' in l]
    assert inf == ['guardian_queue_age_cycles_bucket'
                   '{tenant="a",le="+Inf"} 2']
    assert 'guardian_queue_age_cycles_count{tenant="a"} 2' in lines
    assert 'guardian_queue_age_cycles_sum{tenant="a"} 501' in lines
    # bucket counts are cumulative (never decreasing)
    buckets = [int(l.rsplit(" ", 1)[1]) for l in lines
               if "queue_age_cycles_bucket" in l]
    assert buckets == sorted(buckets)


# ---------------------------------------------------------------------------
# EventTrace + Perfetto export
# ---------------------------------------------------------------------------


def test_trace_ring_buffer_drops_oldest():
    tr = EventTrace(capacity=4)
    for i in range(6):
        tr.emit(f"e{i}", "trk", cycle=i)
    assert len(tr) == 4 and tr.emitted == 6
    assert [e.name for e in tr.events()] == ["e2", "e3", "e4", "e5"]
    tr.clear()
    assert len(tr) == 0 and tr.emitted == 6


def test_trace_disabled_emits_nothing():
    tr = EventTrace(enabled=False)
    tr.emit("e", "trk", cycle=0)
    assert len(tr) == 0 and tr.emitted == 0


def test_chrome_export_roundtrips_with_monotonic_tracks():
    tr = EventTrace()
    tr.emit("a", "t0", cycle=0, slots=4)
    tr.emit("b", "t1", cycle=0)
    tr.emit("c", "t0", cycle=1)
    tr.emit("drain", DRAIN_TRACK, cycle=1, dur_us=5.0,
            ts_us=tr.now_us())
    doc = json.loads(tr.to_json())           # round-trips json.loads
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert {"guardian", "t0", "t1", DRAIN_TRACK} <= names
    body = [e for e in evs if e["ph"] in ("i", "X")]
    assert all(e["cat"] == "guardian" for e in body)
    assert all("cycle" in e["args"] for e in body)
    by_tid = {}
    for e in body:
        by_tid.setdefault(e["tid"], []).append(e["ts"])
    for ts in by_tid.values():               # per-track monotonic
        assert ts == sorted(ts)
    x = [e for e in body if e["ph"] == "X"]
    assert len(x) == 1 and x[0]["dur"] == 5.0


# ---------------------------------------------------------------------------
# Manager-plane instrumentation
# ---------------------------------------------------------------------------


def test_drain_instrumentation_and_report_shape():
    mgr, clients, ptrs = make_mgr(3)
    drive(mgr, clients, ptrs, rounds=3)
    reg = mgr.telemetry.registry
    assert reg.counter("drain_cycles") > 0
    assert reg.counter("tenants_registered") == 3
    for t in ("t0", "t1", "t2"):
        assert reg.percentiles("queue_age_cycles",
                               tenant=t)["count"] == 3.0
    rep = mgr.metrics_report()
    for key in ("tenants", "scheduler", "drain", "drain_cycles",
                "launch", "jit_cache", "elastic", "memory",
                "violations", "counters", "gauges", "trace"):
        assert key in rep
    row = rep["tenants"]["t1"]
    assert row["state"] == "active"
    assert {"p50", "p90", "p99", "count"} <= set(row["queue_age"])
    assert rep["scheduler"]["queue_age"]["count"] == 9.0
    assert rep["drain"]["count"] == float(reg.counter("drain_cycles"))
    # drain-cycle duration events land on their own Perfetto track
    drains = [e for e in mgr.telemetry.trace.events()
              if e.track == DRAIN_TRACK]
    assert drains and all(e.dur_us is not None for e in drains)
    starts = [e.ts_us for e in drains]
    assert starts == sorted(starts)          # cycles never overlap


def test_legacy_reports_are_views_of_the_recorder():
    mgr, clients, ptrs = make_mgr(2)
    drive(mgr, clients, ptrs, rounds=1)
    assert mgr.violation_report() == mgr.telemetry.violation_view()
    assert mgr.jit_cache_stats() == mgr.telemetry.jit_cache_view()
    vio = mgr.violation_report()
    assert {"tenants", "transfer_violations", "events"} <= set(vio)
    jc = mgr.jit_cache_stats()
    assert {"capacity", "entries", "per_kernel", "evictions",
            "fused_capacity", "fused_entries",
            "fused_evictions"} <= set(jc)


def test_queue_age_percentiles_under_lookahead():
    """2 tenants x 2 ops with lookahead=1 dispatch as one width-4 step:
    ages (1, 1, 0, 0) -> p50=0, p90=p99=1 — exact, because ages are
    integers on bucket edges (tests the ROADMAP per-class p50/p99 row)."""
    mgr, clients, ptrs = make_mgr(2, lookahead_cycles=1)
    for _ in range(2):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(8,))
    mgr.synchronize()
    st_ = mgr.scheduler.stats
    assert st_.queue_age_percentiles() == {
        "p50": 0.0, "p90": 1.0, "p99": 1.0, "count": 4.0, "mean": 0.5}
    assert mgr.telemetry.registry.counter("lookahead_holds") >= 1
    assert any(e.name == "lookahead_flush"
               for e in mgr.telemetry.trace.events())


def test_telemetry_off_is_byte_identical_and_empty():
    arenas, snaps = [], []
    for enabled in (True, False):
        mgr, clients, ptrs = make_mgr(2, telemetry=enabled)
        drive(mgr, clients, ptrs, rounds=2)
        mgr.synchronize()
        arenas.append(np.asarray(mgr.arena.buf))
        snaps.append(mgr.telemetry.registry.snapshot())
        if not enabled:
            assert len(mgr.telemetry.trace) == 0
    np.testing.assert_array_equal(arenas[0], arenas[1])
    assert snaps[1] == {"counters": {}, "gauges": {}, "histograms": {}}


def test_zero_added_syncs_on_fenced_traffic():
    """BITWISE drains with telemetry ON must never read device memory:
    the ViolationLog stays clean and is never snapshotted (the
    dirty-flag discipline) — the record paths are host dict writes."""
    mgr, clients, ptrs = make_mgr(2, policy=FencePolicy.BITWISE)
    calls = []
    orig = mgr.violog.snapshot
    mgr.violog.snapshot = lambda: (calls.append(1), orig())[1]
    drive(mgr, clients, ptrs, rounds=3)
    assert mgr.telemetry.registry.counter("drain_cycles") > 0
    assert not calls                         # no log sync on fenced drains
    assert not mgr.violog.dirty


def test_quarantine_gauges_counters_and_events():
    mgr = GuardianManager(total_slots=TOTAL, policy=FencePolicy.CHECK,
                          standalone_fast_path=False,
                          quarantine_policy=ThresholdPolicy(
                              quarantine_after=1))
    a = mgr.register_tenant("a", 128)
    mgr.register_tenant("b", 128)
    a.module_load("bump", bump)
    part = mgr.bounds.lookup("a")
    a.launch_kernel("bump", args=(jnp.int32(part.end + 50), 4))
    mgr.run_queued()                         # poll quarantines "a"
    reg = mgr.telemetry.registry
    assert not mgr.quarantine.state_of("a").admissible
    assert reg.counter("quarantines", tenant="a") == 1
    assert reg.gauge("violations_gather", tenant="a") >= 1
    names = {(e.name, e.track) for e in mgr.telemetry.trace.events()}
    assert ("quarantine", "a") in names
    assert mgr.metrics_report()["tenants"]["a"]["state"] != "active"


# ---------------------------------------------------------------------------
# Serving plane: run-to-run bit-identity + request counters
# ---------------------------------------------------------------------------


def _serve_run():
    from repro.configs import get_config
    from repro.launch.serve import ServeEngine

    cfg = get_config("stablelm-3b").reduced()
    eng = ServeEngine(cfg, max_batch=4, max_len=16)
    eng.register_tenant("t0", 2)
    eng.register_tenant("t1", 2)
    rng = np.random.default_rng(0)
    for t in ("t0", "t1"):
        eng.submit(t, rng.integers(0, cfg.vocab, 8).astype(np.int32))
    outs = eng.run(max_new_tokens=3)
    return outs, eng.manager.telemetry


def test_serve_metrics_bit_identical_jit_vs_eager():
    """Two runs of the compiled trusted steps, on fresh engines, agree on
    every logical metric (wall-clock histograms excluded) AND on the
    tokens — telemetry observes the schedule, not the implementation —
    and count each tenant's request once."""
    outs_a, tel_a = _serve_run()
    outs_b, tel_b = _serve_run()
    assert outs_a == outs_b
    snap_a = tel_a.registry.snapshot(include_timing=False)
    snap_b = tel_b.registry.snapshot(include_timing=False)
    assert snap_a == snap_b
    assert tel_a.registry.counter("requests", tenant="t0") == 1
    assert tel_a.registry.counter("requests", tenant="t1") == 1


def test_shared_manager_refuses_per_engine_telemetry_override():
    from repro.configs import get_config
    from repro.launch.serve import ServeEngine, make_shared_manager

    cfg = get_config("stablelm-3b").reduced()
    mgr = make_shared_manager(2, max_batch=2)
    with pytest.raises(ValueError, match="telemetry"):
        ServeEngine(cfg, max_batch=2, manager=mgr, telemetry=False)


# ---------------------------------------------------------------------------
# Dashboard rendering
# ---------------------------------------------------------------------------


def test_sparkline():
    assert sparkline([]) == ""
    assert sparkline([0, 0, 0]) == "▁▁▁"
    s = sparkline([0, 1, 2, 4])
    assert len(s) == 4 and s[-1] == "█" and s[0] == "▁"
    assert sparkline([5]) == "█"


def test_format_report_renders_live_manager():
    mgr, clients, ptrs = make_mgr(2)
    drive(mgr, clients, ptrs, rounds=2)
    text = format_report(mgr.metrics_report(),
                         registry=mgr.telemetry.registry)
    assert "guardian flight recorder" in text
    for section in ("tenants", "scheduler", "drain cycles", "jit cache",
                    "elastic", "memory", "launch path", "slo ledger",
                    "trace"):
        assert section in text
    assert "t0" in text and "t1" in text
    assert "▁" in text or "█" in text        # bucket sparklines present


def test_format_report_tolerates_empty_report():
    text = format_report({})
    assert "guardian flight recorder" in text
    assert "0 tenant(s)" in text
