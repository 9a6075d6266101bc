"""Batched multi-tenant launch scheduler (Guardian §4.2.3–§4.2.4 at scale):
cross-tenant isolation of fused batches, coalescing fairness/ordering,
standalone fast path, equivalence with the per-launch drain, cross-cycle
lookahead under a latency budget, weighted fairness, and the LRU-bounded
jit caches."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import HAVE_HYPOTHESIS, given, settings, st
from repro.core import (
    FencePolicy,
    GuardianManager,
    GuardianViolation,
    LaunchRequest,
    LRUCache,
    SharingMode,
)


def bump(arena, ptr, n):
    idx = ptr + jnp.arange(n, dtype=jnp.int32)
    vals = jnp.take(arena, idx, axis=0)
    return arena.at[idx].set(vals + 1.0), None


def evil_write(arena, target, n):
    idx = target + jnp.arange(n, dtype=jnp.int32)
    return arena.at[idx].set(999.0), None


def make_manager(n=4, slots=512, **kw):
    mgr = GuardianManager(total_slots=slots, **kw)
    clients = [mgr.register_tenant(f"t{i}", slots // (2 * n))
               for i in range(n)]
    return mgr, clients


# ---------------------------------------------------------------------------
# Fusion mechanics + equivalence
# ---------------------------------------------------------------------------


def test_compatible_launches_fuse_into_one_step():
    mgr, clients = make_manager(4)
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(8)
        c.memcpy_h2d(p, np.zeros(8, np.float32))
        ptrs.append(p)
    for _ in range(3):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(8,))
    mgr.synchronize()
    st = mgr.scheduler.stats
    assert st.batched_launches == 12
    assert st.fused_steps == 3
    assert list(st.batch_widths) == [4, 4, 4]
    assert st.mean_batch_width == 4.0 and st.max_batch_width == 4
    for c, p in zip(clients, ptrs):
        np.testing.assert_array_equal(c.memcpy_d2h(p, 8),
                                      np.full(8, 3.0, np.float32))


def test_fused_matches_per_launch_drain():
    """The fused path is bit-identical to batch_launches=False round-robin."""
    arenas = []
    for batched in (True, False):
        mgr, clients = make_manager(4, batch_launches=batched)
        for i, c in enumerate(clients):
            c.module_load("bump", bump)
            p = c.malloc(16)
            c.memcpy_h2d(p, np.arange(16, dtype=np.float32) * (i + 1))
            for _ in range(i + 1):           # unequal load per tenant
                c.launch_kernel("bump", ptrs=[p], args=(16,))
        mgr.synchronize()
        if batched:
            assert mgr.scheduler.stats.fused_steps > 0
        else:
            assert mgr.scheduler.stats.fused_steps == 0
        arenas.append(np.asarray(mgr.arena.buf))
    np.testing.assert_array_equal(arenas[0], arenas[1])


# ---------------------------------------------------------------------------
# Cross-tenant isolation of every fused batch
# ---------------------------------------------------------------------------


def test_fused_batch_cross_tenant_isolation():
    """Every row of a fused batch is fenced with its own (base, mask): four
    tenants each aim a forged slot id straight at their neighbour, all four
    launches fuse into ONE device step, and every write wraps back into the
    attacker's own partition."""
    mgr, clients = make_manager(4, policy=FencePolicy.BITWISE)
    parts = [mgr.bounds.lookup(f"t{i}") for i in range(4)]
    # pre-fill every partition with a sentinel via validated transfers
    ptrs = []
    for i, c in enumerate(clients):
        c.module_load("evil", evil_write)
        p = c.malloc(16)
        c.memcpy_h2d(p, np.full(16, float(i + 1), np.float32))
        ptrs.append(p)
    mgr.synchronize()
    before = np.asarray(mgr.arena.buf).copy()

    # tenant i attacks tenant (i+1) % 4
    for i, c in enumerate(clients):
        victim = ptrs[(i + 1) % 4]
        c.launch_kernel("evil", args=(jnp.int32(victim.addr), 16))
    mgr.synchronize()
    assert list(mgr.scheduler.stats.batch_widths) == [4]   # one fused step

    after = np.asarray(mgr.arena.buf)
    for i, part in enumerate(parts):
        own = after[part.base:part.base + part.size]
        # the attacker's damage landed inside its OWN partition...
        assert (own == 999.0).any(), f"t{i}: wrap-around missing"
        # ...and its malloc'd sentinel region was never hit by a neighbour:
        # only values 999 (own wrapped write) or the original sentinel occur
        changed = own != before[part.base:part.base + part.size]
        assert (own[changed] == 999.0).all(), f"t{i}: foreign write leaked"


def test_fused_batch_isolation_matches_sequential_wraparound():
    """The wrap-around targets of a fused step equal the per-launch path's
    (same fence, same rows) — fusion changes scheduling, not semantics."""
    results = []
    for batched in (True, False):
        mgr, clients = make_manager(2, batch_launches=batched)
        for c in clients:
            c.module_load("evil", evil_write)
        other = mgr.bounds.lookup("t1")
        clients[0].launch_kernel(
            "evil", args=(jnp.int32(other.base + 3), 8))
        t0 = mgr.bounds.lookup("t0")
        clients[1].launch_kernel(
            "evil", args=(jnp.int32(t0.base + 5), 8))
        mgr.synchronize()
        results.append(np.asarray(mgr.arena.buf))
    np.testing.assert_array_equal(results[0], results[1])


# ---------------------------------------------------------------------------
# Fairness + ordering of the coalescing drain
# ---------------------------------------------------------------------------


def test_coalescing_preserves_round_robin_fairness():
    """Unequal queue depths: each drain cycle takes at most one launch per
    tenant (round-robin selection), so light tenants finish early and the
    heavy tenant never monopolizes a batch."""
    mgr, clients = make_manager(3)
    for c in clients:
        c.module_load("bump", bump)
    ptrs = [c.malloc(4) for c in clients]
    for c, p in zip(clients, ptrs):
        c.memcpy_h2d(p, np.zeros(4, np.float32))
    mgr.synchronize()
    mgr.scheduler.dispatch_log.clear()
    loads = {0: 4, 1: 2, 2: 1}
    for i, c in enumerate(clients):
        for _ in range(loads[i]):
            c.launch_kernel("bump", ptrs=[ptrs[i]], args=(4,))
    mgr.synchronize()
    log = list(mgr.scheduler.dispatch_log)
    # cycle batches: (t0,t1,t2), (t0,t1), (t0,), (t0,)
    assert log == [("t0", "t1", "t2"), ("t0", "t1"), ("t0",), ("t0",)]
    for batch in log:
        assert len(set(batch)) == len(batch)   # ≤ 1 launch/tenant/batch


def test_head_of_line_blocking_preserves_tenant_order():
    """_take_batch never lets a tenant's later op jump its earlier one:
    once an op of tenant A is deferred, subsequent A-ops are blocked from
    the open batch even if compatible."""
    mgr, _ = make_manager(2)

    def req(tenant, name, static=7):
        return LaunchRequest(tenant_id=tenant, name=name,
                             policy=FencePolicy.BITWISE, entry=None,
                             part=None, call_args=(static,))

    sched = mgr.scheduler
    pending = [req("b", "k1"), req("a", "k2"), req("a", "k1")]
    batch, rest = sched._take_batch(pending)
    # a.k1 is compatible with the open k1 batch, but a.k2 was deferred
    # first — admitting a.k1 would reorder tenant a's program.
    assert [(r.tenant_id, r.name) for r in batch] == [("b", "k1")]
    assert [(r.tenant_id, r.name) for r in rest] == [("a", "k2"),
                                                     ("a", "k1")]


def test_incompatible_signatures_do_not_fuse():
    """Different kernels or different static launch dims -> separate
    device steps."""
    mgr, clients = make_manager(2)
    for c in clients:
        c.module_load("bump", bump)
    p0, p1 = clients[0].malloc(8), clients[1].malloc(8)
    clients[0].memcpy_h2d(p0, np.zeros(8, np.float32))
    clients[1].memcpy_h2d(p1, np.zeros(8, np.float32))
    mgr.synchronize()
    # same kernel, different static n -> incompatible
    clients[0].launch_kernel("bump", ptrs=[p0], args=(8,))
    clients[1].launch_kernel("bump", ptrs=[p1], args=(4,))
    mgr.synchronize()
    st = mgr.scheduler.stats
    assert st.fused_steps == 0 and st.single_steps == 2


def test_max_fuse_caps_batch_width():
    mgr = GuardianManager(total_slots=1024, max_fuse=2)
    clients = [mgr.register_tenant(f"t{i}", 64) for i in range(5)]
    for c in clients:
        c.module_load("bump", bump)
    ptrs = []
    for c in clients:
        p = c.malloc(4)
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        ptrs.append(p)
    mgr.synchronize()
    for c, p in zip(clients, ptrs):
        c.launch_kernel("bump", ptrs=[p], args=(4,))
    mgr.synchronize()
    assert max(mgr.scheduler.stats.batch_widths) <= 2
    assert mgr.scheduler.stats.batched_launches + \
        mgr.scheduler.stats.single_steps == 5


# ---------------------------------------------------------------------------
# Standalone fast path + policy degradation
# ---------------------------------------------------------------------------


def test_standalone_single_tenant_native_fast_path():
    """Paper §4.2.3: a standalone tenant gets the NATIVE kernel — the
    scheduler never builds a fused step for it."""
    mgr = GuardianManager(total_slots=256)
    c = mgr.register_tenant("solo", 64)
    c.module_load("bump", bump)
    p = c.malloc(8)
    c.memcpy_h2d(p, np.zeros(8, np.float32))
    for _ in range(4):
        c.launch_kernel("bump", ptrs=[p], args=(8,))
    mgr.synchronize()
    st = mgr.scheduler.stats
    assert st.fused_steps == 0
    assert st.single_steps == 4
    # the enqueued requests carried the NONE (native) policy
    assert all(len(b) == 1 for b in mgr.scheduler.dispatch_log)
    np.testing.assert_array_equal(c.memcpy_d2h(p, 8),
                                  np.full(8, 4.0, np.float32))


def test_stale_standalone_policy_reresolved_at_drain():
    """A launch enqueued while standalone (NONE/native) must NOT execute
    unfenced after a second tenant registers: the policy is re-resolved
    when the op is selected, so the deferred flush runs the fenced twin
    and the attack wraps into the attacker's own partition."""
    mgr = GuardianManager(total_slots=256)
    a = mgr.register_tenant("a", 64)
    a.module_load("evil", evil_write)
    # enqueued while standalone -> snapshotted as NONE (native)
    a.launch_kernel("evil", args=(jnp.int32(64 + 3), 8))
    # second tenant registers and uploads a secret BEFORE the drain
    b = mgr.register_tenant("b", 64)
    pb = b.malloc(16)
    b.memcpy_h2d(pb, np.full(16, 7.0, np.float32))
    mgr.synchronize()
    part_b = mgr.bounds.lookup("b")
    sl = np.asarray(mgr.arena.unsafe_read_range(part_b.base, part_b.size))
    assert not (sl == 999.0).any(), "stale native launch hit tenant b"
    part_a = mgr.bounds.lookup("a")
    own = np.asarray(mgr.arena.unsafe_read_range(part_a.base, part_a.size))
    assert (own == 999.0).any()       # fenced wrap into a's own partition


def test_manager_fence_table_tracks_repartition():
    """Remove + re-register under the same tenant name must rebuild the
    manager's all-tenant FenceTable (the partition bounds can move), magic
    rows included — the serving plane reads its per-row guard from here."""
    mgr = GuardianManager(total_slots=256)
    mgr.register_tenant("a", 16)
    t1, row1 = mgr.fence_table()
    old_row = np.asarray(t1.rows)[row1["a"]].copy()
    mgr.register_tenant("b", 32)      # occupies slots next to a
    mgr.remove_tenant("a")
    mgr.register_tenant("a", 64)      # buddy allocator must move a
    t2, row2 = mgr.fence_table()
    new_part = mgr.bounds.lookup("a")
    np.testing.assert_array_equal(
        np.asarray(t2.rows)[row2["a"]],
        [new_part.base, new_part.mask])
    assert not np.array_equal(old_row, np.asarray(t2.rows)[row2["a"]])
    # the magic table tracks the same rebuild: (base, size, m, s) with the
    # reciprocal constants of the NEW size (m is a uint32 bit pattern)
    from repro.core.fence import magic_row
    m, s = magic_row(new_part.size)
    np.testing.assert_array_equal(
        np.asarray(t2.magic).view(np.uint32)[row2["a"]],
        np.array([new_part.base, new_part.size, m, s], np.uint32))


# ---------------------------------------------------------------------------
# MODULO fusion via the magic row table
# ---------------------------------------------------------------------------


def test_modulo_launches_fuse_into_one_step():
    """MODULO is no longer the odd one out: compatible MODULO launches
    from different tenants coalesce into one fused device step driven by
    the (T, 4) magic row table."""
    mgr, clients = make_manager(4, policy=FencePolicy.MODULO)
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(8)
        c.memcpy_h2d(p, np.zeros(8, np.float32))
        ptrs.append(p)
    for _ in range(3):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(8,))
    mgr.synchronize()
    st = mgr.scheduler.stats
    assert st.fused_steps == 3 and st.mean_batch_width == 4.0
    for c, p in zip(clients, ptrs):
        np.testing.assert_array_equal(c.memcpy_d2h(p, 8),
                                      np.full(8, 3.0, np.float32))


def test_modulo_fused_matches_per_launch_drain():
    """Fused MODULO batches are byte-identical to standalone MODULO
    launches (the per-launch path's static per-partition magic constants
    vs the fused path's traced magic rows — same exact division).  Mirrors
    the CHECK selective-commit equality test."""
    arenas = []
    for batched in (True, False):
        mgr, clients = make_manager(4, policy=FencePolicy.MODULO,
                                    batch_launches=batched)
        for i, c in enumerate(clients):
            c.module_load("bump", bump)
            p = c.malloc(16)
            c.memcpy_h2d(p, np.arange(16, dtype=np.float32) * (i + 1))
            for _ in range(i + 1):           # unequal load per tenant
                c.launch_kernel("bump", ptrs=[p], args=(16,))
        mgr.synchronize()
        if batched:
            assert mgr.scheduler.stats.fused_steps > 0
        else:
            assert mgr.scheduler.stats.fused_steps == 0
        arenas.append(np.asarray(mgr.arena.buf))
    np.testing.assert_array_equal(arenas[0], arenas[1])


def test_modulo_fused_batch_cross_tenant_isolation():
    """Fused MODULO rows wrap a forged pointer into the attacker's own
    partition — same containment as the static per-partition binaries."""
    mgr, clients = make_manager(4, policy=FencePolicy.MODULO)
    parts = [mgr.bounds.lookup(f"t{i}") for i in range(4)]
    ptrs = []
    for i, c in enumerate(clients):
        c.module_load("evil", evil_write)
        p = c.malloc(16)
        c.memcpy_h2d(p, np.full(16, float(i + 1), np.float32))
        ptrs.append(p)
    mgr.synchronize()
    before = np.asarray(mgr.arena.buf).copy()
    for i, c in enumerate(clients):
        victim = ptrs[(i + 1) % 4]
        c.launch_kernel("evil", args=(jnp.int32(victim.addr), 16))
    mgr.synchronize()
    assert list(mgr.scheduler.stats.batch_widths) == [4]
    after = np.asarray(mgr.arena.buf)
    for i, part in enumerate(parts):
        own = after[part.base:part.base + part.size]
        assert (own == 999.0).any(), f"t{i}: wrap-around missing"
        changed = own != before[part.base:part.base + part.size]
        assert (own[changed] == 999.0).all(), f"t{i}: foreign write leaked"


def test_modulo_fused_non_pow2_partition_sizes():
    """The magic row table handles arbitrary partition sizes: a fused
    MODULO step over hand-built non-pow2 bounds produces the same arena
    bytes as standalone static-magic launches over the same bounds (the
    reciprocal constants, not the pow2 mask, do the wrapping)."""
    from repro.core import FenceParams, FenceTable, sandbox

    bounds = [(0, 48), (48, 12), (60, 3)]        # none pow2-aligned
    table = FenceTable.modulo_from_bounds([b for b, _ in bounds],
                                          [s for _, s in bounds])
    assert table.rows is None and table.magic.shape == (3, 4)

    def kern(arena, start, n):
        idx = start + jnp.arange(n, dtype=jnp.int32)
        vals = jnp.take(arena, idx, axis=0)
        return arena.at[idx].set(vals + 100.0), None

    # standalone reference: static magic constants per partition
    ref = np.arange(64, dtype=np.float32)
    arena_ref = jnp.asarray(ref)
    sb = sandbox(kern, arena_argnums=(0,), policy=FencePolicy.MODULO)
    starts = [40, 55, 61]                        # each straddles its end
    for (base, size), start in zip(bounds, starts):
        (arena_ref, _), _ok = sb(FenceParams(base=base, size=size),
                                 arena_ref, jnp.int32(start), 8)

    # fused run: same launches as rows of one compiled step
    from repro.core.manager import GuardianManager
    mgr = GuardianManager(total_slots=64)
    mgr.register_kernel("kern", kern)
    entry = mgr.pointer_to_symbol["kern"]
    fused = mgr.scheduler._build_fused_modulo(
        entry, (("d", (), jnp.int32), ("s", 8)), 3)
    arena = jnp.asarray(ref)
    starts_dev = [jnp.int32(s) for s in starts]
    arena, _outs = fused(arena, table.magic, *starts_dev)
    np.testing.assert_array_equal(np.asarray(arena), np.asarray(arena_ref))


def test_per_tenant_policies_fuse_in_separate_batches():
    """Tenants may override the manager's fence policy; policy groups
    fuse separately (the policy is part of the batch signature) and the
    MODULO group rides the magic row table."""
    mgr = GuardianManager(total_slots=512, policy=FencePolicy.BITWISE)
    clients = [
        mgr.register_tenant("m0", 32, policy=FencePolicy.MODULO),
        mgr.register_tenant("m1", 32, policy=FencePolicy.MODULO),
        mgr.register_tenant("b0", 32),
        mgr.register_tenant("b1", 32),
    ]
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(8)
        c.memcpy_h2d(p, np.zeros(8, np.float32))
        ptrs.append(p)
    mgr.synchronize()
    mgr.scheduler.dispatch_log.clear()
    for c, p in zip(clients, ptrs):
        c.launch_kernel("bump", ptrs=[p], args=(8,))
    mgr.synchronize()
    # one MODULO pair + one BITWISE pair, never mixed
    assert sorted(mgr.scheduler.dispatch_log) == [("b0", "b1"),
                                                  ("m0", "m1")]
    assert mgr.scheduler.stats.fused_steps == 2
    for c, p in zip(clients, ptrs):
        np.testing.assert_array_equal(c.memcpy_d2h(p, 8),
                                      np.full(8, 1.0, np.float32))


# ---------------------------------------------------------------------------
# Stats + shared fairness helper
# ---------------------------------------------------------------------------


def test_scheduler_stats_summary_fresh_is_all_zeros():
    """A fresh scheduler has dispatched nothing: every summary metric is
    0.0 — no division by zero for device_steps == 0 (regression)."""
    from repro.core import SchedulerStats

    st = SchedulerStats()
    summary = st.summary()
    assert summary == {k: 0.0 for k in summary}
    assert st.launches_per_step == 0.0
    assert st.fused_fraction == 0.0
    assert st.mean_batch_width == 0.0


def test_round_robin_interleave_matches_drain_fairness():
    from repro.core import round_robin_interleave

    by_tenant = {"t0": ["a0", "a1", "a2", "a3"], "t1": ["b0", "b1"],
                 "t2": ["c0"]}
    order = round_robin_interleave(by_tenant)
    assert order == ["a0", "b0", "c0", "a1", "b1", "a2", "a3"]
    assert round_robin_interleave(by_tenant, limit=4) == \
        ["a0", "b0", "c0", "a1"]
    assert round_robin_interleave({}) == []
    # inputs are not consumed
    assert by_tenant["t0"] == ["a0", "a1", "a2", "a3"]


def test_launch_result_handle_filled_by_drain():
    """SPATIAL launches return a request handle whose .result is set once
    the scheduler dispatches it — fused, CHECK and single paths alike."""
    for policy in (FencePolicy.BITWISE, FencePolicy.MODULO,
                   FencePolicy.CHECK):
        mgr, clients = make_manager(2, policy=policy)

        def echo(arena, ptr, n):
            idx = ptr + jnp.arange(n, dtype=jnp.int32)
            vals = jnp.take(arena, idx, axis=0)
            return arena.at[idx].set(vals + 1.0), jnp.sum(vals)

        reqs = []
        for i, c in enumerate(clients):
            c.module_load("echo", echo)
            p = c.malloc(4)
            c.memcpy_h2d(p, np.full(4, float(i + 1), np.float32))
            reqs.append(c.launch_kernel("echo", ptrs=[p], args=(4,)))
        mgr.synchronize()
        for i, req in enumerate(reqs):
            assert req.result is not None, policy
            assert float(req.result) == 4.0 * (i + 1), policy


def test_check_policy_contains_and_attributes_on_scheduler_path():
    """CHECK launches ride the scheduler's attributing commit path: the
    offender's writes are rolled back on device and the violation lands in
    its ViolationLog row — no exception interrupts the drain (the
    per-launch paths, TIME_SHARE and batch_launches=False, still raise;
    see test_manager.test_check_policy_detects_oob)."""
    mgr = GuardianManager(total_slots=256, policy=FencePolicy.CHECK)
    a = mgr.register_tenant("a", 64)
    mgr.register_tenant("b", 64)

    def oob(arena, n):
        idx = 200 + jnp.arange(n, dtype=jnp.int32)   # b's partition
        return arena.at[idx].set(1.0), None

    a.module_load("oob", oob)
    a.launch_kernel("oob", args=(4,))
    mgr.synchronize()                     # contains; does not raise
    assert mgr.scheduler.stats.check_steps == 1
    assert not (np.asarray(mgr.arena.buf) == 1.0).any()   # rolled back
    assert mgr.violog.counts("a")["scatter"] == 4
    assert mgr.violog.total("b") == 0


def test_check_policy_unbatched_drain_still_raises():
    """batch_launches=False restores the raising per-launch CHECK path."""
    mgr = GuardianManager(total_slots=256, policy=FencePolicy.CHECK,
                          batch_launches=False)
    a = mgr.register_tenant("a", 64)
    mgr.register_tenant("b", 64)

    def oob(arena, n):
        idx = 9999 + jnp.arange(n, dtype=jnp.int32)
        return arena.at[idx].set(1.0), None

    # verify=False: the constant-OOB scatter would be refuted at trace
    # time otherwise; this test pins the raising runtime CHECK path
    a.module_load("oob", oob, verify=False)
    a.launch_kernel("oob", args=(4,))
    with pytest.raises(GuardianViolation):
        mgr.synchronize()
    assert mgr.violations
    assert mgr.violog.counts("a")["scatter"] == 4   # attributed even so


# ---------------------------------------------------------------------------
# Cross-cycle lookahead + weighted fairness
# ---------------------------------------------------------------------------


def test_lookahead_off_is_the_default_and_changes_nothing():
    """lookahead_cycles=0 (default): every launch dispatches in its
    submission cycle — mean_queue_age and lookahead_fused stay 0."""
    mgr, clients = make_manager(3)
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(4)
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        ptrs.append(p)
    for _ in range(3):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(4,))
    mgr.synchronize()
    st_ = mgr.scheduler.stats
    assert st_.mean_queue_age == 0.0
    assert st_.lookahead_fused == 0
    assert all(a == 0 for a in st_.queue_ages)


def test_lookahead_fuses_across_cycles_exact_stats():
    """2 tenants x 2 compatible ops, lookahead=1: the first cycle's
    width-2 batch is held, the second cycle's ops join, and ONE width-4
    step dispatches — with exactly the two held launches counted as
    lookahead_fused and mean_queue_age = (1+1+0+0)/4."""
    mgr, clients = make_manager(2, lookahead_cycles=1)
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(4)
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        ptrs.append(p)
    mgr.synchronize()
    for _ in range(2):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(4,))
    mgr.synchronize()
    st_ = mgr.scheduler.stats
    assert st_.fused_steps == 1 and list(st_.batch_widths) == [4]
    assert st_.lookahead_fused == 2
    assert st_.queue_age_sum == 2 and st_.age_samples == 4
    assert st_.mean_queue_age == 0.5
    assert st_.summary()["lookahead_fused"] == 2.0
    assert st_.summary()["mean_queue_age"] == 0.5
    # every result handle filled (run_queued always fully drains)
    assert mgr.scheduler.pending == 0
    for c, p in zip(clients, ptrs):
        np.testing.assert_array_equal(c.memcpy_d2h(p, 4),
                                      np.full(4, 2.0, np.float32))


def test_lookahead_bit_identical_to_eager_drain():
    """Lookahead changes *when* fused steps dispatch, never what they
    compute: the final arena equals the no-lookahead (and the unbatched)
    drain over the same launches."""
    arenas = []
    for look, batched in ((3, True), (0, True), (0, False)):
        mgr, clients = make_manager(3, lookahead_cycles=look,
                                    batch_launches=batched)
        for i, c in enumerate(clients):
            c.module_load("bump", bump)
            p = c.malloc(8)
            c.memcpy_h2d(p, np.arange(8, dtype=np.float32) * (i + 1))
            for _ in range(i + 2):          # unequal load per tenant
                c.launch_kernel("bump", ptrs=[p], args=(8,))
        mgr.synchronize()
        arenas.append(np.asarray(mgr.arena.buf))
    np.testing.assert_array_equal(arenas[0], arenas[1])
    np.testing.assert_array_equal(arenas[0], arenas[2])


def test_lookahead_latency_budget_bounds_queue_age():
    """Deterministic sweep: whatever the load pattern, no launch waits
    more than lookahead_cycles // weight drain cycles (the hold check
    runs every cycle; the end-of-drain flush executes unconditionally)."""
    for look in (1, 2, 3):
        for depths in ((5, 1, 2), (1, 1, 1), (4, 4, 0), (7, 2, 5)):
            mgr, clients = make_manager(3, lookahead_cycles=look,
                                        max_fuse=4)
            for c in clients:
                c.module_load("bump", bump)
            ptrs = [c.malloc(4) for c in clients]
            for c, p in zip(clients, ptrs):
                c.memcpy_h2d(p, np.zeros(4, np.float32))
            mgr.synchronize()
            mgr.scheduler.stats.queue_ages.clear()
            for c, p, d in zip(clients, ptrs, depths):
                for _ in range(d):
                    c.launch_kernel("bump", ptrs=[p], args=(4,))
            mgr.synchronize()
            ages = list(mgr.scheduler.stats.queue_ages)
            assert len(ages) == sum(depths)
            assert mgr.scheduler.pending == 0
            assert all(a <= look for a in ages), (look, depths, ages)


def test_weighted_priority_tenant_never_held_exact():
    """A weight-4 tenant (weight > lookahead) zeroes the hold budget of
    every batch its ops join: its launches always dispatch in their
    submission cycle while best-effort tenants still fuse via lookahead.
    Exact dispatch trace: [p,p,p,a,b] at age 0, then [a,a,b,b] with the
    held pair at age 1."""
    mgr = GuardianManager(total_slots=512, lookahead_cycles=3)
    prio = mgr.register_tenant("p", 32, weight=4)
    others = [mgr.register_tenant(t, 32) for t in ("a", "b")]
    clients = [prio, *others]
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(4)
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        ptrs.append(p)
    mgr.synchronize()
    mgr.scheduler.dispatch_log.clear()
    mgr.scheduler.stats.queue_ages.clear()
    for c, p in zip(clients, ptrs):
        for _ in range(3):
            c.launch_kernel("bump", ptrs=[p], args=(4,))
    mgr.synchronize()
    log = list(mgr.scheduler.dispatch_log)
    assert log == [("p", "p", "p", "a", "b"), ("a", "b", "a", "b")]
    assert list(mgr.scheduler.stats.queue_ages) == [0, 0, 0, 0, 0,
                                                    1, 1, 0, 0]
    # correctness untouched by priority scheduling
    for c, p in zip(clients, ptrs):
        np.testing.assert_array_equal(c.memcpy_d2h(p, 4),
                                      np.full(4, 3.0, np.float32))


def test_weight_equal_to_lookahead_never_waits():
    """Regression: a priority tenant with weight == lookahead_cycles must
    have hold budget 0 (not lookahead // weight == 1) — the documented
    zero-latency guarantee is weight >= lookahead, not weight >."""
    mgr = GuardianManager(total_slots=512, lookahead_cycles=2)
    prio = mgr.register_tenant("p", 32, weight=2)
    best = mgr.register_tenant("a", 32)
    for c in (prio, best):
        c.module_load("bump", bump)
    pp, pa = prio.malloc(4), best.malloc(4)
    prio.memcpy_h2d(pp, np.zeros(4, np.float32))
    best.memcpy_h2d(pa, np.zeros(4, np.float32))
    mgr.synchronize()
    mgr.scheduler.stats.queue_ages.clear()
    mgr.scheduler.dispatch_log.clear()
    prio.launch_kernel("bump", ptrs=[pp], args=(4,))
    for _ in range(3):
        best.launch_kernel("bump", ptrs=[pa], args=(4,))
    mgr.synchronize()
    log = list(mgr.scheduler.dispatch_log)
    # p's op dispatches in its submission cycle (with a's first op)
    assert log[0][:2] == ("p", "a")
    ages = list(mgr.scheduler.stats.queue_ages)
    assert ages[0] == 0        # the priority op never waited


def _run_lookahead_case(depths, weights, look):
    """Shared body for the deterministic sweep + hypothesis mirror:
    returns (scheduler, per-request (tenant, age) pairs)."""
    mgr = GuardianManager(total_slots=1024, lookahead_cycles=look)
    clients = []
    for i, w in enumerate(weights):
        clients.append(mgr.register_tenant(f"t{i}", 32, weight=w))
    ptrs = []
    for c in clients:
        c.module_load("bump", bump)
        p = c.malloc(4)
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        ptrs.append(p)
    mgr.synchronize()
    mgr.scheduler.dispatch_log.clear()
    mgr.scheduler.stats.queue_ages.clear()
    reqs = []
    for c, p, d in zip(clients, ptrs, depths):
        for _ in range(d):
            reqs.append(c.launch_kernel("bump", ptrs=[p], args=(4,)))
    mgr.run_queued()
    sched = mgr.scheduler
    dispatched = [t for batch in sched.dispatch_log for t in batch]
    ages = list(sched.stats.queue_ages)
    # every submitted launch dispatched exactly once (no starvation, no
    # leftovers) and the age log aligns with the dispatch log
    assert len(dispatched) == len(ages) == len(reqs) == sum(depths)
    assert sched.pending == 0
    return sched, list(zip(dispatched, ages))


def _hold_bound(look, w):
    """Mirror of BatchedLaunchScheduler._hold_budget."""
    if w <= 1:
        return look
    return 0 if w >= look else look // w


def _check_lookahead_invariants(depths, weights, look):
    sched, pairs = _run_lookahead_case(depths, weights, look)
    for tenant, age in pairs:
        w = weights[int(tenant[1:])]
        assert age <= _hold_bound(look, w), (depths, weights, look, pairs)


def test_lookahead_weighted_fairness_sweep():
    """Deterministic mirror of the hypothesis property: every dispatched
    launch waited at most lookahead // weight cycles — weighted fairness
    that lookahead can never starve."""
    cases = [
        ((3, 3, 3), (4, 1, 1), 3),
        ((5, 1, 0), (1, 2, 1), 2),
        ((2, 2, 2), (1, 1, 1), 1),
        ((4, 0, 4), (3, 1, 3), 3),
        ((1, 6, 2), (2, 1, 4), 4),
    ]
    for depths, weights, look in cases:
        _check_lookahead_invariants(depths, weights, look)


@settings(max_examples=25, deadline=None)
@given(
    depths=st.tuples(*[st.integers(min_value=0, max_value=5)] * 3),
    weights=st.tuples(*[st.integers(min_value=1, max_value=4)] * 3),
    look=st.integers(min_value=0, max_value=4),
)
def test_lookahead_weighted_fairness_property(depths, weights, look):
    if sum(depths) == 0:
        return
    _check_lookahead_invariants(depths, weights, look)


def test_adaptive_lookahead_off_by_default_and_static_knob_wins():
    mgr, _ = make_manager(2)
    assert mgr.scheduler.current_lookahead == 0
    mgr2, _ = make_manager(2, lookahead_cycles=3, adaptive_lookahead=True)
    # the static knob overrides adaptation entirely
    assert mgr2.scheduler.current_lookahead == 3
    mgr2.scheduler._adaptive_budget = 7
    assert mgr2.scheduler.current_lookahead == 3


def _ewma_mirror(series, alpha=0.5):
    """Host mirror of pressure.Ewma (seeded first sample)."""
    v = None
    for x in series:
        v = float(x) if v is None else alpha * x + (1 - alpha) * v
    return v or 0.0


def _derived_mirror(total_rate, max_fuse, cap):
    """Host mirror of pressure.derive_lookahead."""
    import math
    if total_rate <= 0 or max_fuse <= 1:
        return 0
    return max(0, min(math.ceil((max_fuse - 1) / total_rate), cap))


def test_adaptive_lookahead_budget_tracks_arrival_rates_exact():
    """Deterministic sweep: after each drain the scheduler's budget
    equals ceil((max_fuse-1)/sum(EWMA rates)) clamped to the cap — the
    documented derivation, mirrored in plain arithmetic."""
    from repro.core import derive_lookahead

    for pattern in ([(2, 2, 2)], [(1, 0, 0), (1, 0, 0)],
                    [(3, 1, 0), (0, 0, 0), (2, 2, 2)]):
        mgr, clients = make_manager(3, adaptive_lookahead=True,
                                    adaptive_lookahead_cap=4, max_fuse=8)
        for c in clients:
            c.module_load("bump", bump)
        ptrs = [c.malloc(4) for c in clients]
        for c, p in zip(clients, ptrs):
            c.memcpy_h2d(p, np.zeros(4, np.float32))
        mgr.synchronize()
        per_tenant = {c.tenant_id: [] for c in clients}
        for depths in pattern:
            for c, p, d in zip(clients, ptrs, depths):
                for _ in range(d):
                    c.launch_kernel("bump", ptrs=[p], args=(4,))
            mgr.run_queued()
            # mirror: every drain cycle in run_queued updates the EWMA;
            # the final budget reflects the last cycle's rates
        sched = mgr.scheduler
        total = sum(ew.value for ew in sched._arrival_ewma.values())
        expect = _derived_mirror(total, sched.max_fuse,
                                 sched.adaptive_lookahead_cap)
        assert sched.current_lookahead == expect
        assert sched.current_lookahead == derive_lookahead(
            (ew.value for ew in sched._arrival_ewma.values()),
            sched.max_fuse, sched.adaptive_lookahead_cap)
        assert sched.stats.summary()["lookahead_budget"] == float(expect)


def test_adaptive_lookahead_dense_traffic_keeps_budget_small():
    """Dense arrivals (every tenant submitting each cycle) fill batches
    within a cycle: the derived budget collapses to 1 — adaptation never
    inflates latency where the static tuning would be 0-1."""
    mgr, clients = make_manager(4, adaptive_lookahead=True,
                                adaptive_lookahead_cap=8, max_fuse=4)
    for c in clients:
        c.module_load("bump", bump)
    ptrs = [c.malloc(4) for c in clients]
    for c, p in zip(clients, ptrs):
        c.memcpy_h2d(p, np.zeros(4, np.float32))
    mgr.synchronize()
    for _ in range(4):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(4,))
        mgr.run_queued()
    assert mgr.scheduler.current_lookahead == 1
    # and every launch still dispatched within the budget
    assert all(a <= 1 for a in mgr.scheduler.stats.queue_ages)


def test_adaptive_lookahead_sparse_traffic_holds_for_fusion():
    """Sparse single-tenant-per-cycle traffic: the derived budget grows
    (capped), and under-filled batches hold across cycles — lookahead
    fusion happens with no static knob at all."""
    mgr, clients = make_manager(2, adaptive_lookahead=True,
                                adaptive_lookahead_cap=4, max_fuse=4)
    for c in clients:
        c.module_load("bump", bump)
    ptrs = [c.malloc(4) for c in clients]
    for c, p in zip(clients, ptrs):
        c.memcpy_h2d(p, np.zeros(4, np.float32))
    mgr.synchronize()
    # warm the EWMA: both tenants trickle one op per drain
    for _ in range(3):
        for c, p in zip(clients, ptrs):
            c.launch_kernel("bump", ptrs=[p], args=(4,))
        mgr.run_queued()
    assert mgr.scheduler.current_lookahead >= 1
    base_fused = mgr.scheduler.stats.lookahead_fused
    # uneven depths in ONE drain: cycle 1's width-2 batch is held under
    # the derived budget and cycle 2's op joins it — lookahead fusion
    # with no static knob at all
    clients[0].launch_kernel("bump", ptrs=[ptrs[0]], args=(4,))
    clients[0].launch_kernel("bump", ptrs=[ptrs[0]], args=(4,))
    clients[1].launch_kernel("bump", ptrs=[ptrs[1]], args=(4,))
    mgr.run_queued()
    assert mgr.scheduler.stats.lookahead_fused > base_fused
    assert mgr.scheduler.pending == 0


def test_adaptive_lookahead_bit_identical_results():
    """Adaptation changes dispatch timing, never results: final arena
    equals the static-knob and no-lookahead drains."""
    arenas = []
    for kw in ({"adaptive_lookahead": True, "adaptive_lookahead_cap": 3},
               {"lookahead_cycles": 3}, {}):
        mgr, clients = make_manager(3, **kw)
        for i, c in enumerate(clients):
            c.module_load("bump", bump)
            p = c.malloc(8)
            c.memcpy_h2d(p, np.arange(8, dtype=np.float32) * (i + 1))
            for _ in range(i + 2):
                c.launch_kernel("bump", ptrs=[p], args=(8,))
        mgr.synchronize()
        arenas.append(np.asarray(mgr.arena.buf))
    np.testing.assert_array_equal(arenas[0], arenas[1])
    np.testing.assert_array_equal(arenas[0], arenas[2])


def test_adaptive_lookahead_forgets_departed_tenants():
    mgr, clients = make_manager(3, adaptive_lookahead=True)
    for c in clients:
        c.module_load("bump", bump)
    ptrs = [c.malloc(4) for c in clients]
    for c, p in zip(clients, ptrs):
        c.memcpy_h2d(p, np.zeros(4, np.float32))
        c.launch_kernel("bump", ptrs=[p], args=(4,))
    mgr.synchronize()
    assert "t0" in mgr.scheduler._arrival_ewma
    mgr.remove_tenant("t0")
    assert "t0" not in mgr.scheduler._arrival_ewma


def _run_adaptive_case(depth_rounds, cap):
    mgr, clients = make_manager(3, adaptive_lookahead=True,
                                adaptive_lookahead_cap=cap, max_fuse=4)
    for c in clients:
        c.module_load("bump", bump)
    ptrs = [c.malloc(4) for c in clients]
    for c, p in zip(clients, ptrs):
        c.memcpy_h2d(p, np.zeros(4, np.float32))
    mgr.synchronize()
    mgr.scheduler.stats.queue_ages.clear()
    n = 0
    for depths in depth_rounds:
        for c, p, d in zip(clients, ptrs, depths):
            for _ in range(d):
                c.launch_kernel("bump", ptrs=[p], args=(4,))
                n += 1
        mgr.run_queued()
    sched = mgr.scheduler
    assert sched.pending == 0
    ages = list(sched.stats.queue_ages)
    assert len(ages) == n
    # the latency invariant: no launch ever waits past the cap
    assert all(a <= cap for a in ages), (depth_rounds, cap, ages)


def test_adaptive_lookahead_latency_bounded_by_cap_sweep():
    cases = [
        ([(2, 0, 0), (0, 2, 0), (0, 0, 2)], 2),
        ([(1, 1, 1)] * 3, 1),
        ([(3, 0, 1), (0, 0, 0), (1, 2, 0)], 4),
        ([(1, 0, 0)] * 5, 3),
    ]
    for depth_rounds, cap in cases:
        _run_adaptive_case(depth_rounds, cap)


@settings(max_examples=15, deadline=None)
@given(
    rounds=st.lists(
        st.tuples(*[st.integers(min_value=0, max_value=3)] * 3),
        min_size=1, max_size=4),
    cap=st.integers(min_value=0, max_value=4),
)
def test_adaptive_lookahead_latency_property(rounds, cap):
    if sum(sum(r) for r in rounds) == 0:
        return
    _run_adaptive_case(rounds, cap)


def test_round_robin_interleave_weighted():
    from repro.core import round_robin_interleave

    by_tenant = {"t0": ["a0", "a1", "a2"], "t1": ["b0", "b1"],
                 "t2": ["c0"]}
    order = round_robin_interleave(by_tenant, weights={"t0": 2})
    assert order == ["a0", "a1", "b0", "c0", "a2", "b1"]
    assert round_robin_interleave(by_tenant, limit=3,
                                  weights={"t0": 2}) == ["a0", "a1", "b0"]
    # weights below 1 degrade to strict round-robin, inputs not consumed
    assert round_robin_interleave(by_tenant, weights={"t1": 0}) == \
        ["a0", "b0", "c0", "a1", "b1", "a2"]
    assert by_tenant["t0"] == ["a0", "a1", "a2"]


# ---------------------------------------------------------------------------
# Trusted-step jit + multi-engine fusion (scheduler level)
# ---------------------------------------------------------------------------


def test_trusted_requests_fuse_when_jitted():
    """Two tenants' trusted steps with equal signatures coalesce into one
    compiled device step (the multi-engine fused decode, scheduler
    view); results land on each request handle."""
    mgr = GuardianManager(total_slots=64)

    def step(arena, x):
        return arena, x * 2.0

    mgr.register_trusted_kernel("step", step)
    a = mgr.register_tenant("a", 8)
    b = mgr.register_tenant("b", 8)
    ra = a.launch_kernel("step", args=(jnp.ones((4,), jnp.float32),))
    rb = b.launch_kernel("step", args=(jnp.full((4,), 3.0, jnp.float32),))
    mgr.synchronize()
    st_ = mgr.scheduler.stats
    assert st_.fused_steps == 1 and list(st_.batch_widths) == [2]
    np.testing.assert_array_equal(np.asarray(ra.result),
                                  np.full(4, 2.0, np.float32))
    np.testing.assert_array_equal(np.asarray(rb.result),
                                  np.full(4, 6.0, np.float32))
    # the fused binary is cached under the trusted key
    assert any(k[0] == "trusted" for k in mgr.scheduler._fused_cache)


def test_trusted_pytree_operands_fuse_by_structure():
    """Trusted signatures hash pytree operands (params/cache/guard trees)
    by treedef + leaf structure: equal-structure steps fuse, different
    shapes stay apart."""
    mgr = GuardianManager(total_slots=64)

    def step(arena, tree):
        return arena, tree["x"] + tree["y"]

    mgr.register_trusted_kernel("step", step)
    a = mgr.register_tenant("a", 8)
    b = mgr.register_tenant("b", 8)
    t1 = {"x": jnp.ones((4,)), "y": jnp.zeros((4,))}
    t2 = {"x": jnp.full((4,), 2.0), "y": jnp.ones((4,))}
    t3 = {"x": jnp.ones((8,)), "y": jnp.zeros((8,))}   # different shape
    ra = a.launch_kernel("step", args=(t1,))
    rb = b.launch_kernel("step", args=(t2,))
    mgr.synchronize()
    assert mgr.scheduler.stats.fused_steps == 1
    np.testing.assert_array_equal(np.asarray(ra.result), np.ones(4))
    np.testing.assert_array_equal(np.asarray(rb.result),
                                  np.full(4, 3.0))
    ra2 = a.launch_kernel("step", args=(t1,))
    rb2 = b.launch_kernel("step", args=(t3,))
    mgr.synchronize()
    assert mgr.scheduler.stats.fused_steps == 1   # no second fused step
    np.testing.assert_array_equal(np.asarray(rb2.result), np.ones(8))
    np.testing.assert_array_equal(np.asarray(ra2.result), np.ones(4))


# ---------------------------------------------------------------------------
# LRU-bounded jit caches
# ---------------------------------------------------------------------------


def test_lru_cache_semantics():
    lru = LRUCache(2)
    lru["a"] = 1
    lru["b"] = 2
    assert lru["a"] == 1            # refreshes recency
    lru["c"] = 3                    # evicts b (coldest)
    assert "b" not in lru and "a" in lru and "c" in lru
    assert lru.evictions == 1
    lru["a"] = 10                   # overwrite refreshes, no eviction
    assert lru["a"] == 10 and lru.evictions == 1
    del lru["c"]                    # purge-path deletion still works
    assert list(lru) == ["a"]
    with pytest.raises(ValueError):
        LRUCache(0)


def test_jit_cache_lru_bound_evicts_and_counts():
    """jit_cache_capacity bounds each kernel entry's compiled-variant
    cache: churning operand signatures evicts the coldest binaries (a
    recompile on reuse, never an error) and the eviction stat reports."""
    mgr = GuardianManager(total_slots=64, jit_cache_capacity=2)

    def step(arena, x):
        return arena, x + 1.0

    mgr.register_trusted_kernel("step", step)
    c = mgr.register_tenant("svc", 16)
    for n in (2, 4, 8, 16):         # 4 distinct signatures, capacity 2
        req = c.launch_kernel("step", args=(jnp.zeros((n,), jnp.float32),))
        mgr.synchronize()
        np.testing.assert_array_equal(np.asarray(req.result),
                                      np.ones(n, np.float32))
    entry = mgr.pointer_to_symbol["step"]
    assert len(entry.jit_cache) == 2
    assert entry.jit_cache.evictions == 2
    stats = mgr.jit_cache_stats()
    assert stats["capacity"] == 2 and stats["evictions"] == 2
    assert stats["per_kernel"]["step"] == 2
    # an evicted signature recompiles transparently
    req = c.launch_kernel("step", args=(jnp.zeros((2,), jnp.float32),))
    mgr.synchronize()
    np.testing.assert_array_equal(np.asarray(req.result),
                                  np.ones(2, np.float32))
    assert mgr.jit_cache_stats()["evictions"] == 3
    # the scheduler's fused-step cache is bounded the same way
    assert isinstance(mgr.scheduler._fused_cache, LRUCache)
    assert stats["fused_capacity"] == mgr.scheduler._fused_cache.capacity


def test_signature_distinguishes_policies():
    """Policies never mix within a batch (the policy is part of the batch
    signature) — but every fencing policy is fusable now, MODULO included
    (via the magic row table); only NONE degrades to the native path."""
    r1 = LaunchRequest(tenant_id="a", name="k", policy=FencePolicy.BITWISE,
                       entry=None, part=None, call_args=(jnp.int32(1), 4))
    r2 = LaunchRequest(tenant_id="b", name="k", policy=FencePolicy.MODULO,
                       entry=None, part=None, call_args=(jnp.int32(2), 4))
    r3 = LaunchRequest(tenant_id="b", name="k", policy=FencePolicy.BITWISE,
                       entry=None, part=None, call_args=(jnp.int32(3), 4))
    r4 = LaunchRequest(tenant_id="b", name="k", policy=FencePolicy.NONE,
                       entry=None, part=None, call_args=(jnp.int32(4), 4))
    assert r1.signature != r2.signature
    assert r1.signature == r3.signature
    assert r1.fusable and r3.fusable and r2.fusable
    assert not r4.fusable
