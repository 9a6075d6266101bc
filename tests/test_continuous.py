"""Continuous batching over the fenced paged KV pool (serve path).

Proof obligations for the per-request driver:

* **bit-identity** — continuous generations equal lockstep / solo-run
  generations token for token (same-arrival and churny arrival traces);
* **containment** — forged virtual page tables wrap into the forger's
  own extent; join/leave churn never aliases a live page;
* **zero-copy elasticity** — grows, rebases and background compaction
  never dispatch a data-moving relocation step in paged mode;
* **sampling** — temperature/top-k decode is deterministic per PRNG key
  and the greedy default compiles the unchanged argmax program.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.elastic import ElasticPolicy
from repro.core.fence import FenceParams, FencePolicy
from repro.launch.serve import (
    ServeEngine,
    make_shared_manager,
    serve_continuous,
    serve_engines,
)
from repro.models import kvcache as KV
from repro.models.guard import GuardSpec


CFG = get_config("stablelm-3b").reduced()


def _prompts(n, plen=6, salt=0):
    return [[(7 * i + 3 * j + salt) % 211 + 1 for j in range(plen)]
            for i in range(n)]


def _solo_refs(prompts, budgets, max_len=64):
    refs = []
    for p, b in zip(prompts, budgets):
        eng = ServeEngine(CFG, max_batch=2, max_len=max_len, seed=0)
        eng.register_tenant("solo", 2)
        rid = eng.submit("solo", p)
        refs.append(eng.run(max_new_tokens=b)[rid])
    return refs


# --------------------------------------------------------------------- #
# Bit-identity                                                          #
# --------------------------------------------------------------------- #
def test_continuous_matches_lockstep_same_arrival():
    """Same-arrival uniform workload: the continuous driver and the
    lockstep slab driver emit identical tokens per request."""
    prompts = _prompts(4)
    budget = 5

    lock = ServeEngine(CFG, max_batch=4, max_len=64, seed=0)
    lock.register_tenant("t", 4)
    lock_rids = [lock.submit("t", p) for p in prompts]
    lock_out = serve_engines([lock], max_new_tokens=budget)[0]

    mgr = make_shared_manager(1, max_batch=4, paged=True, max_len=64)
    cont = ServeEngine(CFG, max_batch=4, max_len=64, seed=0,
                       manager=mgr, paged=True)
    cont.register_tenant("t", 4)
    cont_rids = [cont.submit("t", p, max_new=budget) for p in prompts]
    cont_out = serve_continuous([cont], max_new_tokens=budget)[0]

    for lr, cr in zip(lock_rids, cont_rids):
        assert cont_out[cr] == lock_out[lr]
    assert cont.manager.elastic.stats["reloc_steps"] == 0


def test_continuous_churn_solo_identity():
    """Staggered arrivals, mixed budgets, two tenants (one sized to
    force an elastic grow): every request's generation equals its solo
    run — rows joining/leaving mid-flight never perturb neighbours."""
    n = 8
    prompts = _prompts(n)
    budgets = [3 if i % 2 else 6 for i in range(n)]
    refs = _solo_refs(prompts, budgets)

    mgr = make_shared_manager(1, max_batch=4, paged=True, max_len=64)
    eng = ServeEngine(CFG, max_batch=4, max_len=64, seed=0,
                      manager=mgr, paged=True, max_inflight=4)
    eng.register_tenant("a", 4)
    eng.register_tenant("b", 1)      # 1-page extent: churn forces reuse
    rids = [eng.submit("b" if i % 3 == 0 else "a", prompts[i],
                       max_new=budgets[i], arrive=i // 2)
            for i in range(n)]
    out = serve_continuous([eng], max_new_tokens=16)[0]

    for i, rid in enumerate(rids):
        assert out[rid] == refs[i], f"request {i} diverged"
    assert eng.manager.elastic.stats["reloc_steps"] == 0


def test_short_request_row_refills_immediately():
    """A finished short request's row refills from the admission queue at
    the next cycle boundary: total cycles stay well under the sum of
    sequential waves."""
    prompts = _prompts(6)
    mgr = make_shared_manager(1, max_batch=2, paged=True, max_len=64)
    eng = ServeEngine(CFG, max_batch=2, max_len=64, seed=0,
                      manager=mgr, paged=True)
    eng.register_tenant("t", 2)
    for i, p in enumerate(prompts):
        eng.submit("t", p, max_new=2 if i else 8)
    st = eng._cont_begin(16)
    # drive manually to observe the state
    while True:
        eng._cont_leave(st)
        joiners = eng._cont_join(st)
        h = eng._cont_dispatch(st, joiners)
        if h[0] is None and h[1] is None and not eng._cont_waiting(st):
            break
        eng.manager.run_queued()
        eng._cont_finish(st, *h)
    out = eng._cont_finalize(st)
    assert len(out) == 6
    # 1 long (8 cycles incl. prefill) + 5 shorts (2 cycles each) on 2
    # rows: continuous packs the shorts into the long request's shadow;
    # lockstep waves would cost ~3 waves x wave-max cycles
    assert st.cycles <= 13


# --------------------------------------------------------------------- #
# Containment                                                           #
# --------------------------------------------------------------------- #
def test_forged_virtual_page_table_stays_fenced():
    """Serve-path containment: a page table forged with another tenant's
    virtual ids wraps into the forger's own extent before the page_map
    translation — the victim's physical pages are never read."""
    cache = KV.init_global_kv_cache(CFG, 2, 128, 16)
    pages_per_req = cache.page_table.shape[1]
    assert pages_per_req == 2

    # virtual space: attacker owns [0, 2) -> phys [1, 2];
    #                victim   owns [4, 6) -> phys [3, 4]
    page_map = np.zeros((8,), np.int32)
    page_map[0:2] = [1, 2]
    page_map[4:6] = [3, 4]

    def guard_for(tables_rows):
        return GuardSpec(
            policy=FencePolicy.BITWISE,
            kv=FenceParams(base=jnp.asarray([0, 4], jnp.int32),
                           size=jnp.asarray([2, 2], jnp.int32)),
            page=FenceParams(base=0, size=16),
            page_map=jnp.asarray(page_map))

    rng = np.random.default_rng(0)
    k_new = jnp.asarray(rng.normal(size=(2, 1, CFG.n_kv_heads,
                                         CFG.head_dim)), jnp.float32)
    honest = dataclasses.replace(
        cache, page_table=jnp.asarray([[0, 1], [4, 5]], jnp.int32),
        seq_lens=jnp.asarray([3, 3], jnp.int32))
    forged = dataclasses.replace(
        honest, page_table=jnp.asarray([[4, 5], [4, 5]], jnp.int32))

    g = guard_for(None)
    c_h = KV.append_token_kv(honest, 0, k_new, k_new, guard=g)
    c_f = KV.append_token_kv(forged, 0, k_new, k_new, guard=g)
    # row 0's forged victim ids wrap to its own extent: victim phys
    # pages (3, 4) hold identical bytes in both runs (only row 1, their
    # real owner, wrote them)
    np.testing.assert_array_equal(np.asarray(c_h.k[:, 3:5]),
                                  np.asarray(c_f.k[:, 3:5]))
    # and the forged write landed somewhere inside attacker phys [1, 2]
    assert (np.asarray(c_f.k[:, 1:3]) != 0).any()

    # reads: gather with forged tables returns attacker-extent bytes,
    # so zeroing the victim's pages changes nothing for row 0
    reads1 = KV.gather_layer_kv(c_f, 0, guard=g)[0]
    c_z = dataclasses.replace(
        c_f, k=c_f.k.at[:, 3:5].set(0.0), v=c_f.v.at[:, 3:5].set(0.0))
    reads2 = KV.gather_layer_kv(c_z, 0, guard=g)[0]
    np.testing.assert_array_equal(np.asarray(reads1[0]),
                                  np.asarray(reads2[0]))


def test_join_leave_never_aliases_freed_page():
    """The join-time allocator invariant holds across heavy churn: pages
    of concurrently active requests are disjoint and inside their
    owner's extent (the assertions inside _cont_join fire otherwise),
    and every request still completes."""
    n = 10
    prompts = _prompts(n, salt=3)
    mgr = make_shared_manager(1, max_batch=4, paged=True, max_len=64)
    eng = ServeEngine(CFG, max_batch=4, max_len=64, seed=0,
                      manager=mgr, paged=True)
    eng.register_tenant("t", 2)      # 2 pages for 4 rows: constant churn
    rids = [eng.submit("t", p, max_new=1 + i % 3, arrive=i // 3)
            for i, p in enumerate(prompts)]
    out = serve_continuous([eng], max_new_tokens=8)[0]
    assert sorted(out) == sorted(rids)
    for i, rid in enumerate(rids):
        assert len(out[rid]) == 1 + i % 3


def test_step_guard_page_map_is_a_snapshot():
    """A step's page_map operand is fixed when the step is launched.  The
    host rewrites the PagePool map in place at later cycle boundaries
    (join, leave, elastic rebase) while the step may still wait in the
    device queue.  On the CPU, JAX adopts a 64-byte-aligned host array
    without copying it, so the guard must hand over a snapshot: put the
    map at an aligned address, rewrite it after the guard was built, and
    the guard must still hold the map as it was."""
    mgr = make_shared_manager(1, max_batch=2, paged=True, max_len=256)
    eng = ServeEngine(CFG, max_batch=2, max_len=256, seed=0,
                      manager=mgr, paged=True)
    eng.register_tenant("t", 4)
    pages = eng._pool.pages
    n = pages.page_map.size
    raw = np.zeros((n + 16,), np.int32)
    off = (-raw.ctypes.data % 64) // raw.itemsize
    aligned = raw[off:off + n]
    aligned[:] = pages.page_map
    pages.page_map = aligned
    want = aligned.copy()
    assert want.any()
    guard = eng._guard_for_rows([None, None])
    pages.release_extent("t")        # zeroes the tenant's map rows
    np.testing.assert_array_equal(np.asarray(guard.page_map), want)


# --------------------------------------------------------------------- #
# Zero-copy elasticity                                                  #
# --------------------------------------------------------------------- #
def test_background_compaction_is_zero_copy():
    """Evicting a middle tenant fragments the virtual space; idle drain
    cycles trigger the PressureTracker-driven background compaction,
    which rebases extents through the PagePool map — zero relocation
    steps — and post-compaction generations stay bit-identical."""
    prompts = _prompts(2)
    refs = _solo_refs(prompts, [4, 4])

    mgr = make_shared_manager(2, max_batch=4, paged=True, max_len=64,
                              elastic_policy=ElasticPolicy(
                                  background_compact=True,
                                  compact_interval=2))
    eng = ServeEngine(CFG, max_batch=4, max_len=64, seed=0,
                      manager=mgr, paged=True)
    eng.register_tenant("a", 4)
    eng.register_tenant("b", 4)
    eng.register_tenant("c", 4)

    rid0 = eng.submit("c", prompts[0], max_new=4)
    out = serve_continuous([eng], max_new_tokens=4)[0]
    assert out[rid0] == refs[0]

    eng.quarantine_tenant("b")
    eng.evict_tenant("b")            # hole below c
    base_before = mgr.bounds.lookup("c").base
    for _ in range(4):               # idle cycles drive the compactor
        mgr.run_queued()
    assert mgr.elastic.stats["compactions"] >= 1
    assert mgr.bounds.lookup("c").base < base_before
    assert mgr.elastic.stats["reloc_steps"] == 0

    rid1 = eng.submit("c", prompts[1], max_new=4)
    out = serve_continuous([eng], max_new_tokens=4)[0]
    assert out[rid1] == refs[1]
    assert mgr.elastic.stats["reloc_steps"] == 0


# --------------------------------------------------------------------- #
# Sampling                                                              #
# --------------------------------------------------------------------- #
def _sampled_run(seed, temperature=0.7, top_k=4):
    mgr = make_shared_manager(1, max_batch=2, paged=True, max_len=64)
    eng = ServeEngine(CFG, max_batch=2, max_len=64, seed=seed,
                      manager=mgr, paged=True,
                      temperature=temperature, top_k=top_k)
    eng.register_tenant("t", 2)
    rid = eng.submit("t", _prompts(1)[0], max_new=6)
    return eng, eng.run(max_new_tokens=6)[rid]


def test_sampled_decode_deterministic_per_key():
    eng1, toks1 = _sampled_run(0)
    eng2, toks2 = _sampled_run(0)
    assert toks1 == toks2            # same PRNG key -> same stream
    assert "sampled" in eng1._steps.decode_name
    _, toks3 = _sampled_run(1)       # model params differ too, but the
    assert len(toks3) == 6           # run must still complete


def test_greedy_default_pinned():
    """temperature=0 compiles the unchanged argmax decode program under
    the unsuffixed step name — bit-identical to the slab engine's."""
    mgr = make_shared_manager(1, max_batch=2, paged=True, max_len=64)
    eng = ServeEngine(CFG, max_batch=2, max_len=64, seed=0,
                      manager=mgr, paged=True)
    assert "sampled" not in eng._steps.decode_name
    assert eng._sample_key is None
    eng.register_tenant("t", 2)
    rid = eng.submit("t", _prompts(1)[0], max_new=5)
    out = eng.run(max_new_tokens=5)
    assert out[rid] == _solo_refs(_prompts(1), [5])[0]
