"""Pallas kernel sweeps — every kernel vs its pure-jnp oracle, across
shapes and dtypes.  The tests run on the CPU, so every call asks for
interpret mode; tests/test_chip_compile.py compiles the served kernel for
the TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops
from repro.kernels import ref as R


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KH,D,page,P_total,max_pages",
    [
        (2, 4, 4, 16, 8, 32, 4),     # MHA
        (4, 8, 2, 32, 16, 64, 8),    # GQA
        (1, 8, 1, 64, 8, 16, 2),     # MQA
        (3, 6, 2, 16, 4, 32, 16),    # odd batch, many pages
    ])
def test_paged_attention_sweep(dtype, B, H, KH, D, page, P_total,
                               max_pages):
    rng = np.random.default_rng(B * 100 + H)
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(P_total, page, KH, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(P_total, page, KH, D)), dtype)
    half = P_total // 2
    base = jnp.asarray(rng.choice([0, half], size=B), jnp.int32)
    mask = jnp.full((B,), half - 1, jnp.int32)
    pt = jnp.asarray(rng.integers(0, P_total, size=(B, max_pages)),
                     jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_pages * page, size=B),
                       jnp.int32)
    out = ops.paged_attention(q, kp, vp, pt, lens, base, mask,
                              interpret=True)
    ref = R.paged_attention_ref(q, kp, vp, pt, lens, base, mask)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@given(st.integers(min_value=1, max_value=64),
       st.integers(min_value=2, max_value=6))
@settings(max_examples=10, deadline=None)
def test_paged_attention_isolation_property(seed, logsize):
    """Adversarial page tables never read outside the tenant partition:
    outputs must be identical whether the other tenant's pool half is
    zeroed or randomized."""
    rng = np.random.default_rng(seed)
    P_total = 2 ** logsize
    half = P_total // 2
    B, H, KH, D, page, max_pages = 2, 4, 2, 16, 4, 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    vp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    pt = jnp.asarray(rng.integers(0, P_total, size=(B, max_pages)),
                     jnp.int32)   # ids point everywhere
    lens = jnp.full((B,), max_pages * page, jnp.int32)
    base = jnp.zeros((B,), jnp.int32)       # tenant owns [0, half)
    mask = jnp.full((B,), half - 1, jnp.int32)
    out1 = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp), pt,
                               lens, base, mask, interpret=True)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[half:] = 12345.0      # mutate the OTHER tenant's half
    vp2[half:] = -999.0
    out2 = ops.paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2), pt,
                               lens, base, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("V,D,N", [(32, 8, 4), (128, 64, 32),
                                   (1024, 128, 7)])
def test_fenced_gather_sweep(dtype, V, D, N):
    rng = np.random.default_rng(V + N)
    table = jnp.asarray(rng.normal(size=(V, D)), dtype)
    idx = jnp.asarray(rng.integers(-V, 2 * V, size=(N,)), jnp.int32)
    base, mask = V // 2, V // 2 - 1
    out = ops.gather_rows(table, idx, base, mask, interpret=True)
    ref = R.gather_rows_ref(table, idx, base, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("P,page,KH,D,N", [(16, 4, 2, 8, 3),
                                           (64, 16, 4, 32, 8)])
def test_fenced_scatter_sweep(dtype, P, page, KH, D, N):
    rng = np.random.default_rng(P + N)
    pool = jnp.zeros((P, page, KH, D), dtype)
    pages = jnp.asarray(rng.normal(size=(N, page, KH, D)), dtype)
    ids = jnp.asarray(rng.integers(0, 4 * P, size=(N,)), jnp.int32)
    base, mask = 0, P // 2 - 1
    out = ops.scatter_pages(pool, pages, ids, base, mask, interpret=True)
    ref = R.scatter_pages_ref(jnp.zeros((P, page, KH, D), dtype), pages,
                              ids, base, mask)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # isolation: nothing written at or beyond P//2
    assert (np.asarray(out)[P // 2:] == 0).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KH,D,qb,kb", [
    (1, 128, 4, 4, 32, 128, 128),
    (2, 256, 4, 2, 32, 128, 128),
    (2, 256, 8, 1, 64, 64, 128),
])
def test_flash_attention_sweep(dtype, B, S, H, KH, D, qb, kb):
    rng = np.random.default_rng(S + H)
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, KH, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, KH, D)), dtype)
    out = ops.flash_attention(q, k, v, causal=True, q_blk=qb, kv_blk=kb,
                              interpret=True)
    ref = R.flash_attention_ref(q, k, v, causal=True)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@given(st.integers(min_value=1, max_value=300),
       st.integers(min_value=1, max_value=8),
       st.integers(min_value=2, max_value=5))
@settings(max_examples=15, deadline=None)
def test_moe_histogram_property(T, K, loge):
    E = 2 ** loge
    rng = np.random.default_rng(T * K)
    ids = jnp.asarray(rng.integers(0, 2 * E, size=(T, K)), jnp.int32)
    out = ops.moe_histogram(ids, E, 0, E // 2 - 1 if E > 1 else 0,
                            interpret=True)
    ref = R.moe_histogram_ref(ids, E, 0, E // 2 - 1 if E > 1 else 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert int(out.sum()) == T * K


# ---------------------------------------------------------------------------
# Deterministic seeded mirrors of the hypothesis properties (always run).
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,logsize", [(1, 2), (7, 3), (13, 4), (42, 6)])
def test_paged_attention_isolation_sweep(seed, logsize):
    """Mirror of the isolation property: mutating the other tenant's pool
    half never changes the fenced outputs."""
    rng = np.random.default_rng(seed)
    P_total = 2 ** logsize
    half = P_total // 2
    B, H, KH, D, page, max_pages = 2, 4, 2, 16, 4, 4
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    vp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    pt = jnp.asarray(rng.integers(0, P_total, size=(B, max_pages)),
                     jnp.int32)
    lens = jnp.full((B,), max_pages * page, jnp.int32)
    base = jnp.zeros((B,), jnp.int32)
    mask = jnp.full((B,), half - 1, jnp.int32)
    out1 = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp), pt,
                               lens, base, mask, interpret=True)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[half:] = 12345.0
    vp2[half:] = -999.0
    out2 = ops.paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2), pt,
                               lens, base, mask, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,KH,D,page,P_total,max_pages",
    [
        (2, 4, 4, 16, 8, 32, 4),
        (4, 8, 2, 32, 16, 64, 8),
        (3, 6, 2, 16, 4, 32, 16),
    ])
def test_paged_attention_page_map_sweep(dtype, B, H, KH, D, page, P_total,
                                        max_pages):
    """Serve-path layout: page tables hold VIRTUAL ids translated through
    a manager-owned page_map after the fence.  Kernel vs oracle."""
    rng = np.random.default_rng(B * 100 + H + 1)
    n_virt = 2 * P_total
    q = jnp.asarray(rng.normal(size=(B, H, D)), dtype)
    kp = jnp.asarray(rng.normal(size=(P_total, page, KH, D)), dtype)
    vp = jnp.asarray(rng.normal(size=(P_total, page, KH, D)), dtype)
    half = n_virt // 2
    base = jnp.asarray(rng.choice([0, half], size=B), jnp.int32)
    mask = jnp.full((B,), half - 1, jnp.int32)
    pmap = jnp.asarray(rng.permutation(P_total)[
        rng.integers(0, P_total, size=n_virt)], jnp.int32)
    pt = jnp.asarray(rng.integers(0, n_virt, size=(B, max_pages)),
                     jnp.int32)
    lens = jnp.asarray(rng.integers(1, max_pages * page, size=B),
                       jnp.int32)
    out = ops.paged_attention(q, kp, vp, pt, lens, base, mask, pmap,
                              interpret=True)
    ref = R.paged_attention_ref(q, kp, vp, pt, lens, base, mask, pmap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("seed,logsize", [(3, 3), (11, 4), (29, 5)])
def test_paged_attention_page_map_isolation(seed, logsize):
    """Serve-path isolation proof: with virtual extents + page_map
    translation, an adversarial page table full of other-tenant virtual
    ids still only reaches the physical pages the map assigns to the
    attacker's own extent — mutating every other physical page changes
    nothing."""
    rng = np.random.default_rng(seed)
    P_total = 2 ** logsize
    n_virt = P_total
    half = n_virt // 2
    B, H, KH, D, page, max_pages = 2, 4, 2, 16, 4, 4
    # tenant A owns virtual [0, half) mapped to ODD physical pages; the
    # rest of the pool (even pages + page 0) belongs to others
    pmap = np.zeros((n_virt,), np.int32)
    a_phys = [p for p in range(1, P_total) if p % 2 == 1][:half]
    for v, p in enumerate(a_phys):
        pmap[v] = p
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    kp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    vp = np.asarray(rng.normal(size=(P_total, page, KH, D)), np.float32)
    # adversarial: virtual ids spray the whole virtual space
    pt = jnp.asarray(rng.integers(0, n_virt, size=(B, max_pages)),
                     jnp.int32)
    lens = jnp.full((B,), max_pages * page, jnp.int32)
    base = jnp.zeros((B,), jnp.int32)        # fenced into [0, half)
    mask = jnp.full((B,), half - 1, jnp.int32)
    pmapj = jnp.asarray(pmap)
    out1 = ops.paged_attention(q, jnp.asarray(kp), jnp.asarray(vp), pt,
                               lens, base, mask, pmapj, interpret=True)
    kp2, vp2 = kp.copy(), vp.copy()
    others = [p for p in range(P_total) if p not in set(a_phys)]
    kp2[others] = 31337.0                    # clobber every foreign page
    vp2[others] = -31337.0
    out2 = ops.paged_attention(q, jnp.asarray(kp2), jnp.asarray(vp2), pt,
                               lens, base, mask, pmapj, interpret=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


@pytest.mark.parametrize("T,K,E", [(1, 1, 4), (17, 2, 8), (300, 8, 32),
                                   (64, 4, 16)])
def test_moe_histogram_sweep(T, K, E):
    rng = np.random.default_rng(T * K + E)
    ids = jnp.asarray(rng.integers(0, 2 * E, size=(T, K)), jnp.int32)
    out = ops.moe_histogram(ids, E, 0, E // 2 - 1 if E > 1 else 0,
                            interpret=True)
    ref = R.moe_histogram_ref(ids, E, 0, E // 2 - 1 if E > 1 else 0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert int(out.sum()) == T * K
