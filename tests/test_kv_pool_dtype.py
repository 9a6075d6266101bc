"""The global paged KV pool in the model's dtype, read by (layer, page).

Proof obligations:

* **bit-identity** — a bfloat16 model decodes the same logits, bit for
  bit, from a float32 pool and from a bfloat16 one: K and V are computed
  in bfloat16, so the wider pool stores nothing more;
* **containment** — the (layer, page) gather reads only the forger's own
  extent at the scan's own layer, and an unfenced page id never leaves
  its layer;
* **allocation** — ``ServeEngine(paged=True)`` allocates the pool in the
  configuration's dtype, and co-hosted engines share a pool only when
  geometry and dtype both match.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.fence import FenceParams, FencePolicy
from repro.launch.serve import ServeEngine, make_shared_manager, \
    serve_continuous
from repro.models import kvcache as KV
from repro.models import transformer as T
from repro.models.guard import GuardSpec

CFG = get_config("stablelm-3b").reduced()
CFG_BF16 = dataclasses.replace(CFG, dtype="bfloat16")
N_PAGES = 16

# virtual space: row 0's tenant owns [0, 2) -> phys [1, 2];
#                row 1's tenant owns [4, 6) -> phys [3, 4]
PAGE_MAP = np.zeros((8,), np.int32)
PAGE_MAP[0:2] = [1, 2]
PAGE_MAP[4:6] = [3, 4]
GUARD = GuardSpec(
    policy=FencePolicy.BITWISE,
    kv=FenceParams(base=jnp.asarray([0, 4], jnp.int32),
                   size=jnp.asarray([2, 2], jnp.int32)),
    page=FenceParams(base=0, size=N_PAGES),
    page_map=jnp.asarray(PAGE_MAP))


def _decode_logits(pool_dtype, n_steps=8):
    params = T.init(jax.random.PRNGKey(0), CFG_BF16)
    cache = KV.init_global_kv_cache(CFG_BF16, 2, 128, N_PAGES,
                                    dtype=pool_dtype)
    cache = dataclasses.replace(
        cache, page_table=jnp.asarray([[0, 1], [4, 5]], jnp.int32))
    prompt = jnp.asarray([[5, 17, 3, 99, 42, 8, 1, 64],
                          [250, 2, 77, 13, 9, 31, 120, 6]], jnp.int32)
    prefill = jax.jit(lambda c, t: T.prefill(CFG_BF16, params, c, t,
                                             guard=GUARD))
    decode = jax.jit(lambda c, t: T.decode(CFG_BF16, params, c, t,
                                           guard=GUARD))
    cache, logits = prefill(cache, prompt)
    out = [logits]
    for _ in range(n_steps):
        cache, logits = decode(cache, jnp.argmax(out[-1], -1)
                               .astype(jnp.int32))
        out.append(logits)
    assert cache.k.dtype == jnp.dtype(pool_dtype)
    return np.stack([np.asarray(x.astype(jnp.float32)) for x in out])


def test_bf16_pool_decodes_bit_identical_to_f32_pool():
    wide = _decode_logits(jnp.float32)
    narrow = _decode_logits(jnp.bfloat16)
    assert np.isfinite(wide).all()
    np.testing.assert_array_equal(wide, narrow)


def _coded_cache(dtype):
    """A pool whose every entry of (layer l, phys page p) reads
    ``l * N_PAGES + p + 1`` — exact in bfloat16."""
    cache = KV.init_global_kv_cache(CFG, 2, 128, N_PAGES, dtype=dtype)
    L = cache.k.shape[0]
    code = (jnp.arange(L)[:, None] * N_PAGES
            + jnp.arange(N_PAGES)[None, :] + 1).astype(dtype)
    k = jnp.broadcast_to(code[:, :, None, None, None], cache.k.shape)
    return dataclasses.replace(cache, k=k, v=-k)


def _pages_read(kv, page_size):
    """(B, P*page, KH, D) history -> the code of each page, (B, P)."""
    B, S = kv.shape[:2]
    pages = np.asarray(kv.astype(jnp.float32)).reshape(
        B, S // page_size, -1)
    assert (pages == pages[:, :, :1]).all()     # whole pages, no mixing
    return pages[:, :, 0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forged_page_ids_wrap_into_own_extent_at_own_layer(dtype):
    """Row 0 forges its neighbour's virtual ids and an id far outside the
    virtual space: the (layer, page) gather still reads row 0's own
    physical pages, at the layer the scan asks for."""
    cache = _coded_cache(dtype)
    forged = dataclasses.replace(
        cache, page_table=jnp.asarray([[4, 1029], [4, 5]], jnp.int32))
    gather = jax.jit(lambda c, l: KV.gather_layer_kv(c, l, guard=GUARD))
    for layer in range(cache.k.shape[0]):
        k, v = gather(forged, jnp.int32(layer))
        want = layer * N_PAGES + 1 + np.asarray([[1, 2], [3, 4]])
        np.testing.assert_array_equal(_pages_read(k, cache.page_size), want)
        np.testing.assert_array_equal(_pages_read(v, cache.page_size),
                                      -want)
    # zeroing the victim's pages changes nothing that row 0 reads
    zeroed = dataclasses.replace(
        forged, k=forged.k.at[:, 3:5].set(0), v=forged.v.at[:, 3:5].set(0))
    np.testing.assert_array_equal(
        np.asarray(gather(forged, jnp.int32(1))[0][0]),
        np.asarray(gather(zeroed, jnp.int32(1))[0][0]))


def test_unfenced_page_id_stays_in_its_layer():
    """Without a guard, a page id past the pool would address the next
    layer's pages in the (L * P_total) view: it is clipped into its own."""
    cache = _coded_cache(jnp.float32)
    stray = dataclasses.replace(
        cache, page_table=jnp.asarray([[N_PAGES + 3, 2], [-1, 5]],
                                      jnp.int32))
    k, _ = KV.gather_layer_kv(stray, 0)
    np.testing.assert_array_equal(_pages_read(k, cache.page_size),
                                  [[N_PAGES, 3], [1, 6]])


def _pool_dtypes(eng):
    return {leaf.dtype.name for leaf in jax.tree.leaves(eng._pool.buf)}


@pytest.mark.parametrize("cfg", [CFG, CFG_BF16], ids=["float32", "bfloat16"])
def test_paged_engine_pool_takes_the_model_dtype(cfg):
    eng = ServeEngine(cfg, max_batch=2, max_len=64, seed=0, paged=True)
    assert _pool_dtypes(eng) == {cfg.dtype}
    assert eng._steps.pool_name.endswith(f":{cfg.dtype}]")
    eng.register_tenant("t", 2)
    rid = eng.submit("t", [5, 17, 3, 99], max_new=4)
    assert len(serve_continuous([eng], max_new_tokens=4)[0][rid]) == 4


def test_slab_engine_pool_takes_the_model_dtype():
    eng = ServeEngine(CFG_BF16, max_batch=2, max_len=64, seed=0)
    assert _pool_dtypes(eng) == {"bfloat16"}


def test_cohosted_engines_share_a_pool_only_of_one_dtype():
    mgr = make_shared_manager(4, max_batch=2, paged=True, max_len=64)
    a = ServeEngine(CFG, max_batch=2, max_len=64, seed=0, manager=mgr,
                    paged=True)
    b = ServeEngine(CFG, max_batch=2, max_len=64, seed=1, manager=mgr,
                    paged=True)
    c = ServeEngine(CFG_BF16, max_batch=2, max_len=64, seed=0,
                    manager=mgr, paged=True)
    assert a._pool is b._pool
    assert c._pool is not a._pool
    assert _pool_dtypes(a) == {"float32"}
    assert _pool_dtypes(c) == {"bfloat16"}
    assert a._pool.buf["k"].shape == c._pool.buf["k"].shape
