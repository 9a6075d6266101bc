"""The seams between the serve engine and the model: the step identity the
model API gives its compiled steps, and the walks over the pool and meta
fields each cache type declares (``models/kvcache.py``)."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import ServeEngine, make_shared_manager
from repro.models import get_model
from repro.models import kvcache as KV

MAX_LEN = 128
#: a reduced Mellum2: MoE with a held share, sliding and full layers, YaRN
MOE_ARCH = "mellum2-12b-a2.5b"


def _moe_cfg():
    cfg = get_config(MOE_ARCH).reduced()
    assert cfg.moe.held < cfg.moe.num_experts and cfg.yarn and cfg.attn_window
    return cfg


#: a change of one field that no weight shape shows
VARIANTS = {
    "rope_theta": lambda c: dataclasses.replace(
        c, rope_theta=2 * c.rope_theta),
    "attn_window": lambda c: dataclasses.replace(
        c, attn_window=2 * c.attn_window),
    "yarn": lambda c: dataclasses.replace(
        c, yarn=dataclasses.replace(c.yarn, factor=2 * c.yarn.factor)),
    "held_experts": lambda c: dataclasses.replace(
        c, moe=dataclasses.replace(c.moe, expert_base=c.moe.held)),
}


@pytest.mark.parametrize("field", sorted(VARIANTS))
def test_configs_that_differ_in_one_field_compile_apart(field):
    """Two engines on one manager whose configs differ only in ``field``
    share the pool but get step names of their own; the same config twice
    shares one name."""
    cfg = _moe_cfg()
    other = VARIANTS[field](cfg)
    like = jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        get_model(cfg).init, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), jax.eval_shape(
        get_model(other).init, jax.random.PRNGKey(0))) == like
    mgr = make_shared_manager(4, max_batch=2, max_len=MAX_LEN, paged=True)
    engines = [ServeEngine(c, max_batch=2, max_len=MAX_LEN, manager=mgr,
                           paged=True) for c in (cfg, other, cfg)]
    steps = [e._steps for e in engines]
    assert len({s.pool_name for s in steps}) == 1
    for name in ("prefill_name", "decode_name"):
        a, b, again = (getattr(s, name) for s in steps)
        assert a != b and a == again
        assert {a, b} <= set(mgr.pointer_to_symbol)


def test_step_id_is_the_same_in_every_process():
    """The step names do not depend on the process (no ``hash()``), so a
    persistent compile cache finds the steps again."""
    code = ("from repro.configs import get_config\n"
            "from repro.models import get_model\n"
            f"print(get_model(get_config({MOE_ARCH!r}).reduced()).step_id)")
    ids = set()
    for seed in ("1", "2"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu")
        ids.add(subprocess.run([sys.executable, "-c", code], env=env,
                               check=True, capture_output=True,
                               text=True).stdout.strip())
    assert ids == {get_model(_moe_cfg()).step_id}


# ---------------------------------------------------------------------------
# The cache walks
# ---------------------------------------------------------------------------

def _slab(arch):
    def make():
        cfg = get_config(arch).reduced()
        return get_model(cfg).init_cache(2, MAX_LEN, dtype=jnp.float32,
                                         slots=4)
    return make


def _global(arch):
    def make():
        return KV.init_global_kv_cache(get_config(arch).reduced(), 2,
                                       MAX_LEN, 8)
    return make


#: every cache type the engine serves from (the slab ones with 4 slots,
#: the global ones with 8 pages)
CACHES = {
    "dense_slab": _slab("stablelm-3b"),
    "dense_global": _global("stablelm-3b"),
    "moe_global": _global(MOE_ARCH),
    "hybrid": _slab("zamba2-7b"),
    "ssm": _slab("xlstm-350m"),
    "encdec": _slab("seamless-m4t-medium"),
}


def _filled(cache):
    """``cache`` with every pool and meta leaf nonzero and distinct."""
    pool, meta = KV.split_pool(cache)
    pool = jax.tree.map(lambda a: (jnp.arange(a.size, dtype=a.dtype)
                                   .reshape(a.shape) + 1), pool)
    meta = jax.tree.map(lambda a: jnp.arange(a.size, dtype=a.dtype)
                        .reshape(a.shape) + 3, meta)
    return KV.join_pool(pool, meta)


def _equal(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _parts_with(cache, name):
    """Every part of ``cache`` (itself included) that has meta ``name``."""
    found = [cache] if name in cache.META else []
    for f in dataclasses.fields(cache):
        if f.name not in cache.POOL + cache.META:
            found += _parts_with(getattr(cache, f.name), name)
    return found


@pytest.mark.parametrize("kind", sorted(CACHES))
def test_cache_walks(kind):
    """Split then join is the identity; a scrub zeroes exactly its slots
    (pages, in the global layout) of every pool tensor and nothing else;
    a slot-id rewrite and a seq-len reset touch only meta."""
    cache = _filled(CACHES[kind]())
    pool, meta = KV.split_pool(cache)
    assert jax.tree.leaves(pool) and all(
        leaf.ndim >= 2 and leaf.shape[1] == KV.pool_slots(cache)
        for leaf in jax.tree.leaves(pool))
    assert not set(map(id, jax.tree.leaves(meta))) & set(
        map(id, jax.tree.leaves(pool)))
    _equal(KV.join_pool(pool, meta), cache)

    ids = (1, 2) if KV.pool_slots(cache) == 4 else (3, 5)
    scrubbed_pool, scrubbed_meta = KV.split_pool(KV.scrub(cache, ids))
    _equal(scrubbed_meta, meta)
    for before, after in zip(jax.tree.leaves(pool),
                             jax.tree.leaves(scrubbed_pool)):
        before, after = np.asarray(before), np.asarray(after)
        keep = [i for i in range(before.shape[1]) if i not in ids]
        assert not after[:, list(ids)].any()
        np.testing.assert_array_equal(after[:, keep], before[:, keep])

    slots = jnp.asarray([3, 0], jnp.int32)
    for new, name, want in (
            (KV.with_slot_ids(cache, slots), "slot_ids", [3, 0]),
            (KV.reset_seq_lens(cache), "seq_lens", [0, 0])):
        new_pool, new_meta = KV.split_pool(new)
        _equal(new_pool, pool)
        parts = _parts_with(new_meta, name)
        assert parts
        for part in parts:
            np.testing.assert_array_equal(np.asarray(getattr(part, name)),
                                          want)
        _equal(_other_meta(new_meta, name), _other_meta(meta, name))


def _other_meta(meta, name):
    """The meta leaves of every field but ``name``."""
    return [leaf for path, leaf in jax.tree_util.tree_flatten_with_path(
        meta)[0] if path[-1].name != name]


def test_pages_per_slot_reads_the_kv_part():
    """The guard's page clamp: a slab's pages, the global pool's pages,
    or 1 where no part holds KV pages."""
    assert KV.pages_per_slot(CACHES["dense_slab"]()) == MAX_LEN // 64
    assert KV.pages_per_slot(CACHES["hybrid"]()) >= 1
    assert KV.pages_per_slot(CACHES["dense_global"]()) == 8
    assert KV.pages_per_slot(CACHES["ssm"]()) == 1
