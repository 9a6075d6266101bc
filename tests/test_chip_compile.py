"""v5e compile tests: the served path at stablelm-3b widths compiles for
one described TPU v5e chip, and fits its 16 GB.

Nothing here runs on a chip.  Each test hands the TPU compiler shapes
(``jax.eval_shape``; params are never materialised) placed on one device
of a described ``v5e:2x2`` topology, and compiles:

* the served paged prefill and decode steps, jitted the way the manager
  jits a trusted step (arena and KV pool donated), with a real
  ``GuardSpec`` carrying the per-row fence params and the page map;
* ``fenced_paged_attention`` with ``page_map`` and ``interpret=False``.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.  Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.fence import FenceParams
from repro.core.arena import PagePool
from repro.kernels.fenced_paged_attention import fenced_paged_attention
from repro.launch.serve import make_shared_manager
from repro.launch.steps import build_trusted_serve_steps, split_cache_pool
from repro.models import get_model
from repro.models import kvcache as KV
from repro.models.guard import GuardSpec

ARCH = "stablelm-3b"
MAX_BATCH = 8
MAX_LEN = 256
PROMPT_LEN = 16
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described (not attached) v5e:2x2 host, with the
    persistent compile cache off: an entry written here cannot be read
    back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def served(one_chip):
    """The manager, step bundle and operand shapes of the served paged
    path, built as ``ServeEngine(paged=True)`` on ``make_shared_manager``
    builds them (three tenants, batch rows 0-2 active)."""
    cfg = get_config(ARCH)
    api = get_model(cfg)
    mgr = make_shared_manager(1, max_batch=MAX_BATCH, max_len=MAX_LEN,
                              paged=True)
    n_pages = mgr.bounds.total_slots
    pages = PagePool(n_pages, n_pages)
    mgr.register_tenant("__scratch", MAX_LEN // KV.PAGE_SIZE)
    for t in ("t0", "t1", "t2"):
        mgr.register_tenant(t, 16)
        part = mgr.bounds.lookup(t)
        pages.bind_extent(t, part.base, part.size)
    table, row_of = mgr.fence_table()
    cols = np.full((MAX_BATCH,), row_of["__scratch"], np.int32)
    cols[:3] = [row_of[t] for t in ("t0", "t1", "t2")]
    guard = GuardSpec(
        policy=mgr.policy,
        vocab=FenceParams(base=0, size=1 << (cfg.vocab - 1).bit_length()),
        kv=table.gather(jnp.asarray(cols)),
        page=FenceParams(base=0, size=n_pages),
        page_map=jnp.asarray(pages.page_map))

    cache = jax.eval_shape(lambda: KV.init_global_kv_cache(
        cfg, MAX_BATCH, MAX_LEN, n_pages, dtype=jnp.float32))
    pool, meta = split_cache_pool(cache)
    steps = build_trusted_serve_steps(api, f"chip-compile:{ARCH}")
    steps.register(mgr, pool)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    ops = dict(
        arena=mgr.arena.buf, pool=pool, params=params, meta=meta,
        guard=guard,
        prefill_x={"tokens": jax.ShapeDtypeStruct((MAX_BATCH, PROMPT_LEN),
                                                  jnp.int32)},
        decode_x=jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32))
    return mgr, steps, {k: _on(one_chip, v) for k, v in ops.items()}


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_served_step_compiles_and_fits(served, phase):
    mgr, steps, ops = served
    name = steps.prefill_name if phase == "prefill" else steps.decode_name
    entry = mgr.pointer_to_symbol[name]
    step = mgr._trusted_exec(entry, (), arg_sig=("v5e-compile", phase))
    compiled = step.lower(ops["arena"], ops["pool"], ops["params"],
                          ops["meta"], ops[f"{phase}_x"],
                          ops["guard"]).compile()
    mem = compiled.memory_analysis()
    # the arena and the pool are donated: they alias the outputs
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(ops["pool"]))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fenced_paged_attention_compiles(one_chip, dtype):
    cfg = get_config(ARCH)
    H, KH, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    n_pages = 64
    per_req = MAX_LEN // KV.PAGE_SIZE
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    i32 = jnp.int32
    compiled = fenced_paged_attention.lower(
        sds((MAX_BATCH, H, D), dtype),
        sds((n_pages, KV.PAGE_SIZE, KH, D), dtype),
        sds((n_pages, KV.PAGE_SIZE, KH, D), dtype),
        sds((MAX_BATCH, per_req), i32), sds((MAX_BATCH,), i32),
        sds((MAX_BATCH,), i32), sds((MAX_BATCH,), i32),
        sds((n_pages,), i32), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
