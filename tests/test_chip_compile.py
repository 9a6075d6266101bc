"""v5e compile tests: the served path at stablelm-3b widths compiles for
one described TPU v5e chip, and fits its 16 GB.

Nothing here runs on a chip.  Each test hands the TPU compiler shapes
(``jax.eval_shape``; params are never materialised) placed on one device
of a described ``v5e:2x2`` topology, and compiles:

* the served paged prefill and decode steps, jitted the way the manager
  jits a trusted step (arena and KV pool donated, the pool in the
  model's dtype), with a real ``GuardSpec`` carrying the per-row fence
  params and the page map; the decode step also at the benchmark's
  2 x 1024, where it must read the pool by page, never by layer;
* the stablelm3b cells' steps at 2 x 1024, pinned op for op;
* Mellum2's served steps at its benchmark's 2 x 8192 (prefill at the
  traffic's longest prompt, 5376), within 16 GB, their expert products
  the grouped matmul over the whole stacked bank;
* ``fenced_paged_attention`` with ``page_map`` and ``interpret=False``.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file.  Keep every such compile in this one file.
"""

import os
import re

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
MELLUM = "mellum2-12b-a2.5b"
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


def _served(sharding, max_batch, max_len, arch=ARCH, prompt_len=PROMPT_LEN):
    """The manager, step bundle and operand shapes of the served paged
    path, built as ``ServeEngine(paged=True)`` on ``make_shared_manager``
    builds them (three tenants of 16 pages, beside the scratch extent)."""
    cfg = get_config(arch)
    api = get_model(cfg)
    mgr = make_shared_manager(1, max_batch=max_batch, max_len=max_len,
                              paged=True)
    n_pages = mgr.bounds.total_slots
    pages = PagePool(n_pages, n_pages)
    mgr.register_tenant("__scratch", max_len // KV.PAGE_SIZE)
    for t in ("t0", "t1", "t2"):
        mgr.register_tenant(t, 16)
        part = mgr.bounds.lookup(t)
        pages.bind_extent(t, part.base, part.size)
    table, row_of = mgr.fence_table()
    cols = np.full((max_batch,), row_of["__scratch"], np.int32)
    active = ("t0", "t1", "t2")[:max_batch]
    cols[:len(active)] = [row_of[t] for t in active]
    guard = GuardSpec(
        policy=mgr.policy,
        vocab=FenceParams(base=0, size=1 << (cfg.vocab - 1).bit_length()),
        kv=table.gather(jnp.asarray(cols)),
        expert=FenceParams(base=0, size=cfg.moe.held) if cfg.moe else None,
        page=FenceParams(base=0, size=n_pages),
        page_map=jnp.asarray(pages.page_map))

    cache = jax.eval_shape(lambda: KV.init_global_kv_cache(
        cfg, max_batch, max_len, n_pages))
    pool, meta = split_cache_pool(cache)
    steps = build_trusted_serve_steps(
        api, f"chip-compile:{arch}:{max_batch}x{max_len}",
        counts=bool(api.counters))
    steps.register(mgr, pool)
    params = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    ops = dict(
        arena=mgr.arena.buf, pool=pool, params=params, meta=meta,
        guard=guard,
        prefill_x={"tokens": jax.ShapeDtypeStruct((max_batch, prompt_len),
                                                  jnp.int32)},
        decode_x=jax.ShapeDtypeStruct((max_batch,), jnp.int32))
    return mgr, steps, {k: _on(sharding, v) for k, v in ops.items()}


def _compile(served, phase):
    mgr, steps, ops = served
    name = steps.prefill_name if phase == "prefill" else steps.decode_name
    entry = mgr.pointer_to_symbol[name]
    step = mgr._trusted_exec(entry, (), arg_sig=("v5e-compile", phase))
    return step.lower(ops["arena"], ops["pool"], ops["params"],
                      ops["meta"], ops[f"{phase}_x"], ops["guard"]).compile()


@pytest.fixture(scope="module")
def served(one_chip):
    """Rows 0-2 active of 8 x 256."""
    return _served(one_chip, MAX_BATCH, MAX_LEN)


@pytest.fixture(scope="module")
def served_bench(one_chip):
    """The benchmark's shape: 2 rows of 1024, 64 pool pages."""
    return _served(one_chip, 2, 1024)


@pytest.mark.parametrize("phase", ["prefill", "decode"])
def test_served_step_compiles_and_fits(served, phase):
    ops = served[2]
    compiled = _compile(served, phase)
    mem = compiled.memory_analysis()
    # the arena and the pool are donated: they alias the outputs
    assert mem.alias_size_in_bytes >= sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(ops["pool"]))
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_served_decode_reads_pages_not_layers(served_bench):
    """At 2 x 1024 the rows address 32 of the pool's 64 pages a layer:
    the decode step gathers those pages from the bfloat16 pool by
    (layer, page) and never materialises a layer of the pool."""
    cfg = get_config(ARCH)
    ops = served_bench[2]
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree.leaves(ops["pool"]))
    n_pages = ops["pool"]["k"].shape[1]
    compiled = _compile(served_bench, "decode")
    layer = "[%d,%d,%d,%d]" % (n_pages, KV.PAGE_SIZE, cfg.n_kv_heads,
                               cfg.head_dim)
    # an instruction's output shape sits between its name and its opcode
    outputs = re.findall(r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ",
                         compiled.as_text(), flags=re.M)
    assert outputs
    assert not [o for o in outputs if layer in o]
    # 7,739,044,352 B on a v5e; with the float32 pool it was 9,886,528,000
    arg_bytes = compiled.memory_analysis().argument_size_in_bytes
    assert arg_bytes < 7.75e9


#: the stablelm3b cells' served steps at 2 x 1024 as compiled before the
#: moe family learned layer kinds, held experts and dropless serving:
#: argument and temporary bytes, and the count of copy and gather ops and
#: of all ops.  Those changes leave the dense path's programs alone, op
#: for op.
DENSE_PINS = {
    ("prefill", 768): (7739049984, 151833600, 8, 2, 931),
    ("prefill", 1008): (7739052032, 275563008, 8, 2, 939),
    ("decode", 768): (7739044352, 0, 9, 6, 1120),
}


@pytest.mark.parametrize("phase,prompt_len", sorted(DENSE_PINS))
def test_stablelm_served_steps_are_pinned(one_chip, phase, prompt_len):
    """The stablelm3b cells' prefill (the chat mix's 768 and the
    interactive mix's 1008) and decode steps compile to the pinned
    programs."""
    served = _served(one_chip, 2, 1024, prompt_len=prompt_len)
    compiled = _compile(served, phase)
    mem = compiled.memory_analysis()
    ops = re.findall(r"^\s*(?:ROOT )?%\S+ = .*? ([a-z][a-z0-9-]*)\(",
                     compiled.as_text(), flags=re.M)
    assert (mem.argument_size_in_bytes, mem.temp_size_in_bytes,
            ops.count("copy"), ops.count("gather"), len(ops)) == \
        DENSE_PINS[phase, prompt_len]


def _copies(text, array):
    """``copy`` instructions whose output holds as many bfloat16 elements
    as ``array``: a copy of it in its own shape or any view of it (the
    pool's (L * P, page, KH, D) page rows, say)."""
    n = 0
    for dims in re.findall(r"= bf16\[([0-9,]+)\]\S* copy\(", text):
        n += int(np.prod([int(d) for d in dims.split(",")])) == array.size
    return n


@pytest.fixture(scope="module")
def served_mellum(one_chip):
    """The benchmark's Mellum2 shape: 2 rows of 8192, 512 pool pages, the
    longest prompt of its traffic (5376) for prefill."""
    return _served(one_chip, 2, 8192, arch=MELLUM, prompt_len=5376)


def _expert_slices(text, bank):
    """Instructions whose output holds bfloat16 expert weights in the
    bank's trailing (depth, width) shape but fewer experts than the
    whole bank: a layer's or a period's experts taken out of the stacks."""
    n = 0
    for out in re.findall(r"^\s*(?:ROOT )?%\S+ = (\(.*?\)|\S+) ", text,
                          flags=re.M):
        for dims in re.findall(r"bf16\[([0-9,]+)\]", out):
            dims = [int(d) for d in dims.split(",")]
            n += (dims[-2:] == list(bank.shape[-2:])
                  and int(np.prod(dims[:-2])) < np.prod(bank.shape[:-2]))
    return n


@pytest.mark.parametrize("phase,copies", [("prefill", 4), ("decode", 0)])
def test_mellum_steps_compile_and_fit(served_mellum, phase, copies):
    """Mellum2's served steps at the benchmark's 2 x 8192 fit one v5e
    beside the 7.66 GB of weights and the 1.88 GB pool.  The decode step
    reads the pool by page and copies none of it, in no view; the 2 x
    5376 prefill step relayouts each pool half before its layer loop and
    back after it, four copies (the whole-pool copy fault, at prefill),
    and still fits.  Neither step relayouts the stacked q projection, and
    neither takes a layer's or a period's experts out of the stacked
    bank: the grouped matmul reads them where they lie."""
    ops = served_mellum[2]
    pool = ops["pool"]["k"]
    assert pool.shape == (28, 512, KV.PAGE_SIZE, 4, 128)
    assert pool.dtype == jnp.bfloat16
    compiled = _compile(served_mellum, phase)
    mem = compiled.memory_analysis()
    assert 9.5e9 < mem.argument_size_in_bytes < 9.6e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    text = compiled.as_text()
    assert _copies(text, pool) == copies
    assert _copies(text, ops["params"]["layers"]["attn"]["wq"]) == 0
    moe = ops["params"]["layers"]["moe"]
    for name in ("wg", "wu", "wd"):
        assert _expert_slices(text, moe[name]) == 0, name
    assert "tpu_custom_call" in text


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
