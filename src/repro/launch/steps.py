"""Step builders — train / prefill / decode as pjit-ready callables with
full sharding trees.

``build_step(cfg, shape, mesh, ...)`` returns a :class:`StepBundle`:

    fn              the python callable (pure)
    in_specs        pytree of ShapeDtypeStructs (the dry-run inputs)
    in_shardings    matching NamedShardings
    out_shardings   NamedShardings (or None -> let GSPMD choose)
    donate_argnums  buffers that alias in-place (params/opt/cache)

Used by dryrun.py (lower+compile with abstract inputs), train.py and
serve.py (real execution).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.core.fence import FenceParams, FencePolicy
from repro.distributed.sharding import ShardingRules, make_rules
from repro.models import kvcache as KV
from repro.models.api import ModelAPI, get_model
from repro.models.encdec import EncDecCache
from repro.models.guard import GuardSpec
from repro.models.hybrid import HybridCache
from repro.optim import adamw, apply_updates, cosine


#: names of the served steps.  A jitted step compiles as ``jit_<name>``
#: (``jit_fused_<name>`` when the scheduler fuses engines) and its
#: operations sit under ``jax.named_scope(<name>)``: the benchmark's
#: device-trace readers find the steps by these names.
PREFILL_STEP = "prefill_step"
DECODE_STEP = "decode_step"


def _named_step(name: str) -> Callable[[Callable], Callable]:
    """Give a step function the stable ``name`` (``__name__``, which jit
    turns into the program's name) and scope its body under it."""
    def wrap(fn: Callable) -> Callable:
        def step(*args):
            with jax.named_scope(name):
                return fn(*args)
        step.__name__ = step.__qualname__ = name
        return step
    return wrap


@dataclasses.dataclass
class StepBundle:
    fn: Callable
    in_specs: Tuple
    in_shardings: Tuple
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    rules: ShardingRules
    api: ModelAPI


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _is_axes(x):
    return isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x)


def _shard_tree(mesh: Mesh, rules: ShardingRules, axes_tree,
                shape_tree=None):
    """Logical axes -> NamedShardings.  When ``shape_tree`` is given,
    dimensions whose size is not divisible by the mapped mesh-axis size
    are replicated instead (input shardings, unlike constraints, require
    exact divisibility)."""
    if shape_tree is None:
        return jax.tree.map(
            lambda axes: rules.sharding(mesh, axes), axes_tree,
            is_leaf=_is_axes)

    def one(axes, shaped):
        dims = tuple(shaped.shape)
        checked = []
        for i, logical in enumerate(axes):
            mesh_axis = rules.lookup(logical) if logical else None
            if mesh_axis is not None and i < len(dims) and \
                    dims[i] % _axis_size(mesh, mesh_axis) != 0:
                mesh_axis = None
            checked.append(mesh_axis)
        return NamedSharding(mesh, P(*checked))

    return jax.tree.map(one, axes_tree, shape_tree, is_leaf=_is_axes)


def _batch_axes(specs: Dict[str, jax.ShapeDtypeStruct]):
    out = {}
    for k, v in specs.items():
        out[k] = ("batch",) + (None,) * (len(v.shape) - 1)
    return out


def make_guard(cfg: ModelConfig, shape: ShapeConfig,
               policy: FencePolicy = FencePolicy.BITWISE,
               enabled: bool = True) -> Optional[GuardSpec]:
    """Default single-tenant-owns-everything guard (fences still compiled
    in — the overhead-measurement configuration).  ``enabled=False`` is the
    paper's standalone fast path (no fence instructions emitted)."""
    if not enabled:
        return None
    import math

    def pow2(n):
        return 1 << max(int(n - 1).bit_length(), 0) if n > 1 else 1

    slots = pow2(shape.global_batch)
    pages = pow2(max(shape.seq_len // KV.PAGE_SIZE, 1))
    vocab = pow2(cfg.vocab)
    expert = pow2(cfg.moe.num_experts) if cfg.moe else 0

    def fp(n):
        return FenceParams(base=0, size=n) if n else None

    return GuardSpec(policy=policy, vocab=fp(vocab), kv=fp(slots),
                     state=fp(slots), expert=fp(expert), page=fp(pages))


# ---------------------------------------------------------------------------
# Cache sharding-axes trees (structure-matched to the cache pytrees)
# ---------------------------------------------------------------------------

def _kv_pool_axes(mesh: Mesh, n_kv_heads: int):
    """(L, slots, P, page, KH, D) sharding for the KV pool.

    KH shards over the model axis when divisible; otherwise the model
    axis falls back to head_dim (all assigned archs have head_dim
    divisible by 16) so the pool is never replicated across TP ranks."""
    model = mesh.shape.get("model", 1)
    if n_kv_heads % model == 0:
        return (None, "pages", None, None, "kv_heads", None)
    return (None, "pages", None, None, None, "heads")


def _paged_axes(mesh, cache_shape: KV.PagedKVCache,
                n_kv_heads: int) -> KV.PagedKVCache:
    kv = _kv_pool_axes(mesh, n_kv_heads)
    return KV.PagedKVCache(k=kv, v=kv, page_table=("batch", None),
                           slot_ids=("batch",), seq_lens=("batch",))


def _state_axes(cache_shape: KV.StateCache) -> KV.StateCache:
    pools = {name: (None, "pages") + (None,) * (len(arr.shape) - 2)
             for name, arr in cache_shape.pools.items()}
    return KV.StateCache(pools=pools, slot_ids=("batch",),
                         seq_lens=("batch",))


def cache_axes(mesh, cfg, cache_shape):
    if isinstance(cache_shape, KV.PagedKVCache):
        return _paged_axes(mesh, cache_shape, cfg.n_kv_heads)
    if isinstance(cache_shape, KV.StateCache):
        return _state_axes(cache_shape)
    if isinstance(cache_shape, HybridCache):
        return HybridCache(
            kv=_paged_axes(mesh, cache_shape.kv, cfg.n_kv_heads),
            state=_state_axes(cache_shape.state))
    if isinstance(cache_shape, EncDecCache):
        model = mesh.shape.get("model", 1)
        if cfg.n_kv_heads % model == 0:
            cross = (None, "pages", None, "kv_heads", None)
        else:
            cross = (None, "pages", None, None, "heads")
        return EncDecCache(
            kv=_paged_axes(mesh, cache_shape.kv, cfg.n_kv_heads),
            cross_k=cross, cross_v=cross, src_lens=("batch",))
    raise TypeError(type(cache_shape))


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                     fsdp: bool = True, guard_enabled: bool = True,
                     policy: FencePolicy = FencePolicy.BITWISE,
                     remat: bool = True,
                     peak_lr: float = 3e-4,
                     moe_dispatch: str = "scatter",
                     remat_policy: str = "nothing") -> StepBundle:
    api = get_model(cfg)
    rules = make_rules(mesh, fsdp=fsdp)
    guard = make_guard(cfg, shape, policy, guard_enabled)
    opt = adamw(cosine(peak_lr, 2_000, 100_000))
    extra = {"dispatch": moe_dispatch,
             "remat_policy": remat_policy} if cfg.moe else {}

    def train_step(params, opt_state, batch):
        def loss_of(p):
            return api.loss(p, batch, guard=guard, rules=rules,
                            remat=remat, **extra)
        loss, grads = jax.value_and_grad(loss_of)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss}

    # abstract trees
    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    opt_shape = jax.eval_shape(opt.init, params_shape)
    batch_specs = api.batch_specs(shape)

    p_axes = api.param_logical_axes()
    p_shard = _shard_tree(mesh, rules, p_axes, params_shape)
    # optimizer state: m/v/vr/vc inherit param sharding; scalars replicated
    rep = NamedSharding(mesh, P())

    def opt_shardings(opt_tree):
        def walk(sub, ps):
            if isinstance(sub, dict) and ("m" in sub or "v" in sub):
                out = {}
                for k, v in sub.items():
                    if k == "step":
                        out[k] = rep
                    else:
                        out[k] = jax.tree.map(lambda a, s: s, v, ps) \
                            if _same_structure(v, ps) else jax.tree.map(
                                lambda a: rep, v)
                return out
            return jax.tree.map(lambda a: rep, sub)
        return walk(opt_tree, p_shard)

    def _same_structure(a, b):
        try:
            jax.tree.map(lambda x, y: None, a, b)
            return True
        except ValueError:
            return False

    o_shard = opt_shardings(opt_shape)
    b_shard = _shard_tree(mesh, rules, _batch_axes(batch_specs),
                          batch_specs)

    return StepBundle(
        fn=train_step,
        in_specs=(params_shape, opt_shape, batch_specs),
        in_shardings=(p_shard, o_shard, b_shard),
        out_shardings=(p_shard, o_shard,
                       {"loss": NamedSharding(mesh, P())}),
        donate_argnums=(0, 1),
        rules=rules,
        api=api,
    )


# ---------------------------------------------------------------------------
# Serve steps (prefill / decode)
# ---------------------------------------------------------------------------

_KV_DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32,
              "f8": jnp.float8_e4m3fn}


#: a serve cache's ``(pool, meta)`` halves and back (``models/kvcache.py``)
split_cache_pool = KV.split_pool
join_cache_pool = KV.join_pool


@dataclasses.dataclass(frozen=True)
class TrustedStepBundle:
    """The serving engine's prefill/decode as *trusted manager kernels*
    (see ``GuardianManager.register_trusted_kernel``): internally fenced
    via a per-row GuardSpec, params/meta/guard passed as operands (never
    closed over — closures would bake the weights into every compiled
    step), the flat manager arena AND the shared KV pool arena threaded
    through (``fn(arena, pool, params, meta, x, guard) ->
    (arena, pool, (meta, next_ids))``).

    Names carry the model's step id (``ModelAPI.step_id``) and the
    pool's geometry, so engines serving the *same* model share one symbol
    entry — and therefore one compiled step that the scheduler can fuse
    across engines, all addressing one manager-owned pool — while engines
    serving different models stay on separate entries (a shared name
    with different step functions would silently run the first engine's
    model for everyone).
    """

    pool_name: str
    prefill_name: str
    decode_name: str
    prefill_fn: Callable
    decode_fn: Callable
    #: demand an extent-mode static bounds proof on the first dispatch of
    #: each operand signature instead of blind trust — see
    #: ``GuardianManager.register_trusted_kernel(verify=True)``
    verify: bool = False

    def register(self, manager, pool: Dict) -> Any:
        """Adopt ``pool`` as the manager arena (idempotent — co-hosted
        engines converge on the first-registered pool) and register both
        step kernels against it.  Returns the live PoolArena."""
        arena = manager.register_pool(self.pool_name, pool)
        manager.register_trusted_kernel(
            self.prefill_name, self.prefill_fn, pool_arena=self.pool_name,
            verify=self.verify)
        manager.register_trusted_kernel(
            self.decode_name, self.decode_fn, pool_arena=self.pool_name,
            verify=self.verify)
        return arena


def build_trusted_serve_steps(api: ModelAPI, pool_key: str,
                              verify: bool = False,
                              step_key: Optional[str] = None,
                              temperature: float = 0.0,
                              top_k: int = 0,
                              counts: bool = False) -> TrustedStepBundle:
    """Trusted prefill/decode step functions for one model API.

    The step rebuilds the cache from the manager-threaded pool + the
    engine's meta operand, runs the model, and splits the result back.
    Sampling happens *inside* the step: the engine's decode loop stays
    fully asynchronous — per step it receives ``(meta, next_ids)`` (and,
    with ``counts`` for a model with ``api.counters``, its per-row counts
    as a third item) and never materializes the ``(B, vocab)`` logits on
    the host.

    ``pool_key`` must identify the pool geometry (slot count, page
    layout); ``step_key`` (default: ``pool_key``) additionally carries
    the model identity when the pool is the *global paged* layout shared
    by engines serving different models — such engines address one pool
    arena but keep distinct step symbols (a shared name with different
    step functions would silently run the first engine's model for
    everyone).

    ``temperature > 0`` builds the *sampled* decode step: its token
    operand is ``(toks, key)`` — the PRNG key threads as an operand, so
    the step stays pure and jit-cached — and next ids draw from the
    temperature-scaled, optionally top-k-truncated distribution.  The
    greedy default (``temperature=0``) compiles the exact argmax program
    of previous revisions, bit-identical, under the unsuffixed symbol
    names.
    """
    sk = step_key or pool_key
    kw = {}
    if counts and api.counters:
        kw = {"counts": True}
        sk += ":counts"

    def run(model_fn, params, cache, x, guard):
        """The model's step; its per-row counts, if any, ride beside."""
        out = model_fn(params, cache, x, guard=guard, **kw)
        return out[0], out[1], out[2:]

    @_named_step(PREFILL_STEP)
    def prefill_step(arena, pool, params, meta, batch, guard):
        cache = join_cache_pool(pool, meta)
        cache, logits, extra = run(api.prefill, params, cache, batch, guard)
        new_pool, new_meta = split_cache_pool(cache)
        return arena, new_pool, (
            new_meta, jnp.argmax(logits, -1).astype(jnp.int32), *extra)

    if temperature > 0:
        @_named_step(DECODE_STEP)
        def decode_step(arena, pool, params, meta, x, guard):
            toks, key = x
            cache = join_cache_pool(pool, meta)
            cache, logits, extra = run(api.decode, params, cache, toks,
                                       guard)
            new_pool, new_meta = split_cache_pool(cache)
            logits = logits.astype(jnp.float32)
            if top_k:
                kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
                logits = jnp.where(logits < kth, -jnp.inf, logits)
            nxt = jax.random.categorical(key, logits / temperature, axis=-1)
            return arena, new_pool, (new_meta, nxt.astype(jnp.int32),
                                     *extra)
        decode_name = f"serve.decode.sampled[{sk}:t{temperature}:k{top_k}]"
    else:
        @_named_step(DECODE_STEP)
        def decode_step(arena, pool, params, meta, toks, guard):
            cache = join_cache_pool(pool, meta)
            cache, logits, extra = run(api.decode, params, cache, toks,
                                       guard)
            new_pool, new_meta = split_cache_pool(cache)
            return arena, new_pool, (
                new_meta, jnp.argmax(logits, -1).astype(jnp.int32), *extra)
        decode_name = f"serve.decode[{sk}]"

    return TrustedStepBundle(
        pool_name=f"serve.pool[{pool_key}]",
        prefill_name=f"serve.prefill[{sk}]",
        decode_name=decode_name,
        prefill_fn=prefill_step,
        decode_fn=decode_step,
        verify=verify,
    )


# ---------------------------------------------------------------------------
# Elastic relocation steps (core/elastic.py)
# ---------------------------------------------------------------------------


def build_flat_relocation_step(moves: Tuple[Tuple[int, int, int], ...],
                               zeros: Tuple[Tuple[int, int], ...],
                               src_extent: Tuple[int, int],
                               dst_extent: Tuple[int, int]) -> Callable:
    """On-device compaction step for the flat arena — a *trusted* kernel
    (``fn(arena) -> (arena, None)``) the elastic manager registers and
    dispatches through the BatchedLaunchScheduler between drain cycles.

    ``moves`` are absolute ``(src, dst, len)`` slot copies, applied in
    order (the elastic planner emits them ascending with ``dst <= src``
    per move, so in-place packing never reads a clobbered source);
    ``zeros`` scrub the vacated ranges afterwards (no stale tenant bytes
    in reclaimed slots).  Reads are fenced against the tenant's source
    extent and writes against its destination extent, and the scrub
    ranges — static ints — are validated here against the union of the
    two extents before the step exists at all: the relocation step obeys
    the same bounds discipline as any tenant kernel, so a bug in the
    planner cannot touch a co-tenant's slots.
    """
    src_fp = FenceParams(base=src_extent[0], size=src_extent[1])
    dst_fp = FenceParams(base=dst_extent[0], size=dst_extent[1])
    for start, ln in zeros:
        in_src = (src_extent[0] <= start
                  and start + ln <= src_extent[0] + src_extent[1])
        in_dst = (dst_extent[0] <= start
                  and start + ln <= dst_extent[0] + dst_extent[1])
        if ln < 0 or not (in_src or in_dst):
            raise ValueError(
                f"relocation scrub range [{start},{start + ln}) leaves "
                f"the moving tenant's extents {src_extent}/{dst_extent}")

    def relocate(arena):
        from repro.core.fence import (
            guarded_dynamic_slice,
            guarded_dynamic_update_slice,
        )
        for src, dst, ln in moves:
            data = guarded_dynamic_slice(
                arena, jnp.int32(src), ln, src_fp, FencePolicy.BITWISE)
            arena = guarded_dynamic_update_slice(
                arena, jnp.int32(dst), data, dst_fp, FencePolicy.BITWISE)
        for start, ln in zeros:
            z = jnp.zeros((ln, *arena.shape[1:]), arena.dtype)
            arena = jax.lax.dynamic_update_slice_in_dim(
                arena, z, start, axis=0)
        return arena, None

    return relocate


def build_pool_relocation_step(src: int, dst: int, size: int) -> Callable:
    """Slot-extent move for a manager-owned serve pool — a trusted kernel
    with ``pool_arena`` threading (``fn(arena, pool) -> (arena, pool,
    None)``) so a tenant's KV/state slots follow its partition when the
    elastic manager grows or relocates it.

    Every slot-indexed pool tensor (axis 1 — see
    ``kvcache.PagedKVCache``) has ``[src, src+size)`` copied wholesale to
    ``[dst, dst+size)`` and the vacated source range zeroed; per-slot
    page tables live in the engines' meta halves and are slot-relative,
    so they survive the move untouched.  Distinct buddy extents never
    overlap (pow2 blocks nest or are disjoint), which makes
    copy-then-zero exact.
    """

    def move(arr):
        if arr.ndim < 2 or arr.shape[1] < max(src, dst) + size:
            # meta-shaped straggler: too short to be slot-indexed over
            # BOTH extents — touching it would clamp the copy into the
            # wrong rows, so it passes through untouched
            return arr
        data = jax.lax.dynamic_slice_in_dim(arr, src, size, axis=1)
        arr = jax.lax.dynamic_update_slice_in_dim(arr, data, dst, axis=1)
        z = jnp.zeros_like(data)
        return jax.lax.dynamic_update_slice_in_dim(arr, z, src, axis=1)

    def relocate(arena, pool):
        return arena, jax.tree.map(move, pool), None

    return relocate


def _cache_shape_for(api: ModelAPI, shape: ShapeConfig,
                     kv_dtype: str = "bf16"):
    return jax.eval_shape(functools.partial(
        api.init_cache, shape.global_batch, shape.seq_len,
        dtype=_KV_DTYPES[kv_dtype]))


def build_prefill_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                       guard_enabled: bool = True,
                       policy: FencePolicy = FencePolicy.BITWISE,
                       kv_dtype: str = "bf16") -> StepBundle:
    api = get_model(cfg)
    rules = make_rules(mesh, fsdp=False)   # serving: weights TP-only
    guard = make_guard(cfg, shape, policy, guard_enabled)

    @_named_step(PREFILL_STEP)
    def prefill_step(params, cache, batch):
        cache, logits = api.prefill(params, cache, batch, guard=guard,
                                    rules=rules)
        return cache, logits

    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache_shape = _cache_shape_for(api, shape, kv_dtype)
    batch_specs = api.batch_specs(shape)

    p_shard = _shard_tree(mesh, rules, api.param_logical_axes(),
                          params_shape)
    c_shard = _shard_tree(mesh, rules, cache_axes(mesh, cfg, cache_shape),
                          cache_shape)
    b_shard = _shard_tree(mesh, rules, _batch_axes(batch_specs),
                          batch_specs)
    logits_shard = _shard_tree(mesh, rules, ("batch", "vocab"),
                               jax.ShapeDtypeStruct(
                                   (shape.global_batch, cfg.vocab),
                                   jnp.float32))

    return StepBundle(
        fn=prefill_step,
        in_specs=(params_shape, cache_shape, batch_specs),
        in_shardings=(p_shard, c_shard, b_shard),
        out_shardings=(c_shard, logits_shard),
        donate_argnums=(1,),
        rules=rules,
        api=api,
    )


def build_decode_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh, *,
                      guard_enabled: bool = True,
                      policy: FencePolicy = FencePolicy.BITWISE,
                      kv_dtype: str = "bf16") -> StepBundle:
    api = get_model(cfg)
    rules = make_rules(mesh, fsdp=False)
    guard = make_guard(cfg, shape, policy, guard_enabled)

    @_named_step(DECODE_STEP)
    def decode_step(params, cache, tokens):
        cache, logits = api.decode(params, cache, tokens, guard=guard,
                                   rules=rules)
        return cache, logits

    params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
    cache_shape = _cache_shape_for(api, shape, kv_dtype)
    tokens_spec = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)

    p_shard = _shard_tree(mesh, rules, api.param_logical_axes(),
                          params_shape)
    c_shard = _shard_tree(mesh, rules, cache_axes(mesh, cfg, cache_shape),
                          cache_shape)
    t_shard = _shard_tree(mesh, rules, ("batch",), tokens_spec)
    logits_shard = _shard_tree(mesh, rules, ("batch", "vocab"),
                               jax.ShapeDtypeStruct(
                                   (shape.global_batch, cfg.vocab),
                                   jnp.float32))

    return StepBundle(
        fn=decode_step,
        in_specs=(params_shape, cache_shape, tokens_spec),
        in_shardings=(p_shard, c_shard, t_shard),
        out_shardings=(c_shard, logits_shard),
        donate_argnums=(1,),
        rules=rules,
        api=api,
    )


def build_step(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
               **kw) -> StepBundle:
    if shape.kind == "train":
        return build_train_step(cfg, shape, mesh, **kw)
    kw.pop("moe_dispatch", None)
    kw.pop("remat_policy", None)
    if shape.kind == "train":
        kw.pop("kv_dtype", None)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, shape, mesh, **kw)
    return build_decode_step(cfg, shape, mesh, **kw)
