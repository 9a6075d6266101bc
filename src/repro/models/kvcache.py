"""KV / state caches for serving — slab-paged pools with Guardian fencing.

Two levels of pooling (see DESIGN.md §Hardware adaptation):

1. **Slab-paged pool** (this module, used by the sharded serve steps):
   the pool is ``(L, slots, pages_per_slot, page, KH, D)``; a *slot* is a
   pow2-partitionable sequence slot (tenants own contiguous pow2 slot
   ranges), and pages within a slot's slab are indirected through a
   per-slot page table.  Two data-dependent index spaces → two fences:

       slot ids  — fenced with the tenant's (base, mask)  [space "kv"]
       page ids  — fenced into the slab [0, pages_per_slot) [space "page"]

   Both batch and slot axes shard over the data axes, so every gather is
   shard-local under GSPMD (no cross-host page traffic).

2. **Global paged pool** (``k.ndim == 5``: ``(L, P_total, page, KH, D)``,
   the continuous-batching serve layout + the Pallas kernel
   `kernels/paged_attention`): one flat page pool shared by every tenant,
   with per-request page lists in *virtual* page ids.  Virtual ids are
   fenced into the owning tenant's extent (space "kv", per-row params),
   then translated virt->phys through the manager-owned
   ``GuardSpec.page_map`` and clamped into the pool (space "page") — see
   :func:`repro.models.guard.fence_pages`.  Elastic compaction rewrites
   the map instead of moving KV bytes.  The same indirection is fenced in
   the Pallas kernel's scalar-prefetch on TPU — the closest analogue of
   the paper's PTX fence.

SSM/recurrent state uses the same slot discipline: ``(L, slots, ...state)``
with fenced slot ids (space "state").

Every serve cache type names its fields once, as class attributes:
``POOL`` are the tensors indexed by slot (or, in the global layout, by
page) on axis 1, which the manager owns as one pool arena; ``META`` are
the per-batch-row fields (slot ids, seq lens, page tables) that travel as
an engine's step operand; any other field is a cache of its own.  The
walks at the end of this module (split and join, slot count, slot ids,
seq-len reset, scrub, pages per slot) read only those declarations.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.guard import GuardSpec, fence, fence_pages

PAGE_SIZE = 64


def _pow2_at_least(n: int) -> int:
    return 1 << max(int(n - 1).bit_length(), 0) if n > 1 else 1


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """Paged KV pool (pytree).

    Two layouts, told apart by rank:

    * slab (6-dim): k/v ``(L, slots, P, page, KH, D)`` — page_table holds
      slab-relative logical->physical page ids, slot_ids pick the slab;
    * global (5-dim): k/v ``(L, P_total, page, KH, D)`` — page_table holds
      *virtual* page ids into the shared pool (slot_ids are unused and
      kept only for pytree-shape compatibility).
    """

    POOL: ClassVar[Tuple[str, ...]] = ("k", "v")
    META: ClassVar[Tuple[str, ...]] = ("page_table", "slot_ids", "seq_lens")

    k: jax.Array
    v: jax.Array
    page_table: jax.Array     # (B, P) int32: logical page -> physical page
    slot_ids: jax.Array       # (B,) int32: request -> pool slot
    seq_lens: jax.Array       # (B,) int32: tokens currently cached

    @property
    def global_paged(self) -> bool:
        return self.k.ndim == 5

    @property
    def pages_per_slot(self) -> int:
        return self.k.shape[1] if self.global_paged else self.k.shape[2]

    @property
    def page_size(self) -> int:
        return self.k.shape[2] if self.global_paged else self.k.shape[3]

    @property
    def max_len(self) -> int:
        return self.page_table.shape[1] * self.page_size


def kv_cache_spec(cfg: ModelConfig, batch: int, max_len: int,
                  *, slots: Optional[int] = None, page_size: int = PAGE_SIZE,
                  dtype=jnp.bfloat16, n_layers: Optional[int] = None
                  ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Abstract shapes for the cache (dry-run / eval_shape safe)."""
    L = n_layers if n_layers is not None else cfg.decoder_layers
    slots = slots or _pow2_at_least(batch)
    pages = max(max_len // page_size, 1)
    kv_shape = (L, slots, pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(kv_shape, dtype),
        "v": jax.ShapeDtypeStruct(kv_shape, dtype),
        "page_table": jax.ShapeDtypeStruct((batch, pages), jnp.int32),
        "slot_ids": jax.ShapeDtypeStruct((batch,), jnp.int32),
        "seq_lens": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


def kv_cache_axes() -> Dict[str, Tuple]:
    """Logical sharding axes matching kv_cache_spec order."""
    kv = (None, "pages", None, None, "kv_heads", None)
    return {"k": kv, "v": kv, "page_table": ("batch", None),
            "slot_ids": ("batch",), "seq_lens": ("batch",)}


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                  slots: Optional[int] = None, page_size: int = PAGE_SIZE,
                  dtype=jnp.bfloat16, n_layers: Optional[int] = None
                  ) -> PagedKVCache:
    spec = kv_cache_spec(cfg, batch, max_len, slots=slots,
                         page_size=page_size, dtype=dtype, n_layers=n_layers)
    pages = spec["page_table"].shape[1]
    return PagedKVCache(
        k=jnp.zeros(spec["k"].shape, dtype),
        v=jnp.zeros(spec["v"].shape, dtype),
        # identity mapping by default (fresh slabs)
        page_table=jnp.broadcast_to(
            jnp.arange(pages, dtype=jnp.int32)[None, :], (batch, pages)
        ).copy(),
        slot_ids=jnp.arange(batch, dtype=jnp.int32),
        seq_lens=jnp.zeros((batch,), jnp.int32),
    )


def global_pool_key(cfg: ModelConfig, total_pages: int, dtype) -> str:
    """The global paged pool's name: its KV geometry and dtype, and no
    model identity, so engines serving different models whose geometry
    and dtype are equal share one pool (their steps keep names of their
    own)."""
    return (f"paged:L{cfg.decoder_layers}:kh{cfg.n_kv_heads}:"
            f"d{cfg.head_dim}:pg{PAGE_SIZE}:P{total_pages}:"
            f"{jnp.dtype(dtype).name}")


def init_global_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                         total_pages: int, *,
                         page_size: int = PAGE_SIZE,
                         dtype=None,
                         n_layers: Optional[int] = None) -> PagedKVCache:
    """Global paged pool: one ``(L, total_pages, page, KH, D)`` tensor
    shared by every tenant; each batch row carries ``max_len //
    page_size`` virtual page ids (see module docstring, layout 2).  The
    pool holds ``dtype``, by default the model's: K and V are computed in
    it, so a wider pool stores nothing more."""
    L = n_layers if n_layers is not None else cfg.decoder_layers
    dtype = jnp.dtype(cfg.dtype if dtype is None else dtype)
    pages_per_req = max(max_len // page_size, 1)
    shape = (L, total_pages, page_size, cfg.n_kv_heads, cfg.head_dim)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        page_table=jnp.zeros((batch, pages_per_req), jnp.int32),
        slot_ids=jnp.zeros((batch,), jnp.int32),
        seq_lens=jnp.zeros((batch,), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Fenced read / write paths
# ---------------------------------------------------------------------------

def _fenced_phys_pages(cache: PagedKVCache, table: jax.Array,
                       guard: Optional[GuardSpec]) -> jax.Array:
    """Global layout: virtual page ids -> fenced physical page ids.

    The virtual ids are fenced into the owning tenant's extent (space
    "kv" — per-row params on the serve path), translated through the
    manager's page_map, then clamped into the pool (space "page")."""
    virt = fence(guard, "kv", table)
    return fence_pages(guard, virt)


def _take_pages(cache: PagedKVCache, layer: jax.Array,
                phys: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Global layout: the pages ``phys`` (B, n) of one layer of K and V,
    each (B, n, page, KH, D)."""
    # one gather at (layer, page) over the (L * P_total) page rows — a
    # bitcast of the pool — so no op materialises a layer slice: only the
    # pages the rows address are read.  The clip keeps an unfenced id
    # inside its layer (fenced ids are in range already), so the take
    # needs no out-of-range fill.
    L, P_total = cache.k.shape[:2]
    rows = (jnp.asarray(layer, jnp.int32) * P_total
            + jnp.clip(phys, 0, P_total - 1))
    k_p = jnp.take(cache.k.reshape(L * P_total, *cache.k.shape[2:]),
                   rows, axis=0, mode="clip")
    v_p = jnp.take(cache.v.reshape(L * P_total, *cache.v.shape[2:]),
                   rows, axis=0, mode="clip")
    return k_p, v_p


def _histories(k_p: jax.Array, v_p: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """(B, n, page, KH, D) pages -> (B, n * page, KH, D) histories."""
    B, P, page, KH, D = k_p.shape
    return (k_p.reshape(B, P * page, KH, D),
            v_p.reshape(B, P * page, KH, D))


def window_pages(cache: PagedKVCache, window: int) -> int:
    """Pages that hold any ``window`` consecutive positions, at most the
    row's page table."""
    return min(-(-window // cache.page_size) + 1, cache.page_table.shape[1])


def gather_window_kv(cache: PagedKVCache, layer: jax.Array,
                     kv_len: jax.Array, window: int,
                     guard: Optional[GuardSpec] = None
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Global layout: each row's history of one layer, read only from the
    ``window_pages`` pages that hold its last ``window`` of ``kv_len``
    (B,) positions (``window`` 0: every page of its table).  Returns k, v
    (B, n * page, KH, D) and the position of their first key (B,)."""
    P = cache.page_table.shape[1]
    n = window_pages(cache, window) if window else P
    if n == P:
        first = jnp.zeros_like(kv_len)
        table = cache.page_table
    else:
        first = jnp.clip((kv_len - window) // cache.page_size, 0, P - n)
        logical = first[:, None] + jnp.arange(n, dtype=jnp.int32)[None, :]
        table = jnp.take_along_axis(cache.page_table, logical, axis=1)
    pages = _take_pages(cache, layer, _fenced_phys_pages(cache, table, guard))
    # the barrier keeps the attention's preferred layout of the history
    # from reaching back through the gather: at a pool whose page tile is
    # (KH, D), as Mellum2's (4, 128), the TPU compiler otherwise relayouts
    # the whole pool for every gather, not the pages gathered
    k, v = _histories(*jax.lax.optimization_barrier(pages))
    return k, v, first * cache.page_size


def gather_layer_kv(cache: PagedKVCache, layer: jax.Array,
                    guard: Optional[GuardSpec] = None,
                    rules=None) -> Tuple[jax.Array, jax.Array]:
    """Read the full (paged) KV history for every request at one layer.

    Returns k, v: (B, S_max, KH, D) where S_max = pages*page.  Invalid tail
    positions are masked by the caller via ``seq_lens``.
    """
    from repro.distributed.sharding import constrain
    if cache.global_paged:
        phys = _fenced_phys_pages(cache, cache.page_table, guard)  # (B,P)
        return _histories(*_take_pages(cache, layer, phys))
    slots = fence(guard, "kv", cache.slot_ids)            # (B,)
    pages = fence(guard, "page", cache.page_table)        # (B,P)
    k_l = jax.lax.dynamic_index_in_dim(cache.k, layer, axis=0,
                                       keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cache.v, layer, axis=0,
                                       keepdims=False)
    # slot gather: (B, P, page, KH, D).  NOTE (§Perf H3 iter2, refuted):
    # pinning this gather's output to batch sharding does NOT stop the
    # partitioner from replicating the pool slice (the replication happens
    # inside the gather lowering) and costs an extra copy — measured 20-25%
    # regression on decode cells, so no constraint here.  The real fix is
    # shard-local pools (documented in EXPERIMENTS.md §Perf H3).
    k_s = jnp.take(k_l, slots, axis=0)
    v_s = jnp.take(v_l, slots, axis=0)
    # page indirection within each request's slab
    k_p = jnp.take_along_axis(
        k_s, pages[:, :, None, None, None], axis=1)
    v_p = jnp.take_along_axis(
        v_s, pages[:, :, None, None, None], axis=1)
    B, P, page, KH, D = k_p.shape
    return (k_p.reshape(B, P * page, KH, D),
            v_p.reshape(B, P * page, KH, D))


def append_token_kv(cache: PagedKVCache, layer: jax.Array,
                    k_new: jax.Array, v_new: jax.Array,
                    guard: Optional[GuardSpec] = None) -> PagedKVCache:
    """Write one new token's K,V per request at ``layer`` (decode step).

    k_new/v_new: (B, 1, KH, D).  The write position is data-dependent
    (seq_lens) — slot, page and in-page offsets are all fenced.
    """
    B = k_new.shape[0]
    page_size = cache.page_size
    pos = cache.seq_lens                                   # (B,)
    logical_page = pos // page_size
    offset = pos % page_size
    if cache.global_paged:
        virt = jnp.take_along_axis(cache.page_table,
                                   logical_page[:, None], axis=1)[:, 0]
        phys = _fenced_phys_pages(cache, virt, guard)      # (B,)
        idx_l = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
        k = cache.k.at[idx_l, phys, offset].set(
            k_new[:, 0], mode="promise_in_bounds")
        v = cache.v.at[idx_l, phys, offset].set(
            v_new[:, 0], mode="promise_in_bounds")
        return dataclasses.replace(cache, k=k, v=v)
    slots = fence(guard, "kv", cache.slot_ids)
    phys = jnp.take_along_axis(cache.page_table,
                               logical_page[:, None], axis=1)[:, 0]
    phys = fence(guard, "page", phys)
    idx_l = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
    scat = jnp.stack([idx_l, slots, phys, offset], axis=1)  # (B,4)
    k = cache.k.at[scat[:, 0], scat[:, 1], scat[:, 2], scat[:, 3]].set(
        k_new[:, 0], mode="promise_in_bounds")
    v = cache.v.at[scat[:, 0], scat[:, 1], scat[:, 2], scat[:, 3]].set(
        v_new[:, 0], mode="promise_in_bounds")
    return dataclasses.replace(cache, k=k, v=v)


def write_prefill_kv(cache: PagedKVCache, layer: jax.Array,
                     k_new: jax.Array, v_new: jax.Array,
                     guard: Optional[GuardSpec] = None,
                     mode: str = "permute") -> PagedKVCache:
    """Write a full prefill's K,V (B, S, KH, D) into the slabs at ``layer``.

    S is padded to a page multiple.  Pages go through the (fenced) page
    table; slots through the (fenced) slot ids.

    Two formulations (§Perf hillclimb H2):

    * ``scatter``  — direct 4D scatter ``pool[l, slot, phys, off] = kv``.
      Semantically obvious, but the layer-indexed scatter is opaque to the
      SPMD partitioner: it replicates the full (slots, P, page, KH, D)
      pool slice per device (observed: 21.5 GB f32 all-gathers per layer).
    * ``permute`` — collective-free: (1) tiny int32 scatter builds the
      inverse page permutation per slab, (2) a batch-aligned
      take_along_axis materializes each request's slab (local), (3) a
      one-hot einsum places slabs into slot rows (SPMD-friendly
      contraction), (4) dynamic_update_slice writes the layer slice
      (unsharded dim — local).  Fences are applied to the same indices,
      so the isolation guarantee is unchanged.
    """
    B, S, KH, D = k_new.shape
    page_size = cache.page_size
    pad = (-S) % page_size
    if pad:
        k_new = jnp.pad(k_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_new = jnp.pad(v_new, ((0, 0), (0, pad), (0, 0), (0, 0)))
        S += pad
    n_pages = S // page_size
    if cache.global_paged:
        phys = _fenced_phys_pages(
            cache, cache.page_table[:, :n_pages], guard)          # (B,n)
        k_pg = k_new.reshape(B, n_pages, page_size, KH, D)
        v_pg = v_new.reshape(B, n_pages, page_size, KH, D)
        ll = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B, n_pages))
        k = cache.k.at[ll, phys].set(
            k_pg.astype(cache.k.dtype), mode="promise_in_bounds")
        v = cache.v.at[ll, phys].set(
            v_pg.astype(cache.v.dtype), mode="promise_in_bounds")
        return dataclasses.replace(cache, k=k, v=v)
    slots = fence(guard, "kv", cache.slot_ids)                    # (B,)
    pages = fence(guard, "page", cache.page_table[:, :n_pages])   # (B,n)
    k_pg = k_new.reshape(B, n_pages, page_size, KH, D)
    v_pg = v_new.reshape(B, n_pages, page_size, KH, D)

    if mode == "scatter":
        bb = jnp.broadcast_to(slots[:, None], (B, n_pages))
        ll = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B, n_pages))
        k = cache.k.at[ll, bb, pages].set(k_pg, mode="promise_in_bounds")
        v = cache.v.at[ll, bb, pages].set(v_pg, mode="promise_in_bounds")
        return dataclasses.replace(cache, k=k, v=v)

    P_slab = cache.pages_per_slot
    S_slots = cache.k.shape[1]
    # (1) inverse page permutation + write mask — tiny int32 scatters
    bidx = jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[:, None], (B, n_pages))
    logical = jnp.broadcast_to(
        jnp.arange(n_pages, dtype=jnp.int32)[None, :], (B, n_pages))
    inv = jnp.zeros((B, P_slab), jnp.int32).at[bidx, pages].set(
        logical, mode="drop")
    wrote = jnp.zeros((B, P_slab), bool).at[bidx, pages].set(
        True, mode="drop")

    def place(pool, new_pg):
        # (2) per-request slab via batch-aligned gather (local)
        slab_new = jnp.take_along_axis(
            new_pg, inv[:, :, None, None, None], axis=1)
        # keep old contents where this prefill wrote nothing
        old = jnp.take(jax.lax.dynamic_index_in_dim(
            pool, layer, axis=0, keepdims=False), slots, axis=0)
        slab = jnp.where(wrote[:, :, None, None, None],
                         slab_new.astype(pool.dtype), old)
        # (3) slot placement as a one-hot contraction (SPMD-friendly)
        oh = jax.nn.one_hot(slots, S_slots, dtype=pool.dtype)    # (B,S_sl)
        # rows not owned by any request keep their old value
        owned = jnp.einsum("bs,b...->s...", oh, jnp.ones(
            (B, 1, 1, 1, 1), pool.dtype))                        # (S_sl,1..)
        placed = jnp.einsum("bs,bpqkd->spqkd", oh, slab)
        pool_l = jax.lax.dynamic_index_in_dim(pool, layer, axis=0,
                                              keepdims=False)
        new_l = jnp.where(owned > 0, placed, pool_l)
        # (4) layer write on the unsharded axis (local)
        return jax.lax.dynamic_update_slice_in_dim(
            pool, new_l[None], layer, axis=0)

    k = place(cache.k, k_pg)
    v = place(cache.v, v_pg)
    return dataclasses.replace(cache, k=k, v=v)


def advance(cache: PagedKVCache, n: int = 1) -> PagedKVCache:
    return dataclasses.replace(cache, seq_lens=cache.seq_lens + n)


# ---------------------------------------------------------------------------
# SSM / recurrent state pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class StateCache:
    """Recurrent state pool (pytree).

    ``pools`` maps state name -> (L_kind, slots, *state_shape) arrays;
    slot ids are fenced with the tenant's partition (space "state").
    """

    POOL: ClassVar[Tuple[str, ...]] = ("pools",)
    META: ClassVar[Tuple[str, ...]] = ("slot_ids", "seq_lens")

    pools: Dict[str, jax.Array]
    slot_ids: jax.Array        # (B,)
    seq_lens: jax.Array        # (B,)

    def read(self, name: str, layer: jax.Array,
             guard: Optional[GuardSpec] = None) -> jax.Array:
        slots = fence(guard, "state", self.slot_ids)
        pool_l = jax.lax.dynamic_index_in_dim(
            self.pools[name], layer, axis=0, keepdims=False)
        return jnp.take(pool_l, slots, axis=0)

    def write(self, name: str, layer: jax.Array, value: jax.Array,
              guard: Optional[GuardSpec] = None) -> "StateCache":
        slots = fence(guard, "state", self.slot_ids)
        B = value.shape[0]
        ll = jnp.broadcast_to(jnp.asarray(layer, jnp.int32), (B,))
        pools = dict(self.pools)
        pools[name] = pools[name].at[ll, slots].set(
            value, mode="promise_in_bounds")
        return dataclasses.replace(self, pools=pools)


# ---------------------------------------------------------------------------
# Walks over the declared fields (module docstring): pool and meta
# ---------------------------------------------------------------------------

def _parts(cache) -> Tuple[str, ...]:
    """The fields of ``cache`` that are caches of their own."""
    own = cache.POOL + cache.META
    return tuple(f.name for f in dataclasses.fields(cache)
                 if f.name not in own)


def _walk(cache) -> Iterator[Any]:
    yield cache
    for name in _parts(cache):
        yield from _walk(getattr(cache, name))


def split_pool(cache) -> Tuple[Dict[str, Any], Any]:
    """Split a serve cache into ``(pool, meta)``: ``pool`` is a dict of the
    ``POOL`` fields (nested by part), ``meta`` the same cache with them set
    to None.  The pool lives as a manager-owned arena, so N engines address
    one slot space; the meta stays an engine's step operand."""
    pool: Dict[str, Any] = {}
    meta: Dict[str, Any] = {}
    for name in _parts(cache):
        pool[name], meta[name] = split_pool(getattr(cache, name))
    for name in cache.POOL:
        pool[name], meta[name] = getattr(cache, name), None
    return pool, dataclasses.replace(cache, **meta)


def join_pool(pool: Dict[str, Any], meta) -> Any:
    """The cache that :func:`split_pool` split into ``pool`` and ``meta``."""
    full = {name: join_pool(pool[name], getattr(meta, name))
            for name in _parts(meta)}
    full.update((name, pool[name]) for name in meta.POOL)
    return dataclasses.replace(meta, **full)


def _map_meta(cache, name: str, fn):
    """``cache`` with ``fn`` applied to the meta field ``name`` of every
    part that has one."""
    new = {part: _map_meta(getattr(cache, part), name, fn)
           for part in _parts(cache)}
    if name in cache.META:
        new[name] = fn(getattr(cache, name))
    return dataclasses.replace(cache, **new)


def with_slot_ids(meta, slot_ids: jax.Array):
    """``meta`` with every part's rows on the slots ``slot_ids`` (B,)."""
    return _map_meta(meta, "slot_ids", lambda _: slot_ids)


def reset_seq_lens(meta):
    """``meta`` with every part's sequences empty."""
    return _map_meta(meta, "seq_lens", jnp.zeros_like)


def pool_slots(cache) -> int:
    """The pool's extent on axis 1: slots, or the global layout's pages."""
    return jax.tree.leaves(split_pool(cache)[0])[0].shape[1]


def pages_per_slot(cache) -> int:
    """The page ids one slot may address: its slab's pages, every page of
    the global pool, or 1 where no part holds KV pages."""
    return max((c.pages_per_slot for c in _walk(cache)
                if isinstance(c, PagedKVCache)), default=1)


def scrub(cache, ids) -> Any:
    """Zero the slots (or, in the global layout, the physical pages)
    ``ids`` of every pool tensor: reclaimed slots hand over empty."""
    if not ids:
        return cache
    idx = jnp.asarray(tuple(ids), jnp.int32)
    pool, meta = split_pool(cache)
    return join_pool(jax.tree.map(
        lambda a: a.at[:, idx].set(jnp.zeros((), a.dtype)), pool), meta)
