"""Fenced paged-attention decode kernel — the paper's PTX fence, TPU-native.

This is the closest TPU analogue of Guardian's sandboxed kernel: the
per-sequence page table is **scalar-prefetched into SMEM**, and the fence
``phys = (page_id & mask) | base`` is applied to the page id *inside the
BlockSpec index_map* — i.e. before the page id forms a DMA descriptor,
exactly where the paper patches the PTX register before ``ld.global``.
A corrupted or malicious page table therefore cannot steer the DMA engine
outside the tenant's partition of the shared page pool; like the paper's
bitwise mode, a bad id wraps around inside the tenant's own pages.

Layout (one grid step per (sequence, page)):

    q          (B, H, D)                 queries, one token per sequence
    k_pages    (P_total, page, KH, D)    shared global pool (all tenants)
    v_pages    (P_total, page, KH, D)
    page_table (B, max_pages) int32      logical -> physical (untrusted!)
    seq_lens   (B,) int32
    fence_base (B,) int32                per-row tenant partition base
    fence_mask (B,) int32                per-row tenant partition mask

    grid = (B, max_pages); pages sequentially accumulate online softmax
    in VMEM scratch (m, l, acc); the output row is written at the last
    page.  Cost: 2 integer lane-ops per page DMA (the paper's "two bitwise
    instructions per load").
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _fence(idx, base, mask):
    return jax.lax.bitwise_or(jax.lax.bitwise_and(idx, mask), base)


def _kv_index_map(b, p, page_table, seq_lens, base, mask):
    """BlockSpec index map for the page pool: the Guardian fence lands
    here, on the scalar-prefetched page id, before the DMA."""
    phys = _fence(page_table[b, p], base[b], mask[b])
    return (phys, 0, 0, 0)


def _kv_index_map_mapped(b, p, page_table, seq_lens, base, mask, page_map,
                         *, phys_mask):
    """Virtual-extent variant: the fence clamps the (untrusted) id into
    the tenant's *virtual* page extent, then the manager-owned page_map
    translates virtual -> physical — still inside the index map, before
    the DMA descriptor forms.  The translated id gets a second, static
    clamp to the physical pool (defense in depth: the map itself is a
    trusted operand, but a stale row costs a wrong-page read, never an
    OOB one)."""
    virt = _fence(page_table[b, p], base[b], mask[b])
    phys = jax.lax.bitwise_and(page_map[virt], phys_mask)
    return (phys, 0, 0, 0)


def _q_index_map(b, p, page_table, seq_lens, base, mask, *extra):
    return (b, 0, 0)


def _o_index_map(b, p, page_table, seq_lens, base, mask, *extra):
    return (b, 0, 0)


def _kernel(page_table, seq_lens, base, mask,   # scalar prefetch (SMEM)
            q_ref, k_ref, v_ref,                # VMEM blocks
            o_ref,                              # VMEM out
            m_ref, l_ref, acc_ref):             # VMEM scratch
    b = pl.program_id(0)
    p = pl.program_id(1)
    page = k_ref.shape[1]
    scale = 1.0 / (q_ref.shape[-1] ** 0.5)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (H, D)
    k = k_ref[0].astype(jnp.float32)                  # (page, KH, D)
    v = v_ref[0].astype(jnp.float32)
    H, D = q.shape
    KH = k.shape[1]
    G = H // KH
    qg = q.reshape(KH, G, D)
    s = jnp.einsum("kgd,pkd->kgp", qg, k)             # (KH, G, page)

    # mask positions beyond the sequence length
    pos = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    valid = pos < seq_lens[b]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                               # (KH, G)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new[..., None])              # (KH, G, page)
    l_new = l_ref[...] * alpha + jnp.sum(pexp, axis=-1)
    pv = jnp.einsum("kgp,pkd->kgd", pexp, v)          # (KH, G, D)
    acc_ref[...] = acc_ref[...] * alpha[..., None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o = acc_ref[...] / l[..., None]               # (KH, G, D)
        o_ref[0] = o.reshape(H, D).astype(o_ref.dtype)


def _kernel_mapped(page_table, seq_lens, base, mask, page_map,
                   q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    """Same body — page_map only steers the BlockSpec index maps."""
    _kernel(page_table, seq_lens, base, mask,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fenced_paged_attention(q, k_pages, v_pages, page_table, seq_lens,
                           fence_base, fence_mask, page_map=None, *,
                           interpret: bool):
    """q (B,H,D); pools (P,page,KH,D); returns (B,H,D).

    ``page_map`` (n_virt,) int32, optional: page_table then holds
    *virtual* ids — fenced into the tenant's virtual extent, translated
    through the manager-owned map, and statically clamped to the pool
    (which must be pow2-sized) inside the index map.  This is the
    serve-path layout behind elastic zero-copy compaction."""
    B, H, D = q.shape
    P_total, page, KH, D2 = k_pages.shape
    max_pages = page_table.shape[1]

    if page_map is not None:
        if P_total & (P_total - 1):
            raise ValueError(
                f"page_map translation needs a pow2 physical pool, "
                f"got P_total={P_total}")
        num_scalar = 5
        kernel_fn = _kernel_mapped
        kv_map = functools.partial(_kv_index_map_mapped,
                                   phys_mask=P_total - 1)
    else:
        num_scalar = 4
        kernel_fn = _kernel
        kv_map = _kv_index_map

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, H, D), _q_index_map),
            pl.BlockSpec((1, page, KH, D), kv_map),
            pl.BlockSpec((1, page, KH, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, D), _o_index_map),
        scratch_shapes=[
            pltpu.VMEM((KH, H // KH), jnp.float32),       # m
            pltpu.VMEM((KH, H // KH), jnp.float32),       # l
            pltpu.VMEM((KH, H // KH, D), jnp.float32),    # acc
        ],
    )

    kernel = pl.pallas_call(
        kernel_fn,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    scalars = [page_table.astype(jnp.int32),
               seq_lens.astype(jnp.int32),
               fence_base.astype(jnp.int32),
               fence_mask.astype(jnp.int32)]
    if page_map is not None:
        scalars.append(page_map.astype(jnp.int32))
    return kernel(*scalars, q, k_pages, v_pages)
