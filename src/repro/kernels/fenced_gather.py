"""Fenced row gather — embedding lookups from a shared vocab arena.

Grid = one step per index block; the indices are scalar-prefetched and the
fence is applied in the *input* BlockSpec index_map, so each (1, D) row
DMA is bounded to the tenant's partition before it is issued.  2 integer
ops per row — the paper's Listing-1 cost model, applied to a gather.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _fence(idx, base, mask):
    return jax.lax.bitwise_or(jax.lax.bitwise_and(idx, mask), base)


def _table_index_map(i, idx_ref, base_ref, mask_ref):
    return (_fence(idx_ref[i], base_ref[0], mask_ref[0]), 0)


def _out_index_map(i, idx_ref, base_ref, mask_ref):
    return (i, 0)


def _kernel(idx_ref, base_ref, mask_ref, row_ref, o_ref):
    o_ref[...] = row_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fenced_gather(table, idx, fence_base, fence_mask, *, interpret: bool):
    """table (V, D); idx (N,) int32 -> (N, D) with fenced row ids."""
    V, D = table.shape
    N = idx.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, D), _table_index_map)],
        out_specs=pl.BlockSpec((1, D), _out_index_map),
    )
    kernel = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, D), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    return kernel(idx.astype(jnp.int32),
                  jnp.asarray([fence_base], jnp.int32),
                  jnp.asarray([fence_mask], jnp.int32),
                  table)
