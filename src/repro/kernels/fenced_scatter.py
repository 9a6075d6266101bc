"""Fenced page scatter — KV-cache page writes into the shared pool.

The *output* BlockSpec index_map applies the fence to the destination
page id, so the store DMA can only land inside the tenant's partition —
the st.global analogue of the paper's Listing 1.  The pool is aliased
in-place (input_output_aliases), as a real cache write must be.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _fence(idx, base, mask):
    return jax.lax.bitwise_or(jax.lax.bitwise_and(idx, mask), base)


def _pages_index_map(n, ids_ref, base_ref, mask_ref):
    return (n, 0, 0, 0)


def _pool_index_map(n, ids_ref, base_ref, mask_ref):
    return (_fence(ids_ref[n], base_ref[0], mask_ref[0]), 0, 0, 0)


def _kernel(ids_ref, base_ref, mask_ref, pages_ref, pool_in_ref, o_ref):
    o_ref[...] = pages_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def fenced_scatter(pool, pages, page_ids, fence_base, fence_mask, *,
                   interpret: bool):
    """pool (P,page,KH,D); pages (N,page,KH,D); page_ids (N,) -> pool'."""
    P, page, KH, D = pool.shape
    N = pages.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((1, page, KH, D), _pages_index_map),
            pl.BlockSpec((1, page, KH, D), _pool_index_map),
        ],
        out_specs=pl.BlockSpec((1, page, KH, D), _pool_index_map),
    )
    kernel = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},   # pool aliases the output
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    return kernel(page_ids.astype(jnp.int32),
                  jnp.asarray([fence_base], jnp.int32),
                  jnp.asarray([fence_mask], jnp.int32),
                  pages, pool)
