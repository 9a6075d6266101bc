"""Fenced MoE expert histogram — the dispatch-side counter.

Routing produces data-dependent expert ids; before they become offsets
into per-expert buffers, Guardian fences them into the tenant's expert
partition.  This kernel computes per-expert token counts (the quantity
every capacity-based dispatcher needs) with the fence applied in-kernel
on the VMEM-resident id block — 2 lane-ops per id.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(base_ref, mask_ref, ids_ref, o_ref, *, num_experts, blk):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    ids = ids_ref[...].reshape(-1)                       # (blk*K,)
    fenced = jax.lax.bitwise_or(
        jax.lax.bitwise_and(ids, mask_ref[0]), base_ref[0])
    onehot = (fenced[:, None] ==
              jax.lax.broadcasted_iota(jnp.int32,
                                       (ids.shape[0], num_experts), 1))
    o_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=0)[None]


@functools.partial(jax.jit, static_argnames=("num_experts", "interpret"))
def moe_histogram(expert_ids, num_experts, fence_base, fence_mask, *,
                  interpret: bool):
    """expert_ids (T, K) int32 -> counts (num_experts,)."""
    T, K = expert_ids.shape
    blk = min(T, 256)
    pad = (-T) % blk
    if pad:
        # pad with an id that fences to `fence_base`; subtract later
        expert_ids = jnp.pad(expert_ids, ((0, pad), (0, 0)),
                             constant_values=fence_base)
    nt = (T + pad) // blk
    kernel = pl.pallas_call(
        functools.partial(_kernel, num_experts=num_experts, blk=blk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt,),
            in_specs=[pl.BlockSpec((blk, K), lambda t, b, m: (t, 0))],
            out_specs=pl.BlockSpec((1, num_experts), lambda t, b, m: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, num_experts), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )
    counts = kernel(jnp.asarray([fence_base], jnp.int32),
                    jnp.asarray([fence_mask], jnp.int32),
                    expert_ids.astype(jnp.int32))[0]
    if pad:
        counts = counts.at[fence_base].add(-pad * K)
    return counts
