"""Blocked causal flash attention (forward) — train/prefill hot path.

Standard TPU flash tiling: grid (B, H, nq, nk); (q_blk x kv_blk) score
tiles live in VMEM/VREGs only (never HBM — the memory-term win the
roofline analysis attributes to this kernel), with online-softmax scratch
carried across the kv dimension.  Block shapes default to MXU-aligned
(128 x 128).  GQA: KV blocks are indexed by head-group.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, scale, q_blk, kv_blk, causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = (not causal) or (ki * kv_blk <= qi * q_blk + q_blk - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32) * scale    # (q_blk, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (kv_blk, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = q @ k.T                                    # (q_blk, kv_blk)
        if causal:
            q_pos = qi * q_blk + jax.lax.broadcasted_iota(
                jnp.int32, (q_blk, kv_blk), 0)
            kv_pos = ki * kv_blk + jax.lax.broadcasted_iota(
                jnp.int32, (q_blk, kv_blk), 1)
            s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "q_blk", "kv_blk",
                                    "interpret"))
def flash_attention(q, k, v, *, causal=True, q_blk=128, kv_blk=128,
                    interpret: bool):
    """q (B,S,H,D); k/v (B,S,KH,D) -> (B,S,H,D)."""
    B, S, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    q_blk = min(q_blk, S)
    kv_blk = min(kv_blk, S)
    assert S % q_blk == 0 and S % kv_blk == 0, (S, q_blk, kv_blk)
    nq, nk = S // q_blk, S // kv_blk
    scale = 1.0 / (D ** 0.5)

    # (B, H, S, D) layout for blocking; kv indexed by head group
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)

    kernel = pl.pallas_call(
        functools.partial(_kernel, scale=scale, q_blk=q_blk,
                          kv_blk=kv_blk, causal=causal),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_blk, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, kv_blk, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((1, 1, kv_blk, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_blk, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk,), jnp.float32),
            pltpu.VMEM((q_blk, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    out = kernel(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)
