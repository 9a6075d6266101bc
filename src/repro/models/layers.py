"""Shared model building blocks — pure JAX, pytree params, shardable.

Conventions:
* params are nested dicts of jnp arrays; a parallel tree of *logical axis
  tuples* (see ``repro.distributed.sharding``) drives pjit placement.
* activations: (batch, seq, d_model); attention internals
  (batch, seq, heads, head_dim).
* every data-dependent index op takes the optional ``guard`` spec
  (``repro.models.guard``) so Guardian fencing is a first-class switch.
* attention is **chunked/online-softmax** (flash-style) so 32k prefill
  never materializes an (S, S) score matrix.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import FULL, ModelConfig
from repro.core.fence import guarded_take
from repro.models.guard import GuardSpec, fence

Params = Dict[str, Any]


def dtype_of(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
            ).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)
            ).astype(dt)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array,
              eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(dt)


def norm_init(cfg: ModelConfig, d: Optional[int] = None) -> Params:
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def norm_axes(cfg: ModelConfig) -> Params:
    if cfg.norm == "layernorm":
        return {"scale": (None,), "bias": (None,)}
    return {"scale": (None,)}


def apply_norm(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"])
    return rmsnorm(x, p["scale"])


# ---------------------------------------------------------------------------
# Rotary position embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def yarn_freqs(head_dim: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies (``transformers``' ``yarn`` rope type,
    correction range truncated to whole dimensions): each frequency is
    blended from its interpolated (``/ factor``) and extrapolated value
    over a linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times within the original context."""
    def dim_of(rotations):
        return head_dim * math.log(yarn.original_max_position_embeddings
                                   / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    extra = rope_freqs(head_dim, theta)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                 # share that extrapolates
    return extra / yarn.factor * (1.0 - keep) + extra * keep


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn=None) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32.  With ``yarn``, the YaRN
    frequencies, and cos and sin scaled by its attention factor."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta) if yarn is None else yarn_freqs(d, theta,
                                                               yarn)
    freqs = jnp.asarray(inv, jnp.float32)                       # (D/2,)
    ang = positions.astype(jnp.float32)[..., None] * freqs      # (B,S,D/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos = cos * yarn.attention_factor
        sin = sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions3: jax.Array, theta: float,
                sections: Tuple[int, int, int]) -> jax.Array:
    """M-RoPE (qwen2-vl): positions3 (B, S, 3) — temporal/height/width ids.

    The D/2 frequency slots are split into three sections; each section
    rotates by its own position component.
    """
    d = x.shape[-1]
    half = d // 2
    assert sum(sections) == half, (sections, half)
    freqs = jnp.asarray(rope_freqs(d, theta), jnp.float32)       # (half,)
    sec_id = np.concatenate([
        np.full((s,), i) for i, s in enumerate(sections)])       # (half,)
    pos = jnp.take_along_axis(
        positions3.astype(jnp.float32),
        jnp.broadcast_to(jnp.asarray(sec_id, jnp.int32)[None, None, :],
                         (*positions3.shape[:2], half)),
        axis=-1)                                                 # (B,S,half)
    ang = pos * freqs
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """qwen2-vl default split of the D/2 slots: 1/4 temporal, 3/8, 3/8."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    return (t, h, half - t - h)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — online softmax over KV blocks
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B, Sq, KH, G, D)  k: (B, Skv, KH, D) -> (B, KH, G, Sq, Skv)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _block_mask(qi, ki, q_chunk, kv_chunk, q_off, kv_valid,
                causal: bool, window: int, batched: bool):
    """Mask for one (q-block, kv-block) pair.

    batched=False (training: uniform offsets, full kv) -> (qc, kc) — keeps
    the mask batch-free so GSPMD never materializes a (B, S, S) predicate.
    batched=True  -> (B, qc, kc).
    """
    q_ids = jnp.arange(q_chunk, dtype=jnp.int32)
    kv_pos = ki * kv_chunk + jnp.arange(kv_chunk, dtype=jnp.int32)
    if not batched:
        q_pos = q_off + qi * q_chunk + q_ids                  # (qc,)
        mask = kv_pos[None, :] < kv_valid
        if causal:
            mask = mask & (kv_pos[None, :] <= q_pos[:, None])
        if window:
            mask = mask & (kv_pos[None, :] > q_pos[:, None] - window)
        return mask                                           # (qc,kc)
    q_pos = q_off[:, None] + qi * q_chunk + q_ids[None, :]     # (B,qc)
    mask = jnp.broadcast_to(kv_pos[None, None, :] < kv_valid[:, None, None],
                            (q_pos.shape[0], q_chunk, kv_chunk))
    if causal:
        mask = mask & (kv_pos[None, None, :] <= q_pos[:, :, None])
    if window:
        mask = mask & (kv_pos[None, None, :] > q_pos[:, :, None] - window)
    return mask                                               # (B,qc,kc)


def _apply_mask(s, mask):
    """s (B,KH,G,qc,kc); mask (qc,kc) or (B,qc,kc)."""
    if mask.ndim == 2:
        return jnp.where(mask[None, None, None], s, NEG_INF)
    return jnp.where(mask[:, None, None], s, NEG_INF)


def _c(x, spec):
    """Sharding constraint that no-ops outside a mesh context (CPU smoke
    tests) but pins loop-carry shardings in the dry-run/production path —
    GSPMD's propagation through while carries is weak, and an unpinned
    carry silently replicates the batch (16x flops/memory)."""
    if spec is None:
        return x
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError, TypeError):
        return x


def _flash_specs(static):
    """(acc, m/denom/lse, out-stack, lse-stack) PartitionSpecs."""
    return static[7] if len(static) > 7 and static[7] is not None else \
        (None, None, None, None)


def _flash_fwd_pass(static, q, k, v, q_off, kv_valid):
    (causal, window, q_chunk, kv_chunk, nq, nk, batched) = static[:7]
    spec_acc, spec_m, spec_outs, spec_lses = _flash_specs(static)
    B, _, qc, KH, G, D = q.shape
    scale = 1.0 / math.sqrt(D)

    def q_block(qi):
        q_blk = q[:, qi]

        def kv_block(carry, ki):
            acc, m, denom = carry
            k_blk, v_blk = k[:, ki], v[:, ki]
            s = _gqa_scores(q_blk, k_blk) * scale   # (B,KH,G,qc,kc)
            mask = _block_mask(qi, ki, q_chunk, kv_chunk, q_off, kv_valid,
                               causal, window, batched)
            s = _apply_mask(s, mask)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            denom = denom * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v_blk.dtype),
                            v_blk, preferred_element_type=jnp.float32)
            acc = acc * corr[..., None] + pv
            return (_c(acc, spec_acc), _c(m_new, spec_m),
                    _c(denom, spec_m)), None

        acc0 = _c(jnp.zeros((B, KH, G, qc, D), jnp.float32), spec_acc)
        m0 = _c(jnp.full((B, KH, G, qc), NEG_INF, jnp.float32), spec_m)
        d0 = _c(jnp.zeros((B, KH, G, qc), jnp.float32), spec_m)
        (acc, m, denom), _ = jax.lax.scan(
            kv_block, (acc0, m0, d0), jnp.arange(nk))
        denom = jnp.maximum(denom, 1e-30)
        out = acc / denom[..., None]
        lse = m + jnp.log(denom)                    # (B,KH,G,qc)
        return _c(out, spec_acc), _c(lse, spec_m)

    outs, lses = jax.lax.map(q_block, jnp.arange(nq))
    # outs (nq,B,KH,G,qc,D); lses (nq,B,KH,G,qc)
    return _c(outs, spec_outs), _c(lses, spec_lses)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(static, q, k, v, q_off, kv_valid):
    outs, _ = _flash_fwd_pass(static, q, k, v, q_off, kv_valid)
    return outs


def _flash_fwd(static, q, k, v, q_off, kv_valid):
    outs, lses = _flash_fwd_pass(static, q, k, v, q_off, kv_valid)
    return outs, (q, k, v, q_off, kv_valid, outs, lses)


def _flash_bwd(static, res, g):
    """FlashAttention backward: recompute per-block scores from (q,k,lse);
    never materializes more than one (qc,kc) block per (KH,G)."""
    (causal, window, q_chunk, kv_chunk, nq, nk, batched) = static[:7]
    spec_acc, spec_m, spec_outs, spec_lses = _flash_specs(static)
    q, k, v, q_off, kv_valid, outs, lses = res
    B, _, qc, KH, G, D = q.shape
    scale = 1.0 / math.sqrt(D)
    # delta = rowsum(dO * O): (nq,B,KH,G,qc)
    delta = _c(jnp.sum(g.astype(jnp.float32) * outs, axis=-1), spec_lses)

    def q_block(carry, qi):
        dk_acc, dv_acc = carry
        q_blk = q[:, qi].astype(jnp.float32)        # (B,qc,KH,G,D)
        g_blk = g[qi].astype(jnp.float32)           # (B,KH,G,qc,D)
        lse_blk = lses[qi]                          # (B,KH,G,qc)
        delta_blk = delta[qi]

        def kv_block(dq_carry, ki):
            dq_blk, dk_acc, dv_acc = dq_carry
            k_blk = k[:, ki].astype(jnp.float32)    # (B,kc,KH,D)
            v_blk = v[:, ki].astype(jnp.float32)
            s = _gqa_scores(q_blk, k_blk) * scale   # (B,KH,G,qc,kc)
            mask = _block_mask(qi, ki, q_chunk, kv_chunk, q_off, kv_valid,
                               causal, window, batched)
            s = _apply_mask(s, mask)
            p = jnp.exp(s - lse_blk[..., None])     # (B,KH,G,qc,kc)
            # dv += p^T g
            dv = jnp.einsum("bkgqs,bkgqd->bskd", p, g_blk)
            # dp = g v^T
            dp = jnp.einsum("bkgqd,bskd->bkgqs", g_blk, v_blk)
            ds = p * (dp - delta_blk[..., None]) * scale
            dq = jnp.einsum("bkgqs,bskd->bqkgd", ds, k_blk)
            dk = jnp.einsum("bkgqs,bqkgd->bskd", ds, q_blk)
            dk_acc = dk_acc.at[:, ki].add(dk)
            dv_acc = dv_acc.at[:, ki].add(dv)
            return (dq_blk + dq, dk_acc, dv_acc), None

        dq0 = jnp.zeros((B, qc, KH, G, D), jnp.float32)
        (dq_blk, dk_acc, dv_acc), _ = jax.lax.scan(
            kv_block, (dq0, dk_acc, dv_acc), jnp.arange(nk))
        return (dk_acc, dv_acc), dq_blk

    kv_spec = None
    if spec_acc is not None:
        try:
            from jax.sharding import PartitionSpec as _P
            ps = list(spec_acc) + [None] * (6 - len(spec_acc))
            kv_spec = _P(ps[0], None, None, ps[1], None)   # (B,nk,kc,KH,D)
        except TypeError:
            kv_spec = None
    dk0 = _c(jnp.zeros(k.shape, jnp.float32), kv_spec)
    dv0 = _c(jnp.zeros(v.shape, jnp.float32), kv_spec)
    (dk, dv), dqs = jax.lax.scan(q_block, (dk0, dv0), jnp.arange(nq))
    dq = jnp.moveaxis(dqs, 0, 1)                   # (B,nq,qc,KH,G,D)
    zero_off = np.zeros(jnp.shape(q_off), jax.dtypes.float0)
    zero_len = np.zeros(jnp.shape(kv_valid), jax.dtypes.float0)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_off, zero_len)


_flash.defvjp(_flash_fwd, _flash_bwd)


def chunked_attention(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Skv, KH, D)
    v: jax.Array,                  # (B, Skv, KH, D)
    *,
    causal: bool = True,
    q_offset: Any = 0,             # absolute position of q[0] (int or (B,))
    window: int = 0,               # 0 = full; else sliding window
    kv_len: Optional[jax.Array] = None,   # (B,) valid KV length (masking)
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
    rules: Any = None,             # ShardingRules: pins loop-carry shardings
) -> jax.Array:
    """Blockwise flash attention with online softmax + recomputation
    backward (custom_vjp) — never materializes an (S, S) score matrix in
    either pass.  GQA native: H = KH * G query heads share KH kv heads.
    Returns (B, Sq, H, D) in q.dtype.
    """
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    G = H // KH

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    pq = (-Sq) % q_chunk
    pk = (-Skv) % kv_chunk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    Sq_p, Skv_p = Sq + pq, Skv + pk
    nq, nk = Sq_p // q_chunk, Skv_p // kv_chunk

    qb = q.reshape(B, nq, q_chunk, KH, G, D)
    kb = k.reshape(B, nk, kv_chunk, KH, D)
    vb = v.reshape(B, nk, kv_chunk, KH, D)

    # batch-free masks when offsets are uniform and kv is full (training)
    batched = not (isinstance(q_offset, int) and kv_len is None)
    if batched:
        q_off = jnp.asarray(q_offset, jnp.int32)
        if q_off.ndim == 0:
            q_off = jnp.broadcast_to(q_off, (B,))
        kv_valid = (jnp.full((B,), Skv, jnp.int32) if kv_len is None
                    else kv_len.astype(jnp.int32))
    else:
        q_off = jnp.int32(q_offset)
        kv_valid = jnp.int32(Skv)   # padded tail masked by causal+valid
        if pk:
            kv_valid = jnp.int32(Skv)

    specs = None
    if rules is not None:
        from jax.sharding import PartitionSpec as _P
        ba = rules.lookup("batch")
        ma = rules.lookup("kv_heads")
        specs = (_P(ba, ma, None, None, None),    # acc   (B,KH,G,qc,D)
                 _P(ba, ma, None, None),          # m/lse (B,KH,G,qc)
                 _P(None, ba, ma, None, None, None),  # outs stack
                 _P(None, ba, ma, None, None))        # lse stack
        qb = _c(qb, _P(ba, None, None, ma, None, None))
        kb = _c(kb, _P(ba, None, None, ma, None))
        vb = _c(vb, _P(ba, None, None, ma, None))

    static = (causal, window, q_chunk, kv_chunk, nq, nk, batched, specs)
    outs = _flash(static, qb, kb, vb, q_off, kv_valid)
    # outs (nq,B,KH,G,qc,D) -> (B, Sq, H, D)
    out = jnp.transpose(outs, (1, 0, 4, 2, 3, 5)).reshape(
        B, Sq_p, H, D)[:, :Sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                  # (B, 1, H, D)
    k: jax.Array,                  # (B, Skv, KH, D)
    v: jax.Array,                  # (B, Skv, KH, D)
    kv_len: jax.Array,             # (B,)
    *,
    window: int = 0,
    kv_start: Optional[jax.Array] = None,   # (B,) position of k[:, 0]
) -> jax.Array:
    """Single-token attention over a (gathered) KV history.

    One un-chunked pass: scores are (B, H, 1, Skv) — linear in Skv, fine.
    ``kv_start`` places a history that begins past position 0 (a window's
    pages).
    """
    B, _, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, 1, KH, G, D)
    s = _gqa_scores(qg, k) * scale           # (B,KH,G,1,Skv)
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)[None, :]
    if kv_start is not None:
        pos = pos + kv_start[:, None]
    mask = pos < kv_len[:, None]              # (B,Skv)
    if window:
        mask = mask & (pos > kv_len[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (projections + rope + chunked attention)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ModelConfig, dtype=None) -> Params:
    dtype = dtype or dtype_of(cfg)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: Params = {
        "wq": dense_init(k1, d, cfg.q_dim, dtype),
        "wk": dense_init(k2, d, cfg.kv_dim, dtype),
        "wv": dense_init(k3, d, cfg.kv_dim, dtype),
        "wo": dense_init(k4, cfg.q_dim, d, dtype,
                         scale=1.0 / math.sqrt(2 * cfg.n_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), dtype)
    return p


def attention_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "wq": ("embed", "heads"),
        "wk": ("embed", "kv_heads"),
        "wv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads",)
        p["bk"] = ("kv_heads",)
        p["bv"] = ("kv_heads",)
    return p


def qkv_proj(cfg: ModelConfig, p: Params, x: jax.Array
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,d) -> q (B,S,H,D), k/v (B,S,KH,D)."""
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    return q, k, v


def out_proj(cfg: ModelConfig, p: Params, o: jax.Array) -> jax.Array:
    B, S = o.shape[:2]
    return o.reshape(B, S, cfg.q_dim) @ p["wo"]


def positions_rope(cfg: ModelConfig, q, k, positions, kind: str = ""):
    """Apply (M-)RoPE to q,k given positions ((B,S) or (B,S,3)).  A full
    layer (``kind``) of a configuration with ``yarn`` rotates by YaRN."""
    if cfg.mrope:
        secs = mrope_sections(cfg.head_dim)
        if positions.ndim == 2:  # text-only: all three components equal
            positions = jnp.repeat(positions[..., None], 3, axis=-1)
        q = apply_mrope(q, positions, cfg.rope_theta, secs)
        k = apply_mrope(k, positions, cfg.rope_theta, secs)
    else:
        if positions.ndim == 3:
            positions = positions[..., 0]
        yarn = cfg.yarn if kind == FULL else None
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)
    return q, k


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ModelConfig, d_ff: Optional[int] = None,
             dtype=None) -> Params:
    dtype = dtype or dtype_of(cfg)
    d_ff = d_ff or cfg.d_ff
    d = cfg.d_model
    if cfg.act == "silu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {"wg": dense_init(k1, d, d_ff, dtype),
                "wu": dense_init(k2, d, d_ff, dtype),
                "wd": dense_init(k3, d_ff, d, dtype,
                                 scale=1.0 / math.sqrt(2 * cfg.n_layers))}
    k1, k2 = jax.random.split(key, 2)
    return {"wu": dense_init(k1, d, d_ff, dtype),
            "wd": dense_init(k2, d_ff, d, dtype,
                             scale=1.0 / math.sqrt(2 * cfg.n_layers))}


def mlp_axes(cfg: ModelConfig) -> Params:
    if cfg.act == "silu":
        return {"wg": ("embed", "ffn"), "wu": ("embed", "ffn"),
                "wd": ("ffn", "embed")}
    return {"wu": ("embed", "ffn"), "wd": ("ffn", "embed")}


def mlp_apply(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    return jax.nn.gelu(x @ p["wu"]) @ p["wd"]


# ---------------------------------------------------------------------------
# Embedding + LM head (fenced token gather — Guardian vocab space)
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ModelConfig, dtype=None) -> Params:
    dtype = dtype or dtype_of(cfg)
    k1, k2 = jax.random.split(key)
    p: Params = {"table": embed_init(k1, cfg.vocab, cfg.d_model, dtype)}
    if not cfg.tie_embeddings:
        p["head"] = dense_init(k2, cfg.d_model, cfg.vocab, dtype)
    return p


def embedding_axes(cfg: ModelConfig) -> Params:
    p: Params = {"table": ("vocab", "embed_nofsdp")}
    if not cfg.tie_embeddings:
        p["head"] = ("embed_nofsdp", "vocab")
    return p


def embed_tokens(p: Params, tokens: jax.Array,
                 guard: Optional[GuardSpec] = None) -> jax.Array:
    """Token-id gather.  With a guard, ids are fenced into the tenant's
    vocab partition (token ids are untrusted request data)."""
    ids = fence(guard, "vocab", tokens.astype(jnp.int32))
    return jnp.take(p["table"], ids, axis=0)


def lm_logits(cfg: ModelConfig, p: Params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return x @ p["table"].T
    return x @ p["head"]


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """Mean token NLL.  logits (B,S,V) any float dtype; labels (B,S)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
