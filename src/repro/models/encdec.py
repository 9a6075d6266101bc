"""Encoder-decoder backbone — seamless-m4t-medium.

The audio frontend is a stub per assignment: the encoder consumes
precomputed frame embeddings (B, S_src, d).  Decoder = causal self-attn
(paged-slab KV, fenced) + cross-attn to the encoder memory (computed once
per request, stored per slot in the pool — slot ids fenced).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingRules, constrain
from repro.models import layers as L
from repro.models import kvcache as KV
from repro.models.guard import GuardSpec, fence

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _enc_layer_init(key, cfg):
    k1, k2 = jax.random.split(key)
    return {
        "attn": L.attention_init(k1, cfg),
        "mlp": L.mlp_init(k2, cfg),
        "norm1": L.norm_init(cfg),
        "norm2": L.norm_init(cfg),
    }


def _dec_layer_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "attn": L.attention_init(k1, cfg),
        "cross": L.attention_init(k2, cfg),
        "mlp": L.mlp_init(k3, cfg),
        "norm1": L.norm_init(cfg),
        "norm_x": L.norm_init(cfg),
        "norm2": L.norm_init(cfg),
    }


def init(rng, cfg: ModelConfig) -> Params:
    k_emb, k_enc, k_dec = jax.random.split(rng, 3)
    enc = jax.vmap(lambda k: _enc_layer_init(k, cfg))(
        jax.random.split(k_enc, cfg.enc_layers))
    dec = jax.vmap(lambda k: _dec_layer_init(k, cfg))(
        jax.random.split(k_dec, cfg.n_layers))
    return {
        "embed": L.embedding_init(k_emb, cfg),
        "enc": enc,
        "dec": dec,
        "norm_enc": L.norm_init(cfg),
        "norm_f": L.norm_init(cfg),
    }


def param_logical_axes(cfg: ModelConfig) -> Params:
    def stack(tree):
        return jax.tree.map(lambda axes: (None, *axes), tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": L.embedding_axes(cfg),
        "enc": stack({
            "attn": L.attention_axes(cfg), "mlp": L.mlp_axes(cfg),
            "norm1": L.norm_axes(cfg), "norm2": L.norm_axes(cfg)}),
        "dec": stack({
            "attn": L.attention_axes(cfg), "cross": L.attention_axes(cfg),
            "mlp": L.mlp_axes(cfg), "norm1": L.norm_axes(cfg),
            "norm_x": L.norm_axes(cfg), "norm2": L.norm_axes(cfg)}),
        "norm_enc": L.norm_axes(cfg),
        "norm_f": L.norm_axes(cfg),
    }


# ---------------------------------------------------------------------------
# Encoder
# ---------------------------------------------------------------------------

def encode(cfg: ModelConfig, params: Params, src: jax.Array,
           rules: Optional[ShardingRules] = None,
           remat: bool = False) -> jax.Array:
    """src: precomputed frame embeddings (B, S_src, d) -> memory."""
    B, S, _ = src.shape
    positions = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    x = src

    def layer(x, p):
        q, k, v = L.qkv_proj(cfg, p["attn"],
                             L.apply_norm(cfg, p["norm1"], x))
        q, k = L.positions_rope(cfg, q, k, positions)
        o = L.chunked_attention(q, k, v, causal=False, rules=rules)
        x = x + L.out_proj(cfg, p["attn"], o)
        x = x + L.mlp_apply(cfg, p["mlp"],
                            L.apply_norm(cfg, p["norm2"], x))
        if rules is not None:
            x = constrain(x, rules, ("batch", "seq", None))
        return x, None

    body = layer
    if remat:
        body = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["enc"])
    return L.apply_norm(cfg, params["norm_enc"], x)


# ---------------------------------------------------------------------------
# Decoder (training / scoring)
# ---------------------------------------------------------------------------

def _cross_attn(cfg, p, x, memory, rules=None):
    """Cross attention: queries from x, keys/values from encoder memory."""
    B, S, _ = x.shape
    xn = L.apply_norm(cfg, p["norm_x"], x)
    q = (xn @ p["cross"]["wq"]).reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = (memory @ p["cross"]["wk"]).reshape(
        B, memory.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = (memory @ p["cross"]["wv"]).reshape(
        B, memory.shape[1], cfg.n_kv_heads, cfg.head_dim)
    o = L.chunked_attention(q, k, v, causal=False, rules=rules)
    return L.out_proj(cfg, p["cross"], o)


def decode_train(cfg: ModelConfig, params: Params, tgt: jax.Array,
                 memory: jax.Array, *, guard: Optional[GuardSpec] = None,
                 rules: Optional[ShardingRules] = None,
                 remat: bool = False) -> jax.Array:
    B, S = tgt.shape
    positions = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    x = L.embed_tokens(params["embed"], tgt, guard)

    def layer(x, p):
        q, k, v = L.qkv_proj(cfg, p["attn"],
                             L.apply_norm(cfg, p["norm1"], x))
        q, k = L.positions_rope(cfg, q, k, positions)
        o = L.chunked_attention(q, k, v, causal=True, rules=rules)
        x = x + L.out_proj(cfg, p["attn"], o)
        x = x + _cross_attn(cfg, p, x, memory, rules)
        x = x + L.mlp_apply(cfg, p["mlp"],
                            L.apply_norm(cfg, p["norm2"], x))
        if rules is not None:
            x = constrain(x, rules, ("batch", "seq", None))
        return x, None

    body = layer
    if remat:
        body = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["dec"])
    x = L.apply_norm(cfg, params["norm_f"], x)
    return L.lm_logits(cfg, params["embed"], x)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            remat: bool = True) -> jax.Array:
    memory = encode(cfg, params, batch["src"], rules, remat)
    tgt = batch["tgt"]
    logits = decode_train(cfg, params, tgt[:, :-1], memory, guard=guard,
                          rules=rules, remat=remat)
    return L.softmax_cross_entropy(logits, tgt[:, 1:], batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving — decoder KV slabs + per-slot cross-attention memory pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EncDecCache:
    POOL: ClassVar[Tuple[str, ...]] = ("cross_k", "cross_v")
    META: ClassVar[Tuple[str, ...]] = ("src_lens",)

    kv: KV.PagedKVCache            # decoder self-attention
    cross_k: jax.Array             # (L, slots, S_src, KH, D)
    cross_v: jax.Array
    src_lens: jax.Array            # (B,)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, src_len: int,
               dtype=jnp.bfloat16, slots=None) -> EncDecCache:
    kv = KV.init_kv_cache(cfg, batch, max_len, dtype=dtype, slots=slots)
    slots = kv.k.shape[1]
    shape = (cfg.n_layers, slots, src_len, cfg.n_kv_heads, cfg.head_dim)
    return EncDecCache(
        kv=kv,
        cross_k=jnp.zeros(shape, dtype),
        cross_v=jnp.zeros(shape, dtype),
        src_lens=jnp.zeros((batch,), jnp.int32),
    )


def prefill(cfg: ModelConfig, params: Params, cache: EncDecCache,
            src: jax.Array, tgt: jax.Array, *,
            guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None
            ) -> Tuple[EncDecCache, jax.Array]:
    """Encode src, precompute per-layer cross KV, prefill decoder slabs."""
    memory = encode(cfg, params, src, rules)
    B, S_src, _ = memory.shape
    B2, S = tgt.shape
    positions = jnp.broadcast_to(
        jnp.arange(S, dtype=jnp.int32)[None, :], (B2, S))
    x = L.embed_tokens(params["embed"], tgt, guard)
    slots = fence(guard, "kv", cache.kv.slot_ids)

    def body(carry, inp):
        x, kc, vc, xk, xv = carry
        p, lidx = inp
        # self-attention with slab write
        q, k, v = L.qkv_proj(cfg, p["attn"],
                             L.apply_norm(cfg, p["norm1"], x))
        q, k = L.positions_rope(cfg, q, k, positions)
        tmp = dataclasses.replace(cache.kv, k=kc, v=vc)
        tmp = KV.write_prefill_kv(tmp, lidx, k.astype(kc.dtype),
                                  v.astype(vc.dtype), guard)
        o = L.chunked_attention(q, k, v, causal=True, rules=rules)
        x = x + L.out_proj(cfg, p["attn"], o)
        # cross attention + stash cross KV for decode
        ck = (memory @ p["cross"]["wk"]).reshape(
            B, S_src, cfg.n_kv_heads, cfg.head_dim)
        cv = (memory @ p["cross"]["wv"]).reshape(
            B, S_src, cfg.n_kv_heads, cfg.head_dim)
        xk = xk.at[lidx, slots].set(ck.astype(xk.dtype),
                                    mode="promise_in_bounds")
        xv = xv.at[lidx, slots].set(cv.astype(xv.dtype),
                                    mode="promise_in_bounds")
        xn = L.apply_norm(cfg, p["norm_x"], x)
        qx = (xn @ p["cross"]["wq"]).reshape(
            B2, S, cfg.n_heads, cfg.head_dim)
        ox = L.chunked_attention(qx, ck, cv, causal=False, rules=rules)
        x = x + L.out_proj(cfg, p["cross"], ox)
        x = x + L.mlp_apply(cfg, p["mlp"],
                            L.apply_norm(cfg, p["norm2"], x))
        return (x, tmp.k, tmp.v, xk, xv), None

    lidxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, kc, vc, xk, xv), _ = jax.lax.scan(
        body, (x, cache.kv.k, cache.kv.v, cache.cross_k, cache.cross_v),
        (params["dec"], lidxs))
    kv = dataclasses.replace(cache.kv, k=kc, v=vc,
                             seq_lens=cache.kv.seq_lens + S)
    cache = EncDecCache(kv=kv, cross_k=xk, cross_v=xv,
                        src_lens=jnp.full((B,), S_src, jnp.int32))
    x = L.apply_norm(cfg, params["norm_f"], x[:, -1:])
    return cache, L.lm_logits(cfg, params["embed"], x)[:, 0]


def decode(cfg: ModelConfig, params: Params, cache: EncDecCache,
           tokens: jax.Array, *, guard: Optional[GuardSpec] = None,
           rules: Optional[ShardingRules] = None,
           positions: Optional[jax.Array] = None
           ) -> Tuple[EncDecCache, jax.Array]:
    B = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens[:, None], guard)
    if positions is None:
        positions = cache.kv.seq_lens[:, None]
    slots = fence(guard, "kv", cache.kv.slot_ids)

    def body(carry, inp):
        x, kc, vc = carry
        p, lidx = inp
        q, k, v = L.qkv_proj(cfg, p["attn"],
                             L.apply_norm(cfg, p["norm1"], x))
        q, k = L.positions_rope(cfg, q, k, positions)
        tmp = dataclasses.replace(cache.kv, k=kc, v=vc)
        tmp = KV.append_token_kv(tmp, lidx, k.astype(kc.dtype),
                                 v.astype(vc.dtype), guard)
        k_hist, v_hist = KV.gather_layer_kv(tmp, lidx, guard, rules)
        o = L.decode_attention(q, k_hist.astype(q.dtype),
                               v_hist.astype(q.dtype),
                               cache.kv.seq_lens + 1)
        x = x + L.out_proj(cfg, p["attn"], o)
        # cross attention against the cached memory KV
        xn = L.apply_norm(cfg, p["norm_x"], x)
        qx = (xn @ p["cross"]["wq"]).reshape(
            B, 1, cfg.n_heads, cfg.head_dim)
        ck = jnp.take(xk_l(lidx, cache.cross_k), slots, axis=0)
        cv = jnp.take(xk_l(lidx, cache.cross_v), slots, axis=0)
        ox = L.decode_attention(qx, ck.astype(qx.dtype),
                                cv.astype(qx.dtype), cache.src_lens)
        x = x + L.out_proj(cfg, p["cross"], ox)
        x = x + L.mlp_apply(cfg, p["mlp"],
                            L.apply_norm(cfg, p["norm2"], x))
        return (x, tmp.k, tmp.v), None

    def xk_l(lidx, pool):
        return jax.lax.dynamic_index_in_dim(pool, lidx, axis=0,
                                            keepdims=False)

    lidxs = jnp.arange(cfg.n_layers, dtype=jnp.int32)
    (x, kc, vc), _ = jax.lax.scan(body, (x, cache.kv.k, cache.kv.v),
                                  (params["dec"], lidxs))
    kv = dataclasses.replace(cache.kv, k=kc, v=vc,
                             seq_lens=cache.kv.seq_lens + 1)
    cache = dataclasses.replace(cache, kv=kv)
    x = L.apply_norm(cfg, params["norm_f"], x)
    return cache, L.lm_logits(cfg, params["embed"], x)[:, 0]
