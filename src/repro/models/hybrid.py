"""zamba2-7b — hybrid backbone: Mamba2 (SSD) layers with a *shared*
full-attention+MLP block invoked after every ``attn_every`` SSD layers.

Layer layout for n_layers=81, attn_every=6:

    [6 mamba] attn* [6 mamba] attn* ... (13 groups) ... [3 mamba tail]

where ``attn*`` is the same parameter block every time (zamba2's weight
sharing).  Windowed attention (cfg.attn_window) keeps the arch
sub-quadratic, so long_500k runs: SSM state is O(1) per token and the
shared-attention KV is capped at the window.

Serving state per request: 13 windowed-KV slabs (one per attn invocation)
+ per-mamba-layer SSM/conv state in the Guardian state pool.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import ShardingRules, constrain
from repro.models import layers as L
from repro.models import kvcache as KV
from repro.models import mamba2 as M2
from repro.models.guard import GuardSpec

Params = Dict[str, Any]


def group_structure(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_groups of attn_every mamba layers each followed by shared attn,
    n_tail mamba layers)."""
    g = cfg.n_layers // cfg.attn_every
    return g, cfg.n_layers - g * cfg.attn_every


def n_attn_calls(cfg: ModelConfig) -> int:
    return group_structure(cfg)[0]


def init(rng, cfg: ModelConfig) -> Params:
    k_emb, k_m, k_a, k_mlp = jax.random.split(rng, 4)
    g, tail = group_structure(cfg)
    mkeys = jax.random.split(k_m, cfg.n_layers)
    grouped = jax.vmap(lambda k: M2.block_init(k, cfg))(
        mkeys[:g * cfg.attn_every])
    grouped = jax.tree.map(
        lambda x: x.reshape(g, cfg.attn_every, *x.shape[1:]), grouped)
    tail_p = (jax.vmap(lambda k: M2.block_init(k, cfg))(
        mkeys[g * cfg.attn_every:]) if tail else None)
    p: Params = {
        "embed": L.embedding_init(k_emb, cfg),
        "mamba": grouped,
        "shared_attn": {
            "attn": L.attention_init(k_a, cfg),
            "mlp": L.mlp_init(k_mlp, cfg),
            "norm1": L.norm_init(cfg),
            "norm2": L.norm_init(cfg),
        },
        "norm_f": L.norm_init(cfg),
    }
    if tail:
        p["mamba_tail"] = tail_p
    return p


def param_logical_axes(cfg: ModelConfig) -> Params:
    g, tail = group_structure(cfg)

    def stack2(tree):
        return jax.tree.map(lambda axes: (None, None, *axes), tree,
                            is_leaf=lambda x: isinstance(x, tuple))

    def stack1(tree):
        return jax.tree.map(lambda axes: (None, *axes), tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    p: Params = {
        "embed": L.embedding_axes(cfg),
        "mamba": stack2(M2.block_axes(cfg)),
        "shared_attn": {
            "attn": L.attention_axes(cfg),
            "mlp": L.mlp_axes(cfg),
            "norm1": L.norm_axes(cfg),
            "norm2": L.norm_axes(cfg),
        },
        "norm_f": L.norm_axes(cfg),
    }
    if tail:
        p["mamba_tail"] = stack1(M2.block_axes(cfg))
    return p


# ---------------------------------------------------------------------------
# Training / full-sequence forward
# ---------------------------------------------------------------------------

def _shared_attn_full(cfg, p, x, positions, rules=None):
    sa = p["shared_attn"]
    q, k, v = L.qkv_proj(cfg, sa["attn"], L.apply_norm(cfg, sa["norm1"], x))
    q, k = L.positions_rope(cfg, q, k, positions)
    o = L.chunked_attention(q, k, v, causal=True, window=cfg.attn_window, rules=rules)
    x = x + L.out_proj(cfg, sa["attn"], o)
    x = x + L.mlp_apply(cfg, sa["mlp"], L.apply_norm(cfg, sa["norm2"], x))
    return x


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None, *,
            guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            remat: bool = False) -> jax.Array:
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, guard)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def mamba_group(x, group_p):
        def one(x, p):
            y, _, _ = M2.block_apply(cfg, p, x)
            x = x + y
            if rules is not None:
                x = constrain(x, rules, ("batch", "seq", None))
            return x, None
        x, _ = jax.lax.scan(one, x, group_p)
        return x

    def group_body(x, group_p):
        x = mamba_group(x, group_p)
        x = _shared_attn_full(cfg, params, x, positions, rules)
        return x, None

    body = group_body
    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["mamba"])
    if "mamba_tail" in params:
        def one_t(x, p):
            y, _, _ = M2.block_apply(cfg, p, x)
            return x + y, None
        x, _ = jax.lax.scan(one_t, x, params["mamba_tail"])
    x = L.apply_norm(cfg, params["norm_f"], x)
    return L.lm_logits(cfg, params["embed"], x)


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            remat: bool = True) -> jax.Array:
    tokens = batch["tokens"]
    logits = forward(cfg, params, tokens[:, :-1], guard=guard, rules=rules,
                     remat=remat)
    return L.softmax_cross_entropy(logits, tokens[:, 1:],
                                   batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving — hybrid cache: windowed-KV slabs + SSM state pool
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HybridCache:
    #: no fields of its own: both parts are caches (kvcache's walks)
    POOL: ClassVar[Tuple[str, ...]] = ()
    META: ClassVar[Tuple[str, ...]] = ()

    kv: KV.PagedKVCache          # n_attn_calls layers, windowed
    state: KV.StateCache         # per-mamba-layer ssm + conv state


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, slots=None) -> HybridCache:
    g, tail = group_structure(cfg)
    window = cfg.attn_window or max_len
    kv = KV.init_kv_cache(cfg, batch, min(max_len, window), dtype=dtype,
                          n_layers=g, slots=slots)
    shapes = M2.state_shapes(cfg)
    if slots is None:
        slots = max(1 << (batch - 1).bit_length(), 1) if batch > 1 else 1
    pools = {
        "ssm": jnp.zeros((cfg.n_layers, slots, *shapes["ssm"]),
                         jnp.float32),
        "conv": jnp.zeros((cfg.n_layers, slots, *shapes["conv"]),
                          dtype),
    }
    state = KV.StateCache(pools=pools,
                          slot_ids=jnp.arange(batch, dtype=jnp.int32),
                          seq_lens=jnp.zeros((batch,), jnp.int32))
    return HybridCache(kv=kv, state=state)


def prefill(cfg: ModelConfig, params: Params, cache: HybridCache,
            tokens: jax.Array, *, guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            positions: Optional[jax.Array] = None
            ) -> Tuple[HybridCache, jax.Array]:
    """Process the prompt: run SSD blocks full-sequence capturing final
    states; write the last `window` tokens' KV for each shared-attention
    invocation; return last-position logits."""
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, guard)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))
    g, tail = group_structure(cfg)
    state = cache.state
    kvc = cache.kv
    window = kvc.max_len

    def mamba_full(x, st, lidx, p):
        y, h_f, tail_c = M2.block_apply(cfg, p, x)
        st = st.write("ssm", lidx, h_f, guard)
        st = st.write("conv", lidx,
                      tail_c[:, -(cfg.ssm.conv_width - 1):].astype(
                          st.pools["conv"].dtype), guard)
        return x + y, st

    def group_body(carry, inp):
        x, st, kc, vc = carry
        gi, group_p = inp

        def m_body(c, inp2):
            x, st = c
            li, p = inp2
            x, st = mamba_full(x, st, gi * cfg.attn_every + li, p)
            return (x, st), None
        (x, st), _ = jax.lax.scan(
            m_body, (x, st),
            (jnp.arange(cfg.attn_every, dtype=jnp.int32), group_p))
        sa = params["shared_attn"]
        q, k, v = L.qkv_proj(cfg, sa["attn"],
                             L.apply_norm(cfg, sa["norm1"], x))
        q, k = L.positions_rope(cfg, q, k, positions)
        o = L.chunked_attention(q, k, v, causal=True,
                                window=cfg.attn_window, rules=rules)
        x = x + L.out_proj(cfg, sa["attn"], o)
        x = x + L.mlp_apply(cfg, sa["mlp"],
                            L.apply_norm(cfg, sa["norm2"], x))
        # stash the trailing window of KV for decode
        kw = k[:, -window:] if S >= window else k
        vw = v[:, -window:] if S >= window else v
        pad = window - kw.shape[1]
        if pad > 0:
            kw = jnp.pad(kw, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vw = jnp.pad(vw, ((0, 0), (0, pad), (0, 0), (0, 0)))
        tmp = dataclasses.replace(kvc, k=kc, v=vc)
        tmp = KV.write_prefill_kv(tmp, gi, kw.astype(kc.dtype),
                                  vw.astype(vc.dtype), guard)
        if rules is not None:
            x = constrain(x, rules, ("batch", "seq", None))
        return (x, st, tmp.k, tmp.v), None

    (x, state, kc, vc), _ = jax.lax.scan(
        group_body, (x, state, kvc.k, kvc.v),
        (jnp.arange(g, dtype=jnp.int32), params["mamba"]))
    kvc = dataclasses.replace(kvc, k=kc, v=vc,
                              seq_lens=jnp.minimum(kvc.seq_lens + S,
                                                   window))
    if "mamba_tail" in params:
        def t_body(c, inp2):
            x, st = c
            li, p = inp2
            x, st = mamba_full(x, st, g * cfg.attn_every + li, p)
            return (x, st), None
        (x, state), _ = jax.lax.scan(
            t_body, (x, state),
            (jnp.arange(tail, dtype=jnp.int32), params["mamba_tail"]))
    state = dataclasses.replace(state, seq_lens=state.seq_lens + S)
    x = L.apply_norm(cfg, params["norm_f"], x[:, -1:])
    logits = L.lm_logits(cfg, params["embed"], x)
    return HybridCache(kv=kvc, state=state), logits[:, 0]


def _decode_shared_attn(cfg, params, cache: KV.PagedKVCache, x, lidx,
                        positions, guard, rules=None):
    sa = params["shared_attn"]
    q, k, v = L.qkv_proj(cfg, sa["attn"], L.apply_norm(cfg, sa["norm1"], x))
    q, k = L.positions_rope(cfg, q, k, positions)
    # windowed cache: write position wraps modulo the window
    wrapped = dataclasses.replace(
        cache, seq_lens=jnp.minimum(cache.seq_lens, cache.max_len - 1))
    wrapped = KV.append_token_kv(wrapped, lidx, k.astype(cache.k.dtype),
                                 v.astype(cache.v.dtype), guard)
    cache = dataclasses.replace(cache, k=wrapped.k, v=wrapped.v)
    k_hist, v_hist = KV.gather_layer_kv(cache, lidx, guard, rules)
    kv_len = jnp.minimum(cache.seq_lens + 1,
                         jnp.int32(cache.max_len))
    o = L.decode_attention(q, k_hist.astype(q.dtype),
                           v_hist.astype(q.dtype), kv_len,
                           window=cfg.attn_window)
    x = x + L.out_proj(cfg, sa["attn"], o)
    x = x + L.mlp_apply(cfg, sa["mlp"], L.apply_norm(cfg, sa["norm2"], x))
    return cache, x


def decode(cfg: ModelConfig, params: Params, cache: HybridCache,
           tokens: jax.Array, *, guard: Optional[GuardSpec] = None,
           rules: Optional[ShardingRules] = None,
           positions: Optional[jax.Array] = None
           ) -> Tuple[HybridCache, jax.Array]:
    """One decode step through the hybrid stack."""
    B = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens[:, None], guard)
    if positions is None:
        positions = cache.state.seq_lens[:, None]
    g, tail = group_structure(cfg)
    state = cache.state
    kvc = cache.kv

    def mamba_one(x, state, lidx, p):
        h = state.read("ssm", lidx, guard)
        tail_c = state.read("conv", lidx, guard)
        y, h_new, tail_new = M2.block_step(cfg, p, x, h, tail_c)
        state = state.write("ssm", lidx, h_new, guard)
        state = state.write("conv", lidx, tail_new.astype(
            state.pools["conv"].dtype), guard)
        return x + y, state

    def group_body(carry, inp):
        x, st, kc, vc = carry
        gi, group_p = inp

        def m_body(c, inp2):
            x, st = c
            li, p = inp2
            x, st = mamba_one(x, st, gi * cfg.attn_every + li, p)
            return (x, st), None
        (x, st), _ = jax.lax.scan(
            m_body, (x, st),
            (jnp.arange(cfg.attn_every, dtype=jnp.int32), group_p))
        tmp = dataclasses.replace(kvc, k=kc, v=vc)
        tmp, x = _decode_shared_attn(cfg, params, tmp, x, gi, positions,
                                     guard, rules)
        return (x, st, tmp.k, tmp.v), None

    (x, state, kc, vc), _ = jax.lax.scan(
        group_body, (x, state, kvc.k, kvc.v),
        (jnp.arange(g, dtype=jnp.int32), params["mamba"]))
    kvc = dataclasses.replace(kvc, k=kc, v=vc, seq_lens=kvc.seq_lens + 1)
    if "mamba_tail" in params:
        def t_body(c, inp2):
            x, st = c
            li, p = inp2
            x, st = mamba_one(x, st, g * cfg.attn_every + li, p)
            return (x, st), None
        (x, state), _ = jax.lax.scan(
            t_body, (x, state),
            (jnp.arange(tail, dtype=jnp.int32), params["mamba_tail"]))
    state = dataclasses.replace(state, seq_lens=state.seq_lens + 1)
    x = L.apply_norm(cfg, params["norm_f"], x)
    logits = L.lm_logits(cfg, params["embed"], x)
    return HybridCache(kv=kvc, state=state), logits[:, 0]
