"""MoE decoder LM — grok-1-314b (8e top-2) / qwen3-moe-30b-a3b (128e top-8)
/ mellum2-12b-a2.5b (64e top-8, sliding and full layers).

Expert parallelism: expert tensors carry an ``expert`` logical axis mapped
to the mesh ``model`` axis; training dispatch uses the capacity-based
one-hot einsum formulation so GSPMD inserts the all-to-alls.  A chip may
hold a share of each layer's experts (``MoEConfig.experts_held``): the
router scores every expert, and only the pairs with held experts are
computed here.  Serving is dropless (:func:`moe_dropless`).  The
**router's expert ids are data-dependent** — the Guardian "expert" fence
is applied to them before they form dispatch offsets, so a
corrupted/adversarial router can never address another tenant's
expert-buffer rows (the MoE analogue of the paper's fenced ld/st).

Layers of different kinds (``ModelConfig.layer_types``) run as a scan over
the periods of the kind pattern with each period unrolled, so every
layer's window and rotary table stay static.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as megablox_gmm

from repro.configs.base import FULL, SLIDING, ModelConfig
from repro.distributed.sharding import ShardingRules, constrain
from repro.models import layers as L
from repro.models import kvcache as KV
from repro.models.guard import GuardSpec, fence

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Expert MLP bank + router
# ---------------------------------------------------------------------------

def moe_init(key, cfg: ModelConfig) -> Params:
    """The router over every expert and the bank of the experts held
    here, the bank drawn in the served dtype."""
    m = cfg.moe
    d, e, f = cfg.d_model, m.held, m.d_ff_expert
    kr, kg, ku, kd = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    out_std = 1.0 / math.sqrt(f * 2 * cfg.n_layers)
    dt = L.dtype_of(cfg)
    p: Params = {
        "router": (jax.random.normal(kr, (d, m.num_experts), jnp.float32)
                   * std).astype(jnp.float32),
        "wu": jax.random.normal(ku, (e, d, f), dt) * jnp.asarray(std, dt),
        "wd": jax.random.normal(kd, (e, f, d), dt) * jnp.asarray(out_std,
                                                                 dt),
    }
    if cfg.act == "silu":
        p["wg"] = jax.random.normal(kg, (e, d, f), dt) * jnp.asarray(std,
                                                                     dt)
    return p


def moe_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "router": (None, None),
        "wu": ("expert", "embed", None),
        "wd": ("expert", None, "embed"),
    }
    if cfg.act == "silu":
        p["wg"] = ("expert", "embed", None)
    return p


def held_experts(cfg: ModelConfig, guard: Optional[GuardSpec],
                 expert_ids: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The router's choices over every expert -> (local, held): each
    pair's index into this chip's bank and whether the bank holds its
    expert.  Only held ids pass the "expert" fence; a pair whose expert is
    held elsewhere gets the index one past the bank, set after the fence,
    so it never wraps onto a held expert."""
    m = cfg.moe
    local = expert_ids - m.expert_base
    held = (local >= 0) & (local < m.held)
    local = fence(guard, "expert", jnp.where(held, local, 0))
    return jnp.where(held, local, m.held), held


def _route(cfg: ModelConfig, p: Params, xf: jax.Array):
    """Softmax over every expert, top-k, weights renormalised over the k."""
    logits = (xf.astype(jnp.float32) @ p["router"])            # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, cfg.moe.top_k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    return probs, gate_vals, expert_ids.astype(jnp.int32)


def moe_apply(cfg: ModelConfig, p: Params, x: jax.Array,
              guard: Optional[GuardSpec] = None,
              rules: Optional[ShardingRules] = None,
              dispatch: str = "scatter",
              capacity_factor: float = 2.0) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar).

    Capacity-based dispatch (training): top-k expert choice per token,
    tokens routed into per-expert buffers of capacity C = ceil(2*K*T/E);
    overflow drops (standard), as do pairs with experts held elsewhere.
    All shapes static => dry-run friendly.

    Two dispatch implementations (§Perf hillclimb H1):

    * ``einsum``  — Mesh-TF one-hot dispatch tensors (T,E,C).  Simple, but
      the dispatch/combine einsums cost O(T·E·C·d) FLOPs — they dominate
      the step for fine-grained MoE (qwen3: 128e top-8).
    * ``scatter`` — fenced destination indices ``dest = e·C + pos`` with a
      scatter into the (E·C, d) buffer and a gather back: O(T·K·d) data
      movement, zero dispatch FLOPs.  The fence on ``dest`` is exactly the
      paper's store fence (a corrupted route wraps inside the tenant's
      expert-buffer partition).
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.held, m.top_k
    xf = x.reshape(T, d)

    probs, gate_vals, expert_ids = _route(cfg, p, xf)          # (T,K)
    # load-balancing aux loss (Switch-style), over every expert
    me = jnp.mean(probs, axis=0)                                # (E_all,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(
        expert_ids, m.num_experts, dtype=jnp.float32), axis=1), axis=0)
    aux = m.num_experts * jnp.sum(me * ce)
    # Guardian: expert ids are data-dependent addresses into the expert
    # bank — fence them into the tenant's expert partition.
    expert_ids, held = held_experts(cfg, guard, expert_ids)

    capacity = max(int(capacity_factor * K * T / E), 8)
    onehot = jax.nn.one_hot(expert_ids, E, dtype=jnp.float32)   # (T,K,E)
    pos_in_e = (jnp.cumsum(onehot.reshape(T * K, E), axis=0) - 1.0
                ).reshape(T, K, E)                              # rank
    pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(jnp.int32)  # (T,K)
    keep = (pos < capacity) & held
    gate_vals = gate_vals * keep.astype(gate_vals.dtype)

    if dispatch == "einsum":
        # dispatch tensor (T, E, C) — one-hot on (expert, slot)
        slot_oh = jax.nn.one_hot(jnp.where(keep, pos, capacity),
                                 capacity, dtype=xf.dtype)      # (T,K,C)
        disp = jnp.einsum("tke,tkc->tec", onehot.astype(xf.dtype),
                          slot_oh)
        xin = jnp.einsum("td,tec->ecd", xf, disp)               # (E,C,d)
    else:
        # fenced scatter dispatch: dest in [0, E*C) by construction
        # (expert_ids fenced + pos < capacity); dropped rows -> E*C.
        # Sharding: src rows are token-major (data axes); buf rows are
        # expert-major (model axis) — the scatter across them is the
        # dispatch all-to-all.  Without these constraints GSPMD
        # replicates the (E*C, d) buffer on every chip (§Perf H1 iter2).
        dest = (expert_ids * capacity + jnp.minimum(
            pos, capacity - 1)).reshape(T * K)                  # (T*K,)
        dest = jnp.where(keep.reshape(T * K), dest, E * capacity)
        src = jnp.broadcast_to(xf[:, None, :], (T, K, d)).reshape(
            T * K, d)
        if rules is not None:
            src = constrain(src, rules, ("batch", None))
        buf = jnp.zeros((E * capacity + 1, d), xf.dtype)
        if rules is not None:
            buf = constrain(buf, rules, ("expert", None))
        buf = buf.at[dest].set(src, mode="drop")
        if rules is not None:
            buf = constrain(buf, rules, ("expert", None))
        xin = buf[:E * capacity].reshape(E, capacity, d)
    if rules is not None:
        xin = constrain(xin, rules, ("expert", None, None))

    if cfg.act == "silu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin, p["wg"])) * \
            jnp.einsum("ecd,edf->ecf", xin, p["wu"])
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin, p["wu"]))
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wd"])               # (E,C,d)
    if rules is not None:
        out_e = constrain(out_e, rules, ("expert", None, None))

    if dispatch == "einsum":
        combine = jnp.einsum("tec,tke,tk->tec", disp,
                             onehot.astype(xf.dtype),
                             gate_vals.astype(xf.dtype))
        out = jnp.einsum("tec,ecd->td", combine, out_e)
    else:
        flat = out_e.reshape(E * capacity, d)
        if rules is not None:
            flat = constrain(flat, rules, ("expert", None))
        y_tk = jnp.take(flat, jnp.minimum(dest, E * capacity - 1),
                        axis=0).reshape(T, K, d)
        if rules is not None:
            y_tk = constrain(y_tk, rules, ("batch", None, None))
        w = (gate_vals * keep.astype(gate_vals.dtype)).astype(xf.dtype)
        out = jnp.einsum("tkd,tk->td", y_tk, w)
    return out.reshape(B, S, d), aux


#: the scoped VMEM a grouped-matmul tile set may take, double-buffered
#: (the TPU's default scoped limit is 16 MiB)
GMM_VMEM_BYTES = 14 << 20


def _tiles(dim: int):
    """Tile sizes of one dimension, largest first: the whole dimension,
    then its divisors that are multiples of 128 lanes."""
    return [dim] + [t for t in range(dim - dim % 128, 0, -128)
                    if t < dim and dim % t == 0]


def gmm_tiling(m: int, k: int, n: int, itemsize: int) -> Tuple[int, int, int]:
    """(tm, tk, tn) of ``megablox.gmm`` for ``m`` sorted rows of depth
    ``k`` and width ``n``: the rows' tile follows the row count (a decode
    step's 2 x top-8 = 16 rows take one tile of 16, a prefill wave's take
    512s), then the widest, then deepest, weight tile whose buffers fit
    ``GMM_VMEM_BYTES`` (wide first: each output tile reads its rows once);
    the rows' tile halves while none fits."""
    def fitting(tm):
        return [(tm, tk, tn) for tn in _tiles(n) for tk in _tiles(k)
                if 2 * (tm * tk + tk * tn) * itemsize + 3 * tm * tn * 4
                <= GMM_VMEM_BYTES]

    tm = min(512, max(16, 1 << (m - 1).bit_length()))
    while tm > 16 and not fitting(tm):
        tm //= 2
    return (fitting(tm) or [(tm, _tiles(k)[-1], _tiles(n)[-1])])[0]


def grouped_megablox(a: jax.Array, w: jax.Array, sizes: jax.Array, *,
                     interpret: bool) -> jax.Array:
    """a (m, k) rows sorted by group @ w (G, k, n), group g's rows by w[g]:
    the TPU's grouped matmul (Pallas), float32 out.  Each visited group's
    weight tiles are read where ``w`` holds them, and a group with no rows
    is never visited; rows past the groups are left unwritten."""
    m, k = a.shape
    tm, tk, tn = gmm_tiling(m, k, w.shape[2], w.dtype.itemsize)
    pad = -m % tm
    if pad:
        a = jnp.pad(a, ((0, pad), (0, 0)))
    out = megablox_gmm(a, w, sizes, preferred_element_type=jnp.float32,
                       tiling=(tm, tk, tn), interpret=interpret)
    return out[:m] if pad else out


def grouped_ragged(a: jax.Array, w: jax.Array,
                   sizes: jax.Array) -> jax.Array:
    """The same products by ``jax.lax.ragged_dot``, float32 out; rows past
    the groups come out zero."""
    return jax.lax.ragged_dot(a, w, sizes,
                              preferred_element_type=jnp.float32)


def _expert_mlp(cfg: ModelConfig, p: Params, grouped: Callable,
                xs: jax.Array, sizes: jax.Array) -> jax.Array:
    """The experts' MLP over rows sorted by expert, in float32."""
    if cfg.act == "silu":
        h = jax.nn.silu(grouped(xs, p["wg"], sizes)) * \
            grouped(xs, p["wu"], sizes)
    else:
        h = jax.nn.gelu(grouped(xs, p["wu"], sizes))
    return grouped(h.astype(xs.dtype), p["wd"], sizes)


def expert_bank(moe: Params) -> Params:
    """The stacked layers' held experts as one bank of ``n_layers * held``
    experts, layer ``l``'s from ``l * held`` on: merging the leading
    dimensions is a view and copies nothing."""
    return {k: a.reshape(-1, *a.shape[2:]) for k, a in moe.items()
            if k != "router"}


def moe_dropless(cfg: ModelConfig, p: Params, x: jax.Array,
                 guard: Optional[GuardSpec] = None, lidx=0
                 ) -> Tuple[jax.Array, jax.Array]:
    """x (B,S,d) -> (out (B,S,d), counts (B,4) int32), dropping nothing.

    ``p`` holds layer ``lidx``'s router and a bank of experts (``wg``,
    ``wu``, ``wd`` of ``n * held``; :func:`expert_bank`), the layer's own
    from ``lidx * held`` on: one layer's experts alone with ``lidx`` 0.
    The token-expert pairs are sorted by held expert, those held elsewhere
    last, and their group sizes placed at the layer's experts, every other
    group empty, so the products read the bank where it lies and no pair
    reaches another layer's experts.  On a TPU they run as the grouped
    matmul of ``megablox`` (a group without rows is never fetched),
    elsewhere as ``ragged_dot``; either way the rows of pairs held
    elsewhere are selected away, never weighted by zero, since the kernel
    leaves them unwritten.  ``counts`` holds each batch row's
    ``ROW_COUNTS``: pairs computed here, held elsewhere, held but dropped
    (a held pair whose sorted row lies outside the rows the placed group
    sizes give its expert), and held experts reached."""
    m = cfg.moe
    B, S, d = x.shape
    T, K, H = B * S, m.top_k, m.held
    xf = x.reshape(T, d)
    _, gate_vals, expert_ids = _route(cfg, p, xf)
    local, held = held_experts(cfg, guard, expert_ids)        # (T,K)
    flat = local.reshape(T * K)
    order = jnp.argsort(flat, stable=True)                    # (T*K,)
    sizes = jnp.bincount(flat, length=H + 1)[:H].astype(jnp.int32)
    placed = jax.lax.dynamic_update_slice(
        jnp.zeros((p["wu"].shape[0],), jnp.int32), sizes, (lidx * H,))
    xs = jnp.take(xf, order // K, axis=0)
    y = jax.lax.platform_dependent(
        xs, placed,
        tpu=functools.partial(_expert_mlp, cfg, p, functools.partial(
            grouped_megablox, interpret=False)),
        default=functools.partial(_expert_mlp, cfg, p, grouped_ragged))
    y = y.astype(xf.dtype)                                    # sorted
    back = jnp.zeros((T * K,), jnp.int32).at[order].set(
        jnp.arange(T * K, dtype=jnp.int32))
    y_tk = jnp.take(y, back, axis=0).reshape(T, K, d)
    y_tk = jnp.where(held[..., None], y_tk.astype(jnp.float32), 0.0)
    out = jnp.einsum("tkd,tk->td", y_tk, gate_vals)

    held_b = jnp.sum(held.reshape(B, S * K), axis=-1, dtype=jnp.int32)
    # the bank's expert whose rows hold each pair's sorted row
    group = jnp.searchsorted(jnp.cumsum(placed), back, side="right")
    dropped = held.reshape(T * K) & (group != lidx * H + flat)
    dropped_b = jnp.sum(dropped.reshape(B, S * K), axis=-1, dtype=jnp.int32)
    reached = jnp.zeros((B, H + 1), bool).at[
        jnp.arange(T * K) // (S * K), flat].set(True)
    read_b = jnp.sum(reached[:, :H], axis=-1, dtype=jnp.int32)
    counts = jnp.stack([held_b, S * K - held_b, dropped_b, read_b], -1)
    return out.astype(x.dtype).reshape(B, S, d), counts


#: the per-row counts of :func:`moe_dropless`, in its column order, under
#: their telemetry names
ROW_COUNTS = ("moe.pairs_held", "moe.pairs_elsewhere", "moe.pairs_dropped",
              "moe.experts_read")
#: named scopes of the served steps' operations, by layer kind
SCOPE_MOE = "guardian.moe"
SCOPE_ATTN = {SLIDING: "guardian.attn_window", FULL: "guardian.attn_full"}


# ---------------------------------------------------------------------------
# Model (attention blocks reuse the dense transformer pieces)
# ---------------------------------------------------------------------------

def layer_init(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    return {
        "attn": L.attention_init(k1, cfg),
        "moe": moe_init(k2, cfg),
        "norm1": L.norm_init(cfg),
        "norm2": L.norm_init(cfg),
    }


def init(rng, cfg: ModelConfig) -> Params:
    k_emb, k_layers = jax.random.split(rng)
    stacked = jax.vmap(lambda k: layer_init(k, cfg))(
        jax.random.split(k_layers, cfg.n_layers))
    return {
        "embed": L.embedding_init(k_emb, cfg),
        "layers": stacked,
        "norm_f": L.norm_init(cfg),
    }


def param_logical_axes(cfg: ModelConfig) -> Params:
    def stack(tree):
        return jax.tree.map(lambda axes: (None, *axes), tree,
                            is_leaf=lambda x: isinstance(x, tuple))
    return {
        "embed": L.embedding_axes(cfg),
        "layers": stack({
            "attn": L.attention_axes(cfg),
            "moe": moe_axes(cfg),
            "norm1": L.norm_axes(cfg),
            "norm2": L.norm_axes(cfg),
        }),
        "norm_f": L.norm_axes(cfg),
    }


def over_layers(cfg: ModelConfig, layers: Params, body: Callable, carry,
                remat: Optional[Callable] = None):
    """``carry = body(carry, p, lidx, kind)`` for every layer: a scan over
    the periods of ``cfg.layer_period``, each period unrolled, so that
    every layer's kind is a static string.  Each layer's weights are
    indexed from the stacked ``layers`` by its own index, so no period's
    weights are gathered into a slab first.  ``remat`` wraps the period."""
    kinds = cfg.layer_period

    def period(carry, first):
        for j, kind in enumerate(kinds):
            p = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
                a, first + j, keepdims=False), layers)
            carry = body(carry, p, first + j, kind)
        return carry, None

    if remat is not None:
        period = remat(period)
    firsts = jnp.arange(0, cfg.n_layers, len(kinds), dtype=jnp.int32)
    carry, _ = jax.lax.scan(period, carry, firsts)
    return carry


def _qkv(cfg, p, x, positions, kind):
    h = L.apply_norm(cfg, p["norm1"], x)
    B, S, _ = h.shape
    a = p["attn"]
    # the barrier keeps the heads' layout from reaching back into the
    # projections: without it the TPU compiler relayouts every layer's wq,
    # wk and wv in one copy of the stacked weights each step
    q, k, v = jax.lax.optimization_barrier(
        (h @ a["wq"], h @ a["wk"], h @ a["wv"]))
    if cfg.qkv_bias:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q, k = L.positions_rope(cfg, q, k, positions, kind)
    return q, k, v


def forward(cfg: ModelConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None, *,
            guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            remat: bool = False,
            dispatch: str = "scatter",
            remat_policy: str = "nothing") -> Tuple[jax.Array, jax.Array]:
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, guard)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def body(carry, p, lidx, kind):
        x, aux = carry
        q, k, v = _qkv(cfg, p, x, positions, kind)
        o = L.chunked_attention(q, k, v, causal=True,
                                window=cfg.window_of(kind), rules=rules)
        x = x + L.out_proj(cfg, p["attn"], o)
        h, a = moe_apply(cfg, p["moe"], L.apply_norm(cfg, p["norm2"], x),
                         guard, rules, dispatch)
        x = x + h
        if rules is not None:
            x = constrain(x, rules, ("batch", "seq", None))
        return x, aux + a

    wrap = None
    if remat:
        pol = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
               if remat_policy == "dots"
               else jax.checkpoint_policies.nothing_saveable)
        wrap = functools.partial(jax.checkpoint, policy=pol)
    x, aux = over_layers(cfg, params["layers"], body,
                         (x, jnp.float32(0.0)), wrap)
    x = L.apply_norm(cfg, params["norm_f"], x)
    logits = L.lm_logits(cfg, params["embed"], x)
    return logits, aux / cfg.n_layers


def loss_fn(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            remat: bool = True, aux_weight: float = 0.01,
            dispatch: str = "scatter",
            remat_policy: str = "nothing") -> jax.Array:
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits, aux = forward(cfg, params, inputs, guard=guard, rules=rules,
                          remat=remat, dispatch=dispatch,
                          remat_policy=remat_policy)
    return (L.softmax_cross_entropy(logits, labels, batch.get("mask"))
            + aux_weight * aux)


# ---------------------------------------------------------------------------
# Serving — same cache discipline as the dense model, dropless experts.
# With ``counts=True`` prefill and decode also return each batch row's
# ``ROW_COUNTS`` (B, 4), summed over the layers.
# ---------------------------------------------------------------------------

def _served_layers(params: Params) -> Tuple[Params, Params]:
    """(the stacked layers the period scan indexes by layer, the expert
    bank it leaves whole): a layer's experts are read from the bank where
    they lie, never sliced out of the stacks first."""
    layers = params["layers"]
    scanned = dict(layers, moe={"router": layers["moe"]["router"]})
    return scanned, expert_bank(layers["moe"])


def _moe_block(cfg, p, bank, lidx, x, guard):
    with jax.named_scope(SCOPE_MOE):
        return moe_dropless(cfg, dict(bank, router=p["moe"]["router"]),
                            L.apply_norm(cfg, p["norm2"], x), guard, lidx)


def prefill(cfg: ModelConfig, params: Params, cache: KV.PagedKVCache,
            tokens: jax.Array, *, guard: Optional[GuardSpec] = None,
            rules: Optional[ShardingRules] = None,
            positions: Optional[jax.Array] = None,
            counts: bool = False):
    B, S = tokens.shape
    x = L.embed_tokens(params["embed"], tokens, guard)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :], (B, S))

    def body(carry, p, lidx, kind):
        x, kc, vc, pairs = carry
        with jax.named_scope(SCOPE_ATTN[kind]):
            q, k, v = _qkv(cfg, p, x, positions, kind)
            tmp = dataclasses.replace(cache, k=kc, v=vc)
            tmp = KV.write_prefill_kv(tmp, lidx, k.astype(kc.dtype),
                                      v.astype(vc.dtype), guard)
            o = L.chunked_attention(q, k, v, causal=True,
                                    window=cfg.window_of(kind), rules=rules)
            x = x + L.out_proj(cfg, p["attn"], o)
        h, n = _moe_block(cfg, p, bank, lidx, x, guard)
        x = x + h
        if rules is not None:
            x = constrain(x, rules, ("batch", "seq", None))
        return x, tmp.k, tmp.v, pairs + n

    layers, bank = _served_layers(params)
    x, kc, vc, pairs = over_layers(
        cfg, layers, body,
        (x, cache.k, cache.v, jnp.zeros((B, len(ROW_COUNTS)), jnp.int32)))
    cache = dataclasses.replace(cache, k=kc, v=vc,
                                seq_lens=cache.seq_lens + S)
    x = L.apply_norm(cfg, params["norm_f"], x[:, -1:])
    logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
    return (cache, logits, pairs) if counts else (cache, logits)


def decode(cfg: ModelConfig, params: Params, cache: KV.PagedKVCache,
           tokens: jax.Array, *, guard: Optional[GuardSpec] = None,
           rules: Optional[ShardingRules] = None,
           positions: Optional[jax.Array] = None,
           counts: bool = False):
    B = tokens.shape[0]
    x = L.embed_tokens(params["embed"], tokens[:, None], guard)
    if positions is None:
        positions = cache.seq_lens[:, None]
    elif positions.ndim == 1:
        positions = positions[:, None]
    kv_len = cache.seq_lens + 1

    def body(carry, p, lidx, kind):
        x, kc, vc, pairs = carry
        window = cfg.window_of(kind)
        with jax.named_scope(SCOPE_ATTN[kind]):
            q, k, v = _qkv(cfg, p, x, positions, kind)
            tmp = dataclasses.replace(cache, k=kc, v=vc)
            tmp = KV.append_token_kv(tmp, lidx, k.astype(kc.dtype),
                                     v.astype(vc.dtype), guard)
            if tmp.global_paged:
                # a sliding layer reads only the pages of its window
                k_hist, v_hist, start = KV.gather_window_kv(
                    tmp, lidx, kv_len, window, guard)
            else:
                k_hist, v_hist = KV.gather_layer_kv(tmp, lidx, guard, rules)
                start = None
            o = L.decode_attention(q, k_hist.astype(q.dtype),
                                   v_hist.astype(q.dtype), kv_len,
                                   window=window, kv_start=start)
            x = x + L.out_proj(cfg, p["attn"], o)
        h, n = _moe_block(cfg, p, bank, lidx, x, guard)
        return x + h, tmp.k, tmp.v, pairs + n

    layers, bank = _served_layers(params)
    x, kc, vc, pairs = over_layers(
        cfg, layers, body,
        (x, cache.k, cache.v, jnp.zeros((B, len(ROW_COUNTS)), jnp.int32)))
    cache = dataclasses.replace(cache, k=kc, v=vc,
                                seq_lens=cache.seq_lens + 1)
    x = L.apply_norm(cfg, params["norm_f"], x)
    logits = L.lm_logits(cfg, params["embed"], x)[:, 0]
    return (cache, logits, pairs) if counts else (cache, logits)
