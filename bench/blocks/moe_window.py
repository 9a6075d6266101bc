"""The sparse-expert decoder with sliding-window and full attention layers
(Mellum2): its configuration, its random weights, its plain float32
reference and its lower-precision control, and its operation and byte
counts (the interface is in ``bench/blocks``).

Block: pre-norm (RMSNorm) GQA attention then a pre-norm sparse expert
layer, each added to the residual.  ``layer_types`` gives each layer's
kind: a sliding layer attends its last ``sliding_window`` keys (itself
included) with default RoPE; a full layer attends every earlier key with
YaRN RoPE, whose cos and sin carry the attention factor, so q.k scales by
its square.  The q.k scale is 1/sqrt(head_dim).  The router is a softmax
over every published expert, the top ``num_experts_per_tok`` are kept and
renormalised over themselves (``norm_topk_prob``); this chip holds
``num_experts`` of them, from ``expert_base`` on, and only their terms
are summed: what the experts held on other chips would add is left out,
here as in the program.  Experts are SwiGLU.  Final norm, then the
untied output head.

The reference imports nothing of the program.  It runs a full causal
forward over whole token sequences under
``jax.default_matmul_precision("highest")``, one layer at a time (one
compiled program per layer kind), attention over blocks of queries and
the held experts one after another, densely over every token with each
token's gate weight (zero where the token did not choose the expert), so
that 4 rows of 8192 fit beside the weights once the pool is freed.  The
sequence is cut after the last position read (causal: nothing later
changes it).

``quant="fp8"`` is the control: every projection (q, k, v, o, the router,
the experts and the head) computes from float8 e4m3 inputs, scaled per
output channel and per row, accumulating in float32: the step below the
bfloat16 the configuration states.

Counts are what the algorithm needs, whatever implements it.  A decode
step reads attention, router, norms and head once, and of each layer's
held experts those its ``max_batch`` rows are expected to reach under
uniform top-k routing; a token computes ``top_k * held / experts`` expert
pairs a layer; a sliding layer reads and attends min(ctx, window) keys,
a full layer ctx.  A multiply-add is two operations.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
NORM_SPREAD = 0.1       # norm scales 1 + N(0, 0.1)
KV_BYTES = 2            # logical KV element: bfloat16
SLIDING, FULL = "sliding", "full"
KINDS = {"sliding_attention": SLIDING, "full_attention": FULL}
QUERY_BLOCK = 256       # reference queries per attention block


# --------------------------------------------------------------------- #
# Configuration                                                         #
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Model:
    """The model a configuration file describes, with this chip's share."""

    name: str
    arch: str
    layer_types: Tuple[str, ...]   # SLIDING / FULL, one per layer
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    norm_eps: float
    window: int
    rope_theta: float              # both kinds' theta (parse checks)
    yarn_factor: float
    yarn_original_max: int
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_attention_factor: float
    experts: int                   # the router's published width
    top_k: int
    held: int                      # experts held on this chip
    expert_base: int               # the first of them
    d_expert: int
    rows: int                      # serving rows a decode step holds
    dtype: str                     # dtype the weights are served in

    @property
    def layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)


def parse(raw: Dict) -> Model:
    """The model the configuration's keys (departures applied) describe;
    refuses keys this block does not model."""
    rope = raw["rope_parameters"]
    full, slide = rope["full_attention"], rope["sliding_attention"]
    kinds = tuple(KINDS[k] for k in raw["layer_types"])
    model = Model(
        name=raw["name"], arch=raw["arch"], layer_types=kinds,
        d_model=raw["hidden_size"], heads=raw["num_attention_heads"],
        kv_heads=raw["num_key_value_heads"], head_dim=raw["head_dim"],
        vocab=raw["vocab_size"], norm_eps=float(raw["rms_norm_eps"]),
        window=raw["sliding_window"], rope_theta=float(slide["rope_theta"]),
        yarn_factor=float(full["factor"]),
        yarn_original_max=int(full["original_max_position_embeddings"]),
        yarn_beta_fast=float(full["beta_fast"]),
        yarn_beta_slow=float(full["beta_slow"]),
        yarn_attention_factor=float(full["attention_factor"]),
        experts=raw["num_experts_published"],
        top_k=raw["num_experts_per_tok"], held=raw["num_experts"],
        expert_base=raw["expert_base"],
        d_expert=raw["moe_intermediate_size"],
        rows=raw["serve"]["max_batch"],
        dtype={"bfloat16": "bfloat16", "float32": "float32"}[
            raw["torch_dtype"]])
    modelled = (
        raw["hidden_act"] == "silu" and not raw["attention_bias"]
        and not raw["tie_word_embeddings"] and raw["norm_topk_prob"]
        and raw["use_sliding_window"] and raw["max_window_layers"] == 0
        and set(raw["mlp_layer_types"]) == {"sparse"}
        and len(kinds) == raw["num_hidden_layers"]
        and slide["rope_type"] == "default" and full["rope_type"] == "yarn"
        and float(full["rope_theta"]) == model.rope_theta
        and 0 <= model.expert_base
        and model.expert_base + model.held <= model.experts)
    if not modelled:
        raise ValueError(f"{model.name}: only untied SwiGLU expert blocks "
                         "with renormalised top-k, no attention bias, "
                         "default RoPE on sliding and YaRN on full layers "
                         "of one theta, and a held share inside the "
                         "router's experts are modelled")
    return model


def program_fields(m: Model) -> Dict:
    """The program's ``ModelConfig`` fields this block fixes.  The expert
    share and YaRN are the program's own dataclasses, so they are built
    here, from the configuration's numbers, to be compared."""
    from repro.configs import MoEConfig, YarnConfig

    return dict(
        n_layers=m.layers, d_model=m.d_model, n_heads=m.heads,
        n_kv_heads=m.kv_heads, head_dim=m.head_dim, vocab=m.vocab,
        tie_embeddings=False, norm="rmsnorm", dtype=m.dtype,
        rope_theta=m.rope_theta, attn_window=m.window,
        layer_types=m.layer_types,
        moe=MoEConfig(num_experts=m.experts, top_k=m.top_k,
                      d_ff_expert=m.d_expert, experts_held=m.held,
                      expert_base=m.expert_base),
        yarn=YarnConfig(factor=m.yarn_factor,
                        original_max_position_embeddings=m.yarn_original_max,
                        beta_fast=m.yarn_beta_fast,
                        beta_slow=m.yarn_beta_slow,
                        attention_factor=m.yarn_attention_factor))


# --------------------------------------------------------------------- #
# Weights                                                               #
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnums=0)
def make_weights(m: Model, key: jax.Array) -> Dict[str, jax.Array]:
    """Matrices N(0, 1/fan_in) (output projections further scaled by
    1/sqrt(2 layers)), router N(0, 1/d) in float32, embedding N(0, 0.02),
    norms near identity."""
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m.dtype]
    L, d, V, E, H, f = (m.layers, m.d_model, m.vocab, m.experts, m.held,
                        m.d_expert)
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out_scale = 1.0 / math.sqrt(2 * L)
    shapes = {
        "wq": ((L, d, q), 1.0, dt), "wk": ((L, d, kv), 1.0, dt),
        "wv": ((L, d, kv), 1.0, dt), "wo": ((L, q, d), out_scale, dt),
        "router": ((L, d, E), 1.0, F32),
        "wg": ((L, H, d, f), 1.0, dt), "wu": ((L, H, d, f), 1.0, dt),
        "wd": ((L, H, f, d), out_scale, dt), "head": ((d, V), 1.0, dt),
    }
    keys = iter(jax.random.split(key, len(shapes) + 4))
    w = {}
    for name, (shape, scale, dtype) in shapes.items():
        std = scale / math.sqrt(shape[-2])
        w[name] = (jax.random.normal(next(keys), shape, F32)
                   * std).astype(dtype)
    w["embed"] = (jax.random.normal(next(keys), (V, d), F32)
                  * 0.02).astype(dt)
    for name, shape in (("norm1", (L, d)), ("norm2", (L, d)),
                        ("norm_f", (d,))):
        w[name + ".scale"] = 1.0 + NORM_SPREAD * jax.random.normal(
            next(keys), shape, F32)
    return w


def to_program_tree(m: Model, w: Dict[str, jax.Array], like) -> Dict:
    """The weights in the program's parameter tree (``like`` is the tree a
    ``ServeEngine`` built); shares the arrays, copies nothing."""
    tree = {
        "embed": {"table": w["embed"], "head": w["head"]},
        "layers": {
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "moe": {k: w[k] for k in ("router", "wg", "wu", "wd")},
            "norm1": {"scale": w["norm1.scale"]},
            "norm2": {"scale": w["norm2.scale"]},
        },
        "norm_f": {"scale": w["norm_f.scale"]},
    }
    want = jax.tree.structure(like)
    if jax.tree.structure(tree) != want:
        raise ValueError(f"program parameter tree changed: {want}")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(like)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"program parameter {b.shape} {b.dtype} "
                             f"!= benchmark {a.shape} {a.dtype}")
    return tree


# --------------------------------------------------------------------- #
# Reference                                                             #
# --------------------------------------------------------------------- #

def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` through float8 e4m3, scaled along ``axis`` to its range."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def _mm(x: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...i,io->...o", x, w)


def _norm(m: Model, x, scale):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + m.norm_eps) * scale


def inverse_frequencies(m: Model, kind: str) -> Tuple[np.ndarray, float]:
    """A layer kind's rotary inverse frequencies and the factor on its cos
    and sin: default RoPE on sliding layers; on full layers YaRN, blended
    between interpolated (/ factor) and extrapolated frequencies over a
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times in the original context (truncated to whole dimensions)."""
    D, theta = m.head_dim, m.rope_theta
    base = 1.0 / theta ** (np.arange(0, D, 2) / D)
    if kind == SLIDING:
        return base, 1.0

    def dim(turns):
        return D * math.log(m.yarn_original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(m.yarn_beta_fast)), 0)
    hi = min(math.ceil(dim(m.yarn_beta_slow)), D - 1)
    ramp = np.clip((np.arange(D // 2) - lo) / max(hi - lo, 0.001), 0, 1)
    inv = base / m.yarn_factor * ramp + base * (1.0 - ramp)
    return inv, m.yarn_attention_factor


def _rope(m: Model, kind: str, x, pos):
    """x (S, T, H, D); pos (T,).  The two halves rotate together."""
    inv, factor = inverse_frequencies(m, kind)
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)
    cos = (jnp.cos(ang) * factor)[None, :, None]
    sin = (jnp.sin(ang) * factor)[None, :, None]
    x1, x2 = x[..., :m.head_dim // 2], x[..., m.head_dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(m: Model, kind: str, q, k, v):
    """Causal attention, windowed on sliding layers, one block of queries
    at a time.  q (S, T, H, D); k, v (S, T, H, D)."""
    S, T = q.shape[:2]
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK, 1)
        s = jnp.einsum("sqhd,skhd->shqk", qb, k) / math.sqrt(m.head_dim)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        seen = kpos[None, :] <= qpos[:, None]
        if kind == SLIDING:
            seen = seen & (kpos[None, :] > qpos[:, None] - m.window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        return jnp.einsum("shqk,skhd->sqhd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(T // QUERY_BLOCK))  # (n,S,QB,H,D)
    return jnp.moveaxis(o, 0, 1).reshape(S, T, m.heads, m.head_dim)


def _experts(m: Model, quant, h, p):
    """The held experts' part of the expert layer, h (S, T, d)."""
    probs = jax.nn.softmax(_mm(h, p["router"], quant), axis=-1)
    gate, ids = jax.lax.top_k(probs, m.top_k)
    gate = gate / jnp.sum(gate, -1, keepdims=True)

    def expert(acc, inp):
        e, wg, wu, wd = inp
        g = jnp.sum(jnp.where(ids == m.expert_base + e, gate, 0.0), -1)
        y = _mm(jax.nn.silu(_mm(h, wg, quant)) * _mm(h, wu, quant), wd,
                quant)
        return acc + g[..., None] * y, None

    acc, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                          (jnp.arange(m.held), p["wg"], p["wu"], p["wd"]))
    return acc


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _layer(m: Model, quant, kind: str, x, w: Dict, layer):
    S, T, _ = x.shape
    p = {n: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
         for n, a in w.items()}
    pos = jnp.arange(T)
    h = _norm(m, x, p["norm1.scale"])
    q = _mm(h, p["wq"], quant).reshape(S, T, m.heads, m.head_dim)
    k = _mm(h, p["wk"], quant).reshape(S, T, m.kv_heads, m.head_dim)
    v = _mm(h, p["wv"], quant).reshape(S, T, m.kv_heads, m.head_dim)
    q, k = _rope(m, kind, q, pos), _rope(m, kind, k, pos)
    group = m.heads // m.kv_heads
    o = _attention(m, kind, q, jnp.repeat(k, group, axis=2),
                   jnp.repeat(v, group, axis=2))
    x = x + _mm(o.reshape(S, T, m.heads * m.head_dim), p["wo"], quant)
    return x + _experts(m, quant, _norm(m, x, p["norm2.scale"]), p)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(m: Model, quant, x, w: Dict, read):
    """Logits (S, K, V) at positions ``read`` (S, K) of each row."""
    h = jnp.take_along_axis(x, read[:, :, None], axis=1)
    return _mm(_norm(m, h, w["norm_f.scale"]), w["head"], quant)


@functools.partial(jax.jit, static_argnums=0)
def _embed(m: Model, w: Dict, tokens):
    return jnp.take(w["embed"], tokens, axis=0).astype(F32)


def logits_at(m: Model, w: Dict, tokens: np.ndarray, read: np.ndarray,
              quant: Optional[str] = None) -> jax.Array:
    """Full forward over ``tokens`` (S, T); logits at ``read`` (S, K)."""
    read = np.asarray(read)
    upto = -(-(int(read.max()) + 1) // QUERY_BLOCK) * QUERY_BLOCK
    tokens = np.asarray(tokens)[:, :upto]
    if tokens.shape[1] < upto:
        tokens = np.pad(tokens, ((0, 0), (0, upto - tokens.shape[1])))
    layer_w = {n: w[n] for n in w
               if n not in ("embed", "head", "norm_f.scale")}
    with jax.default_matmul_precision("highest"):
        x = _embed(m, w, jnp.asarray(tokens))
        for layer, kind in enumerate(m.layer_types):
            x = _layer(m, quant, kind, x, layer_w, jnp.int32(layer))
        return _logits(m, quant, x, {"norm_f.scale": w["norm_f.scale"],
                                     "head": w["head"]}, jnp.asarray(read))


# --------------------------------------------------------------------- #
# Counts                                                                #
# --------------------------------------------------------------------- #

def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def attention_params(m: Model) -> int:
    q = m.heads * m.head_dim
    kv = m.kv_heads * m.head_dim
    return m.d_model * (q + 2 * kv) + q * m.d_model


def expert_params(m: Model) -> int:
    return 3 * m.d_model * m.d_expert


def experts_reached(m: Model, rows: int) -> float:
    """Held experts of one layer that ``rows`` tokens are expected to
    reach when each picks ``top_k`` of the ``experts`` uniformly."""
    return m.held * (1.0 - ((m.experts - m.top_k) / m.experts) ** rows)


def held_pairs(m: Model) -> float:
    """Expert pairs a token computes on this chip, each layer."""
    return m.top_k * m.held / m.experts


def _shared_bytes(m: Model) -> float:
    """Attention, router, norms and head: read by every step."""
    return (m.layers * attention_params(m) + m.d_model * m.vocab) \
        * _itemsize(m.dtype) \
        + m.layers * m.d_model * m.experts * 4 \
        + (2 * m.layers + 1) * m.d_model * 4


def weight_bytes_per_step(m: Model) -> float:
    """Weights one decode step reads: attention, router, norms and head,
    and the held experts that the configuration's rows are expected to
    reach.  The embedding rows a step gathers are a few KiB and not
    counted."""
    return _shared_bytes(m) + m.layers * experts_reached(m, m.rows) \
        * expert_params(m) * _itemsize(m.dtype)


def keys_attended(m: Model, ctx: int) -> int:
    """Keys one query at context ``ctx`` (itself included) attends, summed
    over the layers: min(ctx, window) on sliding, ctx on full layers."""
    return m.count(SLIDING) * min(ctx, m.window) + m.count(FULL) * ctx


def _causal_keys(m: Model, prompt: int) -> int:
    """Keys the queries of a whole prompt attend, summed over layers."""
    w = min(prompt, m.window)
    window = w * (w + 1) // 2 + (prompt - w) * m.window
    return m.count(SLIDING) * window + m.count(FULL) \
        * (prompt * (prompt + 1) // 2)


def _token_matmul_flops(m: Model) -> float:
    return 2 * (m.layers * (attention_params(m) + m.d_model * m.experts
                            + held_pairs(m) * expert_params(m)))


def decode_token_flops(m: Model, ctx: int) -> float:
    """One decoded token attending ``ctx`` cached tokens (itself included)."""
    return _token_matmul_flops(m) + 2 * m.d_model * m.vocab \
        + 4 * m.heads * m.head_dim * keys_attended(m, ctx)


def kv_bytes_per_key(m: Model) -> int:
    """K and V of one position in one layer, at bfloat16."""
    return 2 * m.kv_heads * m.head_dim * KV_BYTES


def decode_token_bytes(m: Model, ctx: int) -> float:
    """KV one decoded token reads (its keys in every layer) and writes (one
    position in every layer)."""
    return kv_bytes_per_key(m) * (keys_attended(m, ctx) + m.layers)


def prefill_flops(m: Model, prompt: int) -> float:
    """A prompt of ``prompt`` tokens, logits at its last position only."""
    return _token_matmul_flops(m) * prompt + 2 * m.d_model * m.vocab \
        + 4 * m.heads * m.head_dim * _causal_keys(m, prompt)


def prefill_bytes(m: Model, prompt: int, rows: int = 1) -> float:
    """A prefill step of ``rows`` prompts of ``prompt`` tokens: every
    weight once (all held experts: a wave's tokens reach each of them),
    and the KV of every position written."""
    experts = m.layers * m.held * expert_params(m) * _itemsize(m.dtype)
    return _shared_bytes(m) + experts \
        + rows * prompt * m.layers * kv_bytes_per_key(m)
