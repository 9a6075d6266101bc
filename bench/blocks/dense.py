"""The dense decoder block: its configuration, its random weights, its
plain float32 reference and its lower-precision control, and its
operation and byte counts (the interface is in ``bench/blocks``).

Block: pre-norm attention then a pre-norm SwiGLU MLP, each added to the
residual; rotary embedding on the first ``rope_dims`` of each head (the
two halves rotate together); causal softmax attention with scale
1/sqrt(head_dim); final norm, then the output head (the embedding table
when tied).

The reference imports nothing of the program.  It takes the benchmark's
weights (``make_weights``) and a configuration file's sizes, runs a full
causal forward pass over whole token sequences under
``jax.default_matmul_precision("highest")``, one layer at a time (one
compiled layer program reused for every layer), so it fits beside the
weights after the served model's KV pool is freed.

``quant="fp8"`` is the control: every projection (q, k, v, o, the MLP
and the head) computes from float8 e4m3 inputs, the weights scaled per
output channel and the activations per row, accumulating in float32.
That is the step below the bfloat16 the configurations state.

Counts are what the algorithm needs, whatever implements it: weights at
the dtype they are served in and read once per step, and the logical KV
cache at bfloat16 and the logical head_dim, of the tokens a request really
attends (pads not counted).  A multiply-add is two operations.
``pool_bytes``, which no reader takes yet, is the exception: it is the
page pool as the program allocates it, in the served dtype with lanes
padded to 128, for the memory budget.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import LANES, PAGE_SIZE

F32 = jnp.float32
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
NORM_SPREAD = 0.1       # norm scales 1 + N(0, 0.1), biases N(0, 0.1)
KV_BYTES = 2            # logical KV element: bfloat16

#: published keys this block does not model; a configuration runs only
#: where its departures set them to null
UNMODELLED = ("scale_emb", "scale_depth", "dim_model_base")


# --------------------------------------------------------------------- #
# Configuration                                                         #
# --------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Model:
    """The dense decoder a configuration file describes."""

    name: str
    arch: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str                 # "layernorm" (with bias) or "rmsnorm"
    norm_eps: float
    rope_fraction: float      # share of head_dim that rotates
    rope_theta: float
    tied: bool
    dtype: str                # dtype the weights are served in

    @property
    def rope_dims(self) -> int:
        return int(self.head_dim * self.rope_fraction) // 2 * 2


def parse(raw: Dict) -> Model:
    """The model the configuration's keys (departures applied) describe;
    refuses keys this block does not model."""
    hidden = raw["hidden_size"]
    heads = raw["num_attention_heads"]
    if "norm_eps" in raw:
        norm, eps = "layernorm", raw["norm_eps"]
    else:
        norm, eps = "rmsnorm", raw["rms_norm_eps"]
    dtypes = {"bfloat16": "bfloat16", "float32": "float32"}
    model = Model(
        name=raw["name"], arch=raw["arch"],
        layers=raw["num_hidden_layers"], d_model=hidden, heads=heads,
        kv_heads=raw.get("num_key_value_heads", heads),
        head_dim=raw.get("head_dim", hidden // heads),
        d_ff=raw["intermediate_size"], vocab=raw["vocab_size"],
        norm=norm, norm_eps=float(eps),
        rope_fraction=float(raw.get("rope_pct", 1.0)),
        rope_theta=float(raw["rope_theta"]),
        tied=bool(raw["tie_word_embeddings"]),
        dtype=dtypes[raw["torch_dtype"]])
    if raw["hidden_act"] != "silu" or raw.get("use_qkv_bias", False) \
            or any(raw.get(k) is not None for k in UNMODELLED):
        raise ValueError(f"{model.name}: only SwiGLU blocks without a qkv "
                         f"bias or any of {UNMODELLED} are modelled")
    return model


def program_fields(m: Model) -> Dict:
    """The program's ``ModelConfig`` fields this block fixes."""
    return dict(n_layers=m.layers, d_model=m.d_model, n_heads=m.heads,
                n_kv_heads=m.kv_heads, head_dim=m.head_dim, d_ff=m.d_ff,
                vocab=m.vocab, tie_embeddings=m.tied, norm=m.norm,
                dtype=m.dtype, rope_theta=m.rope_theta)


# --------------------------------------------------------------------- #
# Weights                                                               #
# --------------------------------------------------------------------- #

@functools.partial(jax.jit, static_argnums=0)
def make_weights(m: Model, key: jax.Array) -> Dict[str, jax.Array]:
    """Matrices N(0, 1/fan_in) (output projections further scaled by
    1/sqrt(2 layers)), embedding N(0, 0.02), norms near identity."""
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[m.dtype]
    L, d, ff, V = m.layers, m.d_model, m.d_ff, m.vocab
    q, kv = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out_scale = 1.0 / math.sqrt(2 * L)
    shapes = {
        "wq": ((L, d, q), 1.0), "wk": ((L, d, kv), 1.0),
        "wv": ((L, d, kv), 1.0), "wo": ((L, q, d), out_scale),
        "wg": ((L, d, ff), 1.0), "wu": ((L, d, ff), 1.0),
        "wd": ((L, ff, d), out_scale),
    }
    if not m.tied:
        shapes["head"] = ((d, V), 1.0)
    keys = iter(jax.random.split(key, len(shapes) + 8))
    w = {}
    for name, (shape, scale) in shapes.items():
        std = scale / math.sqrt(shape[-2])
        w[name] = (jax.random.normal(next(keys), shape, jnp.float32)
                   * std).astype(dt)
    w["embed"] = (jax.random.normal(next(keys), (V, d), jnp.float32)
                  * 0.02).astype(dt)
    for name, shape in (("norm1", (L, d)), ("norm2", (L, d)),
                        ("norm_f", (d,))):
        w[name + ".scale"] = 1.0 + NORM_SPREAD * jax.random.normal(
            next(keys), shape, jnp.float32)
        if m.norm == "layernorm":
            w[name + ".bias"] = NORM_SPREAD * jax.random.normal(
                next(keys), shape, jnp.float32)
    return w


def to_program_tree(m: Model, w: Dict[str, jax.Array], like) -> Dict:
    """The weights in the program's parameter tree (``like`` is the tree a
    ``ServeEngine`` built); shares the arrays, copies nothing."""

    def norm(name):
        p = {"scale": w[name + ".scale"]}
        if m.norm == "layernorm":
            p["bias"] = w[name + ".bias"]
        return p

    embed = {"table": w["embed"]}
    if not m.tied:
        embed["head"] = w["head"]
    tree = {
        "embed": embed,
        "layers": {
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: w[k] for k in ("wg", "wu", "wd")},
            "norm1": norm("norm1"),
            "norm2": norm("norm2"),
        },
        "norm_f": norm("norm_f"),
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


def _norm(m: Model, x, scale, bias):
    if m.norm == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + m.norm_eps) * scale + bias
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + m.norm_eps) * scale


def _rope(m: Model, x, pos):
    """x (S, T, H, D); pos (S, T)."""
    r = m.rope_dims
    freqs = 1.0 / (m.rope_theta ** (np.arange(0, r, 2) / r))
    ang = pos.astype(F32)[..., None] * jnp.asarray(freqs, F32)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def _layer_weights(w: Dict, layer, names):
    return {n: jax.lax.dynamic_index_in_dim(w[n], layer, 0, keepdims=False)
            for n in names if n in w}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(m: Model, quant, x, w: Dict, layer):
    S, T, _ = x.shape
    p = _layer_weights(w, layer, ("wq", "wk", "wv", "wo", "wg", "wu", "wd",
                                  "norm1.scale", "norm1.bias",
                                  "norm2.scale", "norm2.bias"))
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (S, T))
    h = _norm(m, x, p["norm1.scale"], p.get("norm1.bias"))
    q = _mm(h, p["wq"], quant).reshape(S, T, m.heads, m.head_dim)
    k = _mm(h, p["wk"], quant).reshape(S, T, m.kv_heads, m.head_dim)
    v = _mm(h, p["wv"], quant).reshape(S, T, m.kv_heads, m.head_dim)
    q, k = _rope(m, q, pos), _rope(m, k, pos)
    group = m.heads // m.kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("sqhd,skhd->shqk", q, k) / math.sqrt(m.head_dim)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    o = jnp.einsum("shqk,skhd->sqhd", jax.nn.softmax(s, axis=-1), v)
    x = x + _mm(o.reshape(S, T, m.heads * m.head_dim), p["wo"], quant)
    h = _norm(m, x, p["norm2.scale"], p.get("norm2.bias"))
    gate = jax.nn.silu(_mm(h, p["wg"], quant)) * _mm(h, p["wu"], quant)
    return x + _mm(gate, p["wd"], quant)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logits(m: Model, quant, x, w: Dict, read):
    """Logits (S, K, V) at positions ``read`` (S, K) of each row."""
    h = jnp.take_along_axis(x, read[:, :, None], axis=1)
    h = _norm(m, h, w["norm_f.scale"], w.get("norm_f.bias"))
    head = w["embed"].T if m.tied else w["head"]
    return _mm(h, head, quant)


@functools.partial(jax.jit, static_argnums=0)
def _embed(m: Model, w: Dict, tokens):
    return jnp.take(w["embed"], tokens, axis=0).astype(F32)


def logits_at(m: Model, w: Dict, tokens: np.ndarray, read: np.ndarray,
              quant: Optional[str] = None) -> jax.Array:
    """Full forward over ``tokens`` (S, T); logits at ``read`` (S, K)."""
    layer_w = {n: w[n] for n in w if n not in ("embed", "head")
               and not n.startswith("norm_f")}
    head_w = {n: w[n] for n in w if n in ("embed", "head")
              or n.startswith("norm_f")}
    with jax.default_matmul_precision("highest"):
        x = _embed(m, w, jnp.asarray(tokens))
        for layer in range(m.layers):
            x = _layer(m, quant, x, layer_w, jnp.int32(layer))
        return _logits(m, quant, x, head_w, jnp.asarray(read))


# --------------------------------------------------------------------- #
# Counts                                                                #
# --------------------------------------------------------------------- #

def _itemsize(dtype: str) -> int:
    return {"bfloat16": 2, "float32": 4}[dtype]


def layer_matmul_params(m: Model) -> int:
    q = m.heads * m.head_dim
    kv = m.kv_heads * m.head_dim
    return m.d_model * (q + 2 * kv) + q * m.d_model + 3 * m.d_model * m.d_ff


def head_params(m: Model) -> int:
    return m.d_model * m.vocab


def weight_bytes_per_step(m: Model) -> int:
    """Weights one step reads: every layer matrix and the output head
    (the tied table doubles as the head), plus float32 norm parameters.
    The embedding rows a step gathers are a few KiB and not counted."""
    norms = (2 * m.layers + 1) * m.d_model * (2 if m.norm == "layernorm"
                                              else 1)
    mats = m.layers * layer_matmul_params(m) + head_params(m)
    return mats * _itemsize(m.dtype) + norms * 4


def kv_bytes_per_token(m: Model) -> int:
    return m.layers * 2 * m.kv_heads * m.head_dim * KV_BYTES


def pool_bytes(m: Model, pages: int) -> int:
    """The program's K and V page pools in the served dtype, head_dim
    padded to 128 lanes."""
    lanes = -(-m.head_dim // LANES) * LANES
    return 2 * m.layers * pages * PAGE_SIZE * m.kv_heads * lanes \
        * _itemsize(m.dtype)


def attention_flops(m: Model, queries: int, keys: int) -> int:
    """QK^T and PV for ``queries`` rows each attending ``keys`` keys, all
    layers.  For causal prefill pass the summed key counts as ``keys``
    with ``queries=1``."""
    return 4 * m.layers * m.heads * m.head_dim * queries * keys


def decode_token_flops(m: Model, ctx: int) -> int:
    """One decoded token attending ``ctx`` cached tokens (itself included)."""
    return 2 * (m.layers * layer_matmul_params(m) + head_params(m)) \
        + attention_flops(m, 1, ctx)


def decode_token_bytes(m: Model, ctx: int) -> int:
    """KV one decoded token reads (``ctx`` tokens) and writes (one)."""
    return kv_bytes_per_token(m) * (ctx + 1)


def prefill_flops(m: Model, prompt: int) -> int:
    """A prompt of ``prompt`` tokens, logits at its last position only."""
    causal_keys = prompt * (prompt + 1) // 2
    return 2 * m.layers * layer_matmul_params(m) * prompt \
        + 2 * head_params(m) + attention_flops(m, 1, causal_keys)
