"""Unified model API — one surface over all six families.

``get_model(cfg)`` returns a :class:`ModelAPI` with:

    init(rng)                     -> params
    param_logical_axes()          -> logical-axis pytree (matches params)
    loss(params, batch, ...)      -> scalar loss           [train shapes]
    init_cache(batch, max_len, *, dtype, slots)
                                  -> cache pytree          [serve shapes]
    prefill(params, cache, batch) -> (cache, last_logits)
    decode(params, cache, tokens) -> (cache, logits)
    batch_specs(shape)            -> ShapeDtypeStruct dict for the batch
    counters                      -> names of the per-row counts that
                                     prefill/decode append as a third
                                     output, (B, len(counters)) int32,
                                     when called with ``counts=True``

and what a serve engine asks of a model, so that the engine never reads
the family or its fields:

    global_paged                  -> whether the family also serves from
                                     the global paged KV layout
    prefill_input(tokens)         -> the prefill batch for (B, S) tokens
    step_id                       -> the model's part of its compiled
                                     steps' names
    fence_sizes                   -> the static fences: space -> size
    key_counts(start, stop)       -> attention keys per layer kind that
                                     queries at [start, stop) attend

``batch_specs`` is the assignment's ``input_specs()``: weak-type-correct,
shardable stand-ins for every model input, no device allocation.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FULL, SLIDING, ModelConfig, ShapeConfig
from repro.core.partition import next_pow2
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import kvcache as KV
from repro.models import moe as MOE
from repro.models import transformer as TF
from repro.models import vlm as VLM
from repro.models import xlstm as XL

# frontend stubs: source frames / image patches per request
SRC_FRAMES = 1_024       # seamless encoder input length (frame embeddings)
N_PATCHES = 256          # qwen2-vl patches per request

#: per-tenant counters of the keys attended in sliding-window layers and in
#: full layers (``ModelAPI.key_counts``)
KEYS_WINDOW = "attn.keys_window"
KEYS_FULL = "attn.keys_full"


def _keys_upto(n: int, window: int) -> int:
    """Keys that queries at positions 0..n-1 attend under causal attention
    over the last ``window`` positions (0: all of them)."""
    if not window or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig
    init: Callable
    param_logical_axes: Callable
    loss: Callable
    init_cache: Callable
    prefill: Callable
    decode: Callable
    batch_specs: Callable
    counters: tuple = ()
    global_paged: bool = False
    prefill_input: Callable = lambda tokens: {"tokens": tokens}

    @property
    def step_id(self) -> str:
        """The config's name and a digest of its whole ``repr``: configs
        that differ in any field, shapes or not, compile apart, and the
        name is the same in every process (the persistent compile cache
        finds it again)."""
        digest = hashlib.sha256(repr(self.cfg).encode()).hexdigest()
        return f"{self.cfg.name}:{digest[:16]}"

    @property
    def fence_sizes(self) -> Dict[str, int]:
        """The static fences of the model's index spaces: the vocabulary,
        and the expert bank this chip holds."""
        sizes = {"vocab": next_pow2(self.cfg.vocab)}
        if self.cfg.moe:
            sizes["expert"] = next_pow2(self.cfg.moe.held)
        return sizes

    @functools.cached_property
    def _key_layers(self) -> Tuple[Tuple[str, int, int], ...]:
        kinds = self.cfg.layer_kinds
        return tuple((name, kinds.count(kind), self.cfg.window_of(kind))
                     for name, kind in ((KEYS_WINDOW, SLIDING),
                                        (KEYS_FULL, FULL))
                     if kinds.count(kind))

    def key_counts(self, start: int, stop: int) -> Dict[str, int]:
        """The keys that queries at positions [start, stop) attend, summed
        over the sliding and over the full layers (every position is
        written, pads too)."""
        return {name: layers * (_keys_upto(stop, window)
                                - _keys_upto(start, window))
                for name, layers, window in self._key_layers}


def _tok_spec(b, s):
    return jax.ShapeDtypeStruct((b, s), jnp.int32)


def get_model(cfg: ModelConfig) -> ModelAPI:
    fam = cfg.family
    if (cfg.layer_types or cfg.yarn) and fam != "moe":
        # the other families would serve every layer alike, with plain RoPE
        raise ValueError(
            f"layer_types and yarn are honoured by the moe family only, "
            f"not by {fam!r}")
    if fam in ("dense", "vlm"):
        return _dense_api(cfg) if fam == "dense" else _vlm_api(cfg)
    if fam == "moe":
        return _moe_api(cfg)
    if fam == "hybrid":
        return _hybrid_api(cfg)
    if fam == "ssm":
        return _ssm_api(cfg)
    if fam == "encdec":
        return _encdec_api(cfg)
    raise ValueError(f"unknown family {fam!r}")


# ---------------------------------------------------------------------------

def _dense_api(cfg: ModelConfig) -> ModelAPI:
    def batch_specs(shape: ShapeConfig):
        if shape.kind == "train":
            return {"tokens": _tok_spec(shape.global_batch,
                                        shape.seq_len + 1)}
        if shape.kind == "prefill":
            return {"tokens": _tok_spec(shape.global_batch, shape.seq_len)}
        return {"tokens": jax.ShapeDtypeStruct((shape.global_batch,),
                                               jnp.int32)}

    return ModelAPI(
        cfg=cfg,
        init=lambda rng: TF.init(rng, cfg),
        param_logical_axes=lambda: TF.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: TF.loss_fn(cfg, params, batch,
                                                    **kw),
        init_cache=lambda batch, max_len, **kw: KV.init_kv_cache(
            cfg, batch, max_len, **kw),
        prefill=lambda params, cache, batch, **kw: TF.prefill(
            cfg, params, cache, batch["tokens"], **kw),
        decode=lambda params, cache, tokens, **kw: TF.decode(
            cfg, params, cache, tokens, **kw),
        batch_specs=batch_specs,
        global_paged=True,
    )


def _moe_api(cfg: ModelConfig) -> ModelAPI:
    dense = _dense_api(cfg)
    return dataclasses.replace(
        dense,
        init=lambda rng: MOE.init(rng, cfg),
        param_logical_axes=lambda: MOE.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: MOE.loss_fn(cfg, params, batch,
                                                     **kw),
        prefill=lambda params, cache, batch, **kw: MOE.prefill(
            cfg, params, cache, batch["tokens"], **kw),
        decode=lambda params, cache, tokens, **kw: MOE.decode(
            cfg, params, cache, tokens, **kw),
        counters=MOE.ROW_COUNTS,
    )


def _vlm_api(cfg: ModelConfig) -> ModelAPI:
    def batch_specs(shape: ShapeConfig):
        b = shape.global_batch
        d = cfg.d_model
        if shape.kind == "train":
            return {
                "tokens": _tok_spec(b, shape.seq_len + 1),
                "patches": jax.ShapeDtypeStruct((b, N_PATCHES, d),
                                                jnp.bfloat16),
                "positions": jax.ShapeDtypeStruct((b, shape.seq_len, 3),
                                                  jnp.int32),
            }
        if shape.kind == "prefill":
            return {
                "tokens": _tok_spec(b, shape.seq_len),
                "patches": jax.ShapeDtypeStruct((b, N_PATCHES, d),
                                                jnp.bfloat16),
                "positions": jax.ShapeDtypeStruct((b, shape.seq_len, 3),
                                                  jnp.int32),
            }
        return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}

    return ModelAPI(
        cfg=cfg,
        init=lambda rng: VLM.init(rng, cfg),
        param_logical_axes=lambda: VLM.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: VLM.loss_fn(cfg, params, batch,
                                                     **kw),
        init_cache=lambda batch, max_len, **kw: KV.init_kv_cache(
            cfg, batch, max_len, **kw),
        prefill=lambda params, cache, batch, **kw: VLM.prefill(
            cfg, params, cache, batch["tokens"], batch["patches"],
            batch["positions"], **kw),
        decode=lambda params, cache, tokens, **kw: VLM.decode(
            cfg, params, cache, tokens, **kw),
        batch_specs=batch_specs,
    )


def _hybrid_api(cfg: ModelConfig) -> ModelAPI:
    def batch_specs(shape: ShapeConfig):
        if shape.kind == "train":
            return {"tokens": _tok_spec(shape.global_batch,
                                        shape.seq_len + 1)}
        if shape.kind == "prefill":
            return {"tokens": _tok_spec(shape.global_batch, shape.seq_len)}
        return {"tokens": jax.ShapeDtypeStruct((shape.global_batch,),
                                               jnp.int32)}

    return ModelAPI(
        cfg=cfg,
        init=lambda rng: HY.init(rng, cfg),
        param_logical_axes=lambda: HY.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: HY.loss_fn(cfg, params, batch,
                                                    **kw),
        init_cache=lambda batch, max_len, **kw: HY.init_cache(
            cfg, batch, max_len, **kw),
        prefill=lambda params, cache, batch, **kw: HY.prefill(
            cfg, params, cache, batch["tokens"], **kw),
        decode=lambda params, cache, tokens, **kw: HY.decode(
            cfg, params, cache, tokens, **kw),
        batch_specs=batch_specs,
    )


def _ssm_api(cfg: ModelConfig) -> ModelAPI:
    def batch_specs(shape: ShapeConfig):
        if shape.kind == "train":
            return {"tokens": _tok_spec(shape.global_batch,
                                        shape.seq_len + 1)}
        if shape.kind == "prefill":
            return {"tokens": _tok_spec(shape.global_batch, shape.seq_len)}
        return {"tokens": jax.ShapeDtypeStruct((shape.global_batch,),
                                               jnp.int32)}

    return ModelAPI(
        cfg=cfg,
        init=lambda rng: XL.init(rng, cfg),
        param_logical_axes=lambda: XL.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: XL.loss_fn(cfg, params, batch,
                                                    **kw),
        # the state pools have no length: max_len is taken and unused
        init_cache=lambda batch, max_len=None, **kw: XL.init_cache(
            cfg, batch, **kw),
        prefill=lambda params, cache, batch, **kw: XL.prefill(
            cfg, params, cache, batch["tokens"], **kw),
        decode=lambda params, cache, tokens, **kw: XL.decode(
            cfg, params, cache, tokens, **kw),
        batch_specs=batch_specs,
    )


def _encdec_api(cfg: ModelConfig) -> ModelAPI:
    def batch_specs(shape: ShapeConfig):
        b = shape.global_batch
        d = cfg.d_model
        src = jax.ShapeDtypeStruct((b, SRC_FRAMES, d), jnp.bfloat16)
        if shape.kind == "train":
            return {"src": src, "tgt": _tok_spec(b, shape.seq_len + 1)}
        if shape.kind == "prefill":
            return {"src": src, "tgt": _tok_spec(b, shape.seq_len)}
        return {"tokens": jax.ShapeDtypeStruct((b,), jnp.int32)}

    return ModelAPI(
        cfg=cfg,
        init=lambda rng: ED.init(rng, cfg),
        param_logical_axes=lambda: ED.param_logical_axes(cfg),
        loss=lambda params, batch, **kw: ED.loss_fn(cfg, params, batch,
                                                    **kw),
        init_cache=lambda batch, max_len, src_len=SRC_FRAMES, **kw:
            ED.init_cache(cfg, batch, max_len, src_len, **kw),
        prefill=lambda params, cache, batch, **kw: ED.prefill(
            cfg, params, cache, batch["src"], batch["tgt"], **kw),
        decode=lambda params, cache, tokens, **kw: ED.decode(
            cfg, params, cache, tokens, **kw),
        batch_specs=batch_specs,
        # served text has no source: zero frames stand in for it
        prefill_input=lambda tokens: {
            "src": jnp.zeros((tokens.shape[0], SRC_FRAMES, cfg.d_model),
                             jnp.float32),
            "tgt": tokens},
    )
