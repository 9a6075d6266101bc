"""Unified model API — one surface over all six families.

``get_model(cfg)`` returns a :class:`ModelAPI` with:

    init(rng)                     -> params
    param_logical_axes()          -> logical-axis pytree (matches params)
    loss(params, batch, ...)      -> scalar loss           [train shapes]
    init_cache(batch, max_len)    -> cache pytree          [serve shapes]
    prefill(params, cache, batch) -> (cache, last_logits)
    decode(params, cache, tokens) -> (cache, logits)
    batch_specs(shape)            -> ShapeDtypeStruct dict for the batch
    counters                      -> names of the per-row counts that
                                     prefill/decode append as a third
                                     output, (B, len(counters)) int32,
                                     when called with ``counts=True``

``batch_specs`` is the assignment's ``input_specs()``: weak-type-correct,
shardable stand-ins for every model input, no device allocation.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
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
    )
