"""mellum2-12b-a2.5b — code model: 64-expert top-8 MoE in every layer,
GQA 32/4 of head_dim 128, three sliding-window layers (1024 keys, default
RoPE) to every full layer (YaRN, factor 16 over 8192 positions)
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json].

Served as one chip of a four-chip expert-parallel host: attention, norms,
router, embedding and head on every chip, 16 of each layer's 64 experts
here (experts 0-15).  No per-head q/k norm: the config states none.  The
MTP head is left out (serving without speculation does not use it).
"""

from repro.configs.base import (
    FULL,
    SLIDING,
    ModelConfig,
    MoEConfig,
    YarnConfig,
    register,
)

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2_304,
    n_heads=32,
    n_kv_heads=4,
    d_ff=7_168,
    vocab=98_304,
    head_dim=128,
    norm="rmsnorm",
    act="silu",
    rope_theta=500_000.0,
    moe=MoEConfig(num_experts=64, top_k=8, d_ff_expert=896,
                  experts_held=16, expert_base=0),
    attn_window=1_024,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL) * 7,
    yarn=YarnConfig(factor=16.0, original_max_position_embeddings=8_192,
                    beta_fast=32.0, beta_slow=1.0,
                    attention_factor=1.2772588722239782),
    source="hf:JetBrains/Mellum2-12B-A2.5B-Instruct; 16 of 64 experts held "
           "(4-way expert parallelism)",
))
