"""The Mellum2 configuration file cut to a tiny size for CPU tests: 4
layers (3 sliding, 1 full), window 8, 8 of 16 experts held with top-4,
widths 64 / 4 heads of 16 / 2 KV heads / experts of 32, vocabulary 256,
YaRN over 16 original positions."""

import copy
import json

from bench.model import BENCH_DIR


def mellum(**keys) -> dict:
    raw = json.loads((BENCH_DIR / "configs" / "mellum2.json").read_text())
    raw.update(num_hidden_layers=4, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
               vocab_size=256, sliding_window=8, num_experts=8,
               num_experts_published=16, num_experts_per_tok=4,
               torch_dtype="float32",
               layer_types=["sliding_attention"] * 3 + ["full_attention"],
               mlp_layer_types=["sparse"] * 4)
    raw["rope_parameters"] = copy.deepcopy(raw["rope_parameters"])
    raw["rope_parameters"]["full_attention"][
        "original_max_position_embeddings"] = 16
    raw.update(keys)
    return raw
