"""Operation and byte counts against the numbers worked out by hand."""

from bench import flops, model
from bench.blocks import dense

STABLELM = model.load("stablelm3b").model
MINICPM = model.load("minicpm2b").model
V5E = (197e12, 819e9)


def test_layer_and_head_parameters():
    # stablelm: q,k,v,o 4 x 2560^2, MLP 3 x 2560 x 6912
    assert dense.layer_matmul_params(STABLELM) == \
        4 * 2560 * 2560 + 3 * 2560 * 6912
    assert dense.head_params(STABLELM) == 2560 * 50304
    # minicpm: 36 heads of 64 = 2304
    assert dense.layer_matmul_params(MINICPM) == \
        4 * 2304 * 2304 + 3 * 2304 * 5760


def test_pool_bytes_as_allocated():
    # bfloat16 (the served dtype), head_dim padded to 128 lanes, K and V
    assert dense.pool_bytes(STABLELM, 64) == 2_147_483_648      # 2.15 GB
    assert dense.pool_bytes(STABLELM, 128) == 4_294_967_296     # 4.29 GB
    assert dense.pool_bytes(MINICPM, 64) == 3_019_898_880       # 3.02 GB
    assert dense.pool_bytes(MINICPM, 32) == 1_509_949_440


def test_decode_least_bytes():
    # bf16 weights read once: 32 layers + head, plus f32 layernorms
    w = 2 * (32 * 79_298_560 + 128_778_240) + 4 * 2 * (2 * 32 + 1) * 2560
    assert dense.weight_bytes_per_step(STABLELM) == w
    assert 5.33e9 < w < 5.34e9
    # logical bf16 KV per token: 32 layers x K,V x 32 heads x 80
    assert dense.kv_bytes_per_token(STABLELM) == 327_680
    assert dense.kv_bytes_per_token(MINICPM) == 368_640
    # tied minicpm: the table doubles as the head; f32 RMSNorm scales
    assert dense.weight_bytes_per_step(MINICPM) == \
        2 * (40 * dense.layer_matmul_params(MINICPM) + 2304 * 122753) \
        + 4 * (2 * 40 + 1) * 2304
    # one step of 8 rows at 150 tokens of context: weights plus the KV
    least = flops.decode_least_seconds(dense, STABLELM, 1, [150] * 8, *V5E)
    assert least == (w + 8 * 151 * 327_680) / 819e9
    assert 0.0069 < least < 0.0072


def test_decode_is_memory_bound_at_every_row_count():
    for name in ("stablelm3b", "minicpm2b"):
        cfg = model.load(name)
        assert flops.memory_bound_always(cfg.block, cfg.model,
                                         cfg.max_batch, cfg.max_len, *V5E)


def test_prefill_flops():
    m = STABLELM
    one = dense.prefill_flops(m, 1)
    assert one == 2 * 32 * dense.layer_matmul_params(m) + 2 * 2560 * 50304 \
        + 4 * 32 * 32 * 80
    # 128 prompt tokens: 2 x 2.54e9 x 128 + head + attention, 0.65 TFLOP
    assert 0.65e12 < dense.prefill_flops(m, 128) < 0.66e12
