"""The work counts behind step_mfu and attn_roofline, by hand."""
from bench import work
from bench.configs import dense_gqa

# 2 layers, d 8, 4 query heads of 2 per KV head, head_dim 2, d_ff 16
M = {"layers": 2, "d": 8, "hq": 4, "hkv": 2, "dh": 2, "ff": 16,
     "vocab": 10, "qkv_bias": False}
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_matmul_params():
    # q 8x8, k 8x4, v 8x4, o 8x8, gate/up 8x16, down 16x8 -> 576 / layer
    assert work.matmul_params(M) == 2 * (64 + 32 + 32 + 64 + 3 * 128)


def test_attention_flops():
    assert work.attn_flops(M, 5) == 4 * 2 * 4 * 2 * 5
    # positions 2, 3: keys 3 + 4
    assert work.prompt_attn_flops(M, 2, 4) == 4 * 2 * 4 * 2 * 7
    assert work.prompt_attn_flops(M, 0, 3) == 4 * 2 * 4 * 2 * 6


def test_model_flops():
    # one prompt of 3 tokens, two decode rows at ctx 4 and 5, 3 sampled
    got = work.model_flops(M, [(0, 3)], [4, 5], 3)
    want = 2 * 1152 * 5 + 64 * (6 + 4 + 5) + 3 * 2 * 8 * 10
    assert got == want


def test_decode_and_chunk_bytes():
    # bf16: q+o row 2*4*2*2 = 32 B, K+V per token 2*2*2*2 = 16 B, 2 layers
    f, b = work.decode_attn_work(M, [4, 5])
    assert f == 64 * 9
    assert b == 2 * ((32 + 16 * 4) + (32 + 16 * 5))
    f, b = work.chunk_attn_work(M, [(2, 4)])
    assert f == 64 * 7
    assert b == 2 * (32 * 2 + 16 * 4)


def test_least_seconds_takes_the_larger_bound():
    assert work.least_seconds(1000, 10, PEAKS) == 10.0
    assert work.least_seconds(10, 1000, PEAKS) == 100.0


def test_dims_from_a_config_file():
    spec = {"hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "intermediate_size": 11008,
            "vocab_size": 151936, "num_hidden_layers": 36, "qkv_bias": True}
    m = dense_gqa.dims(spec)
    assert m["dh"] == 128
    # Qwen2.5-3B: 2.77e9 parameters in the layers' matmuls
    assert work.matmul_params(m) == 36 * (2048 * 2048 * 2 + 2048 * 256 * 2
                                          + 3 * 2048 * 11008)
