"""Operation and byte counts of prefill and decode against hand-computed
values, for the benchmark's configuration and for an untied head at wider
published widths (Qwen2-72B's, at 4 layers)."""
from bench.harness import spec
from bench.harness.work import Sizes, least_seconds


def _sizes(name):
    return Sizes.of(spec.load_json(f"{spec.BENCH_DIR}/configs/{name}.json"))

def test_qwen25_3b_sizes():
    s = _sizes("qwen2.5-3b")
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, mlp 3x2048x11008
    layer = 2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008
    assert s.layer_matmul_params == layer == 77_070_336
    assert s.head_params == 2048 * 151936
    assert s.kv_bytes_per_pos == 36 * 2 * 2 * 128 * 2 == 36_864
    assert s.params == 3_085_938_688          # Qwen2.5-3B: 3.09 B
    bias = 36 * (16 + 4) * 128
    assert s.weight_bytes == 2 * (36 * layer + 2048 * 151936 + bias) \
        + 4 * (73 * 2048)

UNTIED = {"hidden_size": 8192, "intermediate_size": 29568,
          "num_attention_heads": 64, "num_key_value_heads": 8,
          "num_hidden_layers": 4, "vocab_size": 152064,
          "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def test_untied_head_sizes():
    s = Sizes.of(UNTIED)
    layer = 8192 * 8192 * 2 + 2 * 8192 * 1024 + 3 * 8192 * 29568
    assert s.layer_matmul_params == layer == 877_658_112
    assert s.head_params == 8192 * 152064
    assert s.kv_bytes_per_pos == 4 * 2 * 8 * 128 * 2 == 16_384
    assert s.params == 4 * layer + 4 * (64 + 16) * 128 + 9 * 8192 \
        + 2 * 152064 * 8192

def test_prefill_counts():
    s = _sizes("qwen2.5-3b")
    f, b = s.prefill(256)
    attn = 4 * 36 * 16 * 128 * (256 * 257 // 2)
    assert f == 2 * 36 * 77_070_336 * 256 + 2 * 2048 * 151936 + attn
    assert b == s.weight_bytes + 36_864 * 256

def test_decode_block_counts():
    s = _sizes("qwen2.5-3b")
    # slot at 100 needs 8 steps, slot at 10 needs 3, a free slot none
    f, b = s.decode_block([(100, 8), (10, 3), (0, 0)])
    attended = (8 * 100 + 36) + (3 * 10 + 6)
    assert f == 2 * (36 * 77_070_336 + 2048 * 151936) * 11 \
        + 4 * 36 * 16 * 128 * attended
    assert b == 8 * s.weight_bytes + 36_864 * attended
    assert s.decode_block([(5, 0)]) == (0, 0)

def test_least_seconds_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_seconds(1000, 50, peak) == (10.0, "compute")
    assert least_seconds(10, 50, peak) == (5.0, "memory")
