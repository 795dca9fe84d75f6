"""The yardstick's arithmetic: bench/counts.py against the hand counts,
and bench/peaks.py's table."""
import pytest

from bench import counts as C
from bench import peaks
from bench_tiny import load


def test_qwen3_matmul_params_and_kv_bytes():
    cfg = load("configs", "qwen3-1.7b")
    # per layer: q, o 2048 x 2048; k, v 2048 x 1024; MLP 3 x 2048 x 6144
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    assert C.matmul_params(cfg) == 28 * layer + 2048 * 151936
    assert round(C.matmul_params(cfg) / 1e9, 2) == 1.72
    # 28 layers x (k + v) x 8 heads x 128 x 2 bytes = 112 KiB
    assert C.kv_bytes_per_token(cfg) == 112 * 1024


def test_deepseek_7b_per_chip_kv_is_120_kib_over_four_chips():
    # deepseek-ai/deepseek-llm-7b-base config.json
    cfg = dict(hidden_size=4096, intermediate_size=11008,
               num_hidden_layers=30, num_attention_heads=32,
               num_key_value_heads=32, vocab_size=102400, rope_theta=1e4,
               rms_norm_eps=1e-6, tie_word_embeddings=False)
    assert C.kv_bytes_per_token(cfg) // 4 == 120 * 1024
    layer = 4 * 4096 * 4096 + 3 * 4096 * 11008
    assert C.matmul_params(cfg) == 30 * layer + 4096 * 102400


def test_decode_step_counts_weights_once_and_real_context():
    cfg = load("configs", "qwen3-1.7b")
    fl, by = C.decode_step(cfg, [100, 300])
    kv = C.kv_bytes_per_token(cfg)
    assert by == C.weight_bytes(cfg) + 400 * kv + 2 * kv
    attn = 4 * 28 * 16 * 128 * (101 + 301)
    assert fl == 2 * 2 * C.matmul_params(cfg) + attn
    # an idle lane costs nothing
    assert C.decode_step(cfg, []) == (0.0, float(C.weight_bytes(cfg)))


def test_prefill_flops_sum_causal_keys():
    cfg = load("configs", "qwen3-1.7b")
    layer = 2 * 2048 * 2048 + 2 * 2048 * 1024 + 3 * 2048 * 6144
    got = C.prefill(cfg, 16, 48, head=True)
    keys = sum(p + 1 for p in range(16, 48))
    want = 2 * layer * 28 * 32 + 4 * 28 * 16 * 128 * keys \
        + 2 * 2048 * 151936
    assert got == want


def test_peaks_are_keyed_by_device_kind():
    p = peaks.peak("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")
