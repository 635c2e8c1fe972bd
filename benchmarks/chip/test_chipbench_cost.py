"""Operation and byte counts against hand-worked numbers, and the peak
table."""

import json
from pathlib import Path

import pytest

from chipbench import cost

CONFIGS = Path(__file__).resolve().parent / "configs"


def shape(name):
    return cost.Shape.of(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_qwen2_0_5b_counts():
    s = shape("qwen2-0.5b")
    # per layer: q 896*896 + k, v 2*896*128 + o 896*896 + mlp 3*896*4864
    assert s.layer_params == 802_816 + 229_376 + 802_816 + 13_074_432
    # 24 layers + untied embedding and head of 151936 x 896 each
    assert s.weight_params == 24 * 14_909_440 + 2 * 136_134_656
    assert round(s.weight_params / 1e6, 1) == 630.1
    assert round(s.weight_bytes / 1e9, 3) == 1.260
    assert s.kv_bytes_per_token == 12_288      # 2 * 24 * 2 * 64 * 2 B


# hf Qwen/Qwen3-8B config.json: the widths the counts read
QWEN3_8B = {"num_hidden_layers": 36, "hidden_size": 4096,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "head_dim": 128, "intermediate_size": 12288,
            "vocab_size": 151936, "tie_word_embeddings": False,
            "torch_dtype": "bfloat16"}


def test_qwen3_8b_counts():
    s = cost.Shape.of(QWEN3_8B)
    assert round(s.weight_params / 1e9, 3) == 8.190
    assert s.kv_bytes_per_token == 147_456     # 2 * 36 * 8 * 128 * 2 B
    assert s.kv_bytes_per_token // 4 == 36_864   # a chip's share of four


def test_work_of_a_step():
    s = shape("qwen2-0.5b")
    kv = [100, 300]
    # matrices and head: 2 flops a parameter a token; attention 4 a
    # (head, dim, key) over 24 layers of 14 heads of 64
    assert s.decode_flops(kv) == 2 * 2 * s.matmul_params \
        + 4 * 24 * 14 * 64 * 400
    per_row = 2 * 2 * 64 * 2                  # K and V, 2 heads, bf16
    qo = 2 * 14 * 64 * 2
    assert s.attn_bytes(kv) == 24 * (400 * per_row + 2 * qo)
    assert s.decode_bytes(kv) == s.matmul_params * 2 + 2 * 896 * 2 \
        + 2 * 12_288 + s.attn_bytes(kv)
    # a 4-token prompt with 2 cached: blocks for 2 tokens, keys 3 + 4
    assert s.prefill_flops(4, 2) == 2 * 24 * s.layer_params * 2 \
        + 4 * 24 * 14 * 64 * 7 + 2 * s.head_params


def test_peaks_and_least_time():
    p = cost.peaks("TPU v5 lite")
    assert p["bf16_flop_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert cost.least_seconds(197e12, 819e9, p) == 1.0
    assert cost.least_seconds(0, 4 * 819e9, p, chips=4) == 1.0
    with pytest.raises(KeyError, match="no peaks"):
        cost.peaks("TPU v9 imaginary")
