"""Operation and byte counts of the dense family on hand-worked shapes."""

import json
from pathlib import Path

import pytest

from bench import modeldef
from bench.counts import least_time
from bench.peaks import PEAKS, peak

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
Shapes = modeldef.family({"model_type": "qwen2"}).Shapes


def hand():
    # 1 layer, d 4, 2 query heads and 1 KV head of 2, ff 8, vocab 10
    return Shapes(layers=1, d=4, heads=2, kv_heads=1, head_dim=2, ff=8,
                  vocab=10, tied=True, qkv_bias=True, dtype_bytes=2)


def test_hand_worked_params_and_flops():
    sh = hand()
    # q 4x4, k and v 4x2 each, o 4x4, gate/up/down 3 * 4x8
    assert sh.layer_matmul_params == 16 + 8 + 8 + 16 + 96
    # biases 4 + 2 + 2, two norms of 4
    assert sh.layer_params == 144 + 8 + 8
    assert sh.params == 160 + 40 + 4          # tied table 10x4, final norm
    assert sh.kv_bytes_per_token == 1 * 2 * 1 * 2 * 2
    # a token at context 3: 2 * 144 + 4 * 3 * 2 heads * 2 dims
    assert sh.token_flops(3) == 288 + 48
    assert sh.logits_flops == 2 * 4 * 10
    assert sh.prefill_flops(2, start=1) == (sh.token_flops(2)
                                            + sh.token_flops(3) + 80)
    assert sh.decode_flops([3, 5]) == (sh.token_flops(3) + sh.token_flops(5)
                                       + 160)
    # weights once, KV of contexts 3 and 5, two new tokens written
    assert sh.decode_bytes([3, 5]) == sh.weight_bytes + (8 + 2) * 8


def test_untied_head_is_read_and_table_is_not():
    sh = Shapes(**{**hand().__dict__, "tied": False})
    assert sh.params == 160 + 80 + 4
    assert sh.weight_bytes == (160 + 40 + 4) * 2


def test_qwen_decode_bytes_at_one_live_token():
    cfg = json.loads((CONFIGS / "qwen2.5-3b.json").read_text())
    sh = Shapes.of(cfg)
    assert sh.weight_bytes == pytest.approx(6.18e9, rel=5e-3)
    assert sh.kv_bytes_per_token == 36 * 2 * 2 * 128 * 2
    # one live row at context 1: its KV read once and written once
    assert sh.decode_bytes([1]) == sh.weight_bytes + 2 * sh.kv_bytes_per_token


def test_granite_kv_bytes_per_token():
    # granite-3.0-2b's published widths: 32 query and 8 KV heads of 64
    cfg = {"num_hidden_layers": 40, "hidden_size": 2048,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "intermediate_size": 8192, "vocab_size": 49155,
           "tie_word_embeddings": True, "torch_dtype": "bfloat16"}
    sh = Shapes.of(cfg)
    assert sh.head_dim == 64
    assert sh.kv_bytes_per_token == 40 * 2 * 8 * 64 * 2


def test_least_time_is_the_larger_bound():
    p = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert least_time(1000, 20, p) == 10.0
    assert least_time(100, 50, p) == 5.0


def test_peak_table():
    assert peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert all("source" in v for v in PEAKS.values())
    with pytest.raises(KeyError):
        peak("cpu")
