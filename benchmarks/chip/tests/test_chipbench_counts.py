"""The benchmark's yardstick: the peak table and the operation and byte counts,
against hand counts."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import catalog, counts, device  # noqa: E402


def _config(name):
    return catalog.load_json(os.path.join(BENCH, "configs", name + ".json"))


def test_peak_table_gives_the_published_v5e_peaks():
    row = device.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in row["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="not in the peak table"):
        device.peaks("TPU v9 imaginary")


def test_qwen3_flops_per_token_by_hand():
    c = _config("qwen3-0.6b")
    # per layer: q 1024x2048, k and v 1024x1024 each, o 2048x1024, gate/up/down 3 x 1024x3072
    per_layer = 1024 * 2048 + 2 * 1024 * 1024 + 2048 * 1024 + 3 * 1024 * 3072
    assert per_layer == 15_728_640
    matmul = 28 * per_layer + 1024 * 151_936        # the tied head is one matmul
    assert counts.lm_matmul_params(c) == matmul == 595_984_384
    # causal attention at 2,048 tokens: 16 heads x (QK^T + PV) x 128 x 2048 / 2, x2 per MAC
    attn = 28 * 2 * 16 * 128 * 2048
    assert counts.lm_forward_flops_per_token(c, 2048) == 2 * matmul + attn
    assert counts.lm_train_flops_per_token(c, 2048) == 3 * (2 * matmul + attn) == 4_280_549_376


def test_train_flops_per_round_counts_the_active_cluster():
    q = _config("qwen3-0.6b")
    q_mix = catalog.load_json(os.path.join(BENCH, "mixes", "chs-dense-s2048.json"))
    # 2 clients x K=4 steps x 2,048 tokens
    assert counts.train_flops_per_round(q, q_mix) == 2 * 4 * 2048 * 4_280_549_376


def test_flash_forward_flops():
    # B*H = 16, T = S = 2048, hd = 128: 2 * 2 * 16 * 2048 * 2048 * 128 / 2
    assert counts.flash_forward_flops(16, 2048, 2048, 128) == 2 * 2 * 16 * 2048 * 2048 * 128 / 2


def test_qsgd_bytes_by_hand():
    # s = 16: 33 codes need 6 bits; one 1024-block is 6 * 1024 / 32 = 192 words + a norm
    assert counts.qsgd_code_bits(16) == 6
    assert counts.packed_wire_bytes(3000, 6, 1024) == 3 * (192 * 4 + 4)
    wire = 3 * (192 * 4 + 4)
    assert counts.qsgd_message_bytes([3000], 16, 1024, 2, 4) == 3000 * 2 + 2 * wire + 3000 * 4


def test_qsgd_bytes_depend_only_on_leaf_shapes_and_bits():
    a = counts.qsgd_message_bytes([1024 * 3072, 128, 7], 16, 1024, 2, 4)
    b = counts.qsgd_message_bytes([7, 1024 * 3072, 128], 16, 1024, 2, 4)
    assert a == b
    # the same number of bits per code (s = 16 and s = 31 both take 6 bits) moves the same bytes
    assert a == counts.qsgd_message_bytes([1024 * 3072, 128, 7], 31, 1024, 2, 4)
    assert a < counts.qsgd_message_bytes([1024 * 3072, 128, 7], 32, 1024, 2, 4)
