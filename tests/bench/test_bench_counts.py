"""Operation and byte counts of the dense family against hand counts."""
import json

from conftest import CHIP, harness

dense = harness.counts("dense")


def _cfg(name):
    return json.loads((CHIP / "configs" / f"{name}.json").read_text())


def test_gpt_train_flops_by_hand():
    c = _cfg("gpt")
    # per layer: q, k, v, o 768x768 each, SwiGLU 3 x 768x3072
    per_layer = 4 * 768 * 768 + 3 * 768 * 3072
    assert per_layer == 9_437_184
    head = 768 * 50257
    assert dense.matmul_params(c) == 12 * per_layer + head
    # attention: causal, query i of 1024 reads i + 1 keys, 12 heads of 64
    attn = 12 * 4 * 12 * 64 * (1024 * 1025 // 2)
    fwd = 2 * (12 * per_layer + head) * 8 * 1024 + 8 * attn
    assert dense.train_flops(c, 8, 1024) == 3 * fwd
    # about 1.0 GFLOP per token: 6 x 152 M weights plus attention
    assert 0.9e9 < dense.train_flops(c, 8, 1024) / 8192 < 1.2e9


def test_yi_decode_counts_by_hand():
    c = _cfg("yi-9b")
    per_layer = (2 * 4096 * 4096 + 2 * 4096 * 512 + 3 * 4096 * 11008)
    mm = 12 * per_layer + 4096 * 64000
    assert dense.matmul_params(c) == mm
    # with the embedding and the norms, 2.6 B parameters
    assert 2.5e9 < mm + 64000 * 4096 + 12 * 2 * 4096 + 4096 < 2.7e9
    # at position 1000: 32 queries of 32 heads x 128 over 1001 keys
    assert dense.decode_flops(c, 32, 1000) == \
        32 * (2 * mm + 12 * 4 * 32 * 128 * 1001)
    kv_pos = 12 * 32 * 2 * 4 * 128 * 2           # bytes per cached position
    weights = (mm + 12 * 2 * 4096 + 4096) * 2 + 32 * 4096 * 2
    assert dense.decode_bytes(c, 32, 1000) == weights + kv_pos * 1002
    # the whole 4096-position cache would be 3.2 GB
    assert abs(kv_pos * 4096 - 3.22e9) < 0.01e9


def test_peaks_table_has_the_v5e_and_refuses_others():
    import pytest
    p = harness.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")
