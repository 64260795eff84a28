"""Operation and byte counts of each kernel call against hand counts."""

import numpy as np
import pytest

from perfbench import work

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("w_bits, idx_bytes", [(4, 2560 * 2560 // 2), (3, 2560 * 2560 // 2),
                                               (8, 2560 * 2560)])
def test_lut_gemm_counts(w_bits, idx_bytes):
    m, k, n = 64, 2560, 2560
    ops, nbytes = work.lut_gemm(m, k, n, w_bits, 4)
    assert ops == 2 * 64 * 2560 * 2560
    books = 4 * (2 ** w_bits + 16 + 15)
    assert nbytes == idx_bytes + books + 64 * 2560 * 2 + 64 * 4 + 64 * 2560 * 4


def test_lut_gemm_decode_is_bandwidth_bound():
    ops, nbytes = work.lut_gemm(64, 6144, 24576, 4, 4)
    assert work.least_seconds(ops, nbytes, PEAK) == nbytes / PEAK["hbm_bytes_per_s"]


def test_orizuru_counts():
    ops, nbytes = work.orizuru(64, 6912, work.outliers_per_side(6912, 0.005))
    assert work.outliers_per_side(6912, 0.005) == 35
    assert ops == 0
    assert nbytes == 64 * 6912 * 4 + 2 * 64 * 35 * 8
    _, with_codes = work.orizuru(64, 6912, 35, codes=True)
    assert with_codes == nbytes + 64 * 6912 * 4


def _attn(slots, positions, window=0, kv_bits=4):
    return work.paged_attention(np.asarray(slots), np.asarray(positions), heads=4,
                                kv_heads=2, head_dim=8, block_size=16, window=window,
                                kv_bits=kv_bits)


def test_paged_attention_counts_real_context_and_distinct_blocks():
    per_token = 2 * 2 * (8 * 4 / 8 + 4)  # K and V: int4 indices + a float32 scale per head
    # one decode row at position 40: 41 keys in blocks 0..2
    ops, nbytes = _attn([0], [[40]])
    assert ops == 4 * 41 * 4 * 8
    assert nbytes == 3 * 16 * per_token + 2 * 1 * 4 * 8 * 2
    # a prefill chunk of three rows of one slot reads its blocks once
    ops, nbytes = _attn([1, 1, 1], [[30], [31], [32]])
    assert ops == 4 * (31 + 32 + 33) * 4 * 8
    assert nbytes == 3 * 16 * per_token + 2 * 3 * 4 * 8 * 2
    # padded rows count nothing; the block table's length never enters
    ops2, nbytes2 = _attn([1, 1, 1, 0], [[30], [31], [32], [-1]])
    assert (ops2, nbytes2) == (ops, nbytes)


def test_paged_attention_window_caps_context():
    ops, nbytes = _attn([0], [[100]], window=20)
    assert ops == 4 * 20 * 4 * 8
    # keys 81..100 lie in blocks 5 and 6
    assert nbytes == 2 * 16 * 2 * 2 * (8 * 4 / 8 + 4) + 2 * 4 * 8 * 2


def test_step_flops_hand_count():
    cfg = {"d_model": 8, "head_dim": 2, "n_heads": 4, "n_kv_heads": 2, "d_ff": 16,
           "act_fn": "relu2", "n_layers": 3, "vocab_size": 10, "sliding_window": 0}
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 8 * 16 + 16 * 8
    pos = np.array([[5], [-1], [0]])
    want = 2 * 2 * (3 * per_layer + 8 * 10) + 4 * (6 + 1) * 4 * 2 * 3
    assert work.step_flops(cfg, pos) == want


def test_unknown_device_is_an_error(tmp_path):
    with pytest.raises(KeyError):
        work.peaks("TPU v99")
    assert work.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
