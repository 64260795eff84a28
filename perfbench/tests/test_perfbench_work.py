"""Operation and byte counts of each kernel call against hand counts."""

import json
from types import SimpleNamespace as NS

import numpy as np
import pytest
from conftest import REPO

from perfbench import discover, work

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


H2O_POS = np.array([[5], [100], [4200], [-1], [63], [64], [4095]])
H2O_SLOTS = np.array([0, 1, 2, 0, 3, 3, 4])


def _least(name, cfg, serve):
    """The least seconds that metric ``name``'s reader counts for the step
    (H2O_SLOTS, H2O_POS)."""
    mod = discover._module(discover.HERE / "metrics" / f"{name}.py", "metric")
    got = {}

    def share(run, pattern, least):
        got["least"] = least(H2O_SLOTS, H2O_POS)

    mod.share = share
    mod.read(NS(cfg=cfg, serve=serve, peaks=work.peaks("TPU v5 lite")))
    return got["least"]


def _h2o():
    return json.loads((REPO / "perfbench" / "configs" / "h2o_danube_1_8b.json").read_text())


@pytest.mark.parametrize("what, want", [
    ("step_flops", 23061135360.0),
    ("lut_gemm_roofline", 0.0013039594432234431),
    ("orizuru_roofline", 1.2215794871794874e-05),
    ("paged_attn_roofline", 0.00017542282783882782),
])
def test_h2o_counts_are_pinned(what, want):
    """h2o-danube's counts as they stood before mixture-of-experts and
    per-layer windows entered them."""
    cfg = _h2o()
    assert work.projections(cfg) == [
        ("attn/wq", 2560, 2560), ("attn/wk", 2560, 640), ("attn/wv", 2560, 640),
        ("attn/wo", 2560, 2560), ("mlp/wi", 2560, 13824), ("mlp/wd", 6912, 2560)]
    assert work.detected_widths(cfg) == [2560, 2560, 2560, 6912]
    assert work.layer_windows(cfg) == [4096] * 24
    if what == "step_flops":
        assert work.step_flops(cfg, H2O_POS) == want
    else:
        assert _least(what, cfg, {"block_size": 16}) == want


MOE = {"d_model": 8, "head_dim": 2, "n_heads": 4, "n_kv_heads": 2, "d_ff": 4, "act_fn": "silu",
       "n_layers": 3, "vocab_size": 10, "sliding_window": 0, "n_experts": 6,
       "experts_per_token": 2, "n_shared_experts": 2, "shared_expert_d_ff": 3}


def test_moe_counts_hand_sums():
    attn = 8 * 8 + 2 * 8 * 4 + 8 * 8
    shared = 8 * (2 * 6) + 6 * 8  # two shared experts of 3: one gated MLP of 6
    expert = 8 * (2 * 4) + 4 * 8
    assert work.projections(MOE)[4:] == [("moe/shared/wi", 8, 12), ("moe/shared/wd", 6, 8)]
    assert work.experts(MOE) == [("moe/wi", 8, 8), ("moe/wd", 4, 8)]
    assert work.detected_widths(MOE) == [8, 8, 8, 6]
    per_layer = attn + shared + 8 * 6 + 2 * expert + 8  # router, top-2 experts, shared gate
    pos = np.array([[5], [-1], [0]])
    want = 2 * 2 * (3 * per_layer + 8 * 10) + 4 * (6 + 1) * 4 * 2 * 3
    assert work.step_flops(MOE, pos) == want
    no_shared = dict(MOE, n_shared_experts=0)
    assert work.projections(no_shared)[4:] == []
    assert work.step_flops(no_shared, pos) == want - 2 * 2 * 3 * (shared + 8)


def test_window_pattern_counts_hand_sums():
    cfg = {"d_model": 8, "head_dim": 2, "n_heads": 4, "n_kv_heads": 2, "d_ff": 16,
           "act_fn": "relu2", "n_layers": 5, "vocab_size": 10, "sliding_window": 4,
           "window_pattern": [3, 3, 0]}
    assert work.layer_windows(cfg) == [3, 3, 0, 3, 3]
    assert work.layer_windows(dict(cfg, window_pattern=None)) == [4] * 5
    per_layer = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 8 * 16 + 16 * 8
    pos = np.array([[5], [-1], [1]])
    # windowed layers see 3 and 2 keys, the full one 6 and 2
    attn = 4 * 4 * 2 * (4 * (3 + 2) + 1 * (6 + 2))
    assert work.step_flops(cfg, pos) == 2 * 2 * (5 * per_layer + 8 * 10) + attn


def test_paged_attention_reader_takes_each_layers_window():
    cfg = dict(_h2o(), n_layers=4, window_pattern=[16, 0])
    got = _least("paged_attn_roofline", cfg, {"block_size": 16})
    one = lambda w: work.least_seconds(*work.paged_attention(
        H2O_SLOTS, H2O_POS, heads=32, kv_heads=8, head_dim=80, block_size=16, window=w,
        kv_bits=4), work.peaks("TPU v5 lite"))
    assert got == pytest.approx(2 * one(16) + 2 * one(0))
    assert one(16) < one(0)


def test_expert_gemm_counts():
    # one token, top-8 of 64: eight experts' weights, read once
    ops, nbytes = work.expert_gemm(1, 2304, 1792, 64, 8, 4, 4)
    assert ops == 2 * 8 * 2304 * 1792
    books = 4 * (8 * 16 + 16 + 15)
    assert nbytes == 8 * 2304 * 1792 // 2 + books + 2304 * 2 + 4 + 8 * 1792 * 4
    # many tokens: every expert once, however many tokens reach it
    ops, nbytes = work.expert_gemm(64, 896, 2304, 64, 8, 8, 4)
    assert ops == 2 * 64 * 8 * 896 * 2304
    books = 4 * (64 * 256 + 16 + 15)
    assert nbytes == 64 * 896 * 2304 + books + 64 * 896 * 2 + 64 * 4 + 64 * 8 * 2304 * 4
    # never more weight bytes than the experts hold, never fewer than one token needs
    w_all = 64 * 896 * 2304
    for rows in (1, 7, 8, 9, 1000):
        _, nb = work.expert_gemm(rows, 896, 2304, 64, 8, 8, 4)
        assert nb - (rows * 896 * 2 + rows * 4 + rows * 8 * 2304 * 4) <= w_all + 4 * (64 * 256 + 31)
