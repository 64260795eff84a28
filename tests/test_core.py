"""Core quantization invariants: codebooks, packing, LUT-GEMM forms,
outlier look-ahead exactness. Unit + hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")  # property tests need it
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    assign,
    assign_via_boundaries,
    boundaries_from_centroids,
    build_lut,
    compensate_gather,
    compensate_scatter,
    dequantize_activation,
    dequantize_weight,
    detect_outliers_static,
    detect_outliers_topk,
    fit_activation_codebook,
    kmeans_fit,
    lut_gemm,
    lut_gemm_counting,
    num_outliers,
    orizuru_comparisons,
    outlier_residuals,
    pack_int4,
    quantize_activation,
    quantize_weight,
    static_thresholds,
    token_scale,
    unpack_int4,
)
from repro.core.qlinear import QLinearConfig, qlinear_apply, quantize_linear
from repro.core.quantize import lookup


def _rand(shape, seed=0, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


# ---------------------------------------------------------------------------
# codebook
# ---------------------------------------------------------------------------

def test_kmeans_sorted_and_within_range():
    x = _rand((4096,), 1)
    c = kmeans_fit(x, 16)
    assert np.all(np.diff(c) >= 0)
    assert c.min() >= x.min() and c.max() <= x.max()


def test_kmeans_beats_rtn_on_gaussian():
    """The paper's premise: learned centroids < uniform grid on real dists."""
    x = _rand((8192,), 2)
    km = kmeans_fit(x, 16)
    grid = jnp.linspace(x.min(), x.max(), 16)
    err_km = jnp.mean((x - km[assign(x, km)]) ** 2)
    err_grid = jnp.mean((x - grid[assign(x, grid)]) ** 2)
    assert float(err_km) < float(err_grid)


def test_weighted_kmeans_shifts_centroids():
    """Fisher-weighted fit must allocate resolution to high-weight samples."""
    x = jnp.concatenate([_rand((1000,), 3), 5.0 + 0.1 * _rand((50,), 4)])
    w_hi = jnp.concatenate([jnp.ones(1000), 100.0 * jnp.ones(50)])
    c_plain = kmeans_fit(x, 8)
    c_wtd = kmeans_fit(x, 8, w=w_hi)
    # weighted codebook has more centroids near the heavy cluster at ~5
    near = lambda c: int(jnp.sum(c > 4.0))
    assert near(c_wtd) >= near(c_plain)


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.sampled_from([8, 16]))
def test_boundary_assign_equals_argmin(seed, n):
    x = jax.random.normal(jax.random.PRNGKey(seed), (257,)) * 2
    book = kmeans_fit(jax.random.normal(jax.random.PRNGKey(seed + 1), (512,)), n)
    np.testing.assert_array_equal(assign_via_boundaries(x, book), assign(x, book))


# ---------------------------------------------------------------------------
# packing / containers
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 17), k2=st.integers(1, 33))
def test_pack_unpack_roundtrip(seed, m, k2):
    idx = jax.random.randint(jax.random.PRNGKey(seed), (m, 2 * k2), 0, 16)
    np.testing.assert_array_equal(unpack_int4(pack_int4(idx)), idx)


@pytest.mark.parametrize("n", [8, 16, 32, 256])
def test_lookup_equals_gather(n):
    book = jnp.sort(_rand((n,), 5))
    idx = jax.random.randint(jax.random.PRNGKey(6), (37, 129), 0, n)
    np.testing.assert_array_equal(lookup(book, idx), book[idx])


def test_quantized_weight_hbm_bytes():
    qw = quantize_weight(_rand((128, 64)), 4)
    assert qw.hbm_bytes() == 128 * 64 // 2 + 16 * 4 + 64 * 4
    assert qw.packed.dtype == jnp.uint8 and qw.packed.shape == (128, 32)


def test_weight_quantization_error_bounded():
    w = _rand((256, 128), 7)
    deq = dequantize_weight(quantize_weight(w, 4))
    rel = jnp.linalg.norm(deq - w) / jnp.linalg.norm(w)
    assert float(rel) < 0.1  # 4-bit K-Means on gaussian ~ 4-5% typical


# ---------------------------------------------------------------------------
# LUT-GEMM equivalences (the paper's core mathematical claim)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("a_bits", [3, 4])
def test_counting_equals_factorized_equals_dequant(a_bits):
    w = _rand((64, 32), 3, 0.5)
    x = _rand((8, 64), 4)
    qw = quantize_weight(w, 4)
    qa = quantize_activation(x, fit_activation_codebook(x, a_bits))
    y_count = lut_gemm_counting(qa, qw)
    y_fact = lut_gemm(qa, qw)
    y_deq = dequantize_activation(qa) @ dequantize_weight(qw)
    np.testing.assert_allclose(y_count, y_fact, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(y_fact, y_deq, rtol=1e-4, atol=1e-4)


def test_lut_is_cartesian_product():
    a = jnp.array([1.0, 2.0])
    w = jnp.array([3.0, 5.0, 7.0])
    np.testing.assert_array_equal(build_lut(a, w), jnp.outer(a, w))


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 9), k=st.sampled_from([32, 64]),
       n=st.sampled_from([2, 16, 30]))
def test_lut_gemm_property(seed, m, k, n):
    w = jax.random.normal(jax.random.PRNGKey(seed), (k, n))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (m, k))
    qw = quantize_weight(w, 4)
    qa = quantize_activation(x, fit_activation_codebook(x, 4))
    np.testing.assert_allclose(
        lut_gemm_counting(qa, qw), lut_gemm(qa, qw), rtol=1e-3, atol=1e-3
    )


# ---------------------------------------------------------------------------
# outliers: look-ahead + compensation exactness (paper Fig. 4/7)
# ---------------------------------------------------------------------------

def _outlier_setup(seed=0, m=8, k=64, n=32, frac=0.05):
    w = _rand((k, n), seed, 0.5)
    x = _rand((m, k), seed + 1)
    x = x.at[0, 3].set(9.0).at[2, 10].set(-7.0)  # inject outliers
    cfg = QLinearConfig(detection="dynamic", outlier_frac=frac)
    p = quantize_linear(w, x, cfg)
    return w, x, cfg, p


def test_lookahead_equals_detect_then_split():
    """Y* + Y' == (quantized inliers + FP outliers) @ W~  — bit-level claim."""
    w, x, cfg, p = _outlier_setup()
    y = qlinear_apply(p, x, cfg)
    k = num_outliers(x.shape[-1], cfg.outlier_frac)
    outs = detect_outliers_topk(x, k)
    deq_a = dequantize_activation(quantize_activation(x, p.act_codebook))
    onehot = jax.nn.one_hot(outs.channels, x.shape[-1]).sum(-2)
    x_split = jnp.where(onehot > 0, x, deq_a)
    y_split = x_split @ dequantize_weight(p.qw)
    np.testing.assert_allclose(y, y_split, rtol=1e-4, atol=1e-4)


def test_gather_equals_scatter_compensation():
    w, x, cfg, p = _outlier_setup()
    y_g = qlinear_apply(p, x, QLinearConfig(outlier_frac=0.05, comp_mode="gather"))
    y_s = qlinear_apply(p, x, QLinearConfig(outlier_frac=0.05, comp_mode="scatter"))
    np.testing.assert_allclose(y_g, y_s, rtol=1e-4, atol=1e-4)


def test_outlier_compensation_improves_accuracy():
    w, x, cfg, p = _outlier_setup(frac=0.05)
    y_ref = x @ w
    y_with = qlinear_apply(p, x, cfg)
    y_without = qlinear_apply(p, x, QLinearConfig(detection="none"))
    err_with = float(jnp.linalg.norm(y_with - y_ref))
    err_without = float(jnp.linalg.norm(y_without - y_ref))
    assert err_with < err_without


def test_static_detection_masks_non_violations():
    x = _rand((4, 64), 5)
    lo, hi = static_thresholds(x, 0.02)
    outs = detect_outliers_static(x, lo, hi, k=4)
    # masked slots contribute exactly zero residual
    qa = quantize_activation(x, fit_activation_codebook(x, 4))
    r = outlier_residuals(outs, qa)
    assert np.all(np.asarray(r)[np.asarray(outs.mask) == 0] == 0)


def test_orizuru_comparison_count_beats_spatten():
    from repro.core.outlier import naive_topk_comparisons

    for n in (1024, 4096, 12288):
        k = max(1, n // 200)
        assert orizuru_comparisons(n, k) < naive_topk_comparisons(n)


def test_dynamic_beats_static_on_shifted_distribution():
    """Paper Fig. 3: offline thresholds transfer poorly across datasets ->
    dynamic detection compensates more error than static."""
    w = _rand((64, 32), 11, 0.5)
    calib = _rand((64, 64), 12)  # offline calibration data
    online = _rand((16, 64), 13) * 2.0 + 0.5  # shifted online distribution
    cfg_d = QLinearConfig(detection="dynamic", outlier_frac=0.05)
    cfg_s = QLinearConfig(detection="static", outlier_frac=0.05)
    p_d = quantize_linear(w, calib, cfg_d)
    p_s = quantize_linear(w, calib, cfg_s)
    y_ref = online @ w
    err_d = float(jnp.linalg.norm(qlinear_apply(p_d, online, cfg_d) - y_ref))
    err_s = float(jnp.linalg.norm(qlinear_apply(p_s, online, cfg_s) - y_ref))
    assert err_d <= err_s * 1.05  # dynamic at least matches static


def test_static_dense_compensation_matches_semantics():
    """static_dense (prefill path): dense masked compensation == exact
    correction of every threshold-violating activation."""
    w = _rand((64, 32), 21, 0.5)
    x = _rand((8, 64), 22)
    x = x.at[1, 5].set(7.0)
    cfg = QLinearConfig(detection="static_dense", outlier_frac=0.02)
    p = quantize_linear(w, x, cfg)
    y = qlinear_apply(p, x, cfg)
    # manual: lookahead + dense masked residual
    qa = quantize_activation(x, p.act_codebook)
    deq = dequantize_activation(qa)
    mask = (x > p.thr_hi) | (x < p.thr_lo)
    y_ref = deq @ dequantize_weight(p.qw) + jnp.where(mask, x - deq, 0) @ dequantize_weight(p.qw)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    assert bool(mask.any())  # the injected outlier is actually compensated


def test_bf16_fused_quantize_close_to_f32_path():
    """Production bf16 sum-of-compares bucketize agrees with the exact f32
    searchsorted path on all but boundary-rounding ties."""
    x32 = _rand((64, 128), 31)
    book = fit_activation_codebook(x32, 4)
    qa32 = quantize_activation(x32, book)
    qa16 = quantize_activation(x32.astype(jnp.bfloat16), book)
    assert qa16.idx.dtype == jnp.int8
    mismatch = float(jnp.mean((qa16.idx.astype(jnp.int32) != qa32.idx).astype(jnp.float32)))
    assert mismatch < 0.02, mismatch  # bf16 rounding flips only boundary ties
