"""Per-kernel validation: shape/dtype sweeps + property tests vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests need hypothesis; the parity sweeps below do not
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - env-dependent
    HAVE_HYPOTHESIS = False

    def given(**kw):  # stub decorators so the defs still parse
        return lambda fn: pytest.mark.skip(reason="hypothesis not installed")(fn)

    def settings(**kw):
        return lambda fn: fn

    class st:  # noqa: N801 - stand-in for hypothesis.strategies
        @staticmethod
        def integers(*a, **kw):
            return None

        @staticmethod
        def data():
            return None

from repro.core.codebook import boundaries_from_centroids
from repro.core.outlier import (
    OutlierSet,
    compensate_gather,
    detect_outliers_static,
    detect_outliers_topk,
    outlier_mask,
    outlier_residuals_direct,
)
from repro.core.quantize import (
    dequantize_weight,
    fit_activation_codebook,
    quantize_activation,
    quantize_weight,
    token_scale,
)
from repro.kernels import ops, ref
from repro.kernels.bucketize import bucketize_kernel_call
from repro.kernels.lut_gemm import lut_gemm_kernel_call
from repro.kernels.topk_outlier import topk_outlier_kernel_call


def _books(seed, n_a=16, n_w=16):
    a = jnp.sort(jax.random.normal(jax.random.PRNGKey(seed), (n_a,)))
    w = jnp.sort(jax.random.normal(jax.random.PRNGKey(seed + 1), (n_w,)))
    return a, w


# ---------------------------------------------------------------------------
# lut_gemm kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "m,k,n,bm,bn,bk",
    [
        (1, 128, 64, 8, 32, 128),     # decode-like M=1
        (24, 256, 48, 16, 16, 128),   # ragged M/N vs blocks (padding path)
        (128, 512, 128, 128, 128, 512),  # exactly one MXU-aligned block
        (33, 384, 130, 32, 64, 128),  # everything ragged
        (7, 128, 2, 8, 2, 64),        # tiny N
    ],
)
def test_lut_gemm_kernel_shapes(m, k, n, bm, bn, bk):
    key = jax.random.PRNGKey(m * 7 + n)
    a_idx = jax.random.randint(key, (m, k), 0, 16)
    w_packed = jax.random.randint(jax.random.PRNGKey(1), (k, n // 2), 0, 256).astype(jnp.uint8)
    a_book, w_book = _books(2)
    y = lut_gemm_kernel_call(a_idx, w_packed, a_book, w_book, block_m=bm, block_n=bn, block_k=bk)
    np.testing.assert_allclose(y, ref.lut_gemm_ref(a_idx, w_packed, a_book, w_book),
                               rtol=1e-5, atol=1e-4)


def test_lut_gemm_kernel_3bit_activations():
    """3-bit activation codebook (W4A3, the paper's OASIS-A3 config)."""
    a_book, w_book = _books(3, n_a=8)
    a_idx = jax.random.randint(jax.random.PRNGKey(0), (16, 128), 0, 8)
    w_packed = jax.random.randint(jax.random.PRNGKey(1), (128, 32), 0, 256).astype(jnp.uint8)
    y = lut_gemm_kernel_call(a_idx, w_packed, a_book, w_book, block_m=8, block_n=32, block_k=64)
    np.testing.assert_allclose(y, ref.lut_gemm_ref(a_idx, w_packed, a_book, w_book),
                               rtol=1e-5, atol=1e-4)


def test_lut_gemm_kernel_unaligned_k():
    """K not divisible by block_k is PADDED (used to raise): padding columns
    must contribute exactly zero, not book[0]*book[0] garbage."""
    a_book, w_book = _books(4)
    a_idx = jax.random.randint(jax.random.PRNGKey(0), (4, 100), 0, 16)
    w_packed = jax.random.randint(jax.random.PRNGKey(1), (100, 8), 0, 256).astype(jnp.uint8)
    y = lut_gemm_kernel_call(a_idx, w_packed, a_book, w_book, block_k=64)
    np.testing.assert_allclose(y, ref.lut_gemm_ref(a_idx, w_packed, a_book, w_book),
                               rtol=1e-5, atol=1e-4)


def test_lut_gemm_kernel_rejects_odd_block_n():
    """Nibble tier packs two columns per byte: odd block_n cannot tile it."""
    a_book, w_book = _books(4)
    a_idx = jnp.zeros((4, 128), jnp.int32)
    w_packed = jnp.zeros((128, 8), jnp.uint8)
    with pytest.raises(ValueError):
        lut_gemm_kernel_call(a_idx, w_packed, a_book, w_book, block_n=7)


def test_ops_lut_gemm_matches_core_and_counting():
    """Kernel path == factorized jnp == counting-form oracle, with scales."""
    from repro.core.lut_gemm import lut_gemm as lut_jnp
    from repro.core.lut_gemm import lut_gemm_counting

    w = jax.random.normal(jax.random.PRNGKey(11), (256, 64))
    x = jax.random.normal(jax.random.PRNGKey(12), (10, 256))
    qw = quantize_weight(w, 4)
    qa = quantize_activation(x, fit_activation_codebook(x, 4))
    y_kernel = ops.lut_gemm(qa, qw)
    np.testing.assert_allclose(y_kernel, lut_jnp(qa, qw), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(y_kernel, lut_gemm_counting(qa, qw), rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(1, 40),
    kb=st.integers(1, 4),
    n=st.integers(1, 40),
    seed=st.integers(0, 2**31 - 1),
)
def test_lut_gemm_kernel_property(m, kb, n, seed):
    k = kb * 64
    key = jax.random.PRNGKey(seed)
    a_idx = jax.random.randint(key, (m, k), 0, 16)
    w_packed = jax.random.randint(jax.random.fold_in(key, 1), (k, n), 0, 256).astype(jnp.uint8)
    a_book, w_book = _books(seed % 1000)
    y = lut_gemm_kernel_call(a_idx, w_packed, a_book, w_book, block_m=16, block_n=32, block_k=64)
    np.testing.assert_allclose(y, ref.lut_gemm_ref(a_idx, w_packed, a_book, w_book),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# byte-packed weight tier (W5-W8) + W3
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("w_nbits", [5, 6, 7, 8])
def test_lut_gemm_kernel_byte_tier(w_nbits):
    """One-hot-matmul 256-entry lookup == gather oracle, every byte tier."""
    n_w = 2 ** w_nbits
    a_book, w_book = _books(w_nbits, n_w=n_w)
    a_idx = jax.random.randint(jax.random.PRNGKey(0), (9, 256), 0, 16)
    w_idx = jax.random.randint(jax.random.PRNGKey(1), (256, 40), 0, n_w).astype(jnp.uint8)
    y = lut_gemm_kernel_call(a_idx, w_idx, a_book, w_book, byte_packed=True,
                             block_m=8, block_n=32, block_k=128)
    np.testing.assert_allclose(y, ref.lut_gemm_byte_ref(a_idx, w_idx, a_book, w_book),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(1, 64, 7), (33, 300, 130), (8, 512, 64)])
def test_lut_gemm_kernel_byte_padding(m, k, n):
    """Odd/unaligned M, K, N on the byte tier: padding must contribute zero."""
    a_book, w_book = _books(9, n_w=256)
    a_idx = jax.random.randint(jax.random.PRNGKey(m), (m, k), 0, 16)
    w_idx = jax.random.randint(jax.random.PRNGKey(n), (k, n), 0, 256).astype(jnp.uint8)
    y = lut_gemm_kernel_call(a_idx, w_idx, a_book, w_book, byte_packed=True)
    np.testing.assert_allclose(y, ref.lut_gemm_byte_ref(a_idx, w_idx, a_book, w_book),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("w_nbits", [3, 8])
def test_ops_lut_gemm_matches_counting_w3_w8(w_nbits):
    """ops.lut_gemm (nibble W3 / byte W8 dispatch) == counting-form oracle."""
    from repro.core.lut_gemm import lut_gemm_counting

    w = jax.random.normal(jax.random.PRNGKey(21), (192, 80))
    x = jax.random.normal(jax.random.PRNGKey(22), (6, 192))
    qw = quantize_weight(w, w_nbits)
    qa = quantize_activation(x, fit_activation_codebook(x, 4))
    np.testing.assert_allclose(ops.lut_gemm(qa, qw), lut_gemm_counting(qa, qw),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused quantize+GEMM kernel
# ---------------------------------------------------------------------------

def _fused_case(seed, m, k, n, w_nbits, dtype):
    key = jax.random.PRNGKey(seed)
    x = (jax.random.normal(key, (m, k)) * 2).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (k, n))
    qw = quantize_weight(w, w_nbits)
    book = fit_activation_codebook(jax.random.normal(jax.random.fold_in(key, 2), (64, k)), 4)
    return x, qw, book


@pytest.mark.parametrize("m,k,n,w_nbits", [
    (1, 128, 64, 4),      # decode M=1, nibble
    (24, 300, 130, 4),    # everything ragged, nibble
    (8, 256, 48, 8),      # byte tier
    (33, 190, 66, 3),     # W3 nibble, odd K
])
def test_fused_kernel_matches_ref(m, k, n, w_nbits):
    from repro.kernels.lut_gemm import fused_lut_gemm_kernel_call

    x, qw, book = _fused_case(m * 31 + n, m, k, n, w_nbits, jnp.float32)
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 3 + 1e-6
    b = boundaries_from_centroids(book)
    y = fused_lut_gemm_kernel_call(x, s, qw.packed, b, book, qw.codebook,
                                   byte_packed=w_nbits > 4, mul_form=False)
    want = ref.fused_lut_gemm_ref(x, s, qw.packed, b, book, qw.codebook,
                                  byte_packed=w_nbits > 4, mul_form=False)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ops_bit_identical_indices(dtype):
    """lut_gemm_fused == quantize_activation + lut_gemm for both dtype forms
    (f32 searchsorted form, bf16 sum-of-compares mul form) — the property
    that makes kernel routing token-identical in serving."""
    from repro.core.lut_gemm import lut_gemm as lut_jnp

    x, qw, book = _fused_case(5, 16, 256, 64, 4, dtype)
    y_fused = ops.lut_gemm_fused(x, book, qw)
    qa = quantize_activation(x, book)
    y_two = lut_jnp(qa, qw, out_dtype=jnp.float32, compute_dtype=jnp.float32)
    np.testing.assert_allclose(y_fused, y_two, rtol=2e-5, atol=1e-4)


def _outlier_case(x, k_out, static):
    """Outliers of ``x``: Orizuru's top-k + bottom-k, or a static-style set
    whose thresholds leave some slots masked."""
    xf = x.astype(jnp.float32)
    if not static:
        return detect_outliers_topk(xf, k_out)
    hi = jnp.quantile(xf, 0.99)
    return detect_outliers_static(xf, -hi, hi, k_out)


def _marked(outs, k):
    """The oracle's own outlier mask, set slot by slot in numpy."""
    ch, keep = np.asarray(outs.channels), np.asarray(outs.mask) > 0
    marked = np.zeros((ch.shape[0], k), bool)
    for row in range(ch.shape[0]):
        marked[row, ch[row][keep[row]]] = True
    return marked


@pytest.mark.parametrize("static", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("m,k,n,w_nbits,bm,bk", [
    (24, 300, 130, 4, 16, 128),  # nibble tier, M % bm and K % bk ragged
    (13, 190, 66, 8, 16, 128),   # byte tier, M % bm and K % bk ragged
])
def test_fused_kernel_outlier_fold_matches_oracle(m, k, n, w_nbits, bm, bk,
                                                  dtype, static):
    """Outlier operand in the tile == detect-then-split in plain jnp:
    centroids everywhere, the exact x/s at the marked channels, then the
    scaled GEMM."""
    from repro.kernels.lut_gemm import fused_lut_gemm_kernel_call

    x, qw, book = _fused_case(m + k, m, k, n, w_nbits, dtype)
    outs = _outlier_case(x, 4, static)
    if static:
        keep = np.asarray(outs.mask)
        assert 0 < keep.sum() < keep.size  # some slots really are masked
    qa = quantize_activation(x, book)
    y = fused_lut_gemm_kernel_call(
        x, qa.scale, qw.packed, boundaries_from_centroids(book), book,
        qw.codebook, byte_packed=w_nbits > 4, mul_form=dtype == jnp.bfloat16,
        outlier_mask=outlier_mask(outs, k), block_m=bm, block_k=bk)
    xf = x.astype(jnp.float32)
    a = jnp.where(_marked(outs, k), xf / qa.scale,
                  book[qa.idx.astype(jnp.int32)])
    want = (a * qa.scale) @ dequantize_weight(qw)
    np.testing.assert_allclose(y * qa.scale * qw.scale, want,
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("w_nbits", [4, 8])
def test_fused_fold_matches_gather_compensation(w_nbits):
    """The folded kernel == today's separate branch (fused main GEMM plus
    the row-gather compensation), on inputs with distinct values."""
    x, qw, book = _fused_case(11, 21, 260, 96, w_nbits, jnp.float32)
    assert all(np.unique(row).size == row.size for row in np.asarray(x))
    outs = detect_outliers_topk(x, 3)
    r = outlier_residuals_direct(outs, token_scale(x), book)
    y_split = ops.lut_gemm_fused(x, book, qw) + compensate_gather(r, outs, qw)
    np.testing.assert_allclose(ops.lut_gemm_fused(x, book, qw, outliers=outs),
                               y_split, rtol=2e-5, atol=1e-4)


def test_outlier_mask_marks_each_channel_once():
    """Masked slots mark nothing; a channel in both the top-k and the
    bottom-k (a token with fewer than 2k distinct values) is marked once."""
    outs = OutlierSet(values=jnp.zeros((2, 4)),
                      channels=jnp.array([[1, 3, 3, 0], [2, 5, 0, 4]], jnp.int32),
                      mask=jnp.array([[1, 1, 1, 1], [1, 0, 1, 0]], jnp.float32))
    got = outlier_mask(outs, 6)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(got, [[1, 1, 0, 1, 0, 0], [1, 0, 1, 0, 0, 0]])


def test_fused_leading_batch_dims():
    x, qw, book = _fused_case(6, 12, 128, 32, 4, jnp.float32)
    x3 = x.reshape(3, 4, 128)
    y3 = ops.lut_gemm_fused(x3, book, qw)
    assert y3.shape == (3, 4, 32)
    np.testing.assert_allclose(y3.reshape(12, 32), ops.lut_gemm_fused(x, book, qw),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# bucketize kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,nbits", [(4, 64, 4), (37, 130, 4), (128, 512, 3), (1, 16, 4)])
def test_bucketize_kernel(m, k, nbits):
    x = jax.random.normal(jax.random.PRNGKey(m), (m, k)) * 2
    book = jnp.sort(jax.random.normal(jax.random.PRNGKey(5), (2**nbits,)))
    b = boundaries_from_centroids(book)
    got = bucketize_kernel_call(x, b, block_m=16, block_k=64)
    np.testing.assert_array_equal(got, ref.bucketize_ref(x, b))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 64), k=st.integers(1, 256))
def test_bucketize_is_nearest_centroid(seed, m, k):
    """Property: boundary bucketize == argmin |x - c| (the K-Means assignment)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, k)) * 3
    book = jnp.sort(jax.random.normal(jax.random.PRNGKey(seed + 1), (16,)))
    got = bucketize_kernel_call(x, boundaries_from_centroids(book))
    nearest = jnp.argmin(jnp.abs(x[..., None] - book), axis=-1)
    np.testing.assert_array_equal(got, nearest)


# ---------------------------------------------------------------------------
# topk_outlier kernel (Orizuru)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [(1, 64, 5), (13, 64, 5), (32, 128, 9), (5, 16, 8), (8, 4096, 20)])
def test_topk_kernel_random(m, n, k):
    x = jax.random.normal(jax.random.PRNGKey(m + n), (m, n))
    hv, hi, lv, li = topk_outlier_kernel_call(x, k, block_m=4)
    rhv, rhi, rlv, rli = ref.topk_outlier_ref(x, k)
    np.testing.assert_array_equal(hv, rhv)
    np.testing.assert_array_equal(hi, rhi)
    np.testing.assert_array_equal(lv, rlv)
    np.testing.assert_array_equal(li, rli)


def test_topk_kernel_ties_deterministic():
    """Heavy ties: integer-valued activations (the paper's ~2%-of-tokens case)."""
    x = jax.random.randint(jax.random.PRNGKey(7), (13, 64), -5, 6).astype(jnp.float32)
    hv, hi, lv, li = topk_outlier_kernel_call(x, 6, block_m=4)
    rhv, rhi, rlv, rli = ref.topk_outlier_ref(x, 6)
    np.testing.assert_array_equal(hi, rhi)
    np.testing.assert_array_equal(li, rli)


def test_topk_kernel_exhausts_pairs():
    """k > N/2: some pairs fully popped (both leaves) — tree maintenance must
    fall back through B to -inf without corrupting order; k > N must raise."""
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16))
    hv, hi, lv, li = topk_outlier_kernel_call(x, 10, block_m=4)
    rhv, rhi, rlv, rli = ref.topk_outlier_ref(x, 10)
    np.testing.assert_array_equal(hi, rhi)
    np.testing.assert_array_equal(li, rli)
    with pytest.raises(ValueError):
        topk_outlier_kernel_call(x, 17, block_m=4)


def test_topk_kernel_full_n():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 16))
    hv, hi, lv, li = topk_outlier_kernel_call(x, 16, block_m=4)
    rhv, rhi, rlv, rli = ref.topk_outlier_ref(x, 16)
    np.testing.assert_array_equal(hi, rhi)
    np.testing.assert_array_equal(li, rli)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), m=st.integers(1, 16), half_n=st.integers(1, 64),
       data=st.data())
def test_topk_kernel_property(seed, m, half_n, data):
    n = 2 * half_n
    k = data.draw(st.integers(1, n))
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, n))
    hv, hi, lv, li = topk_outlier_kernel_call(x, k, block_m=8)
    rhv, rhi, rlv, rli = ref.topk_outlier_ref(x, k)
    np.testing.assert_array_equal(hi, rhi)
    np.testing.assert_array_equal(li, rli)


def test_ops_topk_matches_core():
    x = jax.random.normal(jax.random.PRNGKey(12), (6, 10, 64))
    o = ops.topk_outlier(x, 3)
    o2 = detect_outliers_topk(x, 3)
    np.testing.assert_array_equal(o.values, o2.values)
    np.testing.assert_array_equal(o.channels, o2.channels)
