"""Jit'd public wrappers around the Pallas kernels.

These adapt the kernels to the core library's types (QuantizedActivation /
QuantizedWeight / OutlierSet), handle arbitrary leading batch dims, apply the
rank-1 scales, and auto-select interpret mode off-TPU. On a TPU they run
compiled (``interpret=False``): ``tests/test_tpu_compile.py`` compiles the
fused LUT-GEMM (W4 nibble and W8 byte tiers, f32 and bf16 inputs), both
Orizuru kernels and both paged-attention pools for a described v5e at
h2o-danube-1.8B widths, and ``chip_smoke.py`` serves that model through
all of them on a v5e chip.

``lut_gemm`` dispatches both weight tiers (nibble-packed <= 4 bits, byte-
packed 5..8 bits); ``lut_gemm_fused`` is the serving hot path: raw
activations in, quantization fused into the GEMM tile (no idx HBM
roundtrip). Block sizes come from explicit ``blocks=`` overrides, else from
the :func:`autotune_lut_blocks` cache (populated by an explicit sweep — run
it before the first traced call for a shape; benchmarks do), else kernel
defaults.
"""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.codebook import boundaries_from_centroids
from repro.core.outlier import OutlierSet
from repro.core.quantize import QuantizedActivation, QuantizedWeight, token_scale
from repro.kernels.bucketize import bucketize_kernel_call
from repro.kernels.lut_gemm import fused_lut_gemm_kernel_call, lut_gemm_kernel_call
from repro.kernels.topk_outlier import (
    streaming_quantize_outlier_kernel_call,
    topk_outlier_kernel_call,
)

__all__ = ["lut_gemm", "lut_gemm_fused", "bucketize", "topk_outlier",
           "quantize_outlier_streaming", "should_interpret",
           "autotune_lut_blocks", "index_histogram"]


def should_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _flatten_leading(x: jax.Array) -> tuple[jax.Array, tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


# ---------------------------------------------------------------------------
# block-size autotune (per (M, K, N, tier, fused) shape key)
# ---------------------------------------------------------------------------

# shape key -> (block_m, block_n, block_k). Consulted at TRACE time by the
# wrappers below when no explicit override is given; a jitted caller that
# traced before the sweep keeps its compiled defaults (jit caches by shape).
_BLOCK_CACHE: dict[tuple, tuple[int, int, int]] = {}

# block_n >= 256 keeps a nibble tile's packed block (block_n / 2 lanes) on
# the TPU's 128-lane tiling
_CANDIDATES = (
    (128, 256, 512),
    (128, 256, 256),
    (128, 512, 256),
    (256, 256, 128),
    (64, 256, 256),
    (8, 256, 512),
)


def _block_key(m: int, k: int, n: int, w_nbits: int, a_nbits: int,
               fused: bool) -> tuple:
    return (m, k, n, w_nbits, a_nbits, fused)


def _cached_blocks(m, k, n, w_nbits, a_nbits, fused) -> dict:
    hit = _BLOCK_CACHE.get(_block_key(m, k, n, w_nbits, a_nbits, fused))
    if hit is None:
        return {}
    bm, bn, bk = hit
    return {"block_m": bm, "block_n": bn, "block_k": bk}


def autotune_lut_blocks(
    x: jax.Array,
    codebook: jax.Array,
    qw: QuantizedWeight,
    *,
    fused: bool = True,
    candidates: tuple[tuple[int, int, int], ...] = _CANDIDATES,
    reps: int = 3,
) -> tuple[int, int, int]:
    """Small grid sweep over (block_m, block_n, block_k) for one GEMM shape.

    Times each candidate end-to-end through the jitted wrapper (compile
    excluded via a warmup call) and caches the winner; subsequent
    ``lut_gemm``/``lut_gemm_fused`` traces for the same shape pick it up.
    Returns the winning (bm, bn, bk).
    """
    x2d, _ = _flatten_leading(x)
    m, k = x2d.shape
    n = qw.shape[1]
    a_nbits = int(codebook.shape[0]).bit_length() - 1
    best, best_t = None, float("inf")
    for bm, bn, bk in candidates:
        blocks = (bm, bn, bk)
        if fused:
            fn = partial(lut_gemm_fused, x, codebook, qw, blocks=blocks)
        else:
            qa = _quantize_for_tune(x2d, codebook)
            fn = partial(lut_gemm, qa, qw, blocks=blocks)
        jax.block_until_ready(fn())  # compile + warmup
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / reps
        if dt < best_t:
            best, best_t = blocks, dt
    _BLOCK_CACHE[_block_key(m, k, n, qw.nbits, a_nbits, fused)] = best
    return best


def _quantize_for_tune(x2d, codebook):
    from repro.core.quantize import quantize_activation

    return quantize_activation(x2d, codebook)


@partial(jax.jit, static_argnames=("out_dtype", "blocks"))
def lut_gemm(qa: QuantizedActivation, qw: QuantizedWeight,
             out_dtype=jnp.float32,
             blocks: tuple[int, int, int] | None = None) -> jax.Array:
    """Kernel-backed factorized LUT-GEMM with scales. Matches core.lut_gemm.

    Dispatches on the weight tier: nibble-packed (<= 4 bits) or byte-packed
    (5..8 bits, the mixed-precision W8 tier).
    """
    idx2d, lead = _flatten_leading(qa.idx)
    m, k = idx2d.shape
    kw = (dict(zip(("block_m", "block_n", "block_k"), blocks)) if blocks
          else _cached_blocks(m, k, qw.shape[1], qw.nbits, qa.nbits, False))
    y = lut_gemm_kernel_call(
        idx2d.astype(jnp.int32),
        qw.packed,
        qa.codebook.astype(jnp.float32),
        qw.codebook.astype(jnp.float32),
        byte_packed=qw.nbits > 4,
        interpret=should_interpret(),
        **kw,
    )
    y = y.reshape(*lead, qw.shape[1])
    return (y * qa.scale * qw.scale).astype(out_dtype)


@partial(jax.jit, static_argnames=("scale_mode", "out_dtype", "blocks"))
def lut_gemm_fused(x: jax.Array, codebook: jax.Array, qw: QuantizedWeight,
                   scale_mode: str = "rms", out_dtype=jnp.float32,
                   blocks: tuple[int, int, int] | None = None) -> jax.Array:
    """Fused quantize+index-GEMM: raw activations in, scaled output out.

    The per-token scale (a rank-1 full-K reduction XLA fuses) is computed
    here; bucketize + centroid lookup + GEMM happen inside the kernel tile.
    Index selection is bit-identical to ``quantize_activation`` for the
    input dtype (f32: searchsorted form; bf16: sum-of-compares mul form),
    so routing through this path preserves greedy token identity with the
    jnp factorized route.
    """
    x2d, lead = _flatten_leading(x)
    m, k = x2d.shape
    a_nbits = int(codebook.shape[0]).bit_length() - 1
    kw = (dict(zip(("block_m", "block_n", "block_k"), blocks)) if blocks
          else _cached_blocks(m, k, qw.shape[1], qw.nbits, a_nbits, True))
    s = token_scale(x2d, scale_mode)  # (M, 1) f32
    book = codebook.astype(jnp.float32)
    y = fused_lut_gemm_kernel_call(
        x2d, s, qw.packed,
        boundaries_from_centroids(book), book,
        qw.codebook.astype(jnp.float32),
        byte_packed=qw.nbits > 4,
        mul_form=x.dtype == jnp.bfloat16,
        interpret=should_interpret(),
        **kw,
    )
    y = y.reshape(*lead, qw.shape[1])
    return (y * s.reshape(*lead, 1) * qw.scale).astype(out_dtype)


@partial(jax.jit, static_argnames=("n_bins",))
def index_histogram(idx: jax.Array, n_bins: int, weights=None) -> jax.Array:
    """Occupancy histogram of codebook indices: (n_bins,) f32 scatter-add.

    ``weights`` (optional, broadcast-compatible with ``idx``) lets callers
    mask elements out with 0/1 weights; counts stay integer-exact in f32 up
    to 2^24 elements per bin (numpy oracle: ``np.bincount``). Serves the
    quality-probe layer (core/numerics) — the indices come straight from the
    bucketize/streaming kernels' output, so the histogram audits exactly
    what the LUT-GEMM consumed.
    """
    flat = idx.reshape(-1).astype(jnp.int32)
    if weights is None:
        w = jnp.ones(flat.shape, jnp.float32)
    else:
        w = jnp.broadcast_to(weights, idx.shape).reshape(-1).astype(jnp.float32)
    return jnp.zeros((n_bins,), jnp.float32).at[flat].add(w)


@jax.jit
def bucketize(x: jax.Array, codebook: jax.Array) -> jax.Array:
    """Nearest-centroid indices via the Clustering-Unit kernel."""
    x2d, lead = _flatten_leading(x)
    idx = bucketize_kernel_call(
        x2d, boundaries_from_centroids(codebook), interpret=should_interpret()
    )
    return idx.reshape(*lead, x.shape[-1])


@partial(jax.jit, static_argnames=("k",))
def topk_outlier(x: jax.Array, k: int) -> OutlierSet:
    """Orizuru kernel -> OutlierSet (top-k then bottom-k, mask all-ones)."""
    x2d, lead = _flatten_leading(x)
    hi_v, hi_i, lo_v, lo_i = topk_outlier_kernel_call(
        x2d, k, interpret=should_interpret()
    )
    values = jnp.concatenate([hi_v, lo_v], axis=-1).reshape(*lead, 2 * k)
    channels = jnp.concatenate([hi_i, lo_i], axis=-1).reshape(*lead, 2 * k)
    return OutlierSet(values=values, channels=channels, mask=jnp.ones_like(values))


@partial(jax.jit, static_argnames=("k", "scale_mode"))
def quantize_outlier_streaming(
    x: jax.Array, codebook: jax.Array, k: int, scale_mode: str = "rms"
) -> tuple[QuantizedActivation, OutlierSet]:
    """One-pass activation quantize + Orizuru detect (the streaming form).

    Emits the SAME ``QuantizedActivation`` as ``quantize_activation`` (bit-
    identical indices and scale for either input dtype) and the SAME
    ``OutlierSet`` as ``topk_outlier`` on the f32 activations — but reads the
    activation tile once, so dynamic detection adds no extra HBM roundtrip
    at decode shapes.
    """
    x2d, lead = _flatten_leading(x)
    s = token_scale(x2d, scale_mode)  # (M, 1) f32
    book = codebook.astype(jnp.float32)
    mul_form = x.dtype == jnp.bfloat16
    idx, hi_v, hi_i, lo_v, lo_i = streaming_quantize_outlier_kernel_call(
        x2d.astype(jnp.float32), s, boundaries_from_centroids(book), k,
        mul_form=mul_form, interpret=should_interpret(),
    )
    if mul_form:
        idx = idx.astype(jnp.int8)  # quantize_activation's bf16 index dtype
    nbits = int(codebook.shape[0]).bit_length() - 1
    qa = QuantizedActivation(
        idx=idx.reshape(*lead, x.shape[-1]),
        scale=s.reshape(*lead, 1), codebook=codebook, nbits=nbits,
    )
    values = jnp.concatenate([hi_v, lo_v], axis=-1).reshape(*lead, 2 * k)
    channels = jnp.concatenate([hi_i, lo_i], axis=-1).reshape(*lead, 2 * k)
    outs = OutlierSet(values=values, channels=channels,
                      mask=jnp.ones_like(values))
    return qa, outs
