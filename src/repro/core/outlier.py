"""Outlier-aware quantization: detection + look-ahead error compensation (§III-C, §IV-D).

The paper keeps the top-0.5% largest and bottom-0.5% smallest activations per
token in FP16. Instead of detect-then-split (detection on the critical path,
Fig. 4(a)), the **look-ahead** scheme (Fig. 4(b)) runs two branches:

  main branch    : quantize EVERYTHING (outliers land on their nearest
                   centroid) and start the LUT-GEMM immediately;
  outlier branch : find the outliers, compute residuals r = x - q(x), and
                   compensate  Y' = r_outlier @ W~[outlier_channels, :].

Y* + Y' is mathematically identical to detect-then-split — asserted bit-level
(fp32) in tests.

On the fused LUT-GEMM kernel route the two forms meet again: detection runs
first and :func:`outlier_mask` hands the kernel each token's outlier
channels, where the tile uses the exact activation instead of its centroid —
detect-then-split inside the one dot, so no weight row is gathered or
unpacked. :func:`compensate_gather` / :func:`compensate_scatter` serve the
jnp route (and the kernel route's explicit ``comp_mode`` ablation).

TPU adaptation of Orizuru: the ASIC pops one (value, index) per cycle from a
two-fold tournament tree. On TPU we return the whole top-k/bottom-k at once
(``jax.lax.top_k`` here; the Pallas kernel in ``kernels/topk_outlier.py``
keeps the paper's shared-pairwise-comparison trick). The comparison-count
analytics (1.5N + 2k·log2 N vs 6N for SpAtten's engine) are reproduced in
``benchmarks/bench_orizuru.py``.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import codebook as cb
from repro.core.quantize import (
    QuantizedActivation,
    QuantizedWeight,
    dequantize_activation,
    dequantize_weight,
    lookup,
)

__all__ = [
    "OutlierSet",
    "num_outliers",
    "detect_outliers_topk",
    "detect_outliers_static",
    "static_thresholds",
    "outlier_residuals",
    "outlier_residuals_direct",
    "outlier_mask",
    "compensate_gather",
    "compensate_scatter",
    "orizuru_comparisons",
    "naive_topk_comparisons",
]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["values", "channels", "mask"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class OutlierSet:
    """Per-token outliers: FP values, channel indices, and a validity mask.

    values   : fp32 (..., T) original FP activation values
    channels : int32 (..., T) channel indices within the token
    mask     : fp32 (..., T) 1.0 where the slot holds a real outlier
               (static-threshold detection can yield < T genuine outliers;
               masked slots contribute exactly zero to compensation).
    """

    values: jax.Array
    channels: jax.Array
    mask: jax.Array


def num_outliers(k_channels: int, frac: float) -> int:
    """Outliers per side for a token of ``k_channels`` (paper: frac=0.005)."""
    return max(1, int(round(k_channels * frac)))


def detect_outliers_topk(x: jax.Array, k: int) -> OutlierSet:
    """Dynamic detection: top-k largest AND bottom-k smallest per token.

    This is the Orizuru contract: exactly k max + k min per token, determinism
    on ties inherited from ``lax.top_k`` (stable, lowest-index-first — the
    paper's left-child tie-break has the same "always exactly k" property).
    """
    hi_v, hi_i = jax.lax.top_k(x, k)
    lo_v_neg, lo_i = jax.lax.top_k(-x, k)
    values = jnp.concatenate([hi_v, -lo_v_neg], axis=-1).astype(jnp.float32)
    channels = jnp.concatenate([hi_i, lo_i], axis=-1).astype(jnp.int32)
    return OutlierSet(values=values, channels=channels, mask=jnp.ones_like(values))


def static_thresholds(calib_x: jax.Array, frac: float = 0.005) -> tuple[jax.Array, jax.Array]:
    """OASIS-S: offline thresholds from a calibration set (per layer).

    Returns scalar (lo, hi) = (frac, 1-frac) quantiles over all calibration
    activations. The paper's Fig. 3 shows these transfer poorly across
    datasets — which is exactly what the OASIS-vs-OASIS-S benchmark measures.
    """
    flat = calib_x.reshape(-1).astype(jnp.float32)
    lo = jnp.quantile(flat, frac)
    hi = jnp.quantile(flat, 1.0 - frac)
    return lo, hi


def detect_outliers_static(x: jax.Array, lo: jax.Array, hi: jax.Array, k: int) -> OutlierSet:
    """Static (OASIS-S) detection with fixed-shape output.

    Scores threshold violations, keeps the top-2k violators, masks the rest.
    (A token may have fewer than 2k violations — extra slots get mask=0 — or
    more — excess smallest violations are dropped, mirroring a fixed-budget
    outlier buffer in the ASIC.)
    """
    score = jnp.maximum(x - hi, 0.0) + jnp.maximum(lo - x, 0.0)
    sv, si = jax.lax.top_k(score, 2 * k)
    values = jnp.take_along_axis(x, si, axis=-1).astype(jnp.float32)
    return OutlierSet(
        values=values,
        channels=si.astype(jnp.int32),
        mask=(sv > 0).astype(jnp.float32),
    )


def outlier_residuals(out: OutlierSet, qa: QuantizedActivation) -> jax.Array:
    """r = x - q(x) at the outlier channels (paper's Error Calculation Unit)."""
    deq = dequantize_activation(qa)
    q_at = jnp.take_along_axis(deq, out.channels, axis=-1)
    return (out.values - q_at) * out.mask


def outlier_residuals_direct(
    out: OutlierSet, scale: jax.Array, codebook: jax.Array,
    mul_form: bool = False,
) -> jax.Array:
    """r = x - q(x) computed from the outlier VALUES alone — no full
    QuantizedActivation required.

    The fused-kernel route never materializes activation indices (they live
    only in VMEM), but quantization is elementwise, so q(x) at the 2k
    outlier channels per token can be recomputed from the gathered values
    and the per-token ``scale`` directly. Bit-identical to
    :func:`outlier_residuals` as long as the compare form matches the dtype
    ``quantize_activation`` would have used: ``mul_form=False`` for f32
    inputs (searchsorted on x/s), ``mul_form=True`` for bf16 (sum-of-
    compares against s*b_i).
    """
    v = out.values  # f32 (..., T), originals gathered at detection time
    if mul_form:
        b = cb.boundaries_from_centroids(codebook)
        idx = jnp.zeros(v.shape, jnp.int32)
        for i in range(b.shape[0]):
            idx += (v >= scale * b[i]).astype(jnp.int32)
    else:
        idx = cb.assign_via_boundaries((v / scale).astype(jnp.float32), codebook)
    deq = lookup(codebook, idx) * scale
    return (v - deq) * out.mask


def outlier_mask(out: OutlierSet, k_channels: int) -> jax.Array:
    """Dense (..., K) int32 mask: 1 at each token's outlier channels.

    The fused LUT-GEMM kernel's outlier operand (detect-then-split in the
    tile). Masked slots mark nothing, and a channel listed twice (top-k and
    bottom-k of a token with fewer than 2k distinct values) is marked once.
    A one-hot compare-reduce over the 2k slots.
    """
    ch = jnp.where(out.mask > 0, out.channels, -1)
    hit = ch[..., None] == jnp.arange(k_channels, dtype=jnp.int32)
    return jnp.any(hit, axis=-2).astype(jnp.int32)


def compensate_gather(
    residuals: jax.Array, out: OutlierSet, qw: QuantizedWeight, compute_dtype=jnp.float32
) -> jax.Array:
    """Y'[m, n] = Σ_t r[m, t] · W~[ch[m, t], n], via per-token weight-row gather.

    Mirrors the ASIC outlier branch: fetch one input channel of the weight
    index matrix per outlier, dequantize just those rows (Dequantization
    Unit), multiply-accumulate. Preferred when M (tokens) is small — decode.
    """
    w_idx_rows = jnp.take(qw.indices, out.channels, axis=0)  # (..., T, N)
    w_rows = (lookup(qw.codebook, w_idx_rows) * qw.scale).astype(compute_dtype)
    return jnp.einsum("...t,...tn->...n", residuals.astype(compute_dtype), w_rows)


def compensate_scatter(
    residuals: jax.Array, out: OutlierSet, qw: QuantizedWeight, compute_dtype=jnp.float32
) -> jax.Array:
    """Scatter residuals into a dense (..., K) matrix, one dense GEMM with W~.

    Preferred at prefill (large M): a dense MXU matmul at ~1% density beats
    M·T row gathers in HBM traffic once M is large. Selection logic lives in
    ``core/qlinear.py``.

    Implemented as a true scatter-add (O(M·K) memory). The obvious one-hot
    einsum is O(M·T·K) — measured 300+ GB/device at 32k prefill on
    nemotron-15b before this was rewritten.
    """
    k_channels = qw.shape[0]
    lead = residuals.shape[:-1]
    t = residuals.shape[-1]
    # Scatter with the leading (batch, seq) dims KEPT as explicit batch index
    # dims: GSPMD partitions batch-parallel scatters along sharded leading
    # dims, whereas the flattened (M, K) form was replicated per device
    # (observed ~73 GB/device of transients at 32k prefill — three concurrent
    # projections' scatter buffers, each fully replicated).
    idx = [
        jax.lax.broadcasted_iota(jnp.int32, (*lead, t), i) for i in range(len(lead))
    ]
    r_dense = jnp.zeros((*lead, k_channels), compute_dtype).at[
        (*idx, out.channels)
    ].add(residuals.astype(compute_dtype))
    w = dequantize_weight(qw, compute_dtype)
    return jnp.einsum("...k,kn->...n", r_dense, w)


# ---------------------------------------------------------------------------
# Orizuru comparison-count analytics (paper §IV-D)
# ---------------------------------------------------------------------------

def orizuru_comparisons(n: int, k: int) -> int:
    """1.5N + 2k·log2(N): init max tree (N-1 ≈ N), min tree reuses level-1
    comparisons (N/2 saved), each of 2k pops costs log2 N maintenance."""
    import math

    return int(1.5 * n + 2 * k * math.log2(n))


def naive_topk_comparisons(n: int) -> int:
    """SpAtten-style top-k engine baseline: ~6N comparisons."""
    return 6 * n
