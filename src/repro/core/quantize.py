"""Dual-side (weight + activation) K-Means quantization (paper §III-A).

Weights  : n-bit K-Means, ONE codebook per weight matrix, per-output-channel
           scale, no outlier protection.
Activations: n-bit K-Means, per-token scale, codebook learned OFFLINE on a
           calibration set (paper Fig. 5 shows offline==online centroids after
           normalization; per-token *scales* stay dynamic).

Storage formats are honest about bytes (this feeds the roofline): weight
indices are packed two-4-bit-per-uint8 in HBM; codebooks are 2^n fp32 scalars;
scales are fp32 vectors.

Interpretation note (recorded in DESIGN.md): the paper says "each token has its
own set of quantization centroids and scaling factors" learned offline. A
literal per-unseen-token offline codebook is impossible; following the paper's
own Fig. 5 evidence we use an offline codebook in *scale-normalized* space plus
a dynamic per-token scale. Default scale is the token RMS (robust to the very
outliers the outlier branch compensates); ``absmax`` is available for ablation.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core import codebook as cb

__all__ = [
    "QuantizedWeight",
    "QuantizedActivation",
    "pack_int4",
    "unpack_int4",
    "lookup",
    "quantize_weight",
    "dequantize_weight",
    "token_scale",
    "quantize_activation",
    "dequantize_activation",
    "fit_activation_codebook",
]

ScaleMode = Literal["rms", "absmax"]


# ---------------------------------------------------------------------------
# int4 packing
# ---------------------------------------------------------------------------

def pack_int4(idx: jax.Array) -> jax.Array:
    """Pack 4-bit indices pairwise along the last axis into uint8.

    Last axis must be even. ``packed[..., i] = idx[..., 2i] | idx[..., 2i+1]<<4``.
    """
    if idx.shape[-1] % 2:
        raise ValueError(f"last axis must be even for int4 packing, got {idx.shape}")
    lo = idx[..., 0::2].astype(jnp.uint8)
    hi = idx[..., 1::2].astype(jnp.uint8)
    return lo | (hi << 4)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4`; returns int32 indices."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def lookup(codebook, idx: jax.Array) -> jax.Array:
    """``codebook[idx]`` for a 1-D codebook of up to 256 entries, as compare-
    select chains instead of a gather (same values).

    Up to 16 entries this is one chain of selects; larger codebooks select
    per high nibble among per-low-nibble chains. A TPU runs a gather from a
    small table one element at a time: at h2o-danube-1.8B serving shapes
    the codebook gathers took 5.3 s of a 5.7 s packed step on a v5e, the
    chains a few ms. ``codebook`` may be a Pallas SMEM ref (read one scalar
    at a time), so the kernels share this lookup.
    """
    n = codebook.shape[-1]
    idx = idx.astype(jnp.int32)

    def chain(sel, base, n_entries):
        out = jnp.full(sel.shape, codebook[base], codebook.dtype)
        for i in range(1, n_entries):
            out = jnp.where(sel == i, codebook[base + i], out)
        return out

    if n <= 16:
        return chain(idx, 0, n)
    hi, lo = idx >> 4, idx & 0xF
    out = chain(lo, 0, 16)
    for h in range(1, -(-n // 16)):
        out = jnp.where(hi == h, chain(lo, 16 * h, min(16, n - 16 * h)), out)
    return out


# ---------------------------------------------------------------------------
# Quantized containers (pytrees)
# ---------------------------------------------------------------------------

@partial(
    jax.tree_util.register_dataclass,
    data_fields=["packed", "codebook", "scale"],
    meta_fields=["shape", "nbits"],
)
@dataclasses.dataclass(frozen=True)
class QuantizedWeight:
    """K-Means-quantized weight matrix of logical shape ``shape = (K, N)``.

    packed   : uint8. nbits <= 4: (K, N//2) — two 4-bit codebook indices per
               byte (3-bit codebooks still use nibble packing; the wasted bit
               is accounted for in benchmarks). nbits in (5..8] — the
               mixed-precision W8 tier — stores one index per byte, (K, N).
    codebook : fp32 (2^nbits,) — sorted centroids, shared by the whole matrix.
    scale    : fp32 (N,)       — per-output-channel scale.
    """

    packed: jax.Array
    codebook: jax.Array
    scale: jax.Array
    shape: tuple[int, int]
    nbits: int

    @property
    def indices(self) -> jax.Array:
        """Unpacked int32 index matrix, shape ``(K, N)``."""
        if self.nbits <= 4:
            return unpack_int4(self.packed)
        return self.packed.astype(jnp.int32)

    def hbm_bytes(self) -> int:
        k, n = self.shape
        idx_bytes = k * n // 2 if self.nbits <= 4 else k * n
        return idx_bytes + self.codebook.size * 4 + n * 4


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["idx", "scale", "codebook"],
    meta_fields=["nbits"],
)
@dataclasses.dataclass(frozen=True)
class QuantizedActivation:
    """Per-token quantized activations.

    idx      : int32 (..., K) codebook indices (kept unpacked here: in the
               fused inference path indices exist only in VMEM; packed storage
               is used by the quantized KV cache).
    scale    : fp32 (..., 1) per-token scale.
    codebook : fp32 (2^nbits,) shared offline-learned centroids
               (normalized space).
    """

    idx: jax.Array
    scale: jax.Array
    codebook: jax.Array
    nbits: int


# ---------------------------------------------------------------------------
# Weight quantization (PTQ)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("nbits", "iters", "method"))
def quantize_weight(w: jax.Array, nbits: int = 4, iters: int = 25,
                    method: str = "kmeans") -> QuantizedWeight:
    """Post-training quantization of a ``(K, N)`` weight matrix.

    Per-output-channel absmax scale; method="kmeans" fits a single learned
    codebook on the normalized entries (paper §III-A); method="uniform" uses
    an RTN-style evenly spaced grid (the INT-WAQ baseline of Table III).
    """
    k, n = w.shape
    if nbits > 8:
        raise ValueError(f"weight codebooks top out at 8 bits, got {nbits}")
    scale = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-12)  # (N,)
    wn = (w / scale[None, :]).astype(jnp.float32)
    if method == "kmeans":
        book = cb.kmeans_fit(wn, 2**nbits, iters=iters)
    elif method == "uniform":
        book = jnp.linspace(-1.0, 1.0, 2**nbits)
    else:
        raise ValueError(method)
    idx = cb.assign_via_boundaries(wn, book)
    if nbits <= 4:
        if n % 2:
            raise ValueError("N must be even to nibble-pack along output channels")
        packed = pack_int4(idx)
    else:  # 5..8 bits: one index per byte
        packed = idx.astype(jnp.uint8)
    return QuantizedWeight(
        packed=packed, codebook=book, scale=scale.astype(jnp.float32),
        shape=(k, n), nbits=nbits,
    )


def dequantize_weight(qw: QuantizedWeight, dtype=jnp.float32) -> jax.Array:
    """W~[k, n] = C[idx[k, n]] * scale[n]."""
    return (lookup(qw.codebook, qw.indices) * qw.scale[None, :]).astype(dtype)


# ---------------------------------------------------------------------------
# Activation quantization
# ---------------------------------------------------------------------------

def token_scale(x: jax.Array, mode: ScaleMode = "rms") -> jax.Array:
    """Per-token scale over the last (channel) axis, shape ``(..., 1)``."""
    if mode == "rms":
        s = jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True))
    elif mode == "absmax":
        s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    else:
        raise ValueError(mode)
    return jnp.maximum(s, 1e-12)


def quantize_activation(
    x: jax.Array,
    codebook: jax.Array,
    scale_mode: ScaleMode = "rms",
) -> QuantizedActivation:
    """Quantize ``(..., K)`` activations against an offline codebook.

    bf16 inputs (the production serving dtype) use the fused sum-of-compares
    rank — the SAME formulation as the Pallas Clustering-Unit kernel —
    against per-token-SCALED boundaries: a pure elementwise chain XLA fuses
    to zero intermediates, with an int8 index. The searchsorted path
    materialized f32 x/s + int32 idx + binary-search gathers: 3.2 GB/device
    PER PROJECTION at 32k prefill (EXPERIMENTS §Perf P1, 73 -> 20 GB).
    f32 inputs keep the exact searchsorted path (bit-equal to argmin, which
    the tests assert).
    """
    s = token_scale(x, scale_mode)
    nbits = int(codebook.shape[0]).bit_length() - 1
    if x.dtype == jnp.bfloat16:
        b = cb.boundaries_from_centroids(codebook)
        idx = jnp.zeros(x.shape, jnp.int8)
        xf = x.astype(jnp.float32)  # fused into the compares, never stored
        for i in range(b.shape[0]):
            idx += (xf >= s * b[i]).astype(jnp.int8)
        return QuantizedActivation(idx=idx, scale=s, codebook=codebook, nbits=nbits)
    idx = cb.assign_via_boundaries((x / s).astype(jnp.float32), codebook)
    return QuantizedActivation(idx=idx, scale=s, codebook=codebook, nbits=nbits)


def dequantize_activation(qa: QuantizedActivation, dtype=jnp.float32) -> jax.Array:
    return (lookup(qa.codebook, qa.idx) * qa.scale).astype(dtype)


def fit_activation_codebook(
    samples: jax.Array,
    nbits: int = 4,
    fisher: jax.Array | None = None,
    scale_mode: ScaleMode = "rms",
    iters: int = 25,
    method: str = "kmeans",
) -> jax.Array:
    """Offline activation-codebook learning (paper §III-A, Fig. 17).

    ``samples``: (tokens, K) calibration activations. ``fisher``: optional
    per-element Fisher-information weights (same shape) — the paper's
    weighted-K-Means. Centroids are fit in per-token-normalized space.
    method="uniform" gives the RTN/INT-WAQ activation grid baseline.
    """
    s = token_scale(samples, scale_mode)
    xn = (samples / s).astype(jnp.float32)
    if method == "uniform":
        lim = jnp.max(jnp.abs(xn))
        return jnp.linspace(-lim, lim, 2**nbits)
    w = None if fisher is None else fisher.astype(jnp.float32)
    return cb.kmeans_fit(xn, 2**nbits, w=w, iters=iters)
