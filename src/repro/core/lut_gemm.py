"""WAQ LUT-based GEMM (paper §III-B).

Both operands are index-coded against learned codebooks, so every scalar
product is one of ``2^(nA+nW)`` values — the **Cartesian-product LUT**

    LUT[i, j] = cA[i] * cW[j].

The paper's ASIC reduces along K by (1) concatenating (aIdx, wIdx), (2)
histogramming the concatenated patterns, (3) taking a weighted sum of LUT
entries — K FP adds become 2^(nA+nW) FP adds, and the LUT is independent of
the reduction length (Table I).

On TPU we implement BOTH formulations:

* :func:`lut_gemm_counting` — the paper-faithful counting form, expressed with
  one-hot matmuls. It is the mathematical oracle for tests and the basis of
  the Table-I analytics. (On an MXU this form costs *more* FLOPs than the
  factorized form; it exists to prove equivalence, not for speed.)

* :func:`lut_gemm` — the TPU-native **factorized** form. Because the LUT is an
  outer product, the weighted LUT sum collapses algebraically:

      Y[m,n] = sA[m]·sW[n] · Σ_k cA[aIdx[m,k]] · cW[wIdx[k,n]]

  i.e. gather centroids (in VMEM, from 16-entry tables) and feed the MXU.
  No dequantized weight matrix ever exists in HBM — the paper's
  "no-dequantization" property survives on the memory side, which is the side
  that matters on TPU (decode GEMMs are HBM-bound). The perf-critical packed
  version lives in ``repro/kernels/lut_gemm.py`` (Pallas).

Equivalence of the two forms (and of both against dequantize-then-matmul) is
asserted by unit + hypothesis tests.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import QuantizedActivation, QuantizedWeight, lookup

__all__ = [
    "build_lut",
    "lut_gemm_counting",
    "lut_gemm",
    "reduction_flops_counting",
    "woq_lut_size",
    "waq_lut_size",
]


def build_lut(act_codebook: jax.Array, wgt_codebook: jax.Array) -> jax.Array:
    """Precompute the Cartesian-product LUT, shape ``(2^nA, 2^nW)``.

    Offline (paper Fig. 6 step 0): both codebooks are known before inference,
    so the LUT is a constant that lives on-chip (it is 2^(nA+nW) fp32 values —
    1 KiB for W4A4; on TPU it is constant-folded into the program).
    """
    return jnp.outer(act_codebook, wgt_codebook)


def lut_gemm_counting(
    qa: QuantizedActivation, qw: QuantizedWeight, out_dtype=jnp.float32
) -> jax.Array:
    """Paper-faithful counting-form GEMM (Fig. 6 steps 1-3).

    Steps, vectorized: one-hot the activation indices (M,K,2^nA) and weight
    indices (K,N,2^nW); their contraction over K *is* the per-(m,n) histogram
    of concatenated indices; the weighted sum with the LUT finishes the GEMM.

      counts[m,n,i,j] = Σ_k 1[aIdx[m,k]=i] · 1[wIdx[k,n]=j]
      Y[m,n]          = sA[m]·sW[n] · Σ_ij counts[m,n,i,j] · LUT[i,j]

    Only used as an oracle / for analytics: O(M·N·2^(nA+nW)) memory.
    """
    lut = build_lut(qa.codebook, qw.codebook)
    a1h = jax.nn.one_hot(qa.idx, 2**qa.nbits, dtype=jnp.float32)  # (..., K, 2^nA)
    w1h = jax.nn.one_hot(qw.indices, 2**qw.nbits, dtype=jnp.float32)  # (K, N, 2^nW)
    counts = jnp.einsum("...ki,knj->...nij", a1h, w1h)  # histogram of concat indices
    y = jnp.einsum("...nij,ij->...n", counts, lut)
    return (y * qa.scale * qw.scale).astype(out_dtype)


def lut_gemm(
    qa: QuantizedActivation, qw: QuantizedWeight, out_dtype=jnp.float32,
    compute_dtype=jnp.float32,
) -> jax.Array:
    """Factorized LUT-GEMM — the TPU-native production form (jnp reference).

    Centroid gathers happen from 16-entry tables (VMEM-resident after
    constant hoisting); the reduction runs on the MXU. Bit-for-bit the same
    result as :func:`lut_gemm_counting` up to float summation order.
    """
    a = lookup(qa.codebook, qa.idx).astype(compute_dtype)  # (..., K)
    w = lookup(qw.codebook, qw.indices).astype(compute_dtype)  # (K, N)
    y = jnp.einsum("...k,kn->...n", a, w)
    return (y * qa.scale.astype(compute_dtype) * qw.scale.astype(compute_dtype)).astype(
        out_dtype
    )


# ---------------------------------------------------------------------------
# Table-I analytics (LUT sizes / reduction FLOPs), used by benchmarks
# ---------------------------------------------------------------------------

def woq_lut_size(mu: int, k: int, entry_bytes: int = 2) -> int:
    """WOQ inner-product LUT size in bytes: 2^mu entries per group, K/mu groups."""
    return (2**mu) * (k // mu) * entry_bytes


def waq_lut_size(n_a: int, n_w: int, entry_bytes: int = 2) -> int:
    """Ours: Cartesian-product LUT, 2^(nA+nW) entries, K-independent."""
    return (2 ** (n_a + n_w)) * entry_bytes


def reduction_flops_counting(n_a: int, n_w: int, n_out: int) -> int:
    """FP adds for reduction per output row in the counting form: 2^(nA+nW)·N."""
    return (2 ** (n_a + n_w)) * n_out
