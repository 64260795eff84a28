"""Pallas TPU kernels for the perf-critical compute of KLLM/OASIS.

- lut_gemm:      W4A4 K-Means index GEMM (dequant-in-VMEM -> MXU)
- bucketize:     activation clustering (Clustering Unit)
- topk_outlier:  Orizuru dual top-k/bottom-k detection
- paged_attn:    paged KV-cache decode attention (block-table gather,
                 int4 dequant-in-VMEM)

``ops`` holds the jit'd public wrappers, ``ref`` the pure-jnp oracles.
Kernels are validated in interpret mode on CPU; tests/test_tpu_compile.py
compiles them for a described TPU v5e at published widths.
"""

from repro.kernels import ops, ref
from repro.kernels.bucketize import bucketize_kernel_call
from repro.kernels.lut_gemm import lut_gemm_kernel_call
from repro.kernels.paged_attn import paged_attn_kernel_call
from repro.kernels.topk_outlier import topk_outlier_kernel_call

__all__ = [
    "ops",
    "ref",
    "bucketize_kernel_call",
    "lut_gemm_kernel_call",
    "paged_attn_kernel_call",
    "topk_outlier_kernel_call",
]
