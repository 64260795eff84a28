"""QLinear: the paper's full dual-branch quantized linear layer.

    y = LUT-GEMM(quantize(x), Wq)            # main branch (look-ahead)
      + r_outlier @ W~[outlier_channels, :]  # outlier branch (compensation)
      + bias

This is the composable unit the model zoo uses for quantized inference. The
main branch routes per the ``kernel`` policy field (see
``repro.core.kernel_routing``): ``pallas`` runs the FUSED quantize+index-GEMM
Pallas kernel (activation indices never leave VMEM, no dequantized (K, N)
weight ever exists — W3/W4 nibble and W5-W8 byte tiers); ``jnp`` runs
quantize-then-factorized-GEMM; ``auto`` picks pallas on TPU, jnp on CPU.
Both routes are exact vs the counting-form oracle and token-identical to
each other under greedy serving (index selection is bit-equal; see
``kernels/ops.lut_gemm_fused``). Fallbacks off a requested pallas route are
explicit — counted in the dispatch registry and warned once — never silent.

The outlier branch routes independently (``detect_kernel``): dynamic (OASIS)
detection runs the Pallas Orizuru tournament kernel or ``lax.top_k``. On the
jnp GEMM route with Pallas detection the layer uses the STREAMING form —
bucketize + dual top-k in one pass over the activation tile
(``kernels/ops.quantize_outlier_streaming``) — so detection adds no extra
HBM roundtrip; the jnp route then compensates separately, by weight-row
gather (few tokens) or scatter + dense GEMM (``comp_auto_tokens``). On the
fused GEMM route detection (dynamic or static) runs first and compensation
is FOLDED into the kernel tile: the token's outlier channels enter the dot
with their exact ``x/s`` instead of their centroid (detect-then-split, equal
to the two branches' sum), so no weight row is gathered, scattered or
unpacked outside the kernel (``comp:fused``; an explicit ``comp_mode`` keeps
the separate branch as an ablation). Detection routes are bit-identical in
their index/value selection, so greedy serving tokens match across them.
The A3 activation tier (8-entry codebook) is legal only with
``detection != "none"`` (see ``QLinearConfig.validate``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

import repro.core.kernel_routing as kr
import repro.core.numerics as nx
import repro.core.outlier as ol
import repro.core.quantize as qz
from repro.core.lut_gemm import lut_gemm as _lut_gemm_jnp

__all__ = [
    "QLinearConfig",
    "QLinearParams",
    "quantize_linear",
    "qlinear_apply",
    "with_kernel_route",
    "with_detect_route",
]

Detection = Literal["dynamic", "static", "static_dense", "none"]
CompMode = Literal["auto", "gather", "scatter"]
KernelRoute = Literal["auto", "pallas", "jnp"]


@dataclasses.dataclass(frozen=True)
class QLinearConfig:
    """Static configuration of a quantized linear layer (hashable, jit-static)."""

    w_bits: int = 4
    a_bits: int = 4
    method: str = "kmeans"  # kmeans (paper) | uniform (RTN/INT-WAQ baseline)
    outlier_frac: float = 0.005  # per side; paper default 0.5% + 0.5%
    detection: Detection = "dynamic"  # OASIS='dynamic', OASIS-S='static'
    comp_mode: CompMode = "auto"
    # comp_mode="auto": folded into the fused kernel on the pallas route;
    # on the jnp route, gather at <= this many tokens, else scatter
    comp_auto_tokens: int = 64
    scale_mode: qz.ScaleMode = "rms"
    compute_dtype: object = jnp.float32
    use_kernel: bool = False  # legacy boolean opt-in; kernel="pallas" spelling
    # main-branch GEMM routing policy (kernel_routing.resolve_route):
    # auto = Pallas on TPU / jnp factorized on CPU (REPRO_LUT_KERNEL env
    # overrides the auto default, mirroring REPRO_PAGED_KERNEL)
    kernel: KernelRoute = "auto"
    # outlier-detection routing policy (kernel_routing.resolve_detect_route):
    # dynamic detection resolves to the Pallas Orizuru tournament kernel or
    # lax.top_k; independent of the GEMM route so they flip separately.
    # REPRO_TOPK_KERNEL env overrides the auto default.
    detect_kernel: KernelRoute = "auto"
    # quant-health probes (core/numerics): emitted only when a probe
    # collector is active at trace time (the `quality` telemetry level);
    # rule-addressable via QuantSpec so noisy layers can be muted.
    probe: bool = True

    def __post_init__(self):
        if self.kernel not in kr.ROUTES:
            raise ValueError(
                f"kernel must be one of {kr.ROUTES}, got {self.kernel!r}")
        if self.detect_kernel not in kr.ROUTES:
            raise ValueError(
                f"detect_kernel must be one of {kr.ROUTES}, "
                f"got {self.detect_kernel!r}")
        if not 2 <= self.w_bits <= 8:
            raise ValueError(f"w_bits must be in [2, 8], got {self.w_bits}")
        if not 3 <= self.a_bits <= 8:
            raise ValueError(f"a_bits must be in [3, 8], got {self.a_bits}")

    def validate(self) -> "QLinearConfig":
        """Cross-field legality, checked where a config is *applied* (QuantSpec
        resolution, quantize_linear, explicit qlinear_apply overrides) — not in
        ``__post_init__``, so per-rule ``dataclasses.replace`` chains may pass
        through transiently-illegal states.

        The A3 activation tier (8-entry K-Means codebook) is only legal with
        online outlier compensation: sub-4-bit codebooks have no headroom for
        the tails, so the outlier branch must carry them (KVQuant's sub-1%-
        outlier argument). The ``uniform`` (RTN/INT-WAQ) grid is exempt — it
        is the deliberate collapse baseline of the Table III analog, not the
        K-Means A3 tier.
        """
        if self.a_bits < 4 and self.detection == "none" and self.method == "kmeans":
            raise ValueError(
                f"a_bits={self.a_bits} (the A3 K-Means tier) requires online "
                "outlier compensation: set detection to 'dynamic', 'static', "
                "or 'static_dense' (A3 is only legal with detection != 'none')"
            )
        return self


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["qw", "act_codebook", "bias", "thr_lo", "thr_hi"],
    meta_fields=["cfg"],
)
@dataclasses.dataclass(frozen=True)
class QLinearParams:
    """Quantized-linear parameters WITH their resolved apply-time config.

    ``cfg`` is a pytree *meta* field (static under jit): the per-layer
    :class:`QLinearConfig` a :class:`~repro.core.quantspec.QuantSpec` resolved
    for this projection. Apply-time behaviour (detection mode, outlier budget,
    kernel routing) travels with the params — there is no ambient/global
    apply config.
    """

    qw: qz.QuantizedWeight
    act_codebook: jax.Array  # fp32 (2^a_bits,) offline-learned
    bias: jax.Array | None
    thr_lo: jax.Array | None  # OASIS-S static thresholds (scalars)
    thr_hi: jax.Array | None
    cfg: QLinearConfig = QLinearConfig()


def quantize_linear(
    w: jax.Array,
    calib_acts: jax.Array,
    cfg: QLinearConfig,
    bias: jax.Array | None = None,
    fisher: jax.Array | None = None,
) -> QLinearParams:
    """PTQ a linear layer: weight K-Means + offline activation codebook.

    ``w``: (K, N). ``calib_acts``: (tokens, K) calibration activations for
    this layer (paper: 16 C4 samples). ``fisher``: optional per-element
    Fisher-information weights for weighted K-Means.
    """
    cfg.validate()
    qw = qz.quantize_weight(w, nbits=cfg.w_bits, method=cfg.method)
    book = qz.fit_activation_codebook(
        calib_acts, nbits=cfg.a_bits, fisher=fisher, scale_mode=cfg.scale_mode,
        method=cfg.method,
    )
    thr_lo = thr_hi = None
    if cfg.detection in ("static", "static_dense"):
        thr_lo, thr_hi = ol.static_thresholds(calib_acts, cfg.outlier_frac)
    return QLinearParams(qw=qw, act_codebook=book, bias=bias, thr_lo=thr_lo,
                         thr_hi=thr_hi, cfg=cfg)


def with_kernel_route(params, kernel: KernelRoute):
    """Return a copy of a (tree of) QLinearParams with the routing policy
    swapped — codebooks/indices untouched, so outputs stay comparable
    bit-for-bit across routes (tests + benchmarks flip routes this way
    instead of re-quantizing)."""
    def swap(p):
        if isinstance(p, QLinearParams):
            return dataclasses.replace(
                p, cfg=dataclasses.replace(p.cfg, kernel=kernel))
        return p

    return jax.tree_util.tree_map(
        swap, params, is_leaf=lambda p: isinstance(p, QLinearParams))


def with_detect_route(params, detect_kernel: KernelRoute):
    """Like :func:`with_kernel_route`, for the outlier-detection route: swap
    ``detect_kernel`` across a (tree of) QLinearParams without re-quantizing,
    so detection routes stay bit-comparable (the streaming/detection kernels
    are index- and value-identical to the lax.top_k path)."""
    def swap(p):
        if isinstance(p, QLinearParams):
            return dataclasses.replace(
                p, cfg=dataclasses.replace(p.cfg, detect_kernel=detect_kernel))
        return p

    return jax.tree_util.tree_map(
        swap, params, is_leaf=lambda p: isinstance(p, QLinearParams))


def _tokens(x: jax.Array) -> int:
    return math.prod(x.shape[:-1]) if x.ndim > 1 else 1


def _detect(p: QLinearParams, x: jax.Array, cfg: QLinearConfig,
            detect_route: str, k_out: int) -> ol.OutlierSet:
    """Top-k + bottom-k (dynamic) or threshold (static) outliers of ``x``."""
    with jax.named_scope("orizuru"):
        xf = x.astype(jnp.float32)
        if cfg.detection == "dynamic" and detect_route == "pallas":
            from repro.kernels import ops as kops

            # detection-only Orizuru kernel: the fused-GEMM main branch has
            # no activation indices to stream; a_bits > 4 on the jnp route
            # lands here too (the streaming form's compare chain, like fused
            # bucketize, tops out at A4)
            return kops.topk_outlier(xf, k_out)
        if cfg.detection == "dynamic":
            return ol.detect_outliers_topk(xf, k_out)
        return ol.detect_outliers_static(xf, p.thr_lo, p.thr_hi, k_out)


def qlinear_apply(p: QLinearParams, x: jax.Array, cfg: QLinearConfig | None = None) -> jax.Array:
    """Dual-branch forward (paper Fig. 7). Output dtype follows ``x``.

    ``cfg`` defaults to the config resolved at quantize time and stored in
    the params (``p.cfg``); pass one explicitly only to override it for an
    ablation (quantize-time artifacts — codebook size, static thresholds —
    obviously cannot be changed after the fact).
    """
    cfg = p.cfg if cfg is None else cfg.validate()
    out_dtype = x.dtype
    a_nbits = int(p.act_codebook.shape[0]).bit_length() - 1
    tier = f"w{p.qw.nbits}a{a_nbits}"
    mul_form = x.dtype == jnp.bfloat16

    route = kr.resolve_route(cfg.kernel, cfg.use_kernel)
    if route == "pallas" and a_nbits > 4:
        # the fused kernel's in-tile bucketize is a 2^a - 1 compare chain:
        # fine through A4 (15 compares), untenable for 256-entry activation
        # codebooks. EXPLICIT fallback — counted + warned, never silent.
        kr.record_fallback(tier, f"activation codebook has 2^{a_nbits} "
                                 "entries (> 16); fused bucketize supports "
                                 "a_bits <= 4")
        route = "jnp"
    kr.record_dispatch(tier, route)

    # ---- outlier detection routing (resolved BEFORE the main branch: the
    # streaming kernel fuses detection into the activation-quantize pass) ----
    detect_route = None
    k_out = 0
    if cfg.detection != "none" and cfg.outlier_frac > 0:
        k_out = ol.num_outliers(x.shape[-1], cfg.outlier_frac)
        if cfg.detection == "dynamic":
            detect_route = kr.resolve_detect_route(cfg.detect_kernel)
            kr.record_detect_dispatch(tier, detect_route)
        else:
            # static/static_dense score against offline thresholds — there is
            # no top-k tournament to run, so a requested Orizuru route is an
            # EXPLICIT demotion; auto resolves to jnp quietly.
            detect_route = "jnp"
            if cfg.detect_kernel == "pallas":
                kr.record_detect_fallback(
                    tier, f"detection={cfg.detection!r} scores against static "
                          "thresholds (no top-k tournament); only 'dynamic' "
                          "routes to the Orizuru kernel")
            else:
                kr.record_detect_dispatch(tier, "jnp")

    # kernel route with top-k / threshold detection: the outlier branch is
    # folded into the fused LUT-GEMM tile (detect-then-split), so detection
    # runs first; an explicit comp_mode keeps the separate branch (ablation)
    fold = (route == "pallas" and k_out > 0 and cfg.comp_mode == "auto"
            and cfg.detection in ("dynamic", "static"))
    qa = None
    outs = _detect(p, x, cfg, detect_route, k_out) if fold else None

    # ---- main branch: look-ahead LUT-GEMM over ALL activations ------------
    # (device scopes lut_gemm / orizuru / compensate: telemetry.SCOPES)
    if route == "pallas":
        from repro.kernels import ops as kops

        # ONE fused Pallas dispatch: bucketize x in VMEM + index-GEMM.
        # Handles every weight tier (W<=4 nibble-packed, W5-8 byte-packed);
        # no QuantizedActivation and no dequantized (K, N) weight exist.
        # With ``outliers`` the kernel also carries the outlier branch (its
        # mask is built under the compensate scope).
        with jax.named_scope("lut_gemm"):
            y = kops.lut_gemm_fused(x, p.act_codebook, p.qw,
                                    scale_mode=cfg.scale_mode,
                                    out_dtype=cfg.compute_dtype,
                                    outliers=outs)
    else:
        if (detect_route == "pallas" and cfg.detection == "dynamic"
                and a_nbits <= 4):
            from repro.kernels import ops as kops

            # streaming Orizuru: bucketize + dual top-k in ONE pass over the
            # activation tile — detection adds no extra HBM roundtrip. Bit-
            # identical to quantize_activation + lax.top_k (kernel contract).
            with jax.named_scope("orizuru"):
                qa, outs = kops.quantize_outlier_streaming(
                    x, p.act_codebook, k_out, cfg.scale_mode)
        else:
            with jax.named_scope("lut_gemm"):
                qa = qz.quantize_activation(x, p.act_codebook, cfg.scale_mode)
        with jax.named_scope("lut_gemm"):
            y = _lut_gemm_jnp(qa, p.qw, out_dtype=cfg.compute_dtype,
                              compute_dtype=cfg.compute_dtype)

    # ---- outlier branch: detect, residual, compensate ----------------------
    if fold:
        kr.record_comp_route("fused")
    elif cfg.detection == "static_dense" and cfg.outlier_frac > 0:
        # OASIS-S with dense masked compensation: zero sorts, one extra dense
        # matmul. Orizuru/lax.top_k at 32k-token prefill means a full sort per
        # projection (~12 GB/device of sort+gather workspace x concurrency —
        # EXPERIMENTS §Perf P1); thresholds are offline (paper's OASIS-S) and
        # the mask/residual chain fuses to nothing. Decode keeps the dynamic
        # Orizuru path (sorting 1 token is free; accuracy is higher).
        with jax.named_scope("compensate"):
            if qa is None:
                # kernel route: the dense residual needs q(x) at EVERY
                # channel; recompute it as the same elementwise chain (XLA
                # fuses it into the mask/where below — no idx roundtrip, main
                # GEMM unaffected)
                qa = qz.quantize_activation(x, p.act_codebook, cfg.scale_mode)
            deq = qz.dequantize_activation(qa, dtype=cfg.compute_dtype)
            xf = x.astype(cfg.compute_dtype)
            mask = (xf > p.thr_hi) | (xf < p.thr_lo)
            r = jnp.where(mask, xf - deq, 0)
            w = qz.dequantize_weight(p.qw, cfg.compute_dtype)
            y = y + jnp.einsum("...k,kn->...n", r, w)
    elif k_out > 0:
        if outs is None:
            outs = _detect(p, x, cfg, detect_route, k_out)
        mode = cfg.comp_mode
        if mode == "auto":
            # decode-ish (few tokens): row-gather; prefill-ish: scatter+dense GEMM
            mode = "gather" if _tokens(x) <= cfg.comp_auto_tokens else "scatter"
        kr.record_comp_route(mode)
        with jax.named_scope("compensate"):
            if qa is None:
                # kernel route (explicit comp_mode): q(x) at the 2k outlier
                # channels, recomputed from the gathered values (quantization
                # is elementwise) — bit-equal to the qa-based residual,
                # without materializing indices
                r = ol.outlier_residuals_direct(
                    outs, qz.token_scale(x, cfg.scale_mode), p.act_codebook,
                    mul_form=mul_form)
            else:
                r = ol.outlier_residuals(outs, qa)
            comp = (
                ol.compensate_gather(r, outs, p.qw, cfg.compute_dtype)
                if mode == "gather"
                else ol.compensate_scatter(r, outs, p.qw, cfg.compute_dtype)
            )
            y = y + comp

    if cfg.probe and nx.collecting():
        # quant-health probes (quality telemetry level only): pure reductions
        # on the intermediates above; `y` is never touched. Outside collect()
        # this is a no-op and the traced path is byte-identical.
        nx.probe_qlinear(
            p, x, qa=qa, outs=outs, k_out=k_out,
            dynamic=(cfg.detection == "dynamic" and cfg.outlier_frac > 0),
            scale_mode=cfg.scale_mode, tier=tier)

    if p.bias is not None:
        y = y + p.bias.astype(cfg.compute_dtype)
    return y.astype(out_dtype)
