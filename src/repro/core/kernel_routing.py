"""Kernel routing policy + per-tier dispatch accounting (LUT-GEMM + Orizuru).

Every quantized projection resolves a route — ``pallas`` (the fused
quantize+index-GEMM Pallas kernel, ``repro/kernels/lut_gemm.py``) or ``jnp``
(the factorized ``core/lut_gemm.py`` form) — from its
``QLinearConfig.kernel`` field:

  auto   : Pallas on TPU backends, jnp elsewhere (interpret-mode Pallas is
           far slower than XLA's fused gather+einsum on CPU). The
           ``REPRO_LUT_KERNEL`` env var overrides the auto default with the
           same spelling as ``REPRO_PAGED_KERNEL``: "0"/"off"/"false" forces
           jnp, any other value forces the kernel.
  pallas : always the kernel (interpret mode off-TPU).
  jnp    : always the factorized jnp form.

**Outlier detection routes the same way** (``QLinearConfig.detect_kernel``):
the dual-branch layer's ``detection="dynamic"`` top-k/bottom-k resolves to
the Pallas Orizuru tournament kernel (``repro/kernels/topk_outlier.py`` —
on the jnp GEMM route as the STREAMING variant that emits (idx, scale,
OutlierSet) in the quantize pass) or to ``jax.lax.top_k``. The
``REPRO_TOPK_KERNEL`` env var overrides the auto default, mirroring
``REPRO_LUT_KERNEL``. Static (OASIS-S) detection is threshold scoring with
no tournament to run — it always resolves to jnp; requesting
``detect_kernel="pallas"`` for it is an explicit, warned fallback.

Route resolution happens at **trace time** (``qlinear_apply`` runs under
jit), so the dispatch counters here record which path was *compiled
into* each jaxpr — one count per projection per traced shape, not per
executed step. That is exactly the observability question ("which path
actually ran?") a trace-time decision can answer truthfully; incrementing
per execution would need a host callback on the serving hot path. The
serving scheduler surfaces these counts as lazy gauges in the PR-6
telemetry registry (``serving_lut_*`` and ``serving_outlier_*``) and in
``ServingEngine.stats``. Compensation-route choices (gather, scatter, or
fused into the LUT-GEMM kernel; ``QLinearConfig.comp_mode`` resolution)
are counted here too.

Fallbacks are never silent: an unsupported tier demoted from a requested
``pallas`` route increments a counter AND warns once per reason
(the pre-routing code silently dropped W8 to jnp even with
``use_kernel=True``).
"""

from __future__ import annotations

import os
import warnings
from collections import Counter

import jax

__all__ = [
    "resolve_route",
    "resolve_detect_route",
    "record_dispatch",
    "record_fallback",
    "record_detect_dispatch",
    "record_detect_fallback",
    "record_comp_route",
    "dispatch_counts",
    "kernel_calls",
    "jnp_calls",
    "fallback_count",
    "detect_dispatch_counts",
    "detect_calls",
    "detect_kernel_calls",
    "detect_jnp_calls",
    "detect_fallback_count",
    "comp_route_counts",
    "snapshot",
    "reset",
]

ROUTES = ("auto", "pallas", "jnp")

# (tier, route) -> number of trace-time route resolutions, e.g.
# ("w4a4", "pallas") -> 3. Process-global by design: qlinear_apply has no
# handle on an engine, and the telemetry registry reads these lazily.
_DISPATCH: Counter = Counter()
# reason -> count of explicit pallas->jnp demotions
_FALLBACKS: Counter = Counter()
_WARNED: set[str] = set()

# Outlier-detection routing state, mirroring the GEMM counters above:
# (tier, route) dispatches, explicit fallbacks, and the comp-route choice
# (gather, scatter or fused) that the dual branch resolves per trace.
_DETECT_DISPATCH: Counter = Counter()
_DETECT_FALLBACKS: Counter = Counter()
_COMP_ROUTES: Counter = Counter()

# Resolved on first use, NOT at import: jax.default_backend() initializes
# the backend, which would break platform overrides in programs that merely
# import the core stack. Tests monkeypatch this to force a route.
_AUTO_DEFAULT: bool | None = None
_DETECT_AUTO_DEFAULT: bool | None = None


def _auto_default() -> bool:
    """auto-route default: kernel on TPU, jnp elsewhere; env-overridable."""
    global _AUTO_DEFAULT
    if _AUTO_DEFAULT is None:
        env = os.environ.get("REPRO_LUT_KERNEL", "auto").strip().lower()
        if env in ("", "auto"):
            _AUTO_DEFAULT = jax.default_backend() == "tpu"
        else:
            _AUTO_DEFAULT = env not in ("0", "off", "false")
    return _AUTO_DEFAULT


def resolve_route(kernel: str, use_kernel: bool = False) -> str:
    """Resolve a ``QLinearConfig.kernel`` policy to a concrete route.

    ``use_kernel`` is the legacy boolean opt-in: under ``kernel="auto"`` it
    still forces the Pallas route so pre-policy configs keep their meaning.
    """
    if kernel == "pallas":
        return "pallas"
    if kernel == "jnp":
        return "jnp"
    if kernel != "auto":
        raise ValueError(f"kernel must be one of {ROUTES}, got {kernel!r}")
    if use_kernel:
        return "pallas"
    return "pallas" if _auto_default() else "jnp"


def _detect_auto_default() -> bool:
    """auto detect-route default: Orizuru kernel on TPU, lax.top_k elsewhere;
    overridable via ``REPRO_TOPK_KERNEL`` ("0"/"off"/"false" forces jnp)."""
    global _DETECT_AUTO_DEFAULT
    if _DETECT_AUTO_DEFAULT is None:
        env = os.environ.get("REPRO_TOPK_KERNEL", "auto").strip().lower()
        if env in ("", "auto"):
            _DETECT_AUTO_DEFAULT = jax.default_backend() == "tpu"
        else:
            _DETECT_AUTO_DEFAULT = env not in ("0", "off", "false")
    return _DETECT_AUTO_DEFAULT


def resolve_detect_route(detect_kernel: str) -> str:
    """Resolve a ``QLinearConfig.detect_kernel`` policy to a concrete route."""
    if detect_kernel == "pallas":
        return "pallas"
    if detect_kernel == "jnp":
        return "jnp"
    if detect_kernel != "auto":
        raise ValueError(
            f"detect_kernel must be one of {ROUTES}, got {detect_kernel!r}"
        )
    return "pallas" if _detect_auto_default() else "jnp"


def record_dispatch(tier: str, route: str) -> None:
    _DISPATCH[(tier, route)] += 1


def record_fallback(tier: str, reason: str) -> None:
    """Explicit pallas->jnp demotion: counted, warned once per reason."""
    _FALLBACKS[reason] += 1
    _DISPATCH[(tier, "fallback")] += 1
    if reason not in _WARNED:
        _WARNED.add(reason)
        warnings.warn(
            f"LUT-GEMM kernel route unavailable for tier {tier}: {reason}; "
            f"falling back to the jnp factorized path",
            RuntimeWarning,
            stacklevel=3,
        )


def record_detect_dispatch(tier: str, route: str) -> None:
    _DETECT_DISPATCH[(tier, route)] += 1


def record_detect_fallback(tier: str, reason: str) -> None:
    """Explicit detect pallas->jnp demotion: counted, warned once per reason."""
    _DETECT_FALLBACKS[reason] += 1
    _DETECT_DISPATCH[(tier, "fallback")] += 1
    key = f"detect:{reason}"
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(
            f"Orizuru detection kernel route unavailable for tier {tier}: "
            f"{reason}; falling back to the jnp (lax.top_k / threshold) path",
            RuntimeWarning,
            stacklevel=3,
        )


def record_comp_route(mode: str) -> None:
    """Count the resolved compensation route ("gather", "scatter", or
    "fused": folded into the fused LUT-GEMM kernel)."""
    _COMP_ROUTES[mode] += 1


def dispatch_counts() -> dict[str, int]:
    """``{"<tier>/<route>": count}`` snapshot of every recorded dispatch."""
    return {f"{tier}/{route}": n for (tier, route), n in sorted(_DISPATCH.items())}


def kernel_calls() -> int:
    """Total projections routed to the Pallas kernel (trace-time count)."""
    return sum(n for (_, route), n in _DISPATCH.items() if route == "pallas")


def jnp_calls() -> int:
    return sum(n for (_, route), n in _DISPATCH.items() if route == "jnp")


def fallback_count() -> int:
    return sum(_FALLBACKS.values())


def detect_dispatch_counts() -> dict[str, int]:
    """``{"<tier>/<route>": count}`` snapshot of detection dispatches."""
    return {
        f"{tier}/{route}": n
        for (tier, route), n in sorted(_DETECT_DISPATCH.items())
    }


def detect_calls() -> int:
    """Total outlier-branch detection resolutions (any route, incl. fallback
    demotions — every one of these compiled *some* detection into the jaxpr)."""
    return sum(_DETECT_DISPATCH.values())


def detect_kernel_calls() -> int:
    """Detections routed to the Pallas Orizuru kernel (trace-time count)."""
    return sum(n for (_, route), n in _DETECT_DISPATCH.items() if route == "pallas")


def detect_jnp_calls() -> int:
    return sum(n for (_, route), n in _DETECT_DISPATCH.items() if route == "jnp")


def detect_fallback_count() -> int:
    return sum(_DETECT_FALLBACKS.values())


def comp_route_counts() -> dict[str, int]:
    """``{"fused": f, "gather": n, "scatter": m}`` resolved compensation
    routes (routes never taken are absent)."""
    return dict(sorted(_COMP_ROUTES.items()))


def snapshot() -> dict[str, int]:
    """Flat copy for delta-based assertions (benchmarks, tests)."""
    d = dispatch_counts()
    for key, n in detect_dispatch_counts().items():
        d[f"detect:{key}"] = n
    for mode, n in comp_route_counts().items():
        d[f"comp:{mode}"] = n
    d["_kernel_calls"] = kernel_calls()
    d["_jnp_calls"] = jnp_calls()
    d["_fallbacks"] = fallback_count()
    d["_detect_calls"] = detect_calls()
    d["_detect_kernel_calls"] = detect_kernel_calls()
    d["_detect_jnp_calls"] = detect_jnp_calls()
    d["_detect_fallbacks"] = detect_fallback_count()
    return d


def reset() -> None:
    """Clear counters (tests). The one-time-warning set is kept — warning
    spam does not become useful again just because counters were zeroed."""
    _DISPATCH.clear()
    _FALLBACKS.clear()
    _DETECT_DISPATCH.clear()
    _DETECT_FALLBACKS.clear()
    _COMP_ROUTES.clear()
