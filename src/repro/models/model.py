"""Unified model API: family dispatch + quantized-inference transformation.

``build(cfg)`` returns a :class:`Model` with a family-independent contract:

    params               = model.init(key)
    out                  = model.apply(params, batch)                 # train/prefill
    out                  = model.apply(params, batch, caches=...)     # decode
    caches               = model.init_caches(batch_size, cache_len)
    qparams              = quantize_model(model, params, spec, calib) # PTQ -> QLinearParams tree

``out`` is a :class:`ModelOutput` (logits, caches, aux_loss). ``batch`` is a
dict with "tokens" (B, S) and, for the VLM family, "image_embeds".

Quantization is policy-driven: ``quantize_model`` resolves a declarative
:class:`~repro.core.quantspec.QuantSpec` (ordered path-glob rules) to a
concrete per-projection :class:`QLinearConfig`, which is stored INSIDE each
produced :class:`QLinearParams` — apply-time behaviour travels with the
params, there is no ambient/global apply config.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.qlinear import QLinearConfig, QLinearParams
from repro.core.quantize import fit_activation_codebook, quantize_weight
from repro.core.quantspec import QuantSpec
from repro.models import mamba, moe, multimodal, rglru, transformer

__all__ = ["Model", "ModelOutput", "build", "quantize_model", "quantize_params",
           "unstack_for_capture", "head_matrix"]

_FAMILY_MODULES = {
    "dense": transformer,
    "audio": transformer,  # musicgen backbone == decoder-only LM over codec tokens
    "moe": moe,
    "ssm": mamba,
    "hybrid": rglru,
    "vlm": multimodal,
}


@dataclasses.dataclass
class ModelOutput:
    logits: jax.Array | None  # (B, S, vocab_padded) f32 (None if hidden-only)
    caches: Any = None
    aux_loss: jax.Array | None = None
    hidden: jax.Array | None = None  # final-norm hidden states (B, S, d)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    @property
    def _mod(self):
        return _FAMILY_MODULES[self.cfg.family]

    def init(self, key) -> dict:
        return self._mod.init(key, self.cfg)

    def init_caches(self, batch: int, cache_len: int, dtype=jnp.bfloat16,
                    quantized: bool = False, layout: str = "ring",
                    block_size: int = 16, n_blocks: int = 0):
        """layout="ring" (every family) or "paged" (families exporting cache
        policies: dense/audio/moe/ssm/hybrid) — per-layer pools for the
        continuous-batching scheduler: a global block pool for (windowed)
        paged-KV layers, slot-indexed constant-size state for recurrent
        layers; see repro.serving.paged_cache."""
        if layout == "paged":
            if self.cache_policies() is None:
                raise ValueError(
                    f"family {self.cfg.family} exports no cache policies "
                    "(no paged serving layout)"
                )
            return self._mod.init_caches(self.cfg, batch, cache_len, dtype, quantized,
                                         layout="paged", block_size=block_size,
                                         n_blocks=n_blocks)
        return self._mod.init_caches(self.cfg, batch, cache_len, dtype, quantized)

    def cache_policies(self):
        """Per-layer :class:`~repro.serving.paged_cache.CachePolicy` list for
        the serving scheduler, or None when the family cannot serve through
        the packed paged step (vlm — the engine falls back to the fixed-slot
        ring path)."""
        fn = getattr(self._mod, "cache_policies", None)
        return None if fn is None else fn(self.cfg)

    def apply(self, params, batch: dict, *, positions=None, caches=None,
              last_only: bool = False, return_hidden_only: bool = False) -> ModelOutput:
        """``positions`` may be (S,) shared or (B, S) per-row — the latter is
        the serving scheduler's layout (per-request decode depths / the
        packed token-budget step, position -1 = unused row)."""
        kwargs = dict(positions=positions, caches=caches, last_only=last_only,
                      return_hidden_only=return_hidden_only)
        if self.cfg.family == "vlm":
            kwargs["image_embeds"] = batch["image_embeds"]
        out = self._mod.apply(params, self.cfg, batch["tokens"], **kwargs)
        if self.cfg.family == "moe":
            val, caches_out, aux = out
        else:
            (val, caches_out), aux = out, None
        if return_hidden_only:
            return ModelOutput(None, caches_out, aux, hidden=val)
        return ModelOutput(val, caches_out, aux)


def build(cfg: ModelConfig) -> Model:
    if cfg.family not in _FAMILY_MODULES:
        raise ValueError(f"unknown family {cfg.family}")
    return Model(cfg)


def head_matrix(model: Model, params) -> jax.Array:
    """(d, vocab_padded) unembedding matrix (transposed table when tied)."""
    if model.cfg.tie_embeddings:
        return params["embed"]["table"].T
    return params["head"]["w"]


def unstack_for_capture(model: Model, params):
    """(model, scan-stacked params) -> (unscanned model, per-layer param list).

    Calibration taps only fire in plain-Python forwards; scan bodies are
    traced, so capture requires the unrolled (scan_layers=False) variant.
    Supported for the single-stack families (dense/audio/moe/ssm)."""
    cfg = model.cfg
    if not cfg.scan_layers or cfg.family == "vlm":
        return model, params
    blocks = params["blocks"]
    n = jax.tree.leaves(blocks)[0].shape[0]
    blocks_list = [jax.tree.map(lambda a: a[i], blocks) for i in range(n)]
    cfg2 = dataclasses.replace(cfg, scan_layers=False)
    return build(cfg2), {**params, "blocks": blocks_list}


# ---------------------------------------------------------------------------
# PTQ parameter transformation
# ---------------------------------------------------------------------------

# Keys whose 'w' leaves are the paper-quantizable projections. Router weights,
# norms, embeddings and the lm head stay fp REGARDLESS of the spec (paper:
# norms/softmax fp16; router is tiny and accuracy-critical) — the spec decides
# which of the eligible projections are quantized and how.
_QUANT_KEYS = {
    "wq", "wk", "wv", "wo", "wi", "wd",
    "in_proj", "x_proj", "dt_proj", "out_proj",
    "lin_y", "lin_x", "lin_out", "w_a", "w_x",
}
_SKIP_KEYS = {"router", "head", "embed", "shared_gate"}

# param leaf key -> calibration tap name(s) it feeds (see dense_apply's
# tap_name plumbing in models/*.py). Cross-attention q/o taps are "cross.*";
# the path carries "cross" for those blocks, handled in _tap_candidates.
_TAP_OF = {
    "wq": ("attn.q",), "wk": ("attn.k",), "wv": ("attn.v",), "wo": ("attn.o",),
    "wi": ("mlp.wi",), "wd": ("mlp.wd",),
    "in_proj": ("mamba.in_proj",), "x_proj": ("mamba.x_proj",),
    "dt_proj": ("mamba.dt_proj",), "out_proj": ("mamba.out_proj",),
    "lin_y": ("rec.lin_y",), "lin_x": ("rec.lin_x",), "lin_out": ("rec.lin_out",),
    "w_a": ("rglru.wa",), "w_x": ("rglru.wx",),
}


def _default_codebook(nbits: int, method: str = "kmeans") -> jax.Array:
    """Structural activation codebook (gaussian quantiles) for when no
    calibration activations are available (dry-run / structural quantization).
    Real deployments calibrate via repro.core.calibration."""
    if method == "uniform":
        return jnp.linspace(-2.5, 2.5, 2**nbits)
    from jax.scipy.stats import norm as _norm

    qs = (jnp.arange(2**nbits, dtype=jnp.float32) + 0.5) / (2**nbits)
    return _norm.ppf(qs).astype(jnp.float32)


def quantize_model(model: Model, params, spec: QuantSpec,
                   calib: dict | None = None) -> dict:
    """PTQ a whole model under a declarative per-layer policy.

    ``spec`` is a :class:`~repro.core.quantspec.QuantSpec`: ordered
    ``(path-glob -> QLinearConfig overrides | skip)`` rules resolved against
    each quantizable projection's parameter path (e.g. ``blocks/attn/wq``).
    The resolved config is stored inside each produced
    :class:`QLinearParams`, so the returned tree is self-describing — serve
    it directly, or persist it with ``repro.core.artifact.save_quantized``.

    ``calib``: optional {tap_name: (tokens, K) activations} from
    ``core.calibration.capture`` — when provided, activation codebooks (and
    OASIS-S static thresholds) are learned per projection; otherwise the
    structural gaussian codebook is used.
    """
    # the param tree itself carries the structure the rules match against;
    # the model is used to catch params/model mix-ups before a shape error
    # surfaces deep inside apply
    expect = {"embed"}
    expect |= {"self_blocks", "cross_blocks"} if model.cfg.family == "vlm" else {"blocks"}
    if not model.cfg.tie_embeddings:
        expect |= {"head"}
    missing = expect - set(params)
    if missing:
        raise ValueError(
            f"params are missing {sorted(missing)} — not a parameter tree of "
            f"{model.cfg.arch_id} (family {model.cfg.family})"
        )
    return quantize_params(params, spec, calib)


def quantize_params(params, spec, calib: dict | None = None, path: str = ""):
    """Recursively replace quantizable fp linears with QLinearParams.

    ``spec`` may be a :class:`QuantSpec` or (backward compat) a bare
    :class:`QLinearConfig`, which behaves as a rule-free spec. Projections a
    rule resolves to ``skip`` keep their fp weight dict. Works on stacked
    (scan) params one layer at a time — note stacked projections share one path
    (``blocks/attn/wq``), so per-layer-index rules need scan_layers=False.
    """
    if isinstance(spec, QLinearConfig):
        spec = QuantSpec(base=spec)
    if isinstance(params, list):
        return [quantize_params(p, spec, calib, f"{path}/{i}" if path else str(i))
                for i, p in enumerate(params)]
    if not isinstance(params, dict):
        return params
    out = {}
    for k, v in params.items():
        sub = f"{path}/{k}" if path else k
        if k in _SKIP_KEYS:
            out[k] = v
        elif k in _QUANT_KEYS and isinstance(v, dict) and "w" in v:
            cfg = spec.resolve(sub)
            out[k] = v if cfg is None else _quantize_one(v, cfg, calib, sub)
        elif isinstance(v, (dict, list)):
            out[k] = quantize_params(v, spec, calib, sub)
        else:
            out[k] = v
    return out


def _quantize_one(p: dict, cfg: QLinearConfig, calib: dict | None, path: str):
    """Quantize one projection under its RESOLVED config (stored in the
    result's ``cfg`` meta field, so apply needs no outside configuration)."""
    w = p["w"]
    bias = p.get("b")

    def one(w2d, b1d):
        qw = quantize_weight(w2d.astype(jnp.float32), nbits=cfg.w_bits, method=cfg.method)
        book = _codebook_for(path, cfg, calib)
        thr_lo = thr_hi = None
        if cfg.detection in ("static", "static_dense"):
            acts = _calib_for(path, calib)
            if acts is not None:
                from repro.core.outlier import static_thresholds

                thr_lo, thr_hi = static_thresholds(acts, cfg.outlier_frac)
            else:
                thr_lo, thr_hi = jnp.float32(-3.0), jnp.float32(3.0)
        return QLinearParams(qw=qw, act_codebook=book, bias=b1d, thr_lo=thr_lo,
                             thr_hi=thr_hi, cfg=cfg)

    if w.ndim < 2:
        raise ValueError(f"unexpected weight rank {w.ndim} at {path}")
    # walk stacked scan axes (layers, or vlm's groups x layers) one slice at
    # a time: K-Means temporaries of a whole stack at once overflow HBM at
    # published widths, one layer's fit easily
    fn = lambda wb: one(*wb)
    for _ in range(w.ndim - 2):
        fn = functools.partial(jax.lax.map, fn)
    return fn((w, bias))


def _tap_candidates(path: str) -> tuple[str, ...]:
    """Calibration tap names that feed the projection at ``path``."""
    leaf = path.rsplit("/", 1)[-1]
    taps = _TAP_OF.get(leaf, (leaf,))
    if "cross" in path:  # vlm cross-attn blocks tap under layer_tag="cross"
        taps = tuple(t.replace("attn.", "cross.") for t in taps) + taps
    return taps


def _calib_for(path: str, calib: dict | None):
    """Captured activations for the projection at ``path``, or None.

    Tap names are projection-scoped ("attn.q", "mlp.wd", ...), not
    path-scoped: scanned stacks capture one pooled tensor per projection.
    Exact tap-name match first, then suffix match (unrolled captures may
    prefix names).
    """
    if not calib:
        return None
    for tap in _tap_candidates(path):
        if tap in calib:
            return calib[tap]
    for tap in _tap_candidates(path):
        for name, acts in calib.items():
            if name.endswith(tap):
                return acts
    return None


def _codebook_for(path: str, cfg: QLinearConfig, calib: dict | None):
    acts = _calib_for(path, calib)
    if acts is not None:
        return fit_activation_codebook(acts, nbits=cfg.a_bits,
                                       scale_mode=cfg.scale_mode, method=cfg.method)
    return _default_codebook(cfg.a_bits, cfg.method)
