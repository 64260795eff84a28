"""Orizuru outlier engine: detect-route resolution + env override, dispatch
accounting, explicit fallbacks, odd-N padding, tie-breaking parity vs
``lax.top_k`` (duplicate-heavy / all-equal / property-tested), streaming
quantize+detect bit-identity, the A3 legality rule, and detect-route parity
through the full dual-branch QLinear up to greedy serving token identity."""

import dataclasses
import warnings

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

import repro.core.kernel_routing as kr
import repro.core.outlier as ol
from repro.core.qlinear import (
    QLinearConfig,
    qlinear_apply,
    quantize_linear,
    with_detect_route,
    with_kernel_route,
)
from repro.core.quantize import fit_activation_codebook, quantize_activation
from repro.core.quantspec import QuantSpec
from repro.kernels import ops as kops
from repro.kernels.ref import streaming_quantize_outlier_ref, topk_outlier_ref
from repro.kernels.topk_outlier import (
    streaming_quantize_outlier_kernel_call,
    topk_outlier_kernel_call,
)


def _layer(cfg: QLinearConfig, k=128, n=48, seed=0, bias=True):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (k, n))
    calib = jax.random.normal(jax.random.fold_in(key, 1), (64, k)) * 1.5
    b = jax.random.normal(jax.random.fold_in(key, 2), (n,)) if bias else None
    return quantize_linear(w, calib, cfg, bias=b)


# ---------------------------------------------------------------------------
# detect-route resolution + config plumbing
# ---------------------------------------------------------------------------

def test_detect_kernel_field_validated():
    with pytest.raises(ValueError, match="detect_kernel"):
        QLinearConfig(detect_kernel="cuda")


def test_resolve_detect_route_passthrough():
    assert kr.resolve_detect_route("pallas") == "pallas"
    assert kr.resolve_detect_route("jnp") == "jnp"
    with pytest.raises(ValueError):
        kr.resolve_detect_route("bogus")


def test_detect_auto_route_env_override(monkeypatch):
    monkeypatch.setattr(kr, "_DETECT_AUTO_DEFAULT", None)
    monkeypatch.setenv("REPRO_TOPK_KERNEL", "1")
    assert kr.resolve_detect_route("auto") == "pallas"
    monkeypatch.setattr(kr, "_DETECT_AUTO_DEFAULT", None)
    monkeypatch.setenv("REPRO_TOPK_KERNEL", "off")
    assert kr.resolve_detect_route("auto") == "jnp"
    monkeypatch.setattr(kr, "_DETECT_AUTO_DEFAULT", None)
    monkeypatch.setenv("REPRO_TOPK_KERNEL", "auto")
    want = "pallas" if jax.default_backend() == "tpu" else "jnp"
    assert kr.resolve_detect_route("auto") == want
    # the GEMM env var must NOT leak into the detection route
    monkeypatch.setattr(kr, "_DETECT_AUTO_DEFAULT", None)
    monkeypatch.setenv("REPRO_LUT_KERNEL", "1")
    monkeypatch.setenv("REPRO_TOPK_KERNEL", "off")
    assert kr.resolve_detect_route("auto") == "jnp"


def test_quantspec_detect_rule_and_json_roundtrip():
    spec = QuantSpec(base=QLinearConfig(),
                     rules=[("attn/*", {"detect_kernel": "pallas"})])
    assert spec.resolve("blocks/attn/wq").detect_kernel == "pallas"
    assert spec.resolve("blocks/mlp/wi").detect_kernel == "auto"
    assert QuantSpec.from_json_dict(spec.to_json_dict()) == spec
    # pre-Orizuru artifacts (no "detect_kernel" key) load with the auto default
    d = spec.to_json_dict()
    d["base"].pop("detect_kernel")
    assert QuantSpec.from_json_dict(d).base.detect_kernel == "auto"


def test_with_detect_route_flips_tree():
    p = _layer(QLinearConfig())
    tree = {"a": p, "b": [p, jnp.ones(3)]}
    out = with_detect_route(tree, "pallas")
    assert out["a"].cfg.detect_kernel == "pallas"
    assert out["b"][0].cfg.detect_kernel == "pallas"
    assert out["a"].cfg.kernel == "auto"  # GEMM route untouched
    assert p.cfg.detect_kernel == "auto"  # original untouched
    np.testing.assert_array_equal(out["b"][1], tree["b"][1])


# ---------------------------------------------------------------------------
# A3 tier legality
# ---------------------------------------------------------------------------

def test_a3_requires_detection():
    cfg = QLinearConfig(a_bits=3, detection="none")  # constructible...
    with pytest.raises(ValueError, match="A3"):
        cfg.validate()  # ...but not applicable
    with pytest.raises(ValueError, match="A3"):
        QuantSpec(base=cfg).resolve("blocks/mlp/wi")
    with pytest.raises(ValueError, match="A3"):
        _layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 128))
    with pytest.raises(ValueError, match="A3"):
        qlinear_apply(_layer(QLinearConfig()), x, cfg=cfg)


def test_a3_legal_with_detection_and_rule_unlock():
    for detection in ("dynamic", "static", "static_dense"):
        QLinearConfig(a_bits=3, detection=detection).validate()
    # a rule chain may pass THROUGH an illegal intermediate state as long as
    # the final per-layer config is legal
    spec = QuantSpec(base=QLinearConfig(detection="none"),
                     rules=[("mlp/*", {"a_bits": 3}),
                            ("mlp/*", {"detection": "dynamic"})])
    assert spec.resolve("blocks/mlp/wi").a_bits == 3
    with pytest.raises(ValueError, match="A3"):
        QuantSpec(base=QLinearConfig(detection="none"),
                  rules=[("mlp/*", {"a_bits": 3})]).resolve("blocks/mlp/wi")


def test_a3_uniform_grid_exempt():
    # the RTN/INT-WAQ A3 grid is the deliberate collapse baseline
    # (bench_ppl's rtn_w4a3 row) — not gated by the K-Means rule
    QLinearConfig(a_bits=3, method="uniform", detection="none").validate()


def test_bit_width_ranges_checked():
    with pytest.raises(ValueError, match="a_bits"):
        QLinearConfig(a_bits=2)
    with pytest.raises(ValueError, match="a_bits"):
        QLinearConfig(a_bits=9)
    with pytest.raises(ValueError, match="w_bits"):
        QLinearConfig(w_bits=1)
    with pytest.raises(ValueError, match="w_bits"):
        QLinearConfig(w_bits=9)


def test_a3_qlinear_end_to_end():
    """An A3 dual-branch layer runs and the outlier branch visibly repairs
    the 8-entry codebook's tail error."""
    cfg = QLinearConfig(a_bits=3, detection="dynamic", outlier_frac=0.02)
    p = _layer(cfg, k=192, n=64, seed=3)
    x = jax.random.normal(jax.random.PRNGKey(11), (5, 192)) * 2
    y = qlinear_apply(p, x)
    assert y.shape == (5, 64) and jnp.all(jnp.isfinite(y))
    y_none = qlinear_apply(p, x, cfg=dataclasses.replace(
        cfg, detection="static", outlier_frac=0.0))
    assert not jnp.array_equal(y, y_none)  # compensation actually fired


# ---------------------------------------------------------------------------
# kernel: odd-N padding + tie-breaking vs lax.top_k
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,k", [(4, 7, 2), (2, 9, 9), (3, 129, 5),
                                   (1, 3, 1), (5, 31, 4)])
def test_topk_kernel_odd_n_matches_oracle(m, n, k):
    """Odd N is padded in-kernel (-inf max side / +inf min side), not
    rejected; with k <= N the pads are never popped."""
    x = jax.random.normal(jax.random.PRNGKey(n * 7 + k), (m, n))
    got = topk_outlier_kernel_call(x, k)
    want = topk_outlier_ref(x, k)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_topk_kernel_k_above_n_still_raises():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 7))
    with pytest.raises(ValueError):
        topk_outlier_kernel_call(x, 8)
    with pytest.raises(ValueError):
        topk_outlier_kernel_call(x, 0)


@pytest.mark.parametrize("n", [16, 17])
def test_topk_kernel_duplicate_heavy_ties(n):
    """lax.top_k breaks value ties lowest-index-first; the tournament's
    left-child rule must agree exactly, or greedy serving tokens diverge."""
    vals = jnp.array([3.0, -3.0, 0.0, 1.0])
    x = vals[jax.random.randint(jax.random.PRNGKey(5), (6, n), 0, 4)]
    got = topk_outlier_kernel_call(x, 3)
    want = topk_outlier_ref(x, 3)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("n", [8, 13])
def test_topk_kernel_all_equal(n):
    x = jnp.full((3, n), 2.5)
    hi_v, hi_i, lo_v, lo_i = topk_outlier_kernel_call(x, 2)
    np.testing.assert_array_equal(hi_v, jnp.full((3, 2), 2.5))
    np.testing.assert_array_equal(lo_v, jnp.full((3, 2), 2.5))
    # all-equal: both sides must pick indices 0..k-1 (lowest-index-first)
    np.testing.assert_array_equal(hi_i, jnp.broadcast_to(jnp.arange(2), (3, 2)))
    np.testing.assert_array_equal(lo_i, jnp.broadcast_to(jnp.arange(2), (3, 2)))


@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
@settings(max_examples=30, deadline=None)
@given(n=st.integers(min_value=2, max_value=70), data=st.data())
def test_topk_kernel_property(n, data):
    """Any (N, k, dtype) — odd N included, values drawn from a small integer
    set to force heavy ties — matches the sort-based counting oracle."""
    k = data.draw(st.integers(min_value=1, max_value=n))
    dtype = (jnp.float32, jnp.bfloat16)[data.draw(st.integers(0, 1))]
    seed = data.draw(st.integers(min_value=0, max_value=2**16))
    vals = jnp.arange(-2, 3, dtype=jnp.float32)
    x = vals[jax.random.randint(jax.random.PRNGKey(seed), (2, n), 0, 5)]
    x = x.astype(dtype).astype(jnp.float32)  # kernel contract: f32 in
    got = topk_outlier_kernel_call(x, k, block_m=2)
    want = topk_outlier_ref(x, k)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# ---------------------------------------------------------------------------
# streaming quantize+detect
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,mul_form", [(32, False), (33, False), (32, True)])
def test_streaming_kernel_matches_ref(n, mul_form):
    key = jax.random.PRNGKey(n)
    x = jax.random.normal(key, (5, n))
    scale = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1), (5, 1))) + 0.5
    boundaries = jnp.sort(jax.random.normal(jax.random.fold_in(key, 2), (15,)))
    got = streaming_quantize_outlier_kernel_call(
        x, scale, boundaries, 3, mul_form=mul_form)
    want = streaming_quantize_outlier_ref(
        x, scale, boundaries, 3, mul_form=mul_form)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("a_bits", [4, 3])
def test_ops_streaming_bit_identity(dtype, a_bits):
    """quantize_outlier_streaming == quantize_activation + detect_outliers_topk
    bit-for-bit: idx (dtype included), scale, outlier values and channels —
    the contract that makes detect routes token-identical under serving."""
    key = jax.random.PRNGKey(a_bits)
    calib = jax.random.normal(key, (64, 96))
    book = fit_activation_codebook(calib, a_bits)
    x = (jax.random.normal(jax.random.fold_in(key, 1), (7, 96)) * 2).astype(dtype)
    qa, outs = kops.quantize_outlier_streaming(x, book, 2)
    qa_ref = quantize_activation(x, book)
    det_ref = ol.detect_outliers_topk(x.astype(jnp.float32), 2)
    assert qa.idx.dtype == qa_ref.idx.dtype
    np.testing.assert_array_equal(qa.idx, qa_ref.idx)
    np.testing.assert_array_equal(qa.scale, qa_ref.scale)
    np.testing.assert_array_equal(outs.values, det_ref.values)
    np.testing.assert_array_equal(outs.channels, det_ref.channels)


# ---------------------------------------------------------------------------
# dispatch accounting + explicit fallback
# ---------------------------------------------------------------------------

def test_detect_dispatch_counters_record_routes():
    p = _layer(QLinearConfig())
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 128))
    kr.reset()
    qlinear_apply(with_detect_route(p, "jnp"), x)
    qlinear_apply(with_detect_route(p, "pallas"), x)
    counts = kr.detect_dispatch_counts()
    assert counts["w4a4/jnp"] == 1
    assert counts["w4a4/pallas"] == 1
    assert kr.detect_kernel_calls() == 1 and kr.detect_jnp_calls() == 1
    assert kr.detect_calls() == 2
    # the dual branch also resolved a compensation route each time
    assert sum(kr.comp_route_counts().values()) == 2
    snap = kr.snapshot()
    assert snap["_detect_kernel_calls"] == 1 and snap["_detect_fallbacks"] == 0


@pytest.mark.parametrize("detection", ["dynamic", "static"])
def test_kernel_route_folds_compensation(detection):
    """On the kernel route the outlier branch is folded into the fused
    LUT-GEMM (comp:fused, no gather or scatter); an explicit comp_mode keeps
    the separate branch as an ablation, and agrees with the fold."""
    p = _layer(QLinearConfig(detection=detection, outlier_frac=0.02,
                             kernel="pallas"), seed=12)
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 128)) * 2
    kr.reset()
    y = qlinear_apply(p, x)
    assert kr.comp_route_counts() == {"fused": 1}
    for mode in ("gather", "scatter"):
        kr.reset()
        y_split = qlinear_apply(p, x, cfg=dataclasses.replace(p.cfg, comp_mode=mode))
        assert kr.comp_route_counts() == {mode: 1}
        np.testing.assert_allclose(y, y_split, rtol=2e-5, atol=1e-4)


def test_jnp_route_keeps_gather_and_scatter():
    p = _layer(QLinearConfig(detection="dynamic", outlier_frac=0.02,
                             kernel="jnp", comp_auto_tokens=4), seed=13)
    kr.reset()
    qlinear_apply(p, jax.random.normal(jax.random.PRNGKey(6), (4, 128)))
    qlinear_apply(p, jax.random.normal(jax.random.PRNGKey(7), (5, 128)))
    assert kr.comp_route_counts() == {"gather": 1, "scatter": 1}


def test_engine_reports_fused_compensation():
    """Serving on the kernel route: the engine's outlier_comp_fused stat
    counts the fold, beside the gather and scatter stats."""
    from repro.configs.base import get_smoke_config
    from repro.models.model import build, quantize_model
    from repro.serving.engine import ServeConfig, ServingEngine

    model = build(get_smoke_config("llama3_2_1b"))
    spec = QuantSpec(base=QLinearConfig(detection="dynamic", outlier_frac=0.02,
                                        kernel="pallas"))
    qp = quantize_model(model, model.init(jax.random.PRNGKey(0)), spec)
    eng = ServingEngine(model, qp, ServeConfig(cache_len=32, cache_dtype="float32",
                                               block_size=8),
                        batch_slots=2)
    kr.reset()
    eng.generate([[1, 2, 3], [4]], max_new_tokens=2)
    stats = eng.stats
    assert stats["outlier_comp_fused"] > 0
    assert stats["outlier_comp_gather"] == stats["outlier_comp_scatter"] == 0


def test_static_pallas_detect_fallback_is_explicit():
    """Static (OASIS-S) detection has no tournament: a requested pallas
    detect route is demoted — warned once, counted, bit-equal to jnp."""
    cfg = QLinearConfig(detection="static", detect_kernel="pallas")
    p = _layer(cfg, seed=9)
    x = jax.random.normal(jax.random.PRNGKey(4), (4, 128))
    kr.reset()
    kr._WARNED.clear()
    with pytest.warns(RuntimeWarning, match="falling back"):
        y = qlinear_apply(p, x)
    assert kr.detect_fallback_count() == 1
    y_jnp = qlinear_apply(with_detect_route(p, "jnp"), x)
    np.testing.assert_array_equal(y, y_jnp)  # same path -> bit-equal
    # second apply: counted again, but no warning spam
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        qlinear_apply(p, x)
    assert kr.detect_fallback_count() == 2
    assert kr.detect_calls() == 3  # fallback rows still count as detections


# ---------------------------------------------------------------------------
# detect-route parity through the full dual-branch layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gemm_route", ["jnp", "pallas"])
@pytest.mark.parametrize("a_bits", [3, 4, 5])
def test_qlinear_detect_route_parity(gemm_route, a_bits):
    """pallas detection (streaming on the jnp GEMM route, detection-only on
    the fused route / A>4) is BIT-equal to the lax.top_k route — not just
    allclose — so greedy tokens cannot diverge."""
    cfg = QLinearConfig(a_bits=a_bits, detection="dynamic", outlier_frac=0.02,
                        kernel=gemm_route)
    p = _layer(cfg, k=192, n=64, seed=a_bits)
    x = jax.random.normal(jax.random.PRNGKey(7), (5, 192)) * 2
    y_jnp = qlinear_apply(with_detect_route(p, "jnp"), x)
    y_pal = qlinear_apply(with_detect_route(p, "pallas"), x)
    np.testing.assert_array_equal(y_pal, y_jnp)


def test_qlinear_detect_route_parity_bf16():
    cfg = QLinearConfig(detection="dynamic", outlier_frac=0.02, kernel="jnp")
    p = _layer(cfg, k=128, n=32, seed=2)
    x = (jax.random.normal(jax.random.PRNGKey(9), (4, 128)) * 2).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        qlinear_apply(with_detect_route(p, "pallas"), x),
        qlinear_apply(with_detect_route(p, "jnp"), x))


# ---------------------------------------------------------------------------
# serving token identity: detect route flipped, prefix + speculation on
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_token_identity_across_detect_routes():
    from repro.configs.base import get_smoke_config
    from repro.models.model import build, quantize_model
    from repro.serving.engine import ServeConfig, ServingEngine
    from repro.serving.speculative import DEFAULT_DRAFT_SPEC, SpeculativeConfig

    cfg = get_smoke_config("llama3_2_1b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = QuantSpec(base=QLinearConfig(a_bits=3, detection="dynamic",
                                        outlier_frac=0.01))
    qp = quantize_model(model, params, spec)
    dqp = quantize_model(model, params, DEFAULT_DRAFT_SPEC)
    prompts = [[1, 2, 3, 4, 5, 6, 7, 8], [9, 8, 7], [1, 2, 3, 4, 5, 6, 20, 21]]

    def serve(route):
        eng = ServingEngine(
            model, with_detect_route(qp, route),
            ServeConfig(cache_len=64, cache_dtype="float32", block_size=8,
                        prefill_chunk=4, prefix_cache=True,
                        speculative=SpeculativeConfig(k=2)),
            batch_slots=3,
            draft=(model, with_detect_route(dqp, route), DEFAULT_DRAFT_SPEC))
        out = eng.generate(prompts, max_new_tokens=6)
        return out, eng.stats

    kr.reset()
    out_jnp, _ = serve("jnp")
    out_pal, stats = serve("pallas")
    assert out_jnp == out_pal
    assert stats["outlier_kernel_calls"] > 0  # Orizuru really ran in serving
    assert stats["outlier_detect_calls"] > 0
    assert stats["outlier_fallbacks"] == 0
    assert stats["outlier_comp_gather"] + stats["outlier_comp_scatter"] > 0
