"""Bring-up check: serve h2o-danube-1.8B at full width on one TPU chip.

Drives the normal serving path once — ``build`` -> ``quantize_model`` ->
``save_quantized``/``load_quantized`` -> ``ServingEngine`` (paged
scheduler) -> ``generate`` — at the published width and depth of
``h2o_danube_1_8b`` (24 layers, d=2560, GQA 32/8, SWA 4096, bf16) with
random weights from ``--seed``, under the quantization spec that
``examples/serve_quantized.py`` serves: W4A4 with dynamic Orizuru outliers,
W8 ``mlp/wd``, int4 paged KV. Every Pallas kernel on that path runs
compiled for the chip.

Checks, each fatal:
  * every request returns exactly its budget of valid token ids;
  * the route counters show LUT-GEMM and Orizuru kernel dispatches, no
    fallbacks, and the paged-attention kernel is on;
  * the kernels compute the right thing on the chip: each one, compiled,
    matches its jnp oracle on the same inputs at the served widths within
    ``KERNEL_TOL`` (``check_kernels``);
  * one prompt's logits from the Pallas route agree with the jnp route's
    (``with_kernel_route`` / ``with_detect_route``, paged kernel off) on the
    same quantized params within ``ROUTE_RATIO_MAX`` and
    ``ROUTE_AGREEMENT_MIN`` (``check_routes_agree``).

The lines before the last are a bring-up record (compile seconds and
persistent-cache hits per phase, quantize and generate wall time, peak
HBM), not a benchmark. The last line is the JSON result. Without a TPU the
script exits nonzero and prints no result.

The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in this checkout. The artifact goes to ``.smoke_artifact/``.

Run: python chip_smoke.py [--seed 0]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))  # run from a checkout, no install step

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.core.kernel_routing as kr  # noqa: E402
import repro.models.layers as layers  # noqa: E402
from repro.configs.h2o_danube_1_8b import CONFIG  # noqa: E402
from repro.core import QLinearConfig, QuantSpec, quantize_model  # noqa: E402
from repro.core.artifact import load_quantized, save_quantized  # noqa: E402
from repro.core.codebook import boundaries_from_centroids  # noqa: E402
from repro.core.outlier import num_outliers  # noqa: E402
from repro.core.qlinear import with_detect_route, with_kernel_route  # noqa: E402
from repro.core.quantize import token_scale  # noqa: E402
from repro.kernels import ref  # noqa: E402
from repro.kernels.ops import should_interpret  # noqa: E402
from repro.kernels.lut_gemm import fused_lut_gemm_kernel_call  # noqa: E402
from repro.kernels.paged_attn import paged_attn_kernel_call  # noqa: E402
from repro.kernels.topk_outlier import (  # noqa: E402
    streaming_quantize_outlier_kernel_call,
    topk_outlier_kernel_call,
)
from repro.models.model import build  # noqa: E402
from repro.serving.engine import ServeConfig, ServingEngine  # noqa: E402
from repro.serving.speculative import make_packed_fn  # noqa: E402

# the spec examples/serve_quantized.py serves
SPEC = QuantSpec(
    base=QLinearConfig(detection="dynamic", outlier_frac=0.005),
    rules=[("mlp/wd", {"w_bits": 8})],
    kv_bits=4, kv_dtype="float32",
)

# Each Pallas kernel against its jnp oracle (kernels/ref.py) on the same
# inputs at the served widths, matmuls at HIGHEST precision on both sides:
# max |difference| within this fraction of the oracle's max |value| (top-k
# outputs and bucket indices must be equal).
KERNEL_TOL = 1e-4

# Served logits of the Pallas route against the jnp route. A random-weight
# 24-layer W4A4 stack is ill-conditioned: A4 bucketize, Orizuru top-k and the
# int4 KV cache turn one-ulp rounding differences into different codes, and
# those spread. (A one-ulp bf16 nudge to 1% of the embedding entries moved
# the last logits by 0.36 of their max and kept 44% of greedy tokens, on the
# CPU at d=256 and 24 layers.) So these gates catch only a broken route,
# whose greedy tokens would agree about 1/vocab of the time; the kernels'
# arithmetic is held to KERNEL_TOL.
ROUTE_RATIO_MAX = 1.0
ROUTE_AGREEMENT_MIN = 0.1


@dataclasses.dataclass
class SmokeSize:
    """What one run serves; ``FULL`` is the bring-up run on the chip."""

    n_requests: int = 8
    prompt_lens: tuple[int, int] = (64, 1024)
    new_tokens: int = 32
    cache_len: int = 2048
    slots: int = 8


FULL = SmokeSize()


class CompileLog:
    """Backend-compile seconds and persistent-cache hits/misses per phase,
    from JAX's monitoring events."""

    def __init__(self):
        self.secs = 0.0
        self.hits = 0
        self.misses = 0

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration

    @contextlib.contextmanager
    def listening(self):
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_listener(self._on_event)
            jax.monitoring.unregister_event_duration_listener(self._on_duration)

    @contextlib.contextmanager
    def phase(self, name: str, log):
        secs, hits, misses = self.secs, self.hits, self.misses
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        log(f"phase {name}: wall {wall:.2f} s, compile {self.secs - secs:.2f} s, "
            f"cache hits {self.hits - hits}, misses {self.misses - misses}")


def require(ok, what) -> None:
    """A failed check ends the run; unlike ``assert``, also under -O."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


@contextlib.contextmanager
def _paged_kernel(on: bool):
    was = layers._USE_PAGED_KERNEL
    layers._USE_PAGED_KERNEL = on
    try:
        yield
    finally:
        layers._USE_PAGED_KERNEL = was


def route_logits(model, params, prompt: list[int], cache_dtype: str,
                 block_size: int, pallas: bool) -> np.ndarray:
    """(len(prompt), vocab) logits of one prompt through the packed serving
    step (one token per row, int4 paged KV), on one route."""
    route = "pallas" if pallas else "jnp"
    params = with_detect_route(with_kernel_route(params, route), route)
    plen = len(prompt)
    n_blocks = -(-plen // block_size)
    pools = model.init_caches(1, plen, jnp.dtype(cache_dtype), quantized=True,
                              layout="paged", block_size=block_size,
                              n_blocks=n_blocks)
    with _paged_kernel(pallas):
        _, logits, _ = jax.jit(make_packed_fn(model))(
            params, pools, jnp.arange(n_blocks, dtype=jnp.int32)[None],
            jnp.zeros((plen,), jnp.int32),
            jnp.arange(plen, dtype=jnp.int32)[:, None],
            jnp.arange(1, plen + 1, dtype=jnp.int32),
            jnp.asarray(prompt, jnp.int32)[:, None])
    return np.asarray(logits[:, 0], np.float32)


def check_routes_agree(model, params, prompt, sc: ServeConfig, log) -> None:
    """Pallas vs jnp route logits on the same params, at served precision."""
    pal, ref = (route_logits(model, params, prompt, sc.cache_dtype,
                             sc.block_size, pallas) for pallas in (True, False))
    require(np.isfinite(pal).all() and np.isfinite(ref).all(), "finite logits")
    diff = float(np.max(np.abs(pal[-1] - ref[-1])))
    scale = float(np.max(np.abs(ref[-1])))
    agree = float(np.mean(pal.argmax(-1) == ref.argmax(-1)))
    log(f"routes, prompt of {len(prompt)}: max |dlogit| last position "
        f"{diff:.6g} (max |logit| {scale:.6g}, ratio {diff / scale:.3g}, "
        f"limit {ROUTE_RATIO_MAX}); greedy agreement {agree:.4f} over "
        f"{len(prompt)} positions (limit {ROUTE_AGREEMENT_MIN})")
    require(diff <= ROUTE_RATIO_MAX * scale, "route logits ratio")
    require(agree >= ROUTE_AGREEMENT_MIN, "route greedy agreement")


def check_kernels(cfg, params, rows: int, seed: int, log) -> None:
    """Every main-path kernel, compiled, against its jnp oracle on the same
    inputs: the served layer-0 weights and random activations / KV pools at
    the served widths, ``rows`` token rows."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    normal = lambda shape, dtype=jnp.float32: jax.random.normal(
        next(keys), shape, jnp.float32).astype(dtype)

    def close(name, got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        diff = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        log(f"kernel {name}: max |diff| {diff:.6g} (max |oracle| {scale:.6g}, "
            f"limit {KERNEL_TOL} of it)")
        require(diff <= KERNEL_TOL * scale, name)

    def equal(name, got, want):
        same = all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got, want))
        log(f"kernel {name}: outputs equal to the oracle: {same}")
        require(same, name)

    compiled = dict(interpret=should_interpret())  # False on the chip
    layer0 = lambda tree: jax.tree.map(lambda a: a[0], tree)
    with jax.default_matmul_precision("highest"):
        for group, name in (("attn", "wq"), ("mlp", "wi"), ("mlp", "wd")):
            p = layer0(params["blocks"][group][name])
            x = normal((rows, p.qw.shape[0]), jnp.bfloat16)  # served dtype
            book = p.act_codebook.astype(jnp.float32)
            args = (x, token_scale(x), p.qw.packed,
                    boundaries_from_centroids(book), book, p.qw.codebook)
            kw = dict(byte_packed=p.qw.nbits > 4, mul_form=True)
            close(f"lut_gemm_fused {group}/{name} W{p.qw.nbits}A4",
                  jax.jit(functools.partial(fused_lut_gemm_kernel_call,
                                            **kw, **compiled))(*args),
                  jax.jit(functools.partial(ref.fused_lut_gemm_ref, **kw))(*args))

        k_of = lambda n: num_outliers(n, SPEC.base.outlier_frac)
        for n in (cfg.d_model, cfg.d_ff):
            x = normal((rows, n))
            equal(f"orizuru detect N={n}",
                  jax.jit(lambda x: topk_outlier_kernel_call(
                      x, k_of(n), **compiled))(x),
                  jax.jit(lambda x: ref.topk_outlier_ref(x, k_of(n)))(x))
            book = params["blocks"]["mlp"]["wi"].act_codebook[0]
            args = (x, token_scale(x), boundaries_from_centroids(book))
            equal(f"orizuru streaming N={n}",
                  jax.jit(lambda *a: streaming_quantize_outlier_kernel_call(
                      *a, k_of(n), **compiled))(*args),
                  jax.jit(lambda *a: ref.streaming_quantize_outlier_ref(
                      *a, k_of(n)))(*args))

        kv, hd = cfg.n_kv_heads, cfg.head_dim
        bs, max_blk = 16, 16
        n_blocks = 4 * max_blk
        q = normal((rows, 1, kv, cfg.n_heads // kv, hd), jnp.bfloat16)
        tables = jax.random.permutation(next(keys), n_blocks)[
            jnp.arange(rows * max_blk) % n_blocks].reshape(rows, max_blk)
        ctx = jax.random.randint(next(keys), (rows,), 1, max_blk * bs + 1)
        meta = dict(block_tables=tables, ctx_lens=ctx, q_pos=(ctx - 1)[:, None],
                    window=cfg.sliding_window)
        idx = lambda: jax.random.randint(next(keys), (n_blocks, bs, kv, hd // 2),
                                         0, 256).astype(jnp.uint8)
        scale = lambda: jnp.abs(normal((n_blocks, bs, kv, 1))) + 0.1
        pools = {"int4": (idx(), scale(), idx(), scale(),
                          jnp.sort(normal((16,)))),
                 "bf16": tuple(normal((n_blocks, bs, kv, hd), jnp.bfloat16)
                               for _ in range(2))}
        oracles = {"int4": ref.paged_attn_quant_ref, "bf16": ref.paged_attn_ref}
        for pool, storage in pools.items():
            close(f"paged_attn {pool} pool",
                  jax.jit(lambda q, *st: paged_attn_kernel_call(
                      q, *st, **meta, **compiled))(q, *storage),
                  jax.jit(lambda q, *st, f=oracles[pool]: f(q, *st, **meta))(
                      q, *storage))


def smoke(cfg, size: SmokeSize, *, seed: int, workdir: Path, log=print) -> dict:
    """Serve ``cfg`` under SPEC through the normal path and check it; raises
    on any failed check. Returns the bring-up record."""
    clock = CompileLog()
    rec = {}
    with clock.listening():
        model = build(cfg)
        log(f"model {cfg.arch_id}: {cfg.n_layers} layers, d={cfg.d_model}, "
            f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.head_dim}, "
            f"d_ff={cfg.d_ff}, vocab={cfg.vocab_size}, "
            f"window={cfg.sliding_window}, {cfg.param_dtype}")
        with clock.phase("init", log):
            params = jax.block_until_ready(
                jax.jit(model.init)(jax.random.PRNGKey(seed)))
        t0 = time.perf_counter()
        with clock.phase("quantize", log):
            qparams = jax.block_until_ready(quantize_model(model, params, SPEC))
        rec["quantize_s"] = time.perf_counter() - t0
        del params
        with clock.phase("save_load", log):
            shutil.rmtree(workdir, ignore_errors=True)
            save_quantized(str(workdir), cfg, SPEC, qparams)
            del qparams
            model, params, spec = load_quantized(str(workdir))

        rng = np.random.RandomState(seed)
        lens = rng.randint(size.prompt_lens[0], size.prompt_lens[1] + 1,
                           size=size.n_requests)
        prompts = [rng.randint(0, cfg.vocab_size, size=n).tolist() for n in lens]
        sc = ServeConfig.from_spec(spec, cache_len=size.cache_len)
        engine = ServingEngine(model, params, sc, batch_slots=size.slots)
        before = kr.snapshot()
        t0 = time.perf_counter()
        with clock.phase("generate", log):
            outs = engine.generate(prompts, max_new_tokens=size.new_tokens)
        rec["generate_s"] = time.perf_counter() - t0
        counts = {k: v - before.get(k, 0) for k, v in kr.snapshot().items()}
        st = engine.stats
        log(f"served {len(prompts)} requests, prompts {sorted(lens.tolist())}, "
            f"{size.new_tokens} new tokens each: {sum(map(len, outs))} tokens in "
            f"{rec['generate_s']:.2f} s wall (compile included), "
            f"{st['packed_steps']} packed steps, {st['preemptions']} preemptions")
        for out in outs:
            require(len(out) == size.new_tokens, f"{len(out)} tokens returned")
            require(all(0 <= t < cfg.vocab_size for t in out), f"token ids {out}")
        log("route counters (trace-time): " + json.dumps(
            {k: v for k, v in counts.items() if v}, sort_keys=True))
        paged_on = layers._paged_kernel_enabled()
        log(f"paged-attention kernel enabled: {paged_on}")
        require(counts["_kernel_calls"] > 0 and counts["_detect_kernel_calls"] > 0,
                "LUT-GEMM and Orizuru kernel dispatches")
        require(counts["_fallbacks"] == 0 and counts["_detect_fallbacks"] == 0,
                "no kernel fallbacks")
        require(paged_on, "paged-attention kernel on")

        with clock.phase("kernel_check", log):
            rows = sc.token_budget or size.slots + sc.prefill_chunk
            check_kernels(cfg, params, rows, seed, log)
        with clock.phase("route_check", log):
            check_routes_agree(model, params, prompts[int(np.argmin(lens))],
                               sc, log)
    stats = jax.devices()[0].memory_stats() or {}
    rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    log(f"peak_bytes_in_use: {rec['peak_bytes_in_use']}")
    return rec


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and prompts")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 1
    # before the first compile; JAX reads JAX_COMPILATION_CACHE_DIR itself
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = lambda s: print(f"[bring-up record, not a benchmark] {s}", flush=True)
    log(f"device_kind {dev.device_kind}, {len(jax.devices())} device(s), "
        f"jax {jax.__version__}, compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    smoke(CONFIG, FULL, seed=args.seed, workdir=ROOT / ".smoke_artifact", log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
