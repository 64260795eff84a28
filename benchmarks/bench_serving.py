"""Serving-subsystem benchmark: mixed-length traffic, seed engine vs the
packed token-budget scheduler.

Workload: ``N_REQUESTS`` requests with prompt lengths drawn from a clipped
lognormal over [16, 512] tokens and per-request decode budgets over [8, 32],
arriving as a Poisson process. Two engines serve the same trace:

  ring  : the seed fixed-slot batcher (paged=False) — slot-sized chunks,
          left-padded batch prefill, every chunk decodes the max budget
  paged : the block-pool scheduler — ONE packed token-budget step per
          iteration that mixes every running slot's decode token with
          admitting requests' prefill tokens (decode reserved first, so
          admission can never stall decode)

The clock is hybrid discrete-event: compute time is measured wall time, idle
gaps fast-forward to the next arrival, so latency percentiles are
arrival-aware without real sleeps. Emits tokens/s over *requested* tokens
(both engines are credited only for tokens the trace asked for), p50/p95
completion latency, peak block-pool occupancy, preemption count, and the
mixed-step share (packed steps serving prefill AND decode together — the
quantity that was zero when prefill serialized at batch=1).

A second, deterministic **shared-system-prompt phase** measures prefix
sharing: one leader prefills a multi-block system prompt, then a mixed
wave of followers (most sharing the prefix, some unrelated) is served
twice — prefix cache on vs off. It reports ``prefix_hit_tokens`` (tokens
aliased from cached blocks), ``prefill_skipped`` (prefill compute avoided),
COW copies and cached-prefix evictions, asserts the two runs are
token-identical, and asserts prefill tokens computed drop by at least the
shared full-block fraction.

A third, **speculative-decoding phase** serves decode-heavy Poisson traffic
on the TRAINED byte-LM (drafting needs a model whose argmaxes mean
something) twice — speculation off vs on (a W3 K-Means draft of the same
model, saved and loaded as a real artifact). It asserts the two runs are
token-identical (greedy verification is exact regardless of draft quality),
records tokens/s for both plus the acceptance rate and the
drafted / accepted / rolled-back token counters, and on the full trace
asserts speculative decode tokens/s beats the non-speculative baseline.

**Telemetry** (this PR's instrument panel): the paged engine runs at
``telemetry="trace"``, so TTFT / inter-token-latency / queue-wait / e2e
percentiles are **engine-sourced** (serving/telemetry.py histograms, wall
clock) rather than derived from the bench's hybrid sim clock — both are
reported; they answer different questions (sim latency is arrival-aware,
engine latency is compute-path truth). The run writes a Chrome/Perfetto
trace artifact to ``results/serving_trace.json`` (validated as trace-event
JSON here — the CI gate), and a final overhead phase serves one small trace
with ``telemetry="off"`` vs the histograms-on default and records the
wall-time delta.

A final **outlier phase** (``run_outlier_phase``) measures the Orizuru
online outlier engine on the serving path: held-out CE across detection
modes at A4 and the A3 tier (asserting A3+dynamic strictly beats
A3+static), decode tokens/s per mode, and detect-route token identity
(``detect_kernel`` jnp vs pallas) under prefix sharing + speculation with
the kernel-dispatch and fallback counters asserted.

A final **quality phase** (``run_quality_phase``) measures this PR's
quantization-numerics observability: telemetry="quality" vs "metrics" wall
time at the default 1/16 probe sampling (asserted <= 10% + 50 ms slack in
full runs; smoke gates a looser 2.5x canary — CPU dispatch floors dominate
the tiny trace),
the acceptance-criterion gauges (per-site codebook utilization, SQNR,
outlier-energy-captured, drift) populated on a probe-every-step engine,
shadow-reference logit-KL observations recorded, and an induced-drift
subphase: the same traffic served against calibration stats scale-shifted
3x must move the drift gauge past alarm threshold while greedy tokens stay
identical to a telemetry="off" engine.

``--smoke`` (or run(smoke=True)) shrinks all traces for CI; the smoke run
still asserts ``prefix_hit_tokens > 0`` (the prefix-sharing CI gate),
``accepted_tokens > 0`` + speculative/baseline token-identity (the
speculative gate), a non-empty engine TTFT histogram, that the trace
artifact parses (the telemetry gates), ``outlier_detect_calls > 0``
with zero fallbacks plus Orizuru-vs-lax.top_k token identity (the outlier
gates), and the quality gates above (gauges populated, >= 1 shadow KL
observation, drift alarm on the shifted stats, token identity at quality).
"""

from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import RESULTS, emit, record
from repro.configs.base import get_smoke_config
from repro.core.artifact import load_quantized, save_quantized
from repro.core.qlinear import QLinearConfig
from repro.core.quantspec import QuantSpec
from repro.models.model import build, quantize_model
from repro.serving.engine import ServeConfig, ServingEngine

N_REQUESTS = 32
SLOTS = 8
PROMPT_RANGE = (16, 512)
BUDGET_RANGE = (8, 32)
MEAN_INTERARRIVAL_S = 0.05


@dataclasses.dataclass
class Trace:
    prompt: list[int]
    budget: int
    arrival: float


def make_trace(vocab: int, seed: int = 0, n_requests: int = N_REQUESTS,
               prompt_range: tuple[int, int] = PROMPT_RANGE) -> list[Trace]:
    rng = np.random.RandomState(seed)
    lens = np.clip(np.exp(rng.normal(4.5, 1.0, n_requests)).astype(int),
                   *prompt_range)
    budgets = rng.randint(BUDGET_RANGE[0], BUDGET_RANGE[1] + 1, n_requests)
    arrivals = np.cumsum(rng.exponential(MEAN_INTERARRIVAL_S, n_requests))
    return [Trace(list(rng.randint(1, vocab, n)), int(b), float(t))
            for n, b, t in zip(lens, budgets, arrivals)]


def make_shared_trace(vocab: int, prefix_len: int, n_requests: int,
                      tail_range: tuple[int, int], seed: int = 1,
                      shared_frac: float = 0.75):
    """Shared-system-prompt mix: request 0 (the leader) and ~shared_frac of
    the rest start with one common ``prefix_len``-token system prompt; the
    others are unrelated. Returns (traces, is_shared flags)."""
    rng = np.random.RandomState(seed)
    prefix = list(rng.randint(1, vocab, prefix_len))
    traces, shared = [], []
    for i in range(n_requests):
        tail = list(rng.randint(1, vocab, rng.randint(*tail_range)))
        is_shared = i == 0 or rng.rand() < shared_frac
        prompt = prefix + tail if is_shared else \
            list(rng.randint(1, vocab, prefix_len // 2 + len(tail)))
        traces.append(Trace(prompt, int(rng.randint(4, 13)), 0.0))
        shared.append(is_shared)
    return traces, shared


def run_shared_prefix(eng: ServingEngine, trace: list[Trace]):
    """Deterministic warm-cache driver: serve the leader until it decodes
    (its prefix blocks are then registered), then submit the follower wave
    and drain. Returns ({rid: tokens}, elapsed seconds)."""
    sched = eng.scheduler
    results: dict[int, list[int]] = {}
    t0 = time.perf_counter()
    lead = sched.submit(trace[0].prompt, trace[0].budget)
    while not any(r.rid == lead and r.decoding for r in sched._running):
        sched.step(results)
    for t in trace[1:]:
        sched.submit(t.prompt, t.budget)
    results.update(sched.run())
    return results, time.perf_counter() - t0


def _percentiles(lat: list[float]) -> tuple[float, float]:
    return float(np.percentile(lat, 50)), float(np.percentile(lat, 95))


def run_ring(eng: ServingEngine, trace: list[Trace]):
    """Seed path: slot-sized chunks in arrival order; a chunk starts once all
    its requests have arrived and the previous chunk finished, and decodes
    the chunk-max budget (the engine API has one scalar budget)."""
    sim, lat, tokens = 0.0, [], 0
    for i in range(0, len(trace), eng.slots):
        chunk = trace[i : i + eng.slots]
        sim = max(sim, max(t.arrival for t in chunk))
        t0 = time.perf_counter()
        eng.generate([t.prompt for t in chunk],
                     max_new_tokens=max(t.budget for t in chunk))
        sim += time.perf_counter() - t0
        lat += [sim - t.arrival for t in chunk]
        tokens += sum(t.budget for t in chunk)  # only requested tokens count
    return tokens / sim, lat


def run_paged(eng: ServingEngine, trace: list[Trace]):
    sched = eng.scheduler
    results: dict[int, list[int]] = {}
    sim, lat, born = 0.0, {}, {}
    pending = sorted(trace, key=lambda t: t.arrival)
    i = 0
    while True:
        while i < len(pending) and pending[i].arrival <= sim:
            rid = sched.submit(pending[i].prompt, pending[i].budget)
            born[rid] = pending[i].arrival
            i += 1
        if i < len(pending) and not sched._queue and not sched._running:
            sim = pending[i].arrival  # idle: fast-forward to the next arrival
            continue
        t0 = time.perf_counter()
        more = sched.step(results)
        sim += time.perf_counter() - t0
        for rid in results:
            if rid not in lat:
                lat[rid] = sim - born[rid]
        if not more and i >= len(pending):
            break
    tokens = sum(len(v) for v in results.values())
    return tokens / sim, [lat[r] for r in sorted(lat)], results


def run(smoke: bool = False) -> None:
    cfg = get_smoke_config("llama3_2_1b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    spec = QuantSpec(base=QLinearConfig(detection="none"), kv_dtype="float32")
    # serve from a saved artifact, like a production process: quantize once,
    # save, load — the engines below never touch calibration/K-Means again
    with tempfile.TemporaryDirectory() as d:
        save_quantized(d, cfg, spec, quantize_model(model, params, spec))
        model, qparams, spec = load_quantized(d)
    n_req = 8 if smoke else N_REQUESTS
    prompt_range = (8, 96) if smoke else PROMPT_RANGE
    trace = make_trace(cfg.vocab_size, n_requests=n_req, prompt_range=prompt_range)
    cache_len = prompt_range[1] + BUDGET_RANGE[1] + 16

    ring = ServingEngine(model, qparams,
                         ServeConfig.from_spec(spec, cache_len=cache_len,
                                               paged=False),
                         batch_slots=SLOTS)
    paged = ServingEngine(model, qparams,
                          ServeConfig.from_spec(spec, cache_len=cache_len,
                                                block_size=16, prefill_chunk=64,
                                                telemetry="trace"),
                          batch_slots=SLOTS)
    # warm the jit caches so the comparison measures steady-state serving
    ring.generate([[1, 2, 3]] * SLOTS, max_new_tokens=2)
    paged.generate([[1, 2, 3]] * SLOTS, max_new_tokens=2)
    paged.telemetry.reset()  # measurements start clean after warmup

    print("engine,tokens_s,p50_s,p95_s,extra")
    ring_tps, ring_lat = run_ring(ring, trace)
    p50, p95 = _percentiles(ring_lat)
    print(f"ring,{ring_tps:.1f},{p50:.2f},{p95:.2f},slot_chunks={-(-n_req // SLOTS)}")

    paged_tps, paged_lat, _ = run_paged(paged, trace)
    p50q, p95q = _percentiles(paged_lat)
    st = paged.scheduler.stats
    steps = max(st["packed_steps"], 1)
    budget = paged.scheduler.token_budget
    print(f"paged,{paged_tps:.1f},{p50q:.2f},{p95q:.2f},"
          f"peak_occupancy={st['peak_occupancy']:.2f} preemptions={st['preemptions']} "
          f"packed_steps={st['packed_steps']} "
          f"mixed_steps={st['mixed_steps']} "
          f"prefill_tokens={st['prefill_tokens']} "
          f"budget_util={st['packed_tokens'] / (steps * budget):.2f} "
          f"avg_decode_rows={st['decode_slot_tokens'] / steps:.2f}")

    # ---- engine-sourced SLO latencies + Perfetto trace artifact -----------
    snap = paged.telemetry.snapshot()
    ttft, itl = snap["requests"]["ttft_s"], snap["requests"]["itl_s"]
    assert ttft["count"] > 0, "engine TTFT histogram is empty (CI gate)"
    assert itl["count"] > 0, "engine ITL histogram is empty"
    print(f"engine_lat,-,-,-,"
          f"ttft_p50={ttft['p50'] * 1e3:.1f}ms ttft_p95={ttft['p95'] * 1e3:.1f}ms "
          f"itl_p50={itl['p50'] * 1e3:.1f}ms itl_p95={itl['p95'] * 1e3:.1f}ms "
          f"(wall clock, n={ttft['count']} requests)")
    trace_path = paged.telemetry.export_chrome_trace(RESULTS / "serving_trace.json")
    tdata = json.loads(trace_path.read_text())  # the CI gate: trace parses
    assert tdata.get("traceEvents"), "Perfetto trace has no events"
    emit("serving_trace_artifact", 0.0,
         f"{trace_path.name}: {len(tdata['traceEvents'])} trace events "
         f"(open at ui.perfetto.dev)")

    # ---- shared-system-prompt phase: prefix sharing on vs off -------------
    block_size = 16
    prefix_blocks = 4
    n_shared_req = 6 if smoke else 16
    tail_range = (8, 32) if smoke else (8, 96)
    shared_trace, shared_flags = make_shared_trace(
        cfg.vocab_size, prefix_blocks * block_size, n_shared_req, tail_range)
    shared_cache_len = max(len(t.prompt) for t in shared_trace) + 13 + block_size
    mk_shared = lambda pc: ServingEngine(
        model, qparams,
        ServeConfig.from_spec(spec, cache_len=shared_cache_len,
                              block_size=block_size, prefill_chunk=64,
                              prefix_cache=pc),
        batch_slots=SLOTS)
    on = mk_shared(True)
    got_on, dt_on = run_shared_prefix(on, shared_trace)
    off = mk_shared(False)
    got_off, dt_off = run_shared_prefix(off, shared_trace)
    assert got_on == got_off, "prefix sharing changed greedy outputs"
    st_on, st_off = on.stats, off.stats
    total_prompt = sum(len(t.prompt) for t in shared_trace)
    followers = sum(shared_flags) - 1  # every sharer after the leader hits
    expected_skip = followers * prefix_blocks * block_size
    assert st_on["prefix_hit_tokens"] >= expected_skip > 0, (
        f"prefix hits {st_on['prefix_hit_tokens']} < expected {expected_skip}"
    )
    # acceptance: prefill compute drops by >= the shared full-block fraction
    assert st_on["prefill_tokens"] <= st_off["prefill_tokens"] - expected_skip, (
        f"prefill computed {st_on['prefill_tokens']} vs {st_off['prefill_tokens']}"
        f" without sharing: expected a reduction of >= {expected_skip}"
    )
    print(f"prefix,{sum(t.budget for t in shared_trace) / dt_on:.1f},-,-,"
          f"prefix_hit_tokens={st_on['prefix_hit_tokens']} "
          f"prefill_skipped={st_on['prefill_skipped']} "
          f"prefill_tokens={st_on['prefill_tokens']} (off={st_off['prefill_tokens']}) "
          f"cow_copies={st_on['cow_copies']} "
          f"prefix_evictions={st_on['prefix_evictions']}")
    emit("serving_prefix_hit_tokens", 0.0,
         f"{st_on['prefix_hit_tokens']} tokens aliased / {st_on['prefill_skipped']} "
         f"prefill skipped of {total_prompt} prompt tokens "
         f"({followers}/{n_shared_req - 1} followers shared {prefix_blocks} blocks)")

    emit("serving_paged_vs_ring_tokens_s", 0.0,
         f"speedup={paged_tps / ring_tps:.2f}x (paged {paged_tps:.1f} vs ring {ring_tps:.1f} tok/s)")
    # the value rides the generic us_per_call field but the name's unit wins:
    # seconds (this used to multiply by 1e6, recording microseconds as _s)
    emit("serving_paged_p95_latency_s", p95q, f"ring_p95={p95:.2f}s")
    emit("serving_mixed_step_share", 0.0,
         f"{st['mixed_steps']}/{st['packed_steps']} packed steps served prefill+decode together")
    bench_cfg = {"smoke": smoke, "n_requests": n_req, "slots": SLOTS,
                 "prompt_range": list(prompt_range), "cache_len": cache_len,
                 "token_budget": paged.scheduler.token_budget,
                 "w_bits": spec.base.w_bits, "a_bits": spec.base.a_bits,
                 "kv_bits": spec.kv_bits, "served_from_artifact": True}
    record("serving_ring", tokens_s=round(ring_tps, 1), p50_s=round(p50, 3),
           p95_s=round(p95, 3), config=bench_cfg)
    record("serving_paged", tokens_s=round(paged_tps, 1), p50_s=round(p50q, 3),
           p95_s=round(p95q, 3), speedup=round(paged_tps / ring_tps, 2),
           mixed_steps=st["mixed_steps"], packed_steps=st["packed_steps"],
           preemptions=st["preemptions"],
           peak_occupancy=round(st["peak_occupancy"], 3),
           budget_util=round(st["packed_tokens"] / (steps * budget), 3),
           config=bench_cfg)
    record("serving_latency_engine",  # wall-clock, from the engine telemetry
           ttft_p50_s=round(ttft["p50"], 4), ttft_p95_s=round(ttft["p95"], 4),
           ttft_p99_s=round(ttft["p99"], 4),
           itl_p50_s=round(itl["p50"], 5), itl_p95_s=round(itl["p95"], 5),
           itl_p99_s=round(itl["p99"], 5),
           e2e_p95_s=round(snap["requests"]["e2e_s"].get("p95", 0.0), 4),
           queue_wait_p95_s=round(
               snap["requests"]["queue_wait_s"].get("p95", 0.0), 4),
           n_requests=ttft["count"], trace_events=len(tdata["traceEvents"]),
           trace_file=trace_path.name, config=bench_cfg)
    record("serving_prefix",
           prefix_hit_tokens=st_on["prefix_hit_tokens"],
           prefill_skipped=st_on["prefill_skipped"],
           prefill_tokens=st_on["prefill_tokens"],
           prefill_tokens_no_sharing=st_off["prefill_tokens"],
           total_prompt_tokens=total_prompt,
           prefix_hits=st_on["prefix_hits"], cow_copies=st_on["cow_copies"],
           prefix_evictions=st_on["prefix_evictions"],
           shared_requests=sum(shared_flags), n_requests=n_shared_req,
           elapsed_on_s=round(dt_on, 2), elapsed_off_s=round(dt_off, 2),
           config={"smoke": smoke, "prefix_blocks": prefix_blocks,
                   "block_size": block_size, "tail_range": list(tail_range),
                   "slots": SLOTS, "token_identical_vs_off": True})
    # Wall-clock assertions only on the full trace: the 8-request --smoke run
    # on a shared CI box is timing-noise territory (the smoke still gates
    # functional regressions by running the whole path; the deterministic
    # mixed-step property is covered by tests/test_serving.py).
    if not smoke:
        assert paged_tps > ring_tps, (
            f"continuous batching must beat slot-chunked serving on mixed-length "
            f"traffic: paged {paged_tps:.1f} <= ring {ring_tps:.1f} tok/s"
        )
        # the tentpole property: admissions overlap decode inside one jitted
        # step (the PR-1 scheduler serialized every prefill chunk at batch=1)
        assert st["mixed_steps"] > 0, "no packed step mixed prefill with decode"

    run_overhead_phase(model, qparams, spec, cache_len, smoke)
    run_kernel_route_phase(model, qparams, spec, smoke)
    run_speculative_phase(smoke)
    run_outlier_phase(smoke)
    run_heterogeneous_phase(smoke)
    run_quality_phase(smoke)


def run_heterogeneous_phase(smoke: bool) -> None:
    """Per-layer cache policies under traffic: the SAME decode-heavy trace
    served by the SWA stack (``windowed_paged`` policies — out-of-window
    blocks freed as decode advances) and by the same weights with the
    window lifted to full attention (``paged_kv`` policies — history
    pinned). Records decode tokens/s and peak live blocks per sequence for
    both. The block-release cap is asserted (it is the memory headline and
    deterministic); throughput is recorded, not asserted — CPU smoke wall
    time is noise."""
    from repro.serving.paged_cache import windowed_block_cap

    cfg = get_smoke_config("h2o_danube_1_8b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    # same weights, window lifted: sliding_window=0 flips every layer's
    # policy from windowed_paged to paged_kv (outputs differ — full
    # attention sees more history; this phase compares resources, not
    # tokens)
    full_model = build(dataclasses.replace(cfg, sliding_window=0))

    n_req = 6 if smoke else 16
    budget = (24, 33) if smoke else (48, 81)
    rng = np.random.RandomState(11)
    # short prompts + budgets well past the window: steady-state decode is
    # where windowed release pays
    traces = [Trace(list(rng.randint(1, cfg.vocab_size, rng.randint(6, 13))),
                    int(rng.randint(*budget)), float(t))
              for t in np.cumsum(rng.exponential(0.03, n_req))]
    bs = 16
    cache_len = 16 + budget[1] + bs
    mk = lambda m: ServingEngine(
        m, params,
        ServeConfig(cache_len=cache_len, cache_dtype="float32",
                    quantized=False, paged=True, block_size=bs,
                    prefill_chunk=16),
        batch_slots=4)
    swa, full = mk(model), mk(full_model)
    warm = [t.prompt for t in traces[:2]]
    swa.generate(warm, max_new_tokens=2)
    full.generate(warm, max_new_tokens=2)
    for eng in (swa, full):
        eng.telemetry.reset()

    swa_tps, _, _ = run_paged(swa, traces)
    full_tps, _, _ = run_paged(full, traces)
    cap = windowed_block_cap(cfg.sliding_window, bs)
    swa_peak = swa.stats["peak_live_blocks_per_seq"]
    full_peak = full.stats["peak_live_blocks_per_seq"]
    assert swa_peak <= cap, (
        f"windowed release broke its cap: {swa_peak} > {cap}"
    )
    assert full_peak > cap, (
        "full attention pinned fewer blocks than the windowed cap — the "
        "trace never decoded past the window, phase measures nothing"
    )
    print(f"swa_on,{swa_tps:.1f},-,-,peak_live_blocks={swa_peak} cap={cap}")
    print(f"swa_as_full,{full_tps:.1f},-,-,peak_live_blocks={full_peak}")
    emit("serving_heterogeneous_tokens_s", 0.0,
         f"SWA {swa_tps:.1f} vs full-attn {full_tps:.1f} tok/s; peak live "
         f"blocks/seq {swa_peak} (cap {cap}) vs {full_peak}")
    record("serving_heterogeneous",
           swa_tokens_s=round(swa_tps, 1),
           full_attn_tokens_s=round(full_tps, 1),
           swa_peak_live_blocks_per_seq=swa_peak,
           full_attn_peak_live_blocks_per_seq=full_peak,
           windowed_block_cap=cap,
           config={"smoke": smoke, "arch": "h2o_danube_1_8b",
                   "sliding_window": cfg.sliding_window, "block_size": bs,
                   "n_requests": n_req, "budget_range": list(budget),
                   "slots": 4, "cache_len": cache_len})


def run_kernel_route_phase(model, qparams, spec, smoke: bool) -> None:
    """Serve one trace through both GEMM routes: ``kernel=pallas`` (fused
    Pallas quantize+index-GEMM) vs ``kernel=jnp`` (factorized form).

    The CI gate (runs in --smoke too): outputs are token-identical — index
    selection is bit-equal across routes — and the pallas engine's stats
    prove the kernel path actually compiled in (``lut_kernel_calls > 0``,
    zero fallbacks). Wall time is recorded, not asserted: off-TPU the
    kernel runs in interpret mode and loses to XLA by design (kernel=auto
    picks jnp on CPU for exactly that reason)."""
    import repro.core.kernel_routing as kr
    from repro.core.qlinear import with_kernel_route

    cfg = get_smoke_config("llama3_2_1b")
    trace = make_trace(cfg.vocab_size, seed=11, n_requests=3 if smoke else 8,
                       prompt_range=(8, 32))
    cache_len = 32 + BUDGET_RANGE[1] + 16
    outs, times, calls = {}, {}, {}
    for route in ("jnp", "pallas"):
        eng = ServingEngine(
            model, with_kernel_route(qparams, route),
            ServeConfig.from_spec(spec, cache_len=cache_len, block_size=16,
                                  prefill_chunk=32),
            batch_slots=SLOTS)
        before = kr.snapshot()
        t0 = time.perf_counter()
        for t in trace:
            eng.scheduler.submit(t.prompt, t.budget)
        outs[route] = eng.scheduler.run()
        times[route] = time.perf_counter() - t0
        calls[route] = kr.kernel_calls() - before.get("_kernel_calls", 0)
        st = eng.stats
    assert outs["pallas"] == outs["jnp"], \
        "kernel routing changed greedy outputs"
    assert calls["pallas"] > 0, \
        "kernel=pallas served without routing any projection to the kernel"
    assert calls["jnp"] == 0, "kernel=jnp route leaked onto the Pallas kernel"
    assert st["lut_kernel_calls"] > 0 and st["lut_fallbacks"] == 0, st
    print(f"kernel_route,-,-,-,pallas={times['pallas']:.2f}s "
          f"jnp={times['jnp']:.2f}s kernel_dispatches={calls['pallas']} "
          f"token_identical=True (interpret={jax.default_backend() != 'tpu'})")
    emit("serving_kernel_route", 0.0,
         f"pallas route token-identical to jnp; {calls['pallas']} projections "
         f"routed to the fused kernel, 0 fallbacks")
    record("serving_kernel_route",
           wall_s_pallas=round(times["pallas"], 2),
           wall_s_jnp=round(times["jnp"], 2),
           kernel_dispatches=calls["pallas"],
           fallbacks=st["lut_fallbacks"],
           token_identical=True,
           interpret=jax.default_backend() != "tpu",
           config={"smoke": smoke, "n_requests": len(trace), "slots": SLOTS})


def run_overhead_phase(model, qparams, spec, cache_len: int, smoke: bool) -> None:
    """telemetry="off" vs the histograms-on default on one small trace.

    Telemetry never wraps traced code (identical jaxpr — asserted in
    tests/test_telemetry.py), so any delta is pure host-side bookkeeping
    (~10 us/step measured in isolation). A single cold pass per level is
    dominated by whichever engine runs first paying the process-global
    dispatch-cache warmup, so the trace is replayed interleaved and each
    level keeps its best pass. Reported, not asserted: wall-time deltas on
    a shared CI box sit inside scheduler-loop noise (the < 2% claim is
    checked on the recorded numbers across runs)."""
    cfg = get_smoke_config("llama3_2_1b")
    trace = make_trace(cfg.vocab_size, seed=3, n_requests=4 if smoke else 12,
                       prompt_range=(8, 64))
    engines = {}
    for level in ("metrics", "off"):
        engines[level] = ServingEngine(
            model, qparams,
            ServeConfig.from_spec(spec, cache_len=cache_len, block_size=16,
                                  prefill_chunk=64, telemetry=level),
            batch_slots=SLOTS)
        engines[level].generate([[1, 2, 3]] * SLOTS, max_new_tokens=2)  # jit
    times = {"metrics": [], "off": []}
    for _rep in range(3):
        for level, eng in engines.items():
            eng.telemetry.reset()
            t0 = time.perf_counter()
            for t in trace:
                eng.scheduler.submit(t.prompt, t.budget)
            eng.scheduler.run()
            times[level].append(time.perf_counter() - t0)
    assert engines["off"].stats["packed_steps"] == 0, \
        "telemetry=off must read all-zero legacy stats"
    times = {level: min(ts) for level, ts in times.items()}
    overhead = (times["metrics"] - times["off"]) / times["off"]
    print(f"tel_overhead,-,-,-,metrics={times['metrics']:.3f}s "
          f"off={times['off']:.3f}s overhead={overhead * 100:+.1f}%")
    record("serving_telemetry_overhead",
           wall_s_metrics=round(times["metrics"], 4),
           wall_s_off=round(times["off"], 4),
           overhead_pct=round(overhead * 100, 2),
           config={"smoke": smoke, "n_requests": len(trace), "slots": SLOTS})


def run_speculative_phase(smoke: bool) -> None:
    """Decode-heavy Poisson traffic, speculation off vs on (W3 draft).

    Runs on the TRAINED byte-LM (benchmarks.common.trained_lm): greedy
    verification is token-identical no matter the draft, but the acceptance
    rate — what turns verification into throughput — needs a model whose
    argmaxes are structured, which a random-init model's are not.
    """
    from benchmarks.common import trained_lm
    from repro.serving.speculative import DEFAULT_DRAFT_SPEC, SpeculativeConfig

    cfg, model, params, corpus = trained_lm(300 if smoke else 800)
    tspec = QuantSpec(base=QLinearConfig(detection="none"), kv_dtype="float32")
    qparams = quantize_model(model, params, tspec)
    # the draft: W3 weights per the shipped policy; fp32 draft KV here — the
    # int4-KV default trades CPU quantize/dequant time for HBM bytes, the
    # right trade on TPU but not on a CPU smoke box
    draft_spec = dataclasses.replace(DEFAULT_DRAFT_SPEC,
                                     kv_bits=None, kv_dtype="float32")
    spec_k = 2
    n_req = 6 if smoke else 16
    # decode-heavy by construction: short prompts, long generations (the
    # full trace doubly so — speculation is a steady-state decode property,
    # and admission-time draft catch-up amortizes over the budget)
    budget_range = (16, 32) if smoke else (48, 96)
    rng = np.random.RandomState(7)
    crops = rng.randint(0, len(corpus.tokens) - 24, n_req)
    traces = [Trace(list(map(int, corpus.tokens[c : c + int(rng.randint(8, 20))])),
                    int(rng.randint(*budget_range)),
                    float(t))
              for c, t in zip(crops, np.cumsum(rng.exponential(0.03, n_req)))]
    cache_len = 24 + budget_range[1] + 16

    with tempfile.TemporaryDirectory() as d:
        save_quantized(d, cfg, draft_spec,
                       quantize_model(model, params, draft_spec))
        mk = lambda sp: ServingEngine(
            model, qparams,
            ServeConfig.from_spec(tspec, cache_len=cache_len, block_size=16,
                                  prefill_chunk=32, speculative=sp),
            batch_slots=SLOTS)
        base = mk(None)
        specd = mk(SpeculativeConfig(k=spec_k, draft_artifact=d,
                                     draft_token_budget=16))
    warm = [t.prompt for t in traces[:2]]
    base.generate(warm, max_new_tokens=2)
    specd.generate(warm, max_new_tokens=2)
    for eng in (base, specd):
        eng.telemetry.reset()
    specd.scheduler.draft.steps = 0

    base_tps, _, base_out = run_paged(base, traces)
    spec_tps, _, spec_out = run_paged(specd, traces)
    assert spec_out == base_out, \
        "speculative greedy output diverged from the non-speculative baseline"
    st = specd.stats
    assert st["accepted_tokens"] > 0, "no drafted token was ever accepted"
    assert st["drafted_tokens"] == \
        st["accepted_tokens"] + st["rolled_back_tokens"]
    print(f"spec_off,{base_tps:.1f},-,-,packed_steps={base.stats['packed_steps']}")
    print(f"spec_on,{spec_tps:.1f},-,-,"
          f"speedup={spec_tps / base_tps:.2f}x k={spec_k} "
          f"acceptance={st['acceptance_rate']:.2f} "
          f"drafted={st['drafted_tokens']} accepted={st['accepted_tokens']} "
          f"rolled_back={st['rolled_back_tokens']} "
          f"packed_steps={st['packed_steps']} draft_steps={st['draft_steps']}")
    emit("serving_speculative_tokens_s", 0.0,
         f"speedup={spec_tps / base_tps:.2f}x (spec {spec_tps:.1f} vs "
         f"baseline {base_tps:.1f} tok/s) acceptance={st['acceptance_rate']:.2f}")
    record("serving_speculative",
           tokens_s=round(spec_tps, 1), baseline_tokens_s=round(base_tps, 1),
           speedup=round(spec_tps / base_tps, 2),
           acceptance_rate=round(st["acceptance_rate"], 3),
           drafted_tokens=st["drafted_tokens"],
           accepted_tokens=st["accepted_tokens"],
           rolled_back_tokens=st["rolled_back_tokens"],
           spec_rounds=st["spec_rounds"], draft_steps=st["draft_steps"],
           packed_steps=st["packed_steps"],
           packed_steps_baseline=base.stats["packed_steps"],
           token_identical_vs_baseline=True,
           config={"smoke": smoke, "k": spec_k, "n_requests": n_req,
                   "budget_range": list(budget_range), "slots": SLOTS,
                   "draft_w_bits": draft_spec.base.w_bits,
                   "draft_kv_bits": draft_spec.kv_bits,
                   "served_draft_from_artifact": True})
    # the speculative win is a steady-state decode property; the tiny smoke
    # trace is dominated by admissions + timing noise on shared CI boxes
    if not smoke:
        assert spec_tps > base_tps, (
            f"speculative decoding must beat the non-speculative baseline on "
            f"decode-heavy traffic: {spec_tps:.1f} <= {base_tps:.1f} tok/s "
            f"(acceptance {st['acceptance_rate']:.2f})"
        )


def run_outlier_phase(smoke: bool) -> None:
    """Orizuru online outlier engine on the serving path (ROADMAP item 4).

    Three measurements on the TRAINED byte-LM:

    1. **CE table** — detection none/static/dynamic at A4 plus static/dynamic
       at the A3 tier (static thresholds calibrated from captured
       activations). Asserts the paper's accuracy ordering: dynamic <= none
       at A4 (outlier compensation helps) and A3+dynamic STRICTLY better
       than A3+static — online detection is what makes the 8-entry codebook
       usable (the acceptance criterion).
    2. **Decode tokens/s** — one decode-heavy trace served under each
       detection mode (recorded, not asserted: CPU wall time, and off-TPU
       the Orizuru kernel runs in interpret mode).
    3. **Route identity + counters** — an A3+dynamic engine (target AND
       draft) serves a shared-prefix speculative trace under
       ``detect_kernel=jnp`` vs ``pallas``: greedy tokens must be identical,
       the Orizuru kernel must actually dispatch on the serving hot path
       (``detect_kernel_calls`` delta > 0), and the engine's outlier gauges
       must show detections with ZERO fallbacks — the --smoke CI gates.
    """
    import repro.core.kernel_routing as kr
    from benchmarks.common import capture_activations, eval_ce, trained_lm
    from repro.core.qlinear import with_detect_route
    from repro.serving.speculative import DEFAULT_DRAFT_SPEC, SpeculativeConfig

    cfg, model, params, corpus = trained_lm(300 if smoke else 800)
    calib = capture_activations(model, params, corpus)
    # the paper's per-side budget: d_model=128 -> k=1 per side. The tiny
    # budget is WHERE dynamic detection earns its keep — with one channel
    # per side, picking each token's true extreme (vs a global calibration
    # quantile that leaves mild tokens uncompensated) is the whole game;
    # at generous budgets both modes cover the important channels and the
    # ordering washes out (measured on the trained byte-LM).
    frac = 0.005
    ce_batches = 2 if smoke else 4

    # ---- 1. CE across detection mode x activation tier ---------------------
    combos = {
        "a4_none": QLinearConfig(detection="none"),
        "a4_static": QLinearConfig(detection="static", outlier_frac=frac),
        "a4_dynamic": QLinearConfig(detection="dynamic", outlier_frac=frac),
        "a3_static": QLinearConfig(a_bits=3, detection="static",
                                   outlier_frac=frac),
        "a3_dynamic": QLinearConfig(a_bits=3, detection="dynamic",
                                    outlier_frac=frac),
    }
    ce = {name: eval_ce(model, params, corpus, qc, batches=ce_batches,
                        calib=calib)
          for name, qc in combos.items()}
    for name, v in ce.items():
        print(f"outlier_ce,{name},-,-,ce={v:.4f}")
    assert ce["a4_dynamic"] <= ce["a4_none"] + 1e-6, (
        f"dynamic outlier compensation must not hurt A4 CE: "
        f"{ce['a4_dynamic']:.4f} vs none {ce['a4_none']:.4f}")
    assert ce["a3_dynamic"] < ce["a3_static"], (
        f"A3+dynamic must be strictly better than A3+static on the trained "
        f"LM: {ce['a3_dynamic']:.4f} vs {ce['a3_static']:.4f}")

    # ---- 2. decode tokens/s per detection mode -----------------------------
    n_req = 4 if smoke else 10
    budget_range = (8, 16) if smoke else (24, 48)
    rng = np.random.RandomState(17)
    crops = rng.randint(0, len(corpus.tokens) - 24, n_req)
    traces = [Trace(list(map(int, corpus.tokens[c:c + int(rng.randint(8, 20))])),
                    int(rng.randint(*budget_range)), float(t))
              for c, t in zip(crops, np.cumsum(rng.exponential(0.03, n_req)))]
    cache_len = 24 + budget_range[1] + 16
    tps = {}
    for name in ("a4_none", "a4_static", "a4_dynamic", "a3_dynamic"):
        mspec = QuantSpec(base=combos[name], kv_dtype="float32")
        qp = quantize_model(model, params, mspec, calib=calib)
        eng = ServingEngine(model, qp,
                            ServeConfig.from_spec(mspec, cache_len=cache_len,
                                                  block_size=16,
                                                  prefill_chunk=32),
                            batch_slots=SLOTS)
        eng.generate([traces[0].prompt], max_new_tokens=2)  # warm the jit
        tps[name], _, _ = run_paged(eng, traces)
        print(f"outlier_tps,{name},-,-,tokens_s={tps[name]:.1f}")

    # ---- 3. detect-route identity under prefix sharing + speculation -------
    ospec = QuantSpec(base=combos["a3_dynamic"], kv_dtype="float32")
    oqp = quantize_model(model, params, ospec, calib=calib)
    draft_spec = dataclasses.replace(DEFAULT_DRAFT_SPEC,
                                     kv_bits=None, kv_dtype="float32")
    dqp = quantize_model(model, params, draft_spec, calib=calib)
    block_size = 16
    prefix = list(map(int, corpus.tokens[100:100 + 2 * block_size]))
    n_shared = 4 if smoke else 8
    otrace = []
    for i in range(n_shared):
        c = int(rng.randint(0, len(corpus.tokens) - 24))
        tail = list(map(int, corpus.tokens[c:c + int(rng.randint(6, 14))]))
        otrace.append(Trace(prefix + tail, int(rng.randint(8, 13)), 0.0))
    ocache_len = max(len(t.prompt) for t in otrace) + 13 + block_size
    outs, dts, kcalls = {}, {}, {}
    st = None
    for route in ("jnp", "pallas"):
        eng = ServingEngine(
            model, with_detect_route(oqp, route),
            ServeConfig.from_spec(ospec, cache_len=ocache_len,
                                  block_size=block_size, prefill_chunk=32,
                                  prefix_cache=True,
                                  speculative=SpeculativeConfig(
                                      k=2, draft_token_budget=16)),
            batch_slots=SLOTS,
            draft=(model, with_detect_route(dqp, route), draft_spec))
        before = kr.snapshot()
        outs[route], dts[route] = run_shared_prefix(eng, otrace)
        kcalls[route] = (kr.detect_kernel_calls()
                         - before.get("_detect_kernel_calls", 0))
        st = eng.stats
    assert outs["pallas"] == outs["jnp"], \
        "detection routing changed greedy serving outputs"
    assert kcalls["pallas"] > 0, (
        "detect_kernel=pallas served without dispatching the Orizuru kernel")
    assert kcalls["jnp"] == 0, \
        "detect_kernel=jnp route leaked onto the Orizuru kernel"
    # the --smoke CI gates: detection live on the hot path, zero fallbacks
    assert st["outlier_detect_calls"] > 0 and st["outlier_fallbacks"] == 0, st
    assert (st["outlier_comp_gather"] + st["outlier_comp_scatter"]
            + st["outlier_comp_fused"]) > 0, st
    assert st["prefix_hit_tokens"] > 0, "prefix sharing was not exercised"
    assert st["accepted_tokens"] > 0, "speculation was not exercised"
    print(f"outlier_route,-,-,-,pallas={dts['pallas']:.2f}s "
          f"jnp={dts['jnp']:.2f}s orizuru_dispatches={kcalls['pallas']} "
          f"detect_calls={st['outlier_detect_calls']} "
          f"fallbacks={st['outlier_fallbacks']} "
          f"comp_gather={st['outlier_comp_gather']} "
          f"comp_scatter={st['outlier_comp_scatter']} "
          f"comp_fused={st['outlier_comp_fused']} "
          f"token_identical=True (interpret={jax.default_backend() != 'tpu'})")
    emit("serving_outlier_ce_a3", 0.0,
         f"A3 dynamic {ce['a3_dynamic']:.4f} < static {ce['a3_static']:.4f} "
         f"(A4 none {ce['a4_none']:.4f}, dynamic {ce['a4_dynamic']:.4f})")
    emit("serving_outlier_route", 0.0,
         f"Orizuru route token-identical to lax.top_k; {kcalls['pallas']} "
         f"detections dispatched to the kernel, 0 fallbacks "
         f"(prefix sharing + speculation on, A3 target+draft)")
    record("serving_outlier",
           ce={k: round(v, 4) for k, v in ce.items()},
           tokens_s={k: round(v, 1) for k, v in tps.items()},
           orizuru_dispatches=kcalls["pallas"],
           outlier_detect_calls=st["outlier_detect_calls"],
           outlier_kernel_calls=st["outlier_kernel_calls"],
           outlier_jnp_calls=st["outlier_jnp_calls"],
           outlier_fallbacks=st["outlier_fallbacks"],
           comp_gather=st["outlier_comp_gather"],
           comp_scatter=st["outlier_comp_scatter"],
           comp_fused=st["outlier_comp_fused"],
           token_identical=True,
           a3_dynamic_beats_a3_static=True,
           config={"smoke": smoke, "outlier_frac": frac,
                   "ce_batches": ce_batches, "n_requests": n_req,
                   "route_trace_requests": n_shared, "slots": SLOTS,
                   "prefix_sharing": True, "speculative_k": 2,
                   "a3_bits": 3, "detect_routes": ["jnp", "pallas"]})


def run_quality_phase(smoke: bool) -> None:
    """Quantization-numerics observability on the serving path (this PR).

    Four measurements on the TRAINED byte-LM, quantized W4/A4 + dynamic
    Orizuru outliers (so every probe family has something to measure):

    1. **Overhead** — the same decode trace served at telemetry="metrics"
       vs "quality" at the DEFAULT 1/16 probe sampling, interleaved, 3 reps,
       best pass each. Asserted: quality <= metrics * 1.10 + 50 ms — the
       probes ride a separately-jitted sampled step, so the budget is one
       extra (unrolled) forward every 16 steps plus host-side ingestion.
    2. **Gauge population** (the --smoke CI gates) — a probe-every-step
       engine (sample_every=1, shadow_every=4, calibration stats captured
       from the model itself) must populate per-site codebook-utilization /
       SQNR / outlier-energy-captured / drift gauges and record >= 1
       shadow-reference logit-KL observation.
    3. **Induced drift** — the SAME traffic served against calibration
       stats scale-shifted 3x (live activations then sit ~3x off the
       recorded distribution): the drift gauge must move past the control
       engine and the 0.5 alarm threshold, and the alarm counter must fire.
    4. **Token identity** — the drifted quality engine (probing EVERY step,
       i.e. maximal exposure of the unrolled probed path) must produce
       greedy tokens identical to a telemetry="off" engine: observation
       never perturbs serving numerics.
    """
    from benchmarks.common import capture_activations, trained_lm
    from repro.core import numerics as nx
    from repro.serving.telemetry import TelemetryConfig

    cfg, model, params, corpus = trained_lm(300 if smoke else 800)
    spec = QuantSpec(base=QLinearConfig(detection="dynamic", outlier_frac=0.005),
                     kv_dtype="float32")
    calib = capture_activations(model, params, corpus)
    qparams = quantize_model(model, params, spec, calib=calib)
    calib_stats = {t: nx.activation_stats(a) for t, a in calib.items()}

    n_req = 4 if smoke else 10
    budget_range = (12, 24) if smoke else (24, 48)
    rng = np.random.RandomState(23)
    crops = rng.randint(0, len(corpus.tokens) - 24, n_req)
    traces = [Trace(list(map(int, corpus.tokens[c:c + int(rng.randint(8, 20))])),
                    int(rng.randint(*budget_range)), float(t))
              for c, t in zip(crops, np.cumsum(rng.exponential(0.03, n_req)))]
    cache_len = 24 + budget_range[1] + 16
    mk = lambda tel, **kw: ServingEngine(
        model, qparams,
        ServeConfig.from_spec(spec, cache_len=cache_len, block_size=16,
                              prefill_chunk=32, telemetry=tel),
        batch_slots=SLOTS, **kw)

    # ---- 1. overhead at the default 1/16 sampling --------------------------
    engines = {"metrics": mk("metrics"), "quality": mk("quality")}
    for eng in engines.values():  # warm: compiles the probed step too (step 0)
        eng.generate([traces[0].prompt] * 2, max_new_tokens=2)
    times = {k: [] for k in engines}
    for _rep in range(3):
        for level, eng in engines.items():
            eng.telemetry.reset()
            t0 = time.perf_counter()
            for t in traces:
                eng.scheduler.submit(t.prompt, t.budget)
            eng.scheduler.run()
            times[level].append(time.perf_counter() - t0)
    times = {k: min(v) for k, v in times.items()}
    overhead = (times["quality"] - times["metrics"]) / times["metrics"]
    # the probed step costs ~one extra forward, so 1/16 sampling amortizes
    # to <10% wherever compute dominates dispatch (accelerators / full runs).
    # CPU smoke steps are a few ms of host dispatch each, so the extra
    # UNROLLED forward's dispatch floor dominates — gate smoke loosely as a
    # regression canary (catches probe-every-step / recompile-per-step bugs)
    # and hold the 10% contract in full runs.
    limit = 2.5 if smoke else 1.10
    assert times["quality"] <= times["metrics"] * limit + 0.05, (
        f"quality probes cost too much at 1/16 sampling: "
        f"{times['quality']:.3f}s vs metrics {times['metrics']:.3f}s "
        f"({overhead * 100:+.1f}%, limit {limit:.0%})")
    print(f"quality_overhead,-,-,-,quality={times['quality']:.3f}s "
          f"metrics={times['metrics']:.3f}s overhead={overhead * 100:+.1f}% "
          f"(sample_every=16, smoke_limit={smoke})")

    # ---- 2 + 3 + 4: gauges / induced drift / token identity ----------------
    qtel = lambda: TelemetryConfig(level="quality", quality_sample_every=1,
                                   quality_shadow_every=4)
    shifted_stats = {
        t: {**st, "mean": st["mean"] / 3.0, "rms": st["rms"] / 3.0,
            "absmax_mean": st["absmax_mean"] / 3.0,
            "absmax_q50": st["absmax_q50"] / 3.0,
            "absmax_q99": st["absmax_q99"] / 3.0,
            "absmax_max": st["absmax_max"] / 3.0}
        for t, st in calib_stats.items()}
    runs = {}
    for name, tel, cs in (("off", "off", None),
                          ("control", qtel(), calib_stats),
                          ("drifted", qtel(), shifted_stats)):
        eng = mk(tel, calib_stats=cs)
        for t in traces:
            eng.scheduler.submit(t.prompt, t.budget)
        runs[name] = (eng, eng.scheduler.run())
    assert runs["drifted"][1] == runs["off"][1] == runs["control"][1], \
        "quality probes changed greedy serving outputs vs telemetry=off"

    snap = runs["control"][0].snapshot()
    g = snap["gauges"]
    util = [v for k, v in g.items()
            if k.startswith("numerics_a_codebook_util.")]
    sqnr = [v for k, v in g.items() if k.startswith("numerics_sqnr_db.")]
    oe = [v for k, v in g.items()
          if k.startswith("numerics_outlier_energy_captured.")]
    drift_g = [v for k, v in g.items() if k.startswith("numerics_drift.")]
    assert util and all(0.0 < v <= 1.0 for v in util), \
        f"codebook-utilization gauges missing/out of range ({len(util)} sites)"
    assert sqnr and max(sqnr) > 0.0, "per-site SQNR gauges not populated"
    assert oe and max(oe) > 0.0, \
        "outlier-energy-captured gauges not populated (dynamic detection on)"
    assert drift_g, "per-site drift gauges not populated"
    kl = snap["histograms"]["numerics_shadow_logit_kl"]
    assert kl["count"] >= 1, "shadow probe recorded no logit-KL observation"

    dsnap = runs["drifted"][0].snapshot()
    d_ctl = g.get("numerics_drift_max", 0.0)
    d_drift = dsnap["gauges"].get("numerics_drift_max", 0.0)
    alarms = dsnap["counters"].get("numerics_drift_alarms", 0)
    assert d_drift > max(1.0, d_ctl), (
        f"3x-shifted calibration stats must move the drift gauge: "
        f"drifted {d_drift:.2f} vs control {d_ctl:.2f}")
    assert alarms > 0, "induced drift raised no alarm"
    print(f"quality_gauges,-,-,-,sites={len(util)} "
          f"mean_util={sum(util) / len(util):.2f} "
          f"mean_sqnr={sum(sqnr) / len(sqnr):.1f}dB "
          f"outlier_energy_max={max(oe):.3f} shadow_kl_n={kl['count']} "
          f"top1={g.get('numerics_shadow_top1_agreement', -1):.2f}")
    print(f"quality_drift,-,-,-,control_max={d_ctl:.2f} "
          f"drifted_max={d_drift:.2f} alarms={alarms} token_identical=True")
    emit("serving_quality_overhead", 0.0,
         f"quality {times['quality']:.3f}s vs metrics {times['metrics']:.3f}s "
         f"({overhead * 100:+.1f}% at 1/16 sampling)")
    emit("serving_quality_drift", 0.0,
         f"induced 3x drift: gauge {d_drift:.2f} (control {d_ctl:.2f}), "
         f"{alarms} alarms, greedy tokens identical to telemetry=off")
    record("serving_quality",
           wall_s_quality=round(times["quality"], 4),
           wall_s_metrics=round(times["metrics"], 4),
           overhead_pct=round(overhead * 100, 2),
           probed_sites=len(util),
           mean_codebook_util=round(sum(util) / len(util), 4),
           mean_sqnr_db=round(sum(sqnr) / len(sqnr), 2),
           outlier_energy_max=round(max(oe), 4),
           shadow_kl_count=kl["count"],
           shadow_kl_p50=round(kl.get("p50", 0.0), 8),
           shadow_top1_agreement=g.get("numerics_shadow_top1_agreement"),
           shadow_token_agreement=g.get("numerics_shadow_token_agreement"),
           drift_max_control=round(d_ctl, 4),
           drift_max_drifted=round(d_drift, 4),
           drift_alarms_control=snap["counters"].get("numerics_drift_alarms", 0),
           drift_alarms_drifted=alarms,
           token_identical_vs_off=True,
           config={"smoke": smoke, "n_requests": n_req,
                   "budget_range": list(budget_range), "slots": SLOTS,
                   "sample_every_overhead": 16, "sample_every_gates": 1,
                   "shadow_every_gates": 4, "drift_shift": 3.0,
                   "detection": "dynamic", "outlier_frac": 0.005})


if __name__ == "__main__":
    # Standalone entry (CI smoke) writes the same BENCH json run.py would,
    # so the records + trace pointer are uploadable as workflow artifacts.
    from benchmarks import common
    from benchmarks.run import _write_result

    _t0 = time.time()
    run(smoke="--smoke" in sys.argv[1:])
    _write_result("bench_serving", True, time.time() - _t0,
                  list(common.RECORDS))
