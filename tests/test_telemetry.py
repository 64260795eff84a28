"""Serving telemetry tests: metric-type semantics, lifecycle timelines with
a fake clock, engine counter assertions against known traffic, Perfetto
export validity, the telemetry-off guard (identical jaxpr + dispatch count),
fallback-engine counters, and the StepMonitor/StreamingStats unification."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_smoke_config
from repro.core.qlinear import QLinearConfig
from repro.core.quantspec import QuantSpec
from repro.models.model import build, quantize_model
from repro.serving.engine import ServeConfig, ServingEngine
from repro.serving.paged_cache import BlockAllocator, chain_hash
from repro.serving.speculative import make_packed_fn
from repro.serving.telemetry import (
    NULL_TELEMETRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullTelemetry,
    StreamingStats,
    Telemetry,
    TelemetryConfig,
    linear_buckets,
    log_buckets,
    make_telemetry,
)

QSPEC = QuantSpec(base=QLinearConfig(detection="none"))


@pytest.fixture(scope="module")
def small_lm():
    cfg = get_smoke_config("llama3_2_1b")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params, quantize_model(model, params, QSPEC)


def _fake_clock():
    t = [0.0]

    def clock():
        return t[0]

    return t, clock


# ---------------------------------------------------------------------------
# metric types
# ---------------------------------------------------------------------------

def test_counter_semantics():
    c = Counter("x")
    c.add()
    c.add(4)
    c.add(0.5)  # time totals are float counters
    assert c.value == 5.5
    c.reset()
    assert c.value == 0


def test_gauge_set_max_and_callback():
    g = Gauge("g")
    g.set(3.0)
    g.set_max(2.0)  # lower: ignored
    assert g.value == 3.0
    g.set_max(7.0)
    assert g.value == 7.0
    backing = [1, 2, 3]
    live = Gauge("live", fn=lambda: len(backing))
    assert live.value == 3
    backing.append(4)
    assert live.value == 4  # evaluated lazily, not captured


def test_histogram_observe_and_percentiles():
    h = Histogram("h", [1.0, 2.0, 4.0])
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):  # 100 -> the +inf overflow bucket
        h.observe(v)
    assert h.count == 5 and h.counts == [1, 2, 1, 1]
    assert h.min == 0.5 and h.max == 100.0
    s = h.summary()
    assert s["count"] == 5 and s["sum"] == pytest.approx(106.5)
    # percentiles are interpolated but always clamped to observed min/max
    for q in (0, 50, 95, 99, 100):
        assert h.min <= h.percentile(q) <= h.max
    assert h.percentile(40) <= 2.0  # lands in the (1, 2] bucket


def test_histogram_constant_series_percentile_exact():
    h = Histogram("h", log_buckets(1e-3, 10.0))
    for _ in range(10):
        h.observe(0.25)
    # min == max == 0.25 so clamping makes every percentile exact
    assert h.percentile(50) == pytest.approx(0.25)
    assert h.percentile(99) == pytest.approx(0.25)
    assert h.summary()["count"] == 0 or h.summary()["mean"] == pytest.approx(0.25)


def test_histogram_empty_and_bad_bounds():
    h = Histogram("h", [1.0, 2.0])
    assert h.percentile(95) == 0.0 and h.summary() == {"count": 0}
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", [2.0, 1.0])


def test_bucket_helpers():
    lb = log_buckets(1e-3, 1e3, per_decade=2)
    assert lb[0] == pytest.approx(1e-3) and lb[-1] == pytest.approx(1e3)
    assert all(b > a for a, b in zip(lb, lb[1:]))
    assert linear_buckets(0.0, 1.0, 4) == pytest.approx([0.25, 0.5, 0.75, 1.0])
    with pytest.raises(ValueError):
        log_buckets(1.0, 0.5)
    with pytest.raises(ValueError):
        linear_buckets(0.0, 1.0, 0)


def test_registry_get_or_create_and_snapshot():
    r = MetricsRegistry()
    assert r.counter("a") is r.counter("a")  # one object per name
    r.counter("a").add(3)
    r.gauge("g").set(1.5)
    r.histogram("h", [1.0]).observe(0.5)
    snap = r.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    r.reset()
    assert r.counter("a").value == 0 and r.histogram("h").count == 0


# ---------------------------------------------------------------------------
# config + null object
# ---------------------------------------------------------------------------

def test_telemetry_config_parse():
    assert TelemetryConfig.parse(None).level == "off"
    assert TelemetryConfig.parse(False).level == "off"
    assert TelemetryConfig.parse(True).level == "metrics"
    assert TelemetryConfig.parse("trace").level == "trace"
    cfg = TelemetryConfig(level="metrics", step_ring=8)
    assert TelemetryConfig.parse(cfg) is cfg
    with pytest.raises(ValueError, match="level"):
        TelemetryConfig(level="verbose")
    with pytest.raises(ValueError):
        TelemetryConfig(step_ring=0)
    with pytest.raises(TypeError):
        TelemetryConfig.parse(42)


def test_make_telemetry_levels():
    assert make_telemetry("off") is NULL_TELEMETRY
    assert make_telemetry(None) is NULL_TELEMETRY
    assert isinstance(make_telemetry("metrics"), Telemetry)
    assert make_telemetry("trace").tracing
    with pytest.raises(ValueError, match="NullTelemetry"):
        Telemetry(TelemetryConfig(level="off"))


def test_null_telemetry_is_inert(tmp_path):
    n = NullTelemetry()
    n.request_submitted(1, 5)
    n.first_token(1)
    n.tokens_committed(1, 3)
    n.request_finished(1)
    n.step_record(host_s=1, device_s=1, cells=1, budget=1)
    assert n.counter("x").value == 0
    n.counter("x").add(5)
    assert n.counter("x").value == 0  # no-op metric
    assert n.snapshot() == {"level": "off"}
    with n.annotate("span"):
        pass
    p = n.export_chrome_trace(tmp_path / "t.json")
    assert json.loads(p.read_text())["traceEvents"] == []


# ---------------------------------------------------------------------------
# lifecycle timeline semantics (fake clock)
# ---------------------------------------------------------------------------

def test_request_lifecycle_histograms_and_timeline():
    t, clock = _fake_clock()
    tel = Telemetry(TelemetryConfig(level="trace"), clock=clock)
    tel.request_submitted(7, n_prompt=4)
    t[0] = 1.0
    tel.request_admitted(7, prefix_hit_tokens=2)
    t[0] = 2.5
    tel.first_token(7)
    t[0] = 3.5  # a verify round commits 2 tokens simultaneously
    tel.tokens_committed(7, 2)
    t[0] = 4.0
    tel.request_finished(7, n_generated=3)
    assert tel.hist_queue.count == 1 and tel.hist_queue.sum == pytest.approx(1.0)
    assert tel.hist_ttft.count == 1 and tel.hist_ttft.sum == pytest.approx(2.5)
    # ITL amortizes the round over its committed tokens: two samples of 0.5
    assert tel.hist_itl.count == 2 and tel.hist_itl.sum == pytest.approx(1.0)
    assert tel.hist_e2e.sum == pytest.approx(4.0)
    [tr] = tel.completed
    assert tr.t_admit == 1.0 and tr.t_first_token == 2.5 and tr.t_finish == 4.0
    assert tr.n_generated == 3 and tr.prefix_hit_tokens == 2
    names = [name for _, name, _ in tr.events]
    assert names == ["enqueue", "admit", "first_token", "finish"]


def test_readmission_keeps_first_admit_and_ttft_idempotent():
    t, clock = _fake_clock()
    tel = Telemetry(TelemetryConfig(level="metrics"), clock=clock)
    tel.request_submitted(1, 2)
    t[0] = 1.0
    tel.request_admitted(1)
    tel.first_token(1)
    t[0] = 2.0
    tel.request_preempted(1)
    t[0] = 5.0
    tel.request_admitted(1)  # re-admission must not re-observe queue wait
    tel.first_token(1)  # nor TTFT
    assert tel.hist_queue.count == 1 and tel.hist_queue.sum == pytest.approx(1.0)
    assert tel.hist_ttft.count == 1 and tel.hist_ttft.sum == pytest.approx(1.0)
    assert tel.counter("serving_preemptions").value == 1


def test_step_ring_is_bounded():
    tel = Telemetry(TelemetryConfig(level="metrics", step_ring=4))
    for i in range(10):
        tel.step_record(host_s=0.1, device_s=0.2, cells=i, budget=16)
    assert len(tel.steps) == 4
    assert [s["cells"] for s in tel.steps] == [6, 7, 8, 9]  # newest kept
    assert tel.hist_step_util.count == 10  # histograms see every step


def test_telemetry_reset_clears_everything():
    tel = Telemetry(TelemetryConfig(level="trace"))
    tel.request_submitted(1, 3)
    tel.counter("c").add(5)
    tel.step_record(host_s=0.1, device_s=0.1, cells=1, budget=2)
    tel.reset()
    assert tel.counter("c").value == 0
    assert len(tel.steps) == 0 and len(tel._live) == 0
    assert tel.hist_step_util.count == 0


# ---------------------------------------------------------------------------
# StreamingStats / StepMonitor unification
# ---------------------------------------------------------------------------

def test_streaming_stats_window_and_summary():
    s = StreamingStats(window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 5.0):
        s.record(v)
    assert s.times == [2.0, 3.0, 4.0, 5.0]  # windowed
    assert s.median() == pytest.approx(3.5)
    assert s.mean() == pytest.approx(3.5)
    assert s.percentile(95) == 5.0
    assert s.summary()["n"] == 4
    assert StreamingStats().summary() == {}


def test_step_monitor_built_on_streaming_stats():
    from repro.distributed import fault_tolerance as ft

    assert ft.StreamingStats is StreamingStats  # re-export, not a copy
    mon = ft.StepMonitor(window=16, straggler_factor=2.0)
    assert isinstance(mon.stats, StreamingStats)
    for _ in range(12):
        mon.record(0.1)
    assert not mon.is_straggler(0.15)
    assert mon.is_straggler(0.5)
    mon.record(0.5)
    assert mon.straggler_count == 1
    assert mon.summary()["median_s"] == pytest.approx(0.1)
    assert mon.times[-1] == 0.5 and mon.window == 16


# ---------------------------------------------------------------------------
# allocator gauges
# ---------------------------------------------------------------------------

def test_allocator_gauges_and_eviction_counter():
    tel = Telemetry(TelemetryConfig(level="metrics"))
    a = BlockAllocator(3, prefix_cache=True, telemetry=tel)
    got = a.alloc(2)
    g = tel.registry.snapshot()["gauges"]
    assert g["serving_blocks_free"] == 1
    assert g["serving_blocks_live"] == 2
    assert g["serving_blocks_cached"] == 0
    a.register(chain_hash(b"s", [1]), got[0])
    a.free(got)
    g = tel.registry.snapshot()["gauges"]
    assert g["serving_blocks_cached"] == 1 and g["serving_blocks_live"] == 0
    assert a.blocks_allocated == 2 and a.blocks_freed == 2
    a.alloc(3)  # must evict the cached block
    assert tel.counter("serving_block_evictions_pressure").value == 1
    assert a.evictions == 1  # legacy attribute stays in sync


# ---------------------------------------------------------------------------
# engine integration: counters vs known traffic, timelines, Perfetto
# ---------------------------------------------------------------------------

def _mk_engine(model, qp, level, **kw):
    kw.setdefault("cache_len", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 4)
    return ServingEngine(model, qp,
                         ServeConfig(cache_dtype="float32", telemetry=level,
                                     **kw),
                         batch_slots=2)


def test_engine_counters_match_known_traffic(small_lm):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "metrics")
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    snap = eng.snapshot()
    c = snap["counters"]
    assert c["serving_requests_submitted"] == 3
    assert c["serving_requests_finished"] == 3
    assert c["serving_packed_steps"] > 0
    # first token per request comes from prefill logits; the rest decode
    assert c["serving_decode_slot_tokens"] == 3 * (4 - 1)
    # all prompt tokens prefilled (no prefix cache hits on distinct prompts)
    assert c["serving_prefill_tokens"] == sum(len(p) for p in prompts)
    # legacy dict is rebuilt from the same registry
    st = eng.stats
    assert st["packed_steps"] == c["serving_packed_steps"]
    assert st["prefill_tokens"] == c["serving_prefill_tokens"]
    assert snap["requests"]["ttft_s"]["count"] == 3
    assert snap["requests"]["itl_s"]["count"] == 3 * 3  # 3 post-first tokens
    assert snap["steps"]["recorded"] == c["serving_packed_steps"]


def test_engine_prefix_and_cow_counters_mid_run(small_lm):
    """Prefix/COW counters flow through the registry mid-run, matching the
    legacy stats keys exactly."""
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "metrics", block_size=4, prefix_cache=True)
    system = [3, 1, 4, 1, 5, 9, 2, 6]  # two full blocks
    prompts = [system + [40 + i] for i in range(3)]
    eng.generate(prompts, max_new_tokens=3)
    c = eng.snapshot()["counters"]
    # with 2 slots, the first two admit before any blocks are registered;
    # the late-admitted follower aliases the leader's cached system prefix
    assert c["serving_prefix_hits"] >= 1
    assert c["serving_prefix_hit_tokens"] >= len(system)
    st = eng.stats
    assert st["prefix_hits"] == c["serving_prefix_hits"]
    assert st["cow_copies"] == c["serving_cow_copies"]
    g = eng.snapshot()["gauges"]
    # after drain everything is reclaimable again
    assert g["serving_blocks_live"] == 0
    assert g["serving_queue_depth"] == 0 and g["serving_running_requests"] == 0


def test_trace_level_timelines_complete_and_perfetto_valid(small_lm, tmp_path):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "trace")
    prompts = [[1, 2, 3], [4, 5]]
    eng.generate(prompts, max_new_tokens=3)
    tel = eng.telemetry
    assert len(tel.completed) == 2 and not tel._live
    for tr in tel.completed:  # timeline completeness
        assert tr.t_admit is not None
        assert tr.t_first_token is not None
        assert tr.t_finish is not None
        assert tr.t_enqueue <= tr.t_admit <= tr.t_first_token <= tr.t_finish
        assert tr.n_generated == 3
    p = eng.export_chrome_trace(tmp_path / "trace.json")
    data = json.loads(p.read_text())  # valid JSON is the gate
    ev = data["traceEvents"]
    assert isinstance(ev, list) and ev
    for e in ev:
        assert "ph" in e and "pid" in e
        if e["ph"] == "X":
            assert "ts" in e and "dur" in e and e["dur"] >= 0
    # engine packed-step lane + one lane per request
    assert any(e["ph"] == "X" and e["name"] == "packed_step" for e in ev)
    req_tids = {e["tid"] for e in ev if e["pid"] == 1 and e["ph"] == "X"}
    assert len(req_tids) == 2
    assert any(e["name"] == "decode" for e in ev if e["pid"] == 1)


def test_telemetry_off_identical_jaxpr_and_dispatch_count(small_lm):
    """The off guard: telemetry never wraps traced code, so the packed step
    lowers to the identical jaxpr and the scheduler issues exactly the same
    device dispatches with telemetry off as with the metrics default."""
    cfg, model, params, qp = small_lm
    prompts = [[1, 2, 3, 4, 5], [6, 7], [8, 9, 10]]

    def run(level):
        eng = _mk_engine(model, qp, level)
        sched = eng.scheduler
        inner, calls = sched._packed_fn, []
        sched._packed_fn = lambda *a: (calls.append(a), inner(*a))[1]
        out = eng.generate(prompts, max_new_tokens=4)
        return out, calls

    out_off, calls_off = run("off")
    out_on, calls_on = run("metrics")
    assert out_off == out_on  # telemetry never changes scheduling decisions
    assert len(calls_off) == len(calls_on) > 0  # same dispatch count
    # identical jaxpr for the packed step given the same first-call args
    fn = make_packed_fn(model)
    jx = [str(jax.make_jaxpr(fn)(*calls[0])) for calls in (calls_off, calls_on)]
    assert jx[0] == jx[1]


def test_telemetry_off_stats_all_zero(small_lm):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "off")
    eng.generate([[1, 2, 3]], max_new_tokens=3)
    assert eng.telemetry is NULL_TELEMETRY
    st = eng.stats  # legacy keys still exist, all zero, never raising
    assert st["packed_steps"] == 0 and st["preemptions"] == 0
    assert eng.snapshot() == {"level": "off"}


def test_fallback_engine_counters(small_lm):
    cfg, model, params, qp = small_lm
    eng = ServingEngine(model, qp,
                        ServeConfig(cache_len=32, cache_dtype="float32",
                                    paged=False),
                        batch_slots=2)
    prompts = [[1, 2, 3, 4], [5, 6], [7]]  # > slots: two batches, padding
    outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    st = eng.stats
    assert st["prefills"] == 2  # ceil(3 prompts / 2 slots)
    assert st["steps"] == 2 * 3  # (max_new - 1) decode steps per batch
    assert st["tokens"] == 3 * 4  # served tokens count real requests only
    assert st["prompt_tokens"] > 0
    assert 0.0 <= st["pad_fraction"] < 1.0
    assert st["pad_tokens"] == 2 + 0  # [5,6] padded to 4, [7] alone
    # same registry as the paged path
    assert eng.snapshot()["counters"]["serving_fallback_prefills"] == 2


def test_speculative_counters_through_registry(small_lm):
    """A speculative engine's acceptance accounting flows through the
    registry (draft steps, acceptance histogram, per-round counters)."""
    cfg, model, params, qp = small_lm
    from repro.serving.speculative import SpeculativeConfig

    eng = ServingEngine(model, qp,
                        ServeConfig(cache_len=32, cache_dtype="float32",
                                    block_size=8, prefill_chunk=4,
                                    speculative=SpeculativeConfig(k=2),
                                    telemetry="metrics"),
                        batch_slots=2, draft=(model, params))
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=5)
    snap = eng.snapshot()
    c = snap["counters"]
    assert c["serving_spec_rounds"] > 0
    assert c["serving_drafted_tokens"] == \
        c["serving_accepted_tokens"] + c["serving_rolled_back_tokens"]
    assert c["serving_draft_steps"] == eng.scheduler.draft.steps
    h = snap["histograms"]["serving_spec_accepted_per_round"]
    assert h["count"] == c["serving_spec_rounds"]
    assert snap["histograms"]["serving_draft_round_s"]["count"] > 0
    assert c["serving_draft_time_s"] > 0 and c["serving_target_time_s"] > 0


# ---------------------------------------------------------------------------
# scheduler phase spans, device scopes, request stamps, compile counter
# ---------------------------------------------------------------------------

PHASES = ("sched.plan", "sched.dispatch", "sched.wait", "sched.sample",
          "sched.commit")


def test_annotate_carries_args_into_the_span_ring_and_export(tmp_path):
    tel = Telemetry(TelemetryConfig(level="trace"))
    with tel.annotate("sched.plan", step=3):
        pass
    [(name, _, dur, args)] = tel.spans
    assert name == "sched.plan" and dur >= 0 and args == {"step": 3}
    ev = json.loads(tel.export_chrome_trace(tmp_path / "t.json").read_text())
    [span] = [e for e in ev["traceEvents"] if e.get("name") == "sched.plan"]
    assert span["args"] == {"step": 3}
    # below trace level the span is a bare profiler annotation
    quiet = Telemetry(TelemetryConfig(level="metrics"))
    with quiet.annotate("sched.plan", step=3):
        pass
    assert not quiet.spans


def test_scheduler_phase_spans_nest_in_each_packed_step(small_lm):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "trace")
    eng.generate([[1, 2, 3, 4, 5], [6, 7]], max_new_tokens=3)
    spans = list(eng.telemetry.spans)
    steps = [s for s in spans if s[0] == "packed_step"]
    assert [s[3]["step"] for s in steps] == list(range(eng.stats["packed_steps"]))
    for name, t0, dur, args in steps:
        mine = [s for s in spans if s[3].get("step") == args["step"]]
        phases = [s for s in mine if s[0] in PHASES]
        assert [s[0] for s in phases] == list(PHASES)  # once each, in order
        for a, b in zip(phases, phases[1:]):
            assert a[1] + a[2] <= b[1]
        assert all(t0 <= s[1] and s[1] + s[2] <= t0 + dur for s in phases)
        # admission runs before the packed step it feeds
        [admit] = [s for s in mine if s[0] == "sched.admit"]
        assert admit[1] + admit[2] <= t0


def test_step_record_device_time_is_dispatch_plus_wait(small_lm):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "metrics")
    eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
    tel = eng.telemetry
    steps = list(tel.steps)
    assert len(steps) == eng.stats["packed_steps"]
    assert all(s["device_s"] > 0 and s["host_s"] >= 0 for s in steps)
    # the target-time counter is the same dispatch + wait
    assert tel.counter("serving_target_time_s").value == pytest.approx(
        sum(s["device_s"] for s in steps))
    assert not hasattr(TelemetryConfig(), "fence")


def test_completed_request_stamps_at_metrics_level():
    t, clock = _fake_clock()
    tel = Telemetry(TelemetryConfig(level="metrics", max_requests=2), clock=clock)
    for rid in range(3):
        t[0] = 10.0 * rid
        tel.request_submitted(rid, n_prompt=4)
        t[0] += 1.0
        tel.request_admitted(rid)
        t[0] += 2.0
        tel.request_event(rid, "prefill_chunk", start=0, n=2)
        t[0] += 1.0
        tel.request_event(rid, "prefill_chunk", start=2, n=2)
        tel.first_token(rid)
        t[0] += 1.0
        tel.request_finished(rid, n_generated=1)
    assert [tr.rid for tr in tel.completed] == [1, 2]  # bounded, newest kept
    for tr in tel.completed:
        base = 10.0 * tr.rid
        assert (tr.t_enqueue, tr.t_admit, tr.t_first_chunk, tr.t_first_token,
                tr.t_finish) == (base, base + 1, base + 3, base + 4, base + 5)
        assert tr.events is None  # event lists stay a trace-level cost


def test_engine_keeps_request_stamps_at_metrics_level(small_lm):
    cfg, model, params, qp = small_lm
    eng = _mk_engine(model, qp, "metrics")
    eng.generate([[1, 2, 3, 4, 5, 6, 7], [8, 9]], max_new_tokens=3)
    done = list(eng.telemetry.completed)
    assert len(done) == 2
    for tr in done:
        assert tr.t_enqueue <= tr.t_admit <= tr.t_first_chunk <= tr.t_first_token \
            <= tr.t_finish


def test_compile_counter_lives_with_the_engine(small_lm):
    import gc

    from jax._src import monitoring

    cfg, model, params, qp = small_lm
    before = len(monitoring._event_duration_secs_listeners)
    eng = _mk_engine(model, qp, "metrics", cache_len=48)  # shapes not yet compiled
    assert len(monitoring._event_duration_secs_listeners) == before + 1
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    snap = eng.snapshot()["counters"]
    assert snap["serving_compiles"] > 0 and snap["serving_compile_s"] > 0
    assert "serving_compiles " in eng.telemetry.expfmt()
    del eng, snap
    gc.collect()
    assert len(monitoring._event_duration_secs_listeners) == before


def test_scope_map_reads_op_name_metadata():
    from repro.serving.telemetry import scope_map

    text = """
  %fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, calls=%c, metadata={op_type="mul" op_name="jit(packed_step)/layer_scan/while/body/kv_write/mul" source_file="x.py" source_line=3}
  ROOT %custom-call.1 = f32[8]{0} custom-call(f32[8]{0} %fusion.3), metadata={op_name="jit(packed_step)/layer_scan/while/body/lut_gemm/jit(lut_gemm_fused)/pallas_call"}
  %copy.2 = f32[8]{0} copy(f32[8]{0} %p), metadata={op_name="jit(packed_step)/dynamic_slice"}
  %parameter.0 = f32[8]{0} parameter(0)
"""
    assert scope_map(text) == {"fusion.3": "layer_scan/kv_write",
                               "custom-call.1": "layer_scan/lut_gemm",
                               "copy.2": ""}


def test_packed_step_scope_map_on_cpu(small_lm):
    from repro.serving.telemetry import scope_map

    """Every layer of the packed step carries its scope on the compiled
    module's instructions (jnp routes on the CPU)."""
    cfg, model, params, qp_plain = small_lm
    spec = QuantSpec(base=QLinearConfig(detection="dynamic", outlier_frac=0.05),
                     kv_bits=4)
    qp = quantize_model(model, params, spec)
    eng = ServingEngine(model, qp, ServeConfig.from_spec(
        spec, cache_len=32, block_size=8, prefill_chunk=4), batch_slots=2)
    sched = eng.scheduler
    inner, calls = sched._packed_fn, []
    sched._packed_fn = lambda *a: (calls.append(a), inner(*a))[1]
    eng.generate([[1, 2, 3]], max_new_tokens=2)
    text = inner.lower(*calls[0]).compile().as_text()
    scopes = {p.split("/")[-1] for p in scope_map(text).values()}
    assert {"layer_scan", "embed", "norm", "rope", "kv_write", "paged_attention",
            "lut_gemm", "orizuru", "compensate", "mlp_act", "logits"} <= scopes
    # a persistent-cache hit never brings back another revision's scopes
    assert jax.config.jax_compilation_cache_include_metadata_in_key
