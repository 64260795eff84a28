"""The trace -> metric reduction on a small synthetic trace."""

from types import SimpleNamespace as NS

import numpy as np
import pytest

from perfbench import discover, harness, readers, trace


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def planes():
    # two packed steps, 1500..4500 and 6500..9500 ns; the window runs from
    # the first device operation to the end of the last, 1500..10700
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_packed_step(1)", 1500, 3000),
                                       ev("jit_packed_step(1)", 6500, 3000)]),
        NS(name="XLA Ops", events=[
            ev("%lut_gemm_fused.3 = f32[64,256] custom-call(bf16[64,64] %x)", 1500, 1000), ev("%fusion.3 = f32[64] fusion(f32[64] %y)", 2000, 1000),  # overlap
            ev("%while.5 = (s32[]) while(s32[] %z)", 1500, 3000),  # holds the step's ops
            ev("%paged_attention.9 = f32[64,2] custom-call(s32[8] %t)", 3500, 1000),
            ev("%lut_gemm_fused.3 = f32[64,256] custom-call(bf16[64,64] %x)", 6500, 1000), ev("%paged_attention.9 = f32[64,2] custom-call(s32[8] %t)", 8000, 1500),
            ev("%argmax.1 = s32[] reduce(f32[256] %l)", 10500, 200),
        ]),
    ])
    # host spans, which the reduction does not read
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced_window", 1000, 10000),
        ev("bench.step", 1000, 5000), ev("packed_step", 1200, 300),
        ev("bench.step", 6000, 5000), ev("packed_step", 6200, 300),
        ev("bench.submit", 5900, 100),
        ev("unrelated", 1000, 10000),
    ])])
    return [NS(name="/host:metadata", lines=[]), device, host]


def test_busy_union_and_window():
    t = trace.Trace.from_planes(planes())
    assert t.window_s == pytest.approx(9.2e-6)
    # 1500..4500 (a while and the ops it holds, then paged attention),
    # 6500..7500, 8000..9500, 10500..10700
    np.testing.assert_allclose(t.busy_intervals(), [[1500, 4500], [6500, 7500],
                                                    [8000, 9500], [10500, 10700]])
    assert t.busy_s == pytest.approx((3000 + 1000 + 1500 + 200) * 1e-9)


def test_gap_attribution():
    t = trace.Trace.from_planes(planes())
    idle = t.idle_by_place()
    # 4500..6500 between the steps, 7500..8000 inside the second, 9500..10500
    # after the last
    assert idle == {"between packed steps (host loop)": pytest.approx(2000e-9),
                    "inside a packed step": pytest.approx(500e-9),
                    "outside the packed steps": pytest.approx(1000e-9)}
    assert sum(idle.values()) == pytest.approx(t.window_s - t.busy_s)
    b = t.breakdown()
    ops = dict(b["device_ops"])
    assert ops["paged_attention f32[64,2]"] == pytest.approx(2.5e-6)
    assert not any(k.startswith("while") for k in ops)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_kernel_matching_and_readers():
    t = trace.Trace.from_planes(planes())
    assert t.kernel(readers.LUT_GEMM) == pytest.approx([1e-6, 1e-6])
    assert t.kernel(readers.PAGED_ATTN) == pytest.approx([1e-6, 1.5e-6])
    cfg = {"d_model": 64, "head_dim": 16, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
           "act_fn": "silu", "n_layers": 1, "vocab_size": 256, "sliding_window": 0,
           "quant": {"w_bits": 4, "a_bits": 4, "outlier_frac": 0.005, "kv_bits": 4,
                     "rules": [["mlp/wd", {"w_bits": 8}]]}}
    steps = [(np.array([0, 1]), np.array([[3], [-1]])), (np.array([0, 1]), np.array([[4], [9]]))]
    run = harness.Run(cfg=cfg, serve={"block_size": 16}, trace=t, steps=steps,
                      peaks={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    read = lambda name, r=run: discover.metric_reader(name)(r)
    assert read("packed_step_ms") == pytest.approx(3e-3)
    assert read("device_idle_share") == pytest.approx(100 * (1 - t.busy_s / t.window_s))
    # host time: the device-idle time between the two packed steps (4500..6500)
    assert read("sched_host_ms_per_step") == pytest.approx(2000 * 1e-6)
    assert 0 < read("step_mfu.chat") < 100
    for name in ("lut_gemm_roofline", "paged_attn_roofline"):
        assert read(name) > 0
    # nothing to read: the reader says so, never 0
    assert read("orizuru_roofline") is None
    assert read("step_mfu.chat", harness.Run(cfg=cfg, serve={}, peaks={}, trace=t,
                                        steps=steps[:1])) is None


def test_gaps_between_steps_are_labelled_without_host_spans():
    p = planes()
    with_host = trace.Trace.from_planes(p).idle_by_place()
    p[2].lines[0].events = []
    t = trace.Trace.from_planes(p)
    assert t.step_gaps() == [(4500, 6500)]
    assert t.idle_by_place() == with_host
    assert "between packed steps (host loop)" in with_host
