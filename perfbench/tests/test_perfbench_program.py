"""The program-span and program-scope readers on synthetic traces: host
spans on the device clock between two packed steps, scope metadata of the
packed step's instructions, and both read back from a profile file."""

from types import SimpleNamespace as NS

import pytest

from perfbench import discover, program, trace

READERS = ("sched_plan_ms_per_step", "sched_dispatch_ms_per_step",
           "sched_wait_ms_per_step", "sched_sample_ms_per_step",
           "sched_commit_ms_per_step")


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=stats)


def device_trace():
    # two packed steps, 1500..4500 and 6500..9500, and a sampling op in the
    # gap (5000..5200): the device idles 4500..5000 and 5200..6500
    return trace.Trace.from_planes([NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev("jit_packed_step(1)", 1500, 3000),
                                       ev("jit__argmax(2)", 5000, 200),
                                       ev("jit_packed_step(1)", 6500, 3000)]),
        NS(name="XLA Ops", events=[
            ev("%fusion.3 = f32[40960] fusion(f32[64] %y)", 1500, 1000),
            ev("%while.5 = (s32[]) while(s32[] %z)", 1500, 3000),
            ev("%paged_attention.9 = f32[64,2] custom-call(s32[8] %t)", 3500, 1000),
            ev("%fusion.1 = s32[] reduce(f32[256] %l)", 5000, 200),  # another module
            ev("%fusion.3 = f32[40960] fusion(f32[64] %y)", 6500, 1500),
            ev("%select.2 = f32[64,2560] select(f32[64] %y)", 8000, 500),
            ev("%paged_attention.9 = f32[64,2] custom-call(s32[8] %t)", 8500, 1000),
        ]),
    ])])


# the scheduler's spans, on the device's clock: step 0's dispatch and wait
# hold its packed step; its sample and commit, then step 1's admit, plan and
# dispatch, lie in the gap
SPANS = [
    ("sched.plan", 1000, 200), ("sched.dispatch", 1200, 200), ("sched.wait", 1400, 3150),
    ("sched.sample", 4550, 750), ("sched.commit", 5300, 300),
    ("sched.admit", 5600, 300), ("sched.plan", 5900, 400), ("sched.dispatch", 6300, 300),
    ("sched.wait", 6600, 3000), ("packed_step", 1000, 4600),
]

OPS = {"fusion.3": "jit(packed_step)/layer_scan/while/body/checkpoint/kv_write/searchsorted",
       "paged_attention.9": "jit(packed_step)/layer_scan/while/body/paged_attention/pallas_call",
       "select.2": "jit(packed_step)/layer_scan/while/body/compensate/jit(_where)/select_n",
       "while.5": "jit(packed_step)/layer_scan/while"}


def read(name, run):
    return discover.metric_reader(name)(run)


def test_idle_between_steps_is_labelled_by_the_covering_span():
    run = NS(trace=device_trace(), spans=SPANS)
    idle, n = program.idle_by_span(run, __file__)
    assert n == 1
    assert idle == {"sched.wait": pytest.approx(50e-9), "sched.sample": pytest.approx(550e-9),
                    "sched.commit": pytest.approx(300e-9), "sched.admit": pytest.approx(300e-9),
                    "sched.plan": pytest.approx(400e-9), "sched.dispatch": pytest.approx(200e-9),
                    "": pytest.approx(0)}
    # every idle instant between the steps, once
    assert sum(idle.values()) == pytest.approx(1800e-9)
    # a span the program did not emit leaves its time unlabelled
    idle, _ = program.idle_by_span(NS(trace=run.trace, spans=SPANS[:4] + SPANS[5:]), __file__)
    assert idle[""] == pytest.approx(300e-9) and "sched.commit" not in idle


def test_sched_readers_per_gap():
    run = NS(trace=device_trace(), spans=SPANS)
    got = {name: read(name, run) for name in READERS}
    assert got == {"sched_plan_ms_per_step": pytest.approx(700e-6),
                   "sched_dispatch_ms_per_step": pytest.approx(200e-6),
                   "sched_wait_ms_per_step": pytest.approx(50e-6),
                   "sched_sample_ms_per_step": pytest.approx(550e-6),
                   "sched_commit_ms_per_step": pytest.approx(300e-6)}
    # the phases hold all of the device-idle host time between the steps
    host = read("sched_host_ms_per_step", run)
    assert host == pytest.approx(1800e-6)
    assert sum(got.values()) == pytest.approx(host)


@pytest.mark.parametrize("spans", [[], [("packed_step", 1000, 4600)]])
def test_sched_readers_without_phase_spans_read_nothing(spans):
    run = NS(trace=device_trace(), spans=spans)
    assert all(read(name, run) is None for name in READERS)


def test_scope_readers_per_packed_step():
    run = NS(trace=device_trace(), op_names=OPS)
    # kv_write: 1000 + 1500 ns over two runs; the same instruction name in
    # another module's run (5000..5200) is not the packed step's
    assert read("kv_write_ms_per_step", run) == pytest.approx(1250e-6)
    assert read("compensate_ms_per_step", run) == pytest.approx(250e-6)
    by_op, n = program.scope_seconds(run, __file__)
    assert n == 2 and OPS["while.5"] not in by_op  # loops hold other ops
    assert sum(by_op.values()) == pytest.approx(5000e-9)
    # a program without the scopes, or a trace without their metadata
    assert read("compensate_ms_per_step", NS(trace=run.trace, op_names={
        k: v.replace("compensate/", "") for k, v in OPS.items()})) is None
    assert read("kv_write_ms_per_step", NS(trace=run.trace, op_names={})) is None


# --- the same read back from a profile file --------------------------------

def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def msg(*fields) -> bytes:
    """A protobuf message from (field number, int | str | bytes) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += varint(f << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(f << 3 | 2) + varint(len(v)) + v
    return out


def xspace(spans, ops) -> bytes:
    """An XSpace with a host plane holding ``spans`` and a metadata plane
    holding the packed step's HLO (instruction names -> op_name)."""
    names = sorted({n for n, _, _ in spans})
    host = msg((1, 1), (2, "/host:CPU"),
               (3, msg((1, 1), (2, "python"), (3, 0),
                       *[(4, msg((1, names.index(n) + 1), (2, s * 1000), (3, d * 1000)))
                         for n, s, d in spans])),
               *[(4, msg((1, i + 1), (2, msg((1, i + 1), (2, n))))) for i, n in enumerate(names)])
    instrs = [(2, msg((1, k), (7, msg((1, "op"), (2, v))))) for k, v in ops.items()]
    hlo = msg((1, msg((1, "jit_packed_step"), (3, msg((1, "main"), *instrs)))))
    meta = msg((1, 2), (2, "/host:metadata"),
               (4, msg((1, 7), (2, msg((1, 7), (2, "jit_packed_step(1)"),
                                         (5, msg((1, 3), (6, hlo))))))),
               (4, msg((1, 8), (2, msg((1, 8), (2, "jit__argmax(2)"))))),
               (5, msg((1, 3), (2, msg((1, 3), (2, "Hlo Proto"))))))
    return msg((1, host), (1, meta))


def test_spans_and_scopes_from_the_profile_file(tmp_path):
    reader = tmp_path / "perfbench" / "metrics" / "x.py"
    assert program.profile_file(str(reader)) is None  # no traced run yet
    prof = tmp_path / "perfbench" / ".trace" / "plugins" / "profile" / "1"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(xspace(SPANS, OPS))
    assert program.profile_file(str(reader)) == prof / "host.xplane.pb"
    assert program.op_names(NS(), str(reader)) == OPS
    got = sorted(program.spans(NS(), str(reader)))
    assert got == sorted(s for s in SPANS if s[0] != "packed_step")
    run = NS(trace=device_trace())
    assert program.phase_ms(run, ("sched.sample",), str(reader)) == pytest.approx(550e-6)
    assert program.scope_ms(run, "kv_write", str(reader)) == pytest.approx(1250e-6)
