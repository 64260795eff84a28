"""The traced window in the program's own terms: the scheduler's phase spans
on the host plane, and the named scopes of the packed step's device
operations.

Both come from the profile that a traced run leaves under
``perfbench/.trace`` (the reduced trace of ``harness.Run`` holds only the
device plane). The spans (``sched.admit``, ``sched.plan``,
``sched.dispatch``, ``sched.wait``, ``sched.sample``, ``sched.commit``) are
host-plane events, on the device plane's clock. A device operation's scope
is the ``op_name`` metadata of its HLO instruction in the packed step's
module, which the profiler keeps in its ``/host:metadata`` plane
(serialized ``HloProto``; ``jax.profiler.ProfileData`` does not expose that
plane, so it is read from the file's protobuf wire format here). A profile
of a program without these spans or scopes reads as nothing.

A ``Run`` that carries ``spans`` (name, start ns, duration ns) or
``op_names`` (HLO instruction name -> ``op_name``) is read from those
instead of the file.
"""

from __future__ import annotations

import re
from pathlib import Path

import numpy as np

from perfbench.trace import CONTAINERS, PACKED_STEP

PHASES = ("sched.admit", "sched.plan", "sched.dispatch", "sched.wait",
          "sched.sample", "sched.commit")
_INSTR = re.compile(r"%?([^\s=]+)\s*=")
# what was read from the last profile: every reader of a run reads the same
# file, which takes seconds to parse
_last: dict = {}


def profile_file(reader_file: str) -> Path | None:
    """The traced run's profile, beside the metric reader ``reader_file``
    (``<checkout>/perfbench/metrics/x.py`` -> ``<checkout>/perfbench/.trace``)."""
    return next((Path(reader_file).resolve().parents[1] / ".trace").rglob("*.xplane.pb"),
                None)


def _cached(path: Path, what: str, load):
    key = (str(path), path.stat().st_mtime_ns)
    if _last.get("key") != key:
        _last.clear()
        _last["key"] = key
    if what not in _last:
        _last[what] = load(path)
    return _last[what]


def _load_spans(path: Path) -> list[tuple[str, float, float]]:
    from jax.profiler import ProfileData

    return [(e.name, e.start_ns, e.duration_ns)
            for p in ProfileData.from_file(str(path)).planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events if e.name in PHASES]


def spans(run, reader_file: str) -> list[tuple[str, float, float]]:
    """(name, start ns, duration ns) of the scheduler's phase spans."""
    got = getattr(run, "spans", None)
    if got is not None:
        return [s[:3] for s in got if s[0] in PHASES]
    path = profile_file(reader_file)
    return [] if path is None else _cached(path, "spans", _load_spans)


def _idle(busy: np.ndarray, a: float, b: float) -> list[tuple[float, float]]:
    """Intervals of [a, b) outside the sorted, disjoint ``busy`` intervals."""
    out, t = [], a
    for s, e in busy[(busy[:, 1] > a) & (busy[:, 0] < b)]:
        if s > t:
            out.append((t, min(s, b)))
        t = max(t, e)
    if t < b:
        out.append((t, b))
    return out


def idle_by_span(run, reader_file: str) -> tuple[dict[str, float], int] | None:
    """Device-idle seconds between consecutive packed-step runs, by the
    phase span that covers them ("" for none), and the number of such
    gaps; None without a trace, gaps or phase spans."""
    t = run.trace
    sp = spans(run, reader_file)
    gaps = [] if t is None else t.step_gaps()
    if not gaps or not sp:
        return None
    busy = t.busy_intervals()
    out: dict[str, float] = {}
    for a, b in gaps:
        for u, v in _idle(busy, a, b):
            left = v - u
            for name, s, d in sp:
                o = min(v, s + d) - max(u, s)
                if o > 0:
                    out[name] = out.get(name, 0.0) + o * 1e-9
                    left -= o
            out[""] = out.get("", 0.0) + max(left, 0.0) * 1e-9
    return out, len(gaps)


def phase_ms(run, names: tuple[str, ...], reader_file: str) -> float | None:
    """Device-idle milliseconds per gap between packed-step runs inside the
    spans ``names``."""
    got = idle_by_span(run, reader_file)
    if got is None:
        return None
    idle, n = got
    return 1e3 * sum(idle.get(k, 0.0) for k in names) / n


def _fields(buf: memoryview):
    """(field number, value) of one protobuf message; length-delimited values
    as memoryviews, varints as ints, fixed-width values skipped."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        r = s = 0
        while True:
            c = buf[i]
            i += 1
            r |= (c & 0x7F) << s
            s += 7
            if c < 0x80:
                return r

    while i < n:
        key = varint()
        field, wire = key >> 3, key & 7
        if wire == 0:
            yield field, varint()
        elif wire == 2:
            ln = varint()
            yield field, buf[i: i + ln]
            i += ln
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"protobuf wire type {wire}")


def _load_op_names(path: Path) -> dict[str, str]:
    """HLO instruction name -> op_name over every packed-step module in the
    profile's ``/host:metadata`` plane (XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .bytes_value = 6; HloProto.hlo_module
    = 1; HloModuleProto.computations = 3; HloComputationProto.instructions =
    2; HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2)."""
    out: dict[str, str] = {}
    space = memoryview(path.read_bytes())
    for f, plane in _fields(space):
        if f != 1:
            continue
        fields = list(_fields(plane))
        if not any(k == 2 and bytes(v) == b"/host:metadata" for k, v in fields):
            continue
        stat_names = {}
        for k, entry in fields:
            if k == 5:
                md = dict(_fields(dict(_fields(entry)).get(2, memoryview(b""))))
                stat_names[md.get(1)] = bytes(md.get(2, b"")).decode()
        for k, entry in fields:
            if k != 4:
                continue
            md = list(_fields(dict(_fields(entry)).get(2, memoryview(b""))))
            name = next((bytes(v).decode() for j, v in md if j == 2), "")
            if not name.startswith(PACKED_STEP):
                continue
            for j, stat in md:
                st = dict(_fields(stat)) if j == 5 else {}
                if stat_names.get(st.get(1)) != "Hlo Proto" or 6 not in st:
                    continue
                module = dict(_fields(st[6])).get(1, memoryview(b""))
                for c, comp in _fields(module):
                    for i, instr in (_fields(comp) if c == 3 else ()):
                        if i != 2:
                            continue
                        d = dict(_fields(instr))
                        op = dict(_fields(d.get(7, memoryview(b"")))).get(2, b"")
                        out[bytes(d.get(1, b"")).decode()] = bytes(op).decode()
    return out


def op_names(run, reader_file: str) -> dict[str, str]:
    got = getattr(run, "op_names", None)
    if got is not None:
        return got
    path = profile_file(reader_file)
    return {} if path is None else _cached(path, "op_names", _load_op_names)


def scope_seconds(run, reader_file: str) -> tuple[dict[str, float], int] | None:
    """Device seconds of the packed-step runs' operations by ``op_name``
    (loops and calls that hold other operations left out), and the number
    of runs; None without a trace, runs or scope metadata."""
    t = run.trace
    names = op_names(run, reader_file)
    runs = [] if t is None else t.step_runs()
    if not runs or not names:
        return None
    starts = np.array([a for a, _ in runs])
    ends = np.array([b for _, b in runs])
    out: dict[str, float] = {}
    for name, s, d, _ in t.ops:
        i = np.searchsorted(starts, s, side="right") - 1
        m = _INSTR.match(name)
        if i < 0 or s >= ends[i] or not m or m.group(1).split(".")[0] in CONTAINERS:
            continue
        op = names.get(m.group(1))
        if op is not None:
            out[op] = out.get(op, 0.0) + d * 1e-9
    return out, len(runs)


def scope_ms(run, scope: str, reader_file: str) -> float | None:
    """Device milliseconds per packed-step run of the operations under the
    named scope ``scope`` (a component of their ``op_name``)."""
    got = scope_seconds(run, reader_file)
    if got is None:
        return None
    by_op, n = got
    secs = [v for op, v in by_op.items() if scope in op.split("/")]
    return 1e3 * sum(secs) / n if secs else None
