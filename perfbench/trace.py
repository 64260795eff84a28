"""Reduction of a profiler trace (``.xplane.pb``) of the traced window.

Everything is read from the device plane. Device time comes from its
``XLA Ops`` line (one event per operation; a loop's event holds the events
of its body, so busy time is their union) and its ``XLA Modules`` line (one
event per program run). Each operation's event is named by its HLO
instruction text. The traced window runs from the first to the last device
operation. Host spans are not read: on the chip the host plane of a trace
this long came back without them, so an idle gap is labelled by where it
lies against the runs of the packed step, not by what the host was doing.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np

PACKED_STEP = "jit_packed_step"
# device operations that only hold others (a scan over layers is one while)
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Trace:
    ops: list  # (name, start_ns, dur_ns, hlo_category) on the device
    modules: list  # (name, start_ns, dur_ns)
    t0: float
    t1: float

    @classmethod
    def load(cls, path) -> "Trace":
        from jax.profiler import ProfileData

        return cls.from_planes(ProfileData.from_file(str(path)).planes)

    @classmethod
    def from_planes(cls, planes) -> "Trace":
        """From planes shaped like ProfileData's: ``name``, ``lines`` with
        ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``,
        ``stats``)."""
        ops, modules = [], []
        device = [p for p in planes if p.name.startswith("/device:")]
        if not device:
            raise ValueError("trace holds no device plane")
        for line in device[0].lines:
            if line.name == "XLA Ops":
                for e in line.events:
                    cat = dict(e.stats).get("hlo_category", "")
                    ops.append((e.name, e.start_ns, e.duration_ns, str(cat)))
            elif line.name == "XLA Modules":
                modules += [(e.name, e.start_ns, e.duration_ns) for e in line.events]
        if not ops:
            raise ValueError("trace holds no device operation")
        return cls(ops=ops, modules=modules, t0=min(o[1] for o in ops),
                   t1=max(o[1] + o[2] for o in ops))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def busy_intervals(self) -> np.ndarray:
        """Union of the device operations' intervals."""
        iv = np.array([(s, s + d) for _, s, d, _ in self.ops])
        iv = iv[np.argsort(iv[:, 0])]
        out = [list(iv[0])]
        for s, e in iv[1:]:
            if s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], e)
            else:
                out.append([s, e])
        return np.array(out)

    @property
    def busy_s(self) -> float:
        iv = self.busy_intervals()
        return float((iv[:, 1] - iv[:, 0]).sum()) * 1e-9

    def gaps(self) -> list[tuple[float, float]]:
        """Idle intervals of the device inside the window."""
        iv = self.busy_intervals()
        edges = [self.t0] + [x for pair in iv for x in pair] + [self.t1]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    def step_gaps(self) -> list[tuple[float, float]]:
        """(end, next start) of consecutive runs of the packed step."""
        runs = self.step_runs()
        return [(a[1], b[0]) for a, b in zip(runs, runs[1:])]

    def step_runs(self) -> list[tuple[float, float]]:
        return sorted((s, s + d) for n, s, d in self.modules if n.startswith(PACKED_STEP))

    def gap_label(self, t: float) -> str:
        """Where the idle instant ``t`` lies against the packed step's runs.
        Between two runs the device waits on the scheduler's host loop
        (sampling, bookkeeping, planning, dispatch)."""
        if any(a <= t < b for a, b in self.step_runs()):
            return "inside a packed step"
        if any(a <= t < b for a, b in self.step_gaps()):
            return "between packed steps (host loop)"
        return "outside the packed steps"

    def idle_by_place(self) -> dict[str, float]:
        """Idle seconds of the device, by where the gaps lie (``gap_label``)."""
        out: dict[str, float] = {}
        for a, b in self.gaps():
            label = self.gap_label((a + b) / 2)
            out[label] = out.get(label, 0.0) + (b - a) * 1e-9
        return out

    def kernel(self, pattern: str) -> list[float]:
        """Seconds of each device operation whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d, _ in self.ops if rx.search(n)]

    def module(self, pattern: str) -> list[float]:
        rx = re.compile(pattern)
        return [d * 1e-9 for n, _, d in self.modules if rx.search(n)]

    def busy_within(self, a: float, b: float) -> float:
        """Device-busy seconds inside [a, b)."""
        iv = self.busy_intervals()
        lo, hi = np.clip(iv[:, 0], a, b), np.clip(iv[:, 1], a, b)
        return float((hi - lo).sum()) * 1e-9

    def breakdown(self, top: int = 10) -> dict:
        """Device seconds by operation (its name without the instance
        number, and its result type), leaving out the loops and calls that
        hold other operations; idle seconds by where the gaps lie."""
        by_op: dict[str, float] = {}
        for n, _, d, _ in self.ops:
            m = re.match(r"%?([\w\-]+?)(\.\d+)?\s*=\s*(\S+)", n)
            if m and m.group(1) in CONTAINERS:
                continue
            shape = re.match(r"\(?(\w+\[[^\]]*\])", m.group(3)) if m else None
            key = f"{m.group(1)} {shape.group(1) if shape else ''}".strip() if m else n[:64]
            by_op[key] = by_op.get(key, 0.0) + d * 1e-9
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_place().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}
