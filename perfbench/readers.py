"""What the per-layer metric readers in ``metrics/`` share.

Each reader gets a ``harness.Run``: the configuration, the serving
settings, the device peaks, the reduced trace of the traced window and the
per-row inputs (slot ids, positions) of every packed step in it. A reader
that finds nothing to read returns None.

The kernels have no stable names of their own yet. Their device
operations carry the names of the program's jitted kernel wrappers
(``lut_gemm_fused``, ``topk_outlier``, ``quantize_outlier_streaming``) and
of the ``paged_attention`` scope, as the HLO instruction text that names
each event in the trace; the patterns below match those.
"""

from __future__ import annotations

from perfbench import work

PACKED_STEP = r"^jit_packed_step\b"
LUT_GEMM = r"^%lut_gemm(_fused)?[.\s]"
ORIZURU = r"^%(topk_outlier|quantize_outlier_streaming)[.\s]"
PAGED_ATTN = r"^%paged_attention[.\s]"


def steps_match(run) -> bool:
    """The traced packed-step runs are the recorded steps, one for one."""
    return bool(run.steps) and len(run.trace.module(PACKED_STEP)) == len(run.steps)


def share(run, pattern: str, least_per_step) -> float | None:
    """Least seconds of the traced steps (``least_per_step(slot_ids,
    positions)`` summed) over the device seconds of the events matching
    ``pattern``, in percent."""
    if run.trace is None or not steps_match(run):
        return None
    got = run.trace.kernel(pattern)
    if not got:
        return None
    least = sum(least_per_step(slots, pos) for slots, pos in run.steps)
    return 100.0 * least / sum(got)


def step_mfu(run) -> float | None:
    """Model operations of the real tokens of the traced packed steps over
    their device time, as a share of the chip's bf16 peak (read under one
    name per end-to-end metric it moves)."""
    if run.trace is None or not steps_match(run):
        return None
    flops = sum(work.step_flops(run.cfg, pos) for _, pos in run.steps)
    secs = sum(run.trace.module(PACKED_STEP))
    return 100.0 * flops / secs / run.peaks["bf16_flops_per_s"] if secs else None
