"""Device milliseconds per run of the jitted packed step (profiler trace, XLA Modules line)."""

from perfbench.readers import PACKED_STEP


def read(run):
    if run.trace is None:
        return None
    d = run.trace.module(PACKED_STEP)
    return 1e3 * sum(d) / len(d) if d else None
