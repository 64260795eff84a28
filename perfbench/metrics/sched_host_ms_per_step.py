"""Host milliseconds per scheduler step in which the device waits: the
device-idle time between consecutive runs of the packed step, averaged
(profiler trace, device plane). The scheduler's loop is synchronous (it
reads each step's logits before it plans the next), so this is the host
work of one step: sampling, bookkeeping, planning, dispatch."""


def read(run):
    t = run.trace
    if t is None:
        return None
    gaps = t.step_gaps()
    if not gaps:
        return None
    idle = [(b - a) * 1e-9 - t.busy_within(a, b) for a, b in gaps]
    return 1e3 * sum(idle) / len(idle)
