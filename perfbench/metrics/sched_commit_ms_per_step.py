"""Device-idle milliseconds per gap between packed-step runs that the host
spends on the step's bookkeeping: prefill progress, first tokens, block
registration and release, finishing (program span sched.commit on the
profile's host plane)."""

from perfbench.program import phase_ms


def read(run):
    return phase_ms(run, ("sched.commit",), __file__)
