"""Device-idle milliseconds per gap between packed-step runs that the host
spends reading and sampling the logits (program span sched.sample on the
profile's host plane)."""

from perfbench.program import phase_ms


def read(run):
    return phase_ms(run, ("sched.sample",), __file__)
