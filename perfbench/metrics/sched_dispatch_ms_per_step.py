"""Device-idle milliseconds per gap between packed-step runs that the host
spends dispatching the packed step: input transfers and the jitted call
(program span sched.dispatch on the profile's host plane)."""

from perfbench.program import phase_ms


def read(run):
    return phase_ms(run, ("sched.dispatch",), __file__)
