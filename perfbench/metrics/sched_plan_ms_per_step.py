"""Device-idle milliseconds per gap between packed-step runs that the host
spends admitting and planning (program spans sched.admit and sched.plan on
the profile's host plane)."""

from perfbench.program import phase_ms


def read(run):
    return phase_ms(run, ("sched.admit", "sched.plan"), __file__)
