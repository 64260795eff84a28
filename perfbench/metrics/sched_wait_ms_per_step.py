"""Device-idle milliseconds per gap between packed-step runs that the host
spends in sched.wait after the device has finished the step: the runtime's
completion round trip before sampling can start (program span sched.wait on
the profile's host plane)."""

from perfbench.program import phase_ms


def read(run):
    return phase_ms(run, ("sched.wait",), __file__)
