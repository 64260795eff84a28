"""Device milliseconds per packed-step run under the program scope
compensate: the Orizuru outlier residuals, the weight-row gather or
scatter and the index unpack (profile, device plane, scopes from the packed
step's HLO metadata)."""

from perfbench.program import scope_ms


def read(run):
    return scope_ms(run, "compensate", __file__)
