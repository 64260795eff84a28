"""Device milliseconds per packed-step run under the program scope kv_write:
the int4 K-Means KV quantize and the paged pool write (profile, device
plane, scopes from the packed step's HLO metadata)."""

from perfbench.program import scope_ms


def read(run):
    return scope_ms(run, "kv_write", __file__)
