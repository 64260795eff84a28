"""Faults planted under the timed path, to show that the correctness check
catches them: the packed step returning the cache state it was given, half
of the batch left out, and a served token altered where it is sampled.
Used by the benchmark's tests and by ``check_control.py``; never by a run.
"""

from __future__ import annotations

import contextlib


def _kv_unchanged(make):
    def factory(model):
        inner = make(model)

        def packed(params, pools, *args):
            _, logits, extras = inner(params, pools, *args)
            return pools, logits, extras
        return packed
    return factory


def _half_batch(make):
    def factory(model):
        inner = make(model)

        def packed(params, pools, bt, slot_ids, positions, ctx, tokens):
            half = positions.shape[0] // 2
            positions = positions.at[half:].set(-1)
            pools, logits, extras = inner(params, pools, bt, slot_ids, positions, ctx, tokens)
            return pools, logits.at[half:].set(0.0), extras
        return packed
    return factory


FAULTS = ("kv_unchanged", "half_batch", "token_altered")


@contextlib.contextmanager
def planted(fault: str | None):
    """Plants ``fault`` in the serving program for the duration."""
    import repro.serving.scheduler as sched

    saved = sched.make_packed_fn, sched.Scheduler._sample
    if fault == "kv_unchanged":
        sched.make_packed_fn = _kv_unchanged(sched.make_packed_fn)
    elif fault == "half_batch":
        sched.make_packed_fn = _half_batch(sched.make_packed_fn)
    elif fault == "token_altered":
        sample = sched.Scheduler._sample
        sched.Scheduler._sample = lambda self, lg, r: (sample(self, lg, r) + 1) % lg.shape[-1]
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    try:
        yield
    finally:
        sched.make_packed_fn, sched.Scheduler._sample = saved
