"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and gives the requests of one run.

Every seed gets the same work: the sequence of (prompt length, output
length) pairs and of inter-arrival gaps is drawn once from the mix's own
``work_seed``, a fixed schedule that every run replays; ``--seed`` draws
the token ids (and, elsewhere, the weights). So two seeds differ in what
the requests say, not in how much there is to do or when it comes: under
open-loop load the order of long and short requests alone moves a
latency tail by more than any change of the program would.

Lengths follow the lognormal-with-clipping and uniform shapes of the
repository's CPU serving benchmark (``benchmarks/bench_serving.make_trace``);
the logic is restated here so that the yardstick does not move with it.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Req:
    prompt: list[int]
    max_new: int
    due: float | None  # seconds after the arrival clock starts; None: closed loop


def draw_lengths(spec: dict, rng: np.random.RandomState, n: int) -> np.ndarray:
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * rng.randn(n))
        return np.clip(np.rint(x), lo, hi).astype(int)
    if spec["dist"] == "uniform":
        return rng.randint(lo, hi + 1, size=n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def seed_rng(seed: int) -> np.random.RandomState:
    """numpy's RandomState from any whole number (it takes 32 bits)."""
    return np.random.RandomState(np.random.SeedSequence(seed).generate_state(4))


def generate(mix: dict, seed: int, vocab: int) -> list[Req]:
    """All ``mix["requests"]`` requests of one run, in the order they are sent."""
    n = mix["requests"]
    work = np.random.RandomState(mix["work_seed"])
    prompts = draw_lengths(mix["prompt"], work, n)
    outputs = draw_lengths(mix["output"], work, n)
    gaps = work.exponential(1.0, size=n)  # unit-mean gaps, scaled by the rate
    due = [None] * n
    if mix["loop"] == "open":
        due = (np.cumsum(gaps) / mix["rate_rps"]).tolist()
    elif mix["loop"] != "closed":
        raise ValueError(f"unknown loop {mix['loop']!r}")
    rng = seed_rng(seed)
    return [Req(prompt=rng.randint(0, vocab, size=int(p)).tolist(), max_new=int(o), due=d)
            for p, o, d in zip(prompts, outputs, due)]
