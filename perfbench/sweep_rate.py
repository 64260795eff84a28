"""Finds the highest arrival rate an open-loop cell sustains, on the chip.

Builds the cell's engine once and compiles it with the run's throwaway
request, then offers each rate in turn: the cell's own schedule of lengths
and arrivals (its traffic file with only ``rate_rps`` changed), its own
``warmup_s`` of arrivals and a window of the benchmark's ``run_seconds``,
then drains. A rate is sustained when the backlog (requests sent that have
no first token yet) is no larger at the end of its window than at the
start, give or take ``--slack``. The cell's traffic file then takes about
four fifths of the highest sustained rate, as a number.

    python3 perfbench/sweep_rate.py --workload h2o_danube_1_8b.chat --rates 0.4,0.5,0.6

Prints one JSON line per rate. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--slack", type=int, default=2)
    args = ap.parse_args()

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from perfbench import discover, traffic
    from perfbench.harness import Client, build_engine, window_metrics

    bench = discover.load_benchmark()
    cell = discover.workload(bench, args.workload)
    cfg = discover.config(bench, cell["config"])
    mix = discover.traffic(cell["traffic"])
    warmup, seconds = mix["warmup_s"], bench["run_seconds"]
    _, engine = build_engine(cfg, mix["serve"], args.seed)
    first = True
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, rate_rps=rate)
        drv = Client(engine, traffic.generate(m, args.seed, cfg["vocab_size"]), m, print)
        if first:
            drv.warm()
            first = False
        drv.start()

        def backlog():
            firsts = {drv.rid_of[r] for r in drv.tokens.times if r in drv.rid_of}
            return sum(1 for i in drv.sent if i not in firsts)

        drv.run_until(lambda: time.perf_counter() >= drv.t0 + warmup)
        t_open, b_open = time.perf_counter(), backlog()
        drv.run_until(lambda: time.perf_counter() >= t_open + seconds)
        t_close, b_close = time.perf_counter(), backlog()
        done_in = sum(1 for r in drv.results if t_open <= drv.sent[drv.rid_of[r]])
        # drain: stop sending, finish everything
        drv.mix = dict(m, loop="drain")
        while drv.sched.step(drv.results):
            drv.tokens.stamp(time.perf_counter())
        e2e = window_metrics(drv, t_open, t_close, m)
        print(json.dumps({"rate_rps": rate, "backlog_open": b_open, "backlog_close": b_close,
                          "sustained": b_close <= b_open + args.slack,
                          "finished_in_window": done_in, **e2e}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
