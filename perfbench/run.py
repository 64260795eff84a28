"""Benchmark entry point: one run of one cell on the chip it is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints the result as one JSON object on the last line of standard output,
and the numbers the correctness check compared, each beside its limit, as
the last lines of standard error. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for, or when the
benchmark's own files are missing.

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when that is set, else at ``perfbench/.jax_cache`` in this checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from perfbench.discover import BenchError
    from perfbench.harness import run_cell

    log = lambda s: print(f"[perfbench] {s}", file=sys.stderr, flush=True)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START, log=log)
    except BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
