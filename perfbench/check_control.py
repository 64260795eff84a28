"""Readings behind the correctness limits of a cell, on the chip.

For each seed: one run of the cell as the benchmark makes it (short
window), giving the program's readings; then, with ``--control 1``, the
control: the serving program with the configuration's ``control``
quantization (one precision step below what the configuration states)
put in its place, teacher-forced over the same prompts and served tokens,
and the reference gap of the token it puts first at every served position.

    python3 perfbench/check_control.py --workload <cell> --seeds 1,2,3 --seconds 15 --control 1

Prints one JSON line per seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]


def control_picks(cfg: dict, seed: int, seqs: list, starts: list, serve: dict) -> list:
    """Tokens the control puts first at each served position of ``seqs``,
    through the serving program's packed step, one token per row."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build
    from repro.serving.speculative import make_packed_fn

    from perfbench import discover
    from perfbench.harness import build_params

    model = build(discover.model_config(cfg))
    spec = discover.quant_spec(cfg["control"])
    qp = build_params(model, cfg, spec, seed)
    bs = serve["block_size"]
    nb = -(-serve["cache_len"] // bs)
    rows = serve["token_budget"]
    step = jax.jit(make_packed_fn(model))
    picks = []
    for s, st in zip(seqs, starts):
        # prefill the whole sequence, ``rows`` tokens a step as the scheduler
        # packs a prompt: same program shape and compensation route
        pools = model.init_caches(1, serve["cache_len"], jnp.dtype(spec.kv_dtype),
                                  quantized=True, layout="paged", block_size=bs, n_blocks=nb)
        first = []
        for c in range(0, len(s), rows):
            pos = np.full((rows, 1), -1, np.int32)
            chunk = s[c: c + rows]
            pos[: len(chunk), 0] = np.arange(c, c + len(chunk))
            tok = np.zeros((rows, 1), np.int32)
            tok[: len(chunk), 0] = chunk
            pools, lg, _ = step(qp, pools, jnp.arange(nb, dtype=jnp.int32)[None],
                                jnp.zeros((rows,), jnp.int32), jnp.asarray(pos),
                                jnp.asarray(np.maximum(pos[:, 0] + 1, 0)), jnp.asarray(tok))
            first += np.asarray(jnp.argmax(lg[: len(chunk), 0], axis=-1)).tolist()
        picks.append(first[st: len(s) - 1])
    return picks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=int, default=15)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--out", default=None)
    ap.add_argument("--fault", default=None, help="plant one of faults.FAULTS in the program")
    args = ap.parse_args()

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(HERE / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from perfbench import discover, faults
    from perfbench.harness import reference_gaps, run_cell

    log = lambda s: print(f"[check_control] {s}", file=sys.stderr, flush=True)
    for seed in (int(x) for x in args.seeds.split(",")):
        keep: dict = {}
        with faults.planted(args.fault):
            line = run_cell(args.workload, seed, args.seconds, False, keep=keep, log=log)
        rec = {"seed": seed, "fault": args.fault,
               "program": {k: v["value"] for k, v in line["checks"].items()},
               "correct": line["correct"], "metrics": line["metrics"],
               "program_gaps": keep["gaps"]}
        gc.collect()
        if args.control:
            bench = discover.load_benchmark()
            cell = discover.workload(bench, args.workload)
            mix = discover.traffic(cell["traffic"])
            cfg = keep["cfg"]
            picks = control_picks(cfg, seed, keep["seqs"], keep["starts"], mix["serve"])
            ctl = reference_gaps(cfg, seed, keep["seqs"], keep["starts"], discover.REPO,
                                 picks=picks)
            rec["control_gaps"] = ctl.pop("all").tolist()
            rec["control"] = ctl
        print(json.dumps(rec), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
