#!/usr/bin/env python3
"""Readings a cell's correctness limit is set from: runs of the program on
many seeds, in one process so that only the first pays the compiles, each
with the float8 control read on the same sample.  The limit lies above the
largest program reading and below the smallest control reading.

    python3 bench/calibrate.py --workload sc2-decode --seeds 1,2,3 \\
        --seconds 15 --warmup 8

Prints one JSON line per seed: ``gap`` (the program), ``control_gap``,
``correct`` and the run's end-to-end metrics.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--warmup", type=float, default=8.0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench.harness import NoChip, run_cell
    from bench.registry import load_cell

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = load_cell(args.workload)
        if i:
            # this process has compiled every shape by now
            cell.workload = dict(cell.workload, warmup_s=args.warmup)
        t0 = time.monotonic()
        try:
            res = run_cell(cell, seed, args.seconds, False, control=True,
                           t_start=t0,
                           log=lambda s: print(s, file=sys.stderr,
                                               flush=True))
        except NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 2
        print(json.dumps({
            "seed": seed, "gap": res["checks"]["max_logit_gap"]["value"],
            "control_gap": res.get("control_gap"), "correct": res["correct"],
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"],
            "run_s": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
