#!/usr/bin/env python3
"""Run one cell of the serving benchmark once, on the chip this process
finds::

    python3 bench/run.py --workload sc2-chat --seed 7 --seconds 50 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness check
compared, beside its limit.  Progress and the checks go to standard error.

Exits non-zero, and prints no result, when JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache is kept at
``<checkout>/.jax_cache``, whatever the environment says, so only a cell's
first run in a checkout compiles.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench.harness import NoChip, run_cell
    from bench.registry import load_cell

    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    cell = load_cell(args.workload)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START, log=log)
    except NoChip as e:
        log(f"no result: {e}")
        return 2
    for name, c in res["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
