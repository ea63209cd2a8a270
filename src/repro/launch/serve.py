"""Serving launcher: `python -m repro.launch.serve --arch <id>`.

Runs the continuous-batching engine (POP-reclaimed paged KV pool) on the
reduced config with a synthetic request stream and prints pool/reclamation
stats.  An architecture the paged path cannot serve (MLA, windowed, SSM,
MoE, MTP, enc-dec) is refused at engine construction.
"""

from __future__ import annotations

import argparse
import random
import time

import jax

from repro.configs.registry import ARCHS, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.runtime.block_pool import BlockPool
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm_12b", choices=ARCHS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    pool = BlockPool(256, n_engines=1, reclaim_threshold=8)
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8, pool=pool)
    eng.start()
    rng = random.Random(0)
    t0 = time.time()
    reqs = [eng.submit([rng.randrange(1, cfg.vocab) for _ in range(4)],
                       max_new=args.max_new) for _ in range(args.requests)]
    # a request counts as done only with all of its tokens: a failing
    # engine releases its waiters early
    done = sum(r.done.wait(timeout=600) and len(r.out) >= r.max_new
               for r in reqs)
    eng.stop()
    s = pool.stats
    print(f"[launch.serve] {cfg.name}: {done}/{len(reqs)} requests in "
          f"{time.time()-t0:.1f}s | pool freed={s.freed} "
          f"epoch_reclaims={s.epoch_reclaims} pings={s.pings} "
          f"no_leaks={pool.check_no_leaks()}")
    if eng.error is not None:
        raise SystemExit(f"ENGINE FAILED: {type(eng.error).__name__}: "
                         f"{eng.error}")
    if done < len(reqs):
        raise SystemExit(f"{len(reqs) - done} requests did not finish")


if __name__ == "__main__":
    main()
