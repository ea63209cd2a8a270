"""Serving example: batched requests through the sharded continuous-batching
runtime (scheduler -> N engine workers -> reclaimer) whose KV blocks are
reclaimed by a pluggable SMR policy (the paper's techniques as the framework
feature).

    PYTHONPATH=src python examples/serve_paged.py                      # EpochPOP pool
    PYTHONPATH=src python examples/serve_paged.py --engines 2          # sharded runtime
    PYTHONPATH=src python examples/serve_paged.py --engines 2 --prefix-cache
    PYTHONPATH=src python examples/serve_paged.py --smr HazardPtrPOP   # any registry scheme
    PYTHONPATH=src python examples/serve_paged.py --smr EBR
    PYTHONPATH=src python examples/serve_paged.py --smr EpochPOP --sim-backend vec
    PYTHONPATH=src python examples/serve_paged.py \
        --prefill-workers 2 --prefill-chunk 16   # async chunked prefill stage
    PYTHONPATH=src python examples/serve_paged.py --engines 2 \
        --trace /tmp/serve.json --metrics        # Perfetto trace + histograms
    PYTHONPATH=src python examples/serve_paged.py --prefill-workers 2 \
        --sched-policy sjf --preempt-prefill     # SJF + chunk preemption
    PYTHONPATH=src python examples/serve_paged.py --engines 4 \
        --place-policy static --migrate          # migration rescues skew

K/V live physically in the POP-managed block pool's device-resident pages
(runtime/kv_store.py), updated in place by donated writes, and every step
decodes through the Pallas paged-attention kernel (interpret mode on CPU,
compiled Pallas on a TPU; the engine's ``kernel_impl`` says which); a
prefix-cache hit installs NO copies -- the shared pages enter the
request's block table directly.

``--prefill-workers N`` splits prefill out of the decode loop into N
dedicated threads (each a first-class SMR reader slot) running chunked
prefill -- one batched forward per ``--prefill-chunk`` tokens with a pool
safepoint between chunks, so a reclaimer ping landing mid-prefill is
serviced within one chunk instead of one prompt.
"""

import argparse
import time

import jax

from repro.configs.base import ArchConfig, dense_stack
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.obs import Tracer
from repro.runtime.block_pool import BlockPool
from repro.runtime.reclaim import make_policy, supported_schemes
from repro.serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smr", default=None, metavar="SCHEME",
                    help="SMR scheme guarding the block pool: "
                         "'EpochPOP-pool' (native, default) or any of "
                         + ", ".join(supported_schemes()))
    ap.add_argument("--engines", type=int, default=1,
                    help="number of engine worker threads (each its own "
                         "SMR reader; +1 pool slot for the reclaimer)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share page-aligned prompt prefixes across "
                         "requests/engines (blocks retire through SMR)")
    ap.add_argument("--sim-backend", default="gen", choices=("gen", "vec"),
                    help="simulator backend for --smr schemes: 'gen' "
                         "(discrete-event reference) or 'vec' (batch-stepped "
                         "numpy arrays, ~5-10x faster)")
    ap.add_argument("--prefill-workers", type=int, default=0, metavar="N",
                    help="dedicated async-prefill threads (0 = prefill runs "
                         "inline in the decode loop, still chunked)")
    ap.add_argument("--prefill-chunk", type=int, default=16, metavar="C",
                    help="prompt tokens per prefill forward; a pool "
                         "safepoint between chunks bounds the ping-delivery "
                         "window during misses")
    ap.add_argument("--sched-policy", default="fifo",
                    choices=("fifo", "sjf", "deadline"),
                    help="prefill-queue ordering: 'fifo' (arrival order), "
                         "'sjf' (shortest remaining prompt first), or "
                         "'deadline' (earliest deadline first, best-effort "
                         "last)")
    ap.add_argument("--preempt-prefill", action="store_true",
                    help="let long prefills yield to shorter queued work at "
                         "chunk boundaries (the same safepoint cadence that "
                         "bounds the ping window); requires "
                         "--prefill-workers >= 1")
    ap.add_argument("--place-policy", default="least-loaded",
                    choices=("least-loaded", "static"),
                    help="decode placement: 'least-loaded' (default) or "
                         "'static' rid-hash (skew-prone; pair with "
                         "--migrate to watch the monitor rescue it)")
    ap.add_argument("--migrate", action="store_true",
                    help="start the migration monitor: queued requests move "
                         "off the hottest engine onto the coolest, their KV "
                         "blocks re-homed across engine ids via "
                         "BlockPool.adopt (atomic vs publish-on-ping passes)")
    ap.add_argument("--migrate-threshold", type=int, default=4, metavar="N",
                    help="minimum hot-cool load spread (queued+running) "
                         "before the monitor moves requests")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome-trace/Perfetto JSON of the run: "
                         "request lifecycle spans (queue wait, prefill "
                         "chunks, decode steps, retire) plus SMR ping->"
                         "publish->ack trees and block alloc/free instants; "
                         "open in ui.perfetto.dev")
    ap.add_argument("--metrics", action="store_true",
                    help="print the latency/stall histogram summary "
                         "(TTFT, per-token, queue wait, ping stall)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = ArchConfig(name="serve-demo", d_model=64, n_heads=4, n_kv_heads=2,
                     d_ff=128, vocab=128, groups=dense_stack(2), remat="none",
                     dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    # when tracing the native pool policy, force a publish-on-ping pass
    # every few reclaims: a short demo run rarely builds real pressure, and
    # a trace without ping->publish->ack trees would show nothing of the
    # paper's mechanism.  Simulated schemes ping on their own cadence.
    policy_kw = {}
    if args.trace and args.smr in (None, "EpochPOP-pool"):
        policy_kw["pop_every"] = 2
    tracer = Tracer() if args.trace else None
    pool = BlockPool(128, n_engines=args.engines + args.prefill_workers + 1,
                     reclaim_threshold=8, pressure_factor=2,
                     policy=make_policy(args.smr, backend=args.sim_backend,
                                        **policy_kw))
    eng = ServeEngine(cfg, params, max_batch=4, page_size=8,
                      pool=pool, n_engines=args.engines,
                      prefix_cache=args.prefix_cache,
                      prefill_workers=args.prefill_workers,
                      prefill_chunk=args.prefill_chunk,
                      sched_policy=args.sched_policy,
                      preempt_prefill=args.preempt_prefill,
                      place_policy=args.place_policy,
                      migrate=args.migrate,
                      migrate_threshold=args.migrate_threshold,
                      trace=tracer)
    eng.start()
    t0 = time.time()
    # a hot shared prefix (page-aligned when --prefix-cache) + a unique tail
    prefix = [1, 9, 42, 7, 3, 5, 2, 8]
    reqs = [eng.submit(prefix + [1 + i % 16], max_new=8)
            for i in range(args.requests)]
    unfinished = 0
    for i, r in enumerate(reqs):
        # a failing engine releases its waiters early: done means all tokens
        if not (r.done.wait(timeout=300) and len(r.out) >= r.max_new):
            unfinished += 1
        print(f"req {i}: prompt={r.prompt} -> {r.out}")
    eng.stop()
    pool.evict_prefixes(0)
    pool.policy.flush()
    s = pool.stats
    print(f"\n{len(reqs)} requests in {time.time()-t0:.1f}s | "
          f"engines={args.engines} policy={pool.policy.name} | pool: "
          f"allocated={s.allocated} freed={s.freed} "
          f"retired_peak={s.retired_peak} "
          f"epoch_reclaims={s.epoch_reclaims} pings={s.pings} "
          f"pop_reclaims={s.pop_reclaims} touches={s.touches}")
    if args.prefill_workers:
        print(f"prefill stage: workers={args.prefill_workers} "
              f"chunk={args.prefill_chunk} "
              f"prefilled={sum(pw.requests for pw in eng.prefill_workers)} "
              f"tokens={eng.prefill_tokens} "
              f"max_ping_stall={s.max_ping_stall_s*1e3:.1f}ms")
    sched = eng.scheduler
    if (args.sched_policy != "fifo" or args.preempt_prefill or args.migrate
            or args.place_policy != "least-loaded"):
        print(f"scheduler: policy={args.sched_policy} "
              f"place={args.place_policy} "
              f"reorders={sched.queue_reorders} "
              f"preemptions={sched.preemptions} "
              f"migrations={sched.migrations} "
              f"adopts={s.adopts} stale_handoffs={s.stale_handoffs}")
    if args.prefix_cache:
        actors = eng.workers + eng.prefill_workers
        print(f"prefix cache: hits={s.prefix_hits} misses={s.prefix_misses} "
              f"blocks_saved={s.blocks_saved} evictions={s.prefix_evictions} "
              f"prefill_tokens_skipped="
              f"{sum(w.prefill_tokens_skipped for w in actors)}")
    kv = eng.kv_copy_stats()
    print(f"kv_store={kv['kv_store']} kernel={eng.kernel_impl}: "
          f"bytes-copied/request hit={kv['bytes_per_hit']:.0f} "
          f"miss={kv['bytes_per_miss']:.0f} | physical pool="
          f"{eng.kv_store.nbytes} B (constant), pages poisoned="
          f"{eng.kv_store.poisons} | bytes_h2d={kv['bytes_h2d']}")
    if args.metrics:
        print("\nlatency/stall histograms (merged on read):")
        for name, snap in {**eng.snapshot()["metrics"],
                           **eng.snapshot()["pool_metrics"]}.items():
            if snap["count"]:
                print(f"  {name:22s} n={snap['count']:5d} "
                      f"p50={snap['p50']*1e3:8.2f}ms "
                      f"p99={snap['p99']*1e3:8.2f}ms "
                      f"max={snap['max']*1e3:8.2f}ms")
    if tracer is not None:
        obj = tracer.export(args.trace)
        spans = sum(1 for e in obj["traceEvents"]
                    if e.get("name") == "pop_pass")
        print(f"trace: {len(obj['traceEvents'])} events "
              f"({spans} publish-on-ping passes) -> {args.trace} "
              f"(open in ui.perfetto.dev)")
    if eng.error is not None:
        raise SystemExit(f"ENGINE FAILED: {type(eng.error).__name__}: {eng.error}")
    if unfinished:
        raise SystemExit(f"{unfinished} of {len(reqs)} requests did not finish")
    print("use-after-free: none (hard error if one had occurred)")
    print(f"no leaks: {pool.check_no_leaks()}")


if __name__ == "__main__":
    main()
