"""Serving-side reclamation grid: scheme x engines x eviction pressure over
the SMR-managed block pool, with a dedicated reclaimer thread and an
optional shared-prefix workload (runtime/block_pool.py + runtime/reclaim.py
+ serve/worker.py).

Each engine thread runs the serving runtime's block protocol without the
model math: start_step -> allocate (or acquire a prefix-shared block run)
-> batched reserve over its working set -> touch every reserved block (the
use-after-free tripwire) -> retire/release the oldest request -> end_step.
A first-class Reclaimer thread owns its own engine id and retires/frees
through the pluggable policy, so publish-on-ping passes fan out to all N
engines concurrently -- the paper's multi-reader signal-cost scenario.

Workloads:
  * ``private``       -- every request owns all its blocks (the PR-1 grid);
  * ``shared-prefix`` -- requests draw a prompt prefix from a small hot set;
    with ``prefix_cache=True`` the prefix blocks come from the pool's
    content-keyed cache (refcounted, retired -- not freed -- on last drop)
    instead of fresh allocations.  The cache-off twin of each cell is the
    no-sharing baseline the acceptance criteria compare against.

Metrics: **peak-unreclaimed-blocks** (pool.stats.retired_peak, the paper's
garbage-bound axis) and **per-engine throughput** (steps/s min/mean across
engines -- fairness under ping fan-out), plus blocks allocated per request
for the sharing comparison.

Extra axes ride on the grid:

* **kv_store** -- every row records its KV storage layer: the protocol
  grid moves no KV payload (``kv_store="none"``); the prefill-interference
  rows run real model traffic through the paged serving path
  (``"paged"``).
* **evict_policy** -- the shared-prefix comparison runs the prefix cache
  under plain LRU and under refcount-aware eviction (skip entries with
  live readers) so the two policies are directly comparable.
* **prefill interference** (``run_prefill_interference``) -- the async
  prefill pipeline under a long-prompt + short-decode mix: one long prompt
  arrives with a stream of short requests behind it, and each cell runs
  either **inline** (prefill_workers=0: the decode worker prefills the
  long prompt -- chunked, so pings are still serviced -- before any short
  request admits) or **async** (dedicated prefill workers; shorts decode
  while the long prompt prefills), across a chunk sweep.  Metrics: decode
  tok/s of the short requests (the interference axis) and the per-scheme
  **max-ping-stall** (the worst wall-clock wait a publish-on-ping pass
  spent between pinging the readers and seeing every publish -- bounded by
  one chunk of forward work, not one prompt).

Simulator backend: ``--sim-backend vec`` runs the simulated schemes on the
batch-stepped numpy backend (core/sim/vec.py) instead of the generator
discrete-event engine -- ~5-10x the step throughput, which is what lets
the engines axis extend to 8.  The full grid also emits one
**asymmetric-costs** row per simulated scheme: the upper half of the
engine readers live on a "remote socket" (4x ping/signal delivery
latency, 2x memory latency via ``Costs.asymmetric``), the regime where
publish-on-ping's contrast with fence-per-read is widest.

Every row that runs the real serving engine (prefill-interference)
additionally carries per-request latency distributions from
the obs registry -- ``ttft_{p50,p99,p999,max}_s`` and
``tok_latency_*_s`` -- and every row with an SMR pool carries
``ping_stall_*_s`` / ``reclaim_pass_*_s`` percentiles sourced from the
same locked recorder that feeds ``stats.max_ping_stall_s`` (one write
path, so the scalar and the histogram max cannot diverge).

    PYTHONPATH=src python benchmarks/serve_reclaim.py [--quick] [--engines 2]
    PYTHONPATH=src python benchmarks/serve_reclaim.py --sim-backend vec
    PYTHONPATH=src python benchmarks/serve_reclaim.py --quick --metrics \\
        --trace /tmp/serve_reclaim_trace.json

CSV schema (matched to benchmarks/run.py): ``name,us_per_call,derived``
where name = serve_reclaim:<scheme>:e<engines>:<pressure>
[:shared[+cache]][:asym][@vec], us_per_call is wall microseconds per
engine step, and derived packs peak_unreclaimed/freed/pings/publishes/
alloc_per_req/uaf.
"""

from __future__ import annotations

import argparse
import json
import random
import threading
import time
from pathlib import Path

from repro.core.sim.engine import Costs, UseAfterFree
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import Tracer
from repro.runtime.block_pool import BlockPool, OutOfBlocks
from repro.runtime.reclaim import is_simulated, make_policy
from repro.serve.worker import Reclaimer

#: histogram fields every latency column carries (ttft_p99_s style)
LAT_FIELDS = ("p50", "p99", "p999", "max")

# native EpochPOP pool + a representative slice of the registry
DEFAULT_SCHEMES = ("EpochPOP-pool", "HP", "HE", "EBR", "NBR+",
                   "HazardPtrPOP", "HazardEraPOP", "EpochPOP")
QUICK_SCHEMES = ("EpochPOP-pool", "HazardPtrPOP", "EpochPOP")

PRESSURE = {"low": 48, "high": 16}     # pool blocks per engine thread
N_PREFIXES = 4                         # hot prefix set for shared workload
PREFIX_BLOCKS = 2                      # blocks per shared prefix
PRIVATE_BLOCKS = 2                     # private blocks per shared-wl request


def run_one(scheme: str, n_engines: int, pressure: str = "high",
            workload: str = "private", prefix_cache: bool = False,
            duration: float = 0.5, blocks_per_req: int = 4,
            window: int = 3, seed: int = 0, sim_backend: str = "gen",
            asym: bool = False, evict_policy: str = "lru",
            tracer: "Tracer | None" = None) -> dict:
    """One grid cell: n_engines real reader threads + 1 reclaimer thread."""
    num_blocks = PRESSURE[pressure] * n_engines
    # the native pool policy never touches the simulator; don't stamp its
    # rows with a backend or cost model they didn't use (keeps row names
    # comparable across runs with different --sim-backend)
    if not is_simulated(scheme):
        sim_backend = None
        asym = False
    costs = None
    if asym:
        # upper half of the readers live on a remote "socket": 4x ping
        # delivery latency, 2x memory latency; the reclaimer (engine id
        # n_engines) stays local
        remote = range(n_engines - n_engines // 2, n_engines)
        costs = Costs.asymmetric(n_engines + 1, remote=remote,
                                 ping_factor=4.0, mem_factor=2.0)
    pool = BlockPool(num_blocks, n_engines=n_engines + 1,
                     reclaim_threshold=max(4, num_blocks // 8),
                     pressure_factor=2,
                     policy=make_policy(scheme, backend=sim_backend,
                                        costs=costs))
    if tracer is not None:
        pool.attach_tracer(tracer)
    reclaimer = Reclaimer(pool, engine_id=n_engines, interval_s=0.001,
                          evict_policy=evict_policy)
    stop = threading.Event()
    steps = [0] * n_engines
    requests = [0] * n_engines
    uaf = [0]
    errors = []

    def engine(eid: int):
        rng = random.Random(seed * 1000 + eid)
        live = []          # sliding window: (shared_blocks, private_blocks)
        try:
            while not stop.is_set():
                pool.start_step(eid)
                shared, extra = [], []   # prefix part: shared or private
                n_private = blocks_per_req
                if workload == "shared-prefix":
                    n_private = PRIVATE_BLOCKS
                    key = ("px", rng.randrange(N_PREFIXES))
                    hit = (pool.acquire_prefix(eid, key)
                           if prefix_cache else None)
                    if hit is not None:
                        shared = hit[0]
                    else:
                        try:
                            pfx = pool.allocate(eid, PREFIX_BLOCKS)
                        except OutOfBlocks:
                            if prefix_cache:
                                pool.evict_prefixes(eid, 4,
                                                    policy=evict_policy)
                            pool.reclaim(eid)
                            pool.end_step(eid)
                            continue
                        if prefix_cache and pool.share_prefix(eid, key, pfx):
                            shared = pfx
                        else:
                            extra = pfx   # cache off / lost race: private
                try:
                    priv = pool.allocate(eid, n_private)
                except OutOfBlocks:
                    if shared:
                        pool.release_shared(eid, shared)
                        pool.rollback_prefix_hit(len(shared))
                    if extra:
                        pool.retire(eid, extra)
                    if prefix_cache:
                        pool.evict_prefixes(eid, 4, policy=evict_policy)
                    pool.reclaim(eid)
                    pool.end_step(eid)
                    continue
                live.append((shared, extra + priv))
                requests[eid] += 1
                _touch_and_roll(eid, live)
                pool.end_step(eid)
                steps[eid] += 1
        except UseAfterFree as e:
            uaf[0] += 1
            errors.append(str(e))
        except Exception as e:  # noqa: BLE001
            errors.append(f"{type(e).__name__}: {e}")
        finally:
            for sh, pv in live:
                try:
                    pool.retire(eid, pv)
                    if sh:
                        pool.release_shared(eid, sh)
                except Exception:  # noqa: BLE001 -- teardown best effort
                    pass

    def _touch_and_roll(eid: int, live: list) -> None:
        # batched reader session over the whole working set, then touch
        # every block (a decode step reading its KV pages)
        session = [b for sh, pv in live for b in sh + pv]
        pool.reserve(eid, session)
        pool.touch(eid, session)
        if len(live) > window:
            sh, pv = live.pop(0)
            pool.retire(eid, pv)
            if sh:
                pool.release_shared(eid, sh)

    threads = [threading.Thread(target=engine, args=(i,))
               for i in range(n_engines)]
    t0 = time.perf_counter()
    reclaimer.start()
    for t in threads:
        t.start()
    time.sleep(duration)
    stop.set()
    for t in threads:
        t.join(timeout=30)
    reclaimer.stop()
    elapsed = time.perf_counter() - t0
    total = sum(steps)
    pool.evict_prefixes(0)
    pool.policy.flush()
    s = pool.stats
    per_engine = [n / elapsed for n in steps]
    n_reqs = sum(requests)
    return {
        "scheme": scheme, "engines": n_engines, "pressure": pressure,
        "workload": workload, "prefix_cache": prefix_cache,
        "sim_backend": sim_backend, "asym": asym,
        # the protocol grid moves no KV payload; the prefill-interference
        # rows record "paged" here
        "kv_store": "none", "evict_policy": evict_policy,
        "steps": total, "requests": n_reqs,
        "us_per_step": 1e6 * elapsed / max(total, 1),
        "steps_per_s_per_engine": per_engine,
        "steps_per_s_min": min(per_engine) if per_engine else 0.0,
        "steps_per_s_mean": (sum(per_engine) / len(per_engine)
                             if per_engine else 0.0),
        "peak_unreclaimed": s.retired_peak,
        "freed": s.freed, "allocated": s.allocated,
        "alloc_per_req": s.allocated / max(n_reqs, 1),
        "blocks_saved": s.blocks_saved,
        "prefix_hits": s.prefix_hits, "prefix_evictions": s.prefix_evictions,
        "pings": s.pings, "publishes": s.publishes,
        "reclaimer_passes": reclaimer.passes,
        # publish-on-ping delivery window, as a distribution: sourced from
        # the pool's MetricsRegistry (record_locked on every pass), whose
        # merged max is exactly stats.max_ping_stall_s -- one recorder, no
        # split-brain scalar
        "max_ping_stall_s": s.max_ping_stall_s,
        **pool.metrics.flat(["ping_stall_s", "reclaim_pass_s"],
                            fields=LAT_FIELDS),
        "uaf": uaf[0], "errors": errors[:3],
    }


def run_prefill_interference(schemes=("EpochPOP-pool", "EpochPOP"),
                             chunks=(4, 16), prefill_workers: int = 2,
                             n_short: int = 4, long_len: int = 48,
                             max_new: int = 4, sim_backend: str = "vec",
                             tracer: "Tracer | None" = None) -> list:
    """Long-prompt + short-decode mix through REAL paged model traffic:
    inline vs async prefill at each chunk size.  The short requests'
    decode tok/s is the interference metric (inline prefill stalls them
    behind the whole long prompt; the async stage does not), and every
    cell records the per-scheme max-ping-stall -- the publish-on-ping
    delivery window, which chunked prefill bounds by one chunk of forward
    work.  Asserts the acceptance criteria: zero use-after-free
    everywhere, and -- on the NATIVE-policy rows -- best-chunk async
    short-decode tok/s >= best-chunk inline (per-cell numbers are printed;
    the per-cell comparison at small chunks is GIL-noise-bound on a CPU
    host, where a chunk forward and a decode step cannot truly overlap).
    Simulated-scheme cells gate on UAF only: their every pool op is a
    synchronous simulator drive under a policy-wide lock, so wall-clock
    tok/s mixes protocol cost with host-GIL serialization -- for those
    schemes the simulated clock is the figure of merit (see README) and
    the value of these rows is the stall bound and the fan-out running
    clean."""
    import jax

    from repro.configs.base import ArchConfig, dense_stack
    from repro.models.model import init_params
    from repro.serve.engine import ServeEngine

    page, max_batch = 4, 4
    cfg = ArchConfig(name="pf-bench", d_model=32, n_heads=4, n_kv_heads=2,
                     d_ff=64, vocab=64, groups=dense_stack(2), remat="none",
                     dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    long_prompt = [1 + (i % 40) for i in range(long_len)]
    short = [3, 1, 4, 2]
    rows = []
    for scheme in schemes:
        sb = sim_backend if is_simulated(scheme) else None
        best = {"inline": 0.0, "async": 0.0}
        for chunk in chunks:
            pair = {}
            for mode, n_pw in (("inline", 0), ("async", prefill_workers)):
                # reclaim threshold low enough that publish-on-ping passes
                # fire DURING the long prefill (the stall the metric
                # measures) but not on every retire -- a worker-inline POP
                # pass waits up to one chunk for the prefilling reader's
                # publish, and paying that on every short-request retire
                # would measure reclaim stalls, not prefill interference.
                # The generous ping timeout keeps a mid-chunk ping WAITING
                # for the chunk boundary: interpret-mode chunks take
                # seconds of wall time, and a timed-out pass would report
                # the timeout instead of the true chunk-bounded window
                pool = BlockPool(96, n_engines=1 + n_pw + 1,
                                 reclaim_threshold=8, pressure_factor=2,
                                 ping_timeout_s=60.0,
                                 policy=make_policy(scheme, backend=sb))
                eng = ServeEngine(cfg, params, max_batch=max_batch,
                                  page_size=page, pool=pool, n_engines=1,
                                  prefill_workers=n_pw, prefill_chunk=chunk,
                                  trace=tracer)
                eng.start()
                # warmup outside the clock (kernel tracing / first dispatch)
                eng.submit([9, 9, 9], max_new=1).done.wait(timeout=600)
                eng.metrics.reset()    # compile-time TTFT out of the tail
                t0 = time.perf_counter()
                long_r = eng.submit(long_prompt, max_new=max_new)
                shorts = [eng.submit(short[:-1] + [5 + i], max_new=max_new)
                          for i in range(n_short)]
                for r in shorts:
                    r.done.wait(timeout=600)
                t_short = time.perf_counter() - t0
                long_r.done.wait(timeout=600)
                t_all = time.perf_counter() - t0
                eng.stop()
                uaf = int(isinstance(eng.error, UseAfterFree))
                short_toks = sum(len(r.out) for r in shorts)
                s = pool.stats
                row = {
                    "scheme": scheme, "engines": 1, "pressure": "high",
                    "workload": "prefill-interference",
                    "prefill_mode": mode, "prefill_workers": n_pw,
                    "prefill_chunk": chunk,
                    "prefix_cache": False, "sim_backend": sb, "asym": False,
                    "kv_store": "paged", "evict_policy": "lru",
                    "requests": n_short + 1,
                    "short_tokens": short_toks,
                    "tok_per_s_short": short_toks / t_short,
                    "t_short_s": t_short, "t_all_s": t_all,
                    "prefill_tokens": eng.prefill_tokens,
                    "max_ping_stall_s": s.max_ping_stall_s,
                    **eng.metrics.flat(["ttft_s", "tok_latency_s"],
                                       fields=LAT_FIELDS),
                    **pool.metrics.flat(["ping_stall_s"],
                                        fields=LAT_FIELDS),
                    "us_per_step": 1e6 * t_all / max(eng.steps, 1),
                    "peak_unreclaimed": s.retired_peak, "freed": s.freed,
                    "allocated": s.allocated, "pings": s.pings,
                    "publishes": s.publishes, "uaf": uaf, "errors": [],
                }
                rows.append(row)
                pair[mode] = row
                print(f"# prefill-interference {scheme:14s} {mode:6s} "
                      f"c={chunk:2d} short {row['tok_per_s_short']:6.1f} "
                      f"tok/s (t_short={t_short:5.2f}s all={t_all:5.2f}s) "
                      f"ttft p99={row['ttft_p99_s']:5.2f}s "
                      f"ping_stall p99/max="
                      f"{row['ping_stall_p99_s']*1e3:6.1f}/"
                      f"{s.max_ping_stall_s*1e3:6.1f}ms uaf={uaf}")
                assert eng.error is None, \
                    f"prefill-interference {scheme}/{mode} failed: " \
                    f"{eng.error!r}"
            for mode in pair:
                best[mode] = max(best[mode], pair[mode]["tok_per_s_short"])
        if not is_simulated(scheme):
            assert best["async"] >= best["inline"], \
                f"async prefill did not beat inline under {scheme}: " \
                f"best {best['async']:.1f} vs {best['inline']:.1f} tok/s " \
                f"short-decode across chunks {tuple(chunks)}"
    return rows


def run_grid(schemes=DEFAULT_SCHEMES, engines=(1, 2, 4),
             pressures=("low", "high"), duration: float = 0.5,
             shared: bool = True, sim_backend: str = "gen",
             asym: bool = True, tracer: "Tracer | None" = None) -> list:
    """scheme x engines x pressure on the private workload, plus (when
    ``shared``) a cache-on/cache-off shared-prefix pair per scheme -- the
    allocation-reduction comparison from the acceptance criteria -- plus
    (when ``asym``) one asymmetric-costs cell per simulated scheme with
    the remote readers paying 4x ping latency."""
    rows = []
    for scheme in schemes:
        for n in engines:
            for p in pressures:
                r = run_one(scheme, n, p, duration=duration,
                            sim_backend=sim_backend, tracer=tracer)
                rows.append(r)
                print(f"# {scheme:14s} e={n} {p:4s} "
                      f"{r['us_per_step']:9.1f} us/step "
                      f"per-engine min/mean {r['steps_per_s_min']:7.0f}/"
                      f"{r['steps_per_s_mean']:7.0f} steps/s "
                      f"peak_unreclaimed={r['peak_unreclaimed']:4d} "
                      f"freed={r['freed']:6d} pings={r['pings']:5d} "
                      f"uaf={r['uaf']}")
                assert r["uaf"] == 0, \
                    f"use-after-free under {scheme}: {r['errors']}"
        if shared:
            # the allocation-reduction comparison runs at LOW pressure so
            # the hot prefix set can stay resident; the private grid above
            # already covers high-pressure robustness
            n = max(engines) if 2 not in engines else 2
            base = run_one(scheme, n, "low", workload="shared-prefix",
                           prefix_cache=False, duration=duration,
                           sim_backend=sim_backend)
            cached = run_one(scheme, n, "low", workload="shared-prefix",
                             prefix_cache=True, duration=duration,
                             sim_backend=sim_backend)
            # same cell under refcount-aware eviction: entries with live
            # readers survive the reclaimer's pressure sweeps
            cached_rc = run_one(scheme, n, "low", workload="shared-prefix",
                                prefix_cache=True, duration=duration,
                                sim_backend=sim_backend,
                                evict_policy="refcount-aware")
            rows += [base, cached, cached_rc]
            print(f"# {scheme:14s} e={n} shared-prefix alloc/req "
                  f"{base['alloc_per_req']:.2f} -> {cached['alloc_per_req']:.2f} "
                  f"(lru) / {cached_rc['alloc_per_req']:.2f} (refcount-aware; "
                  f"evictions {cached['prefix_evictions']} -> "
                  f"{cached_rc['prefix_evictions']}) "
                  f"hits={cached['prefix_hits']} "
                  f"uaf={base['uaf']}+{cached['uaf']}+{cached_rc['uaf']}")
            assert (base["uaf"] == 0 and cached["uaf"] == 0
                    and cached_rc["uaf"] == 0), \
                f"use-after-free under {scheme} (shared): " \
                f"{base['errors']} {cached['errors']} {cached_rc['errors']}"
            assert cached["alloc_per_req"] < base["alloc_per_req"], \
                f"prefix cache did not reduce allocations under {scheme}: " \
                f"{cached['alloc_per_req']:.2f} vs {base['alloc_per_req']:.2f}"
        if asym and scheme != "EpochPOP-pool" and max(engines) >= 2:
            # asymmetric sockets only exist for the simulated schemes (the
            # native pool policy has no simulated cost model)
            n = max(engines)
            r = run_one(scheme, n, "high", duration=duration,
                        sim_backend=sim_backend, asym=True)
            rows.append(r)
            print(f"# {scheme:14s} e={n} asym "
                  f"{r['us_per_step']:9.1f} us/step "
                  f"per-engine min/mean {r['steps_per_s_min']:7.0f}/"
                  f"{r['steps_per_s_mean']:7.0f} steps/s "
                  f"peak_unreclaimed={r['peak_unreclaimed']:4d} "
                  f"pings={r['pings']:5d} uaf={r['uaf']}")
            assert r["uaf"] == 0, \
                f"use-after-free under {scheme} (asym): {r['errors']}"
    return rows


def to_csv(rows) -> list:
    out = []
    for r in rows:
        if r["workload"] == "prefill-interference":
            tag = (f"serve_reclaim:prefill:{r['scheme']}:"
                   f"{r['prefill_mode']}:c{r['prefill_chunk']}")
            if r.get("sim_backend") not in (None, "gen"):
                tag += "@" + r["sim_backend"]
            out.append(
                f"{tag},{r['us_per_step']:.2f},"
                f"tok_per_s_short={r['tok_per_s_short']:.1f};"
                f"ttft_p99_ms={r['ttft_p99_s']*1e3:.1f};"
                f"max_ping_stall_ms={r['max_ping_stall_s']*1e3:.1f};"
                f"prefill_tokens={r['prefill_tokens']};"
                f"peak_unreclaimed={r['peak_unreclaimed']};uaf={r['uaf']}")
            continue
        tag = f"serve_reclaim:{r['scheme']}:e{r['engines']}:{r['pressure']}"
        if r["workload"] == "shared-prefix":
            tag += ":shared" + ("+cache" if r["prefix_cache"] else "")
            if r.get("evict_policy", "lru") != "lru":
                tag += ":rc"
        if r.get("asym"):
            tag += ":asym"
        if r.get("sim_backend") not in (None, "gen"):
            tag += "@" + r["sim_backend"]
        out.append(
            f"{tag},{r['us_per_step']:.2f},"
            f"peak_unreclaimed={r['peak_unreclaimed']};freed={r['freed']};"
            f"pings={r['pings']};publishes={r['publishes']};"
            f"per_engine_min={r['steps_per_s_min']:.0f};"
            f"alloc_per_req={r['alloc_per_req']:.2f};uaf={r['uaf']}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small grid for CI smoke (3 schemes, high pressure)")
    ap.add_argument("--engines", type=int, default=None, metavar="N",
                    help="restrict the engines axis to a single value")
    ap.add_argument("--sim-backend", default="gen", choices=("gen", "vec"),
                    help="simulator backend for the simulated schemes; "
                         "'vec' extends the default engines axis to 8")
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--skip-prefill", action="store_true",
                    help="skip the prefill-interference rows (real chunked "
                         "prefill traffic; full runs only -- --quick always "
                         "skips them)")
    ap.add_argument("--prefill-workers", type=int, default=2, metavar="N",
                    help="dedicated prefill threads for the async cells of "
                         "the prefill-interference rows")
    ap.add_argument("--prefill-chunk", type=int, default=None, metavar="C",
                    help="restrict the prefill-interference chunk sweep to "
                         "a single chunk size (default: sweep 4 and 16)")
    ap.add_argument("--out", default="results/serve_reclaim.json")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Perfetto-loadable trace of every cell "
                         "(request lifecycle + SMR ping spans) to this path")
    ap.add_argument("--metrics", action="store_true",
                    help="print the per-row latency/stall percentile "
                         "columns as a summary table")
    args = ap.parse_args()
    enable_compile_cache()
    tracer = Tracer() if args.trace else None
    engines = (args.engines,) if args.engines else None
    chunks = (args.prefill_chunk,) if args.prefill_chunk else (4, 16)
    if args.quick:
        rows = run_grid(schemes=QUICK_SCHEMES, engines=engines or (1, 2),
                        pressures=("high",),
                        duration=args.duration or 0.2,
                        sim_backend=args.sim_backend, asym=False,
                        tracer=tracer)
    else:
        # the vec backend is what makes the 8-engine column affordable
        full = (1, 2, 4, 8) if args.sim_backend == "vec" else (1, 2, 4)
        rows = run_grid(engines=engines or full,
                        duration=args.duration or 0.5,
                        sim_backend=args.sim_backend, tracer=tracer)
        if not args.skip_prefill:
            rows += run_prefill_interference(
                chunks=chunks, prefill_workers=args.prefill_workers,
                sim_backend=args.sim_backend, tracer=tracer)
    if tracer is not None:
        obj = tracer.export(args.trace)
        print(f"trace: {len(obj['traceEvents'])} events -> {args.trace}")
    if args.metrics:
        for r in rows:
            cols = ", ".join(f"{k}={r[k]*1e3:.2f}ms" for k in
                             ("ttft_p50_s", "ttft_p99_s",
                              "tok_latency_p50_s", "tok_latency_p99_s",
                              "ping_stall_p50_s", "ping_stall_p99_s",
                              "ping_stall_max_s") if k in r)
            if cols:
                name = f"{r['scheme']}:{r['workload']}"
                if r.get("prefill_mode"):
                    name += f":{r['prefill_mode']}:c{r['prefill_chunk']}"
                else:
                    name += f":e{r['engines']}:{r['pressure']}"
                print(f"# metrics {name:44s} {cols}")
    # regenerate (not append): the file is the CURRENT grid, superseded
    # rows from earlier runs are dropped wholesale
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(rows, indent=1))
    print("name,us_per_call,derived")
    print("\n".join(to_csv(rows)))
    return rows


if __name__ == "__main__":
    main()
