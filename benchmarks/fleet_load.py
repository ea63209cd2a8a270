"""Fleet-scale trace-driven load harness: SLO goodput per scheme x traffic
profile (serve/loadgen.py + obs/slo.py over the sharded serving runtime).

The paper's headline claim is *conditional*: EpochPOP "approaches the
performance of epoch-based reclamation in the common case where threads are
not frequently delayed".  Mean tok/s on a calm loop cannot test a
conditional -- this harness manufactures the conditions and scores them the
way a fleet operator would:

* **replayed traces, not inline RNG** -- every cell replays a trace built
  once per profile by ``serve/loadgen.py`` (seeded, serializable), so every
  scheme sees bit-identical arrivals, tenants, prompts, and output budgets.
  ``--save-workloads DIR`` writes the traces next to the results for exact
  re-runs.
* **traffic profiles** = the paper's regimes:
    - ``calm``          -- flat Poisson arrivals (the "common case");
    - ``bursty``        -- Gamma-burst arrivals (CV^2 = 8) riding a
      piecewise diurnal ramp: the same mean rate arriving in clumps, the
      regime where queues build and tails blow out;
    - ``desched-stall`` -- calm arrivals + a worker-level desched fault
      (worker 0 sleeps mid-step, reader session held, every Nth step):
      the "frequently delayed threads" condition the paper's claim
      excludes.  A POP ping that lands mid-stall waits the full sleep for
      that reader's publish (``max_ping_stall_s`` rises to ~the stall
      length on the native pool policy); an EBR-style pass pins the epoch
      and garbage accumulates instead.
    - ``hot-engine``    -- calm arrivals + STATIC placement (rid-hash keeps
      routing a fixed share of traffic to worker 0) + the same worker-0
      desched fault: one engine of the fleet is both slow and still being
      fed, so its queue builds while its peers idle.  Run twice per
      scheme, migration monitor off vs on -- the on cell must recover the
      p99 TTFT the off cell loses (every migration re-homes the request's
      KV blocks across engine ids via ``BlockPool.adopt``, racing
      whatever reclamation passes the scheme is running).
* **SLO goodput, not throughput** -- each finished request is scored
  against TTFT + per-token budgets (obs/slo.py); rows report
  ``goodput_under_slo`` (SLO-meeting tokens/s: the ROADMAP's
  do-not-regress number), attainment overall / per tenant / per window,
  and full latency percentiles.
* **time series, not end-of-run scalars** -- a background sampler polls
  queue depth, running batch, free/retired blocks, resident KV bytes, and
  the running ping-stall p99 at a fixed cadence; every row carries the
  ``samples`` rows so the diurnal curve and the stall windows are visible
  over the run.

Scheme lineup: the native ``EpochPOP-pool`` policy (real wall-clock pings;
run with ``pop_every=2`` so the POP fallback actually exercises under
benchmark-scale pressure) vs simulated ``EpochPOP`` / ``EBR`` /
``HazardPtrPOP`` on the vec backend -- the paper's contrast plus the HP
robustness baseline.

    PYTHONPATH=src python benchmarks/fleet_load.py [--quick] [--engines 8]
    PYTHONPATH=src python benchmarks/fleet_load.py --trace /tmp/fleet.json
    PYTHONPATH=src python benchmarks/perf_diff.py --baseline  # diff vs git

CSV schema (matched to benchmarks/run.py): ``name,us_per_call,derived``
where name = fleet_load:<scheme>:<profile>:e<engines>[@vec], us_per_call
is wall microseconds per generated token, and derived packs
goodput/attainment/ttft_p99/max_ping_stall/uaf.
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

from repro.core.sim.engine import UseAfterFree
from repro.launch.compile_cache import enable_compile_cache
from repro.obs import SLOSpec, SLOTracker, TimeSeriesSampler, Tracer, \
    engine_probes
from repro.runtime.block_pool import BlockPool
from repro.runtime.reclaim import is_simulated, make_policy
from repro.serve.loadgen import TenantSpec, Trace, WorkloadSpec, generate, \
    replay

DEFAULT_SCHEMES = ("EpochPOP-pool", "EpochPOP", "EBR", "HazardPtrPOP")
QUICK_SCHEMES = ("EpochPOP", "EBR")
PROFILES = ("calm", "bursty", "desched-stall", "hot-engine")

#: profiles that run a migration-on/off A/B per scheme: hot-engine is the
#: cell migration must rescue, calm is the no-harm control
MIGRATE_AB = ("hot-engine", "calm")

#: the hot-engine acceptance bar: with the monitor on, p99 TTFT must come
#: in at or under this fraction of the migration-off cell
HOT_ENGINE_TTFT_RATIO = 0.7

#: the per-request budgets a token must meet to count toward goodput --
#: calibrated to the tiny fleet config on a single-core CI box: calm cells
#: sit comfortably inside them, stall/burst cells measurably do not
SLO = SLOSpec(ttft_s=0.30, tok_latency_s=0.05, name="fleet-default")

#: worker-0 desched fault for the "frequently delayed" profile: sleep
#: 250 ms mid-step (reader session held) every 3rd step -- long enough
#: that one stall blows a victim request's per-token budget, so the cell
#: shows up as lost goodput, not just a latency blip
STALL_EVERY, STALL_S = 3, 0.25

#: the hot-engine profile stalls worker 0 on EVERY step: combined with
#: static placement its queue genuinely backs up (slots turn over at
#: stall speed while the rid-hash keeps feeding it), which is the tail
#: the migration monitor must rescue -- the milder every-3rd-step fault
#: hurts requests already RUNNING on the victim, which no queued-request
#: migration can help
HOT_STALL_EVERY, HOT_STALL_S = 1, 0.25

#: the multi-tenant mix every profile shares: a chatty tenant with a
#: page-aligned shared system prompt + long-tailed lengths, a fixed batch
#: tenant, and a zipf-tailed tools tenant
TENANTS = (
    TenantSpec("chat", weight=3.0, system_prefix=16,
               prompt_len={"kind": "lognormal", "mu": 2.0, "sigma": 0.7,
                           "lo": 4, "hi": 32},
               output_len={"kind": "zipf", "alpha": 1.3, "lo": 2, "hi": 10}),
    TenantSpec("batch", weight=1.0,
               prompt_len={"kind": "fixed", "value": 12},
               output_len={"kind": "fixed", "value": 6}),
    TenantSpec("tools", weight=1.0,
               prompt_len={"kind": "zipf", "alpha": 1.1, "lo": 6, "hi": 28},
               output_len={"kind": "lognormal", "mu": 1.4, "sigma": 0.5,
                           "lo": 2, "hi": 8}),
)


def profile_spec(profile: str, *, duration_s: float, rate_rps: float,
                 seed: int) -> WorkloadSpec:
    """The WorkloadSpec for one traffic profile (the desched-stall profile
    reuses calm arrivals -- its fault lives in the engine, not the trace)."""
    if profile in ("calm", "desched-stall", "hot-engine"):
        return WorkloadSpec(duration_s=duration_s, seed=seed,
                            tenants=TENANTS, process="poisson",
                            rate_rps=rate_rps, vocab=64)
    if profile == "bursty":
        return WorkloadSpec(duration_s=duration_s, seed=seed,
                            tenants=TENANTS, process="gamma",
                            rate_rps=rate_rps, burstiness=8.0,
                            diurnal=((0.0, 0.5), (0.4, 1.8), (0.7, 1.0),
                                     (1.0, 0.4)),
                            vocab=64)
    raise ValueError(f"unknown profile {profile!r}")


def _tiny_cfg_params():
    import jax
    from repro.configs.base import ArchConfig, dense_stack
    from repro.models.model import init_params

    cfg = ArchConfig(name="fleet-bench", d_model=32, n_heads=4, n_kv_heads=2,
                     d_ff=64, vocab=64, groups=dense_stack(2), remat="none",
                     dtype="float32")
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def warm_programs(eng, trace: Trace) -> int:
    """Before ``eng`` starts: load every program a replay of ``trace`` can
    run, so that none compiles inside the measured window.  The paged
    forward is a program per row count and table width: a decode step has
    one row per running request (up to ``max_batch``), a prefill chunk one
    per chunk position (up to ``prefill_chunk``), and the widest table
    covers the longest request.  Each (rows, width) runs once, into pages
    no request holds yet (a page is zeroed when it is allocated), and so
    does the page fill for every page count an allocation or a reclaim
    pass can hand the store.  Returns the forward calls made."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve.paged_model import paged_decode_step

    store, w = eng.kv_store, eng.workers[0]
    longest_prompt = max(len(r.prompt) for r in trace.requests)
    longest = max(len(r.prompt) + r.max_new for r in trace.requests)
    max_rows = max(w.max_batch, min(w.prefill_chunk, longest_prompt))
    max_width = -(-(longest + 1) // store.page)
    for n in range(1, store.num_blocks + 1):
        store.on_alloc(range(n))
    calls = 0
    for b in range(1, max_rows + 1):
        for width in range(1, max_width + 1):
            # row 0 spans ``width`` pages, the others one: the table is
            # (b, width)
            logits = paged_decode_step(
                eng.params, eng.cfg, store, [list(range(width))] * b,
                [store.page * width - 1] + [0] * (b - 1), [1] * b,
                impl=eng.kernel_impl)
            np.asarray(jnp.argmax(logits, axis=-1))
            calls += 1
    return calls


def run_cell(scheme: str, profile: str, trace: Trace, *, engines: int = 8,
             sim_backend: str = "vec", slo: SLOSpec = SLO,
             sample_interval_s: float = 0.1, migrate: bool = False,
             cfg=None, params=None, tracer=None) -> dict:
    """Replay ``trace`` against one (scheme, profile, migrate) fleet cell
    and score it: SLO goodput + latency percentiles + peak gauges + time
    series."""
    from repro.serve.engine import ServeEngine

    if cfg is None or params is None:
        cfg, params = _tiny_cfg_params()
    # both fault profiles stall worker 0 mid-step; hot-engine additionally
    # pins placement (static rid-hash) so the stalled engine keeps being
    # fed its fixed share -- the skew the migration monitor must undo
    stalled = profile in ("desched-stall", "hot-engine")
    stall_every, stall_s = (
        (HOT_STALL_EVERY, HOT_STALL_S) if profile == "hot-engine"
        else (STALL_EVERY, STALL_S) if stalled else (0, 0.0))
    kw = dict(n_engines=engines, max_batch=4, page_size=16,
              prefix_cache=True, stall_every=stall_every, stall_s=stall_s,
              place_policy="static" if profile == "hot-engine"
              else "least-loaded",
              migrate=migrate, migrate_threshold=2,
              migrate_interval_s=0.02, trace=tracer)
    num_pages = engines * 24
    if is_simulated(scheme):
        eng = ServeEngine(cfg, params, num_pages=num_pages, smr=scheme,
                          sim_backend=sim_backend, **kw)
    else:
        # native pool policy: real wall-clock pings.  pop_every forces the
        # POP fallback on every other reclaim pass (benchmark-scale runs
        # rarely build enough retired-list pressure to trigger it), and the
        # 1 s ping timeout caps the shutdown-race pass without clipping
        # real stalls (~STALL_S)
        pool = BlockPool(num_pages, n_engines=engines + 1,
                         reclaim_threshold=8, ping_timeout_s=1.0,
                         policy=make_policy(None, pop_every=2))
        sim_backend = None
        eng = ServeEngine(cfg, params, pool=pool, **kw)
    warm_programs(eng, trace)
    eng.start()
    try:
        # one request end-to-end through the started engine, then the
        # measurement window starts clean
        w = eng.submit([1, 2, 3, 4], max_new=2)
        w.done.wait(120)
        eng.metrics.reset()
        eng.pool.metrics.reset()
        eng.pool.stats.max_ping_stall_s = 0.0

        sampler = TimeSeriesSampler(engine_probes(eng),
                                    interval_s=sample_interval_s).start()
        t0 = time.monotonic()
        pairs = replay(
            trace, lambda r: (r, eng.submit(list(r.prompt),
                                            max_new=r.max_new)),
            stop=lambda: eng.error is not None)
        for _, r in pairs:
            r.done.wait(60)
        elapsed = time.monotonic() - t0

        # score + snapshot BEFORE stop(): a reclaim pass in flight at
        # shutdown pings exiting workers and would pollute the stall max
        slo_t = SLOTracker(slo, window_s=0.5)
        completed = 0
        for treq, r in pairs:
            if not r.out:
                continue
            completed += 1
            ttft = (r.t_first_tok - r.t_submit) if r.t_first_tok else 0.0
            tok_lat = ((r.t_last_tok - r.t_first_tok) / (len(r.out) - 1)
                       if len(r.out) > 1 and r.t_first_tok else 0.0)
            slo_t.observe(t_finish_s=max(r.t_last_tok - t0, 0.0),
                          tokens=len(r.out), ttft_s=ttft,
                          tok_latency_s=tok_lat, tenant=treq.tenant)
        lat = eng.metrics.flat(["ttft_s", "tok_latency_s", "queue_wait_s"])
        lat.update(eng.pool.metrics.flat(["ping_stall_s"]))
        st = eng.pool.stats
        samples = sampler.stop()
        row = {
            "scheme": scheme, "profile": profile, "engines": engines,
            "sim_backend": sim_backend, "kv_store": "paged",
            "place_policy": kw["place_policy"], "migrate": int(migrate),
            "trace_seed": int(trace.meta["seed"]),
            "trace_duration_s": trace.duration_s,
            "offered_rps": trace.offered_rps,
            "requests": len(trace.requests), "completed": completed,
            "elapsed_s": elapsed,
            "tok_per_s": slo_t.summary(elapsed)["tokens_out"] / elapsed,
            "us_per_tok": elapsed * 1e6 / max(slo_t.summary(elapsed)
                                              ["tokens_out"], 1),
            **slo_t.summary(elapsed),
            **lat,
            "max_ping_stall_s": st.max_ping_stall_s,
            "pings": st.pings, "publishes": st.publishes,
            "peak_unreclaimed": st.retired_peak,
            "peak_kv_bytes": sampler.peak("resident_kv_bytes"),
            "peak_queue_depth": sampler.peak("queue_depth"),
            "injected_stalls": eng.injected_stalls,
            "stall_every": stall_every,
            "stall_s": stall_s,
            "migrations": eng.scheduler.migrations,
            "preemptions": eng.scheduler.preemptions,
            "queue_reorders": eng.scheduler.queue_reorders,
            "adopts": st.adopts, "stale_handoffs": st.stale_handoffs,
            "uaf": int(isinstance(eng.error, UseAfterFree)),
            "errors": [repr(eng.error)] if eng.error else [],
            "samples": samples,
        }
    finally:
        eng.stop()
    return row


def run_fleet(schemes=DEFAULT_SCHEMES, profiles=PROFILES, *,
              engines: int = 8, duration_s: float = 3.0,
              rate_rps: float = 16.0, seed: int = 11,
              sim_backend: str = "vec", tracer=None,
              migrate_ab=MIGRATE_AB, save_workloads=None) -> list:
    """The grid: one trace per profile (same seed -> every scheme replays
    identical traffic), every scheme through every profile; profiles in
    ``migrate_ab`` additionally run a migration-on twin (hot-engine: the
    rescue cell the :data:`HOT_ENGINE_TTFT_RATIO` gate scores; calm: the
    no-harm control)."""
    cfg, params = _tiny_cfg_params()
    traces = {p: generate(profile_spec(p, duration_s=duration_s,
                                       rate_rps=rate_rps, seed=seed))
              for p in profiles}
    if save_workloads:
        d = Path(save_workloads)
        d.mkdir(parents=True, exist_ok=True)
        for p, tr in traces.items():
            tr.save(d / f"fleet_{p}.trace.json")
    rows = []
    hot_pairs = {}          # scheme -> {migrate: row} for the rescue gate
    for scheme in schemes:
        for profile in profiles:
            variants = ((False, True) if profile in migrate_ab
                        else (False,))
            for migrate in variants:
                r = run_cell(scheme, profile, traces[profile],
                             engines=engines, sim_backend=sim_backend,
                             migrate=migrate, cfg=cfg, params=params,
                             tracer=tracer)
                rows.append(r)
                if profile == "hot-engine":
                    hot_pairs.setdefault(scheme, {})[int(migrate)] = r
                print(f"# {scheme:14s} {profile:13s} e={engines} "
                      f"m={int(migrate)} "
                      f"goodput={r['goodput_under_slo']:7.1f} tok/s "
                      f"attain={r['slo_attainment']:.2f} "
                      f"ttft_p99={r['ttft_p99_s'] * 1e3:6.1f} ms "
                      f"max_ping_stall={r['max_ping_stall_s'] * 1e3:6.1f} ms "
                      f"migrations={r['migrations']} "
                      f"uaf={r['uaf']}")
                assert r["uaf"] == 0, \
                    f"use-after-free under {scheme}/{profile}: {r['errors']}"
                assert not r["errors"], \
                    f"engine error under {scheme}/{profile}: {r['errors']}"
    for scheme, pair in hot_pairs.items():
        if 0 in pair and 1 in pair:
            off, on = pair[0]["ttft_p99_s"], pair[1]["ttft_p99_s"]
            assert on <= HOT_ENGINE_TTFT_RATIO * off, (
                f"{scheme}: migration failed to rescue the hot engine "
                f"(ttft_p99 on={on * 1e3:.1f}ms vs off={off * 1e3:.1f}ms, "
                f"bar {HOT_ENGINE_TTFT_RATIO:.0%})")
            assert pair[1]["migrations"] > 0, \
                f"{scheme}: hot-engine cell ran with zero migrations"
    return rows


def to_csv(rows) -> list:
    out = []
    for r in rows:
        tag = f"fleet_load:{r['scheme']}:{r['profile']}:e{r['engines']}"
        if r.get("migrate"):
            tag += ":m1"
        if r.get("sim_backend") not in (None, "gen"):
            tag += "@" + r["sim_backend"]
        out.append(
            f"{tag},{r['us_per_tok']:.2f},"
            f"goodput={r['goodput_under_slo']:.1f};"
            f"attain={r['slo_attainment']:.3f};"
            f"ttft_p99_ms={r['ttft_p99_s'] * 1e3:.1f};"
            f"max_ping_stall_ms={r['max_ping_stall_s'] * 1e3:.1f};"
            f"peak_kv_bytes={int(r['peak_kv_bytes'])};"
            f"migrations={r.get('migrations', 0)};"
            f"uaf={r['uaf']}")
    return out


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--quick", action="store_true",
                    help="2 schemes x {calm, desched-stall, hot-engine}, "
                         "shorter trace, migration A/B on hot-engine only")
    ap.add_argument("--engines", type=int, default=8)
    ap.add_argument("--duration", type=float, default=None,
                    help="trace duration in seconds (default 3.0, quick 1.5)")
    ap.add_argument("--rate", type=float, default=16.0,
                    help="mean arrival rate, requests/s")
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--schemes", nargs="*", default=None)
    ap.add_argument("--sim-backend", default="vec", choices=("gen", "vec"))
    ap.add_argument("--out", default="results/fleet_load.json")
    ap.add_argument("--trace", default=None,
                    help="write a Perfetto trace of the whole grid here")
    ap.add_argument("--save-workloads", default=None,
                    help="directory to save the generated workload traces")
    args = ap.parse_args(argv)
    enable_compile_cache()

    schemes = tuple(args.schemes) if args.schemes else (
        QUICK_SCHEMES if args.quick else DEFAULT_SCHEMES)
    profiles = (("calm", "desched-stall", "hot-engine") if args.quick
                else PROFILES)
    migrate_ab = ("hot-engine",) if args.quick else MIGRATE_AB
    duration = args.duration if args.duration is not None else (
        1.5 if args.quick else 3.0)
    tracer = Tracer() if args.trace else None
    rows = run_fleet(schemes, profiles, engines=args.engines,
                     duration_s=duration, rate_rps=args.rate,
                     seed=args.seed, sim_backend=args.sim_backend,
                     tracer=tracer, migrate_ab=migrate_ab,
                     save_workloads=args.save_workloads)
    for line in to_csv(rows):
        print(line)
    if args.out:
        out = Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(rows, indent=1))
        print(f"# wrote {len(rows)} rows -> {out}")
    if tracer is not None:
        obj = tracer.export(args.trace)
        print(f"# trace: {len(obj['traceEvents'])} events -> {args.trace}")
    return rows


if __name__ == "__main__":
    main()
