"""Benchmark entrypoint: one function per paper table/figure + the framework
benches.  Prints ``name,us_per_call,derived`` CSV (plus human-readable logs
as '#'-prefixed lines), regenerates every ``results/*.json`` it owns, and
ends with a one-line per-suite summary (rows written, headline metric)."""

from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

# make `python benchmarks/run.py` equivalent to `python -m benchmarks.run`
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def _quiet(fn, *a, **kw):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        out = fn(*a, **kw)
    for line in buf.getvalue().splitlines():
        print("#", line)
    return out


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    csv = ["name,us_per_call,derived"]
    summaries = []          # (suite, rows, headline) -- printed at the end

    # -- paper Fig. 1-3: SMR throughput (scaled-down quick grid) --
    from benchmarks.smr_throughput import run as smr_run, summarize
    res = _quiet(smr_run, structures=("HML", "HMHT"), threads=(2, 4, 8),
                 duration=200_000.0, out="results/smr_throughput.json")
    summ = summarize(res)
    for r in res:
        # us per op at the simulated 1GHz clock
        us = 1e6 / max(r["throughput"], 1e-9) / 1e3
        csv.append(f"smr:{r['structure']}:{r['workload']}:t{r['threads']}:"
                   f"{r['scheme']},{us:.2f},thr={r['throughput']:.0f};"
                   f"gpeak={r['garbage_peak']}")
    for k, v in summ.items():
        csv.append(f"smr_ratio:{k},0,min={v['min']:.2f};max={v['max']:.2f};"
                   f"mean={v['mean']:.2f}")
    best = max(res, key=lambda r: r["throughput"])
    summaries.append(("smr_throughput", len(res),
                      f"best {best['scheme']}/{best['structure']} "
                      f"{best['throughput']:.0f} ops/Mcyc"))

    # -- paper Fig. 4: long-running reads --
    from benchmarks.long_reads import SCHEMES, run_one
    lr = [_quiet(run_one, s, duration=800_000.0, list_size=2048)
          for s in SCHEMES]
    nr = next(r for r in lr if r["scheme"] == "NR")
    best_ratio = 0.0
    for r in lr:
        ratio = r["read_throughput"] / max(nr["read_throughput"], 1e-9)
        if r["scheme"] != "NR":
            best_ratio = max(best_ratio, ratio)
        csv.append(f"long_reads:{r['scheme']},"
                   f"{1e6/max(r['read_throughput'],1e-9)/1e3:.2f},"
                   f"ratio_vs_NR={ratio:.2f};restarts={r['restarts']}")
    Path("results").mkdir(exist_ok=True)
    Path("results/long_reads.json").write_text(json.dumps(lr, indent=1))
    summaries.append(("long_reads", len(lr),
                      f"best ratio_vs_NR={best_ratio:.2f}"))

    # -- paper Fig. 5-9: garbage bound under stall --
    from benchmarks.memory_footprint import SCHEMES as MSCHEMES, run_one as mem_one
    mem = []
    for stalled in (False, True):
        for s in MSCHEMES:
            r = _quiet(mem_one, s, stalled=stalled, duration=200_000.0)
            mem.append(r)
            csv.append(f"garbage:{s}:{'stall' if stalled else 'nostall'},0,"
                       f"final={r['garbage_final']};retired={r['retired']};"
                       f"unreclaimed={r['unreclaimed_frac']:.3f}")
    Path("results/memory_footprint.json").write_text(json.dumps(mem, indent=1))
    worst = max(mem, key=lambda r: r["unreclaimed_frac"])
    summaries.append(("memory_footprint", len(mem),
                      f"worst unreclaimed={worst['unreclaimed_frac']:.3f} "
                      f"({worst['scheme']})"))

    # -- framework: POP block pool vs eager refcount pool --
    from benchmarks.block_pool_bench import bench_pop, bench_refcount
    pool_rows = [_quiet(bench_refcount, 0.5), _quiet(bench_pop, 0.5),
                 _quiet(bench_pop, 0.5, stalled=True)]
    for r in pool_rows:
        csv.append(f"pool:{r['name'].replace(' ', '_').replace(',', '')},"
                   f"{1e6/max(r['steps_per_s'],1e-9):.2f},"
                   f"steps_per_s={r['steps_per_s']:.0f}")
    summaries.append(("block_pool", len(pool_rows),
                      f"pop {pool_rows[1]['steps_per_s']:.0f} steps/s"))

    # -- framework: serving-side reclamation grid (scheme x engines x pressure
    #    + the shared-prefix allocation comparison) --
    from benchmarks.serve_reclaim import QUICK_SCHEMES, run_grid, to_csv
    sr = _quiet(run_grid, schemes=QUICK_SCHEMES, engines=(1, 2),
                pressures=("high",), duration=0.2, sim_backend="vec",
                asym=False)
    csv.extend(to_csv(sr))
    Path("results/serve_reclaim.json").write_text(json.dumps(sr, indent=1))
    summaries.append(("serve_reclaim", len(sr),
                      f"uaf={sum(r.get('uaf', 0) for r in sr)}"))

    # -- framework: fleet-scale trace-driven load (SLO goodput per scheme) --
    from benchmarks.fleet_load import run_fleet, to_csv as fleet_csv
    fl = _quiet(run_fleet, schemes=("EpochPOP", "EBR"),
                profiles=("calm", "desched-stall"), engines=8,
                duration_s=1.5, rate_rps=16.0)
    csv.extend(fleet_csv(fl))
    Path("results/fleet_load.json").write_text(json.dumps(fl, indent=1))
    head = next(r for r in fl if r["profile"] == "calm")
    summaries.append(("fleet_load", len(fl),
                      f"goodput={head['goodput_under_slo']:.1f} tok/s "
                      f"({head['scheme']}/calm) "
                      f"uaf={sum(r['uaf'] for r in fl)}"))

    # -- kernels --
    from benchmarks.kernel_bench import bench_flash, bench_linear_scan, bench_paged
    kr = [_quiet(bench_flash), _quiet(bench_linear_scan), _quiet(bench_paged)]
    for r in kr:
        csv.append(f"kernel:{r['name'].split()[0]},{r['us_per_call']:.1f},"
                   f"v5e_roofline_us={r['v5e_roofline_us']:.1f}")
    summaries.append(("kernels", len(kr),
                      f"flash {kr[0]['us_per_call']:.1f} us/call"))

    # -- roofline table from the dry-run artifacts (if present) --
    try:
        from benchmarks.roofline_table import csv as roof_csv
        lines = roof_csv().splitlines()[1:]
        csv.extend(lines)
        summaries.append(("roofline", len(lines), "table rebuilt"))
    except Exception as e:  # noqa: BLE001
        print(f"# roofline table unavailable: {e}")

    print("\n".join(csv))
    print("# ---- suite summaries ----")
    for suite, rows, headline in summaries:
        print(f"# {suite:18s} {rows:3d} rows  {headline}")


if __name__ == "__main__":
    main()
