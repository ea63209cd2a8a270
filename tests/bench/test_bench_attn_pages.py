"""The reader of ``attn_skipped_page_share`` checked by hand on synthetic
engine steps (the window filter, steps that decoded nothing, a program
whose spans carry no page counts), and a traced smoke run of
``sc2-decode`` that reports it."""

import pytest

from bench.harness import Run
from bench.registry import load_reader

ENGINE = 1


def _x(name, t, dur, **args):
    """A tracer X event of the engine thread at ``t`` s lasting ``dur`` s."""
    ev = {"name": name, "ph": "X", "cat": "t", "ts": t * 1e6,
          "dur": dur * 1e6, "pid": 1, "tid": ENGINE}
    if args:
        ev["args"] = args
    return ev


def _step(t, pages=None):
    """One 10 ms engine step at ``t``; with ``pages`` = (live, table) it
    decodes, and its ``dispatch`` span carries those page counts."""
    evs = [_x("engine_step", t, 0.010, rows=32 if pages else 0,
              prefill_chunks=0)]
    if pages:
        live, table = pages
        evs += [_x("decode_step", t + 0.001, 0.008, batch=32, finished=0),
                _x("dispatch", t + 0.002, 0.004, live_pages=live,
                   table_pages=table)]
    return evs


def _run(events):
    run = Run(cell=None, seed=0, seconds=10.0, t0=100.0, t1=110.0)
    run.spans = events
    run.span_t0 = 0.0
    return run


def test_attn_skipped_page_share_sums_the_windows_dispatch_counts():
    evs = []
    evs += _step(99.995, (10, 100))          # starts before the window
    evs += _step(101.0, (600, 1408))
    evs += _step(102.0, (550, 1200))
    evs += _step(103.0)                      # decoded nothing
    evs += _step(110.0, (1, 100))            # starts at the close
    share = load_reader("attn_skipped_page_share")(_run(evs))
    assert share == pytest.approx(100 * (1 - 1150 / 2608))


@pytest.mark.parametrize("events", [
    # dispatch spans without page counts, as a program before the kernel
    # fetched only live pages writes them
    [_x("engine_step", 101.0, 0.01, rows=32, prefill_chunks=0),
     _x("dispatch", 101.002, 0.004)],
    # no engine_step span at all
    [_x("dispatch", 101.002, 0.004, live_pages=5, table_pages=10)],
], ids=["no_counts", "no_engine_steps"])
def test_attn_skipped_page_share_reads_nothing_without_page_counts(events):
    assert load_reader("attn_skipped_page_share")(_run(events)) is None


def test_traced_smoke_run_reports_attn_skipped_page_share(smoke_cell):
    from bench.harness import run_cell

    res = run_cell(smoke_cell("sc2-decode"), 2**31 + 31, 6.0, True,
                   allow_cpu=True, log=lambda _: None)
    assert res["correct"], res["checks"]
    assert 0 <= res["metrics"]["attn_skipped_page_share"]["value"] < 100
