"""The ``forward_launches`` reader on synthetic spans (the window filter,
nothing read from spans that carry no count), and a traced smoke run of
``slm12-decode`` that reports it: 2L + 1 programs a decode step."""

import pytest

from bench.harness import Run
from bench.registry import load_reader


def _x(name, t, dur, **args):
    return {"name": name, "ph": "X", "cat": "t", "ts": t * 1e6,
            "dur": dur * 1e6, "pid": 1, "tid": 1, "args": args}


def _step(t, **dispatch_args):
    return [_x("engine_step", t, 0.010, rows=32, prefill_chunks=0),
            _x("decode_step", t, 0.009, batch=32, finished=0),
            _x("dispatch", t + 0.001, 0.004, **dispatch_args)]


def _run(events):
    run = Run(cell=None, seed=0, seconds=10.0, t0=100.0, t1=110.0)
    run.spans = events
    run.span_t0 = 0.0
    return run


def test_mean_launches_of_the_windows_dispatch_spans():
    evs = (_step(99.99, launches=99)            # starts before the window
           + _step(101.0, launches=33, uploads=1)
           + _step(102.0, launches=25, uploads=1)
           + _step(110.0, launches=99))         # starts at the close
    assert load_reader("forward_launches")(_run(evs)) == pytest.approx(29.0)


def test_spans_without_a_count_read_nothing():
    evs = _step(101.0, live_pages=3, table_pages=4) + _step(102.0)
    assert load_reader("forward_launches")(_run(evs)) is None
    assert load_reader("forward_launches")(_run([])) is None


def test_traced_smoke_run_reports_forward_launches(smoke_cell):
    from bench.harness import run_cell

    cell = smoke_cell("slm12-decode")
    res = run_cell(cell, 2**31 + 171, 6.0, True, allow_cpu=True,
                   log=lambda _: None)
    assert res["correct"], res["checks"]
    layers = cell.config["model"]["layers"]
    assert res["metrics"]["forward_launches"]["value"] == 2 * layers + 1
