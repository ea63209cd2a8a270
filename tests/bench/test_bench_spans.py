"""The readers of the program's spans checked by hand on synthetic nested
spans (self time, the ``prefill_chunks`` filter, the window filter), the
device's idle time put down to the engine thread's innermost span while a
shorter span of another thread covers the same instant, and a traced
smoke run of ``sc2-decode`` that reports the span metrics."""

import pytest

from bench import spans
from bench.devtrace import DeviceTrace, Op
from bench.harness import Run
from bench.registry import load_reader

ENGINE, RECLAIMER = 1, 2


def _x(name, t, dur, tid=ENGINE, **args):
    """A tracer X event at ``t`` seconds lasting ``dur`` seconds."""
    ev = {"name": name, "ph": "X", "cat": "t", "ts": t * 1e6,
          "dur": dur * 1e6, "pid": 1, "tid": tid}
    if args:
        ev["args"] = args
    return ev


def _step(t, rows, chunks, retire_s=0.0, publish_s=0.0):
    """One engine step at ``t``: 10 ms long, 2 ms of prefill when it runs
    chunks, then a decode step of reserve 1 ms, tripwire 0.5 ms, dispatch
    4 ms, token sync 2 ms and a retire that may publish inside it."""
    evs = [_x("engine_step", t, 0.010, rows=rows, prefill_chunks=chunks),
           _x("admit", t, 0.002)]
    if chunks:
        evs.append(_x("prefill_chunk", t, 0.0015, rid=1, start=0, end=8))
    if rows:
        d = t + 0.002
        evs += [_x("decode_step", d, 0.0079, batch=rows, finished=0),
                _x("pool_reserve", d, 0.001),
                _x("pool_tripwire", d + 0.001, 0.0005),
                _x("dispatch", d + 0.0015, 0.004),
                _x("token_sync", d + 0.0055, 0.002)]
        if retire_s:
            evs.append(_x("pool_retire", d + 0.0075, retire_s))
            if publish_s:
                evs.append(_x("pop_publish", d + 0.0075, publish_s))
    return evs


def _run(events):
    run = Run(cell=None, seed=0, seconds=10.0, t0=100.0, t1=110.0)
    run.spans = events
    run.span_t0 = 0.0
    return run


def _window_run():
    evs = []
    evs += _step(99.995, 32, 1)                 # starts before the window
    evs += _step(101.0, 32, 0, retire_s=0.0004, publish_s=0.0001)
    evs += _step(102.0, 30, 2)
    evs += _step(103.0, 0, 0)                   # decoded nothing
    evs += _step(110.0, 1, 3)                   # starts at the close
    evs.append(_x("reclaim_pass", 101.001, 0.00005, tid=RECLAIMER))
    return _run(evs)


def test_smr_step_share_counts_own_time_inside_window_steps():
    run = _window_run()
    # steps at 101, 102, 103: 30 ms.  SMR own time: step 101 reserve 1 +
    # tripwire 0.5 + retire 0.3 (0.4 less the publish) + publish 0.1;
    # step 102 reserve 1 + tripwire 0.5; step 103 none
    share = load_reader("smr_step_share")(run)
    assert share == pytest.approx(100 * 3.4 / 30)


def test_step_readers_by_hand():
    run = _window_run()
    assert load_reader("dispatch_ms")(run) == pytest.approx(4.0)
    # only the window's step at 102 ran a chunk
    assert load_reader("prefill_step_ms")(run) == pytest.approx(10.0)
    # steps that decoded: 32 and 30 rows
    assert load_reader("batch_rows")(run) == pytest.approx(31.0)


def test_self_time_and_nesting():
    run = _window_run()
    by_tid = spans.lines(run, 100.0, 110.0)
    assert spans.engine_tid(by_tid) == ENGINE
    step = next(s for s in by_tid[ENGINE] if s.t == pytest.approx(101.0))
    assert [c.name for c in step.children] == ["admit", "decode_step"]
    retire = next(x for x in step.walk() if x.name == "pool_retire")
    assert [c.name for c in retire.children] == ["pop_publish"]
    assert retire.self_s == pytest.approx(0.0003)
    # the decode step's children fill it
    assert step.children[1].self_s == pytest.approx(0.0, abs=1e-12)


def test_span_readers_find_nothing_without_engine_steps():
    # a program whose engine loop has no engine_step span
    run = _run([_x("decode_step", 101.0, 0.06, batch=32, finished=0),
                _x("reclaim_pass", 101.0, 0.001, tid=RECLAIMER)])
    run.device = DeviceTrace(0, 1e9, {}, {})
    run.profile_work = {"t": (100.0, 101.0)}
    for name in ("smr_step_share", "dispatch_ms", "prefill_step_ms",
                 "batch_rows", "dispatch_idle_share"):
        assert load_reader(name)(run) is None, name


def _idle_run():
    """Profiled window 100.0-100.1 s, the trace's clock 1e9 ns ahead of
    the monotonic one.  The chip runs 100.000-100.004 and 100.050-100.100.
    The engine thread: a step 100.000-100.060 whose dispatch covers
    100.002-100.030 and token sync 100.030-100.055; between steps after
    100.060.  The reclaimer: a pass 100.014-100.018, shorter than the
    engine's dispatch around it."""
    evs = [_x("engine_step", 100.0, 0.060, rows=32, prefill_chunks=0),
           _x("decode_step", 100.0, 0.058, batch=32, finished=0),
           _x("pool_reserve", 100.0, 0.002),
           _x("dispatch", 100.002, 0.028),
           _x("token_sync", 100.030, 0.025),
           _x("reclaim_pass", 100.014, 0.004, tid=RECLAIMER)]
    run = _run(evs)
    k = 1e9
    ops = [Op(100.000e9 + k, 0.004e9, "f", ""),
           Op(100.050e9 + k, 0.050e9, "f", "")]
    run.device = DeviceTrace(100.0e9 + k, 100.1e9 + k,
                             {"/device:TPU:0": ops}, {"/device:TPU:0": ops})
    run.mono_to_ns = k
    run.profile_work = {"t": (100.0, 100.1)}
    return run


def test_idle_is_put_down_to_the_engine_lines_innermost_span():
    run = _idle_run()
    # idle 100.004-100.050: dispatch 100.004-100.030, token sync to .050
    split = spans.idle_split(run)
    assert split == pytest.approx({"dispatch": 0.026, "token_sync": 0.020})
    # the shorter reclaimer span inside that idle takes no label from the
    # engine line; it is reported as overlap instead
    assert spans.idle_overlap(run) == pytest.approx({"reclaim_pass": 0.004})
    share = load_reader("dispatch_idle_share")(run)
    assert share == pytest.approx(26.0)


def test_engine_idle_between_steps():
    run = _idle_run()
    # the chip idles after the step too: move the second run of ops later
    ops = run.device.devices["/device:TPU:0"]
    ops[1] = Op(100.080e9 + 1e9, 0.020e9, "f", "")
    split = spans.idle_split(run)
    assert split[spans.ENGINE_IDLE] == pytest.approx(0.020)   # .060-.080
    assert split["token_sync"] == pytest.approx(0.025)


def test_outliers_are_logged(capsys):
    run = _window_run()
    run.spans.append(_x("pop_pass", 102.008, 0.3, tid=RECLAIMER,
                        readers=1, timed_out=[]))
    run.profile_work = {"t": (102.5, 107.5)}
    load_reader("smr_step_share")(run)
    err = capsys.readouterr().err
    assert err.count("long engine_step") == 3
    assert "dispatch 4" in err
    # the ping was set while the engine thread waited on the device
    assert "long pop_pass: 300.000 ms" in err
    assert "engine thread in token_sync at the ping" in err


def test_traced_smoke_run_reports_span_metrics(smoke_cell):
    from bench.harness import run_cell

    res = run_cell(smoke_cell("sc2-decode"), 2**31 + 29, 6.0, True,
                   allow_cpu=True, log=lambda _: None)
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in ("smr_step_share", "dispatch_ms", "batch_rows",
                 "decode_step_ms"):
        assert name in got, got
    assert 0 <= got["smr_step_share"]["value"] < 100
    assert 1 <= got["batch_rows"]["value"] <= 4        # smoke max_batch
    # the decode step holds its dispatch
    assert got["dispatch_ms"]["value"] < got["decode_step_ms"]["value"]
