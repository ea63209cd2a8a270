"""Observability-layer tests: the publish-on-flush Tracer (Chrome-trace
JSON, dual clock domains, zero-cost when disabled), the log-bucketed
MetricsRegistry (thread-local shards, concurrent merge, the locked
max_ping_stall recorder), and the pool/policy wiring that turns a
publish-on-ping pass into a span tree with one publish child per reader."""

import json
import threading

import pytest

from repro.obs import (PID_SIM, PID_WALL, Histogram, MetricsRegistry,
                       Tracer, summary_keys, validate_trace)
from repro.runtime.block_pool import BlockPool
from repro.runtime.reclaim import make_policy


# -- Tracer: spans, schema, clock domains --------------------------------


def test_span_nesting_same_thread():
    tr = Tracer()
    with tr.span("outer", cat="t"):
        with tr.span("inner", cat="t"):
            pass
    evs = {e["name"]: e for e in tr.to_dict()["traceEvents"]
           if e["ph"] == "X"}
    outer, inner = evs["outer"], evs["inner"]
    # X events on one thread nest by interval containment: Perfetto
    # reconstructs parenting from [ts, ts+dur] alone
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6


def test_async_span_pairs_and_schema():
    tr = Tracer()
    aid = tr.next_async_id()
    tr.async_begin("request", aid, cat="request", args={"rid": 1})
    tr.async_begin("queue_wait", aid, cat="request")
    tr.async_end("queue_wait", aid, cat="request")
    tr.instant("first_token", cat="request")
    tr.async_end("request", aid, cat="request")
    obj = tr.to_dict()
    evs = validate_trace(obj)          # schema: required keys, phases, ids
    b = [e for e in evs if e["ph"] == "b"]
    e_ = [e for e in evs if e["ph"] == "e"]
    assert len(b) == len(e_) == 2
    assert all(ev["id"] == f"0x{aid:x}" for ev in b + e_)
    # async nesting is LIFO per id: the inner pair closes first
    names_in_order = [ev["name"] for ev in evs if ev["ph"] in ("b", "e")]
    assert names_in_order == ["request", "queue_wait", "queue_wait",
                              "request"]


def test_clock_domains_separate_pids():
    tr = Tracer()
    tr.complete("wall_work", tr.now_us(), 5.0, cat="t")
    tr.complete("sim_work", Tracer.sim_ts(4000), Tracer.sim_ts(2000),
                cat="t", pid=PID_SIM,
                tid=tr.tid_named("sim t0", PID_SIM))
    evs = tr.to_dict()["traceEvents"]
    wall = next(e for e in evs if e["name"] == "wall_work")
    sim = next(e for e in evs if e["name"] == "sim_work")
    assert wall["pid"] == PID_WALL and sim["pid"] == PID_SIM
    # 1 GHz convention: 4000 cycles -> 4 us
    assert sim["ts"] == pytest.approx(4.0) and sim["dur"] == pytest.approx(2.0)
    # both domains announce themselves via process_name metadata
    named = {e["pid"] for e in evs if e["name"] == "process_name"}
    assert named == {PID_WALL, PID_SIM}


def test_publish_on_flush_and_concurrent_export(tmp_path):
    tr = Tracer()
    barrier = threading.Barrier(4)

    def worker(i):
        barrier.wait()
        for j in range(50):
            tr.complete(f"w{i}", float(j), 1.0, cat="t")
        tr.flush()                     # the explicit safepoint publish

    ts = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    out = tmp_path / "t.json"
    obj = tr.export(out)
    validate_trace(obj)
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == 4 * 50
    validate_trace(json.loads(out.read_text()))   # round-trips through disk


def test_disabled_tracer_is_free(monkeypatch):
    import jax.profiler

    made = []

    class CountingAnnotation(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", CountingAnnotation)
    tr = Tracer(enabled=False)
    # the disabled span is one shared singleton: no per-call allocation
    assert tr.span("a") is tr.span("b")
    with tr.span("a") as sp:
        sp.set_args({"n": 1})
        tr.instant("x")
        tr.complete("y", 0.0, 1.0)
        tr.async_begin("z", 1)
        tr.async_end("z", 1)
    assert tr.events == 0
    # no private buffer was ever created for this thread
    assert tr._buffers == []
    # and no profiler annotation, where an enabled span makes one
    assert made == []
    with Tracer().span("on"):
        pass
    assert made == ["on"]


def test_span_lands_in_the_profiler_trace_from_a_thread(tmp_path):
    import time

    import jax
    from jax.profiler import ProfileData

    tr = Tracer()

    def work():
        with tr.span("worker_span", cat="t"):
            time.sleep(0.03)

    jax.profiler.start_trace(str(tmp_path))
    try:
        th = threading.Thread(target=work, name="engine-7")
        th.start()
        th.join()
    finally:
        jax.profiler.stop_trace()
    x = [e for e in tr.to_dict()["traceEvents"]
         if e["ph"] == "X" and e["name"] == "worker_span"]
    assert len(x) == 1
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [e for p in ProfileData.from_file(str(path)).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name == "worker_span"]
    assert len(host) == 1
    # the same span on the profiler's clock: its length agrees
    assert host[0].duration_ns / 1e3 == pytest.approx(x[0]["dur"], rel=0.1)


def test_validate_trace_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace([])                         # not the object form
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X", "ts": 0}]})  # keys
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"name": "a", "ph": "X", "ts": 0, "pid": 1, "tid": 1}]})  # dur
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [
            {"name": "a", "ph": "b", "ts": 0, "pid": 1, "tid": 1}]})  # id


# -- MetricsRegistry: shards, merge, percentiles -------------------------


def test_histogram_concurrent_shard_merge():
    h = Histogram("lat_s")
    n_threads, per_thread = 8, 2000
    barrier = threading.Barrier(n_threads)

    def worker(i):
        barrier.wait()
        for j in range(per_thread):
            # thread i's samples live in [i+1, i+2) ms: known count and max
            h.record((i + 1) * 1e-3 + (j % 97) * 1e-6)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = h.snapshot()                # merges every shard
    assert snap["count"] == n_threads * per_thread
    assert snap["max"] == pytest.approx(8e-3 + 96e-6)
    assert 0 < snap["p50"] <= snap["p99"] <= snap["p999"] <= snap["max"]


def test_record_locked_returns_running_max():
    h = Histogram("stall_s")
    assert h.record_locked(0.5) == 0.5
    assert h.record_locked(0.1) == 0.5   # monotone: never regresses
    assert h.record_locked(0.9) == 0.9
    assert h.count == 3


def test_registry_flat_row_shape():
    reg = MetricsRegistry()
    for v in (0.010, 0.020, 0.040):
        reg.record("ttft_s", v)
    row = reg.flat(["ttft_s"], fields=("p50", "p99", "max"))
    assert set(row) == {"ttft_p50_s", "ttft_p99_s", "ttft_max_s"}
    assert row["ttft_max_s"] == pytest.approx(0.040)
    # default fields: full summary, with count/mean columns -- count is a
    # sample count so it drops the unit suffix, mean keeps it
    full = reg.flat(["ttft_s"])
    assert set(full) == {"ttft_count", "ttft_mean_s", "ttft_p50_s",
                         "ttft_p99_s", "ttft_p999_s", "ttft_max_s"}
    assert full["ttft_count"] == 3
    assert full["ttft_mean_s"] == pytest.approx((0.010 + 0.020 + 0.040) / 3)
    # the snapshot field set is a stable contract for results-row readers
    assert summary_keys == ("count", "mean", "p50", "p99", "p999", "max")


def test_registry_reset_clears_warmup():
    reg = MetricsRegistry()
    reg.record("ttft_s", 30.0)           # a jit-compile-sized outlier
    reg.reset()
    reg.record("ttft_s", 0.002)
    snap = reg.histogram("ttft_s").snapshot()
    assert snap["count"] == 1
    assert snap["max"] == pytest.approx(0.002)


# -- pool wiring: the split-brain fix and the ping span tree -------------


def test_pool_stall_scalar_equals_histogram_max():
    pool = BlockPool(32, n_engines=2, reclaim_threshold=4)
    for v in (0.002, 0.001, 0.005):
        pool.record_ping_stall(v)
    assert pool.stats.max_ping_stall_s == pytest.approx(0.005)
    assert pool.metrics.histogram("ping_stall_s").max == \
        pool.stats.max_ping_stall_s
    assert pool.metrics.histogram("ping_stall_s").count == 3


def test_pop_pass_span_tree_one_child_per_reader():
    tr = Tracer()
    # pop_every forces the publish-on-ping fallback deterministically, so
    # the trace is guaranteed to contain the paper's mechanism
    pool = BlockPool(32, n_engines=3, reclaim_threshold=2,
                     pressure_factor=1,
                     policy=make_policy(None, pop_every=1), tracer=tr)
    for eid in (1, 2):                   # readers exist and are quiescent
        pool.start_step(eid)
        pool.end_step(eid)
    for _ in range(3):
        pool.start_step(0)
        b = pool.allocate(0, 4)
        pool.retire(0, b)
        pool.end_step(0)
        pool.reclaim(0)
    evs = validate_trace(tr.to_dict())
    passes = [e for e in evs if e["name"] == "pop_pass"]
    pubs = [e for e in evs if e["name"] == "publish"]
    acks = [e for e in evs if e["name"] == "pop_ack"]
    assert passes, "forced POP passes must appear in the trace"
    assert len(acks) == len(passes)
    for p in passes:
        kids = [e for e in pubs if e["args"]["pass"] == p["args"]["pass"]]
        # one publish child per *other* reader slot (engines 1 and 2)
        assert len(kids) == p["args"]["readers"] == 2
        for k in kids:
            assert k["ts"] >= p["ts"] - 1e-6
            assert k["ts"] + k["dur"] <= p["ts"] + p["dur"] + 1e-6
    # block lifecycle instants ride on the same trace
    assert any(e["name"] == "block_alloc" for e in evs)
    assert any(e["name"] == "block_free" for e in evs)
    assert pool.check_no_leaks()
