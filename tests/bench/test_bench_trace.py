"""The benchmark's yardstick checked by hand: the trace reduction (busy
time, idle gaps, module time, roofline share, ``mfu``) on a synthetic
trace, ``bench/work.py`` against a hand count of one decode row and one
prefill chunk, the traffic generator's seeding, and the window read back
from a trace the profiler really wrote."""

import json
import random

import pytest

from bench import devtrace, traffic, work
from bench.devtrace import DeviceTrace, Op
from bench.registry import BENCH, load_reader
from bench.stats import percentile

SC2 = dict(layers=16, d_model=4608, n_heads=36, n_kv_heads=4, head_dim=128,
           d_ff=18432, vocab=49152, act="gelu")
PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}


def _trace():
    # window 0..1000 ns; program runs (start, dur): kernel 100-300, qkv
    # 250-400 (overlapping the kernel), mlp 600-700, a kernel run partly
    # outside, 950-1100; each program one op here
    runs = [Op(100, 200, "jit_paged_attention_pallas(12)",
               "jit_paged_attention_pallas"),
            Op(250, 150, "jit__qkv(3)", "jit__qkv"),
            Op(600, 100, "jit__out_mlp(4)", "jit__out_mlp"),
            Op(950, 150, "jit_paged_attention_pallas(12)",
               "jit_paged_attention_pallas")]
    ops = [Op(o.start_ns, o.dur_ns, "fusion", "") for o in runs]
    return DeviceTrace(0, 1000, {"/device:TPU:0": ops},
                       {"/device:TPU:0": runs})


def test_busy_idle_and_module_time_by_hand():
    dt = _trace()
    # union: 100-400 (300), 600-700 (100), 950-1000 (50) = 450 ns
    assert dt.busy_s() == pytest.approx(450e-9)
    assert dt.window_s == pytest.approx(1000e-9)
    assert dt.idle_gaps() == [(0, 100), (400, 600), (700, 950)]
    # kernel: 200 + 50 clipped
    assert dt.module_s("paged_attention") == pytest.approx(250e-9)
    top = dict(dt.top_modules())
    assert top["jit__qkv"] == pytest.approx(150e-9)


def test_busy_time_is_averaged_over_chips():
    a = [Op(0, 500, "x", "m")]
    b = [Op(0, 100, "x", "m")]
    dt = DeviceTrace(0, 1000, {"/device:TPU:0": a, "/device:TPU:1": b},
                     {"/device:TPU:0": a, "/device:TPU:1": b})
    assert dt.busy_s() == pytest.approx(300e-9)
    assert dt.module_s("m") == pytest.approx(300e-9)


def test_decode_row_work_by_hand():
    # 1000 attended positions: K+V = 1000 * 2 * 4 heads * 128 * 2 B,
    # q and out = 2 * 36 * 128 * 4 B; FLOPs 4 * 36 * 128 * 1000
    f, b = work.decode_row(SC2, 1000)
    assert f == 4 * 36 * 128 * 1000 == 18_432_000
    assert b == 1000 * 2048 + 36_864 == 2_084_864


def test_prefill_chunk_work_by_hand():
    # positions 512..575: row p attends p + 1, so 513..576, sum 34_848;
    # K/V of 576 positions read once, q and out of 64 rows
    f, b = work.prefill_chunk(SC2, 512, 576)
    assert f == 4 * 36 * 128 * 34_848
    assert b == 576 * 2048 + 64 * 36_864


def test_roofline_takes_the_larger_bound_per_call():
    dec = work.decode_row(SC2, 1000)          # bytes-bound
    pre = work.prefill_chunk(SC2, 4096, 4160)  # FLOP-bound
    t = work.roofline_time([dec, pre], PEAKS)
    assert t["bytes_bound_s"] == pytest.approx(dec[1] / 800e9)
    assert t["flops_bound_s"] == pytest.approx(pre[0] / 200e12)
    assert t["s"] == pytest.approx(t["bytes_bound_s"] + t["flops_bound_s"])


def test_token_flops_by_hand():
    per_layer = (4608 * 36 * 128 + 2 * 4608 * 4 * 128 + 36 * 128 * 4608
                 + 2 * 4608 * 18432)
    want = 16 * (2 * per_layer + 4 * 36 * 128 * 100) + 2 * 4608 * 49152
    assert work.token_flops(SC2, 100, sampled=True) == want


class _Run:
    """The parts of a run the device readers look at."""

    def __init__(self, device, calls, decode_ctx, prefill_pos):
        self.device = device
        self.model = SC2
        self.peaks = PEAKS
        self.profile_work = {"calls": calls, "decode_ctx": decode_ctx,
                             "prefill_pos": prefill_pos}


def test_device_readers_on_a_synthetic_trace():
    dt = _trace()
    dec = work.decode_row(SC2, 1000)
    run = _Run(dt, [dec], [1000], [])
    idle = load_reader("device_idle_share")(run)
    assert idle == pytest.approx(55.0)
    roof = load_reader("paged_attn_roofline")(run)
    want = 100 * 16 * (dec[1] / 800e9) / 250e-9
    assert roof == pytest.approx(want)
    mfu = load_reader("mfu")(run)
    assert mfu == pytest.approx(
        100 * work.token_flops(SC2, 1000, sampled=True) / (1e-6 * 200e12))


def test_device_readers_find_nothing_without_a_trace():
    run = _Run(None, [], [], [])
    for name in ("device_idle_share", "paged_attn_roofline", "mfu"):
        assert load_reader(name)(run) is None


def test_trace_window_read_back_from_a_profile(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(devtrace.WINDOW):
        for _ in range(3):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    dt = devtrace.load(str(path))
    assert dt is not None and 0 < dt.window_s < 60
    assert dt.devices == {}          # no TPU plane on the CPU


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    random.Random(0).shuffle(xs)
    assert percentile(xs, 0.90) == 90
    assert percentile(xs, 0.95) == 95
    assert percentile([3.0], 0.9) == 3.0
    assert percentile([], 0.9) is None


def test_seeds_permute_the_same_work():
    mix = dict(json.loads((BENCH / "traffic" / "chat-shared.json")
                          .read_text()), name="chat-shared")
    a = traffic.open_schedule(mix, 3.0, 50.0, 1, 49152, "window")
    b = traffic.open_schedule(mix, 3.0, 50.0, 2**31 + 9, 49152, "window")
    assert len(a) == len(b) == 150
    key = lambda q: (q.tenant, len(q.prompt), q.max_new)   # noqa: E731
    assert sorted(map(key, a)) == sorted(map(key, b))
    assert [key(q) for q in a] != [key(q) for q in b]
    assert a[-1].t_s < 50.0 and b[-1].t_s < 50.0
    gaps = lambda s: sorted(round(y.t_s - x.t_s, 9)          # noqa: E731
                            for x, y in zip(s, s[1:]))
    assert gaps(a)[:-1] != [] and len(gaps(a)) == len(gaps(b))
    again = traffic.open_schedule(mix, 3.0, 50.0, 1, 49152, "window")
    assert [q.prompt for q in again] == [q.prompt for q in a]
    # a tenant's requests open with its shared prefix
    pre = traffic.tenant_prefixes(mix, 49152, 1)
    q = a[0]
    assert q.prompt[:q.shared] == pre[q.tenant] and q.shared == 512


def test_ping_stall_reader_reads_the_pools_histogram():
    from repro.obs import MetricsRegistry

    class _R:
        pool_metrics = MetricsRegistry()

    run = _R()
    read = load_reader("ping_stall_p99_s")
    assert read(run) is None                    # no pass pinged
    for v in [0.001] * 99 + [0.5]:
        run.pool_metrics.histogram("ping_stall_s").record_locked(v)
    # the histogram's upper-edge estimate of the 99th percentile
    assert 0.001 <= read(run) < 0.0012


def test_closed_loop_sizes_are_the_same_for_every_seed():
    mix = dict(json.loads((BENCH / "traffic" / "decode-long.json")
                          .read_text()), name="decode-long")
    a = traffic.closed_requests(mix, 1, 49152)
    b = traffic.closed_requests(mix, 2**31 + 9, 49152)
    assert len(a) == len(b) == mix["requests"]
    key = lambda q: (len(q.prompt), q.max_new)              # noqa: E731
    # the same sizes in the same order: any prefix a window reaches is
    # the same work; only the token ids change with the seed
    assert [key(q) for q in a] == [key(q) for q in b]
    assert [q.prompt for q in a] != [q.prompt for q in b]
    assert all(64 <= len(q.prompt) <= 192 and 128 <= q.max_new <= 512
               for q in a)
    assert [q.prompt for q in traffic.closed_requests(mix, 1, 49152)] == \
        [q.prompt for q in a]


def _seen(*token_times):
    from bench.harness import Tracked

    return [Tracked(traffic.Req(0.0, -1, 0, [1], len(ts)), "window", 0.0,
                    tok_t=list(ts)) for ts in token_times]


def test_window_tokens_and_gaps_by_hand():
    from bench.harness import Run

    run = Run(cell=None, seed=0, seconds=10.0, t0=100.0, t1=110.0)
    # one request before, across and after the window; one inside it
    run.tracked = _seen([95.0, 99.5, 100.5, 101.0], [104.0, 104.1, 104.4],
                        [109.0, 110.0, 111.0])
    # tokens at 100.5, 101.0, 104.0, 104.1, 104.4, 109.0
    assert run.window_tokens() == 6
    assert load_reader("output_tok_s")(run) == pytest.approx(0.6)
    # gaps whose later token is in the window: 1.0, 0.5, 0.1, 0.3
    assert sorted(run.window_gaps()) == pytest.approx([0.1, 0.3, 0.5, 1.0])
    assert load_reader("itl_p99_s")(run) == pytest.approx(1.0)


def test_observer_stamps_every_token_it_sees():
    import threading
    import time
    from types import SimpleNamespace

    from bench.harness import Run, _observe

    run = Run(cell=None, seed=0, seconds=1.0)
    reqs = [SimpleNamespace(out=[], done=threading.Event())
            for _ in range(2)]
    run.tracked = _seen([], [])
    for t, r in zip(run.tracked, reqs):
        t.r = r
    stop = threading.Event()
    th = threading.Thread(target=_observe, args=(run, stop))
    th.start()
    for i in range(5):
        reqs[0].out.append(i)
        if i < 3:
            reqs[1].out.append(i)
        time.sleep(0.02)
    reqs[1].done.set()
    reqs[0].out.append(5)            # the last token lands with the stop
    stop.set()
    th.join()
    assert [len(t.tok_t) for t in run.tracked] == [6, 3]
    ts = run.tracked[0].tok_t
    assert ts == sorted(ts) and ts[3] - ts[0] > 0.04
