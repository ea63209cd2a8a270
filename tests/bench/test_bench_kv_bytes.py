"""The readers of ``kv_pad_share`` and ``attn_kv_bw_share`` checked by
hand: the pool's padding from the store's two counters (none at head dim
128, 37.5 % where 160 lanes pad to 256), the kernel's bytes from the span
args of the profiled window alone over its device time, nothing read
from a program that counts nothing, and a traced smoke run of
``slm12-decode`` that reports the pool's share."""

import pytest

from bench.devtrace import DeviceTrace, Op
from bench.harness import Run
from bench.recorder import RecordingRegistry
from bench.registry import load_reader

PEAKS = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}


def _pool_run(device, element):
    reg = RecordingRegistry()
    if device is not None:
        reg.counter("kv_pool_device_bytes").inc(device)
        reg.counter("kv_pool_element_bytes").inc(element)
    return Run(cell=None, seed=0, seconds=10.0, samples=reg)


@pytest.mark.parametrize("device,element,want", [
    (2 * 4 * 2048 * 16 * 128 * 2, 2 * 4 * 2048 * 16 * 128 * 2, 0.0),
    (2 * 8 * 4096 * 16 * 256 * 2, 2 * 8 * 4096 * 16 * 160 * 2, 37.5),
], ids=["hd128", "hd160_padded"])
def test_kv_pad_share_by_hand(device, element, want):
    assert load_reader("kv_pad_share")(_pool_run(device, element)) == \
        pytest.approx(want)


def test_kv_pad_share_reads_nothing_without_the_counters():
    assert load_reader("kv_pad_share")(_pool_run(None, None)) is None


def _x(name, t, **args):
    return {"name": name, "ph": "X", "cat": "t", "ts": t * 1e6,
            "dur": 2e3, "pid": 1, "tid": 1, "args": args}


def _bw_run(spans, kernel_ns):
    # the profiled window is 100-105 s; the kernel ran kernel_ns in it
    ops = [Op(1000, kernel_ns, "jit_paged_attention_pallas(1)",
              "jit_paged_attention_pallas")]
    run = Run(cell=None, seed=0, seconds=10.0, peaks=PEAKS)
    run.device = DeviceTrace(0, 5e9, {"/device:TPU:0": ops},
                             {"/device:TPU:0": ops})
    run.profile_work["t"] = (100.0, 105.0)
    run.spans, run.span_t0 = spans, 0.0
    return run


def test_attn_kv_bw_share_reads_the_profiled_windows_spans():
    spans = [_x("dispatch", 99.9, kv_bytes=10**12),      # before the window
             _x("dispatch", 101.0, kv_bytes=3 * 10**8, live_pages=9),
             _x("prefill_chunk", 102.0, kv_bytes=10**8),
             _x("token_sync", 102.5),
             _x("dispatch", 105.0, kv_bytes=10**12)]     # at its close
    share = load_reader("attn_kv_bw_share")(_bw_run(spans, 1e6))
    # 4e8 bytes in 1 ms is 400 GB/s, half of 800: the spans outside the
    # window, whose bytes alone would read far above 100 %, are left out
    assert share == pytest.approx(50.0)


@pytest.mark.parametrize("spans,kernel_ns", [
    ([_x("dispatch", 101.0, live_pages=9)], 1e6),        # no kv_bytes
    ([_x("dispatch", 101.0, kv_bytes=10**8)], 0),        # no kernel time
], ids=["no_bytes", "no_kernel"])
def test_attn_kv_bw_share_reads_nothing_without_bytes_or_time(spans,
                                                             kernel_ns):
    assert load_reader("attn_kv_bw_share")(_bw_run(spans, kernel_ns)) is None


def test_traced_smoke_run_reports_the_pools_padding(smoke_cell):
    from bench.harness import run_cell

    res = run_cell(smoke_cell("slm12-decode"), 2**31 + 47, 6.0, True,
                   allow_cpu=True, log=lambda _: None)
    assert res["correct"], res["checks"]
    # nothing is padded off a TPU; no device trace, so no kernel share
    assert res["metrics"]["kv_pad_share"]["value"] == 0.0
    assert "attn_kv_bw_share" not in res["metrics"]
