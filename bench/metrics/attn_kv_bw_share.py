"""attn_kv_bw_share (kernels): the K/V page bytes the paged-attention
kernel's DMAs moved in the profiled window, over the kernel's device time
there, as a share of the chip's HBM bandwidth, in percent.  The bytes are
the ``kv_bytes`` args of the ``dispatch`` and ``prefill_chunk`` spans that
start in the profiled window (program spans: each page at its size on the
device, padding included, as often as the kernel fetches it); the time is
that of ``jit_paged_attention_pallas`` in the device trace.  A program
whose spans carry no such bytes reads nothing."""

SPANS = ("dispatch", "prefill_chunk")


def read(run):
    if run.device is None or "t" not in run.profile_work:
        return None
    lo, hi = run.profile_work["t"]
    moved = sum(ev["args"]["kv_bytes"] for ev in run.spans
                if ev.get("name") in SPANS
                and "kv_bytes" in ev.get("args", {})
                and lo <= run.span_t0 + ev["ts"] / 1e6 < hi)
    kernel_s = run.device.module_s(r"paged_attention")
    if not moved or kernel_s <= 0:
        return None
    return 100.0 * moved / kernel_s / run.peaks["hbm_bytes_per_s"]
