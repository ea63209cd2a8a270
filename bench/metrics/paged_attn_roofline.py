"""paged_attn_roofline (kernels): the least time the chip needs for the
paged-attention work of the profiled window (``bench/work.py``: per layer,
each decode row's attended K/V and FLOPs, each prefill chunk's K/V once
and its rows' FLOPs; each call bound by the larger of FLOPs over peak and
bytes over bandwidth) as a share of the device time of the kernel's
jitted program in the trace, in percent."""

from bench import work


def read(run):
    if run.device is None or not run.profile_work.get("calls"):
        return None
    kernel_s = run.device.module_s(r"paged_attention")
    if kernel_s <= 0:
        return None
    need = work.roofline_time(run.profile_work["calls"], run.peaks)
    return 100.0 * run.model["layers"] * need["s"] / kernel_s
