"""mfu (device, whole step): model FLOPs of the tokens computed in the
profiled window -- prefill and decode, each with its attention at its own
context (``bench/work.py``) -- over the window's length times the chip's
peak bf16 FLOP/s, in percent."""

from bench import work


def read(run):
    if run.device is None or "decode_ctx" not in run.profile_work:
        return None
    m, pw = run.model, run.profile_work
    flops = sum(work.token_flops(m, c, sampled=True) for c in pw["decode_ctx"])
    flops += sum(work.token_flops(m, p + 1, sampled=False)
                 for p in pw["prefill_pos"])
    if not flops:
        return None
    return 100.0 * flops / (run.device.window_s * run.peaks["bf16_flops_per_s"])
