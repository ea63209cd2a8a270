"""ttft_p90_s: 90th percentile of the time to first token over every
request due in the window, timed from when it was due to when the
benchmark saw its first token (host clock).  A
request that never produced all its tokens counts as missing: its time is
at least the wait, from due to the end of the grace after the close."""

from bench.harness import GRACE_S
from bench.stats import percentile


def read(run):
    if run.loop != "open":
        return None
    ttfts = []
    for t in run.window_requests():
        if t.finished:
            ttfts.append(t.tok_t[0] - t.due)
        else:
            ttfts.append(run.t1 + GRACE_S - t.due)
    return percentile(ttfts, 0.90)
