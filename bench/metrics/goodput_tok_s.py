"""goodput_tok_s: output tokens of the window's requests that met both
limits -- time to first token (from due) and mean gap between their
tokens, as the benchmark saw them arrive -- over the window's length (host
clock).  The per-request
arithmetic is ``obs/slo.py``'s: a request meets the limits iff both hold;
a request that did not finish meets neither."""


def read(run):
    if run.loop != "open":
        return None
    slo = run.slo
    good = 0
    for t in run.window_requests():
        if not t.finished:
            continue
        ts = t.tok_t
        n = len(ts)
        gap = (ts[-1] - ts[0]) / (n - 1) if n > 1 else 0.0
        if ts[0] - t.due <= slo["ttft_s"] and gap <= slo["gap_s"]:
            good += n
    return good / run.seconds
