"""itl_p99_s: 99th percentile of every gap between two output tokens of
one request whose later token arrived in the window, as the benchmark saw
them arrive (host clock).  The p99 sits inside the upper mode of the gaps:
the decode steps that wait for a newly admitted request's prefill."""

from bench.stats import percentile


def read(run):
    return percentile(run.window_gaps(), 0.99)
