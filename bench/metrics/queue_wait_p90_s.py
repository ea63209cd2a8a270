"""queue_wait_p90_s (scheduler): 90th percentile of the engine's
``queue_wait_s`` samples recorded in the window -- submit to first pickup
by a worker."""

from bench.stats import percentile


def read(run):
    return percentile(run.in_window("queue_wait_s"), 0.90)
