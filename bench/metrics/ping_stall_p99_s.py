"""ping_stall_p99_s (block pool + SMR): 99th percentile of the pool's
``ping_stall_s`` histogram, reset when the window opens: how long a
publish-on-ping pass waited for every reader to publish.  Nothing to read
when no pass pinged."""


def read(run):
    h = run.pool_metrics.histogram("ping_stall_s")
    if not h.count:
        return None
    return h.percentile(0.99)
