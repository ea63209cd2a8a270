"""pages_freed_per_s (block pool + SMR): pages the reclamation policy
returned to the free list in the window, per second (the pool's ``freed``
counter)."""


def read(run):
    return (run.pool_close["freed"] - run.pool_open["freed"]) / run.seconds
