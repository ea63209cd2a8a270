"""dispatch_idle_share (device): the share of the profiled window in which
the chip ran nothing while the engine thread's innermost span was
``dispatch``, in percent (device trace, the program's spans on its clock):
the device waiting on the host's launches.  It also writes the window's
whole idle time, by the engine thread's innermost span and by the spans
other threads had open, to standard error."""

from bench import spans


def read(run):
    split = spans.idle_split(run)
    if split is None:
        return None
    spans.log_idle(run, split)
    return 100.0 * split.get("dispatch", 0.0) / run.device.window_s
