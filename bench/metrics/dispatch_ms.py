"""dispatch_ms (paged forward): mean length of the ``dispatch`` spans of
the engine steps that start in the window, in milliseconds (program
spans): the host building a decode step's inputs and table and launching
every program of it, up to the call's return."""

from bench import spans


def read(run):
    ds = [x.dur for s in spans.window_steps(run) for x in s.walk()
          if x.name == "dispatch"]
    if not ds:
        return None
    return 1e3 * sum(ds) / len(ds)
