"""smr_step_share (block pool + SMR): the engine thread's own time in the
pool's reader work -- ``pool_reserve``, ``pool_tripwire``, ``pool_retire``
and ``pop_publish``, each without the spans nested in it -- inside the
``engine_step`` spans that start in the window, over those steps' whole
time, in percent (program spans).  It also writes the window's longest
steps and publish-on-ping passes to standard error."""

from bench import spans


def read(run):
    steps = spans.window_steps(run)
    if not steps:
        return None
    spans.log_outliers(run, steps)
    smr = sum(x.self_s for s in steps for x in s.walk()
              if x.name in spans.SMR)
    return 100.0 * smr / sum(s.dur for s in steps)
