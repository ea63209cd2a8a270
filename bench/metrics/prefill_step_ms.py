"""prefill_step_ms (engine loop): mean length of the window's
``engine_step`` spans that ran at least one prefill chunk, in milliseconds
(program spans): the steps that make the upper mode of the token gaps."""

from bench import spans


def read(run):
    ds = [s.dur for s in spans.window_steps(run)
          if s.args.get("prefill_chunks", 0) >= 1]
    if not ds:
        return None
    return 1e3 * sum(ds) / len(ds)
