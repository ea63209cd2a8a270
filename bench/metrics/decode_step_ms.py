"""decode_step_ms (engine loop): mean length of the program's
``decode_step`` spans that start in the window, in milliseconds.  The span
closes after the step's blocking read of the sampled tokens."""


def read(run):
    spans = run.spans_named("decode_step")
    if not spans:
        return None
    return 1e3 * sum(s["dur_s"] for s in spans) / len(spans)
