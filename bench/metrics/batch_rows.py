"""batch_rows (scheduler): mean number of requests decoded by the window's
``engine_step`` spans that decoded any (the span's ``rows``; program
spans)."""

from bench import spans


def read(run):
    rows = [s.args["rows"] for s in spans.window_steps(run)
            if s.args.get("rows", 0) >= 1]
    if not rows:
        return None
    return sum(rows) / len(rows)
