"""output_tok_s: every output token produced in the window, over the
window's length: the tokens the benchmark saw arrive in the window, on its
own clock (host clock)."""


def read(run):
    return run.window_tokens() / run.seconds
