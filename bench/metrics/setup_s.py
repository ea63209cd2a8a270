"""setup_s: seconds from process start to the window's opening -- JAX
start-up, compiles or compile-cache reads, the weights and the warm-up
traffic (host clock)."""


def read(run):
    return run.setup_s
