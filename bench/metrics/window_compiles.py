"""window_compiles (paged forward): programs JAX compiled or read from the
persistent compilation cache inside the window -- each a new shape the
warm-up did not cover, such as a new (batch, table width) of the paged
kernel."""


def read(run):
    return len(run.compiles.between(run.t0, run.t1))
