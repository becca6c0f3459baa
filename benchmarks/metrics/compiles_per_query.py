"""XLA backend compiles inside the window (``jax.monitoring``), per collect
completed: the steady state is 0, and a cell that reads more compiles
programs anew in every collect.  Per collect, so that a faster query that
fits more collects into the window does not read as more compiling."""


def read(run):
    return run["compile"]["window"]["programs"] / run["window"]["collects"]
