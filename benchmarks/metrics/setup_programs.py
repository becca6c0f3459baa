"""Programs XLA compiled before the window (persistent-cache hits are not
among them)."""


def read(run):
    return float(run["compile"]["setup"]["programs"])
