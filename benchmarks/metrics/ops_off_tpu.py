"""Operators the planner placed off the TPU, summed over the cell's
queries: the "cannot run on TPU" lines of the placement report."""


def read(run):
    return float(sum(run["ops_off_tpu"].values()))
