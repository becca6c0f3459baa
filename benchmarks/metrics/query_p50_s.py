"""Median collect of the (traced) window, by the host's clock: the steady
statistic beside ``query_s``."""


def read(run):
    return run["window"]["query_p50_s"]
