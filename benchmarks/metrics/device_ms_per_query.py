"""Milliseconds in which an operation ran on the device, per traced
collect: the union of the device-op intervals inside each collect."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    collects = trace["collects"]
    return 1e3 * sum(c["busy_s"] for c in collects) / len(collects)
