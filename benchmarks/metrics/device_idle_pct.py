"""Share of the traced span (first traced collect's start to the last
one's end) in which no operation ran on the device."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["span_s"])
