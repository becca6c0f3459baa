"""Slowest collect of the (traced) window, by the host's clock: a single
stalled collect, which the 95th percentile of fifty does not move, shows
here.  (Every run, traced or not, also prints it on its ``window`` line,
with a ``slow_collect`` line for each collect over twice its query's
median.)"""


def read(run):
    return run["window"]["query_max_s"]
