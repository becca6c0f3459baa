"""Device milliseconds a traced collect spends in the join execs' probe
programs (``jit_srt_<Join exec>_probe|probesearch|gather_*``: key search,
match expansion and the gather of the output columns; ``join_trace.py``).
Nothing where the trace holds no such program."""

import join_trace


def read(run):
    seconds = join_trace.probe_s_per_collect(run)
    return None if seconds is None else 1e3 * seconds
