"""Device milliseconds a traced collect spends in the window exec's
programs (``jit_srt_WindowExec_*``: the partition sort and the window
evaluation, fused or not; ``exec_trace.py``).  Nothing where the trace
holds no such program."""

import exec_trace


def read(run):
    seconds = exec_trace.exec_s_per_collect(run, "WindowExec")
    return None if seconds is None else 1e3 * seconds
