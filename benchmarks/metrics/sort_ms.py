"""Device milliseconds a traced collect spends in the sort exec's programs
(``jit_srt_SortExec_*``: the permutation over the ORDER BY keys and the
gather of every column; the sort of a range exchange's sampled bounds is
one of them; ``exec_trace.py``).  Nothing where the trace holds no such
program."""

import exec_trace


def read(run):
    seconds = exec_trace.exec_s_per_collect(run, "SortExec")
    return None if seconds is None else 1e3 * seconds
