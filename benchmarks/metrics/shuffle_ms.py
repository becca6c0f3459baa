"""Device milliseconds a traced collect spends in the local exchanges'
programs (``jit_srt_ShuffleExchangeExec_*``: partition ids, the ordering
or split of each map output and the cut into pieces, of every hash and
range exchange of the plan together; ``exec_trace.py``).  Nothing where
the trace holds no such program (every exchange coalesced or rode the
mesh plane)."""

import exec_trace


def read(run):
    seconds = exec_trace.exec_s_per_collect(run, "ShuffleExchangeExec")
    return None if seconds is None else 1e3 * seconds
