"""Share of the HBM roofline at which the joins' probes run.

The least a collect's probes can take on the device is the bytes they have
to move (``join_bytes.py``: the rows that reach each probe at the width of
the columns the query references, the build rows that pass the dimension's
filter, the matched rows at the width handed on - from the generated tables
by the reference's semantics, nothing from the program) over the chip's HBM
bandwidth; that time over the device time of the probe programs a collect
(``join_probe_ms``).  Memory-bound by construction: it counts no operation
and reads the same work whatever implements the probe.  Nothing where the
trace holds no probe program or the tables were not built by a generator
that keeps them; never 0."""

import join_bytes
import join_trace


def read(run):
    seconds = join_trace.probe_s_per_collect(run)
    if not seconds:
        return None
    moved = join_bytes.probe_bytes_per_collect(run)
    if not moved:
        return None
    least = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
