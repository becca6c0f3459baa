"""Share of the HBM roofline at which the answer is sorted.

The least a collect's sort can take on the device is the bytes it has to
move (``sort_bytes.py``: the rows of the reference's answer at the Arrow
width of its columns, read once and written once; nothing from the
program) over the chip's HBM bandwidth; that time over the device time of
the ``SortExec`` programs a collect (``sort_ms``).  Memory-bound by
construction: it counts no comparison and reads the same work whatever
implements the sort.  Nothing where the trace holds no sort program or the
tables were not built by a generator that keeps them; never 0."""

import exec_trace
import sort_bytes


def read(run):
    seconds = exec_trace.exec_s_per_collect(run, "SortExec")
    if not seconds:
        return None
    moved = sort_bytes.sort_bytes_per_collect(run)
    if not moved:
        return None
    least = moved / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
