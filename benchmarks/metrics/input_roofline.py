"""Share of the HBM roofline at which the device reads each query's input.

The least a collect can take on the device is the Arrow bytes of the columns
its SQL references over the chip's HBM bandwidth: the input must be read
once whatever implements the query.  That time, summed over the traced
collects, over the time the device was busy in them.  Memory-bound by
construction: it counts no operation, and it stays valid when a kernel is
replaced.  Nothing is returned where the device did no work at all."""


def read(run):
    trace = run["trace"]
    if not trace:
        return None
    busy = sum(c["busy_s"] for c in trace["collects"])
    if busy <= 0:
        return None
    peak = run["peaks"]["hbm_bytes_per_s"]
    least = sum(run["input_bytes"][c["query"]] / peak
                for c in trace["collects"])
    return 100.0 * least / busy
