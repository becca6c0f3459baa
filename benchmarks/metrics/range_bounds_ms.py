"""Milliseconds a traced collect spends finding a range exchange's bounds
on the host's clock: the spans ``srt:sort:range_bounds`` (one per range
exchange that samples its map outputs: the sample's gather, its sort and
the pick of the boundary rows), time the device mostly idles through.
0 where the program marks its sorts (``srt:sort:compute``) and no exchange
sampled anything (one map output's worth of rows goes to one partition
unsampled); nothing where the program has neither span."""

import program_spans

BOUNDS_SPAN = "srt:sort:range_bounds"
SORT_SPAN = "srt:sort:compute"


def read(run):
    reduced = program_spans.for_run(run)
    if reduced is None:
        return None
    spans = reduced["spans"]
    if BOUNDS_SPAN not in spans and SORT_SPAN not in spans:
        return None
    seconds = spans.get(BOUNDS_SPAN, {"s": 0.0})["s"]
    return 1e3 * seconds / reduced["collects"]
