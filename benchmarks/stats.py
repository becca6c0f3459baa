"""Window and percentile arithmetic of the harness, kept apart so that it
can be checked on made-up numbers."""

from __future__ import annotations

import math
from typing import List, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least ``q``
    per cent of all samples at or below it.  Always one of the samples."""
    if not values:
        raise ValueError("percentile of no samples")
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def window_summary(spans: List[Tuple[float, float]]) -> dict:
    """``spans`` are (start, end) of every collect of the window on one
    clock.  The window runs from the first start to the last end, and
    ``query_s`` is that whole time over the collects: a stall between two
    collects counts, as does a slow one."""
    if not spans:
        raise ValueError("the window completed no collect")
    start = min(s for s, _ in spans)
    end = max(e for _, e in spans)
    durations = [e - s for s, e in spans]
    return {"collects": len(spans), "window_s": end - start,
            "query_s": (end - start) / len(spans),
            "query_p50_s": percentile(durations, 50),
            "query_p95_s": percentile(durations, 95),
            "query_p99_s": percentile(durations, 99),
            "query_max_s": max(durations)}
