"""What a profiler trace (``.xplane.pb``) says about a run on several chips:
per device plane the seconds programs ran on it inside the traced span, and
the seconds and runs of the mesh exchange's programs on it.

A cell whose configuration says ``spark.executor.instances: n`` keeps
partition t on chip t, so the trace holds one ``/device:TPU:<t>`` plane per
executor, each with the stage programs of its partitions, and the programs
that move rows between chips are named ``jit_srt_MeshExchange_*`` (one
compiled program per exchange, launched once over all chips: one event per
plane).  ``reduce_trace.py`` averages over the planes; this file keeps them
apart.  It reads the ``XLA Modules`` line alone (one event per program run;
a chip is busy while a program runs on it): a four-chip trace holds a
million and a half device operations and this is the third reduction of the
file in a traced run, whose set-up already takes minutes.  A trace in which
fewer than two planes worked (one executor, or a program from before the
placement existed) reduces to ``None`` and the readers report nothing.

It imports ``jax.profiler`` (through ``reduce_trace``) and nothing of the
program.  ``python benchmarks/mesh_trace.py <file>`` prints the reduction.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, Optional, Tuple

import program_spans
import reduce_trace as RT

EXCHANGE_PROGRAM = "jit_srt_MeshExchange_"
EXCHANGE_SPAN = "srt:shuffle:mesh_exchange"
NS = 1e-9

_REDUCED: Dict[Tuple[str, float], Optional[dict]] = {}


def reduce(path: str, prefix: str = "bench:") -> Optional[dict]:
    """Per chip, inside the traced span (first collect's start to the last
    one's end): ``busy_s`` (union of its program runs), ``exchange_s`` and
    ``exchange_runs`` (its ``jit_srt_MeshExchange_*`` program runs) and
    ``stage_runs`` (every other ``jit_srt_`` program run)."""
    data = RT.load(path)
    collects = []
    for plane in data.planes:
        if RT.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                collects.extend((s, e) for name, s, e in RT._events(line)
                                if name.startswith(prefix))
    if not collects:
        return None
    lo, hi = min(s for s, _ in collects), max(e for _, e in collects)
    chips = {}
    for plane in data.planes:
        if not RT.DEVICE_PLANE.match(plane.name):
            continue
        modules = [ev for line in plane.lines
                   if line.name in RT.MODULE_LINES for ev in RT._events(line)]
        busy = RT.total(RT.clip(
            RT.merge([(s, e) for _, s, e in modules]), lo, hi)) * NS
        if busy <= 0:
            continue    # a chip of the host that this program never used
        row = {"busy_s": busy, "exchange_s": 0.0, "exchange_runs": 0,
               "stage_runs": 0}
        for name, s, e in modules:
            if not lo <= s < hi:
                continue
            if name.startswith(EXCHANGE_PROGRAM):
                row["exchange_s"] += (e - s) * NS
                row["exchange_runs"] += 1
            elif name.startswith(program_spans.CACHE_PROGRAM):
                row["stage_runs"] += 1
        chips[plane.name] = row
    if len(chips) < 2:
        return None
    return {"collects": len(collects), "span_s": (hi - lo) * NS,
            "chips": chips}


def for_run(run: dict) -> Optional[dict]:
    """The reduction of this run's trace, parsed once; None without a
    trace or where fewer than two chips worked."""
    path = program_spans.trace_file(run)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED.clear()
        _REDUCED[key] = reduce(path)
        if _REDUCED[key] is not None:
            print("mesh_trace: " + json.dumps(_REDUCED[key]),
                  file=sys.stderr)
    return _REDUCED[key]


def collective_s_per_collect(reduced: Optional[dict]) -> Optional[float]:
    """Seconds of ``jit_srt_MeshExchange_*`` programs a collect on the chip
    that spent most in them; None where no such program ran."""
    if reduced is None:
        return None
    worst = max(c["exchange_s"] for c in reduced["chips"].values())
    return worst / reduced["collects"] if worst > 0 else None


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
