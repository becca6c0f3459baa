"""What a profiler trace (``.xplane.pb``) says about each exec type's
programs: their device seconds and runs inside the traced collects.

The kernel cache names every program ``jit_srt_<Exec>_<what>_<digest>`` on
the device's ``XLA Modules`` line (one event per program run), so a trace
tells a ``WindowExec`` program (``wstage``: the partition sort and the
window evaluation in one launch; ``compute``) from a ``SortExec`` one
(``compute``: the permutation and the gather of every column; the sort of
a range exchange's sampled bounds is one too) and from a
``ShuffleExchangeExec`` one (``map``, ``split``, ``shrink``: every local
exchange of the plan together).  ``metrics/window_ms.py``, ``sort_ms.py``
and ``shuffle_ms.py`` each read one exec type.  A trace without a collect
or without a ``jit_srt_`` program reduces to ``None``.

It imports ``jax.profiler`` (through ``reduce_trace``) and nothing of the
program.  ``python benchmarks/exec_trace.py <file>`` prints the reduction.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, Optional, Tuple

import program_spans
import reduce_trace as RT

PROGRAM = re.compile(r"^jit_srt_([A-Za-z0-9]+)_")
NS = 1e-9

_REDUCED: Dict[Tuple[str, float], Optional[dict]] = {}


def reduce(path: str, prefix: str = "bench:") -> Optional[dict]:
    """``collects`` and, per exec type, ``s`` (device seconds of its
    programs that start inside a collect, over all chips) and ``runs``."""
    data = RT.load(path)
    collects = []
    for plane in data.planes:
        if RT.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                collects.extend((s, e) for name, s, e in RT._events(line)
                                if name.startswith(prefix))
    if not collects:
        return None
    execs: Dict[str, dict] = {}
    for plane in data.planes:
        if not RT.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in RT.MODULE_LINES:
                continue
            for name, s, e in RT._events(line):
                found = PROGRAM.match(name)
                if found and any(lo <= s < hi for lo, hi in collects):
                    row = execs.setdefault(found.group(1),
                                           {"s": 0.0, "runs": 0})
                    row["s"] += (e - s) * NS
                    row["runs"] += 1
    if not execs:
        return None
    return {"collects": len(collects), "execs": execs}


def for_run(run: dict) -> Optional[dict]:
    """The reduction of this run's trace, parsed once; None without a
    trace or where no kernel-cache program ran."""
    path = program_spans.trace_file(run)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED.clear()
        _REDUCED[key] = reduce(path)
        if _REDUCED[key] is not None:
            print("exec_trace: " + json.dumps(_REDUCED[key]),
                  file=sys.stderr)
    return _REDUCED[key]


def exec_s_per_collect(run: dict, exec_name: str) -> Optional[float]:
    """Device seconds a collect of one exec type's programs; None where
    the trace holds no program of that exec."""
    reduced = for_run(run)
    if reduced is None or exec_name not in reduced["execs"]:
        return None
    return reduced["execs"][exec_name]["s"] / reduced["collects"]


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
