"""What a profiler trace (``.xplane.pb``) says about the join execs' probe
programs: their device seconds inside the traced collects.

The kernel cache names a join's programs ``jit_srt_<Join exec>_<what>_
<digest>`` on the device's ``XLA Modules`` line.  The probe side of a join
is ``probe`` (search, expansion and the gather of every output column in
one launch; ``fusedprobe`` in programs from before the name said which
exec), ``probesearch`` (the search alone) and ``gather`` / ``gather_chunk``
(the output assembled in a launch of its own); the build side's sort
(``prep``), the bloom filter of a shuffled join and the exchanges are not
probes.  A trace without such a program reduces to ``None``.

It imports ``jax.profiler`` (through ``reduce_trace``) and nothing of the
program.  ``python benchmarks/join_trace.py <file>`` prints the reduction.
"""

from __future__ import annotations

import json
import os
import re
import sys
from typing import Dict, Optional, Tuple

import program_spans
import reduce_trace as RT

PROBE_PROGRAM = re.compile(
    r"^jit_srt_\w*Join\w*Exec_"
    r"(probe|fusedprobe|probesearch|gather|gather_chunk)_[0-9a-f]+")
BUILD_SPANS = ("srt:broadcast:build", "srt:join:adaptive.materialize")
NS = 1e-9

_REDUCED: Dict[Tuple[str, float], Optional[dict]] = {}


def reduce(path: str, prefix: str = "bench:") -> Optional[dict]:
    """``collects``, ``probe_s`` (device seconds of the probe programs
    that start inside a collect, over all chips), ``probe_runs`` and the
    seconds by program name."""
    data = RT.load(path)
    collects = []
    for plane in data.planes:
        if RT.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                collects.extend((s, e) for name, s, e in RT._events(line)
                                if name.startswith(prefix))
    if not collects:
        return None
    by_name: Dict[str, float] = {}
    runs = 0
    for plane in data.planes:
        if not RT.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name not in RT.MODULE_LINES:
                continue
            for name, s, e in RT._events(line):
                if PROBE_PROGRAM.match(name) and any(
                        lo <= s < hi for lo, hi in collects):
                    short = RT.short_module(name)
                    by_name[short] = by_name.get(short, 0.0) + (e - s) * NS
                    runs += 1
    if not runs:
        return None
    return {"collects": len(collects), "probe_s": sum(by_name.values()),
            "probe_runs": runs, "programs": by_name}


def for_run(run: dict) -> Optional[dict]:
    """The reduction of this run's trace, parsed once; None without a
    trace or where no probe program ran."""
    path = program_spans.trace_file(run)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED.clear()
        _REDUCED[key] = reduce(path)
        if _REDUCED[key] is not None:
            print("join_trace: " + json.dumps(_REDUCED[key]),
                  file=sys.stderr)
    return _REDUCED[key]


def probe_s_per_collect(run: dict) -> Optional[float]:
    reduced = for_run(run)
    if reduced is None:
        return None
    return reduced["probe_s"] / reduced["collects"]


if __name__ == "__main__":
    print(json.dumps(reduce(sys.argv[1]), indent=1))
