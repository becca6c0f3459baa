"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.  Nothing here knows a cell, a query or a metric.

What a trace of this engine on a TPU holds (looked at by hand, PERF.md §5):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one
event per device operation and whose line ``XLA Modules`` has one per
program run (operations nest: a ``while`` holds its body's); and
``/host:CPU`` with one line per host thread, on which the harness's
``bench:<query>`` annotations, the program's own annotations (line
``python3``) and the runtime's spans lie.  All planes share one clock, in
nanoseconds from the start of the trace.

``python benchmarks/reduce_trace.py <file>`` prints what a file holds and
what the reduction makes of it.
"""

from __future__ import annotations

import bisect
import json
import re
import sys
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = re.compile(r"^/host:")
#: device-plane lines whose events are time in which the chip works
BUSY_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
NS = 1e-9
TOP = 10
#: idle gaps looked at one by one; the shorter ones are summed under one name
GAPS_ATTRIBUTED = 400

Interval = Tuple[float, float]


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith((".pbtxt", ".txt")):
        with open(path) as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The part of disjoint sorted intervals that lies inside [lo, hi]."""
    i = bisect.bisect_left([e for _, e in merged], lo)
    out = []
    while i < len(merged) and merged[i][0] < hi:
        s, e = max(merged[i][0], lo), min(merged[i][1], hi)
        if e > s:
            out.append((s, e))
        i += 1
    return out


def total(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What of [lo, hi] the disjoint sorted ``busy`` leaves uncovered."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def short_op(text: str) -> str:
    """``%name opcode`` of an operation the profiler names by its whole HLO
    text (``%name = shape opcode(operands), attributes``)."""
    name, eq, rest = text.partition(" = ")
    if not eq:
        return text[:80]
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            if ch == "(" and depth == 0 and i and rest[i - 1] not in " (":
                word = rest[:i].rsplit(" ", 1)[-1]
                if word and word[0].isalpha():
                    return f"{name} {word}"
            depth += 1
        elif ch in ")]}":
            depth -= 1
    return name[:80]


def short_module(text: str) -> str:
    """A program's name without the fingerprint the profiler appends."""
    return re.sub(r"\(\d+\)$", "", text)


def self_seconds(events: Sequence[Tuple[str, float, float]]
                 ) -> Dict[str, float]:
    """Seconds of each name net of the events nested inside it (a ``while``
    holds its body's operations), so that the names add up to the busy
    time."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[Tuple[str, float]] = []     # (name, end)
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= (min(e, stack[-1][1]) - s) * NS
        out[name] += (e - s) * NS
        stack.append((name, e))
    return out


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def _covering(names, starts, ends, gap: Interval) -> str:
    """Name of the innermost host span that lies over the middle of the gap
    and is at least half as long as it."""
    lo, hi = gap
    mid, least = (lo + hi) / 2, (hi - lo) / 2
    lengths = ends - starts
    over = np.flatnonzero((starts <= mid) & (ends >= mid)
                          & (lengths >= least))
    if not len(over):
        return "(no host span)"
    return names[over[np.argmin(lengths[over])]]


def reduce(path: str, prefix: str = "bench:") -> dict:
    """Seconds, all of them from the trace's own clock."""
    data = load(path)
    device: Dict[str, List[Interval]] = {}
    op_seconds: Dict[str, float] = defaultdict(float)
    module_seconds: Dict[str, float] = defaultdict(float)
    op_events = 0
    host: List[Tuple[str, float, float]] = []
    plane_names = []
    for plane in data.planes:
        plane_names.append(plane.name)
        if DEVICE_PLANE.match(plane.name):
            spans: List[Interval] = []
            ops, modules = [], []
            for line in plane.lines:
                if line.name in BUSY_LINES:
                    ops.extend(_events(line))
                elif line.name in MODULE_LINES:
                    modules.extend(_events(line))
            modules.sort(key=lambda ev: ev[1])
            starts = [ev[1] for ev in modules]
            named = []
            for name, s0, e0 in ops:
                spans.append((s0, e0))
                # the program whose run this operation lies in
                i = bisect.bisect_right(starts, s0) - 1
                inside = i >= 0 and s0 < modules[i][2]
                program = short_module(modules[i][0]) if inside else "?"
                named.append((f"{program}/{short_op(name)}", s0, e0))
            for name, seconds in self_seconds(named).items():
                op_seconds[name] += seconds
            op_events += len(ops)
            for name, s0, e0 in modules:
                module_seconds[short_module(name)] += (e0 - s0) * NS
            device[plane.name] = merge(spans)
        elif HOST_PLANE.match(plane.name):
            for line in plane.lines:
                host.extend(_events(line))
    collects = sorted(((s, e, name[len(prefix):]) for name, s, e in host
                       if name.startswith(prefix)))
    others = [h for h in host if not h[0].startswith(prefix)]
    names = [h[0] for h in others]
    starts = np.array([h[1] for h in others], dtype=np.float64)
    ends = np.array([h[2] for h in others], dtype=np.float64)
    if not collects:
        return {"planes": plane_names, "device_planes": len(device),
                "collects": [], "device_op_events": op_events}
    lo, hi = collects[0][0], max(e for _, e, _ in collects)
    chips = max(len(device), 1)
    per_collect, gap_list = [], []
    for s, e, name in collects:
        busy = [clip(m, s, e) for m in device.values()]
        per_collect.append({"query": name, "seconds": (e - s) * NS,
                            "busy_s": sum(map(total, busy)) * NS / chips})
        # idle on every chip at once: what the union of all chips leaves
        gap_list.extend(gaps(merge([iv for b in busy for iv in b]), s, e))
    gap_list.sort(key=lambda g: g[0] - g[1])
    gap_seconds: Dict[str, float] = defaultdict(float)
    for gap in gap_list[:GAPS_ATTRIBUTED]:
        gap_seconds[_covering(names, starts, ends, gap)] += (gap[1] - gap[0]) * NS
    rest = sum(g[1] - g[0] for g in gap_list[GAPS_ATTRIBUTED:]) * NS
    if rest:
        gap_seconds[f"(the {len(gap_list) - GAPS_ATTRIBUTED} shorter gaps)"
                    ] += rest

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:TOP]]
    return {
        "planes": plane_names, "device_planes": len(device),
        "device_op_events": op_events,
        "collects": per_collect,
        "span_s": (hi - lo) * NS,
        "busy_s": sum(total(clip(m, lo, hi)) for m in device.values())
        * NS / chips,
        "busy_in_collects_s": sum(c["busy_s"] for c in per_collect),
        "idle_gaps_count": len(gap_list),
        "device_ops": top(op_seconds),
        "device_modules": top(module_seconds),
        "idle_gaps": top(gap_seconds),
    }


def describe(path: str, out=sys.stdout) -> None:
    """What a trace file holds: planes, lines, event counts, first events."""
    for plane in load(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines", file=out)
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            first = min(e.start_ns for e in events)
            last = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{first:.0f}..{last:.0f} ns", file=out)
            for e in events[:4]:
                stats = [(k, v) for k, v in list(e.stats)[:6]]
                print(f"     {e.name!r} start={e.start_ns:.0f} "
                      f"dur={e.duration_ns:.0f} {stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
    print(json.dumps(reduce(sys.argv[1]), indent=1))
