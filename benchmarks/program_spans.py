"""What the program's own spans say in a profiler trace (``.xplane.pb``).

With ``spark.rapids.tpu.trace.enabled`` (on in every traced run) the program
marks its host work with ``jax.profiler.TraceAnnotation``s named
``srt:<category>:<name>`` (``spark_rapids_tpu/observability/tracer.py``): they
lie on the host plane's thread lines, on the profiler's clock, beside the
device plane that ``reduce_trace.py`` reads.  Programs that go through the
kernel cache are named ``jit_srt_<Exec>_<what>_<digest>`` on the device's
``XLA Modules`` line; every other name there was launched past it (a ``jnp``
call in an exec's Python, a keyless ``jax.jit``).

This file reduces one trace to what the seven ``program_span`` /
``program_counter`` / ``device_trace`` readers under ``metrics/`` need, inside
the harness's ``bench:<query>`` collects only, all seconds from the trace's
clock:

* seconds and self seconds (net of the ``srt:`` spans nested inside, same
  thread) per span name and per category; a category's seconds count a span
  only where no span of the same category encloses it;
* **every** device-idle nanosecond inside a collect (``reduce_trace`` looks at
  the longest 400 gaps, each as a whole) under the innermost ``srt:`` span over
  it: a gap is split wherever a span starts or ends; of several host threads
  the one with the shortest span speaks.  Idle time whose innermost span is a
  ``query``, ``task`` or ``op`` span, or none, is *unattributed*: the
  measurement names the exec at best, not what the host did;
* program runs by module name, and how many lack the ``jit_srt_`` prefix;
* where the launches outside the kernel cache are made: jax marks every
  launch of a jitted function on the host as ``PjitFunction(<name>)``; each is
  counted under the innermost ``srt:`` span around it on its thread.

A trace without a single ``srt:`` span (a program from before the spans
existed) reduces to ``None``: the readers then report nothing.

It imports ``jax.profiler`` (through ``reduce_trace``) and nothing of the
program.  ``python benchmarks/program_spans.py <file>`` prints the tables a
reader of PERF.md needs.
"""

from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import reduce_trace as RT

PREFIX = "srt:"
CACHE_PROGRAM = "jit_srt_"
LAUNCH = "PjitFunction("
#: categories that say which exec ran, not what the host did
UNEXPLAINED = ("query", "task", "op")
NS = 1e-9

Span = Tuple[str, float, float]

#: one parse per trace file and process: (path, mtime) -> reduction
_REDUCED: Dict[Tuple[str, float], Optional[dict]] = {}


def category(name: str) -> str:
    """``scan`` of ``srt:scan:host_decode``."""
    return name[len(PREFIX):].split(":", 1)[0]


def nest(spans: Sequence[Span]) -> List[Tuple[int, int]]:
    """(parent index or -1, depth) of each span of ONE thread, by time:
    a span lies inside the latest-started span that has not ended yet."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][1], -spans[i][2]))
    out = [(-1, 0)] * len(spans)
    stack: List[int] = []
    for i in order:
        while stack and spans[stack[-1]][2] <= spans[i][1]:
            stack.pop()
        out[i] = (stack[-1] if stack else -1, len(stack))
        stack.append(i)
    return out


def innermost(threads: Sequence[Sequence[Span]], depths, points):
    """Index pair (thread, span) of the innermost span over each point of
    time, or (-1, -1): the deepest span of each thread that holds it, and
    over all threads the shortest of those.  Spans of one thread and one
    depth do not overlap, so each (thread, depth) answers with one
    bisection."""
    best_len = np.full(len(points), np.inf)
    best = np.full((len(points), 2), -1, dtype=np.int64)
    for t, spans in enumerate(threads):
        if not spans:
            continue
        starts = np.array([s[1] for s in spans])
        ends = np.array([s[2] for s in spans])
        depth = np.array([d for _, d in depths[t]])
        mine = np.full(len(points), -1, dtype=np.int64)
        for d in range(int(depth.max()) + 1):       # deeper overrides
            idx = np.flatnonzero(depth == d)
            idx = idx[np.argsort(starts[idx], kind="stable")]
            at = np.searchsorted(starts[idx], points, side="right") - 1
            cand = idx[np.clip(at, 0, len(idx) - 1)]
            ok = (at >= 0) & (ends[cand] > points)
            mine[ok] = cand[ok]
        length = np.where(mine >= 0, ends[mine] - starts[mine], np.inf)
        ok = length < best_len
        best_len[ok] = length[ok]
        best[ok, 0] = t
        best[ok, 1] = mine[ok]
    return best


def idle_before(gaps: np.ndarray, times: np.ndarray) -> np.ndarray:
    """Idle nanoseconds before each time, for sorted disjoint gaps."""
    total = np.concatenate([[0.0], np.cumsum(gaps[:, 1] - gaps[:, 0])])
    k = np.searchsorted(gaps[:, 0], times, side="right")
    last_end = gaps[np.maximum(k - 1, 0), 1]
    return total[k] - np.where(k > 0, np.maximum(last_end - times, 0.0), 0.0)


def launch_sites(spans: Sequence[Span], launches: Sequence[Span],
                 inside) -> Dict[Tuple[str, str], int]:
    """(innermost ``srt:`` span, function) -> launches of ONE thread that
    start inside a collect and do not go through the kernel cache.  jax
    nests a launch's annotation inside one of the same name: one launch."""
    out: Dict[Tuple[str, str], int] = defaultdict(int)
    stack: List[Tuple[str, float, bool]] = []      # name, end, is a span
    for start, neg_end, name, is_span in sorted(
            [(s, -e, n, True) for n, s, e in spans]
            + [(s, -e, n, False) for n, s, e in launches]):
        while stack and stack[-1][1] <= start:
            stack.pop()
        around = stack[-1] if stack else None
        stack.append((name, -neg_end, is_span))
        function = name[len(LAUNCH):-1]
        if is_span or (around and not around[2] and around[0] == name) \
                or function.startswith(CACHE_PROGRAM[4:]) \
                or not inside(start):
            continue
        site = next((n for n, _, sp in reversed(stack) if sp),
                    "(no srt span)")
        out[(site, function)] += 1
    return out


def reduce(path: str, prefix: str = "bench:") -> Optional[dict]:
    data = RT.load(path)
    busy: List[RT.Interval] = []
    modules: List[Span] = []
    threads: List[List[Span]] = []
    launches: List[List[Span]] = []     # of the same threads
    collects: List[Span] = []
    for plane in data.planes:
        if RT.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in RT.BUSY_LINES:
                    busy.extend((s, e) for _, s, e in RT._events(line))
                elif line.name in RT.MODULE_LINES:
                    modules.extend(RT._events(line))
        elif RT.HOST_PLANE.match(plane.name):
            for line in plane.lines:
                mine, launched = [], []
                for ev in RT._events(line):
                    if ev[0].startswith(PREFIX):
                        mine.append(ev)
                    elif ev[0].startswith(LAUNCH):
                        launched.append(ev)
                    elif ev[0].startswith(prefix):
                        collects.append(ev)
                if mine:
                    threads.append(mine)
                    launches.append(launched)
    if not collects or not threads:
        return None
    collects.sort(key=lambda c: c[1])
    c_starts = np.array([c[1] for c in collects])
    c_ends = np.array([c[2] for c in collects])

    def inside(start: float) -> bool:
        i = int(np.searchsorted(c_starts, start, side="right")) - 1
        return i >= 0 and start < c_ends[i]

    # -- seconds and self seconds per name and per category ---------------
    spans: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"n": 0, "s": 0.0, "self_s": 0.0, "idle_s": 0.0})
    cats: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"s": 0.0, "self_s": 0.0, "idle_s": 0.0})
    depths = [nest(t) for t in threads]
    for t, mine in enumerate(threads):
        self_ns = [e - s for _, s, e in mine]
        for i, (parent, _) in enumerate(depths[t]):
            if parent >= 0:
                self_ns[parent] -= mine[i][2] - mine[i][1]
        for i, (name, s, e) in enumerate(mine):
            if not inside(s):
                continue
            cat = category(name)
            row = spans[name]
            row["n"] += 1
            row["s"] += (e - s) * NS
            row["self_s"] += self_ns[i] * NS
            cats[cat]["self_s"] += self_ns[i] * NS
            parent = depths[t][i][0]
            while parent >= 0 and category(mine[parent][0]) != cat:
                parent = depths[t][parent][0]
            if parent < 0:      # no span of its own category around it
                cats[cat]["s"] += (e - s) * NS

    # -- every idle nanosecond inside a collect, under its innermost span --
    merged = RT.merge(busy)
    gap_list: List[RT.Interval] = []
    for _, s, e in collects:
        gap_list.extend(RT.gaps(RT.clip(merged, s, e), s, e))
    idle_s = unattributed_s = 0.0
    if gap_list:
        g = np.array(gap_list)
        idle_s = float((g[:, 1] - g[:, 0]).sum()) * NS
        # between two neighbouring span or collect boundaries the innermost
        # span does not change: a gap is split where it crosses one
        bounds = np.unique(np.array(
            [t for mine in threads for _, s, e in mine for t in (s, e)]
            + [t for _, s, e in collects for t in (s, e)]))
        idle = np.diff(idle_before(g, bounds))
        over = innermost(threads, depths, (bounds[:-1] + bounds[1:]) / 2)
        for k in np.flatnonzero(idle > 0):
            t, i = over[k]
            seconds = float(idle[k]) * NS
            name = threads[t][i][0] if t >= 0 else "(no srt span)"
            cat = category(name) if t >= 0 else name
            if t >= 0:
                spans[name]["idle_s"] += seconds
            cats[cat]["idle_s"] += seconds
            if t < 0 or cat in UNEXPLAINED:
                unattributed_s += seconds

    # -- program runs by module name --------------------------------------
    programs: Dict[str, int] = defaultdict(int)
    for name, s, _ in modules:
        if inside(s):
            programs[RT.short_module(name)] += 1
    outside = {k: v for k, v in programs.items()
               if not k.startswith(CACHE_PROGRAM)}
    sites: Dict[str, int] = defaultdict(int)
    for mine, launched in zip(threads, launches):
        for (site, function), count in launch_sites(
                mine, launched, inside).items():
            sites[f"{site} <- {function}"] += count
    return {
        "collects": len(collects),
        "spans": {k: dict(v) for k, v in spans.items()},
        "categories": {k: dict(v) for k, v in cats.items()},
        "idle_s": idle_s, "idle_gaps": len(gap_list),
        "idle_unattributed_s": unattributed_s,
        "programs": dict(programs),
        "program_runs": sum(programs.values()),
        "program_runs_outside_cache": sum(outside.values()),
        "launch_sites": dict(sites),
    }


def trace_file(run: dict) -> Optional[str]:
    """The traced run's file, still on disk while the readers run."""
    if not run.get("trace"):
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    files = sorted(glob.glob(os.path.join(
        here, ".cache", run["cell"]["name"], "trace", "plugins", "profile",
        "*", "*.xplane.pb")))
    return files[-1] if files else None


def for_run(run: dict) -> Optional[dict]:
    """The reduction of this run's trace, parsed once; None without a
    trace, and for a program that has no ``srt:`` spans."""
    path = trace_file(run)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _REDUCED:
        _REDUCED.clear()
        _REDUCED[key] = reduce(path)
        if _REDUCED[key] is not None:
            report(_REDUCED[key], out=sys.stderr, top=12)
    return _REDUCED[key]


def category_ms(run: dict, cat: str, field: str = "s") -> Optional[float]:
    """Milliseconds a traced collect spends in one category (0 where the
    spans exist and this category never fired)."""
    reduced = for_run(run)
    if reduced is None:
        return None
    row = reduced["categories"].get(cat, {})
    return 1e3 * row.get(field, 0.0) / reduced["collects"]


def report(reduced: dict, out=sys.stdout, top: int = 40) -> None:
    n = reduced["collects"]
    print(f"program_spans: {n} collects, idle {reduced['idle_s']:.3f} s in "
          f"{reduced['idle_gaps']} gaps, unattributed "
          f"{reduced['idle_unattributed_s']:.3f} s; {reduced['program_runs']}"
          f" program runs, {reduced['program_runs_outside_cache']} outside "
          f"the kernel cache", file=out)
    print(f"{'category':<16}{'s':>10}{'self s':>10}{'idle s':>10}", file=out)
    for cat, row in sorted(reduced["categories"].items(),
                           key=lambda kv: -kv[1]["idle_s"]):
        print(f"{cat:<16}{row['s']:>10.3f}{row['self_s']:>10.3f}"
              f"{row['idle_s']:>10.3f}", file=out)
    print(f"{'n':>7}{'s':>10}{'self s':>10}{'idle s':>10}  span", file=out)
    rows = sorted(reduced["spans"].items(),
                  key=lambda kv: -(kv[1]["idle_s"] + kv[1]["self_s"]))
    for name, row in rows[:top]:
        print(f"{row['n']:>7}{row['s']:>10.3f}{row['self_s']:>10.3f}"
              f"{row['idle_s']:>10.3f}  {name}", file=out)
    print(f"{'runs':>7}  program", file=out)
    for name, count in sorted(reduced["programs"].items(),
                             key=lambda kv: -kv[1])[:top]:
        print(f"{count:>7}  {name}", file=out)
    print(f"{'n':>7}  launches outside the kernel cache: span <- function",
          file=out)
    for name, count in sorted(reduced["launch_sites"].items(),
                             key=lambda kv: -kv[1])[:top]:
        print(f"{count:>7}  {name}", file=out)


if __name__ == "__main__":
    result = reduce(sys.argv[1])
    if result is None:
        sys.exit("program_spans: no srt: span inside a bench: collect")
    report(result)
