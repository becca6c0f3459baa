"""Per-query attribution reports over tracer timelines.

Answers the question a bare rows/s number can't: where did the wall time
go — operator self-time, blocked device readbacks, kernel trace+compile,
bytes across the host link, spill, semaphore waits — per exec node.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

#: categories whose spans are host-BLOCKING device waits (the "sync time"
#: column): scalar readbacks and D2H fetches both stall the driver for a
#: full host<->device round trip
_BLOCKING_CATS = ("sync", "d2h")

_ZERO = {"sync_ms": 0.0, "sync_n": 0, "compile_ms": 0.0, "compile_n": 0,
         "h2d_bytes": 0, "d2h_bytes": 0, "spill_ms": 0.0,
         "sem_wait_ms": 0.0, "shuffle_ms": 0.0, "fault_n": 0,
         "stage_ms": 0.0, "stage_n": 0}


def aggregate_by_exec(events: List[Dict[str, Any]]
                      ) -> Dict[str, Dict[str, Any]]:
    """Fold a tracer snapshot into per-exec-node attribution rows.  The
    empty exec name (spans fired outside any plan node — e.g. the
    driver's final result fetch) reports as ``(driver)``."""
    out: Dict[str, Dict[str, Any]] = {}
    for ev in events:
        node = ev.get("exec") or "(driver)"
        row = out.get(node)
        if row is None:
            row = out[node] = dict(_ZERO)
        cat = ev.get("cat", "")
        ms = float(ev.get("dur", 0.0)) / 1e3
        args = ev.get("args") or {}
        if cat in _BLOCKING_CATS:
            row["sync_ms"] += ms
            row["sync_n"] += 1
            if cat == "d2h":
                row["d2h_bytes"] += int(args.get("bytes", 0))
        elif cat == "compile":
            row["compile_ms"] += ms
            row["compile_n"] += 1
        elif cat == "h2d":
            row["h2d_bytes"] += int(args.get("bytes", 0))
        elif cat == "spill":
            row["spill_ms"] += ms
        elif cat == "sem_wait":
            row["sem_wait_ms"] += ms
        elif cat == "shuffle":
            row["shuffle_ms"] += ms
        elif cat == "fault":
            row["fault_n"] += 1
        elif cat == "stage":
            row["stage_ms"] += ms
            row["stage_n"] += 1
    return out


def trace_summary(events: List[Dict[str, Any]],
                  counters: Optional[Dict[str, float]] = None,
                  dropped: int = 0) -> Dict[str, Any]:
    """Compact whole-query summary (flight-recorder records, the
    doctor's degraded mode): blocking sync count/ms, kernel
    trace+compile ms, bytes on the wire."""
    agg = aggregate_by_exec(events)
    tot = dict(_ZERO)
    for row in agg.values():
        for k in tot:
            tot[k] += row[k]
    out = {
        "sync_count": int(tot["sync_n"]),
        "sync_ms": round(tot["sync_ms"], 3),
        "compile_count": int(tot["compile_n"]),
        "compile_ms": round(tot["compile_ms"], 3),
        "h2d_bytes": int(tot["h2d_bytes"]),
        "d2h_bytes": int(tot["d2h_bytes"]),
        "spill_ms": round(tot["spill_ms"], 3),
        "sem_wait_ms": round(tot["sem_wait_ms"], 3),
        "events": len(events),
    }
    if tot["stage_n"]:
        # whole-stage evidence (docs/whole_stage.md): fused-stage batch
        # spans + total device dispatches per traced query
        out["stage_count"] = int(tot["stage_n"])
        out["stage_ms"] = round(tot["stage_ms"], 3)
    if counters and counters.get("deviceDispatches"):
        out["device_dispatches"] = int(counters["deviceDispatches"])
    # the host launching programs (the doctor's measured dispatch-bound):
    # self time of the kernel cache's launches and of the eager blocks
    from .doctor import _self_times, _verdict_of
    launch = [ms for ev, ms in zip(events, _self_times(events))
              if _verdict_of(ev) == "dispatch-bound"]
    if launch:
        out["dispatch_count"] = len(launch)
        out["dispatch_ms"] = round(sum(launch), 3)
    if tot["fault_n"]:
        out["fault_count"] = int(tot["fault_n"])
    # truncation is first-class: a consumer (the doctor) must never have
    # to infer from an absent key that the ring did NOT overflow
    out["trace_truncated"] = bool(dropped)
    if dropped:
        out["dropped_events"] = int(dropped)
    if counters:
        out["counters"] = {k: round(v, 3) for k, v in counters.items()}
    return out


def _fmt_bytes(n: int) -> str:
    if n >= 10 * 1024 * 1024:
        return f"{n / (1 << 20):.0f}M"
    if n >= 10 * 1024:
        return f"{n / (1 << 10):.0f}K"
    return str(int(n))


def attribution_table(phys, events: List[Dict[str, Any]],
                      dropped: int = 0) -> str:
    """The extended ``profile_last_query()`` view: the physical tree's
    inclusive/self wall time (from the PROFILING shim) joined with the
    tracer's per-exec sync/compile/transfer attribution.

    Attribution is keyed by node NAME: two instances of the same exec
    type share one attribution row (printed at the first occurrence, ``.``
    after) — per-instance split would need per-node ids on the exec
    stack, which the ring-buffer events deliberately keep small.
    """
    agg = aggregate_by_exec(events)
    lines = [f"{'exec':<34} {'incl_ms':>8} {'self_ms':>8} {'batches':>7}"
             f" | {'sync_ms':>8} {'n':>4} {'compile_ms':>10}"
             f" {'h2d':>7} {'d2h':>7}"]
    seen: set = set()

    def walk(node, level: int):
        incl = node._prof_ns / 1e6
        self_ms = (node._prof_ns
                   - sum(c._prof_ns for c in node.children)) / 1e6
        name = node.node_name()
        label = "  " * level + name
        row = agg.get(name)
        if row is not None and name not in seen:
            seen.add(name)
            trace_cols = (f" | {row['sync_ms']:>8.2f} {row['sync_n']:>4d}"
                          f" {row['compile_ms']:>10.2f}"
                          f" {_fmt_bytes(row['h2d_bytes']):>7}"
                          f" {_fmt_bytes(row['d2h_bytes']):>7}")
        elif row is not None:
            trace_cols = f" | {'.':>8} {'.':>4} {'.':>10} {'.':>7} {'.':>7}"
        else:
            trace_cols = (f" | {0.0:>8.2f} {0:>4d} {0.0:>10.2f}"
                          f" {'0':>7} {'0':>7}")
        lines.append(f"{label:<34} {incl:>8.2f} {max(self_ms, 0.0):>8.2f}"
                     f" {node._prof_batches:>7d}{trace_cols}")
        for c in node.children:
            walk(c, level + 1)

    walk(phys, 0)
    # spans outside the plan (driver-side result fetch, spill, …)
    for name in sorted(set(agg) - seen):
        row = agg[name]
        lines.append(f"{name:<34} {'-':>8} {'-':>8} {'-':>7}"
                     f" | {row['sync_ms']:>8.2f} {row['sync_n']:>4d}"
                     f" {row['compile_ms']:>10.2f}"
                     f" {_fmt_bytes(row['h2d_bytes']):>7}"
                     f" {_fmt_bytes(row['d2h_bytes']):>7}")
    extra = []
    tot = trace_summary(events, dropped=dropped)
    extra.append(f"sync {tot['sync_count']}x/{tot['sync_ms']}ms, "
                 f"compile {tot['compile_count']}x/{tot['compile_ms']}ms, "
                 f"h2d {_fmt_bytes(tot['h2d_bytes'])}B, "
                 f"d2h {_fmt_bytes(tot['d2h_bytes'])}B, "
                 f"spill {tot['spill_ms']}ms, "
                 f"sem_wait {tot['sem_wait_ms']}ms")
    if dropped:
        extra.append(f"WARNING: ring buffer overflowed, {dropped} oldest "
                     f"events dropped (raise "
                     f"spark.rapids.tpu.trace.bufferEvents)")
    return "\n".join(lines + ["", "totals: " + "; ".join(extra)])
