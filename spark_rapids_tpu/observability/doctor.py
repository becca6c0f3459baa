"""Automated bottleneck doctor — ranked, machine-readable attribution of
where a query's time went, from a tracer timeline + metrics snapshot.

The verdict classes (docs/observability.md):

==================  ======================================================
``sync-bound``      blocking scalar readbacks (cat ``sync``) dominate —
                    each is a full host<->device round trip
``compile-bound``   kernel trace+compile (cat ``compile``) — cold
                    cache; warm reruns are the fix, not kernel work
``h2d-d2h-bound``   transfer spans (cats ``h2d``+``d2h``) — bytes crossing
                    the host link; prepack/resident tiers are the levers
``dispatch-bound``  the host launching programs: self time of the
                    ``dispatch`` (kernel-cache launches) and ``eager``
                    (launches past the cache) spans — per-op Python
                    dispatch + launch overhead; whole-stage fusion is the
                    lever
``sem_wait-bound``  device-semaphore waits (cat ``sem_wait``) — tasks
                    contending for chip admission
``spill-bound``     spill tier movement (cat ``spill``)
``shuffle-bound``   exchange materialization + frame (de)serialization
                    (cat ``shuffle``) and queue waits (cat ``queue``)
==================  ======================================================

:func:`diagnose` consumes raw tracer events (best fidelity: exec-level
evidence spans ride each verdict); :func:`diagnose_summary` degrades to a
compact ``trace_summary`` (flight-recorder records).  Both emit
the same schema, validated by ``tools/check_trace.py --doctor``:

.. code-block:: json

   {"schema": "srt-doctor/1", "verdict": "sync-bound",
    "ranked": [{"category": "sync-bound", "ms": 120.3, "count": 18,
                "share": 0.61,
                "evidence": {"top_execs": [...], "counters": {...}}}],
    "wall_ms": 197.0, "attributed_ms": 151.2,
    "trace_truncated": false, "caveats": []}

CLI (CI runs this against the traced-query event log):

    python -m spark_rapids_tpu.observability.doctor <eventlog.jsonl>
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional

SCHEMA = "srt-doctor/1"

#: most recent diagnose() verdict in this process ({"verdict", "at"}) —
#: stamped into fatal-device diagnostic dumps (memory/fatal.py) so a
#: quarantine event records what the engine was bound on pre-mortem
LAST_VERDICT: "Optional[Dict[str, Any]]" = None

#: tracer category -> verdict category
_CAT_TO_VERDICT = {
    "sync": "sync-bound",
    "compile": "compile-bound",
    "h2d": "h2d-d2h-bound",
    "d2h": "h2d-d2h-bound",
    "sem_wait": "sem_wait-bound",
    "spill": "spill-bound",
    "shuffle": "shuffle-bound",
    "queue": "shuffle-bound",
    "admission": "admission-bound",
    "dispatch": "dispatch-bound",
    "eager": "dispatch-bound",
}


def _verdict_of(ev: Dict[str, Any]) -> Optional[str]:
    """The verdict an event's self time counts toward: its category's, but
    a ``dispatch`` span that re-traced (``retraced`` arg) was compiling."""
    cat = ev.get("cat", "")
    if cat == "dispatch" and (ev.get("args") or {}).get("retraced"):
        return "compile-bound"
    return _CAT_TO_VERDICT.get(cat)


VERDICTS = ("sync-bound", "compile-bound", "h2d-d2h-bound",
            "dispatch-bound", "sem_wait-bound", "spill-bound",
            "shuffle-bound", "admission-bound",
            # a tenant consuming its declared SLO error budget faster
            # than allotted (observability/slo.py names the tenant and
            # its dominant bottleneck in the entry's evidence)
            "slo-burn",
            # the query paid for the pod-scale fault domain: peers
            # declared dead, zombie responses fenced, failovers and
            # recomputes (shuffle/manager.py + robustness/
            # failure_detector.py quantify the evidence)
            "peer-failure")

#: verdict -> the remedial lever.  Every verdict kind carries quantified
#: lever evidence (``evidence.levers``) with the same precision
#: dispatch-bound always had.
LEVERS = {
    "sync-bound": "fuse/pipeline the blocking readbacks (async d2h)",
    "compile-bound": "warm the persistent kernel cache / shape buckets",
    "h2d-d2h-bound": "prepack + device-resident tier (cut wire bytes)",
    "dispatch-bound": "whole-stage fusion (cut launches per stage)",
    "sem_wait-bound": "raise semaphore permits or admission weights",
    "spill-bound": "raise the memory budget / spill tier sizing",
    "shuffle-bound": "device-resident shuffle tier / coalesced exchange",
    "admission-bound": "tenant weight, memory budget, "
                       "maxConcurrentQueries",
    "slo-burn": "rebalance the burning tenant's SLO budget or load",
    "peer-failure": "replace/restart the dead peer; tighten "
                    "peers.{suspectMs,deadMs} to detect sooner",
}


def _lever_evidence(entry: Dict[str, Any],
                    stages: int = 0) -> Dict[str, Any]:
    """Quantified lever numbers for one ranked entry, keyed by what the
    verdict's lever actually moves (readbacks for sync, launches for
    dispatch, bytes for transfer...).  Best-effort from the entry's
    existing evidence — degraded summaries simply carry fewer keys."""
    cat = entry["category"]
    ms, n = float(entry["ms"]), int(entry["count"])
    ev = entry.get("evidence") or {}
    lv: Dict[str, Any] = {}
    if cat == "sync-bound":
        lv["readbacks"] = n
        if stages and n:
            lv["readbacks_per_stage"] = round(n / stages, 2)
        if n:
            lv["ms_per_readback"] = round(ms / n, 3)
    elif cat == "compile-bound":
        lv["compiles"] = n
        if n:
            lv["ms_per_compile"] = round(ms / n, 3)
    elif cat == "h2d-d2h-bound":
        for k in ("h2d_bytes", "d2h_bytes", "bytes"):
            if ev.get(k):
                lv[k] = int(ev[k])
    elif cat == "dispatch-bound":
        lv["device_dispatches"] = int(ev.get("device_dispatches", n))
        if ev.get("launches_per_probe_batch") is not None:
            lv["launches_per_probe_batch"] = \
                ev["launches_per_probe_batch"]
    elif cat == "sem_wait-bound":
        lv["wait_ms"] = round(ms, 3)
        if n:
            lv["waits"] = n
    elif cat == "spill-bound":
        lv["spill_ms"] = round(ms, 3)
    elif cat == "shuffle-bound":
        lv["shuffle_ms"] = round(ms, 3)
        if ev.get("bytes"):
            lv["bytes_on_wire"] = int(ev["bytes"])
    elif cat == "admission-bound":
        lv["wait_ms"] = round(ms, 3)
        lv["waiters"] = n
    elif cat == "slo-burn":
        for k in ("tenant", "burn_rate", "window_s"):
            if ev.get(k) is not None:
                lv[k] = ev[k]
    elif cat == "peer-failure":
        for k in ("dead_peers", "stale_epochs", "dead_failovers",
                  "proactive_recomputes"):
            if ev.get(k) is not None:
                lv[k] = ev[k]
        lv["recovery_ms"] = round(ms, 3)
    top = None
    execs = ev.get("top_execs")
    if execs:
        top = execs[0].get("exec")
    if ev.get("top_exec"):
        top = ev["top_exec"]
    if top:
        lv["top_exec"] = top
    return lv


def _stamp_levers(ranked: List[Dict[str, Any]], stages: int = 0) -> None:
    """Stamp ``evidence.levers`` (quantified) + ``evidence.lever`` (the
    named remedy) onto every ranked entry — ISSUE 18: every verdict
    kind, not just dispatch-bound, must justify its follow-up with
    numbers.  Idempotent."""
    for e in ranked:
        ev = e.setdefault("evidence", {})
        ev["levers"] = _lever_evidence(e, stages)
        ev["lever"] = LEVERS.get(e["category"], "")

#: kernel keys named in dispatch-bound evidence (top launch sources)
DISPATCH_TOP_K = 5


def _dispatch_evidence(dispatches: int,
                       metrics: Dict[str, Any],
                       dispatch_by_key: Optional[Dict[str, int]]
                       ) -> Dict[str, Any]:
    """Actionable dispatch-bound evidence: WHICH programs launch and HOW
    OFTEN per unit of work.  ``launches_per_probe_batch`` is the join
    perf-model number (ISSUE 14: one probe batch should cost ≤12
    launches end to end); ``top_kernels`` ranks the per-key launch
    counters (``dispatches{kernel}``) so the verdict names the
    originating exec instead of just a total — kernel labels are
    ``srt_<ExecName>_<what>_<digest>`` (kernel_cache.program_name), so
    the heaviest key IS the exec to fuse."""
    ev: Dict[str, Any] = {"device_dispatches": dispatches}
    probes = int(metrics.get("joinFastpathProbes", 0)
                 + metrics.get("joinFallbackProbes", 0))
    if probes > 0:
        ev["probe_batches"] = probes
        ev["launches_per_probe_batch"] = round(
            dispatches / max(1, probes), 2)
    if dispatch_by_key is None:
        # in-process diagnosis: the kernel cache's per-key launch
        # counters are live and scoped to the last clear_cache()
        try:
            from ..sql.physical import kernel_cache as _kc
            dispatch_by_key = _kc.dispatch_stats_by_key()
        except Exception:  # pragma: no cover - import cycle safety
            dispatch_by_key = {}
    if dispatch_by_key:
        top = sorted(dispatch_by_key.items(), key=lambda kv: -kv[1])
        top = top[:DISPATCH_TOP_K]
        ev["top_kernels"] = [
            {"kernel": k, "launches": int(n)} for k, n in top]
        from ..sql.physical.kernel_cache import exec_of_program
        ev["top_exec"] = exec_of_program(top[0][0])
    return ev


def _verdict_entry(category: str, ms: float, count: int,
                   evidence: Dict[str, Any]) -> Dict[str, Any]:
    return {"category": category, "ms": round(ms, 3), "count": int(count),
            "evidence": evidence}


def _self_times(events: List[Dict[str, Any]]) -> List[float]:
    """SELF milliseconds per attributed event: duration minus spans
    nested inside it on the same thread.  Container spans
    (``exchange.materialize`` wraps its child's whole execution, kernel
    compiles included) would otherwise double-count nested time and let
    a shuffle verdict absorb what is really compile or sync — or plain
    operator — time.  ``op``/``stage`` spans participate in the nesting
    stack as NEUTRAL containers: they absorb their children's time (so a
    shuffle span doesn't pay for the child plan's compute) but are never
    themselves attributed to a verdict."""
    idx = [i for i, ev in enumerate(events)
           if ev.get("cat", "") in _CAT_TO_VERDICT
           or ev.get("cat", "") in ("op", "stage")]
    out = [0.0] * len(events)
    by_tid: Dict[Any, List[int]] = {}
    for i in idx:
        by_tid.setdefault(events[i].get("tid"), []).append(i)
    for tids in by_tid.values():
        # sort by start; ties put the LONGER (outer) span first
        tids.sort(key=lambda i: (float(events[i].get("ts", 0.0)),
                                 -float(events[i].get("dur", 0.0))))
        stack: List[int] = []  # open enclosing spans, innermost last
        for i in tids:
            ts = float(events[i].get("ts", 0.0))
            dur = float(events[i].get("dur", 0.0))
            while stack:
                j = stack[-1]
                jts = float(events[j].get("ts", 0.0))
                jdur = float(events[j].get("dur", 0.0))
                if ts < jts + jdur:  # i nests inside j
                    out[j] -= dur / 1e3  # direct parent pays once
                    break
                stack.pop()
            out[i] += dur / 1e3
            stack.append(i)
    return [max(0.0, ms) for ms in out]


def diagnose(events: List[Dict[str, Any]],
             counters: Optional[Dict[str, float]] = None,
             metrics: Optional[Dict[str, Any]] = None,
             wall_ms: Optional[float] = None,
             dropped_events: int = 0,
             dispatch_by_key: Optional[Dict[str, int]] = None
             ) -> Dict[str, Any]:
    """Ranked bottleneck diagnosis from a tracer snapshot.

    ``events`` is the tracer's event list (``dur`` in µs); ``counters``
    the tracer's aggregate counters; ``metrics`` the session's
    ``last_query_metrics``; ``wall_ms`` the query wall time when known
    (shares are computed against it, else against total attributed ms).
    """
    counters = counters or {}
    metrics = metrics or {}
    self_ms = _self_times(events)
    # per-verdict totals + per-(verdict, exec) evidence rows
    totals: Dict[str, Dict[str, float]] = {}
    by_exec: Dict[str, Dict[str, Dict[str, float]]] = {}
    for i, ev in enumerate(events):
        verdict = _verdict_of(ev)
        if verdict is None:
            continue
        ms = self_ms[i]
        args = ev.get("args") or {}
        nbytes = int(args.get("bytes", 0))
        t = totals.setdefault(verdict, {"ms": 0.0, "n": 0, "bytes": 0})
        t["ms"] += ms
        t["n"] += 1
        t["bytes"] += nbytes
        if verdict == "dispatch-bound":
            key = ev.get("cat", "") + "_ms"     # eager_ms / dispatch_ms
            t[key] = t.get(key, 0.0) + ms
        node = ev.get("exec") or "(driver)"
        rows = by_exec.setdefault(verdict, {})
        row = rows.setdefault(node, {"ms": 0.0, "n": 0, "bytes": 0})
        row["ms"] += ms
        row["n"] += 1
        row["bytes"] += nbytes

    ranked: List[Dict[str, Any]] = []
    for verdict, t in totals.items():
        top = sorted(by_exec.get(verdict, {}).items(),
                     key=lambda kv: -kv[1]["ms"])[:3]
        evidence: Dict[str, Any] = {"top_execs": [
            dict({"exec": name}, ms=round(r["ms"], 3), count=int(r["n"]),
                 **({"bytes": int(r["bytes"])} if r["bytes"] else {}))
            for name, r in top]}
        if t["bytes"]:
            evidence["bytes"] = int(t["bytes"])
        if verdict == "dispatch-bound":
            # measured: self time of the launch spans, split by kind,
            # beside the launch counts that say which programs ran
            dispatches = int(counters.get(
                "deviceDispatches", metrics.get("deviceDispatches", 0))
                or 0)
            evidence.update(_dispatch_evidence(dispatches, metrics,
                                               dispatch_by_key))
            evidence["stage_op_dispatches"] = int(
                metrics.get("stageOpDispatches", 0))
            for key in ("eager_ms", "dispatch_ms"):
                evidence[key] = round(t.get(key, 0.0), 3)
        ranked.append(_verdict_entry(verdict, t["ms"], t["n"], evidence))

    attributed_ms = sum(e["ms"] for e in ranked)

    # peer-failure: the query crossed the pod-scale fault domain —
    # quantified from the fault-domain metric deltas, with the ms cost
    # attributed from the fault-cat trace spans (dead declarations,
    # fenced zombie responses, recomputes)
    dead_peers = int(metrics.get("peersDeclaredDead", 0) or 0)
    stale_epochs = int(metrics.get("staleEpochsRefused", 0) or 0)
    failovers = int(metrics.get("deadPeerFailovers", 0) or 0)
    if dead_peers or stale_epochs or failovers:
        pf_ms = sum(
            self_ms[i] for i, ev in enumerate(events)
            if ev.get("cat") == "fault"
            and str(ev.get("name", "")).startswith(
                ("peer.", "shuffle.recompute", "shuffle.fetch.stale")))
        pf_ev = {"dead_peers": dead_peers, "stale_epochs": stale_epochs,
                 "dead_failovers": failovers,
                 "proactive_recomputes": int(
                     metrics.get("proactiveRecomputes", 0) or 0)}
        ranked.append(_verdict_entry(
            "peer-failure", pf_ms,
            dead_peers + stale_epochs + failovers, pf_ev))

    ranked.sort(key=lambda e: -e["ms"])
    denom = wall_ms if wall_ms else (attributed_ms or 1.0)
    for e in ranked:
        e["share"] = round(min(1.0, e["ms"] / max(denom, 1e-9)), 4)
    _stamp_levers(ranked, stages=sum(
        1 for ev in events if ev.get("cat") == "stage"))

    caveats: List[str] = []
    truncated = bool(dropped_events)
    if truncated:
        caveats.append(
            f"trace ring overflowed: {int(dropped_events)} oldest events "
            f"dropped — attribution UNDERCOUNTS early-query time (raise "
            f"spark.rapids.tpu.trace.bufferEvents)")
    if not events:
        caveats.append("no trace events: diagnosis is counters-only")
    out = {
        "schema": SCHEMA,
        "verdict": ranked[0]["category"] if ranked else "no-bottleneck",
        "ranked": ranked,
        "attributed_ms": round(attributed_ms, 3),
        "trace_truncated": truncated,
        "caveats": caveats,
    }
    if wall_ms is not None:
        out["wall_ms"] = round(float(wall_ms), 3)
    # remembered process-wide so a later fatal-device dump can record
    # what the engine believed it was bound on (memory/fatal.py)
    global LAST_VERDICT
    import time as _t
    LAST_VERDICT = {"verdict": out["verdict"], "at": _t.monotonic()}
    return out


def diagnose_summary(summary: Dict[str, Any],
                     wall_ms: Optional[float] = None) -> Dict[str, Any]:
    """Degraded-fidelity diagnosis from a compact ``trace_summary``
    (flight-recorder records — no per-exec evidence; note the summary's
    ``sync_ms`` already folds blocking d2h time in, so the transfer
    verdict here rides byte counts + the residual)."""
    ranked: List[Dict[str, Any]] = []

    def add(category: str, ms: float, count: int, **ev: Any) -> None:
        if ms > 0 or count > 0:
            ranked.append(_verdict_entry(category, ms, count, dict(ev)))

    add("sync-bound", float(summary.get("sync_ms", 0.0)),
        int(summary.get("sync_count", 0)),
        note="summary sync_ms folds blocking d2h fetch time in")
    add("compile-bound", float(summary.get("compile_ms", 0.0)),
        int(summary.get("compile_count", 0)))
    add("spill-bound", float(summary.get("spill_ms", 0.0)), 0)
    add("sem_wait-bound", float(summary.get("sem_wait_ms", 0.0)), 0)
    h2d, d2h = (int(summary.get("h2d_bytes", 0)),
                int(summary.get("d2h_bytes", 0)))
    if h2d or d2h:
        ranked.append(_verdict_entry(
            "h2d-d2h-bound", 0.0, 0,
            {"h2d_bytes": h2d, "d2h_bytes": d2h,
             "note": "bytes only: summary carries no transfer ms"}))
    if summary.get("dispatch_ms"):
        # the launch spans' self time; summaries may carry the per-key
        # launch table, and when absent evidence degrades to totals
        ev = _dispatch_evidence(
            int(summary.get("device_dispatches", 0) or 0), {},
            dict(summary.get("dispatch_by_key") or {}))
        add("dispatch-bound", float(summary["dispatch_ms"]),
            int(summary.get("dispatch_count", 0)), **ev)
    ranked.sort(key=lambda e: -e["ms"])
    attributed_ms = sum(e["ms"] for e in ranked)
    denom = wall_ms if wall_ms else (attributed_ms or 1.0)
    for e in ranked:
        e["share"] = round(min(1.0, e["ms"] / max(denom, 1e-9)), 4)
    _stamp_levers(ranked)
    caveats = ["diagnosed from compact trace_summary: no exec-level "
               "spans, transfer time folded into sync-bound"]
    if summary.get("trace_truncated") or summary.get("dropped_events"):
        caveats.append("trace was truncated (dropped_events > 0)")
    out = {
        "schema": SCHEMA,
        "verdict": ranked[0]["category"] if ranked else "no-bottleneck",
        "ranked": ranked,
        "attributed_ms": round(attributed_ms, 3),
        "trace_truncated": bool(summary.get("trace_truncated")
                                or summary.get("dropped_events")),
        "caveats": caveats,
    }
    if wall_ms is not None:
        out["wall_ms"] = round(float(wall_ms), 3)
    return out


def diagnose_tenants(records: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Per-TENANT bottleneck verdicts from flight-recorder records (the
    serving tier's ``engine.diagnose_tenants()``): records group by their
    ``tenant`` stamp, each group's trace summaries aggregate into one
    degraded-fidelity :func:`diagnose_summary`, and admission-queue wait
    (``admissionWaitMs`` in each record's metrics) joins the ranking as
    ``admission-bound`` — a tenant whose time goes to waiting for slots
    needs a weight/budget change, not a kernel fix."""
    groups: Dict[str, List[Dict[str, Any]]] = {}
    for rec in records:
        groups.setdefault(str(rec.get("tenant") or "default"),
                          []).append(rec)
    out: Dict[str, Any] = {}
    for tenant, recs in sorted(groups.items()):
        durs = sorted(float(r.get("duration_ms", 0.0)) for r in recs)
        agg: Dict[str, float] = {}
        adm_ms = 0.0
        adm_n = 0
        for r in recs:
            for k, v in (r.get("trace_summary") or {}).items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    agg[k] = agg.get(k, 0.0) + v
            w = (r.get("metrics") or {}).get("admissionWaitMs", 0.0)
            if w:
                adm_ms += float(w)
                adm_n += 1
        wall = sum(durs) + adm_ms
        diag = diagnose_summary(agg, wall_ms=wall or None)
        if adm_ms > 0:
            diag["ranked"].append(_verdict_entry(
                "admission-bound", adm_ms, adm_n,
                {"note": "time queued before execution; levers: tenant "
                         "weight, memory budget, maxConcurrentQueries"}))
            diag["ranked"].sort(key=lambda e: -e["ms"])
            denom = wall or sum(e["ms"] for e in diag["ranked"]) or 1.0
            for e in diag["ranked"]:
                e["share"] = round(min(1.0, e["ms"] / max(denom, 1e-9)), 4)
            diag["verdict"] = diag["ranked"][0]["category"]
            diag["attributed_ms"] = round(
                sum(e["ms"] for e in diag["ranked"]), 3)
            _stamp_levers(diag["ranked"])

        def _pctl(q: float) -> float:
            if not durs:
                return 0.0
            return durs[min(len(durs) - 1, int(q * len(durs)))]

        out[tenant] = {
            "queries": len(recs),
            "failed": sum(1 for r in recs
                          if r.get("status") != "ok"),
            "p50_ms": round(_pctl(0.50), 3),
            "p99_ms": round(_pctl(0.99), 3),
            "admission_wait_ms": round(adm_ms, 3),
            "diagnosis": compact(diag),
        }
    return out


def compact(diag: Dict[str, Any], top: int = 3) -> Dict[str, Any]:
    """Compact form: verdict + top-N {category, ms, share, count}
    (evidence trimmed to its counters; ``diagnose_tenants`` serves this
    per tenant)."""
    rows = []
    for e in diag.get("ranked", [])[:top]:
        row = {"category": e["category"], "ms": e["ms"],
               "share": e.get("share", 0.0), "count": e["count"]}
        ev = e.get("evidence", {})
        for k in ("bytes", "device_dispatches", "h2d_bytes", "d2h_bytes",
                  "launches_per_probe_batch", "top_exec", "top_kernels",
                  "levers"):
            if ev.get(k):
                row[k] = ev[k]
        rows.append(row)
    out = {"verdict": diag.get("verdict", "no-bottleneck"), "ranked": rows}
    if diag.get("trace_truncated"):
        out["trace_truncated"] = True
    return out


# --------------------------------------------------------------------------
# CLI: diagnose an exported event log (JSONL) or Chrome trace JSON
# --------------------------------------------------------------------------

def _events_from_chrome(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """Chrome trace-event JSON -> tracer-shaped events (dur stays µs;
    exec rides args.exec in the export)."""
    out = []
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") != "X":
            continue
        args = dict(ev.get("args") or {})
        out.append({"cat": ev.get("cat", ""), "name": ev.get("name", ""),
                    "ts": float(ev.get("ts", 0.0)),
                    "dur": float(ev.get("dur", 0.0)),
                    "tid": ev.get("tid", 0),
                    "exec": args.pop("exec", ""), "args": args})
    return out


def _load(path: str):
    """[(meta, events)] from a JSONL event log or a Chrome trace file."""
    with open(path) as fh:
        head = fh.read(1)
    if head == "{":
        with open(path) as fh:
            first = json.loads(fh.readline())
        if "traceEvents" in first:  # single-line chrome trace
            return [({}, _events_from_chrome(first))]
    from .export import read_event_log
    try:
        return read_event_log(path)
    except ValueError:
        with open(path) as fh:
            return [({}, _events_from_chrome(json.load(fh)))]


def main(argv: List[str]) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 1
    out_path = None
    if "--out" in argv:
        i = argv.index("--out")
        out_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    logs = _load(argv[0])
    if not logs:
        print("no queries found in", argv[0], file=sys.stderr)
        return 1
    # diagnose the LAST query in the log (newest appended)
    meta, events = logs[-1]
    diag = diagnose(events, counters=meta.get("counters"),
                    dropped_events=int(meta.get("dropped_events", 0)))
    text = json.dumps(diag, indent=1)
    if out_path:
        with open(out_path, "w") as fh:
            fh.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
