#!/usr/bin/env python3
"""Bench regression sentinel — diff two BENCH artifacts with thresholded
verdicts.

Compares the primary rows/s metric, per-shape extra metrics
(join/window/sort/whole-stage/encoded), trace summaries (sync counts/ms,
compile ms, bytes on the wire), stage dispatch counts and wire bytes,
and prints one verdict line per comparable metric:

    OK        within the threshold band
    IMPROVED  better by more than the threshold
    REGRESSED worse by more than the threshold
    ONLY-A / ONLY-B   metric present in one artifact only

Direction matters: rows/s, vs_baseline and GB/s improve UP; sync counts,
compile ms, dispatches and bytes-on-wire improve DOWN.

Evidence gating (ROADMAP item 5): an artifact is ``live`` (a real device
measurement from this round), ``stale-replay`` (an artifact carrying an old
``captured_at`` stamp) or
``cpu-fallback``.  Comparing live vs stale-replay is refused without
``--allow-stale``: a stale replay masquerading as the "before" side
manufactures phantom regressions/improvements.

Ledger mode (the perf sentry's evidence ledger,
.bench_capture/ledger.jsonl, srt-ledger/1): ``--ledger <path>`` resolves
the comparison baseline (side A) automatically as the artifact of the
NEWEST ``evidence: live`` ledger entry — a stale replay never becomes
the baseline no matter how recently it was appended.  With no live
entry the diff is REFUSED (exit 2); ``--allow-stale`` degrades the
resolution to the newest entry of any evidence class, with the usual
cross-evidence warning.

Usage:
  python tools/bench_diff.py A.json B.json [--threshold 0.10]
         [--allow-stale] [--fail-on-regress] [--json]
  python tools/bench_diff.py --ledger LEDGER.jsonl B.json [flags...]

Accepts driver round artifacts ({"parsed": {...}}), raw bench stdout
(last JSON line wins), or a bare result object.  Exit codes: 0 ok,
1 usage/parse error, 2 evidence mismatch / baseline resolution refused,
3 regressions found (only with --fail-on-regress).
"""

from __future__ import annotations

import json
import sys
from typing import Any, Dict, List, Optional, Tuple

#: metric-name fragments whose value improves DOWNWARD.  Serving-tier
#: latency records (docs/serving.md) join here: p50/p99 latency and
#: admission wait improve DOWN while qps improves UP (the default), so
#: "QPS up is IMPROVED, p99 up is REGRESSED" falls out of the fragments.
_LOWER_BETTER = ("sync_count", "sync_ms", "compile_ms", "compile_count",
                 "bytes_on_wire", "dispatches", "spill_ms", "sem_wait_ms",
                 "dropped_events", "h2d_bytes", "d2h_bytes", "seconds",
                 "_us", "p50", "p95", "p99", "latency", "wait_ms",
                 "wall_s",
                 # query-lifecycle records (docs/robustness.md): cancel
                 # drain latency, deadline overshoot and quarantine
                 # counts all improve DOWN
                 "cancel_latency", "overshoot", "quarantine_count",
                 # fault_recovery records (testing/chaos_cluster.py):
                 # detection / recompute / query latencies improve DOWN
                 "detection_ms", "recompute_ms", "query_ms")
#: keys that are identifiers/context, never diffed
_SKIP = ("rows", "chips", "queries", "probe_attempts", "budget_ms",
         "elapsed_ms", "partial_banked_at", "pipeline_host_cores",
         "workload_queries", "parallelism", "tenants",
         "distinct_queries", "serving_rows", "deadline_ms",
         "cancels_measured", "degraded_queries")


def load_artifact(path: str) -> Dict[str, Any]:
    """Load a bench result from a driver artifact, raw stdout capture, or
    bare result JSON."""
    with open(path) as fh:
        text = fh.read()
    try:
        doc = json.loads(text)
        if isinstance(doc, dict):
            if "parsed" in doc and isinstance(doc["parsed"], dict):
                return doc["parsed"]
            if "metric" in doc or "value" in doc:
                return doc
    except ValueError:
        pass
    # raw stdout: last JSON line carrying a final result wins
    best = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and ("metric" in rec or "value" in rec):
            best = rec
    if best is None:
        raise ValueError(f"{path}: no bench result record found")
    return best


def evidence_of(rec: Dict[str, Any]) -> str:
    """The artifact's evidence class; derives it for artifacts banked
    before bench.py stamped ``evidence`` explicitly."""
    ev = rec.get("evidence")
    if ev:
        return str(ev)
    if "captured_at" in rec:
        return "stale-replay"
    if rec.get("platform") == "cpu" or rec.get("platform") is None:
        return "cpu-fallback"
    return "live"


def read_ledger(path: str) -> List[Dict[str, Any]]:
    """Parse an srt-ledger/1 evidence ledger (append-only JSONL),
    skipping torn or foreign lines — mirrors
    observability/sentry.EvidenceLedger.entries() without importing the
    package (this tool stays dependency-free)."""
    out: List[Dict[str, Any]] = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn line (crash mid-append)
            if isinstance(rec, dict) and rec.get("schema") == "srt-ledger/1":
                out.append(rec)
    return out


def resolve_baseline(entries: List[Dict[str, Any]],
                     allow_stale: bool = False) -> Optional[str]:
    """Baseline artifact path from ledger entries: the newest
    ``evidence: live`` entry carrying an artifact path.  ``allow_stale``
    falls back to the newest entry of ANY evidence class — the evidence
    gate in run() then prints the cross-evidence warning."""
    for rec in reversed(entries):
        if rec.get("evidence") == "live" and rec.get("artifact"):
            return str(rec["artifact"])
    if allow_stale:
        for rec in reversed(entries):
            if rec.get("artifact"):
                return str(rec["artifact"])
    return None


def _flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves of a nested dict, dotted-path keyed; skips
    identifier keys and underscore-private keys."""
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            if k.startswith("_") or k in _SKIP or k.endswith("_rows"):
                continue  # sizes are context, not rates
            out.update(_flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, bool):
        pass
    elif isinstance(obj, (int, float)):
        out[prefix[:-1]] = float(obj)
    return out


def comparable_metrics(rec: Dict[str, Any]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(rec.get("value"), (int, float)) and rec.get("value"):
        out[str(rec.get("metric", "value"))] = float(rec["value"])
    for k in ("vs_baseline", "gb_per_s_per_chip", "trace_overhead",
              "chaos_overhead", "sync_rtt_ms"):
        v = rec.get(k)
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = float(v)
    ts = rec.get("trace_summary")
    if isinstance(ts, dict):
        out.update(_flatten(ts, "trace_summary."))
    em = rec.get("extra_metrics")
    if isinstance(em, dict):
        out.update(_flatten(em, ""))
    return out


def lower_is_better(name: str) -> bool:
    return any(f in name for f in _LOWER_BETTER)


def diff(a: Dict[str, float], b: Dict[str, float], threshold: float
         ) -> List[Dict[str, Any]]:
    rows: List[Dict[str, Any]] = []
    for name in sorted(set(a) | set(b)):
        va, vb = a.get(name), b.get(name)
        if va is None or vb is None:
            rows.append({"metric": name, "a": va, "b": vb,
                         "verdict": "ONLY-B" if va is None else "ONLY-A"})
            continue
        if va == 0:
            ratio = None
            verdict = "OK" if vb == 0 else "CHANGED"
        else:
            ratio = vb / va
            rel = ratio - 1.0
            if lower_is_better(name):
                rel = -rel
            if rel >= threshold:
                verdict = "IMPROVED"
            elif rel <= -threshold:
                verdict = "REGRESSED"
            else:
                verdict = "OK"
        rows.append({"metric": name, "a": va, "b": vb,
                     "ratio": round(ratio, 4) if ratio is not None
                     else None, "verdict": verdict})
    return rows


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.4g}"


def run(path_a: str, path_b: str, threshold: float, allow_stale: bool,
        as_json: bool) -> Tuple[int, List[Dict[str, Any]]]:
    ra, rb = load_artifact(path_a), load_artifact(path_b)
    ea, eb = evidence_of(ra), evidence_of(rb)
    if ea != eb and not allow_stale:
        print(f"REFUSED: evidence mismatch — {path_a} is '{ea}', "
              f"{path_b} is '{eb}'.  A stale replay or CPU fallback is "
              f"not comparable to a live device measurement; rerun with "
              f"--allow-stale to force.", file=sys.stderr)
        return 2, []
    rows = diff(comparable_metrics(ra), comparable_metrics(rb), threshold)
    regressed = [r for r in rows if r["verdict"] == "REGRESSED"]
    improved = [r for r in rows if r["verdict"] == "IMPROVED"]
    header = {"a": {"path": path_a, "evidence": ea,
                    "note": ra.get("note", "")[:120]},
              "b": {"path": path_b, "evidence": eb,
                    "note": rb.get("note", "")[:120]},
              "threshold": threshold,
              "regressed": len(regressed), "improved": len(improved)}
    if as_json:
        print(json.dumps({"header": header, "rows": rows}, indent=1))
    else:
        print(f"A: {path_a}  [evidence: {ea}]")
        print(f"B: {path_b}  [evidence: {eb}]")
        if ea != eb:
            print("WARNING: comparing across evidence classes "
                  "(--allow-stale)")
        print(f"threshold: ±{threshold:.0%}\n")
        w = max((len(r["metric"]) for r in rows), default=10)
        print(f"{'metric':<{w}} {'A':>14} {'B':>14} {'B/A':>8}  verdict")
        for r in rows:
            ratio = "-" if r.get("ratio") is None else f"{r['ratio']:.3f}"
            print(f"{r['metric']:<{w}} {_fmt(r['a']):>14} "
                  f"{_fmt(r['b']):>14} {ratio:>8}  {r['verdict']}")
        print(f"\nSUMMARY: {len(improved)} improved, {len(regressed)} "
              f"regressed, {len(rows) - len(improved) - len(regressed)} "
              f"other")
        for r in regressed:
            print(f"  REGRESSED {r['metric']}: {_fmt(r['a'])} -> "
                  f"{_fmt(r['b'])} ({r['ratio']:.3f}x)")
    return (0, rows)


def main(argv: List[str]) -> int:
    if len(argv) < 2 or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 1
    threshold = 0.10
    allow_stale = "--allow-stale" in argv
    fail_on_regress = "--fail-on-regress" in argv
    as_json = "--json" in argv
    argv = [a for a in argv
            if a not in ("--allow-stale", "--fail-on-regress", "--json")]
    if "--threshold" in argv:
        i = argv.index("--threshold")
        threshold = float(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    if "--ledger" in argv:
        i = argv.index("--ledger")
        ledger_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
        if len(argv) != 1:
            print(__doc__)
            return 1
        try:
            entries = read_ledger(ledger_path)
        except OSError as e:
            print(f"ERROR: {e}", file=sys.stderr)
            return 1
        baseline = resolve_baseline(entries, allow_stale=allow_stale)
        if baseline is None:
            print(f"REFUSED: no 'evidence: live' entry with an artifact "
                  f"in ledger {ledger_path} ({len(entries)} entries) — "
                  f"there is no live baseline to diff against.  Capture "
                  f"a live window first, or rerun with --allow-stale to "
                  f"fall back to the newest entry of any evidence "
                  f"class.", file=sys.stderr)
            return 2
        argv = [baseline] + argv
    if len(argv) != 2:
        print(__doc__)
        return 1
    try:
        rc, rows = run(argv[0], argv[1], threshold, allow_stale, as_json)
    except (OSError, ValueError) as e:
        print(f"ERROR: {e}", file=sys.stderr)
        return 1
    if rc:
        return rc
    if fail_on_regress and any(r["verdict"] == "REGRESSED" for r in rows):
        return 3
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
