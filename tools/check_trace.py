#!/usr/bin/env python3
"""Validate an exported Chrome trace-event JSON file against the
trace-event schema subset the tracer emits (observability/export.py):
every event must carry ph/ts/pid/tid/name; "X" complete events must
carry a non-negative dur.  Used by ci/run_ci.sh after the traced-query
step and by tests/test_tracer.py.

Usage: python tools/check_trace.py [<trace.json> ...] [--min-events N]
           [--require-cat CAT] [--require-arg KEY]
           [--prometheus FILE] [--prometheus-label KEY]
           [--doctor FILE] [--flow FILE] [--endpoint URL]
``--require-cat`` additionally fails unless at least one span event
carries that category (e.g. ``fault`` for chaos-soak traces).
``--require-arg`` fails unless at least one span event carries that
args key (e.g. ``tenant`` for serving-engine traces).
``--prometheus-label`` fails unless at least one Prometheus sample
carries that label key (e.g. ``tenant`` for serving metrics).
``--prometheus`` validates a metrics-registry export against the
Prometheus exposition contract (typed series, cumulative histogram
buckets ending at +Inf, consistent _sum/_count).
``--doctor`` validates a doctor diagnosis JSON against the
srt-doctor/1 schema (known verdict, ranked entries with
category/ms/share/evidence; a ``dispatch-bound`` entry is measured from
the ``eager`` and ``dispatch`` spans, never estimated).
``--flow`` validates a merged trace (tools/trace_merge.py output):
every flow id must have both an "s" start and an "f" finish, each
anchored inside a real span on the same pid/tid, and every pid with
spans must carry process_name metadata.
``--endpoint`` scrapes a live telemetry server URL
(observability/server.py) and holds the response body to the
Prometheus exposition contract.
Exit 0 when every requested check passes, 1 otherwise.
"""

import json
import sys

REQUIRED = ("ph", "ts", "pid", "tid", "name")
KNOWN_PH = ("X", "C", "i", "M", "B", "E", "s", "t", "f")

#: categories the tracer emits today (observability/tracer.py
#: CATEGORIES); unknown categories stay opaque — listed for reference
#: and for --require-cat hints, not validated
KNOWN_CATS = ("query", "plan", "task", "op", "stage", "dispatch", "compile",
              "eager", "scan", "sync", "h2d", "d2h", "spill", "shuffle",
              "sem_wait", "fault", "queue", "encode", "admission", "cancel",
              "fatal", "broadcast", "join", "sort", "window")


def check(path: str, min_events: int = 1, require_cat: str = "",
          require_arg: str = ""):
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise ValueError("traceEvents is not a list")
    spans = 0
    cats = set()
    arg_keys = set()
    for i, ev in enumerate(events):
        for field in REQUIRED:
            if field not in ev:
                raise ValueError(f"event {i} missing required field "
                                 f"{field!r}: {ev}")
        if ev["ph"] not in KNOWN_PH:
            raise ValueError(f"event {i} has unknown ph {ev['ph']!r}")
        if not isinstance(ev["ts"], (int, float)):
            raise ValueError(f"event {i} ts is not numeric: {ev['ts']!r}")
        if ev["ph"] == "X":
            if "dur" not in ev or not isinstance(ev["dur"], (int, float)) \
                    or ev["dur"] < 0:
                raise ValueError(f"event {i} 'X' span needs dur >= 0: {ev}")
            spans += 1
            cats.add(ev.get("cat", ""))
            for k in (ev.get("args") or {}):
                arg_keys.add(k)
    if spans < min_events:
        raise ValueError(f"expected at least {min_events} span event(s), "
                         f"found {spans}")
    if require_cat and require_cat not in cats:
        raise ValueError(
            f"no span event with category {require_cat!r} "
            f"(found: {sorted(c for c in cats if c)})")
    if require_arg and require_arg not in arg_keys:
        raise ValueError(
            f"no span event carrying args[{require_arg!r}] "
            f"(found arg keys: {sorted(arg_keys)})")
    return spans, sorted(c for c in cats if c)


#: the doctor's verdict classes (observability/doctor.py VERDICTS)
DOCTOR_VERDICTS = ("sync-bound", "compile-bound", "h2d-d2h-bound",
                   "dispatch-bound", "sem_wait-bound", "spill-bound",
                   "shuffle-bound", "admission-bound", "slo-burn",
                   "no-bottleneck")


def check_flow(path: str, min_flows: int = 1):
    """Validate cross-process flow stitching in a merged trace: every
    flow id pairs an "s" with an "f", both landing inside a span on the
    same pid/tid, and every pid that has spans is named via "M"
    process_name metadata."""
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    spans_by_track = {}
    named_pids = set()
    span_pids = set()
    flows = {}
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph == "X":
            spans_by_track.setdefault(
                (ev["pid"], ev["tid"]), []).append(
                (float(ev["ts"]), float(ev.get("dur", 0.0))))
            span_pids.add(ev["pid"])
        elif ph == "M" and ev.get("name") == "process_name":
            named_pids.add(ev["pid"])
        elif ph in ("s", "t", "f"):
            if "id" not in ev:
                raise ValueError(f"event {i} flow event missing 'id'")
            flows.setdefault(ev["id"], {})[ph] = ev
    if len(flows) < min_flows:
        raise ValueError(f"expected at least {min_flows} flow id(s), "
                         f"found {len(flows)}")
    for fid, phases in flows.items():
        for need in ("s", "f"):
            if need not in phases:
                raise ValueError(f"flow {fid}: missing {need!r} phase "
                                 f"(has {sorted(phases)})")
        if phases["s"].get("name") != phases["f"].get("name") \
                or phases["s"].get("cat") != phases["f"].get("cat"):
            raise ValueError(f"flow {fid}: s/f name or cat mismatch")
        for ph, ev in phases.items():
            ts = float(ev["ts"])
            track = spans_by_track.get((ev["pid"], ev["tid"]), [])
            if not any(t0 - 1e-6 <= ts <= t0 + dur + 1e-6
                       for t0, dur in track):
                raise ValueError(
                    f"flow {fid} {ph!r} at ts={ts} not inside any span "
                    f"on pid={ev['pid']} tid={ev['tid']}")
    cross = sum(1 for p in flows.values()
                if p["s"]["pid"] != p["f"]["pid"])
    for pid in span_pids:
        if pid not in named_pids:
            raise ValueError(f"pid {pid} has spans but no process_name "
                             f"metadata")
    return len(flows), cross, len(span_pids)


def check_prometheus(path: str, require_label: str = ""):
    """Validate a Prometheus exposition FILE (see _check_prom_lines)."""
    with open(path) as fh:
        return _check_prom_lines(fh, require_label)


def check_endpoint(url: str, require_label: str = "") -> str:
    """Scrape a live telemetry URL and hold the response body to the
    Prometheus exposition contract."""
    import urllib.request
    if not url.startswith(("http://", "https://")):
        url = "http://" + url
    with urllib.request.urlopen(url, timeout=10) as resp:
        body = resp.read().decode("utf-8", "replace")
    n, fams = _check_prom_lines(body.splitlines(), require_label)
    return f"{n} samples, {len(fams)} families"


def _check_prom_lines(lines, require_label: str = ""):
    """Validate Prometheus exposition text: every sample belongs to a
    # TYPE-declared family; histogram buckets are cumulative and end at
    +Inf with a count matching _count."""
    import re
    types = {}
    samples = []
    for ln, line in enumerate(lines, 1):
        line = line.rstrip("\n")
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split()
            if typ not in ("counter", "gauge", "histogram"):
                raise ValueError(f"line {ln}: unknown type {typ!r}")
            types[name] = typ
            continue
        if line.startswith("#"):
            continue
        m = re.match(r"^([A-Za-z_:][A-Za-z0-9_:]*)(\{.*\})? "
                     r"([0-9.eE+-]+|\+Inf|NaN)$", line)
        if not m:
            raise ValueError(f"line {ln}: malformed sample: {line!r}")
        samples.append((m.group(1), m.group(2) or "", m.group(3)))
    if not samples:
        raise ValueError("no samples")
    if require_label and not any(
            f'{require_label}="' in labels for _n, labels, _v in samples):
        raise ValueError(f"no sample carries label {require_label!r}")
    fams = set(types)
    buckets = {}
    for name, labels, value in samples:
        base = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in fams:
                base = name[:-len(suffix)]
        if base not in fams:
            raise ValueError(f"sample {name!r} has no # TYPE declaration")
        if name.endswith("_bucket") and types.get(base) == "histogram":
            series = labels.replace('le="', "\0").split("\0")[0]
            buckets.setdefault((base, series), []).append(
                (labels, float("inf") if "+Inf" in labels
                 else None, int(float(value))))
    for (base, _), rows in buckets.items():
        counts = [v for _, _, v in rows]
        if counts != sorted(counts):
            raise ValueError(f"{base}: bucket counts not cumulative")
        if not any(le == float("inf") for _, le, _ in rows):
            raise ValueError(f"{base}: histogram missing +Inf bucket")
    return len(samples), sorted(types)


def check_doctor(path: str):
    """Validate a doctor diagnosis JSON (srt-doctor/1 schema)."""
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("schema") != "srt-doctor/1":
        raise ValueError(f"schema is {doc.get('schema')!r}, "
                         f"expected 'srt-doctor/1'")
    if doc.get("verdict") not in DOCTOR_VERDICTS:
        raise ValueError(f"unknown verdict {doc.get('verdict')!r}")
    ranked = doc.get("ranked")
    if not isinstance(ranked, list):
        raise ValueError("ranked is not a list")
    if doc["verdict"] != "no-bottleneck" and not ranked:
        raise ValueError("non-trivial verdict with empty ranked list")
    last_ms = float("inf")
    for i, e in enumerate(ranked):
        for field in ("category", "ms", "count", "share", "evidence"):
            if field not in e:
                raise ValueError(f"ranked[{i}] missing {field!r}: {e}")
        if e["category"] not in DOCTOR_VERDICTS:
            raise ValueError(f"ranked[{i}] unknown category "
                             f"{e['category']!r}")
        if not 0.0 <= e["share"] <= 1.0:
            raise ValueError(f"ranked[{i}] share out of range: "
                             f"{e['share']}")
        if e["ms"] > last_ms + 1e-9:
            raise ValueError("ranked list not sorted by ms desc")
        if e["category"] == "dispatch-bound" and \
                "estimated" in (e["evidence"] or {}):
            raise ValueError(f"ranked[{i}] dispatch-bound is estimated: "
                             f"it is measured from the eager and "
                             f"dispatch spans' self time")
        last_ms = e["ms"]
    if ranked and doc["verdict"] != ranked[0]["category"]:
        raise ValueError("verdict != top ranked category")
    if not isinstance(doc.get("trace_truncated"), bool):
        raise ValueError("trace_truncated missing or not bool")
    return doc["verdict"], len(ranked)


def main(argv) -> int:
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        return 1
    min_events = 1
    require_cat = ""
    require_arg = ""
    prom_label = ""
    prom_paths = []
    doctor_paths = []
    flow_paths = []
    endpoints = []
    if "--min-events" in argv:
        i = argv.index("--min-events")
        min_events = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    if "--require-cat" in argv:
        i = argv.index("--require-cat")
        require_cat = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if "--require-arg" in argv:
        i = argv.index("--require-arg")
        require_arg = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if "--prometheus-label" in argv:
        i = argv.index("--prometheus-label")
        prom_label = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    while "--prometheus" in argv:
        i = argv.index("--prometheus")
        prom_paths.append(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    while "--doctor" in argv:
        i = argv.index("--doctor")
        doctor_paths.append(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    while "--flow" in argv:
        i = argv.index("--flow")
        flow_paths.append(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    while "--endpoint" in argv:
        i = argv.index("--endpoint")
        endpoints.append(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    rc = 0
    for path in argv:
        try:
            spans, cats = check(path, min_events, require_cat,
                                require_arg)
            print(f"OK {path}: {spans} span events, "
                  f"categories: {', '.join(cats) or '(none)'}")
        except (OSError, ValueError, KeyError) as e:
            print(f"FAIL {path}: {e}", file=sys.stderr)
            rc = 1
    for path in prom_paths:
        try:
            n, fams = check_prometheus(path, prom_label)
            print(f"OK {path}: {n} samples, {len(fams)} families")
        except (OSError, ValueError, KeyError) as e:
            print(f"FAIL {path}: {e}", file=sys.stderr)
            rc = 1
    for path in doctor_paths:
        try:
            verdict, n = check_doctor(path)
            print(f"OK {path}: verdict {verdict}, {n} ranked entries")
        except (OSError, ValueError, KeyError) as e:
            print(f"FAIL {path}: {e}", file=sys.stderr)
            rc = 1
    for path in flow_paths:
        try:
            n, cross, pids = check_flow(path)
            print(f"OK {path}: {n} flow edge(s) "
                  f"({cross} cross-process) over {pids} process(es)")
        except (OSError, ValueError, KeyError) as e:
            print(f"FAIL {path}: {e}", file=sys.stderr)
            rc = 1
    for url in endpoints:
        try:
            desc = check_endpoint(url, prom_label)
            print(f"OK {url}: {desc}")
        except Exception as e:  # urllib raises many flavours
            print(f"FAIL {url}: {e}", file=sys.stderr)
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
