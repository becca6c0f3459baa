#!/usr/bin/env python3
"""Process-kill chaos harness CLI — the pod-scale fault-domain proof.

Spawns a real N-process shuffle topology (driver registry + N executor
subprocesses on the TCP plane) and runs the seeded fault scenarios from
``spark_rapids_tpu.testing.chaos_cluster``:

  sigkill     SIGKILL an executor mid-query: retry -> lineage recompute,
              failure-detector dead-declaration, bit-identical digest.
  zombie      SIGSTOP past dead-declaration + replacement registration
              (epoch bump), then SIGCONT: every stale-epoch response the
              zombie serves must be REFUSED (fencing proof) while the
              result stays bit-identical.
  partition   frozen peer (asymmetric partition): post-declaration
              fetches take the dead-skip fast path straight to
              recompute.

Writes ``report.json`` (with the ``fault_recovery`` latency record)
plus per-process trace event logs suitable
for tools/trace_merge.py + check_trace --require-cat fault.

Usage:
  python tools/chaos_cluster.py [--procs 3] [--seed 7] [--rows 512]
         [--scenario sigkill|zombie|partition|all] [--out DIR] [--json]

Exit codes: 0 every scenario bit-identical and fenced, 1 otherwise.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="process-kill chaos harness for the shuffle fault "
                    "domain")
    p.add_argument("--procs", type=int, default=3,
                   help="executor process count (>= 2; default 3)")
    p.add_argument("--seed", type=int, default=7,
                   help="seed for data AND fault points (default 7)")
    p.add_argument("--rows", type=int, default=512,
                   help="rows per map task (default 512)")
    p.add_argument("--scenario", action="append",
                   choices=["sigkill", "zombie", "partition", "all"],
                   help="fault scenario to run; repeatable (default all)")
    p.add_argument("--out", default="",
                   help="output dir for report.json + event logs "
                        "(default: a fresh temp dir)")
    p.add_argument("--json", action="store_true",
                   help="print the full report as JSON instead of the "
                        "human summary")
    return p


def main(argv) -> int:
    args = build_arg_parser().parse_args(argv)
    # runnable from anywhere: the engine lives one level up from tools/
    # (the leak_sentinel.py pattern — the package is not pip-installed)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    # the child executors import the package by name too
    os.environ["PYTHONPATH"] = repo + os.pathsep + os.environ.get(
        "PYTHONPATH", "")
    # N executor processes cannot share one chip: host platform, always
    from spark_rapids_tpu import pin_host_platform
    pin_host_platform()
    from spark_rapids_tpu.testing.chaos_cluster import SCENARIOS, run_suite

    out = args.out or tempfile.mkdtemp(prefix="srt-chaos-cluster-")
    os.makedirs(out, exist_ok=True)
    selected = args.scenario or ["all"]
    names = (list(SCENARIOS) if "all" in selected
             else [s for s in SCENARIOS if s in selected])
    report = run_suite(names, nprocs=args.procs, seed=args.seed,
                       rows=args.rows, out_dir=out)
    report["out_dir"] = out
    with open(os.path.join(out, "report.json"), "w") as fh:
        json.dump(report, fh, indent=2, sort_keys=True)

    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        for r in report["scenarios"]:
            bits = [f"{r['scenario']:<9}",
                    "bit-identical" if r["ok"] else "PARITY BROKEN"]
            for k in ("detection_ms", "recompute_ms",
                      "degraded_query_ms", "stale_epochs_refused",
                      "blocks_recomputed", "dead_failovers"):
                if k in r:
                    bits.append(f"{k}={r[k]}")
            print("  ".join(bits))
        print(f"report: {os.path.join(out, 'report.json')}")
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
