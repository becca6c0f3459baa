#!/usr/bin/env python3
"""Perf sentry CLI — the unattended live-window capture daemon.

Drives spark_rapids_tpu/observability/sentry.py end to end with zero
manual steps: probe the device on an exponential-backoff cadence
(cancellable, bounded-timeout, every attempt classified and banked), and
on a live window run the bench shape set, bench_diff it against the last
live-evidence baseline auto-resolved from the evidence ledger, and
append the srt-ledger/1 record (artifact path, evidence class,
regression verdicts, doctor verdict, machine-named follow-up).

Usage:
  python tools/perf_sentry.py --daemon [--force] [--full-capture]
  python tools/perf_sentry.py --once [--force]
  python tools/perf_sentry.py --simulate-window [--windows 2]
  python tools/perf_sentry.py --status

Modes:
  --daemon            loop forever (probe cadence with backoff); the
                      default when no mode flag is given
  --once              one probe tick; on a live window one full capture
                      cycle.  Exit 0 when a ledger entry was appended,
                      1 when no window opened.
  --simulate-window   fake an open window (probe always ok) and run the
                      shape set in-process at small row counts with
                      evidence forced to 'live' and the ledger record
                      honestly marked "simulated": true — the CI e2e
                      mode.  Implies --once semantics; --windows N runs
                      N back-to-back windows (so window 2 diffs against
                      window 1's entry).
  --status            print the srt-sentry/1 status payload for the
                      configured ledger and exit

Flags:
  --force             run even with spark.rapids.tpu.sentry.enabled
                      false (the conf gate guards implicit startups,
                      not an operator invoking the CLI directly)
  --full-capture      after the sentry's own shape-set capture on a
                      live window, also run the legacy full capture
                      cycle (bench.py main/warm/suite + leak-sentinel
                      soak into .bench_capture/, throttled to once per
                      2h, mkdir-mutexed) so bench.py's replay fallback
                      keeps being fed
  --ledger PATH       evidence ledger (default: conf ledgerPath, else
                      .bench_capture/ledger.jsonl)
  --shapes CSV        shape subset (default: conf sentry.shapes)
  --rows N            shape-set row count
  --interval-s S      probe interval (default: conf probeIntervalMs)
  --probe-timeout-s S probe deadline (default: conf probeTimeoutMs)
  --budget-s S        shape-set watchdog budget
  --serve-port P      also serve the telemetry plane (incl. /sentry) on
                      127.0.0.1:P while running
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from spark_rapids_tpu.observability import sentry as S  # noqa: E402


def _log(msg: str) -> None:
    ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    print(f"{ts} {msg}", flush=True)


# --------------------------------------------------------------------------
# full-capture cycle
# --------------------------------------------------------------------------

def full_capture_cycle(cap_dir: str) -> str:
    """The full capture payload: bench.py main/warm/suite runs plus
    a leak-sentinel soak, their outputs kept under ``cap_dir`` (nothing
    replays them; bench.py measures live or fails).  Throttled to once per 2h via ``capture_done``; mutexed
    via a ``capture_running`` mkdir (one syscall test-and-set — two
    sentries on one chip must not bank contended numbers as evidence).
    Returns ``done | fruitless | throttled | locked``."""
    os.makedirs(cap_dir, exist_ok=True)
    done_stamp = os.path.join(cap_dir, "capture_done")
    lock = os.path.join(cap_dir, "capture_running")
    try:
        if os.path.exists(done_stamp) \
                and time.time() - os.path.getmtime(done_stamp) < 7200:
            return "throttled"
        # clear a stale lock (a capture should never exceed ~4h)
        if os.path.isdir(lock) \
                and time.time() - os.path.getmtime(lock) > 14400:
            os.rmdir(lock)
    except OSError:
        pass
    try:
        os.mkdir(lock)
    except OSError:
        return "locked"
    cycle_files = []
    try:
        # main FIRST: .jax_cache already holds the warm programs from
        # earlier runs — the 8M-row headline number must not wait behind
        # a warm-up run
        for mode, budget, extra in (("main", 1800, []),
                                    ("warm", 1200, ["2000000"]),
                                    ("suite", 3600, ["--suite"])):
            ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
            _log(f"capture {mode} start")
            env = dict(os.environ, BENCH_BUDGET_S=str(budget))
            out_path = os.path.join(cap_dir, f"run_{ts}_{mode}.out")
            with open(out_path, "w") as out, \
                    open(os.path.join(
                        cap_dir, f"run_{ts}_{mode}.err"), "w") as err:
                try:
                    subprocess.run(
                        [sys.executable,
                         os.path.join(_REPO, "bench.py")] + extra,
                        cwd=_REPO, env=env, stdout=out, stderr=err,
                        timeout=budget + 100)
                except subprocess.TimeoutExpired:
                    pass  # the output file keeps what was printed
            cycle_files.append(out_path)
            _log(f"capture {mode} done")
        # leak-sentinel soak on the SAME live window: short and last —
        # the bench numbers above must never wait behind a soak
        ts = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        _log("capture soak start")
        try:
            subprocess.run(
                [sys.executable,
                 os.path.join(_REPO, "tools", "leak_sentinel.py"),
                 "--seconds", "600", "--tenants", "2", "--rows", "8000",
                 "--out", os.path.join(cap_dir, f"soak_{ts}.json")],
                cwd=_REPO, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL, timeout=700)
        except subprocess.TimeoutExpired:
            pass
        _log("capture soak done")
        # stamp capture_done ONLY if the cycle banked a record bench.py's
        # replay will accept (same predicate — the two can never drift)
        import bench  # parent-safe: bench.py never imports jax at import
        usable = False
        for path in cycle_files:
            try:
                with open(path) as fh:
                    for line in fh:
                        line = line.strip()
                        if not line.startswith("{"):
                            continue
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue
                        if bench._usable_capture_record(rec):
                            usable = True
            except OSError:
                pass
        if usable:
            with open(done_stamp, "w") as fh:
                fh.write(time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime()) + "\n")
            return "done"
        _log("capture cycle banked no on-chip record")
        return "fruitless"
    finally:
        try:
            os.rmdir(lock)
        except OSError:
            pass


# --------------------------------------------------------------------------
# CLI
# --------------------------------------------------------------------------

def build_sentry(args: argparse.Namespace) -> S.PerfSentry:
    overrides = {}
    if args.ledger:
        overrides["ledger"] = args.ledger
    if args.shapes:
        overrides["shapes"] = [s.strip() for s in args.shapes.split(",")
                               if s.strip()]
    if args.rows:
        overrides["rows"] = args.rows
    if args.interval_s is not None:
        overrides["interval_s"] = args.interval_s
    if args.probe_timeout_s is not None:
        overrides["probe_timeout_s"] = args.probe_timeout_s
    if args.budget_s is not None:
        overrides["bench_budget_s"] = args.budget_s
    if args.simulate_window:
        rows = args.rows or 50_000
        budget = args.budget_s or 240.0
        overrides["probe"] = lambda: {"outcome": "ok",
                                      "platform": "simulated",
                                      "elapsed_ms": 0.1}
        overrides["bench"] = lambda shapes: S.run_shape_set_inprocess(
            shapes, rows=rows, budget_s=budget, evidence="live")
        overrides["entry_extra"] = {"simulated": True}
    else:
        # the daemon process stays jax-free: probe and shape set both
        # run in throwaway subprocesses (a wedged device kills a child)
        overrides.setdefault(
            "probe", lambda: S.subprocess_probe(
                args.probe_timeout_s
                if args.probe_timeout_s is not None else 30.0))
    return S.PerfSentry.from_conf(**overrides)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="perf_sentry",
        description="autonomous live-window perf capture daemon")
    mode = p.add_mutually_exclusive_group()
    mode.add_argument("--daemon", action="store_true")
    mode.add_argument("--once", action="store_true")
    mode.add_argument("--status", action="store_true")
    p.add_argument("--simulate-window", action="store_true")
    p.add_argument("--windows", type=int, default=1,
                   help="simulated windows to run back-to-back")
    p.add_argument("--force", action="store_true")
    p.add_argument("--full-capture", action="store_true")
    p.add_argument("--ledger")
    p.add_argument("--shapes")
    p.add_argument("--rows", type=int)
    p.add_argument("--interval-s", type=float)
    p.add_argument("--probe-timeout-s", type=float)
    p.add_argument("--budget-s", type=float)
    p.add_argument("--serve-port", type=int)
    p.add_argument("--json", action="store_true",
                   help="print appended ledger entries as JSON lines")
    args = p.parse_args(argv)

    if args.status:
        led = S.EvidenceLedger(args.ledger)
        payload = {
            "schema": S.STATUS_SCHEMA, "phase": "none",
            "running": False,
            "note": "CLI status for the on-disk ledger",
            "ledger": {"path": led.path, "entries": len(led.entries()),
                       "tail": led.tail(5)},
            "last_live_age_s": led.last_live_age_s(),
        }
        print(json.dumps(payload, indent=1, default=str))
        return 0

    if not (args.force or args.simulate_window) \
            and not S.PerfSentry.enabled():
        print("sentry disabled (spark.rapids.tpu.sentry.enabled=false);"
              " pass --force, or enable the conf", file=sys.stderr)
        return 2

    sentry = build_sentry(args)
    S.set_active(sentry)
    server = None
    if args.serve_port is not None:
        from spark_rapids_tpu.observability.metrics import get_registry
        from spark_rapids_tpu.observability.server import TelemetryServer
        server = TelemetryServer(
            metrics_text=lambda: get_registry().prometheus_text(),
            healthz=lambda: (True, {"sentry": sentry.phase}),
            queries=lambda: [],
            doctor=lambda: {"note": "standalone sentry process"},
            slo=lambda: {},
            port=args.serve_port)
        _log(f"telemetry plane (incl. /sentry) at {server.endpoint}")

    try:
        if args.once or args.simulate_window:
            appended = 0
            for _ in range(max(1, args.windows
                               if args.simulate_window else 1)):
                entry = sentry.run_once()
                if entry is not None:
                    appended += 1
                    if args.json:
                        print(json.dumps(entry, default=str))
                    else:
                        _log(f"ledger entry appended: "
                             f"evidence={entry.get('evidence')} "
                             f"diff={entry.get('diff', {}).get('verdict')} "
                             f"followup={entry.get('followup')!r}")
                    if args.full_capture:
                        _log("full capture cycle: "
                             + full_capture_cycle(
                                 os.path.dirname(os.path.abspath(
                                     sentry.ledger.path))))
                else:
                    last = (sentry.probe_attempts or [{}])[-1]
                    _log(f"no window: probe outcome="
                         f"{last.get('outcome')} "
                         f"next_delay_s={sentry.backoff_s:.0f} "
                         f"error={last.get('error')}")
            return 0 if appended else 1

        # daemon: synchronous loop (not .start()) so --full-capture can
        # run between windows without racing the sentry thread
        _log(f"sentry daemon up: interval={sentry.interval_s:.0f}s "
             f"probe_timeout={sentry.probe_timeout_s:.0f}s "
             f"shapes={','.join(sentry.shapes)} "
             f"ledger={sentry.ledger.path}")
        while True:
            entry = sentry.run_once()
            if entry is not None:
                _log(f"window captured: artifact="
                     f"{entry.get('artifact')} "
                     f"diff={entry.get('diff', {}).get('verdict')} "
                     f"followup={entry.get('followup')!r}")
                if args.full_capture:
                    _log("full capture cycle: "
                         + full_capture_cycle(os.path.dirname(
                             os.path.abspath(sentry.ledger.path))))
            else:
                last = (sentry.probe_attempts or [{}])[-1]
                _log(f"probe {last.get('outcome')}: next in "
                     f"{sentry.backoff_s:.0f}s")
            time.sleep(max(0.05, sentry.backoff_s))
    except KeyboardInterrupt:
        _log("interrupted; shutting down")
        return 0
    finally:
        S.set_active(None)
        if server is not None:
            server.close()


if __name__ == "__main__":
    sys.exit(main())
