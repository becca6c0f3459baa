#!/usr/bin/env python3
"""Leak sentinel — the bounded long-soak leak check (ROADMAP item 1,
docs/robustness.md "query lifecycle").

Runs mixed multi-tenant traffic through one ServingEngine for N seconds
in WAVES — each wave runs the chaos suite's query mix concurrently per
tenant, optionally with the lifecycle fault legs armed (cooperative
cancels via ``query.cancel.race``, per-query deadlines, injected
``device.fatal`` exercising quarantine + probe recovery) — and samples
the process's resource gauges between waves:

* retention pin count (``memory/retention.py``),
* BufferCatalog registered handles (``leak_report()``),
* metrics-registry series cardinality (bounded by ``maxSeries``),
* encoded dictionary-registry size (``columnar/encoded.py``),
* tracer ring high-water (bounded by the ring capacity).

Verdict contract: after each wave (post shuffle TTL-sweep + gc) the
RESOURCE gauges (pins, catalog handles, dictionary registry) must return
to the post-warmup baseline, and the BOUNDED gauges (metrics series,
ring high-water) must respect their caps — a process serving millions of
users must look the same after wave 50 as after wave 1.

``--telemetry`` runs the soak with the embedded telemetry server
enabled (observability/server.py): /metrics and /healthz are scraped
mid-soak to prove the plane serves under load, and after engine close
the leg asserts the server left nothing behind — no lingering
``srt-telemetry-*`` thread and the port rebindable (the series-cap
bound already covers scrape-driven cardinality growth).

``--cluster`` runs the pod-scale fault-domain leg: a real N-process
shuffle cluster (testing/chaos_cluster.py) through kill/recover cycles
— SIGKILL a peer mid-query, wait out the failure detector's dead
declaration, assert bit-identical recovery — and after each cluster
close asserts the fault-domain state drained to baseline: no lingering
``srt-peer-hb`` heartbeat threads, an empty detector peer table, and no
retained peer-epoch or block-source state on the closed manager.

Usage:  python tools/leak_sentinel.py [--seconds 60] [--tenants 2]
            [--rows 8000] [--arm cancel,deadline,fatal] [--telemetry]
            [--cluster] [--out FILE]
Exit 0 = clean verdict; 1 = leak (per-gauge evidence in the report).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import threading
import time


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--seconds", type=float, default=60.0,
                   help="soak duration budget (waves stop after this)")
    p.add_argument("--tenants", type=int, default=2)
    p.add_argument("--rows", type=int, default=8000)
    p.add_argument("--max-waves", type=int, default=1000)
    p.add_argument("--arm", default="cancel,deadline,fatal",
                   help="comma list of lifecycle fault legs to arm: "
                        "cancel (query.cancel.race), deadline (a "
                        "deadline-doomed query per wave), fatal "
                        "(device.fatal -> quarantine + probe)")
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--telemetry", action="store_true",
                   help="soak with the telemetry server enabled and "
                        "assert leak-free shutdown (thread + port)")
    p.add_argument("--cluster", action="store_true",
                   help="run N-process kill/recover cycles through the "
                        "chaos cluster harness and assert heartbeat "
                        "threads, the detector peer table and epoch "
                        "state drain to baseline on close")
    p.add_argument("--out", default="", help="write the JSON report here")
    return p


def _gauges() -> dict:
    """One sample of every leak-relevant gauge."""
    from spark_rapids_tpu.columnar import encoded as enc
    from spark_rapids_tpu.memory import retention
    from spark_rapids_tpu.memory.spill import BufferCatalog
    from spark_rapids_tpu.observability import tracer as OT
    from spark_rapids_tpu.observability.metrics import get_registry
    reg = get_registry()
    with reg._lock:
        series = (len(reg._counters) + len(reg._gauges)
                  + len(reg._hists))
    tr = OT.get_tracer()
    return {
        "retention_pins": retention.pinned_count(),
        "catalog_handles": len(BufferCatalog.get().leak_report()),
        "metrics_series": series,
        "dict_registry": len(enc._DICT_OBJECTS),
        "trace_ring_high_water": tr.high_water,
        "trace_ring_capacity": tr._events.maxlen,
    }


def run_cluster_leg(seconds: float, seed: int,
                    rows: int = 256) -> tuple:
    """Pod-scale fault-domain leg: kill/recover cycles through a REAL
    3-process shuffle cluster, asserting after every cluster close that
    the fault-domain state drained — no ``srt-peer-hb`` heartbeat
    threads beyond the pre-leg count, an empty detector peer table, and
    no retained peer-epoch / block-source state.  Returns
    (leg_report, leaks)."""
    from spark_rapids_tpu.robustness.failure_detector import THREAD_PREFIX
    from spark_rapids_tpu.testing.chaos_cluster import (ChaosCluster,
                                                        expected_digest)

    def hb_threads():
        return [t.name for t in threading.enumerate()
                if t.name.startswith(THREAD_PREFIX)]

    leaks = []
    baseline = len(hb_threads())
    detections, cycles = [], 0
    deadline = time.monotonic() + max(seconds, 1.0)
    while cycles == 0 or (cycles < 3 and time.monotonic() < deadline):
        cseed = seed + cycles
        exp = expected_digest(cseed, 3, rows)
        cl = ChaosCluster(3, cseed, rows)
        try:
            clean = cl.query()
            if any(r["digest"] != exp for r in clean):
                leaks.append(f"cluster cycle {cycles}: clean-run digest "
                             f"mismatch")
            cl.kill_victim()
            cl.expire_victim()
            detections.append(round(cl.wait_dead(), 1))
            degraded = cl.query(cl.survivors)
            if any(r["digest"] != exp for r in degraded):
                leaks.append(f"cluster cycle {cycles}: post-kill digest "
                             f"mismatch (recovery broke parity)")
        finally:
            mgr = cl.driver
            cl.close()
        # drain-to-baseline asserts (the leg's whole point): close()
        # must tear down the heartbeat loop, detector and fencing state
        grace = time.monotonic() + 5.0
        while len(hb_threads()) > baseline \
                and time.monotonic() < grace:
            time.sleep(0.05)
        left = hb_threads()
        if len(left) > baseline:
            leaks.append(f"cluster cycle {cycles}: heartbeat thread(s) "
                         f"lingering after close: {left}")
        if mgr.detector.peer_count() != 0:
            leaks.append(f"cluster cycle {cycles}: detector peer table "
                         f"not drained: {mgr.detector.snapshot()}")
        if mgr._peer_epochs:
            leaks.append(f"cluster cycle {cycles}: peer epochs retained "
                         f"after close: {mgr._peer_epochs}")
        if mgr._block_sources:
            leaks.append(f"cluster cycle {cycles}: block-source map "
                         f"retained after close")
        cycles += 1
    leg = {
        "cycles": cycles,
        "detection_ms": detections,
        "hb_threads_baseline": baseline,
        "hb_threads_final": len(hb_threads()),
        "shutdown": "clean" if not leaks else "leak",
    }
    return leg, leaks


def _scrape(host: str, port: int, route: str) -> tuple:
    """(status, body) from the embedded telemetry server; 503 on a
    degraded /healthz is a valid answer, not an error."""
    import urllib.error
    import urllib.request
    try:
        with urllib.request.urlopen(
                f"http://{host}:{port}{route}", timeout=5) as resp:
            return resp.status, resp.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def run_sentinel(seconds: float = 60.0, tenants: int = 2,
                 rows: int = 8000, seed: int = 11,
                 arm: str = "cancel,deadline,fatal",
                 max_waves: int = 1000,
                 telemetry: bool = False,
                 cluster: bool = False) -> dict:
    """Returns the report dict; report["verdict"] is "clean" or "leak"."""
    import spark_rapids_tpu as srt  # noqa: F401 - engine init path
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.memory.fatal import FatalDeviceError
    from spark_rapids_tpu.memory.spill import BufferCatalog
    from spark_rapids_tpu.robustness import disarm_chaos
    from spark_rapids_tpu.serving import ServingEngine
    from spark_rapids_tpu.serving import lifecycle as lc
    from spark_rapids_tpu.shuffle import get_shuffle_manager
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.session import TpuSession
    from spark_rapids_tpu.testing.chaos import (QUERIES, _base_conf,
                                                _soak_tables)
    legs = {s.strip() for s in arm.split(",") if s.strip()}
    tables = _soak_tables(rows)
    tmp = tempfile.mkdtemp(prefix="srt-leak-")
    prev_active = TpuSession._active
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.memory.spillDir": tmp}))
    max_series = 4096
    eng_conf = dict(_base_conf(tmp))
    eng_conf.update({
        "spark.rapids.tpu.metrics.enabled": True,
        "spark.rapids.tpu.metrics.maxSeries": max_series,
        "spark.rapids.tpu.profile.enabled": True,
        "spark.rapids.tpu.serving.maxConcurrentQueries": max(2, tenants),
    })
    if telemetry:
        eng_conf.update({
            "spark.rapids.tpu.telemetry.enabled": True,
            "spark.rapids.tpu.telemetry.port": 0,  # ephemeral
        })
    typed = {"cancelled": 0, "deadline": 0, "fatal": 0, "quarantined": 0,
             "degraded_refusals": 0, "ok": 0, "unexpected": 0}
    eng = ServingEngine(conf=RapidsConf.get_global().copy(eng_conf))
    # shuffle state must not accrue for the soak's lifetime: reclaim
    # deferred shuffles immediately at each wave's sweep so "returns to
    # baseline" is meaningful (the default TTL parks them for an hour)
    get_shuffle_manager().cleanup_ttl_s = -1.0
    samples = []
    telem: dict = {}
    t_host, t_port = "", 0
    try:
        if telemetry:
            if eng.telemetry is None:
                raise AssertionError("telemetry enabled but no server")
            t_host, t_port = eng.telemetry.host, eng.telemetry.port
            telem["endpoint"] = eng.telemetry.endpoint
        sessions = {f"tenant{i}": eng.session(tenant=f"tenant{i}")
                    for i in range(tenants)}
        if "deadline" in legs:
            # one doomed session per wave: a 1ms deadline on this suite
            # always expires at a poll site
            doomed = eng.session(
                tenant="tenant0",
                **{"spark.rapids.tpu.query.deadlineMs": 1})

        def run_wave(wave: int, armed: bool) -> None:
            errs: dict = {}

            def tenant_work(tname: str, sess) -> None:
                for qname, fn in QUERIES:
                    try:
                        fn(sess, tables, F)
                        typed["ok"] += 1
                    except lc.QueryCancelled:
                        # includes QueryDeadlineExceeded
                        typed["cancelled"] += 1
                    except lc.QueryQuarantined:
                        typed["quarantined"] += 1
                    except lc.EngineDegraded:
                        typed["degraded_refusals"] += 1
                    except FatalDeviceError:
                        typed["fatal"] += 1
                    except BaseException as e:  # noqa: BLE001
                        typed["unexpected"] += 1
                        errs[f"{tname}/{qname}"] = repr(e)

            threads = [threading.Thread(target=tenant_work,
                                        args=(t, s),
                                        name=f"leak-{t}")
                       for t, s in sessions.items()]
            for t in threads:
                t.start()
            if armed and "deadline" in legs:
                try:
                    QUERIES[0][1](doomed, tables, F)
                except lc.QueryCancelled:
                    typed["deadline"] += 1
                except (lc.EngineDegraded, lc.QueryQuarantined):
                    typed["degraded_refusals"] += 1
            if armed and "fatal" in legs and wave % 3 == 1:
                # one poisoned query per third wave: quarantine + the
                # probe-recovery path must also hold the baseline
                from spark_rapids_tpu.robustness import faults
                prev = faults.snapshot_arming()
                faults.arm_chaos(seed=seed + wave,
                                 sites="device.fatal:1.0")
                try:
                    QUERIES[1][1](sessions["tenant0"], tables, F)
                    typed["unexpected"] += 1
                except FatalDeviceError:
                    typed["fatal"] += 1
                except (lc.EngineDegraded, lc.QueryQuarantined):
                    typed["degraded_refusals"] += 1
                finally:
                    faults.restore_arming(prev)
            for t in threads:
                t.join()
            if errs:
                raise AssertionError(f"non-typed errors in wave: {errs}")

        def settle() -> None:
            get_shuffle_manager().sweep_deferred()
            gc.collect()

        # Three phases (the verdict contract):
        #   A. CLEAN warmup — caches (upload/kernel/dictionary, each
        #      session's retained last plan) reach their flat steady
        #      state; the baseline is those gauges.
        #   B. ARMED soak — cancel races, deadlines and fatal injection
        #      run for the time budget; gauges are sampled per wave
        #      (evidence, and the bounded-gauge caps are asserted here).
        #   C. CLEAN drain — faults disarmed, two healthy waves: every
        #      resource gauge must RETURN TO the phase-A baseline.  Any
        #      state a fault wave durably retained that healthy traffic
        #      cannot displace is a leak.
        from spark_rapids_tpu.robustness import faults as _faults
        for w in range(2):
            run_wave(w, armed=False)
        settle()
        baseline = _gauges()
        if "cancel" in legs:
            # per-CHECK probability: poll sites fire dozens of times per
            # query, so a small p cancels a healthy fraction of each
            # wave's queries without drowning the ok-path coverage
            _faults.arm_chaos(seed=seed, sites="query.cancel.race:0.01")
        t_end = time.monotonic() + seconds
        wave = 0
        while time.monotonic() < t_end and wave < max_waves:
            wave += 1
            run_wave(wave, armed=True)
            settle()
            samples.append(dict(_gauges(), wave=wave))
            if telemetry and wave == 1:
                # the plane must serve mid-soak; /healthz may honestly
                # answer 503 here (fatal legs degrade the engine)
                st, body = _scrape(t_host, t_port, "/metrics")
                telem["metrics_scrape"] = {
                    "status": st,
                    "lines": body.count("\n"),
                }
                telem["healthz_status"] = _scrape(
                    t_host, t_port, "/healthz")[0]
        _faults.disarm_chaos()
        for w in range(2):
            run_wave(wave + 1 + w, armed=False)
        settle()
        final = _gauges()
        leaks = []
        for g in ("retention_pins", "catalog_handles", "dict_registry"):
            if final[g] > baseline[g]:
                leaks.append(
                    f"{g} did not return to baseline after the clean "
                    f"drain: {final[g]} > {baseline[g]}")
        for s in samples:
            if s["metrics_series"] > max_series:
                leaks.append(f"wave {s['wave']}: metrics_series "
                             f"{s['metrics_series']} > bound {max_series}")
            if s["trace_ring_high_water"] > s["trace_ring_capacity"]:
                leaks.append(f"wave {s['wave']}: ring high-water over "
                             f"capacity")
        if telemetry:
            if telem.get("metrics_scrape", {}).get("status") != 200:
                leaks.append(
                    f"/metrics scrape mid-soak did not answer 200: "
                    f"{telem.get('metrics_scrape')}")
            # shutdown must be leak-free: close NOW (idempotent; the
            # finally re-closes harmlessly) and probe thread + port
            eng.close()
            import socket
            lingering = [t.name for t in threading.enumerate()
                         if t.name.startswith("srt-telemetry-")]
            if lingering:
                leaks.append(f"telemetry thread(s) lingering after "
                             f"engine close: {lingering}")
            try:
                probe = socket.socket()
                probe.setsockopt(socket.SOL_SOCKET,
                                 socket.SO_REUSEADDR, 1)
                probe.bind((t_host or "127.0.0.1", t_port))
                probe.close()
            except OSError as e:
                leaks.append(f"telemetry port {t_port} still bound "
                             f"after engine close: {e}")
            telem["shutdown"] = "clean" if not any(
                "telemetry" in leak for leak in leaks) else "leak"
        cluster_leg = None
        if cluster:
            # the fault-domain leg runs after the engine soak (its own
            # subprocesses; the engine's gauges are already sampled)
            cluster_leg, cluster_leaks = run_cluster_leg(
                min(seconds, 30.0), seed)
            leaks.extend(cluster_leaks)
        report = {
            "schema": "srt-leak-sentinel/1",
            "verdict": "clean" if not leaks else "leak",
            "waves": wave,
            "tenants": tenants,
            "rows": rows,
            "armed": sorted(legs),
            "outcomes": typed,
            "baseline": baseline,
            "final": final,
            "samples": samples[-5:],
            "leaks": leaks,
        }
        if telemetry:
            report["telemetry"] = telem
        if cluster_leg is not None:
            report["cluster"] = cluster_leg
        return report
    finally:
        eng.close()
        disarm_chaos()
        BufferCatalog.reset()
        TpuSession._active = prev_active


def main() -> int:
    # runnable from anywhere: the engine lives one level up from tools/
    # (the api_validation.py pattern — the package is not pip-installed)
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    # runs on the platform JAX finds (CI sets JAX_PLATFORMS=cpu)
    args = build_arg_parser().parse_args()
    report = run_sentinel(seconds=args.seconds, tenants=args.tenants,
                          rows=args.rows, seed=args.seed, arm=args.arm,
                          max_waves=args.max_waves,
                          telemetry=args.telemetry,
                          cluster=args.cluster)
    print(json.dumps(report, indent=2))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=2)
    if report["verdict"] != "clean":
        print("LEAK SENTINEL FAILED:", *report["leaks"], sep="\n  ",
              file=sys.stderr)
        return 1
    print(f"LEAK SENTINEL PASSED: {report['waves']} waves, "
          f"{report['outcomes']['ok']} ok / "
          f"{report['outcomes']['cancelled']} cancelled / "
          f"{report['outcomes']['deadline']} deadline / "
          f"{report['outcomes']['fatal']} fatal — all gauges at "
          f"baseline")
    return 0


if __name__ == "__main__":
    sys.exit(main())
