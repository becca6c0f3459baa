"""Perf sentry (ISSUE 18): cancellable probe classification, the
append-only evidence ledger (srt-ledger/1) with torn-line safety,
live-over-stale baseline resolution (bench_diff --ledger), simulated
window open/close through the full probe -> bench -> diff -> ledger
cycle, leak-free daemon lifecycle, the /sentry telemetry route contract
(srt-sentry/1), and machine-named doctor follow-ups with quantified
lever evidence for every verdict kind."""

import importlib.util
import json
import os
import sys
import threading
import time

import pytest

from spark_rapids_tpu.observability import doctor as OD
from spark_rapids_tpu.observability import sentry as S
from spark_rapids_tpu.observability.metrics import get_registry
from spark_rapids_tpu.observability.server import TelemetryServer
from spark_rapids_tpu.serving import lifecycle as lc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import check_trace  # noqa: E402


def _bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "tools", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _live_artifact(tmp_path, name, value=1000.0):
    p = tmp_path / name
    p.write_text(json.dumps({
        "metric": "sentry_shape_set", "value": value, "unit": "rows/s",
        "rows": 10, "platform": "tpu", "evidence": "live"}))
    return str(p)


# ---------------------------------------------------------------------------
# probe classification (cancellable, bounded timeout, QueryContext drain)
# ---------------------------------------------------------------------------

def test_device_probe_outcomes_and_context_drain():
    # default op on this CPU host: answers, but on the cpu platform
    att = S.device_probe(timeout_s=20.0)
    assert att["outcome"] == "degraded"
    assert att.get("platform") == "cpu"
    assert att["elapsed_ms"] >= 0

    # an op that raises classifies as refused, with the error banked
    def boom():
        raise RuntimeError("device said no")
    att = S.device_probe(timeout_s=5.0, op=boom)
    assert att["outcome"] == "refused"
    assert "device said no" in att["error"]

    # a wedged op hits the QueryContext deadline -> timeout, bounded
    # (the wedged daemon thread is abandoned, not joined — keep its
    # sleep short so it drains before the lifecycle leak test below)
    t0 = time.perf_counter()
    att = S.device_probe(timeout_s=0.3, op=lambda: time.sleep(2))
    assert att["outcome"] == "timeout"
    assert time.perf_counter() - t0 < 1.5  # bounded, not 2s

    # a healthy non-cpu op is ok
    att = S.device_probe(timeout_s=5.0, op=lambda: "tpu")
    assert att["outcome"] == "ok"
    assert att["platform"] == "tpu"

    # every probe context unregistered, even the cancelled/timed-out one
    assert not [q for q in lc.live_queries()
                if q.session_id == "sentry"]


# ---------------------------------------------------------------------------
# evidence ledger: schema round-trip, append-only, torn-line safety
# ---------------------------------------------------------------------------

def test_ledger_round_trip_append_only_and_torn_line(tmp_path):
    led = S.EvidenceLedger(str(tmp_path / "ledger.jsonl"))
    assert led.entries() == [] and led.last_live() is None
    r1 = led.append({"evidence": "live", "artifact": "/a.json"})
    assert r1["schema"] == S.LEDGER_SCHEMA and r1["at"] and r1["unix"]
    first_line = open(led.path).readline()
    led.append({"evidence": "stale-replay", "artifact": "/b.json"})
    # append-only: the first record's bytes are untouched by the second
    assert open(led.path).readline() == first_line
    assert [e["artifact"] for e in led.entries()] == ["/a.json", "/b.json"]

    # torn trailing line (crash mid-append) and foreign lines are
    # skipped on read, never fatal, and never hide banked history
    with open(led.path, "a") as fh:
        fh.write("not json\n")
        fh.write('{"schema": "other/1", "evidence": "live"}\n')
        fh.write('{"schema": "srt-ledger/1", "evidence": "l')
    assert len(led.entries()) == 2
    assert led.tail(1)[0]["artifact"] == "/b.json"

    # last_live picks the newest LIVE entry, not the newest entry
    assert led.last_live()["artifact"] == "/a.json"
    age = led.last_live_age_s()
    assert age is not None and 0.0 <= age < 60.0


# ---------------------------------------------------------------------------
# baseline resolution: live-over-stale, refusal semantics, exit codes
# ---------------------------------------------------------------------------

def test_resolve_baseline_live_over_stale(tmp_path):
    bd = _bench_diff()
    led = S.EvidenceLedger(str(tmp_path / "ledger.jsonl"))
    led.append({"evidence": "live", "artifact": "/old_live.json"})
    led.append({"evidence": "live", "artifact": "/new_live.json"})
    led.append({"evidence": "stale-replay", "artifact": "/newest.json"})
    entries = bd.read_ledger(led.path)
    assert len(entries) == 3
    # the newest LIVE entry wins even though a stale one is newer
    assert bd.resolve_baseline(entries) == "/new_live.json"
    # no live entries: None without allow_stale, newest-any with it
    stale_only = [e for e in entries if e["evidence"] != "live"]
    assert bd.resolve_baseline(stale_only) is None
    assert bd.resolve_baseline(stale_only,
                               allow_stale=True) == "/newest.json"


def test_bench_diff_ledger_cli_exit_codes(tmp_path):
    bd = _bench_diff()
    base = _live_artifact(tmp_path, "base.json", 1000.0)
    fresh_ok = _live_artifact(tmp_path, "fresh.json", 1001.0)
    regressed = _live_artifact(tmp_path, "regressed.json", 500.0)
    led = S.EvidenceLedger(str(tmp_path / "ledger.jsonl"))
    led.append({"evidence": "live", "artifact": base})
    # auto-resolved live baseline, within threshold
    assert bd.main(["--ledger", led.path, fresh_ok]) == 0
    # --fail-on-regress keeps its exit-3 contract through ledger mode
    assert bd.main(["--ledger", led.path, regressed,
                    "--fail-on-regress"]) == 3
    # a ledger with no live entry refuses (exit 2) without --allow-stale
    stale = S.EvidenceLedger(str(tmp_path / "stale.jsonl"))
    stale.append({"evidence": "stale-replay", "artifact": base})
    assert bd.main(["--ledger", stale.path, fresh_ok]) == 2
    assert bd.main(["--ledger", stale.path, fresh_ok,
                    "--allow-stale"]) == 0


# ---------------------------------------------------------------------------
# window open/close through the full cycle, with fakes
# ---------------------------------------------------------------------------

def _fake_bench(value):
    def fn(shapes):
        return {"metric": "sentry_shape_set", "value": value,
                "unit": "rows/s", "rows": 10, "platform": "tpu",
                "evidence": "live", "shapes": list(shapes),
                "extra_metrics": {"join_trace_summary": {
                    "sync_count": 4, "sync_ms": 80.0,
                    "compile_count": 1, "compile_ms": 5.0}}}
    return fn


def test_window_open_close_backoff_and_ledger_cycle(tmp_path):
    outcomes = iter(["refused", "timeout", "ok", "ok"])

    def probe():
        o = next(outcomes)
        return {"outcome": o, "elapsed_ms": 1.0,
                **({"platform": "tpu"} if o == "ok" else {})}

    s = S.PerfSentry(probe=probe, bench=_fake_bench(1000.0),
                     ledger=str(tmp_path / "ledger.jsonl"),
                     shapes=["join"], interval_s=10.0)
    # closed window: no entry, exponential backoff from the interval
    assert s.run_once() is None
    assert s.backoff_s == 10.0  # first failure: base interval
    assert s.run_once() is None
    assert s.backoff_s == 20.0  # second failure doubles
    assert s.ledger.entries() == [] and s.windows == 0

    # window opens: full probe -> bench -> diff -> ledger cycle
    e1 = s.run_once()
    assert e1 is not None and s.windows == 1
    assert s.backoff_s == 10.0  # success resets the backoff
    assert e1["evidence"] == "live"
    assert os.path.exists(e1["artifact"])
    assert e1["diff"]["verdict"] == "no-baseline"
    assert e1["probe"]["outcome"] == "ok"
    assert e1["doctor"]["verdict"] == "sync-bound"
    assert e1["followup"].startswith("sync-bound:")

    # second window diffs against the first's artifact (auto-resolved
    # live baseline from the ledger)
    s._bench = _fake_bench(2000.0)
    e2 = s.run_once()
    assert e2["diff"]["baseline"] == e1["artifact"]
    assert e2["diff"]["verdict"] == "ok"
    assert e2["diff"]["improved"] >= 1  # value 1000 -> 2000
    assert len(s.ledger.entries()) == 2
    # per-attempt probe telemetry banked with outcomes classified
    st = s.status()
    assert st["probe"]["outcomes"] == {"refused": 1, "timeout": 1,
                                       "ok": 2}


def test_sentry_thread_lifecycle_is_leak_free(tmp_path):
    s = S.PerfSentry(probe=lambda: {"outcome": "refused",
                                    "elapsed_ms": 0.1},
                     bench=_fake_bench(1.0),
                     ledger=str(tmp_path / "ledger.jsonl"),
                     interval_s=0.05)
    s.start()
    assert s.running
    assert S.get_active() is s  # /sentry route now serves this sentry
    assert any(t.name == "srt-sentry" for t in threading.enumerate())
    time.sleep(0.2)
    s.stop(timeout=10.0)
    assert not s.running
    assert S.get_active() is None
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and any(
            t.name.startswith("srt-sentry")
            for t in threading.enumerate()):
        time.sleep(0.05)
    assert not [t.name for t in threading.enumerate()
                if t.name.startswith("srt-sentry")]
    assert not [q for q in lc.live_queries()
                if q.session_id == "sentry"]
    assert s.phase == "stopped"
    # probe attempts were banked as registry metrics while it ran
    text = get_registry().prometheus_text()
    assert "srt_sentry_probe_attempts_total" in text


# ---------------------------------------------------------------------------
# /sentry route contract (srt-sentry/1)
# ---------------------------------------------------------------------------

def test_sentry_route_contract(tmp_path):
    import urllib.error
    import urllib.request

    s = S.PerfSentry(probe=lambda: {"outcome": "ok", "platform": "tpu",
                                    "elapsed_ms": 0.5},
                     bench=_fake_bench(100.0),
                     ledger=str(tmp_path / "ledger.jsonl"),
                     shapes=["join"])
    s.run_once()
    S.set_active(s)
    srv = TelemetryServer(
        metrics_text=lambda: get_registry().prometheus_text(),
        healthz=lambda: (True, {}), queries=lambda: [],
        doctor=lambda: {}, slo=lambda: {})
    try:
        with urllib.request.urlopen(srv.endpoint + "/sentry",
                                    timeout=5) as resp:
            assert resp.status == 200
            doc = json.loads(resp.read().decode())
        assert doc["schema"] == "srt-sentry/1"
        assert doc["phase"] in check_trace.SENTRY_PHASES
        assert doc["windows"] == 1
        assert doc["probe"]["last"]["outcome"] == "ok"
        assert doc["ledger"]["entries"] == 1
        assert doc["ledger"]["tail"][0]["schema"] == "srt-ledger/1"
        assert doc["last_live_age_s"] is not None
        # the CI validator accepts the payload via --endpoint
        desc = check_trace.check_endpoint(srv.endpoint + "/sentry")
        assert desc.startswith("sentry phase ")
        assert check_trace.main(
            ["--endpoint", srv.endpoint + "/sentry"]) == 0
        # 404 names /sentry among the known routes
        try:
            urllib.request.urlopen(srv.endpoint + "/nope", timeout=5)
            raise AssertionError("expected 404")
        except urllib.error.HTTPError as e:
            assert e.code == 404
            assert "/sentry" in json.loads(e.read().decode())["routes"]
    finally:
        srv.close()
        S.set_active(None)

    # with no active sentry the payload degrades honestly but keeps the
    # schema and ledger staleness visible
    none = S.status_payload()
    assert none["schema"] == "srt-sentry/1" and none["phase"] == "none"
    assert check_trace.check_sentry(none).startswith("sentry phase none")

    # a malformed payload is rejected by the validator
    with pytest.raises(ValueError):
        check_trace.check_sentry({"schema": "srt-sentry/1",
                                  "phase": "bogus"})


# ---------------------------------------------------------------------------
# doctor: quantified lever evidence + stale-evidence refusal
# ---------------------------------------------------------------------------

def test_followup_naming_quantified_for_multiple_verdicts():
    sync = OD.diagnose_summary({"sync_count": 18, "sync_ms": 120.0,
                                "compile_count": 1, "compile_ms": 2.0})
    assert sync["verdict"] == "sync-bound"
    f = OD.followup(sync)
    assert f.startswith("sync-bound: ")
    assert "readbacks=18" in f and "ms_per_readback=" in f
    assert "; lever: " in f

    comp = OD.diagnose_summary({"sync_count": 1, "sync_ms": 1.0,
                                "compile_count": 5, "compile_ms": 900.0})
    assert comp["verdict"] == "compile-bound"
    f = OD.followup(comp)
    assert f.startswith("compile-bound: ")
    assert "compiles=5" in f and "ms_per_compile=180" in f
    assert "; lever: " in f

    # EVERY verdict kind has a named lever (the dispatch-bound precision
    # is the floor, not the ceiling)
    for kind in OD.VERDICTS:
        assert kind == "no-bottleneck" or kind in OD.LEVERS


def test_stale_evidence_stamps_age_and_refuses_followup():
    diag = OD.diagnose_summary(
        {"sync_count": 9, "sync_ms": 50.0},
        evidence="stale-replay", evidence_age_s=7200.0)
    assert diag["evidence"] == "stale-replay"
    assert diag["evidence_age_s"] == 7200.0
    assert any("STALE-EVIDENCE" in c for c in diag.get("caveats", []))
    f = OD.followup(diag)
    assert f.startswith("STALE-EVIDENCE")
    assert "refused" in f
    # live evidence passes through to a real follow-up
    live = OD.diagnose_summary({"sync_count": 9, "sync_ms": 50.0},
                               evidence="live", evidence_age_s=1.0)
    assert OD.followup(live).startswith("sync-bound:")


def test_diagnose_artifact_derives_evidence_and_age(tmp_path):
    art = {"metric": "sentry_shape_set", "platform": "tpu",
           "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                        time.gmtime(time.time() - 300)),
           "extra_metrics": {"join_trace_summary": {
               "sync_count": 3, "sync_ms": 30.0}}}
    diag = OD.diagnose_artifact(art)
    # captured_at marks a replay: evidence derived, age stamped, and the
    # follow-up refused with the loud marker
    assert diag["evidence"] == "stale-replay"
    assert 250.0 <= diag["evidence_age_s"] <= 600.0
    assert OD.followup(diag).startswith("STALE-EVIDENCE")


# ---------------------------------------------------------------------------
# bench.run_shape_set: the callable entrypoint, real engine, tiny rows
# ---------------------------------------------------------------------------

def test_run_shape_set_real_engine_small(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "bench_for_sentry_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    out = str(tmp_path / "art.json")
    art = bench.run_shape_set(["sort"], rows=4000, budget_s=120,
                              artifact_path=out, evidence="live")
    assert art["metric"] == "sentry_shape_set"
    assert art["evidence"] == "live"
    assert art["extra_metrics"]["sort_rows_per_sec"] > 0
    assert art["phases"]["shape_sort"]["timed_out"] is False
    # banked incrementally: the on-disk artifact matches
    banked = json.loads(open(out).read())
    assert banked["extra_metrics"]["sort_rows_per_sec"] \
        == art["extra_metrics"]["sort_rows_per_sec"]
    # the doctor can diagnose it end to end (the sentry's ledger step)
    diag = OD.diagnose_artifact(art)
    assert diag["verdict"] in OD.VERDICTS
    assert OD.followup(diag)  # always machine-named, never empty
