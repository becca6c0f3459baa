"""Bottleneck doctor (observability/doctor.py): synthetic traces with
known injected bottlenecks -> expected ranked verdicts (sem_wait-bound and
h2d-bound fixtures per ISSUE 8), nested-span self-time attribution,
truncation caveats and summary-mode degradation."""

import json
import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.observability import doctor as OD
from spark_rapids_tpu.sql import functions as F

def _ev(cat, name, ms, ts=0.0, tid=1, exec_="TpuJoin", **args):
    """Synthetic tracer event (ts/dur in µs like the real ring)."""
    ev = {"cat": cat, "name": name, "ts": ts * 1e3, "dur": ms * 1e3,
          "tid": tid, "exec": exec_}
    if args:
        ev["args"] = args
    return ev


def _categories(diag):
    return [r["category"] for r in diag["ranked"]]


# --------------------------------------------------------------------------
# synthetic single-bottleneck fixtures -> expected top verdict
# --------------------------------------------------------------------------

def test_sync_bound_fixture():
    events = [_ev("sync", "join.readback", 50.0, ts=i * 60.0)
              for i in range(5)]
    events += [_ev("h2d", "upload", 1.0, ts=400.0, bytes=100)]
    diag = OD.diagnose(events, wall_ms=300.0)
    assert diag["schema"] == OD.SCHEMA
    assert diag["verdict"] == "sync-bound"
    top = diag["ranked"][0]
    assert top["ms"] == pytest.approx(250.0)
    assert top["count"] == 5
    assert top["share"] == pytest.approx(250.0 / 300.0, rel=1e-3)
    assert top["evidence"]["top_execs"][0]["exec"] == "TpuJoin"


def test_sem_wait_bound_fixture():
    """ISSUE 8 required fixture: semaphore contention dominates."""
    events = [_ev("sem_wait", "semaphore.acquire", 80.0, ts=i * 100.0,
                  tid=i, exec_="TpuHashAggregate") for i in range(4)]
    events += [_ev("sync", "readback", 2.0, ts=500.0)]
    diag = OD.diagnose(events)
    assert diag["verdict"] == "sem_wait-bound"
    assert diag["ranked"][0]["count"] == 4
    assert _categories(diag)[1] == "sync-bound"


def test_h2d_bound_fixture():
    """ISSUE 8 required fixture: uploads dominate, bytes in evidence."""
    events = [_ev("h2d", "arrow_to_device", 120.0, ts=i * 150.0,
                  exec_="TpuInMemoryScan", bytes=1 << 20)
              for i in range(3)]
    events += [_ev("d2h", "device_get", 30.0, ts=600.0, bytes=4096),
               _ev("sync", "readback", 5.0, ts=700.0)]
    diag = OD.diagnose(events, wall_ms=500.0)
    assert diag["verdict"] == "h2d-d2h-bound"
    top = diag["ranked"][0]
    assert top["ms"] == pytest.approx(390.0)
    assert top["count"] == 4                       # h2d + d2h combined
    assert top["evidence"]["bytes"] == 3 * (1 << 20) + 4096
    assert top["evidence"]["top_execs"][0]["exec"] == "TpuInMemoryScan"


def test_compile_spill_shuffle_fixtures():
    for cat, verdict in (("compile", "compile-bound"),
                         ("spill", "spill-bound"),
                         ("shuffle", "shuffle-bound")):
        events = [_ev(cat, "x", 200.0), _ev("sync", "r", 1.0, ts=300.0)]
        diag = OD.diagnose(events)
        assert diag["verdict"] == verdict, (cat, diag)


def test_dispatch_bound_measured_from_launch_spans():
    """The host launching programs -> dispatch-bound, charged the self
    time of the ``eager`` and ``dispatch`` spans (a readback inside an
    eager block stays sync-bound), with the launch counts as evidence."""
    events = [_ev("eager", "batch.sliced", 40.0, ts=0.0,
                  exec_="TpuTakeOrderedAndProject"),
              _ev("sync", "batch.num_rows", 5.0, ts=10.0),
              _ev("dispatch", "srt_SortExec_compute_0", 30.0, ts=100.0),
              _ev("dispatch", "srt_SortExec_compute_0", 20.0, ts=200.0,
                  retraced=1)]
    diag = OD.diagnose(events, counters={"deviceDispatches": 2000},
                       metrics={"stageOpDispatches": 1500},
                       wall_ms=500.0)
    assert diag["verdict"] == "dispatch-bound"
    top = diag["ranked"][0]
    assert top["ms"] == pytest.approx(65.0)        # 35 eager + 30 dispatch
    assert top["count"] == 2
    assert top["evidence"]["eager_ms"] == pytest.approx(35.0)
    assert top["evidence"]["dispatch_ms"] == pytest.approx(30.0)
    assert top["evidence"]["top_execs"][0]["exec"] == \
        "TpuTakeOrderedAndProject"
    assert "estimated" not in top["evidence"]
    assert top["evidence"]["device_dispatches"] == 2000
    assert top["evidence"]["stage_op_dispatches"] == 1500
    by = {r["category"]: r for r in diag["ranked"]}
    assert by["sync-bound"]["ms"] == pytest.approx(5.0)
    assert by["compile-bound"]["ms"] == pytest.approx(20.0)   # re-trace


def test_dispatch_counts_alone_make_no_verdict():
    diag = OD.diagnose([_ev("sync", "r", 5.0)],
                       counters={"deviceDispatches": 8000})
    assert "dispatch-bound" not in _categories(diag)


# --------------------------------------------------------------------------
# self-time attribution: container spans must not absorb nested time
# --------------------------------------------------------------------------

def test_nested_compile_inside_shuffle_attributes_to_compile():
    """exchange.materialize wraps the map side; a kernel compile inside
    it must count as compile-bound, not shuffle-bound."""
    events = [
        _ev("shuffle", "exchange.materialize", 300.0, ts=0.0,
            exec_="TpuShuffleExchange"),
        _ev("compile", "srt_HashAggregateExec_grp_0badf00d", 280.0, ts=10.0,
            exec_="TpuHashAggregate"),
    ]
    diag = OD.diagnose(events, wall_ms=320.0)
    assert diag["verdict"] == "compile-bound"
    by_cat = {r["category"]: r for r in diag["ranked"]}
    assert by_cat["shuffle-bound"]["ms"] == pytest.approx(20.0)
    assert by_cat["compile-bound"]["ms"] == pytest.approx(280.0)


def test_op_spans_are_neutral_containers():
    """A shuffle span whose time is really the child plan's op compute
    keeps only its self time; the op span itself is never a verdict."""
    events = [
        _ev("shuffle", "exchange.materialize", 200.0, ts=0.0),
        _ev("op", "TpuHashAggregate", 180.0, ts=5.0),
        _ev("sync", "readback", 20.0, ts=10.0),
    ]
    diag = OD.diagnose(events)
    by_cat = {r["category"]: r for r in diag["ranked"]}
    assert by_cat["shuffle-bound"]["ms"] == pytest.approx(20.0)
    assert by_cat["sync-bound"]["ms"] == pytest.approx(20.0)
    assert "op" not in _categories(diag)


def test_parallel_threads_do_not_cross_subtract():
    """Spans overlapping in time on DIFFERENT threads are independent."""
    events = [
        _ev("shuffle", "serialize", 100.0, ts=0.0, tid=1),
        _ev("compile", "k", 100.0, ts=0.0, tid=2),
    ]
    diag = OD.diagnose(events)
    by_cat = {r["category"]: r for r in diag["ranked"]}
    assert by_cat["shuffle-bound"]["ms"] == pytest.approx(100.0)
    assert by_cat["compile-bound"]["ms"] == pytest.approx(100.0)


# --------------------------------------------------------------------------
# caveats, schema, summary mode
# --------------------------------------------------------------------------

def test_truncated_trace_flagged():
    diag = OD.diagnose([_ev("sync", "r", 1.0)], dropped_events=123)
    assert diag["trace_truncated"] is True
    assert any("123" in c for c in diag["caveats"])
    assert OD.diagnose([_ev("sync", "r", 1.0)])["trace_truncated"] is False


def test_empty_trace_no_bottleneck():
    diag = OD.diagnose([])
    assert diag["verdict"] == "no-bottleneck"
    assert diag["ranked"] == []
    assert diag["caveats"]


def test_ranked_ordering_and_shares():
    events = [_ev("sync", "r", 50.0), _ev("spill", "s", 100.0, ts=60.0),
              _ev("sem_wait", "w", 10.0, ts=200.0)]
    diag = OD.diagnose(events, wall_ms=200.0)
    ms = [r["ms"] for r in diag["ranked"]]
    assert ms == sorted(ms, reverse=True)
    assert all(0.0 <= r["share"] <= 1.0 for r in diag["ranked"])
    assert all(r["category"] in OD.VERDICTS for r in diag["ranked"])


def test_diagnose_summary_degraded_mode():
    summary = {"sync_count": 40, "sync_ms": 900.0, "compile_count": 2,
               "compile_ms": 100.0, "h2d_bytes": 1 << 20,
               "d2h_bytes": 2048, "spill_ms": 0.0, "sem_wait_ms": 1.0,
               "device_dispatches": 500, "dispatch_ms": 40.0,
               "dispatch_count": 500, "trace_truncated": False}
    diag = OD.diagnose_summary(summary, wall_ms=1200.0)
    assert diag["verdict"] == "sync-bound"
    cats = _categories(diag)
    assert "h2d-d2h-bound" in cats and "dispatch-bound" in cats
    assert any("trace_summary" in c for c in diag["caveats"])


def test_compact_form():
    events = [_ev("sync", "r", 50.0), _ev("spill", "s", 10.0, ts=60.0)]
    c = OD.compact(OD.diagnose(events, dropped_events=5), top=1)
    assert c["verdict"] == "sync-bound"
    assert len(c["ranked"]) == 1
    assert c["trace_truncated"] is True
    assert set(c["ranked"][0]) >= {"category", "ms", "share", "count"}


# --------------------------------------------------------------------------
# end-to-end: traced join -> session doctor + CLI over the event log
# --------------------------------------------------------------------------

def _join_query(sess, n=12000):
    rng = np.random.default_rng(7)
    fact = pa.table({"fk": rng.integers(0, 300, n), "x": rng.random(n)})
    dim = pa.table({"pk": np.arange(300, dtype=np.int64),
                    "cat": rng.integers(0, 8, 300)})
    f = sess.create_dataframe(fact, num_partitions=2)
    d = sess.create_dataframe(dim)
    return (f.join(d, f.fk == d.pk, "inner").groupBy("cat")
            .agg(F.count("*").alias("n")).orderBy("cat"))


def test_session_diagnose_last_query_end_to_end(tmp_path):
    sink = str(tmp_path / "eventlog")
    sess = srt.session(**{"spark.rapids.tpu.trace.sink": sink})
    _join_query(sess).collect()
    diag = sess.diagnose_last_query()
    assert diag["schema"] == OD.SCHEMA
    assert diag["verdict"] in OD.VERDICTS + ("no-bottleneck",)
    assert diag["ranked"], "a traced join must attribute SOMETHING"
    # every verdict carries supporting exec-level spans or counters
    for r in diag["ranked"]:
        ev = r["evidence"]
        assert ev.get("top_execs") or ev.get("device_dispatches"), r
    # CLI over the exported JSONL event log emits the same schema
    logs = os.listdir(sink)
    assert logs
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.observability.doctor",
         os.path.join(sink, logs[0])],
        capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    cli = json.loads(out.stdout)
    assert cli["schema"] == OD.SCHEMA
    assert cli["verdict"] == diag["verdict"]


def test_diagnose_without_trace_raises():
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": False})
    sess.create_dataframe(pa.table({"k": [1]})).collect()
    with pytest.raises(RuntimeError):
        sess.diagnose_last_query()
