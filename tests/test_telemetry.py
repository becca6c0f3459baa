"""Live telemetry plane (observability/server.py + slo.py) and
cross-process trace stitching over the shuffle wire (shuffle/tcp.py
traced fetch op + serializer frame-trace extension + tools/trace_merge).
"""

import json
import os
import socket
import sys
import threading
import urllib.error
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.observability import slo as OSLO
from spark_rapids_tpu.observability import tracer as OT
from spark_rapids_tpu.observability.metrics import MetricsRegistry
from spark_rapids_tpu.observability.server import TelemetryServer

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"))
import check_trace  # noqa: E402
import trace_merge  # noqa: E402


def _get(base: str, route: str):
    try:
        with urllib.request.urlopen(base + route, timeout=10) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# telemetry server
# ---------------------------------------------------------------------------

def test_server_routes_payloads_and_503():
    healthy = [True]
    srv = TelemetryServer(
        metrics_text=lambda: "# TYPE srt_x counter\nsrt_x 1.0\n",
        healthz=lambda: (healthy[0],
                         {"status": "ok" if healthy[0] else "degraded"}),
        queries=lambda: [{"query": 1, "status": "ok"}],
        doctor=lambda: {"last": None},
        slo=lambda: {"schema": "srt-slo/1", "tenants": {}})
    try:
        base = srv.endpoint
        st, body = _get(base, "/metrics")
        assert st == 200 and "srt_x 1.0" in body
        st, body = _get(base, "/healthz")
        assert st == 200 and json.loads(body)["status"] == "ok"
        st, body = _get(base, "/queries")
        assert st == 200 and json.loads(body)[0]["query"] == 1
        for route in ("/doctor", "/slo"):
            st, body = _get(base, route)
            assert st == 200
            json.loads(body)
        # degraded flips /healthz non-200 while /metrics keeps serving
        healthy[0] = False
        st, body = _get(base, "/healthz")
        assert st == 503 and json.loads(body)["status"] == "degraded"
        assert _get(base, "/metrics")[0] == 200
        # unknown route: 404 naming the known ones
        st, body = _get(base, "/nope")
        assert st == 404 and "/metrics" in body
    finally:
        srv.close()


def test_server_shutdown_is_leak_free():
    srv = TelemetryServer(
        metrics_text=lambda: "", healthz=lambda: (True, {}),
        queries=lambda: [], doctor=lambda: {}, slo=lambda: {})
    host, port = srv.host, srv.port
    assert _get(srv.endpoint, "/healthz")[0] == 200
    srv.close()
    srv.close()  # idempotent
    assert not [t for t in threading.enumerate()
                if t.name == f"srt-telemetry-{port}"]
    probe = socket.socket()
    probe.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    probe.bind((host, port))
    probe.close()


def test_server_source_exception_is_500_not_fatal():
    def boom():
        raise RuntimeError("source failed")
    srv = TelemetryServer(
        metrics_text=lambda: "", healthz=lambda: (True, {}),
        queries=boom, doctor=lambda: {}, slo=lambda: {})
    try:
        st, body = _get(srv.endpoint, "/queries")
        assert st == 500 and "source failed" in body
        # the serve thread survives the exception
        assert _get(srv.endpoint, "/healthz")[0] == 200
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# SLO burn rates
# ---------------------------------------------------------------------------

def _slo_conf(**extra):
    base = {"spark.rapids.tpu.slo.latencyObjectiveMs": 10.0,
            "spark.rapids.tpu.slo.latencyTarget": 0.99,
            "spark.rapids.tpu.slo.availabilityTarget": 0.999,
            "spark.rapids.tpu.slo.burnWindowsS": "300,3600"}
    base.update(extra)
    return RapidsConf.get_global().copy(base)


def _feed(reg, tenant, n_ok, lat_ms, n_err=0):
    for _ in range(n_ok):
        reg.observe("query_ms", lat_ms, status="ok", tenant=tenant)
        reg.inc("queries_total", status="ok", tenant=tenant)
    for _ in range(n_err):
        reg.inc("queries_total", status="error", tenant=tenant)


def test_slo_burn_rates_and_admission_hint():
    now = [1000.0]
    tracker = OSLO.SloTracker(OSLO.SloObjectives.from_conf(_slo_conf()),
                              clock=lambda: now[0])
    reg = MetricsRegistry()
    _feed(reg, "A", n_ok=50, lat_ms=100.0, n_err=5)  # slow AND erroring
    _feed(reg, "B", n_ok=50, lat_ms=1.0)             # healthy
    now[0] = 1100.0
    rep = tracker.report(registry=reg)
    assert rep["schema"] == "srt-slo/1"
    a, b = rep["tenants"]["A"], rep["tenants"]["B"]
    assert a["burning"] and not b["burning"]
    w = a["windows"]["300s"]
    assert w["error_burn"] > 1.0 and w["latency_burn"] > 1.0
    assert b["windows"]["300s"]["error_burn"] == 0.0
    assert tracker.admission_hint("A")["burning"]
    assert not tracker.admission_hint("B")["burning"]
    assert not tracker.admission_hint("unseen")["burning"]


def test_slo_burn_is_windowed_not_cumulative():
    """Old badness outside every window must stop burning: the tracker
    reports deltas over its windows, not lifetime totals."""
    now = [1000.0]
    tracker = OSLO.SloTracker(OSLO.SloObjectives.from_conf(_slo_conf()),
                              clock=lambda: now[0])
    reg = MetricsRegistry()
    _feed(reg, "A", n_ok=10, lat_ms=100.0)
    now[0] = 1100.0
    assert tracker.report(registry=reg)["tenants"]["A"]["burning"]
    # 2h of healthy traffic later the slow burst left every window
    for t in range(72):
        now[0] += 100.0
        _feed(reg, "A", n_ok=5, lat_ms=1.0)
        rep = tracker.report(registry=reg)
    assert not rep["tenants"]["A"]["burning"], rep["tenants"]["A"]


def test_slo_doctor_verdict_passes_schema_check(tmp_path):
    now = [1000.0]
    tracker = OSLO.SloTracker(OSLO.SloObjectives.from_conf(_slo_conf()),
                              clock=lambda: now[0])
    reg = MetricsRegistry()
    _feed(reg, "A", n_ok=50, lat_ms=100.0, n_err=5)
    now[0] = 1100.0
    v = tracker.doctor_verdict(registry=reg)
    assert v["verdict"] == "slo-burn"
    assert v["ranked"][0]["tenant"] == "A"
    assert "A" in v["ranked"][0]["evidence"]
    p = tmp_path / "slo_doctor.json"
    p.write_text(json.dumps(v))
    assert check_trace.check_doctor(str(p)) == ("slo-burn", 1)
    # quiet fleet: no-bottleneck, empty ranking
    quiet = OSLO.SloTracker(OSLO.SloObjectives.from_conf(_slo_conf()),
                            clock=lambda: now[0])
    assert quiet.doctor_verdict(
        registry=MetricsRegistry())["verdict"] == "no-bottleneck"


# ---------------------------------------------------------------------------
# trace context + ring health gauges
# ---------------------------------------------------------------------------

def test_trace_context_gating_and_span_ids():
    assert not OT.TRACING["on"]
    assert OT.current_trace_context() is None  # off -> no context, ever
    ids = {OT.next_span_id() for _ in range(100)}
    assert len(ids) == 100
    assert all(i.startswith(f"{os.getpid():x}.") for i in ids)
    prev = OT.TRACING["on"]
    OT.TRACING["on"] = True
    try:
        ctx = OT.current_trace_context()
        assert ctx is not None and ctx["trace"]
    finally:
        OT.TRACING["on"] = prev


def test_fetch_trace_is_thread_local():
    OT.set_fetch_trace({"trace": "t1", "span": "s1"})
    seen = []
    th = threading.Thread(target=lambda: seen.append(OT.fetch_trace()))
    th.start()
    th.join()
    assert seen == [None]
    assert OT.fetch_trace() == {"trace": "t1", "span": "s1"}
    OT.set_fetch_trace(None)


def test_ring_health_metrics_feed():
    from spark_rapids_tpu.observability import metrics as OM
    tracer = OT.get_tracer()
    prev_t, prev_m = OT.TRACING["on"], OM.METRICS["on"]
    reg = OM.get_registry()
    tracer.reset(capacity=16)  # ring capacity floors at 16
    OT.TRACING["on"] = OM.METRICS["on"] = True
    try:
        for i in range(40):  # capacity 16 -> 24 dropped
            tracer.complete("op", f"ev{i}", 0.0, 0.001)
        snap = reg.json_snapshot()
        gauges = {g["name"]: g["value"] for g in snap["gauges"]}
        counters = {c["name"]: c["value"] for c in snap["counters"]}
        assert gauges.get("trace_ring_high_water", 0) >= 16
        assert counters.get("trace_dropped_events_total", 0) >= 24
        text = reg.prometheus_text()
        assert "srt_trace_ring_high_water" in text
        assert "srt_trace_dropped_events_total" in text
    finally:
        OT.TRACING["on"], OM.METRICS["on"] = prev_t, prev_m
        tracer.reset()


# ---------------------------------------------------------------------------
# traced shuffle wire + stitching
# ---------------------------------------------------------------------------

def test_tcp_traced_fetch_emits_linked_serve_span():
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport
    from spark_rapids_tpu.shuffle.transport import BlockId, PeerInfo
    tracer = OT.get_tracer()
    tracer.reset(session="stitch-test")
    prev = OT.TRACING["on"]
    OT.TRACING["on"] = True
    a = TcpShuffleTransport("exec-a")
    b = TcpShuffleTransport("exec-b")
    try:
        blk = BlockId(5, 0, 1)
        a.publish("exec-a", blk, b"traced-frame-bytes")
        ctx = {"trace": "sess-1:q7", "span": "abc.1", "tenant": "t0"}
        OT.set_fetch_trace(ctx)
        try:
            got = b.fetch(PeerInfo("exec-a", a.endpoint), blk)
        finally:
            OT.set_fetch_trace(None)
        assert got == b"traced-frame-bytes"
        serves = [e for e in tracer.snapshot()
                  if e["name"] == "shuffle.serve"]
        assert serves, "no serve span emitted by the traced op"
        args = serves[-1]["args"]
        assert args["trace_id"] == "sess-1:q7"
        assert args["parent_span"] == "abc.1"
        assert args["requester"] == "exec-b"
        assert args["span_id"]
        # untraced fetch still works and emits no new serve span
        n = len(serves)
        assert b.fetch(PeerInfo("exec-a", a.endpoint), blk) == got
        assert len([e for e in tracer.snapshot()
                    if e["name"] == "shuffle.serve"]) == n
    finally:
        OT.TRACING["on"] = prev
        a.close()
        b.close()
        tracer.reset()


def test_tcp_traced_fetch_falls_back_on_old_peer():
    """A peer that answers the traced op with an error (an old binary)
    must be remembered and served via the plain op — same bytes."""
    from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport
    from spark_rapids_tpu.shuffle.transport import BlockId, PeerInfo
    prev = OT.TRACING["on"]
    OT.TRACING["on"] = True
    a = TcpShuffleTransport("exec-a")
    b = TcpShuffleTransport("exec-b")
    # simulate an old peer: its server rejects op 4 like an unknown op
    a._handle_traced = lambda js: {"error": "unknown op 4"}
    try:
        blk = BlockId(6, 0, 0)
        a.publish("exec-a", blk, b"old-peer-frame")
        OT.set_fetch_trace({"trace": "t", "span": "s", "tenant": ""})
        try:
            got = b.fetch(PeerInfo("exec-a", a.endpoint), blk)
        finally:
            OT.set_fetch_trace(None)
        assert got == b"old-peer-frame"
        assert b._no_trace.get(a.endpoint), \
            "old peer not remembered in _no_trace"
        # second fetch goes straight to the plain op
        assert b.fetch(PeerInfo("exec-a", a.endpoint), blk) == got
    finally:
        OT.TRACING["on"] = prev
        a.close()
        b.close()


def test_serializer_frame_trace_extension_and_compat():
    from spark_rapids_tpu.columnar.convert import (arrow_to_device,
                                                   device_to_arrow)
    from spark_rapids_tpu.shuffle.serializer import (_deserialize_batch,
                                                     deserialize_batch,
                                                     serialize_batch)

    def trace_extension(frame):
        """The frame's decoded schema-header trace extension (the frame
        body may be zstd-compressed: raw bytes prove nothing)."""
        found = []
        _deserialize_batch(frame, trace_out=found)
        return found

    t = pa.table({"x": np.arange(64, dtype=np.int64),
                  "y": np.random.default_rng(0).random(64)})
    batch = arrow_to_device(t)
    tracer = OT.get_tracer()
    assert not OT.TRACING["on"]
    frame_off = serialize_batch(batch)
    assert not trace_extension(frame_off)  # off: no extension on the wire
    OT.TRACING["on"] = True
    tracer.reset(session="ser-test")
    try:
        frame_on = serialize_batch(batch)
        # on: versioned schema extension
        (ext,) = trace_extension(frame_on)
        assert ext["trace"] and ext["span"]
        # new reader surfaces the producer's context on its span
        out = deserialize_batch(frame_on)
        assert device_to_arrow(out).equals(t)
        des = [e for e in tracer.snapshot()
               if e["name"] == "deserialize_batch"][-1]
        assert des["args"]["producer_trace"]
        assert des["args"]["producer_span"]
    finally:
        OT.TRACING["on"] = False
        tracer.reset()
    # old reader (tracing off) ignores the extension: same rows
    out = deserialize_batch(frame_on)
    assert device_to_arrow(out).equals(t)
    # and results are bit-identical across traced/untraced frames
    assert device_to_arrow(deserialize_batch(frame_off)).equals(t)


def test_local_transport_parity_serve_span():
    """Single-process stitching parity: LocalTransport emits the same
    shuffle.serve span the TCP server does, so merge/flow validation is
    testable without sockets."""
    from spark_rapids_tpu.shuffle.transport import (BlockId, LocalTransport,
                                                    PeerInfo)
    tracer = OT.get_tracer()
    tracer.reset(session="local-par")
    prev = OT.TRACING["on"]
    OT.TRACING["on"] = True
    tr = LocalTransport()
    try:
        blk = BlockId(4, 1, 1)
        tr.publish("exec-l", blk, b"local-frame")
        OT.set_fetch_trace({"trace": "t9", "span": "p.1", "tenant": ""})
        try:
            assert tr.fetch(PeerInfo("exec-l", ""), blk) == b"local-frame"
        finally:
            OT.set_fetch_trace(None)
        serve = [e for e in tracer.snapshot()
                 if e["name"] == "shuffle.serve"][-1]
        assert serve["args"]["parent_span"] == "p.1"
        assert serve["args"]["trace_id"] == "t9"
    finally:
        OT.TRACING["on"] = prev
        tr.close()
        tracer.reset()


def test_trace_merge_stitches_flow_events(tmp_path):
    """Two synthetic per-process logs -> one merged trace whose flow
    events pass check_trace --flow (each endpoint inside a span, shared
    id, processes named)."""
    from spark_rapids_tpu.observability.export import write_event_log

    requester = [{"ph": "X", "name": "shuffle.fetch.remote",
                  "cat": "shuffle", "ts": 1000.0, "dur": 500.0,
                  "tid": 1, "args": {"span_id": "aa.1",
                                     "trace_id": "s:q1"}}]
    peer = [{"ph": "X", "name": "shuffle.serve", "cat": "shuffle",
             "ts": 50.0, "dur": 80.0, "tid": 7,
             "args": {"span_id": "bb.1", "parent_span": "aa.1",
                      "trace_id": "s:q1"}}]
    lg1 = tmp_path / "p1.jsonl"
    lg2 = tmp_path / "p2.jsonl"
    write_event_log(str(lg1), requester,
                    {"epoch_unix_s": 100.0, "pid": 111, "session_id": "a"})
    # peer epoch 1ms later: merge must normalize onto one clock
    write_event_log(str(lg2), peer,
                    {"epoch_unix_s": 100.001, "pid": 222,
                     "session_id": "b"})
    doc = trace_merge.merge([str(lg1), str(lg2)])
    assert doc["otherData"]["flows"] == 1
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    assert len({e["id"] for e in flows}) == 1
    s = next(e for e in flows if e["ph"] == "s")
    f = next(e for e in flows if e["ph"] == "f")
    assert s["pid"] != f["pid"]
    # peer ts shifted by the 1ms epoch delta onto the global clock
    assert f["ts"] == pytest.approx(50.0 + 1000.0)
    out = tmp_path / "merged.json"
    out.write_text(json.dumps(doc))
    n, cross, pids = check_trace.check_flow(str(out))
    assert (n, cross, pids) == (1, 1, 2)
    # CLI path too
    assert trace_merge.main([str(tmp_path / "m2.json"),
                             str(lg1), str(lg2)]) == 0
    assert check_trace.main(["--flow", str(tmp_path / "m2.json")]) == 0


def test_check_trace_endpoint_scrape_mode():
    srv = TelemetryServer(
        metrics_text=lambda: ("# TYPE srt_q_total counter\n"
                              'srt_q_total{tenant="t0"} 3.0\n'),
        healthz=lambda: (True, {}), queries=lambda: [],
        doctor=lambda: {}, slo=lambda: {})
    try:
        url = srv.endpoint + "/metrics"
        assert check_trace.check_endpoint(url) == "1 samples, 1 families"
        assert check_trace.main(
            ["--endpoint", url, "--prometheus-label", "tenant"]) == 0
        with pytest.raises(ValueError):
            check_trace.check_endpoint(url, require_label="absent")
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# session/engine integration (conf-gated, off by default)
# ---------------------------------------------------------------------------

def test_session_telemetry_off_by_default_and_gated_start():
    import spark_rapids_tpu as srt
    sess = srt.session()
    assert sess.telemetry is None
    sess2 = srt.session(**{"spark.rapids.tpu.telemetry.enabled": True,
                           "spark.rapids.tpu.telemetry.port": 0})
    try:
        assert sess2.telemetry is not None
        st, body = _get(sess2.telemetry.endpoint, "/healthz")
        assert st == 200 and json.loads(body)["status"] == "ok"
        assert _get(sess2.telemetry.endpoint, "/doctor")[0] == 200
    finally:
        port = sess2.telemetry.port
        sess2.close_telemetry()
        assert sess2.telemetry is None
        sess2.close_telemetry()  # idempotent
        assert not [t for t in threading.enumerate()
                    if t.name == f"srt-telemetry-{port}"]
