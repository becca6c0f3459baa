"""``mesh_trace`` and the four readers of the mesh plane on a small recorded
four-plane trace, against numbers worked out from the events it holds; and
the manifest's entries for the four-chip cell against its files.

``data/trace_mesh4_small.pbtxt`` is the first collect (Q3) of a traced run
of ``tpch-1m-join-q3-mesh4`` on the four chips of one v5e host (PR 28's chip
call a, seed 2147484101, at SF 2/3), cut down to the program runs, device
operations and ``srt:`` spans of 2 ms or more.  Of the exchange program it
holds, in microseconds (start + length):

    /device:TPU:0  jit_srt_MeshExchange_exchange_42ca1608   708129.354 + 432078.190
                   jit_srt_MeshExchange_exchange_f9db5978  1915238.645 +  34510.542
    /device:TPU:1  ..._42ca1608  432026.413   ..._f9db5978  34541.907
    /device:TPU:2  ..._42ca1608  432048.722   ..._f9db5978  34516.489
    /device:TPU:3  ..._42ca1608  431990.159   ..._f9db5978  34547.027

(LINEITEM's exchange and the joined ORDERS'; the others ran under 2 ms and
were cut), 27 or 28 other ``jit_srt_*`` runs a plane, and on the host four
``srt:shuffle:mesh_exchange`` spans, 494264.433 us together.  Busy time is
the union of each plane's program runs (``XLA Modules``); the test works it
out again with a sweep of its own.  ``data/trace_spans_small.pbtxt`` (PR 26) has one device
plane and no mesh span: every reader says nothing there.
"""

import json
import os

import pytest

import mesh_bytes as MB
import mesh_trace as MT
import program_spans as PS
import reduce_trace as RT
import run as R
from conftest import BENCH

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "trace_mesh4_small.pbtxt")
ONE_PLANE = os.path.join(DATA, "trace_spans_small.pbtxt")
READERS = ("exchange_ms", "exchange_collective_ms", "exchange_ici_roofline",
           "least_busy_chip_pct")
CELL = "tpch-1m-join-q3-mesh4"
US = 1e-6
EXCHANGE_US = {0: 432078.190 + 34510.542, 1: 432026.413 + 34541.907,
               2: 432048.722 + 34516.489, 3: 431990.159 + 34547.027}
SPAN_US = 494264.433
CROSSED = 194417013.0    # meshCrossChipBytes of a collect, for the test


def swept_busy_seconds(plane, lo, hi) -> float:
    """Union of the plane's program runs inside [lo, hi], by a sweep
    over sorted starts and ends (``reduce_trace.merge`` builds intervals
    instead)."""
    edges = []
    for line in plane.lines:
        if line.name == "XLA Modules":
            for e in line.events:
                s = max(float(e.start_ns), lo)
                t = min(float(e.start_ns + e.duration_ns), hi)
                if t > s:
                    edges += [(s, 1), (t, -1)]
    edges.sort()
    busy, depth, since = 0.0, 0, 0.0
    for at, step in edges:
        if depth == 0 and step > 0:
            since = at
        depth += step
        if depth == 0:
            busy += at - since
    return busy * 1e-9


def run_record(counter=CROSSED):
    metrics = {"meshExchanges": 4.0, "meshFallbacks": 0.0}
    if counter is not None:
        metrics[MB.COUNTER] = counter
    return {"trace": {"collects": [{}]},
            "cell": {"name": "x", "queries": ["tpch_q3"]},
            "config": {"chips": 4}, "query_metrics": {"tpch_q3": metrics}}


def test_small_recorded_four_plane_trace():
    r = MT.reduce(SMALL)
    assert r["collects"] == 1
    assert sorted(r["chips"]) == [f"/device:TPU:{n}" for n in range(4)]
    data = RT.load(SMALL)
    planes = {p.name: p for p in data.planes}
    lo, hi = next((float(e.start_ns), float(e.start_ns + e.duration_ns))
                  for line in planes["/host:CPU"].lines for e in line.events
                  if e.name == "bench:tpch_q3")
    assert r["span_s"] == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    for n in range(4):
        chip = r["chips"][f"/device:TPU:{n}"]
        assert chip["exchange_runs"] == 2
        assert chip["exchange_s"] == pytest.approx(EXCHANGE_US[n] * US,
                                                   rel=1e-9)
        assert chip["stage_runs"] in (27, 28)
        assert chip["busy_s"] == pytest.approx(
            swept_busy_seconds(planes[f"/device:TPU:{n}"], lo, hi), rel=1e-9)
    assert MT.collective_s_per_collect(r) == pytest.approx(
        max(EXCHANGE_US.values()) * US, rel=1e-9)


def test_one_working_plane_reduces_to_nothing():
    assert MT.reduce(ONE_PLANE) is None
    assert MT.collective_s_per_collect(None) is None


def test_readers_on_the_four_plane_trace(monkeypatch):
    monkeypatch.setattr(PS, "trace_file", lambda run: SMALL)
    monkeypatch.setattr(MB, "device_kind", lambda: "TPU v5 lite")
    got = R.read_metrics(list(READERS), run_record())
    assert got["exchange_ms"] == pytest.approx(SPAN_US / 1e3, rel=1e-9)
    worst = max(EXCHANGE_US.values()) * US
    assert got["exchange_collective_ms"] == pytest.approx(worst * 1e3,
                                                          rel=1e-9)
    # a quarter of what crossed leaves each chip, at 200e9 bytes/s
    assert got["exchange_ici_roofline"] == pytest.approx(
        100 * (CROSSED / 4 / 200e9) / worst, rel=1e-9)
    assert 0 < got["exchange_ici_roofline"] <= 100
    busy = [c["busy_s"] for c in MT.reduce(SMALL)["chips"].values()]
    assert got["least_busy_chip_pct"] == pytest.approx(
        100 * min(busy) / MT.reduce(SMALL)["span_s"], rel=1e-9)
    # a program without the counter, or whose exchanges moved nothing:
    # no share of the roofline, never 0
    for counter in (None, 0.0):
        assert "exchange_ici_roofline" not in R.read_metrics(
            list(READERS), run_record(counter))


def test_readers_say_nothing_on_one_plane_or_without_a_trace(monkeypatch):
    monkeypatch.setattr(MB, "device_kind", lambda: "TPU v5 lite")
    untraced = {**run_record(), "trace": None, "cell": {
        "name": "no-such-cell", "queries": ["tpch_q3"]}}
    assert R.read_metrics(list(READERS), untraced) == {}
    # the parent on the four-chip host: one plane works, no mesh span
    monkeypatch.setattr(PS, "trace_file", lambda run: ONE_PLANE)
    assert R.read_metrics(list(READERS), run_record(None)) == {}


def test_an_unknown_device_kind_is_an_error():
    assert MB.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(SystemExit, match="no ICI peak"):
        MB.ici_bytes_per_s("TPU v9")


def test_the_manifest_covers_the_four_chip_cell():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        m = json.load(f)
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 4 and cell["config"] == "tpch-1m-mesh4"
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 1
    entry = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["scale_factor"]
    with open(os.path.join(os.path.dirname(BENCH), entry["file"])) as f:
        held = json.load(f)
    with open(os.path.join(BENCH, "configs", "tpch-1m-8tables.json")) as f:
        one_chip = json.load(f)
    assert held["chips"] == cell["chips"]
    assert held["source"] == entry["source"]
    assert set(held["reduced"]) == set(entry["reduced"])
    # the deployment, not a switch between planes
    assert held["session_conf"] == {
        "spark.executor.instances": 4, "spark.sql.shuffle.partitions": 4,
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1}
    # the same data, the same guarantees as the one-chip control
    for key in ("generator", "scale", "tables", "schema", "storage",
                "guarantees"):
        assert held[key] == one_chip[key], key
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        assert json.load(f) == {"name": CELL, "config": cell["config"],
                                "view": "memory", "queries": ["tpch_q3"]}
    # it reports all of the one-chip Q3 cell's per-layer metrics, and the
    # mesh plane's four, which no other cell can
    for p in m["per_layer"]:
        if "tpch-1m-join-q3" in p["workloads"]:
            assert CELL in p["workloads"], p["name"]
        if p["name"] in READERS:
            assert p["workloads"] == [CELL] and p["layer"] == "mesh plane"
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               p["name"] + ".py"))
    assert {p["name"] for p in m["per_layer"]} >= set(READERS)
    e2e, per_layer = R.manifest_metrics(CELL)
    assert {e["name"] for e in e2e} == {"query_s", "setup_s"}
    assert len(per_layer) == 16 + len(READERS)
