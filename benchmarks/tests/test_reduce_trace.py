"""``reduce_trace`` on a small recorded trace, against numbers worked out
by hand from the events it holds.

``data/trace_small.pbtxt`` is the first round (Q6, then Q1) of a traced run
of the resident cell on one v5e chip (PR 25, chip call 1), cut down to the
events of 30 ms or more, as a text proto.  It holds, in microseconds:

    device, XLA Modules: 4 x jit_impl of 144.92 ms (Q6's four partitions),
                         4 x jit_impl of 281.8 ms (Q1's)
    device, XLA Ops:     Q6: 4 x %fusion      139105.834, .836, .834, .838
                         Q1: 4 x %conditional 65303.446 65439.544 65307.033
                                              65312.896
                             4 x %fusion.17   190233.215 190235.690
                                              190233.172 190229.303
    host, python3:       bench:tpch_q6 47887.149 + 614192.741
                         bench:tpch_q1 662092.110 + 1272733.084
                         and under each a TpuFusedCollect:task0 and four
                         np.asarray(jax.Array) spans

No operation overlaps another, so the busy time is the plain sum.
"""

import os

import pytest

import reduce_trace as RT

SMALL = os.path.join(os.path.dirname(__file__), "data", "trace_small.pbtxt")
US = 1e-6
Q6_BUSY = (139105.834 + 139105.836 + 139105.834 + 139105.838) * US
Q1_COND = (65303.446 + 65439.544 + 65307.033 + 65312.896) * US
Q1_FUSION = (190233.215 + 190235.690 + 190233.172 + 190229.303) * US
SPAN = (662092.110 + 1272733.084 - 47887.149) * US


def test_small_recorded_trace():
    r = RT.reduce(SMALL)
    assert r["device_planes"] == 1 and r["device_op_events"] == 12
    assert [c["query"] for c in r["collects"]] == ["tpch_q6", "tpch_q1"]
    q6, q1 = r["collects"]
    assert q6["seconds"] == pytest.approx(614192.741 * US)
    assert q6["busy_s"] == pytest.approx(Q6_BUSY, rel=1e-6)
    assert q1["busy_s"] == pytest.approx(Q1_COND + Q1_FUSION, rel=1e-6)
    assert r["span_s"] == pytest.approx(SPAN, rel=1e-9)
    assert r["busy_s"] == pytest.approx(Q6_BUSY + Q1_COND + Q1_FUSION,
                                        rel=1e-6)
    # the busy share of the traced span, by hand 1.578718 / 1.886938
    assert r["busy_s"] / r["span_s"] == pytest.approx(0.836656, rel=1e-5)
    ops = dict(r["device_ops"])
    assert list(ops) == ["jit_impl/%fusion.17 fusion",
                         "jit_impl/%fusion fusion",
                         "jit_impl/%conditional conditional"]
    assert ops["jit_impl/%fusion.17 fusion"] == pytest.approx(Q1_FUSION,
                                                              rel=1e-6)
    assert ops["jit_impl/%fusion fusion"] == pytest.approx(Q6_BUSY, rel=1e-6)
    assert dict(r["device_modules"])["jit_impl"] == pytest.approx(
        (144918.987 + 144919.546 + 144916.846 + 144917.682 + 281768.885
         + 281916.422 + 281782.348 + 281775.491) * US, rel=1e-6)
    # every idle second lies under one of the two host spans of the file
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"TpuFusedCollect:task0", "np.asarray(jax.Array)"}
    assert sum(gaps.values()) == pytest.approx(
        (614192.741 + 1272733.084) * US - r["busy_s"], rel=1e-6)


def test_metric_readers_on_the_small_trace():
    import run as R
    trace = RT.reduce(SMALL)
    run = {"trace": trace, "peaks": {"hbm_bytes_per_s": 819e9},
           "input_bytes": {"tpch_q6": 168_000_000, "tpch_q1": 264_000_000}}
    got = R.read_metrics(["device_ms_per_query", "device_idle_pct",
                          "input_roofline"], run)
    assert got["device_ms_per_query"] == pytest.approx(
        1e3 * (Q6_BUSY + Q1_COND + Q1_FUSION) / 2, rel=1e-6)
    assert got["device_idle_pct"] == pytest.approx(100 * (1 - 0.836656),
                                                   rel=1e-4)
    # (168 MB + 264 MB) / 819 GB/s = 0.527 ms against 1578.7 ms busy
    assert got["input_roofline"] == pytest.approx(
        100 * (432e6 / 819e9) / (Q6_BUSY + Q1_COND + Q1_FUSION), rel=1e-6)
    # nothing to read (an untraced run, or a trace with no device plane):
    # the readers return nothing, never 0
    assert R.read_metrics(["device_ms_per_query", "device_idle_pct",
                           "input_roofline"], {"trace": None}) == {}


def test_interval_arithmetic():
    merged = RT.merge([(5, 7), (0, 2), (1, 3), (6, 6.5), (10, 11)])
    assert merged == [(0, 3), (5, 7), (10, 11)]
    assert RT.total(merged) == 6
    assert RT.clip(merged, 2, 10.5) == [(2, 3), (5, 7), (10, 10.5)]
    assert RT.gaps(RT.clip(merged, 2, 10.5), 2, 12) == [
        (3, 5), (7, 10), (10.5, 12)]
    assert RT.gaps([], 1, 4) == [(1, 4)]


def test_self_seconds_nets_out_nested_operations():
    # a while of 10 s holding a fusion of 4 s that holds a copy of 1 s, and
    # a second fusion of 3 s; then a lone op
    ev = [("while", 0.0, 10e9), ("fusion", 1e9, 5e9), ("copy", 2e9, 3e9),
          ("fusion", 6e9, 9e9), ("lone", 12e9, 13e9)]
    s = RT.self_seconds(ev)
    assert s["while"] == pytest.approx(3.0)
    assert s["fusion"] == pytest.approx(6.0)
    assert s["copy"] == pytest.approx(1.0)
    assert s["lone"] == pytest.approx(1.0)
    assert sum(s.values()) == pytest.approx(11.0)    # = the busy time


def test_short_names():
    text = ("%while.4 = (u32[]{:T(128)}, s32[2097152]{0:T(1024)S(1)}) "
            "while((u32[]{:T(128)}, s32[2097152]{0}) %tuple.54), "
            "condition=%c, body=%b")
    assert RT.short_op(text) == "%while.4 while"
    assert RT.short_op("%fusion.27 = s32[8]{0:T(1024)S(1)} fusion(s32[8]{0} "
                       "%x), kind=kCustom, calls=%f") == "%fusion.27 fusion"
    assert RT.short_op('%custom-call.1 = u32[128]{0:T(128)} custom-call('
                       's64[128]{0:T(128)} %s), custom_call_target="X"'
                       ) == "%custom-call.1 custom-call"
    assert RT.short_op("no equals sign") == "no equals sign"
    assert RT.short_module("jit__expand_runs_u32(5005598340048944826)"
                           ) == "jit__expand_runs_u32"
