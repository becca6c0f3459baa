"""``program_spans`` on a small recorded trace, against numbers worked out
by hand from the events it holds.

``data/trace_spans_small.pbtxt`` is the first collect (Q6) of a traced run of
the parquet cell on one v5e chip (PR 26, chip call A), as a text proto, cut
down to: host spans and host-side launches (``PjitFunction(..)``) of 100 ms
or more, program runs (``XLA Modules``) of 100 ms or more, device operations
(``XLA Ops``) of 130 ms or more, and of the decoder's ``%while.4`` only those
of 250 ms or more (so that the gaps can be worked out by hand).  It holds, in
microseconds (start + length):

    host, python:
      bench:tpch_q6                     46302.439 + 11173384.455
      srt:query:collect                 47641.829 + 11171889.075
      srt:task:TpuFusedCollect:task0    49507.089 + 11169653.635
      srt:op:TpuFusedCollect            49520.769 + 11168887.695
        srt:op:TpuFileScan              49535.009 +  7550978.890
          srt:scan:device_decode        50846.039 +  5935047.502
          srt:scan:host_decode        5985942.851 +   346803.796
          srt:h2d:arrow_to_device     6379246.717 +  1221164.332
        srt:op:TpuFileScan            7600539.739 +  1592378.046
          srt:scan:host_decode        7600581.529 +   205018.406
          srt:h2d:arrow_to_device     7850496.994 +  1342290.821
        srt:op:DeviceToHost           9193054.265 +  2025350.169
         srt:op:TpuProject            9193089.045 +  2023587.889
          srt:op:TpuHashAggregate     9193143.315 +  2022353.709
           srt:op:Replay              9510357.532 +  1515708.543
            srt:op:TpuFileScan        9510364.772 +  1515694.763
             srt:scan:host_decode     9510404.872 +   425524.336
             srt:h2d:arrow_to_device  9972321.717 +  1053645.468
      and inside the ``scan:device_decode`` span 9 launches of
      ``_expand_runs_u32`` and 9 of ``_remap_indices``, each marked twice
      (jax nests a launch's annotation inside one of the same name)
    device, XLA Ops (what "busy" is):
      A %while.4   3604320.887 + 255488.777      B %while.4 4238554.005 + 270515.359
      C %fusion    9202673.942 + 139105.836      D %fusion  9367382.900 + 139105.838
      E %fusion   11034714.669 + 139105.837
    device, XLA Modules: 22 x jit__expand_runs_u32,
                         3 x jit_srt_HashAggregateExec_fusedpartial_36058aa4

The six idle gaps of the collect (46302.439 .. A, A .. B, B .. C, C .. D,
D .. E, E .. 11219686.894: 3558018.448, 378744.341, 4693604.578, 25603.122,
1528225.931 and 45866.388), each split wherever a span starts or ends and
every piece put under the innermost span over it:

    scan:device_decode   50846.039 .. A's start, A's end .. B's start and
                         B's end .. 5985893.541:
                         3553474.848 + 378744.341 + 1476824.177 = 5409043.366
    scan:host_decode     all three, whole: 977346.538
    h2d:arrow_to_device  all three, whole: 3617100.621
    op:TpuFileScan       all of its own time (129557.038, below)
    op:TpuHashAggregate  its start .. C, C .. D, D .. Replay, Replay's end
                         .. E, E's end .. its end: 9530.627 + 25603.122 +
                         3868.794 + 8648.594 + 41676.518 = 89327.655
    op:TpuFusedCollect 180.590, op:DeviceToHost 1762.280, op:TpuProject
    1234.180, op:Replay 13.780: the slivers between a span's start or end
    and its child's; so "op" in all 222075.523
    task 13.680 + 752.260 = 765.940; query 1865.260 + 370.180 = 2235.440
    no span: bench's start .. query's and query's end .. bench's:
             1339.390 + 155.990 = 1495.380
"""

import os

import numpy as np
import pytest

import program_spans as PS

SMALL = os.path.join(os.path.dirname(__file__), "data",
                     "trace_spans_small.pbtxt")
US = 1e-6
G1, G2, G3, G4, G5, G6 = (3558018.448, 378744.341, 4693604.578, 25603.122,
                          1528225.931, 45866.388)
BUSY = 255488.777 + 270515.359 + 139105.836 + 139105.838 + 139105.837
H2D = 1221164.332 + 1342290.821 + 1053645.468
HOST_DECODE = 346803.796 + 205018.406 + 425524.336
UNATTRIBUTED = 222075.523 + 765.940 + 2235.440 + 1495.380   # op, task, ...
NEW_READERS = ("plan_ms", "scan_host_ms", "h2d_ms", "d2h_ms", "retrace_ms",
               "programs_outside_cache_per_query", "idle_unattributed_pct")


def test_small_recorded_trace():
    r = PS.reduce(SMALL)
    assert r["collects"] == 1 and r["idle_gaps"] == 6
    assert r["idle_s"] == pytest.approx((11173384.455 - BUSY) * US, rel=1e-9)
    assert r["idle_s"] == pytest.approx(
        (G1 + G2 + G3 + G4 + G5 + G6) * US, rel=1e-9)

    # seconds, and self seconds net of what is nested inside
    spans = r["spans"]
    scan = spans["srt:op:TpuFileScan"]
    assert scan["n"] == 3
    assert scan["s"] == pytest.approx(
        (7550978.890 + 1592378.046 + 1515694.763) * US, rel=1e-9)
    # 7550978.890 - 5935047.502 - 346803.796 - 1221164.332 = 47963.260;
    # 1592378.046 - 205018.406 - 1342290.821 = 45068.819;
    # 1515694.763 - 425524.336 - 1053645.468 = 36524.959
    assert scan["self_s"] == pytest.approx(
        (47963.260 + 45068.819 + 36524.959) * US, rel=1e-6)
    assert spans["srt:query:collect"]["self_s"] == pytest.approx(
        (11171889.075 - 11169653.635) * US, rel=1e-6)
    assert spans["srt:h2d:arrow_to_device"]["self_s"] == pytest.approx(
        H2D * US, rel=1e-9)
    cats = r["categories"]
    assert cats["h2d"]["s"] == pytest.approx(H2D * US, rel=1e-9)
    assert cats["scan"]["s"] == pytest.approx(
        (5935047.502 + HOST_DECODE) * US, rel=1e-9)
    # a category's seconds count the outermost span of the category only
    assert cats["op"]["s"] == pytest.approx(11168887.695 * US, rel=1e-9)

    # idle time under an srt: span that says what the host did ...
    assert spans["srt:scan:device_decode"]["idle_s"] == pytest.approx(
        (3553474.848 + G2 + 1476824.177) * US, rel=1e-9)
    assert spans["srt:scan:host_decode"]["idle_s"] == pytest.approx(
        HOST_DECODE * US, rel=1e-9)
    assert spans["srt:h2d:arrow_to_device"]["idle_s"] == pytest.approx(
        H2D * US, rel=1e-9)
    # ... under one that names the exec and nothing below it ...
    assert spans["srt:op:TpuFileScan"]["idle_s"] == pytest.approx(
        scan["self_s"], rel=1e-6)
    assert spans["srt:op:TpuHashAggregate"]["idle_s"] == pytest.approx(
        (9530.627 + G4 + 3868.794 + 8648.594 + 41676.518) * US, rel=1e-6)
    assert cats["op"]["idle_s"] == pytest.approx(222075.523 * US, rel=1e-6)
    assert cats["task"]["idle_s"] == pytest.approx(765.940 * US, rel=1e-6)
    assert cats["query"]["idle_s"] == pytest.approx(2235.440 * US, rel=1e-6)
    # ... and under none at all
    assert cats["(no srt span)"]["idle_s"] == pytest.approx(
        (1339.390 + 155.990) * US, rel=1e-6)
    assert r["idle_unattributed_s"] == pytest.approx(UNATTRIBUTED * US,
                                                     rel=1e-6)
    assert sum(c["idle_s"] for c in cats.values()) == pytest.approx(
        r["idle_s"], rel=1e-9)

    # program runs with and without the kernel cache's prefix
    assert r["programs"] == {
        "jit__expand_runs_u32": 22,
        "jit_srt_HashAggregateExec_fusedpartial_36058aa4": 3}
    assert r["program_runs"] == 25
    assert r["program_runs_outside_cache"] == 22
    # and where the host launched them from
    assert r["launch_sites"] == {
        "srt:scan:device_decode <- _expand_runs_u32": 9,
        "srt:scan:device_decode <- _remap_indices": 9}


def test_innermost_span_over_a_point_and_idle_before_a_time():
    # two threads; ns.  thread 0: a task of 100 holding an h2d of 10..30;
    # thread 1: a d2h of 60..90
    threads = [[("srt:task:T", 0.0, 100.0), ("srt:h2d:up", 10.0, 30.0)],
               [("srt:d2h:get", 60.0, 90.0)]]
    depths = [PS.nest(t) for t in threads]
    assert depths[0] == [(-1, 0), (0, 1)] and depths[1] == [(-1, 0)]
    points = np.array([15.0,     # in the h2d, the deeper of thread 0's two
                       45.0,     # only the task is over it
                       70.0,     # the other thread's d2h is the shorter
                       120.0])   # past every span
    over = PS.innermost(threads, depths, points)
    names = [threads[t][i][0] if t >= 0 else None for t, i in over]
    assert names == ["srt:h2d:up", "srt:task:T", "srt:d2h:get", None]
    gaps = np.array([(10.0, 20.0), (40.0, 45.0)])
    assert list(PS.idle_before(gaps, np.array([0.0, 10.0, 13.0, 20.0, 30.0,
                                               42.0, 45.0, 99.0]))) == [
        0.0, 0.0, 3.0, 10.0, 10.0, 12.0, 15.0, 15.0]


def test_launch_sites_of_one_thread():
    spans = [("srt:op:X", 0.0, 100.0), ("srt:shuffle:s", 10.0, 50.0)]
    launches = [("PjitFunction(dynamic_slice)", 12.0, 20.0),
                ("PjitFunction(dynamic_slice)", 13.0, 19.0),   # the twin
                ("PjitFunction(dynamic_slice)", 21.0, 22.0),
                ("PjitFunction(_pad)", 60.0, 61.0),
                ("PjitFunction(srt_SortExec_compute_0a)", 62.0, 63.0),
                ("PjitFunction(_pad)", 120.0, 121.0),   # under no span
                ("PjitFunction(_pad)", 300.0, 301.0)]   # outside a collect
    got = PS.launch_sites(spans, launches, lambda start: start < 200.0)
    assert got == {("srt:shuffle:s", "dynamic_slice"): 2,
                   ("srt:op:X", "_pad"): 1, ("(no srt span)", "_pad"): 1}


def test_a_trace_without_the_programs_spans_reduces_to_nothing():
    # PR 25's recorded trace: a program from before the spans existed
    old = os.path.join(os.path.dirname(SMALL), "trace_small.pbtxt")
    assert PS.reduce(old) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_returns_none_without_a_trace(name):
    import run as R
    run = {"trace": None, "cell": {"name": "no-such-cell"},
           "window": {"collects": 3},
           "kernel_cache": {"retrace_ms": 12.0}}
    assert R.read_metrics([name], run) == {}


def test_new_readers_on_the_small_trace(monkeypatch):
    import run as R
    monkeypatch.setattr(PS, "trace_file", lambda run: SMALL)
    run = {"trace": {"collects": [{}]}, "cell": {"name": "x"},
           "window": {"collects": 4}, "kernel_cache": {"retrace_ms": 12.0}}
    got = R.read_metrics(list(NEW_READERS), run)
    assert got["plan_ms"] == 0.0          # both plan spans are under 100 ms
    assert got["d2h_ms"] == 0.0
    assert got["h2d_ms"] == pytest.approx(H2D / 1e3, rel=1e-9)
    assert got["scan_host_ms"] == pytest.approx(HOST_DECODE / 1e3, rel=1e-9)
    assert got["retrace_ms"] == 3.0
    assert got["programs_outside_cache_per_query"] == 22
    assert got["idle_unattributed_pct"] == pytest.approx(
        100 * UNATTRIBUTED / (G1 + G2 + G3 + G4 + G5 + G6), rel=1e-6)
    # a program without the counter (the parent): nothing, not 0
    del run["kernel_cache"]["retrace_ms"]
    assert R.read_metrics(["retrace_ms"], run) == {}
