"""Query-timeline tracer (observability/): ring-buffer semantics,
thread safety, Chrome-trace/JSONL export schema, session wiring
(profile_last_query attribution, export_chrome_trace, kernel-cache
deltas in last_query_metrics), flag restore-on-exception, and the
nested-TaskContext regression (PR 3 satellites)."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.observability import export as OE
from spark_rapids_tpu.observability import report as OR
from spark_rapids_tpu.observability import tracer as OT
from spark_rapids_tpu.sql import functions as F

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tracing_on():
    """Fresh tracer + flag on, restored afterwards."""
    prev = OT.TRACING["on"]
    OT.get_tracer().reset(256)
    OT.TRACING["on"] = True
    yield OT.get_tracer()
    OT.TRACING["on"] = prev
    OT.get_tracer().reset()


# --------------------------------------------------------------------------
# ring buffer + thread safety
# --------------------------------------------------------------------------

def test_disabled_span_is_null_object():
    prev = OT.TRACING["on"]
    OT.TRACING["on"] = False
    try:
        OT.get_tracer().reset()
        with OT.span("sync", "x", bytes=1):
            pass
        assert OT.get_tracer().snapshot() == []
    finally:
        OT.TRACING["on"] = prev


def test_ring_overflow_keeps_newest_and_counts_drops(tracing_on):
    tr = tracing_on
    tr.reset(capacity=16)
    for i in range(40):
        with OT.span("op", f"e{i}"):
            pass
    events = tr.snapshot()
    assert len(events) == 16
    # newest events kept (the last 16 emitted)
    assert [e["name"] for e in events] == [f"e{i}" for i in range(24, 40)]
    assert tr.dropped_events == 24


def test_thread_safety_under_pool(tracing_on):
    """Concurrent emitters (the shuffle writer/reader pool shape) must
    neither crash nor lose accounting: events kept + dropped == emitted."""
    tr = tracing_on
    tr.reset(capacity=64)
    n_threads, per_thread = 8, 200
    barrier = threading.Barrier(n_threads)

    def emit(t):
        barrier.wait()
        for i in range(per_thread):
            tr.complete("shuffle", f"t{t}-{i}", 0.0, 0.001, bytes=i)

    threads = [threading.Thread(target=emit, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = tr.snapshot()
    assert len(events) == 64
    assert len(events) + tr.dropped_events == n_threads * per_thread


def test_exec_stack_nests_and_attributes(tracing_on):
    tr = tracing_on
    assert OT.current_exec() == ""
    OT.push_exec("Outer")
    OT.push_exec("Inner")
    tr.complete("sync", "readback", 0.0, 0.002)
    OT.pop_exec()
    tr.complete("sync", "readback", 0.0, 0.003)
    OT.pop_exec()
    assert OT.current_exec() == ""
    evs = tr.snapshot()
    assert evs[0]["exec"] == "Inner" and evs[1]["exec"] == "Outer"
    agg = OR.aggregate_by_exec(evs)
    assert agg["Inner"]["sync_n"] == 1 and agg["Outer"]["sync_n"] == 1


# --------------------------------------------------------------------------
# export schema
# --------------------------------------------------------------------------

def _check_chrome_schema(doc):
    assert isinstance(doc["traceEvents"], list) and doc["traceEvents"]
    for ev in doc["traceEvents"]:
        for field in ("ph", "ts", "pid", "tid", "name"):
            assert field in ev, (field, ev)
        assert ev["ph"] in ("X", "C", "i", "M", "B", "E")
        if ev["ph"] == "X":
            assert ev["dur"] >= 0


def test_chrome_trace_schema(tracing_on, tmp_path):
    tr = tracing_on
    with OT.span("d2h", "fetch", bytes=128):
        pass
    tr.counter("readbacks", 2)
    path = str(tmp_path / "trace.json")
    OE.write_chrome_trace(path, tr.snapshot(), tr.meta())
    with open(path) as fh:
        doc = json.load(fh)
    _check_chrome_schema(doc)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert spans[0]["name"] == "fetch" and spans[0]["cat"] == "d2h"
    counters = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert counters and counters[0]["args"]["value"] == 2


def test_check_trace_tool(tracing_on, tmp_path):
    """tools/check_trace.py (the CI validator) accepts a real export and
    rejects a broken one."""
    tr = tracing_on
    with OT.span("sync", "s"):
        pass
    good = str(tmp_path / "good.json")
    OE.write_chrome_trace(good, tr.snapshot(), tr.meta())
    tool = os.path.join(REPO, "tools", "check_trace.py")
    assert subprocess.run([sys.executable, tool, good]).returncode == 0
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as fh:
        json.dump({"traceEvents": [{"ph": "X", "ts": 0}]}, fh)
    assert subprocess.run([sys.executable, tool, bad]).returncode != 0


def test_jsonl_event_log_round_trip(tracing_on, tmp_path):
    tr = tracing_on
    with OT.span("spill", "spill.deviceToHost", bytes=64):
        pass
    with OT.span("h2d", "upload", bytes=32):
        pass
    path = str(tmp_path / "log.jsonl")
    meta = dict(tr.meta(), query=1)
    OE.write_event_log(path, tr.snapshot(), meta)
    # append-only: a second query's log stacks in the same file
    OE.write_event_log(path, tr.snapshot(), dict(meta, query=2))
    logs = OE.read_event_log(path)
    assert len(logs) == 2
    for got_meta, got_events in logs:
        assert got_events == tr.snapshot()
    assert logs[0][0]["query"] == 1 and logs[1][0]["query"] == 2


# --------------------------------------------------------------------------
# session wiring (end-to-end on the join micro-shape)
# --------------------------------------------------------------------------

def _join_query(sess, n=20000, salt=0):
    rng = np.random.default_rng(7)
    fact = pa.table({"fk": rng.integers(0, 500, n), "x": rng.random(n)})
    dim = pa.table({"pk": np.arange(500, dtype=np.int64),
                    "cat": rng.integers(0, 8, 500)})
    f = sess.create_dataframe(fact, num_partitions=2)
    d = sess.create_dataframe(dim)
    return (f.join(d, f.fk == d.pk, "inner")
            .filter(F.col("x") >= float(salt))  # salt -> fresh kernel keys
            .groupBy("cat")
            .agg(F.count("*").alias("n"), F.sum(F.col("x")).alias("sx"))
            .orderBy("cat"))


def test_traced_join_attribution_and_export(tmp_path):
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    _join_query(sess).collect()
    report = sess.profile_last_query()
    # per-exec columns for self-time, sync, compile, h2d/d2h bytes
    for col in ("self_ms", "sync_ms", "compile_ms", "h2d", "d2h"):
        assert col in report, report
    assert "Join" in report
    summary = sess.last_query_trace_summary
    assert summary["sync_count"] >= 1          # join sizing readback
    assert summary["h2d_bytes"] > 0            # arrow -> device upload
    assert summary["d2h_bytes"] > 0            # result fetch
    path = str(tmp_path / "join_trace.json")
    assert sess.export_chrome_trace(path) == path
    with open(path) as fh:
        doc = json.load(fh)
    _check_chrome_schema(doc)
    cats = {e.get("cat") for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "op" in cats and ("sync" in cats or "d2h" in cats)
    # a join sizing readback attributed to a join exec node
    syncs = [e for e in doc["traceEvents"]
             if e["ph"] == "X" and e.get("cat") == "sync"]
    assert any("Join" in e["args"].get("exec", "") for e in syncs), syncs


def test_kernel_cache_stats_in_last_query_metrics():
    sess = srt.session(**{"spark.rapids.tpu.trace.sink": "memory"})
    q = _join_query(sess, salt=1)  # distinct literal -> fresh kernels
    q.collect()
    cold = dict(sess.last_query_metrics)
    assert cold["kernelCacheMisses"] > 0
    assert cold["kernelCompiles"] > 0
    assert cold["kernelCompileMs"] > 0
    q.collect()
    warm = dict(sess.last_query_metrics)
    assert warm["kernelCacheHits"] > 0
    assert warm["kernelCompiles"] == 0
    assert warm["kernelCompileMs"] == 0


def test_trace_sink_writes_jsonl_per_query(tmp_path):
    sink = str(tmp_path / "eventlog")
    sess = srt.session(**{"spark.rapids.tpu.trace.sink": sink})
    _join_query(sess).collect()
    files = os.listdir(sink)
    assert len(files) == 1 and files[0].endswith(".jsonl")
    logs = OE.read_event_log(os.path.join(sink, files[0]))
    assert len(logs) == 1
    meta, events = logs[0]
    assert events and meta["capacity"] > 0


def test_tracing_off_by_default_and_zero_events():
    # explicit default conf: a bare srt.session() would return the
    # process's active session, which another test may have profiled
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": False})
    tr = OT.get_tracer()
    tr.reset()
    _join_query(sess).collect()
    assert OT.TRACING["on"] is False
    assert tr.snapshot() == []
    assert sess.last_query_trace_summary is None


# --------------------------------------------------------------------------
# flag hygiene (satellite: session-scoped-safe process flags)
# --------------------------------------------------------------------------

def test_flags_restored_on_exception():
    from spark_rapids_tpu.sql.physical.base import PROFILING
    prev_prof, prev_trace = PROFILING["on"], OT.TRACING["on"]
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    f = F.udf(lambda a: {}[a], returnType=srt.DOUBLE)  # raises KeyError
    df = sess.create_dataframe(pa.table({"a": [1.0, 2.0]}))
    with pytest.raises(Exception):
        df.select(f(df.a).alias("b")).collect()
    assert PROFILING["on"] == prev_prof
    assert OT.TRACING["on"] == prev_trace


def test_profiling_does_not_leak_across_sessions():
    from spark_rapids_tpu.sql.physical.base import PROFILING
    sess1 = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    _join_query(sess1).collect()
    assert PROFILING["on"] is False  # restored after the query
    sess2 = srt.session(**{"spark.rapids.tpu.profile.enabled": False})
    _join_query(sess2).collect()
    assert sess2.last_query_trace_summary is None


# --------------------------------------------------------------------------
# nested TaskContext restore (satellite: execute_all clobbered the outer)
# --------------------------------------------------------------------------

def test_execute_all_restores_outer_task_context():
    from spark_rapids_tpu.sql.physical.base import TaskContext
    sess = srt.session()
    df = sess.create_dataframe(pa.table({"k": [1, 2, 3]}))
    phys = sess.physical_plan(df.groupBy("k").count())
    outer = TaskContext(99)
    TaskContext._set_current(outer)
    try:
        # a nested map-side execute_all (subquery/broadcast under an
        # outer exchange task) must restore the OUTER context, not None
        phys.execute_all(sess._conf)
        assert TaskContext.current() is outer
    finally:
        TaskContext._set_current(None)


# --------------------------------------------------------------------------
# one primitive, two sinks (PR 26): the sink matrix
# --------------------------------------------------------------------------

@pytest.fixture
def sinks_off():
    prev = dict(OT.TRACING)
    OT.TRACING.update(on=False, profiler=False)
    OT.get_tracer().reset()
    yield OT.get_tracer()
    OT.TRACING.update(prev)
    OT.get_tracer().reset()


def test_both_sinks_off_is_the_shared_null_span(sinks_off):
    assert OT.span("d2h", "x", bytes=1) is OT._NULL_SPAN
    assert OT.span("op", "y") is OT._NULL_SPAN
    with OT.span("d2h", "x") as sp:
        sp.set_metadata(bytes=3)      # accepted, goes nowhere
    assert sinks_off.snapshot() == []


def test_profiler_sink_alone_leaves_the_ring_empty(sinks_off):
    seen = {}
    import spark_rapids_tpu.sql.physical.fusion as fusion
    real = fusion.FusedStageExec._execute_terminal

    def spy(self, pid, tctx):
        seen["flags"] = dict(OT.TRACING)
        seen["span"] = type(OT.span("op", "probe")).__name__
        return real(self, pid, tctx)
    fusion.FusedStageExec._execute_terminal = spy
    try:
        sess = srt.session(**{"spark.rapids.tpu.trace.enabled": True})
        _join_query(sess, salt=0.125).collect()
    finally:
        fusion.FusedStageExec._execute_terminal = real
    # armed for the query only, and as an annotation, not a ring span
    assert seen["flags"] == {"on": False, "profiler": True}
    assert seen["span"] == "TraceAnnotation"
    assert OT.TRACING == {"on": False, "profiler": False}
    tr = OT.get_tracer()
    assert tr.snapshot() == [] and tr.counters == {}
    assert sess.last_query_trace_summary is None
    assert "traceRingHighWater" not in sess.last_query_metrics


def test_ring_sink_alone_yields_its_events(sinks_off):
    sess = srt.session(**{"spark.rapids.tpu.trace.sink": "memory"})
    q = _join_query(sess, salt=0.25)
    q.collect()                       # compiles
    events = list(sess._last_trace_events)
    q.collect()                       # warm: dispatches
    events += sess._last_trace_events
    cats = {e["cat"] for e in events}
    # what the ring held before the profiler sink existed ...
    assert {"op", "stage", "sync", "h2d", "d2h", "compile",
            "shuffle"} <= cats
    assert any(e["name"] == "join.readback" and "Join" in e["exec"]
               for e in events)
    assert all("ts" in e and e["dur"] >= 0 and "tid" in e for e in events)
    # ... plus the layer boundaries, and every category is a known one
    assert {"query", "plan", "task", "dispatch"} <= cats
    assert cats <= set(OT.CATEGORIES)
    assert OT.get_tracer().counters["deviceDispatches"] > 0
    assert OT.TRACING == {"on": False, "profiler": False}


def test_both_sinks_on_feed_both(sinks_off, tmp_path):
    import jax.profiler
    sess = srt.session(**{"spark.rapids.tpu.trace.sink": "memory",
                          "spark.rapids.tpu.trace.enabled": True})
    q = _join_query(sess, salt=0.25)
    q.collect()
    with jax.profiler.trace(str(tmp_path), profiler_options=_no_python()):
        q.collect()
    ring = {(e["cat"], e["name"]) for e in sess._last_trace_events}
    on_profiler = {name for line in _host_lines(str(tmp_path))
                   for name, _, _, _ in line}
    assert ("d2h", "fused_collect.fetch") in ring or \
        ("d2h", "bulk_device_get") in ring
    assert ("query", "collect") in ring
    assert "srt:query:collect" in on_profiler
    # what the ring alone holds are the retroactive complete() sites
    assert {c for c, n in ring if f"srt:{c}:{n}" not in on_profiler} <= \
        {"sem_wait", "queue"}


# --------------------------------------------------------------------------
# the spans on the profiler's clock
# --------------------------------------------------------------------------

def _no_python():
    import jax.profiler
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return options


def _host_lines(trace_dir):
    """Per host thread, the ``srt:`` events as (name, start, end, args)."""
    import glob

    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(files) == 1, files
    lines = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            mine = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events
                    if e.name.startswith("srt:")]
            if mine:
                lines.append(mine)
    return lines


def _aggregate_query(sess):
    rng = np.random.default_rng(11)
    t = pa.table({"k": rng.integers(0, 16, 5000), "v": rng.random(5000)})
    return (sess.create_dataframe(t, num_partitions=2)
            .filter(F.col("v") > 0.25).groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv")))


@pytest.mark.parametrize("make", [_aggregate_query, _join_query],
                         ids=["aggregate", "join"])
def test_spans_nest_under_the_query_on_the_profilers_clock(make, tmp_path):
    import jax.profiler
    sess = srt.session(**{"spark.rapids.tpu.trace.enabled": True})
    q = make(sess)
    q.collect()                       # warm: the traced collect dispatches
    with jax.profiler.trace(str(tmp_path), profiler_options=_no_python()):
        q.collect()
    lines = _host_lines(str(tmp_path))
    roots = [(line, ev) for line in lines for ev in line
             if ev[0] == "srt:query:collect"]
    assert len(roots) == 1
    driver, (_, q0, q1, qargs) = roots[0]
    assert int(qargs["query"]) == sess._query_seq
    assert qargs["session"] == sess.session_id
    inside = {name for name, s, e, _ in driver if q0 <= s and e <= q1}
    for prefix in ("srt:plan:physical", "srt:task:", "srt:op:",
                   "srt:dispatch:", "srt:d2h:"):
        assert any(n.startswith(prefix) for n in inside), (prefix, inside)
    # every span of the collect lies inside the root, carries its query
    # id, and none outlives the span it started in
    for line in lines:
        stack = []
        for name, s, e, args in sorted(line, key=lambda ev: (ev[1], -ev[2])):
            assert q0 <= s and e <= q1, name
            assert int(args["query"]) == sess._query_seq, (name, args)
            while stack and stack[-1] <= s:
                stack.pop()
            assert not stack or e <= stack[-1], name
            stack.append(e)
    assert OT.get_tracer().snapshot() == []     # nothing went to the ring


def test_parquet_read_shows_scan_spans(tmp_path):
    import jax.profiler
    import pyarrow.parquet as pq
    rng = np.random.default_rng(5)
    path = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": rng.integers(0, 9, 4000),
                             "b": rng.random(4000)}), path,
                   row_group_size=1000)
    trace_dir = str(tmp_path / "trace")
    # device decode off: the host decodes, then uploads
    sess = srt.session(**{
        "spark.rapids.tpu.trace.enabled": True,
        "spark.rapids.sql.format.parquet.deviceDecode.enabled": False,
        "spark.rapids.sql.reader.chunked": True})
    df = sess.read.parquet(path).groupBy("a").agg(F.sum(F.col("b")))
    with jax.profiler.trace(trace_dir, profiler_options=_no_python()):
        df.collect()
    events = [ev for line in _host_lines(trace_dir) for ev in line]
    names = [ev[0] for ev in events]
    assert "srt:scan:footer" in names and "srt:scan:host_decode" in names
    decode = [ev for ev in events if ev[0] == "srt:scan:host_decode"]
    uploads = [ev for ev in events if ev[0] == "srt:h2d:arrow_to_device"]
    assert uploads and int(decode[0][3]["row_groups"]) >= 1
    # decode and upload are two spans: no upload lies inside a decode
    assert not any(d[1] <= u[1] < d[2] for d in decode for u in uploads)


def test_one_materialization_shows_its_four_phases(tmp_path):
    """The exchange's phases keep their names now that a map is one
    program: per map ``partition_ids`` (trace/dispatch of the map
    program: its one launch lies inside), ``split`` (the count read and
    the shrink) and ``write``; per reduce partition ``read``, with the
    concat program's launch inside."""
    import jax.profiler
    rng = np.random.default_rng(2)
    n = 24000
    t = pa.table({"k": rng.integers(0, 10**9, n), "v": rng.random(n)})
    sess = srt.session(**{"spark.rapids.tpu.trace.enabled": True,
                          "spark.sql.adaptive.enabled": False,
                          "spark.sql.shuffle.partitions": 4})
    q = (sess.create_dataframe(t, num_partitions=3).groupBy("k")
         .agg(F.count("*").alias("c")))
    q.collect()
    with jax.profiler.trace(str(tmp_path), profiler_options=_no_python()):
        q.collect()
    events = [ev for line in _host_lines(str(tmp_path)) for ev in line]

    def named(name):
        return [ev for ev in events if ev[0] == name]

    (mat,) = named("srt:shuffle:exchange.materialize")
    pids, split, write, read = (
        named("srt:shuffle:exchange." + phase)
        for phase in ("partition_ids", "split", "write", "read"))
    assert len(pids) == len(split) == len(write) == 3     # one per map
    assert len(read) == 4                      # one per reduce partition
    for ev in pids + split + write + read:
        assert mat[1] <= ev[1] and ev[2] <= mat[2]
        assert "declined" not in ev[3]
    launches = [ev for ev in events if ev[0].startswith("srt:dispatch:")]

    def inside(spans, program):
        return [sum(s[1] <= d[1] and d[2] <= s[2] and program in d[0]
                    for d in launches) for s in spans]

    assert inside(pids, "ShuffleExchangeExec_map_") == [1, 1, 1]
    assert inside(split, "ShuffleExchangeExec_shrink_") == [1, 1, 1]
    assert inside(read, "ColumnarBatch_concat_") == [1, 1, 1, 1]
    assert inside([mat], "ShuffleExchangeExec_split_") == [0]


# --------------------------------------------------------------------------
# program names and the retrace counters
# --------------------------------------------------------------------------

_NAMES_SCRIPT = """
import numpy as np, pyarrow as pa
import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.physical import kernel_cache as KC
sess = srt.session()
t = pa.table({"k": np.arange(600) % 7, "v": np.linspace(0, 1, 600),
              "w": np.linspace(1, 2, 600)})
df = sess.create_dataframe(t, num_partitions=2)
df.filter(F.col("v") > 0.25).groupBy("k").agg(F.sum(F.col("v"))).collect()
print("SUM_V", *sorted("jit_" + n for n in KC.retraces_by_name()
                       if n.startswith("srt_")))
KC.clear_cache()
df.filter(F.col("v") > 0.25).groupBy("k").agg(F.sum(F.col("w"))).collect()
print("SUM_W", *sorted("jit_" + n for n in KC.retraces_by_name()
                       if n.startswith("srt_")))
"""


def test_program_names_are_the_same_in_every_process():
    procs = [subprocess.Popen(
        [sys.executable, "-c", _NAMES_SCRIPT], cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu"})
        for seed in ("1", "2")]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err[-2000:]
        outs.append({line.split()[0]: line.split()[1:]
                     for line in out.splitlines()
                     if line.startswith(("SUM_V", "SUM_W"))})
    assert outs[0] == outs[1]
    sum_v, sum_w = outs[0]["SUM_V"], outs[0]["SUM_W"]
    assert sum_v and all(n.startswith("jit_srt_") for n in sum_v + sum_w)
    # the partial aggregate over another expression is another program
    agg_v = {n for n in sum_v if "HashAggregate" in n}
    agg_w = {n for n in sum_w if "HashAggregate" in n}
    assert agg_v and agg_w and agg_v != agg_w


def test_program_name_is_the_keys_and_tells_keys_apart():
    from spark_rapids_tpu.sql.physical import kernel_cache as KC

    def impl(b):
        return b
    a = KC.program_name(("HashAggregateExec", "grp", ("sum", 1)), impl)
    b = KC.program_name(("HashAggregateExec", "grp", ("sum", 2)), impl)
    c = KC.program_name(("ProjectExec", (("col", 0),), ("x",)), impl)
    assert a != b and a.startswith("srt_HashAggregateExec_grp_")
    assert c.startswith("srt_ProjectExec_impl_")
    assert KC.exec_of_program(a) == "HashAggregateExec"
    # sets and dicts render in one order whatever the hash seed
    assert KC._render_key((frozenset({"b", "a"}), {"y": 1, "x": 2})) == \
        "({'a','b'},{'x':2,'y':1})"
    fn = KC.cached_jit(("ProjectExec", "named-test", 7), impl)
    assert fn._label == KC.program_name(("ProjectExec", "named-test", 7),
                                        impl)


def test_retrace_counters_are_zero_on_a_warm_collect():
    from spark_rapids_tpu.sql.physical.kernel_cache import (
        cache_stats, retraces_by_name)
    sess = srt.session()
    q = _join_query(sess, salt=0.375)
    s0 = cache_stats()
    assert {"retrace_ms", "retraces", "unkeyed_jits"} <= set(s0)
    q.collect()
    s1 = cache_stats()
    assert s1["retraces"] > s0["retraces"]
    assert s1["retrace_ms"] > s0["retrace_ms"]
    assert any(n.startswith("srt_") and row["traces"] >= 1
               for n, row in retraces_by_name().items())
    q.collect()
    q.collect()
    s2 = cache_stats()
    q.collect()
    s3 = cache_stats()
    assert s3["retraces"] == s2["retraces"]
    assert s3["retrace_ms"] == s2["retrace_ms"]


# --------------------------------------------------------------------------
# the host's work inside an exec (PR 37): eager blocks, row-count syncs and
# the parquet decode's host phases
# --------------------------------------------------------------------------

def _inside(child, parent):
    return child["tid"] == parent["tid"] and parent["ts"] <= child["ts"] \
        and child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1e-3


def _topn_query(sess, n=5000):
    rng = np.random.default_rng(3)
    df = sess.create_dataframe(pa.table({"k": rng.integers(0, 99, n),
                                        "v": rng.random(n)}),
                               num_partitions=4)
    return df.orderBy(F.col("v").desc()).limit(7)


def test_top_n_runs_its_eager_launches_under_its_op_span():
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    out = _topn_query(sess).collect()
    assert out.num_rows == 7
    events = sess._last_trace_events
    ops = [e for e in events if e["cat"] == "op"
           and e["name"] == "TpuTakeOrderedAndProject"]
    eager = [e for e in events if e["cat"] == "eager"]
    names = {e["name"] for e in eager}
    assert {"top_n.merge", "batch.sliced"} <= names, names
    mine = [e for e in eager if e["name"] in ("top_n.merge", "batch.sliced")
            and e["exec"] == "TpuTakeOrderedAndProject"]
    assert len(mine) >= 5          # a cut of each of 4 partitions, the merge
    for e in mine:
        assert e["exec"] == "TpuTakeOrderedAndProject"
        assert e["args"]["exec"] == "TpuTakeOrderedAndProject"
        assert any(_inside(e, op) for op in ops), e
    # the merge's own cut reads its row count: a sync child
    merge = [e for e in mine if e["name"] == "top_n.merge"]
    syncs = [e for e in events if e["name"] == "batch.num_rows"]
    assert any(_inside(s, m) for s in syncs for m in merge)


def test_a_row_count_readback_is_one_span_per_memo_miss(tracing_on):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import batch as CB
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu import types as T
    col = DeviceColumn(T.INT, jnp.arange(8, dtype=jnp.int32),
                       jnp.ones(8, dtype=bool))
    before = CB.SYNC_STATS["readbacks"]
    b = CB.ColumnarBatch(("a",), (col,), jnp.asarray(5, dtype=jnp.int32))
    assert b.num_rows_int == 5 and b.num_rows_int == 5     # miss, hit
    known = CB.ColumnarBatch.make(("a",), (col,), 5)        # host-known
    assert known.num_rows_int == 5
    reads = [e for e in tracing_on.snapshot()
             if (e["cat"], e["name"]) == ("sync", "batch.num_rows")]
    assert len(reads) == 1
    assert CB.SYNC_STATS["readbacks"] == before + 1


def test_the_readback_count_loses_no_miss_across_threads():
    """Pool and prefetch threads miss the memo concurrently: every miss
    is counted (a lost read-modify-write would show as fewer)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import batch as CB
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu import types as T
    col = DeviceColumn(T.INT, jnp.arange(8, dtype=jnp.int32),
                       jnp.ones(8, dtype=bool))
    rows = jnp.asarray(3, dtype=jnp.int32)
    workers, each = 16, 200
    batches = [[CB.ColumnarBatch(("a",), (col,), rows)
                for _ in range(each)] for _ in range(workers)]
    before = CB.SYNC_STATS["readbacks"]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(
            target=lambda bs: [b.num_rows_int for b in bs], args=(bs,))
            for bs in batches]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    assert CB.SYNC_STATS["readbacks"] - before == workers * each


def test_sync_readbacks_counts_the_querys_memo_misses():
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    _topn_query(sess).collect()
    m = sess.last_query_metrics
    reads = [e for e in sess._last_trace_events
             if (e["cat"], e["name"]) == ("sync", "batch.num_rows")]
    assert reads and m["syncReadbacks"] == len(reads)


def _parquet(tmp_path, compression):
    import pyarrow.parquet as pq
    rng = np.random.default_rng(11)
    path = str(tmp_path / f"t_{compression}.parquet")
    pq.write_table(pa.table({"a": rng.integers(0, 9, 4000),
                             "b": rng.random(4000),
                             "c": rng.integers(0, 1 << 40, 4000)}),
                   path, row_group_size=2000, compression=compression)
    return path


@pytest.mark.parametrize("compression", ["NONE", "SNAPPY"])
def test_device_decode_nests_its_host_phases(tmp_path, compression):
    import pyarrow.parquet as pq
    from spark_rapids_tpu.io_ import device_parquet as DP
    path = _parquet(tmp_path, compression)
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    df = sess.read.parquet(path).groupBy("a").agg(F.sum(F.col("b")))
    df.collect()
    events = sess._last_trace_events
    decode = [e for e in events if (e["cat"], e["name"])
              == ("scan", "device_decode")]
    reads = [e for e in events if (e["cat"], e["name"])
             == ("scan", "chunk_read")]
    pages = [e for e in events if (e["cat"], e["name"]) == ("scan", "pages")]
    cols = [e for e in events if (e["cat"], e["name"])
            == ("eager", "parquet.decode_column")]
    assert decode and reads and pages and cols
    for e in reads + pages + cols:
        assert any(_inside(e, d) for d in decode), e
    md = pq.ParquetFile(path).metadata
    rgs = list(range(md.num_row_groups))
    # two columns read (a, b), one chunk a row group each
    assert len(reads) == 2 * len(rgs) and len(pages) == 2
    compressed = sum(md.row_group(rg).column(li).total_compressed_size
                     for rg in rgs for li in (0, 1))
    assert sum(e["args"]["bytes"] for e in reads) == compressed
    if compression == "NONE":
        assert compressed == DP.chunk_bytes(md, rgs, ["a", "b"])
    assert sum(e["args"]["bytes"] for e in pages) == compressed
    assert all(e["args"]["pages"] >= len(rgs) for e in pages)
    m = sess.last_query_metrics
    assert m["parquetChunkBytesRead"] == compressed
    assert m["parquetPagesDecoded"] == sum(e["args"]["pages"]
                                           for e in pages)
    assert m["parquetBytesDecompressed"] == sum(e["args"]["out_bytes"]
                                                for e in pages)
    # an in-memory query carries no parquet counter
    sess.create_dataframe(pa.table({"k": [1, 2]})).collect()
    assert "parquetPagesDecoded" not in sess.last_query_metrics


def test_every_new_site_is_the_null_span_with_both_sinks_off(
        sinks_off, tmp_path, monkeypatch):
    got = []
    span, eager = OT.span, OT.eager

    def spy_span(cat, name, **args):
        out = span(cat, name, **args)
        got.append((cat, name, out))
        return out

    def spy_eager(site, **args):
        out = eager(site, **args)
        got.append(("eager", site, out))
        return out
    monkeypatch.setattr(OT, "span", spy_span)
    monkeypatch.setattr(OT, "eager", spy_eager)
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": False})
    _topn_query(sess).collect()
    sess.read.parquet(_parquet(tmp_path, "SNAPPY")).groupBy("a").agg(
        F.sum(F.col("b"))).collect()
    seen = {(c, n) for c, n, _ in got}
    assert {("eager", "top_n.merge"),
            ("eager", "batch.sliced"), ("eager", "batch.concat"),
            ("eager", "parquet.decode_column"), ("scan", "chunk_read"),
            ("scan", "pages"), ("sync", "batch.num_rows")} <= seen, seen
    assert [(c, n) for c, n, out in got if out is not OT._NULL_SPAN] == []
    assert sinks_off.snapshot() == []
