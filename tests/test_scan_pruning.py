"""A file scan the planner narrowed (ISSUE 36, ``io_/exec.py``): it reads,
decodes and uploads the columns the query references and no other, on every
path ``FileScanExec`` has — the device decoders, the per-column and the
whole-run host fallbacks, the three reader types, chunked reads, parquet,
ORC, CSV, JSON and Avro — and answers what the whole scan answers.  The
decline rule for a wide string is untouched: a query that reads one declines
as before, one that does not never meets it."""

import contextlib
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.columnar.convert import device_to_arrow
from spark_rapids_tpu.io_.device_parquet import chunk_bytes, decode_file
from spark_rapids_tpu.io_.exec import FileScanExec
from spark_rapids_tpu.sql import column_pruning as CP
from spark_rapids_tpu.sql import functions as F

DEVICE_PARQUET = "spark.rapids.sql.format.parquet.deviceDecode.enabled"
RAGGED = "spark.rapids.sql.strings.raggedSplitBytes"
N = 6000


@contextlib.contextmanager
def session_of(**conf):
    """A session of this test's own, and the one before put back."""
    from spark_rapids_tpu.sql.session import TpuSession
    before = TpuSession._active
    try:
        yield srt.session(**conf)
    finally:
        TpuSession._active = before


@contextlib.contextmanager
def whole_scans():
    """The rule as the parent had it: a file scan reads every column."""
    rule = CP._Pruner._ScanRelation
    CP._Pruner._ScanRelation = CP._Pruner._unknown
    try:
        yield
    finally:
        CP._Pruner._ScanRelation = rule


def table(n=N, seed=0, wide=True):
    """Keys, measures, a short string, and (``wide``) a string column whose
    padded matrix passes a 1 MiB ``raggedSplitBytes``."""
    rng = np.random.default_rng(seed)
    cols = {
        "k": pa.array(rng.integers(0, 9, n), pa.int64()),
        "a": pa.array(rng.integers(0, 100, n).astype(np.int32)),
        "b": pa.array(np.round(rng.random(n) * 1e4, 2)),
        "s": pa.array(["s%d" % (i % 5) for i in range(n)]),
        "d": pa.array(rng.integers(8000, 9000, n).astype(np.int32),
                      pa.date32()),
        "c": pa.array(rng.integers(0, 3, n), pa.int64()),
    }
    if wide:
        cols["wide"] = pa.array(
            [("w" * 3000 if i % 1000 == 0 else "w%d" % (i % 11))
             for i in range(n)])
    return pa.table(cols)


def scan_metrics(sess):
    m = sess.last_query_metrics
    return {k: int(v) for k, v in m.items() if v and (
        k.startswith("scanColumns") or k.startswith("parquet")
        and ("Files" in k or "Columns" in k))}


def the_query(df):
    """Reads k, a, b and s of the table's six or seven columns."""
    return (df.filter(F.col("a") < 70).groupBy("k", "s")
            .agg(F.sum("b").alias("sb"), F.count("*").alias("n"))
            .orderBy("k", "s"))


def same(got, want):
    """Equal but for the last bits of a float sum (a batch of another shape
    adds in another order)."""
    assert got.schema == want.schema
    for name in got.column_names:
        a, b = got.column(name).to_pylist(), want.column(name).to_pylist()
        if pa.types.is_floating(got.schema.field(name).type):
            assert a == pytest.approx(b, rel=1e-12), name
        else:
            assert a == b, name


def _benchmark_reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "metric_" + name, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# --- the decline rule: an unread wide string is never met -----------------------

@pytest.fixture(scope="module")
def wide_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("wide") / "wide.parquet")
    pq.write_table(table(), path, row_group_size=2000)
    return path


def test_an_unread_wide_string_no_longer_declines_the_file(wide_file):
    with session_of(**{RAGGED: 1 << 20}) as s:
        got = the_query(s.read.parquet(wide_file)).collect()
        assert scan_metrics(s) == {
            "scanColumnsRead": 4, "scanColumnsPruned": 3,
            "parquetDeviceDecodedColumns": 4, "parquetDecodeFilesEngaged": 1}
        # a scan that declined nothing says so: the benchmark's reader of
        # ``parquet_files_declined`` takes the key wherever a file engaged
        m = dict(s.last_query_metrics)
        assert m["parquetDecodeFilesDeclined"] == 0
        assert m["parquetDecodeBytesDeclined"] == 0
        assert not any(k.startswith(("orcDecode", "csvDecode")) for k in m)
        assert _benchmark_reader("parquet_files_declined").read(
            {"query_metrics": {"q": m}}) == 0.0
        s.conf.set(DEVICE_PARQUET, "false")
        host = the_query(s.read.parquet(wide_file)).collect()
        assert scan_metrics(s) == {"scanColumnsRead": 4,
                                   "scanColumnsPruned": 3}
        same(got, host)
        # the parent: the whole file, declined at the wide column
        s.conf.set(DEVICE_PARQUET, "true")
        with whole_scans():
            whole = the_query(s.read.parquet(wide_file)).collect()
            m = scan_metrics(s)
        assert m["parquetDecodeFilesDeclined"] == 1
        assert "parquetDecodeFilesEngaged" not in m
        assert m["scanColumnsRead"] == 7 and "scanColumnsPruned" not in m
        same(got, whole)


def test_a_query_that_reads_the_wide_string_declines_as_before(wide_file):
    with session_of(**{RAGGED: 1 << 20}) as s:
        df = s.read.parquet(wide_file)
        q = (df.filter(F.col("a") < 70).groupBy("k")
             .agg(F.max(F.length("wide")).alias("longest"),
                  F.sum("b").alias("sb")).orderBy("k"))
        got = q.collect()
        m = scan_metrics(s)
        assert m["parquetDecodeFilesDeclined"] == 1
        assert "parquetDecodeFilesEngaged" not in m
        assert (m["scanColumnsRead"], m["scanColumnsPruned"]) == (4, 3)
        assert s.last_query_metrics.get("raggedStringSplits", 0) >= 1
        with whole_scans():
            same(got, q.collect())
            assert scan_metrics(s)["parquetDecodeFilesDeclined"] == 1
        assert max(got["longest"].to_pylist()) == 3000


def test_a_declined_run_is_read_again_narrow(wide_file):
    """Chunked: the first run declines at the wide string, the runs after it
    as 'prior-decline'; pyarrow reads the wanted columns of each, and the
    declined bytes are those columns' chunks."""
    md = pq.ParquetFile(wide_file).metadata
    with session_of(**{RAGGED: 1 << 20,
                       "spark.rapids.sql.reader.chunked": True,
                       "spark.rapids.sql.reader.chunked.targetRows": 2000
                       }) as s:
        df = s.read.parquet(wide_file)
        got = df.agg(F.max(F.length("wide")).alias("w"),
                     F.sum("a").alias("a")).collect()
        m = s.last_query_metrics
        assert m["parquetDecodeFilesDeclined"] == 3
        assert m["parquetDecodeBytesDeclined"] == chunk_bytes(
            md, [0, 1, 2], ["a", "wide"])
        assert got["w"].to_pylist() == [3000]
        assert got["a"].to_pylist() == [int(np.sum(table()["a"].to_numpy()))]


# --- one column falls back alone --------------------------------------------------

def test_a_mixed_dictionary_and_plain_column_falls_back_alone(tmp_path):
    """A chunk whose dictionary page overflows ends in PLAIN pages: that
    column alone takes pyarrow and an upload, under the host path's spans."""
    path = str(tmp_path / "mixed.parquet")
    t = table(n=40000, wide=False)
    pq.write_table(t, path, row_group_size=20000,
                   dictionary_pagesize_limit=4096, data_page_size=8192)
    md = pq.ParquetFile(path).metadata
    with session_of(**{"spark.rapids.tpu.trace.enabled": True,
                       "spark.rapids.tpu.trace.sink": "memory"}) as s:
        q = s.read.parquet(path).groupBy("s").agg(
            F.sum("b").alias("sb"), F.sum("a").alias("sa")).orderBy("s")
        got = q.collect()
        assert scan_metrics(s) == {
            "scanColumnsRead": 3, "scanColumnsPruned": 3,
            "parquetDeviceDecodedColumns": 2, "parquetHostDecodedColumns": 1,
            "parquetDecodeFilesEngaged": 1}
        events = [e for e in s._last_trace_events
                  if e["cat"] in ("scan", "h2d")]
        (device,) = [e for e in events if e["name"] == "device_decode"]
        (host,) = [e for e in events if e["name"] == "host_decode"]
        (upload,) = [e for e in events if e["name"] == "arrow_to_device"]
        assert device["args"]["bytes"] == chunk_bytes(md, [0, 1],
                                                      ["a", "b", "s"])
        assert host["args"]["declined"] == "per-column"
        assert host["args"]["columns"] == 1
        assert host["args"]["bytes"] == chunk_bytes(md, [0, 1], ["b"])
        # the one column, with the validity bitmap pyarrow reads for it
        assert 0 <= upload["args"]["bytes"] - t.column("b").nbytes <= 40000 / 8
        assert s.last_query_metrics["parquetDecodeBytesEngaged"] == \
            device["args"]["bytes"]
        s.conf.set(DEVICE_PARQUET, "false")
        same(got, q.collect())


# --- decode_file and chunk_bytes themselves ------------------------------------------

def test_decode_file_reads_the_named_columns_in_the_order_given(tmp_path):
    path = str(tmp_path / "t.parquet")
    t = table(wide=False)
    pq.write_table(t, path, row_group_size=2500)

    class Reads:
        """The file, with every read's (offset, length) kept."""
        def __init__(self, f):
            self.f, self.got, self.at = f, [], 0

        def seek(self, pos):
            self.at = pos
            return self.f.seek(pos)

        def read(self, n):
            self.got.append((self.at, n))
            return self.f.read(n)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            self.f.close()

    import builtins

    from spark_rapids_tpu.io_ import device_parquet as DP
    opened = []

    def spy(p, mode="r", *a, **kw):
        f = builtins.open(p, mode, *a, **kw)
        if p == path and mode == "rb":
            opened.append(Reads(f))
            return opened[-1]
        return f

    DP.open = spy       # the module's own name for the builtin
    try:
        batch = decode_file(path, columns=["s", "a"])
    finally:
        del DP.open
    assert batch.names == ("s", "a")
    got = device_to_arrow(batch)
    assert got.column("s").to_pylist() == t.column("s").to_pylist()
    assert got.column("a").to_pylist() == t.column("a").to_pylist()
    # no byte of another column's chunks was read
    md = pq.ParquetFile(path).metadata
    (reads,) = opened
    want = set()
    for rg in range(md.num_row_groups):
        for li in (1, 3):               # a, s
            cc = md.row_group(rg).column(li)
            start = min(o for o in (cc.dictionary_page_offset,
                                    cc.data_page_offset) if o)
            want.add((start, cc.total_compressed_size))
    assert set(reads.got) == want
    assert decode_file(path, columns=["a", "no_such"]) is None
    whole = decode_file(path)
    assert whole.names == tuple(t.column_names)


def test_chunk_bytes_counts_the_wanted_columns_chunks(tmp_path):
    path = str(tmp_path / "t.parquet")
    t = table(wide=False).append_column(
        "nest", pa.array([{"x": i, "y": float(i)} for i in range(N)]))
    pq.write_table(t, path, row_group_size=2500)
    md = pq.ParquetFile(path).metadata
    groups = list(range(md.num_row_groups))
    whole = sum(md.row_group(g).total_byte_size for g in groups)
    assert chunk_bytes(md, groups) == whole
    assert chunk_bytes(md, groups, t.column_names) == whole
    one = chunk_bytes(md, groups, ["b"])
    nest = chunk_bytes(md, groups, ["nest"])        # both of its leaves
    assert 0 < one < whole and nest > one
    assert chunk_bytes(md, groups, ["b", "nest"]) == one + nest
    assert chunk_bytes(md, groups[:1], ["b"]) < one
    assert chunk_bytes(md, [], ["b"]) == 0


# --- every reader, narrowed, returns what the whole scan returns ----------------

@pytest.fixture(scope="module")
def three_files(tmp_path_factory):
    d = tmp_path_factory.mktemp("three")
    for i in range(3):
        pq.write_table(table(n=3000, seed=i, wide=False),
                       str(d / f"part-{i}.parquet"), row_group_size=1000)
    return str(d)


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("chunked", [False, True], ids=["whole", "chunked"])
@pytest.mark.parametrize("reader", ["PERFILE", "MULTITHREADED", "COALESCING"])
def test_the_reader_types_narrowed_answer_as_the_whole_scan(
        three_files, reader, chunked, device):
    conf = {"spark.rapids.sql.format.parquet.reader.type": reader,
            "spark.rapids.sql.reader.chunked": chunked,
            "spark.rapids.sql.reader.chunked.targetRows": 1000,
            DEVICE_PARQUET: device}
    with session_of(**conf) as s:
        q = the_query(s.read.parquet(three_files))
        got = q.collect()
        m = scan_metrics(s)
        assert (m["scanColumnsRead"], m["scanColumnsPruned"]) == (4, 2)
        if device:
            assert m["parquetDeviceDecodedColumns"] == 4 * (
                9 if chunked and reader != "COALESCING" else 3)
        with whole_scans():
            want = q.collect()
            assert scan_metrics(s)["scanColumnsRead"] == 6
        same(got, want)
        assert got.num_rows == 45
        # a pushed conjunct prunes row groups under the narrowed scan too
        df = s.read.parquet(three_files)
        none = df.filter(df.a > 1000).select("b").collect()
        assert none.num_rows == 0 and none.column_names == ["b"]
        assert s.last_query_metrics.get("rowGroupsPruned") == 9


def _write(fmt, t, path):
    if fmt == "orc":
        import pyarrow.orc as orc
        orc.write_table(t, path, stripe_size=64 * 1024)
    elif fmt == "csv":
        t.to_pandas().to_csv(path, index=False)     # no quote anywhere
    elif fmt == "json":
        t.to_pandas().to_json(path, orient="records", lines=True)
    else:
        from spark_rapids_tpu.io_.avro_reader import write_avro
        write_avro(t, path)


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
@pytest.mark.parametrize("reader", ["PERFILE", "COALESCING"])
@pytest.mark.parametrize("fmt", ["orc", "csv", "json", "avro"])
def test_the_other_formats_narrowed_answer_as_the_whole_scan(
        tmp_path, fmt, reader, device):
    t = table(n=3000, wide=False).drop_columns(["d"])
    for i in range(2):
        _write(fmt, t.slice(1500 * i, 1500), str(tmp_path / f"p{i}.{fmt}"))
    conf = {"spark.rapids.sql.format.parquet.reader.type": reader,
            "spark.rapids.sql.reader.chunked": fmt == "orc",
            "spark.rapids.sql.reader.chunked.targetRows": 500}
    for key in ("orc", "csv", "json"):
        conf[f"spark.rapids.sql.format.{key}.deviceDecode.enabled"] = device
    with session_of(**conf) as s:
        q = the_query(getattr(s.read, fmt)(str(tmp_path)))
        (scan,) = [n for n in _walk(s.physical_plan(q))
                   if isinstance(n, FileScanExec)]
        assert [a.name for a in scan.output] == ["k", "a", "b", "s"]
        got = q.collect()
        m = s.last_query_metrics
        assert (m["scanColumnsRead"], m["scanColumnsPruned"]) == (4, 1)
        engaged = m.get(f"{fmt}DecodeFilesEngaged", 0)
        assert (engaged > 0) == (device and fmt != "avro"
                                 and reader == "PERFILE"), m
        with whole_scans():
            want = q.collect()
            assert s.last_query_metrics["scanColumnsRead"] == 5
        same(got, want)
        assert got.num_rows == 45
        n = s.read.format(fmt).load(str(tmp_path)).agg(
            F.count("*").alias("n")).collect()
        assert n["n"].to_pylist() == [3000]
        assert s.last_query_metrics["scanColumnsRead"] == 1


def _walk(node):
    yield node
    for c in node.children:
        yield from _walk(c)


def test_the_counters_count_a_file_scan_once_a_collect(three_files):
    with session_of() as s:
        q = the_query(s.read.parquet(three_files))     # three partitions
        seen = []
        for _ in range(2):
            q.collect()
            seen.append(scan_metrics(s))
        assert seen[0] == seen[1]
        assert (seen[0]["scanColumnsRead"],
                seen[0]["scanColumnsPruned"]) == (4, 2)
        text = s.explain(q)
        assert "columns=[k, a, b, s] of 6" in text
        s.read.parquet(three_files).collect()
        assert scan_metrics(s)["scanColumnsRead"] == 6
        assert "columns=" not in s.explain(s.read.parquet(three_files))


def test_a_narrowed_scan_reads_through_the_file_cache(three_files, tmp_path):
    """The local file cache hands the scan a copy's path: the narrowed read
    goes through it like the whole one."""
    from spark_rapids_tpu.io_ import filecache as FC
    FC.FileCache.reset()
    try:
        with session_of(**{
                "spark.rapids.filecache.enabled": True,
                "spark.rapids.filecache.path": str(tmp_path / "cache")}) as s:
            before = dict(FC.STATS)
            q = the_query(s.read.parquet(three_files))
            got = q.collect()
            assert scan_metrics(s)["scanColumnsRead"] == 4
            assert FC.STATS["misses"] - before["misses"] >= 3
            with whole_scans():
                same(got, q.collect())
            assert FC.STATS["hits"] - before["hits"] >= 3
    finally:
        FC.FileCache.reset()
