"""Iceberg-analog table format (reference GPU Iceberg read path,
``sql-plugin/src/main/java/com/nvidia/spark/rapids/iceberg/``): snapshot
reads, time travel, partition-transform + column-bound pruning, field-id
schema evolution, position deletes, avro manifests."""

import datetime
import os
import time

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.iceberg import IcebergTable, parse_transform
from spark_rapids_tpu.iceberg.metadata import (latest_metadata_version,
                                               read_table_metadata)


@pytest.fixture()
def sess():
    return srt.session()


SCHEMA = T.StructType([
    T.StructField("id", T.LONG, False),
    T.StructField("v", T.DOUBLE, True),
    T.StructField("tag", T.STRING, True),
])


def make_batch(lo, hi, tag="a"):
    n = hi - lo
    return pa.table({
        "id": pa.array(range(lo, hi), type=pa.int64()),
        "v": pa.array([float(i) * 0.5 for i in range(lo, hi)]),
        "tag": [tag] * n,
    })


def test_create_append_read(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 50))
    t.append(make_batch(50, 100))
    df = t.to_df().orderBy("id").collect()
    assert df["id"].to_pylist() == list(range(100))
    # metadata versions: create + 2 appends
    assert latest_metadata_version(str(tmp_path / "t")) == 2


def test_snapshot_time_travel(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 10))
    first = t.meta.current_snapshot_id
    t.append(make_batch(10, 20))
    cur = t.to_df().collect()
    old = t.to_df(snapshot_id=first).collect()
    assert cur.num_rows == 20
    assert old.num_rows == 10
    hist = t.history()
    assert [h["operation"] for h in hist] == ["append", "append"]
    # timestamp travel: as-of the first snapshot's commit time
    ts0 = t.meta.snapshots[0].timestamp_ms
    asof = t.to_df(as_of_timestamp_ms=ts0).collect()
    assert asof.num_rows == 10


def test_reader_format_integration(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 30))
    df = sess.read.format("iceberg").load(str(tmp_path / "t"))
    assert df.count() == 30
    first = t.meta.current_snapshot_id
    t.append(make_batch(30, 60))
    df_old = (sess.read.format("iceberg").option("snapshot-id", first)
              .load(str(tmp_path / "t")))
    assert df_old.count() == 30


def test_partition_pruning_identity(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA,
                            partition_by=[("tag", "identity")])
    t.append(make_batch(0, 10, "a"))
    t.append(make_batch(10, 20, "b"))
    t.append(make_batch(20, 30, "c"))
    assert len(t.planned_files()) == 3
    pruned = t.planned_files([("tag", "=", "b")])
    assert len(pruned) == 1
    rows = t.to_df(filters=[("tag", "=", "b")]).collect()
    assert sorted(rows["id"].to_pylist()) == list(range(10, 20))
    # != prunes the matching identity partition
    assert len(t.planned_files([("tag", "!=", "b")])) == 2


def test_partition_pruning_bucket(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA,
                            partition_by=[("id", "bucket[4]")])
    t.append(make_batch(0, 200))
    files = t.planned_files()
    assert len(files) == 4  # one file per bucket
    tr = parse_transform("bucket[4]")
    want_bucket = tr.apply(17)
    pruned = t.planned_files([("id", "=", 17)])
    assert len(pruned) == 1
    got = t.to_df(filters=[("id", "=", 17)]).collect()
    assert 17 in got["id"].to_pylist()
    # every row in the surviving file hashes to the same bucket
    ids = got["id"].to_pylist()
    assert all(tr.apply(i) == want_bucket for i in ids)


def test_min_max_file_skipping(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 100))
    t.append(make_batch(100, 200))
    t.append(make_batch(200, 300))
    assert len(t.planned_files([("id", ">=", 250)])) == 1
    assert len(t.planned_files([("id", "<", 100)])) == 1
    assert len(t.planned_files([("id", "in", [50, 150])])) == 2
    got = t.to_df(filters=[("id", ">=", 250)]).collect()
    assert got.num_rows == 100  # file-level pruning only; residual rows stay


def test_time_transforms(sess, tmp_path):
    sch = T.StructType([T.StructField("d", T.DATE, True),
                        T.StructField("x", T.LONG, True)])
    t = IcebergTable.create(sess, str(tmp_path / "t"), sch,
                            partition_by=[("d", "month")])
    jan = pa.table({"d": pa.array([datetime.date(2024, 1, i)
                                   for i in range(1, 11)]),
                    "x": pa.array(range(10), type=pa.int64())})
    mar = pa.table({"d": pa.array([datetime.date(2024, 3, i)
                                   for i in range(1, 11)]),
                    "x": pa.array(range(10, 20), type=pa.int64())})
    t.append(jan)
    t.append(mar)
    assert len(t.planned_files()) == 2
    only_jan = t.planned_files(
        [("d", "=", datetime.date(2024, 1, 5))])
    assert len(only_jan) == 1
    lt_feb = t.planned_files(
        [("d", "<", datetime.date(2024, 2, 1))])
    assert len(lt_feb) == 1


def test_schema_evolution_rename_add_drop(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 10))
    # rename: old files resolve by field id
    t.rename_column("v", "value")
    df = t.to_df().orderBy("id").collect()
    assert "value" in df.column_names
    assert df["value"].to_pylist()[:3] == [0.0, 0.5, 1.0]
    # add: old files null-fill
    t.add_column("extra", T.LONG)
    df = t.to_df().collect()
    assert df["extra"].null_count == 10
    # new writes carry the new schema
    t.append(pa.table({
        "id": pa.array([100, 101], type=pa.int64()),
        "value": pa.array([1.0, 2.0]),
        "tag": ["z", "z"],
        "extra": pa.array([7, 8], type=pa.int64())}))
    df = t.to_df().orderBy("id").collect()
    assert df["extra"].to_pylist()[-2:] == [7, 8]
    # drop
    t.drop_column("tag")
    df = t.to_df().collect()
    assert "tag" not in df.column_names
    # old snapshots still read with their own schema (time travel)
    first_snap = t.meta.snapshots[0].snapshot_id
    old = t.to_df(snapshot_id=first_snap).collect()
    assert "v" in old.column_names and "tag" in old.column_names


def test_position_deletes(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 100))
    n = t.delete_where(("id", "<", 10))
    assert n == 10
    df = t.to_df().orderBy("id").collect()
    assert df.num_rows == 90
    assert df["id"].to_pylist()[0] == 10
    # delete is a snapshot: time travel sees the old rows
    pre_delete = t.meta.snapshots[0].snapshot_id
    old = t.to_df(snapshot_id=pre_delete).collect()
    assert old.num_rows == 100
    # second delete composes with the first
    n2 = t.delete_where(("id", ">=", 95))
    assert n2 == 5
    assert t.to_df().count() == 85
    # deleting already-deleted rows is a no-op
    assert t.delete_where(("id", "<", 10)) == 0


def test_expire_snapshots(sess, tmp_path):
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 10))
    t.append(make_batch(10, 20))
    t.append(make_batch(20, 30))
    assert len(t.meta.snapshots) == 3
    removed = t.expire_snapshots(older_than_ms=int(time.time() * 1000) + 10)
    assert removed == 2  # all but current
    assert len(t.meta.snapshots) == 1
    assert t.to_df().count() == 30
    # reload from disk and confirm persisted
    t2 = IcebergTable.for_path(sess, str(tmp_path / "t"))
    assert len(t2.meta.snapshots) == 1


def test_engine_query_over_iceberg(sess, tmp_path):
    """End-to-end: engine aggregation over a pruned iceberg scan."""
    from spark_rapids_tpu.sql import functions as F
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA,
                            partition_by=[("tag", "identity")])
    t.append(make_batch(0, 50, "a"))
    t.append(make_batch(50, 100, "b"))
    df = t.to_df(filters=[("tag", "=", "b")])
    out = (df.groupBy("tag")
           .agg(F.sum(F.col("id")).alias("s"),
                F.count("*").alias("c")).collect())
    assert out.num_rows == 1
    assert out["s"].to_pylist() == [sum(range(50, 100))]
    assert out["c"].to_pylist() == [50]


def test_concurrent_commit_detected(sess, tmp_path):
    """A writer holding stale metadata must get ConcurrentCommitException,
    not silently drop the other writer's snapshot."""
    from spark_rapids_tpu.iceberg import ConcurrentCommitException
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 10))
    a = IcebergTable.for_path(sess, str(tmp_path / "t"))
    b = IcebergTable.for_path(sess, str(tmp_path / "t"))
    a.append(make_batch(10, 20))
    with pytest.raises(ConcurrentCommitException):
        b.append(make_batch(20, 30))
    # loser refreshes and retries; winner's rows survive
    b.refresh().append(make_batch(20, 30))
    assert IcebergTable.for_path(sess, str(tmp_path / "t")).to_df().count() == 30


def test_identity_partition_on_date(sess, tmp_path):
    sch = T.StructType([T.StructField("d", T.DATE, True),
                        T.StructField("x", T.LONG, True)])
    t = IcebergTable.create(sess, str(tmp_path / "t"), sch,
                            partition_by=[("d", "identity")])
    d1, d2 = datetime.date(2024, 1, 1), datetime.date(2024, 2, 1)
    t.append(pa.table({"d": pa.array([d1, d1, d2]),
                       "x": pa.array([1, 2, 3], type=pa.int64())}))
    assert len(t.planned_files()) == 2
    assert len(t.planned_files([("d", "=", d1)])) == 1
    got = t.to_df(filters=[("d", "=", d1)]).collect()
    assert sorted(got["x"].to_pylist()) == [1, 2]


def test_metadata_tables_and_compaction(sess, tmp_path):
    import pyarrow as pa

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.iceberg import IcebergTable
    schema = T.StructType((T.StructField("id", T.LONG, False),
                           T.StructField("v", T.DOUBLE, True)))
    tab = IcebergTable.create(sess, str(tmp_path / "ice"), schema)
    for i in range(3):
        tab.append(pa.table({"id": pa.array([i * 10, i * 10 + 1],
                                            type=pa.int64()),
                             "v": [1.0 * i, 2.0 * i]}))
    snaps = tab.snapshots_df().collect().to_pandas()
    assert len(snaps) == 3 and set(snaps["operation"]) == {"append"}
    files = tab.files_df().collect().to_pandas()
    assert len(files) == 3
    assert files["record_count"].sum() == 6
    # delete one row, then compact everything into one file
    tab.delete_where(("id", "=", 21))
    compacted = tab.rewrite_data_files(target_files=1)
    assert compacted == 3
    tab = tab.refresh()
    files = tab.files_df().collect().to_pandas()
    assert len(files) == 1
    out = tab.to_df().collect().to_pandas().sort_values("id")
    assert list(out["id"]) == [0, 1, 10, 11, 20]
    # history keeps all operations incl. the replace
    ops = [h["operation"] for h in tab.history()]
    assert ops[-1] == "replace" and "delete" in ops


def test_normalize_data_path_remote_schemes():
    """Real Iceberg metadata commonly stores s3:// / hdfs:// / gs://
    location URIs; they are not absolute OS paths, so they must take the
    data/ / metadata/ suffix fallback rather than coming back verbatim
    (advisor r3 — a verbatim URI joined under the table root produced an
    opaque read error)."""
    from spark_rapids_tpu.iceberg.metadata import normalize_data_path
    root = "/tmp/tbl"
    assert normalize_data_path(
        "s3://bkt/wh/tbl/data/p=1/f.parquet", root) == "data/p=1/f.parquet"
    assert normalize_data_path(
        "hdfs://nn:8020/wh/tbl/metadata/m.avro", root) == "metadata/m.avro"
    assert normalize_data_path(
        "gs://b/x/data/f.parquet", root) == "data/f.parquet"
    with pytest.raises(ValueError, match="unsupported"):
        normalize_data_path("s3://bkt/elsewhere/f.parquet", root)


def test_trivial_scan_rides_device_decode(sess, tmp_path):
    """A deletes-free, evolution-free scan routes through FileScanExec
    and its device parquet decode (table._trivial_scan_paths) instead of
    the host assembly path — and still matches it exactly."""
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 4000))
    t.append(make_batch(4000, 8000, tag="b"))
    got = t.to_df().orderBy("id").collect()
    assert t.last_scan_file_stats == {"device": 2, "host": 0}
    m = sess.last_query_metrics
    assert m.get("parquetDeviceDecodedColumns", 0) > 0, m
    assert got["id"].to_pylist() == list(range(8000))

    # a position delete flips the scan back to the host assembly path
    t.delete_where(("id", "=", 7))
    after = t.to_df().collect()
    assert t.last_scan_file_stats is None
    assert after.num_rows == 7999


def test_partial_device_decode_after_drop_readd(sess, tmp_path):
    """Drop+re-add of a column allocates a fresh field id; the OLD file's
    stale same-named values must null-fill while its untouched columns
    STILL ride the device decode (round 4 declined the
    whole scan).  The new file device-decodes fully."""
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 3000))
    t = t.drop_column("v").add_column("v", T.DOUBLE)
    t.append(make_batch(3000, 5000, tag="b"))

    df = t.to_df()
    assert t.last_scan_file_stats == {"device": 2, "host": 0}, \
        t.last_scan_file_stats
    got = df.orderBy("id").collect()
    m = sess.last_query_metrics
    assert m.get("parquetDeviceDecodedColumns", 0) > 0, m
    assert got["id"].to_pylist() == list(range(5000))
    vs = got["v"].to_pylist()
    assert all(x is None for x in vs[:3000])      # stale ids null-fill
    assert all(x is not None for x in vs[3000:])  # new file's real values


def test_partial_device_decode_after_rename(sess, tmp_path):
    """A renamed column keeps its field id: old files device-decode and
    project the old physical name onto the new one."""
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 2000))
    t = t.rename_column("v", "value")
    df = t.to_df()
    assert t.last_scan_file_stats["host"] == 0
    got = df.orderBy("id").collect()
    assert "value" in got.column_names
    assert sess.last_query_metrics.get("parquetDeviceDecodedColumns",
                                       0) > 0
    exp = make_batch(0, 2000)
    assert got["value"].to_pylist() == exp["v"].to_pylist()


def test_partial_device_decode_matches_host_path(sess, tmp_path):
    """Evolution mix (drop+re-add, rename, add) — the device-projected
    union must equal the host assembly path row-for-row."""
    t = IcebergTable.create(sess, str(tmp_path / "t"), SCHEMA)
    t.append(make_batch(0, 1500))
    t = t.rename_column("tag", "label").add_column("extra", T.LONG)
    t.append(pa.table({
        "id": pa.array(range(1500, 2500), type=pa.int64()),
        "v": pa.array([float(i) for i in range(1000)]),
        "label": pa.array(["x"] * 1000),
        "extra": pa.array(range(1000), type=pa.int64()),
    }))
    got = t.to_df().orderBy("id").collect()
    # host oracle: the id-resolving assembly reader
    parts = t.scan((), None, None)
    host = pa.concat_tables(parts).sort_by("id")
    assert got.column_names == host.column_names
    for c in host.column_names:
        assert got[c].to_pylist() == host[c].to_pylist(), c
    # a delete still flips the whole scan to host assembly
    t.delete_where(("id", "=", 3))
    assert t._device_scan_df((), None, None) is None
