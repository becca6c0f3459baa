"""Interop: read an Iceberg v2 table the engine did NOT write.

Fixture under tests/golden/iceberg/orders is composed by
tools/make_golden_iceberg.py straight from the Iceberg table spec: real
metadata JSON keys, and avro manifest list / manifests in the REAL nested
``manifest_file`` / ``manifest_entry{data_file: r2{...}}`` layout written
by an independent from-scratch avro encoder."""

import os

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.iceberg import IcebergTable

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "iceberg",
                      "orders")


@pytest.fixture()
def sess():
    return srt.session()


def test_foreign_current_snapshot_applies_position_deletes(sess):
    t = IcebergTable.for_path(sess, GOLDEN)
    got = t.to_df().collect().to_pandas().sort_values("order_id")
    # snapshot 1002 deletes order_id=2 (file 0, pos 1) via a position-
    # delete file
    assert list(got["order_id"]) == [1, 3, 4, 5, 6]
    assert got[got.order_id == 4].amount.iloc[0] == 5.25


def test_foreign_time_travel_by_snapshot_id(sess):
    t = IcebergTable.for_path(sess, GOLDEN)
    v1 = (t.to_df(snapshot_id=1001).collect().to_pandas()
          .sort_values("order_id"))
    assert list(v1["order_id"]) == [1, 2, 3, 4, 5, 6]


def test_foreign_time_travel_as_of_timestamp(sess):
    t = IcebergTable.for_path(sess, GOLDEN)
    old = (t.to_df(as_of_timestamp_ms=1735689650000)  # between snapshots
           .collect().to_pandas())
    assert len(old) == 6


def test_real_manifest_layout_parsed(sess):
    """The manifests on disk are the REAL nested avro layout — confirm
    the reader went through that path and recovered file sizes/counts."""
    from spark_rapids_tpu.iceberg.metadata import (read_manifest,
                                                   read_manifest_list)
    t = IcebergTable.for_path(sess, GOLDEN)
    snap = t.meta.snapshot()
    rels = read_manifest_list(GOLDEN, snap.manifest_list)
    assert len(rels) == 2
    entries = [e for rel in rels for e in read_manifest(GOLDEN, rel)]
    data = [e for e in entries if e.data_file.content == 0]
    dels = [e for e in entries if e.data_file.content == 1]
    assert len(data) == 2 and len(dels) == 1
    assert all(e.data_file.record_count > 0 for e in entries)
    assert all(e.data_file.file_size > 0 for e in entries)


def test_history_and_snapshots(sess):
    t = IcebergTable.for_path(sess, GOLDEN)
    ops = [h["operation"] for h in t.history()]
    assert ops == ["append", "delete"]


def test_foreign_equality_deletes(sess):
    """orders_eqdel golden fixture: a foreign v2 table whose second
    snapshot commits an EQUALITY delete (field id 1 = order_id, ids 2 and
    5, written under a HISTORICAL column name so only field-id matching
    finds it).  The scan must drop exactly those rows (reference
    GpuDeleteFilter.java:94 equalityFieldIds)."""
    t = IcebergTable.for_path(
        sess, os.path.join(os.path.dirname(GOLDEN), "orders_eqdel"))
    df = t.to_df()
    got = df.collect().to_pandas().sort_values("order_id")
    assert list(got["order_id"]) == [1, 3, 4, 6]
    assert list(got["amount"]) == [10.0, 30.0, 5.25, 42.0]


def test_engine_equality_delete_roundtrip(sess, tmp_path):
    """Engine-written equality deletes: delete_where_equality commits an
    EQUALITY_DELETES file; a fresh reader applies it.  Data appended
    AFTER the delete (higher sequence number) is NOT affected —
    sequence-number scoping, the part position deletes don't have."""
    import pyarrow as pa
    from spark_rapids_tpu import types as T2
    path = str(tmp_path / "eqtbl")
    t = IcebergTable.create(sess, path, T2.StructType((
        T2.StructField("id", T2.LONG, True),
        T2.StructField("v", T2.DOUBLE, True))))
    t.append(pa.table({"id": pa.array([1, 2, 3], pa.int64()),
                       "v": [1.0, 2.0, 3.0]}))
    t.delete_where_equality(pa.table({"id": pa.array([2], pa.int64())}))
    # re-append id=2 AFTER the delete: must survive (newer sequence)
    t.append(pa.table({"id": pa.array([2], pa.int64()), "v": [99.0]}))
    fresh = IcebergTable.for_path(sess, path)
    got = fresh.to_df().collect().to_pandas().sort_values(["id", "v"])
    assert list(got["id"]) == [1, 2, 3]
    assert list(got["v"]) == [1.0, 99.0, 3.0]


def test_equality_delete_survives_rename(sess, tmp_path):
    """The delete file is stamped with PARQUET:field_id, so the delete
    keeps applying after the key column is renamed (field-id resolution,
    like foreign readers)."""
    import pyarrow as pa
    from spark_rapids_tpu import types as T2
    path = str(tmp_path / "rn")
    t = IcebergTable.create(sess, path, T2.StructType((
        T2.StructField("id", T2.LONG, True),
        T2.StructField("v", T2.DOUBLE, True))))
    t.append(pa.table({"id": pa.array([1, 2, 3], pa.int64()),
                       "v": [1.0, 2.0, 3.0]}))
    t.delete_where_equality(pa.table({"id": pa.array([2], pa.int64())}))
    t.rename_column("id", "ident")
    got = (IcebergTable.for_path(sess, path).to_df()
           .collect().to_pandas().sort_values("ident"))
    assert list(got["ident"]) == [1, 3]


def test_delete_where_skips_eq_deleted_rows(sess, tmp_path):
    """delete_where must not count (or re-delete) rows an equality
    delete already removed (review r4 finding)."""
    import pyarrow as pa
    from spark_rapids_tpu import types as T2
    path = str(tmp_path / "dw")
    t = IcebergTable.create(sess, path, T2.StructType((
        T2.StructField("id", T2.LONG, True),)))
    t.append(pa.table({"id": pa.array([1, 2, 3], pa.int64())}))
    t.delete_where_equality(pa.table({"id": pa.array([2], pa.int64())}))
    n = t.delete_where(("id", "=", 2))
    assert n == 0, n
