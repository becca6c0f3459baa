"""Interop: read Delta tables the engine did NOT write.

Fixtures under tests/golden/delta/ are composed by tools/make_golden_delta.py
straight from the public Delta transaction-log protocol — real-format
actions (protocol / metaData with schemaString / add with partitionValues
and JSON stats / remove) over snappy parquet written by pyarrow."""

import os

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.delta import DeltaTable

GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "delta")


@pytest.fixture()
def sess():
    return srt.session()


def test_foreign_unpartitioned_snapshot(sess):
    t = DeltaTable.forPath(sess, os.path.join(GOLDEN, "people"))
    got = t.toDF().collect().to_pandas().sort_values("id")
    # v2 = after DELETE WHERE score < 7: ids 1,2,3 (file0) + 6 (rewrite)
    assert list(got["id"]) == [1, 2, 3, 6]
    assert list(got["name"]) == ["ada", "bob", "cat", "eve"]
    assert got[got.id == 1].score.iloc[0] == 9.5


def test_foreign_time_travel(sess):
    t = DeltaTable.forPath(sess, os.path.join(GOLDEN, "people"))
    v0 = t.toDF(version=0).collect().to_pandas().sort_values("id")
    assert list(v0["id"]) == [1, 2, 3, 4, 5]
    assert v0["name"].isna().sum() == 1  # null survives the round trip
    v1 = t.toDF(version=1).collect().to_pandas().sort_values("id")
    assert list(v1["id"]) == [1, 2, 3, 4, 5, 6, 7]


def test_foreign_reader_api(sess):
    df = (sess.read.format("delta").option("versionAsOf", 1)
          .load(os.path.join(GOLDEN, "people")))
    assert df.count() == 7


def test_foreign_partitioned_injects_partition_values(sess):
    """Real Delta partition columns live ONLY in add.partitionValues;
    the reader must materialize them as constants per file."""
    t = DeltaTable.forPath(sess, os.path.join(GOLDEN, "events"))
    got = t.toDF().collect().to_pandas()
    assert len(got) == 8
    assert set(got["day"]) == {"2025-01-01", "2025-01-02"}
    assert (got.groupby("day").size() == 4).all()
    assert got["ts"].notna().all() and got["kind"].notna().all()


def test_foreign_stats_populate_file_metadata(sess):
    """Real stats JSON (numRecords/minValues/...) must feed the snapshot's
    per-file record counts even though the engine's own writer uses a
    different top-level field."""
    t = DeltaTable.forPath(sess, os.path.join(GOLDEN, "people"))
    snap = t.log.snapshot(0)
    assert sorted(a.num_records for a in snap.files.values()) == [2, 3]
    assert all(a.stats and "minValues" in a.stats
               for a in snap.files.values())


def test_unsupported_protocol_fails_loudly(sess):
    """minReaderVersion=3 (deletion vectors): silently ignoring the
    protocol action would return deleted rows — must raise instead."""
    t = DeltaTable.forPath(sess, os.path.join(GOLDEN, "unsupported_dv"))
    with pytest.raises(ValueError, match="minReaderVersion"):
        t.toDF()


def test_engine_written_tables_still_read(sess, tmp_path):
    """The engine's native action form keeps working alongside the
    foreign form."""
    import pyarrow as pa
    df = sess.create_dataframe(pa.table({"a": [1, 2, 3]}))
    t = DeltaTable.create(sess, str(tmp_path / "own"), df)
    assert t.toDF().count() == 3


def test_foreign_partitioned_survives_checkpoint(sess, tmp_path):
    """Checkpoints must carry partitionValues — a checkpointed foreign
    partitioned table read back with null partition columns would be
    silent corruption."""
    import shutil
    work = str(tmp_path / "events")
    shutil.copytree(os.path.join(GOLDEN, "events"), work)
    t = DeltaTable.forPath(sess, work)
    t.log.write_checkpoint()
    got = (DeltaTable.forPath(sess, work).toDF()
           .collect().to_pandas())
    assert got["day"].notna().all()
    assert set(got["day"]) == {"2025-01-01", "2025-01-02"}


def test_foreign_partitioned_dml_preserves_partition_values(sess, tmp_path):
    """DELETE on a foreign partitioned table rewrites touched files; the
    surviving rows must keep their partition values."""
    import shutil
    work = str(tmp_path / "events")
    shutil.copytree(os.path.join(GOLDEN, "events"), work)
    t = DeltaTable.forPath(sess, work)
    before = t.toDF().collect().to_pandas()
    kinds = before.groupby("day").kind.apply(list).to_dict()
    n_clicks = int((before.kind == "click").sum())
    deleted = t.delete(lambda df: df.kind == "click")
    assert deleted == n_clicks
    after = t.toDF().collect().to_pandas()
    assert after["day"].notna().all()
    assert (after.kind == "view").all()
    assert len(after) == int((before.kind == "view").sum())


def test_foreign_checkpoint_layout_detected(sess, tmp_path):
    """A Spark-style columnar checkpoint (no `action` column) must be
    skipped in favor of JSON replay, not crash."""
    import pyarrow as pa_
    import pyarrow.parquet as pq_
    import shutil
    work = str(tmp_path / "people")
    shutil.copytree(os.path.join(GOLDEN, "people"), work)
    t = DeltaTable.forPath(sess, work)
    # fake a foreign columnar checkpoint at the tip
    v = t.log.latest_version()
    pq_.write_table(pa_.table({"add": [None], "remove": [None]},
                              schema=pa_.schema([("add", pa_.string()),
                                                 ("remove", pa_.string())])),
                    os.path.join(work, "_delta_log",
                                 f"{v:020d}.checkpoint.parquet"))
    import json as _json
    with open(os.path.join(work, "_delta_log", "_last_checkpoint"),
              "w") as fh:
        _json.dump({"version": v, "size": 2}, fh)
    got = DeltaTable.forPath(sess, work).toDF().collect().to_pandas()
    assert sorted(got["id"]) == [1, 2, 3, 6]


def test_timestamp_as_of_time_travel(sess, tmp_path):
    """timestampAsOf resolves the latest commit at-or-before the given
    time (Spark's rule); earlier-than-first-commit errors like Delta."""
    import json as _json
    import shutil
    work = str(tmp_path / "people")
    shutil.copytree(os.path.join(GOLDEN, "people"), work)
    # give the three commits distinct, known timestamps
    logd = os.path.join(work, "_delta_log")
    for v, ts in [(0, 1_000_000), (1, 2_000_000), (2, 3_000_000)]:
        p = os.path.join(logd, f"{v:020d}.json")
        lines = [_json.loads(ln) for ln in open(p)]
        for a in lines:
            if "commitInfo" in a:
                a["commitInfo"]["timestamp"] = ts
        with open(p, "w") as fh:
            for a in lines:
                fh.write(_json.dumps(a) + "\n")
    t = DeltaTable.forPath(sess, work)
    assert t.toDF(timestamp_ms=1_500_000).count() == 5   # v0
    assert t.toDF(timestamp_ms=2_000_000).count() == 7   # v1 (inclusive)
    assert t.toDF(timestamp_ms=9_999_999).count() == 4   # v2 (latest)
    with pytest.raises(ValueError, match="before the earliest"):
        t.toDF(timestamp_ms=999)
    # reader-option surface, date-string form (far future => latest)
    df = (sess.read.format("delta").option("timestampAsOf", "2030-01-01")
          .load(work))
    assert df.count() == 4
    with pytest.raises(ValueError, match="not both"):
        t.toDF(version=1, timestamp_ms=2_000_000)


def test_timestamp_as_of_monotonic_adjustment(sess, tmp_path):
    """Out-of-order commit timestamps (clock skew) and commitInfo-less
    commits: timestamps adjust to be non-decreasing before the search,
    like Delta."""
    import json as _json
    import shutil
    work = str(tmp_path / "people")
    shutil.copytree(os.path.join(GOLDEN, "people"), work)
    logd = os.path.join(work, "_delta_log")
    # v0: 1000, v1: 3000, v2: 2000 (skewed) -> adjusted [1000, 3000, 3000]
    for v, ts in [(0, 1000), (1, 3000), (2, 2000)]:
        p = os.path.join(logd, f"{v:020d}.json")
        lines = [_json.loads(ln) for ln in open(p)]
        for a in lines:
            if "commitInfo" in a:
                a["commitInfo"]["timestamp"] = ts
        with open(p, "w") as fh:
            for a in lines:
                fh.write(_json.dumps(a) + "\n")
    t = DeltaTable.forPath(sess, work)
    assert t.toDF(timestamp_ms=2500).count() == 5   # v0 only (v1 adj 3000)
    assert t.toDF(timestamp_ms=3000).count() == 4   # v2 (adjusted 3000)


def test_timestamp_as_of_no_commit_info_uses_file_mtime(sess, tmp_path):
    """commitInfo is optional in the protocol; a foreign writer may omit
    it entirely.  The commit file's mtime then stands in for its
    timestamp (Delta's DeltaHistoryManager rule) — previously such
    commits were treated as timestamp 0, resolving ANY timestampAsOf to
    the latest version (advisor r3)."""
    import json as _json
    import shutil
    work = str(tmp_path / "people")
    shutil.copytree(os.path.join(GOLDEN, "people"), work)
    logd = os.path.join(work, "_delta_log")
    for v, ts in [(0, 1_000_000), (1, 2_000_000), (2, 3_000_000)]:
        p = os.path.join(logd, f"{v:020d}.json")
        lines = [_json.loads(ln) for ln in open(p)
                 if "commitInfo" not in ln]
        with open(p, "w") as fh:
            for a in lines:
                fh.write(_json.dumps(a) + "\n")
        os.utime(p, (ts / 1000, ts / 1000))
    t = DeltaTable.forPath(sess, work)
    assert t.toDF(timestamp_ms=1_500_000).count() == 5   # v0
    assert t.toDF(timestamp_ms=2_000_000).count() == 7   # v1 (inclusive)
    with pytest.raises(ValueError, match="before the earliest"):
        t.toDF(timestamp_ms=999_999)
