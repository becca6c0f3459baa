"""Generate FOREIGN Delta tables for interop tests.

This script deliberately does NOT import spark_rapids_tpu: it composes
`_delta_log` actions by hand following the public Delta transaction-log
protocol (PROTOCOL.md: protocol / metaData with schemaString / add with
partitionValues + stats / remove / commitInfo) and writes data files with
pyarrow — i.e. the same byte-level shapes a Spark or delta-rs writer
produces.  The committed fixtures under tests/golden/delta/ are therefore
tables the engine did not write.

Run from the repo root:  python tools/make_golden_delta.py
"""

import json
import os
import shutil
import uuid

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "golden", "delta")


def _log(table, version, actions):
    d = os.path.join(table, "_delta_log")
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"{version:020d}.json"), "w") as fh:
        for a in actions:
            fh.write(json.dumps(a) + "\n")


def _commit_info(op):
    return {"commitInfo": {"timestamp": 1735689600000, "operation": op,
                           "engineInfo": "goldenGen/0.1 DeltaSpec/1"}}


def _schema_string(fields):
    return json.dumps({"type": "struct", "fields": [
        {"name": n, "type": t, "nullable": True, "metadata": {}}
        for n, t in fields]})


def _metadata(fields, partition_columns=()):
    return {"metaData": {
        "id": str(uuid.uuid4()),
        "format": {"provider": "parquet", "options": {}},
        "schemaString": _schema_string(fields),
        "partitionColumns": list(partition_columns),
        "configuration": {},
        "createdTime": 1735689600000,
    }}


def _write_parquet(table_dir, rel, tbl):
    full = os.path.join(table_dir, rel)
    os.makedirs(os.path.dirname(full), exist_ok=True)
    pq.write_table(tbl, full, compression="snappy")
    return os.path.getsize(full)


def _stats(tbl):
    s = {"numRecords": tbl.num_rows, "minValues": {}, "maxValues": {},
         "nullCount": {}}
    for name in tbl.column_names:
        col = tbl.column(name)
        s["nullCount"][name] = col.null_count
        if col.num_chunks and tbl.num_rows > col.null_count:
            vals = [v for v in col.to_pylist() if v is not None]
            s["minValues"][name] = min(vals)
            s["maxValues"][name] = max(vals)
    return s


def _add(rel, size, tbl, partition_values=None):
    return {"add": {
        "path": rel, "partitionValues": partition_values or {},
        "size": size, "modificationTime": 1735689600000,
        "dataChange": True, "stats": json.dumps(_stats(tbl)),
    }}


def make_people():
    """Unpartitioned table: 3 commits — create+2 files, append, delete
    (remove one file, add its filtered replacement)."""
    t = os.path.join(ROOT, "people")
    shutil.rmtree(t, ignore_errors=True)
    fields = [("id", "long"), ("name", "string"), ("score", "double")]

    f0 = pa.table({"id": pa.array([1, 2, 3], pa.int64()),
                   "name": ["ada", "bob", "cat"],
                   "score": [9.5, 7.25, 8.0]})
    f1 = pa.table({"id": pa.array([4, 5], pa.int64()),
                   "name": ["dan", None],
                   "score": [6.5, 5.0]})
    r0 = f"part-00000-{uuid.uuid4()}-c000.snappy.parquet"
    r1 = f"part-00001-{uuid.uuid4()}-c000.snappy.parquet"
    _log(t, 0, [_commit_info("CREATE TABLE AS SELECT"),
                {"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
                _metadata(fields),
                _add(r0, _write_parquet(t, r0, f0), f0),
                _add(r1, _write_parquet(t, r1, f1), f1)])

    f2 = pa.table({"id": pa.array([6, 7], pa.int64()),
                   "name": ["eve", "fay"],
                   "score": [9.9, 4.2]})
    r2 = f"part-00000-{uuid.uuid4()}-c000.snappy.parquet"
    _log(t, 1, [_commit_info("WRITE"),
                _add(r2, _write_parquet(t, r2, f2), f2)])

    # DELETE WHERE score < 7: rewrites f1 (drops id=4 with 6.5, id=5 w 5.0)
    # and f2 (drops id=7) — actually f1 drops BOTH rows -> pure remove
    f2b = f2.filter(pa.compute.greater_equal(f2.column("score"), 7.0))
    r2b = f"part-00000-{uuid.uuid4()}-c000.snappy.parquet"
    _log(t, 2, [_commit_info("DELETE"),
                {"remove": {"path": r1, "dataChange": True,
                            "deletionTimestamp": 1735689700000}},
                {"remove": {"path": r2, "dataChange": True,
                            "deletionTimestamp": 1735689700000}},
                _add(r2b, _write_parquet(t, r2b, f2b), f2b)])


def make_events():
    """Partitioned table: partition column `day` is NOT in the data files
    (real Delta stores it only in add.partitionValues)."""
    t = os.path.join(ROOT, "events")
    shutil.rmtree(t, ignore_errors=True)
    fields = [("ts", "long"), ("kind", "string"), ("day", "string")]
    rng = np.random.default_rng(7)
    actions = [_commit_info("CREATE TABLE AS SELECT"),
               {"protocol": {"minReaderVersion": 1, "minWriterVersion": 2}},
               _metadata(fields, partition_columns=["day"])]
    for day in ("2025-01-01", "2025-01-02"):
        n = 4
        data = pa.table({
            "ts": pa.array(rng.integers(0, 10_000, n), pa.int64()),
            "kind": pa.array(rng.choice(["click", "view"], n)),
        })  # note: no `day` column in the file
        rel = (f"day={day}/part-00000-{uuid.uuid4()}-c000.snappy.parquet")
        size = _write_parquet(t, rel, data)
        actions.append(_add(rel, size, data, {"day": day}))
    _log(t, 0, actions)


def make_unsupported():
    """A table requiring reader features this engine lacks (deletion
    vectors -> minReaderVersion 3): reads must FAIL loudly, not return
    wrong rows."""
    t = os.path.join(ROOT, "unsupported_dv")
    shutil.rmtree(t, ignore_errors=True)
    fields = [("x", "long")]
    f0 = pa.table({"x": pa.array([1, 2, 3], pa.int64())})
    r0 = f"part-00000-{uuid.uuid4()}-c000.snappy.parquet"
    _log(t, 0, [_commit_info("CREATE TABLE"),
                {"protocol": {"minReaderVersion": 3, "minWriterVersion": 7,
                              "readerFeatures": ["deletionVectors"],
                              "writerFeatures": ["deletionVectors"]}},
                _metadata(fields),
                _add(r0, _write_parquet(t, r0, f0), f0)])


if __name__ == "__main__":
    make_people()
    make_events()
    make_unsupported()
    print("golden delta tables written under", ROOT)
