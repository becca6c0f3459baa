"""Generate a FOREIGN Iceberg v2 table for interop tests.

Deliberately does NOT import spark_rapids_tpu: table metadata JSON is
composed straight from the Iceberg table-spec keys, and the avro manifest
list / manifests are written in the REAL nested layout
(``manifest_entry{status, snapshot_id, data_file: r2{...}}`` /
``manifest_file{manifest_path, ...}``) by a from-scratch minimal avro
container encoder below — i.e. the shapes a pyiceberg/Spark writer
produces.  Fixtures land in tests/golden/iceberg/.

Run from the repo root:  python tools/make_golden_iceberg.py
"""

import json
import os
import shutil
import struct
import uuid

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "golden", "iceberg")


# ---------------------------------------------------------------------------
# minimal avro encoder (independent of the engine's codec)
# ---------------------------------------------------------------------------

def _zigzag(out: bytearray, v: int) -> None:
    v = (v << 1) ^ (v >> 63)
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            break


def _encode(out: bytearray, schema, value) -> None:
    if isinstance(schema, list):                      # union
        for i, branch in enumerate(schema):
            if (value is None) == (branch == "null"):
                _zigzag(out, i)
                if branch != "null":
                    _encode(out, branch, value)
                return
        raise ValueError(f"no union branch for {value!r} in {schema}")
    kind = schema["type"] if isinstance(schema, dict) else schema
    if kind in ("long", "int"):
        _zigzag(out, int(value))
    elif kind == "string":
        raw = value.encode("utf-8")
        _zigzag(out, len(raw))
        out.extend(raw)
    elif kind == "bytes":
        _zigzag(out, len(value))
        out.extend(value)
    elif kind == "boolean":
        out.append(1 if value else 0)
    elif kind == "double":
        out.extend(struct.pack("<d", float(value)))
    elif kind == "float":
        out.extend(struct.pack("<f", float(value)))
    elif kind == "record":
        for f in schema["fields"]:
            _encode(out, f["type"], value[f["name"]])
    elif kind == "array":
        if value:
            _zigzag(out, len(value))
            for item in value:
                _encode(out, schema["items"], item)
        _zigzag(out, 0)
    elif kind == "map":
        if value:
            _zigzag(out, len(value))
            for k, v in value.items():
                _encode(out, "string", k)
                _encode(out, schema["values"], v)
        _zigzag(out, 0)
    else:
        raise ValueError(f"unsupported avro type {schema!r}")


def write_avro_file(path: str, schema: dict, rows) -> None:
    sync = os.urandom(16)
    header = bytearray(b"Obj\x01")
    meta = {"avro.schema": json.dumps(schema), "avro.codec": "null"}
    _zigzag(header, len(meta))
    for k, v in meta.items():
        _encode(header, "string", k)
        raw = v.encode("utf-8")
        _zigzag(header, len(raw))
        header.extend(raw)
    _zigzag(header, 0)
    header.extend(sync)
    block = bytearray()
    for row in rows:
        _encode(block, schema, row)
    out = bytearray(header)
    _zigzag(out, len(rows))
    _zigzag(out, len(block))
    out.extend(block)
    out.extend(sync)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as fh:
        fh.write(bytes(out))


# ---------------------------------------------------------------------------
# real Iceberg v2 shapes
# ---------------------------------------------------------------------------

MANIFEST_ENTRY_SCHEMA = {
    "type": "record", "name": "manifest_entry", "fields": [
        {"name": "status", "type": "int"},
        {"name": "snapshot_id", "type": ["null", "long"], "default": None},
        {"name": "data_file", "type": {
            "type": "record", "name": "r2", "fields": [
                {"name": "content", "type": "int"},
                {"name": "file_path", "type": "string"},
                {"name": "file_format", "type": "string"},
                {"name": "partition", "type": {
                    "type": "record", "name": "r102", "fields": []}},
                {"name": "record_count", "type": "long"},
                {"name": "file_size_in_bytes", "type": "long"},
            ]}},
    ]}

MANIFEST_FILE_SCHEMA = {
    "type": "record", "name": "manifest_file", "fields": [
        {"name": "manifest_path", "type": "string"},
        {"name": "manifest_length", "type": "long"},
        {"name": "partition_spec_id", "type": "int"},
        {"name": "added_snapshot_id", "type": ["null", "long"],
         "default": None},
    ]}


def _entry(status, snapshot_id, path, content, records, size):
    return {"status": status, "snapshot_id": snapshot_id,
            "data_file": {"content": content, "file_path": path,
                          "file_format": "PARQUET", "partition": {},
                          "record_count": records,
                          "file_size_in_bytes": size}}


def make_orders():
    t = os.path.join(ROOT, "orders")
    shutil.rmtree(t, ignore_errors=True)
    rng = np.random.default_rng(9)

    def data_file(name, tbl):
        rel = f"data/{name}"
        full = os.path.join(t, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        pq.write_table(tbl, full)
        return rel, os.path.getsize(full), tbl.num_rows

    # real Iceberg writers embed PARQUET:field_id into the data files;
    # projection resolves columns by id, not name
    def fid_schema(pairs):
        return pa.schema([
            pa.field(n, t, metadata={b"PARQUET:field_id":
                                     str(i).encode()})
            for i, (n, t) in enumerate(pairs, start=1)])

    sch = fid_schema([("order_id", pa.int64()), ("amount", pa.float64())])
    f0 = pa.table({"order_id": pa.array([1, 2, 3, 4], pa.int64()),
                   "amount": [10.0, 20.5, 30.0, 5.25]}).cast(sch)
    f1 = pa.table({"order_id": pa.array([5, 6], pa.int64()),
                   "amount": [99.0, 42.0]}).cast(sch)
    r0, s0, n0 = data_file(f"00000-0-{uuid.uuid4()}.parquet", f0)
    r1, s1, n1 = data_file(f"00001-0-{uuid.uuid4()}.parquet", f1)

    # snapshot 1: two data files
    m1 = f"metadata/{uuid.uuid4()}-m0.avro"
    write_avro_file(os.path.join(t, m1), MANIFEST_ENTRY_SCHEMA, [
        _entry(1, 1001, r0, 0, n0, s0),
        _entry(1, 1001, r1, 0, n1, s1)])
    l1 = "metadata/snap-1001-1-x.avro"
    write_avro_file(os.path.join(t, l1), MANIFEST_FILE_SCHEMA, [
        {"manifest_path": m1,
         "manifest_length": os.path.getsize(os.path.join(t, m1)),
         "partition_spec_id": 0, "added_snapshot_id": 1001}])

    # snapshot 2: position-delete of order_id=2 (file f0, pos 1)
    dtab = pa.table({"file_path": pa.array([r0], pa.string()),
                     "pos": pa.array([1], pa.int64())})
    rd, sd, nd = data_file(f"00002-deletes-{uuid.uuid4()}.parquet", dtab)
    m2 = f"metadata/{uuid.uuid4()}-m0.avro"
    write_avro_file(os.path.join(t, m2), MANIFEST_ENTRY_SCHEMA, [
        _entry(1, 1002, rd, 1, nd, sd)])
    l2 = "metadata/snap-1002-1-x.avro"
    write_avro_file(os.path.join(t, l2), MANIFEST_FILE_SCHEMA, [
        {"manifest_path": m1,
         "manifest_length": os.path.getsize(os.path.join(t, m1)),
         "partition_spec_id": 0, "added_snapshot_id": 1001},
        {"manifest_path": m2,
         "manifest_length": os.path.getsize(os.path.join(t, m2)),
         "partition_spec_id": 0, "added_snapshot_id": 1002}])

    meta = {
        "format-version": 2,
        "table-uuid": str(uuid.uuid4()),
        "location": "file:///warehouse/orders",
        "last-updated-ms": 1735689600000,
        "last-column-id": 2,
        "current-schema-id": 0,
        "schemas": [{"type": "struct", "schema-id": 0, "fields": [
            {"id": 1, "name": "order_id", "required": False,
             "type": "long"},
            {"id": 2, "name": "amount", "required": False,
             "type": "double"}]}],
        "default-spec-id": 0,
        "partition-specs": [{"spec-id": 0, "fields": []}],
        "current-snapshot-id": 1002,
        "snapshots": [
            {"snapshot-id": 1001, "timestamp-ms": 1735689600000,
             "manifest-list": l1,
             "summary": {"operation": "append"}},
            {"snapshot-id": 1002, "timestamp-ms": 1735689700000,
             "manifest-list": l2,
             "summary": {"operation": "delete"}}],
        "snapshot-log": [
            {"snapshot-id": 1001, "timestamp-ms": 1735689600000},
            {"snapshot-id": 1002, "timestamp-ms": 1735689700000}],
        "properties": {"write.format.default": "parquet"},
    }
    d = os.path.join(t, "metadata")
    with open(os.path.join(d, "v2.metadata.json"), "w") as fh:
        json.dump(meta, fh, indent=1)
    with open(os.path.join(d, "version-hint.text"), "w") as fh:
        fh.write("2")


MANIFEST_ENTRY_SCHEMA_V2SEQ = {
    "type": "record", "name": "manifest_entry", "fields": [
        {"name": "status", "type": "int"},
        {"name": "snapshot_id", "type": ["null", "long"], "default": None},
        {"name": "sequence_number", "type": ["null", "long"],
         "default": None},
        {"name": "data_file", "type": {
            "type": "record", "name": "r2", "fields": [
                {"name": "content", "type": "int"},
                {"name": "file_path", "type": "string"},
                {"name": "file_format", "type": "string"},
                {"name": "partition", "type": {
                    "type": "record", "name": "r102", "fields": []}},
                {"name": "record_count", "type": "long"},
                {"name": "file_size_in_bytes", "type": "long"},
                {"name": "equality_ids",
                 "type": ["null", {"type": "array", "items": "int"}],
                 "default": None},
            ]}},
    ]}


def _entry_v2(status, snapshot_id, seq, path, content, records, size,
              equality_ids=None):
    return {"status": status, "snapshot_id": snapshot_id,
            "sequence_number": seq,
            "data_file": {"content": content, "file_path": path,
                          "file_format": "PARQUET", "partition": {},
                          "record_count": records,
                          "file_size_in_bytes": size,
                          "equality_ids": equality_ids}}


def make_orders_eqdel():
    """orders_eqdel: snapshot 1 appends two data files (seq 1), snapshot 2
    commits an EQUALITY delete on order_id (seq 2) removing ids 2 and 5 —
    the v2 row-level delete shape the reference applies via
    GpuDeleteFilter.equalityFieldIds."""
    t = os.path.join(ROOT, "orders_eqdel")
    shutil.rmtree(t, ignore_errors=True)

    def data_file(name, tbl):
        rel = f"data/{name}"
        full = os.path.join(t, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        pq.write_table(tbl, full)
        return rel, os.path.getsize(full), tbl.num_rows

    def fid_schema(pairs):
        return pa.schema([
            pa.field(n, ty, metadata={b"PARQUET:field_id":
                                      str(i).encode()})
            for i, (n, ty) in enumerate(pairs, start=1)])

    sch = fid_schema([("order_id", pa.int64()), ("amount", pa.float64())])
    f0 = pa.table({"order_id": pa.array([1, 2, 3, 4], pa.int64()),
                   "amount": [10.0, 20.5, 30.0, 5.25]}).cast(sch)
    f1 = pa.table({"order_id": pa.array([5, 6], pa.int64()),
                   "amount": [99.0, 42.0]}).cast(sch)
    r0, s0, n0 = data_file(f"00000-0-{uuid.uuid4()}.parquet", f0)
    r1, s1, n1 = data_file(f"00001-0-{uuid.uuid4()}.parquet", f1)

    # real writers leave ADDED entries' sequence_number NULL and rely on
    # v2 inheritance from the committing snapshot — the reader must
    # resolve these to snapshot 2001's sequence (1), not 0
    m1 = f"metadata/{uuid.uuid4()}-m0.avro"
    write_avro_file(os.path.join(t, m1), MANIFEST_ENTRY_SCHEMA_V2SEQ, [
        _entry_v2(1, 2001, None, r0, 0, n0, s0),
        _entry_v2(1, 2001, None, r1, 0, n1, s1)])
    l1 = "metadata/snap-2001-1-x.avro"
    write_avro_file(os.path.join(t, l1), MANIFEST_FILE_SCHEMA, [
        {"manifest_path": m1,
         "manifest_length": os.path.getsize(os.path.join(t, m1)),
         "partition_spec_id": 0, "added_snapshot_id": 2001}])

    # equality delete on field id 1 (order_id): drop ids 2 and 5 —
    # written under a HISTORICAL column name to force field-id matching
    dsch = pa.schema([pa.field("order_id_v1", pa.int64(),
                               metadata={b"PARQUET:field_id": b"1"})])
    dtab = pa.table({"order_id_v1": pa.array([2, 5], pa.int64())}).cast(dsch)
    rd, sd, nd = data_file(f"00002-eqdel-{uuid.uuid4()}.parquet", dtab)
    m2 = f"metadata/{uuid.uuid4()}-m0.avro"
    write_avro_file(os.path.join(t, m2), MANIFEST_ENTRY_SCHEMA_V2SEQ, [
        _entry_v2(1, 2002, 2, rd, 2, nd, sd, equality_ids=[1])])
    l2 = "metadata/snap-2002-1-x.avro"
    write_avro_file(os.path.join(t, l2), MANIFEST_FILE_SCHEMA, [
        {"manifest_path": m1,
         "manifest_length": os.path.getsize(os.path.join(t, m1)),
         "partition_spec_id": 0, "added_snapshot_id": 2001},
        {"manifest_path": m2,
         "manifest_length": os.path.getsize(os.path.join(t, m2)),
         "partition_spec_id": 0, "added_snapshot_id": 2002}])

    meta = {
        "format-version": 2,
        "table-uuid": str(uuid.uuid4()),
        "location": "file:///warehouse/orders_eqdel",
        "last-updated-ms": 1735689600000,
        "last-column-id": 2,
        "last-sequence-number": 2,
        "current-schema-id": 0,
        "schemas": [{"type": "struct", "schema-id": 0, "fields": [
            {"id": 1, "name": "order_id", "required": False,
             "type": "long"},
            {"id": 2, "name": "amount", "required": False,
             "type": "double"}]}],
        "default-spec-id": 0,
        "partition-specs": [{"spec-id": 0, "fields": []}],
        "current-snapshot-id": 2002,
        "snapshots": [
            {"snapshot-id": 2001, "timestamp-ms": 1735689600000,
             "sequence-number": 1, "manifest-list": l1,
             "summary": {"operation": "append"}},
            {"snapshot-id": 2002, "timestamp-ms": 1735689700000,
             "sequence-number": 2, "manifest-list": l2,
             "summary": {"operation": "delete"}}],
        "snapshot-log": [
            {"snapshot-id": 2001, "timestamp-ms": 1735689600000},
            {"snapshot-id": 2002, "timestamp-ms": 1735689700000}],
        "properties": {"write.format.default": "parquet"},
    }
    d = os.path.join(t, "metadata")
    with open(os.path.join(d, "v2.metadata.json"), "w") as fh:
        json.dump(meta, fh, indent=1)
    with open(os.path.join(d, "version-hint.text"), "w") as fh:
        fh.write("2")


if __name__ == "__main__":
    make_orders()
    make_orders_eqdel()
    print("golden iceberg table written under", ROOT)
