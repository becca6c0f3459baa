"""Iceberg-analog table: snapshot reads with partition/bound pruning,
field-id schema evolution, position deletes, and writer support so tests
(and users) can build tables without an external catalog.

Read-path parity targets (reference
``sql-plugin/src/main/java/com/nvidia/spark/rapids/iceberg/``):

* ``GpuSparkBatchQueryScan``   -> :meth:`IcebergTable.scan` /
  :meth:`to_df` (snapshot selection, residual filters, file pruning)
* ``SparkSchemaUtil``/pruning  -> field-id projection in
  :meth:`_read_data_file` (rename/add/drop evolution: columns resolve by
  id against each data file's stored schema, never by name)
* ``GpuDeleteFilter``          -> position-delete application (content=1
  files joined on (file_path, pos) before upload)

The write path (append/delete/schema evolution) exists so the format is
self-contained; it follows the metadata commit protocol in
``metadata.py`` (atomic version rename = optimistic concurrency).
"""

from __future__ import annotations

import json
import os
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from .. import types as T
from .metadata import (DATA, POSITION_DELETES, STATUS_ADDED, DataFile,
                       IceSchema, IceSnapshot, ManifestEntry, NestedField,
                       PartitionField, PartitionSpec, TableMetadata,
                       latest_metadata_version, read_manifest,
                       read_manifest_list, read_table_metadata, type_to_ice,
                       write_manifest, write_manifest_list,
                       write_table_metadata)
from .transforms import parse_transform

#: parquet key-value metadata key holding the file's iceberg schema
#: (field ids), the hook schema evolution resolves against
_SCHEMA_PROP = b"iceberg.schema"

_FIELD_ID_KEY = b"PARQUET:field_id"


class IcebergTable:
    def __init__(self, session, path: str,
                 meta: Optional[TableMetadata] = None):
        self._session = session
        self.path = path
        self.meta = meta or read_table_metadata(path)
        #: (file_path, schema-fingerprint, "resolve") -> projection spec
        #: so a wide deletes-free table doesn't re-read every footer per
        #: query (files are immutable; schema changes change the key)
        self._schema_match_cache: Dict[Tuple, Any] = {}
        #: device/host file split of the last _device_scan_df plan; None
        #: when the scan took the host assembly path (deletes present)
        self.last_scan_file_stats: Optional[Dict[str, int]] = None

    # ------------------------------------------------------------------
    # creation / loading
    # ------------------------------------------------------------------
    @staticmethod
    def exists(path: str) -> bool:
        return latest_metadata_version(path) is not None

    @staticmethod
    def create(session, path: str, schema: T.StructType,
               partition_by: Sequence[Tuple[str, str]] = ()
               ) -> "IcebergTable":
        """``partition_by``: (column, transform) pairs, e.g.
        ``[("day_col", "day"), ("id", "bucket[16]")]``."""
        if IcebergTable.exists(path):
            raise FileExistsError(f"iceberg table exists: {path}")
        fields = [NestedField(i + 1, f.name, type_to_ice(f.data_type),
                              not f.nullable)
                  for i, f in enumerate(schema.fields)]
        ice = IceSchema(0, fields)
        pfields = []
        for j, (col, tname) in enumerate(partition_by):
            src = ice.field_by_name(col)
            if src is None:
                raise KeyError(f"partition column {col} not in schema")
            parse_transform(tname)  # validate
            pfields.append(PartitionField(src.field_id, 1000 + j, tname,
                                          f"{col}_{tname.split('[')[0]}"))
        meta = TableMetadata(
            location=path, table_uuid=str(uuid.uuid4()),
            last_column_id=len(fields), current_schema_id=0,
            schemas=[ice], default_spec_id=0,
            partition_specs=[PartitionSpec(0, pfields)])
        write_table_metadata(path, meta)
        return IcebergTable(session, path, meta)

    @staticmethod
    def for_path(session, path: str) -> "IcebergTable":
        return IcebergTable(session, path)

    def refresh(self) -> "IcebergTable":
        self.meta = read_table_metadata(self.path)
        return self

    # ------------------------------------------------------------------
    # write path
    # ------------------------------------------------------------------
    def _column_bounds(self, schema: IceSchema, tab: pa.Table):
        lower, upper, nulls = {}, {}, {}
        for f in schema.fields:
            if f.name not in tab.column_names:
                continue
            col = tab[f.name]
            nulls[f.field_id] = col.null_count
            if col.length() - col.null_count == 0:
                continue
            try:
                import pyarrow.compute as pc
                mn = pc.min(col).as_py()
                mx = pc.max(col).as_py()
            except Exception:
                continue
            if isinstance(mn, (int, float, str)):
                lower[f.field_id] = mn
                upper[f.field_id] = mx
        return lower, upper, nulls

    def _write_parquet(self, tab: pa.Table, schema: IceSchema) -> str:
        """Write a data file whose parquet schema carries the iceberg
        field ids (both as PARQUET:field_id and a schema blob in the file
        metadata) so later reads resolve columns by id."""
        fields = []
        for f in schema.fields:
            if f.name not in tab.column_names:
                continue
            af = tab.schema.field(f.name)
            fields.append(af.with_metadata(
                {_FIELD_ID_KEY: str(f.field_id).encode()}))
        out_schema = pa.schema(fields, metadata={
            _SCHEMA_PROP: json.dumps(schema.to_json()).encode()})
        cols = [tab[f.name] for f in out_schema]
        tab2 = pa.Table.from_arrays(
            [c.combine_chunks() for c in cols], schema=out_schema)
        rel = os.path.join("data", f"{uuid.uuid4().hex}.parquet")
        full = os.path.join(self.path, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        pq.write_table(tab2, full)
        return rel

    def _commit_snapshot(self, new_entries: List[ManifestEntry],
                         carried_manifests: List[str],
                         operation: str) -> IceSnapshot:
        sid = int(uuid.uuid4().int % (1 << 62))
        seq = self.meta.last_sequence_number + 1
        manifests = list(carried_manifests)
        if new_entries:
            for e in new_entries:
                e.snapshot_id = sid
                if not e.data_file.sequence_number:
                    e.data_file.sequence_number = seq
            manifests.append(write_manifest(self.path, new_entries))
        mlist = write_manifest_list(self.path, sid, manifests)
        now = int(time.time() * 1000)
        snap = IceSnapshot(
            snapshot_id=sid, timestamp_ms=now, manifest_list=mlist,
            parent_id=self.meta.current_snapshot_id,
            schema_id=self.meta.current_schema_id,
            summary={"operation": operation,
                     "added-files": str(len(new_entries))},
            sequence_number=seq)
        self.meta.last_sequence_number = seq
        self.meta.snapshots.append(snap)
        self.meta.current_snapshot_id = sid
        self.meta.snapshot_log.append(
            {"timestamp-ms": now, "snapshot-id": sid})
        write_table_metadata(self.path, self.meta)
        return snap

    def append(self, data) -> "IcebergTable":
        """Append a DataFrame / pyarrow table, splitting into one data file
        per partition tuple."""
        tab = data.collect() if hasattr(data, "collect") else data
        schema = self.meta.schema()
        spec = self.meta.spec()
        entries: List[ManifestEntry] = []
        for part_tab, part_vals in self._split_by_partition(tab, spec,
                                                            schema):
            rel = self._write_parquet(part_tab, schema)
            lower, upper, nulls = self._column_bounds(schema, part_tab)
            df = DataFile(file_path=rel, content=DATA,
                          record_count=part_tab.num_rows,
                          file_size=os.path.getsize(
                              os.path.join(self.path, rel)),
                          spec_id=spec.spec_id, partition=part_vals,
                          lower_bounds=lower, upper_bounds=upper,
                          null_counts=nulls)
            entries.append(ManifestEntry(STATUS_ADDED, 0, df))
        carried = self._current_manifests()
        self._commit_snapshot(entries, carried, "append")
        return self

    def _split_by_partition(self, tab: pa.Table, spec: PartitionSpec,
                            schema: IceSchema):
        if spec.is_unpartitioned or tab.num_rows == 0:
            yield tab, ()
            return
        transforms = [(schema.field_by_id(pf.source_id).name,
                       parse_transform(pf.transform))
                      for pf in spec.fields]
        keys = []
        for name, tr in transforms:
            keys.append([tr.apply(v.as_py()) for v in tab[name]])
        tuples = list(zip(*keys))
        order: Dict[Tuple, List[int]] = {}
        for i, t in enumerate(tuples):
            order.setdefault(t, []).append(i)
        for t, idxs in order.items():
            yield tab.take(pa.array(idxs, type=pa.int64())), t

    def _current_manifests(self) -> List[str]:
        snap = self.meta.snapshot()
        if snap is None:
            return []
        return read_manifest_list(self.path, snap.manifest_list)

    # ------------------------------------------------------------------
    # row-level deletes (v2 position deletes)
    # ------------------------------------------------------------------
    def delete_where(self, predicate) -> int:
        """Delete rows matching ``predicate`` (a python fn row-dict->bool
        or a (col, op, literal) triple) by writing position-delete files.
        Returns the number of deleted rows."""
        snap = self.meta.snapshot()
        if snap is None:
            return 0
        files, pos_files, eq_files = self._snapshot_files(snap)
        deleted = 0
        del_rows: Dict[str, List[int]] = {}
        delete_map = self._delete_position_map(snap, pos_files)
        # predicates address the CURRENT schema names (same contract as
        # scan()'s current reads)
        cur_schema = self.meta.schema()
        eq_deletes = self._equality_deletes(snap, cur_schema, eq_files)
        for df in files:
            tab = self._read_data_file(df, cur_schema)
            existing = delete_map.get(df.file_path, set())
            mask = self._eval_predicate(tab, predicate)
            # rows already removed by equality deletes must not count as
            # (re-)deleted — compute the live mask the scan would see
            if eq_deletes:
                live = np.ones(tab.num_rows, dtype=bool)
                for seq, names, keys in eq_deletes:
                    if not keys or (df.sequence_number
                                    and seq <= df.sequence_number):
                        continue
                    vals = list(zip(*[tab[n].to_pylist() for n in names]))
                    live &= np.array([t not in keys for t in vals],
                                     dtype=bool)
                mask = mask & live
            for pos in np.nonzero(mask)[0]:
                if int(pos) not in existing:
                    del_rows.setdefault(df.file_path, []).append(int(pos))
                    deleted += 1
        if not deleted:
            return 0
        entries = []
        for fpath, positions in del_rows.items():
            dtab = pa.table({
                "file_path": [fpath] * len(positions),
                "pos": pa.array(positions, type=pa.int64())})
            rel = os.path.join("data",
                               f"delete-{uuid.uuid4().hex}.parquet")
            full = os.path.join(self.path, rel)
            os.makedirs(os.path.dirname(full), exist_ok=True)
            pq.write_table(dtab, full)
            entries.append(ManifestEntry(STATUS_ADDED, 0, DataFile(
                file_path=rel, content=POSITION_DELETES,
                record_count=len(positions),
                file_size=os.path.getsize(full))))
        self._commit_snapshot(entries, self._current_manifests(), "delete")
        return deleted

    def delete_where_equality(self, keys: "pa.Table") -> "IcebergTable":
        """Commit an EQUALITY_DELETES file: every (current or future-read)
        data row whose values for ``keys``' columns equal one of the key
        rows is deleted.  Columns resolve against the current schema."""
        from .metadata import EQUALITY_DELETES
        schema = self.meta.schema()
        fids = []
        for name in keys.column_names:
            f = schema.field_by_name(name)
            if f is None:
                raise KeyError(name)
            fids.append(f.field_id)
        rel = os.path.join("data", f"eqdel-{uuid.uuid4().hex}.parquet")
        full = os.path.join(self.path, rel)
        os.makedirs(os.path.dirname(full), exist_ok=True)
        # stamp PARQUET:field_id so the delete keeps applying across
        # column renames (and foreign readers resolve it by id)
        stamped = pa.schema([
            pa.field(f.name, f.type,
                     metadata={b"PARQUET:field_id":
                               str(fid).encode()})
            for f, fid in zip(keys.schema, fids)])
        pq.write_table(keys.cast(stamped), full)
        entry = ManifestEntry(STATUS_ADDED, 0, DataFile(
            file_path=rel, content=EQUALITY_DELETES,
            record_count=keys.num_rows,
            file_size=os.path.getsize(full),
            equality_ids=tuple(fids)))
        self._commit_snapshot([entry], self._current_manifests(), "delete")
        return self

    def _eval_predicate(self, tab: pa.Table, predicate) -> np.ndarray:
        if callable(predicate):
            rows = tab.to_pylist()
            return np.array([bool(predicate(r)) for r in rows], dtype=bool)
        col, op, lit = predicate
        vals = tab[col].to_numpy(zero_copy_only=False)
        if op == "=":
            return vals == lit
        if op == "!=":
            return vals != lit
        if op == "<":
            return vals < lit
        if op == "<=":
            return vals <= lit
        if op == ">":
            return vals > lit
        if op == ">=":
            return vals >= lit
        if op == "in":
            return np.isin(vals, list(lit))
        raise ValueError(f"unsupported delete predicate op {op}")

    # ------------------------------------------------------------------
    # schema evolution
    # ------------------------------------------------------------------
    def _evolve(self, mutate) -> "IcebergTable":
        cur = self.meta.schema()
        new_fields = [NestedField(f.field_id, f.name, f.type_str, f.required)
                      for f in cur.fields]
        new_schema = IceSchema(cur.schema_id + 1, new_fields)
        mutate(new_schema)
        self.meta.schemas.append(new_schema)
        self.meta.current_schema_id = new_schema.schema_id
        write_table_metadata(self.path, self.meta)
        return self

    def add_column(self, name: str, dtype) -> "IcebergTable":
        def m(s: IceSchema):
            if s.field_by_name(name):
                raise ValueError(f"column {name} exists")
            self.meta.last_column_id += 1
            s.fields.append(NestedField(self.meta.last_column_id, name,
                                        type_to_ice(dtype), False))
        return self._evolve(m)

    def rename_column(self, old: str, new: str) -> "IcebergTable":
        def m(s: IceSchema):
            f = s.field_by_name(old)
            if f is None:
                raise KeyError(old)
            f.name = new
        return self._evolve(m)

    def drop_column(self, name: str) -> "IcebergTable":
        def m(s: IceSchema):
            f = s.field_by_name(name)
            if f is None:
                raise KeyError(name)
            s.fields.remove(f)
        return self._evolve(m)

    # ------------------------------------------------------------------
    # scan planning
    # ------------------------------------------------------------------
    def _snapshot_files(self, snap: IceSnapshot):
        """ONE manifest pass per scan, classified by content:
        (data_files, position_delete_files, equality_delete_files).
        Entries whose sequence number is null (real writers rely on v2
        INHERITANCE) resolve to the sequence of the snapshot that added
        them — mapping null to 0 would both let older equality deletes
        eat re-inserted rows and let newer deletes be skipped."""
        seq_of = {s.snapshot_id: s.sequence_number
                  for s in self.meta.snapshots}
        data: List[DataFile] = []
        pos: List[DataFile] = []
        eq: List[DataFile] = []
        for mrel in read_manifest_list(self.path, snap.manifest_list):
            for e in read_manifest(self.path, mrel):
                if e.status == 2:
                    continue
                df = e.data_file
                if not df.sequence_number:
                    df.sequence_number = seq_of.get(e.snapshot_id, 0)
                if df.content == DATA:
                    data.append(df)
                elif df.content == POSITION_DELETES:
                    pos.append(df)
                else:
                    eq.append(df)
        return data, pos, eq

    def _live_data_files(self, snap: IceSnapshot) -> List[DataFile]:
        return self._snapshot_files(snap)[0]

    def _delete_files(self, snap: IceSnapshot) -> List[DataFile]:
        return self._snapshot_files(snap)[1]

    def _equality_deletes(self, snap: IceSnapshot, schema,
                          eq_files=None):
        """[(sequence_number, key column names, {key tuples})] for every
        live EQUALITY_DELETES file (reference ``GpuDeleteFilter.java:94``
        equalityFieldIds): a data row is dropped when its values for the
        delete's field ids equal a delete row's (null == null, like
        Iceberg's equality delete semantics), and the delete's sequence
        number is strictly newer than the data file's."""
        if eq_files is None:
            eq_files = self._snapshot_files(snap)[2]
        out = []
        for df in eq_files:
                tab = pq.read_table(os.path.join(self.path, df.file_path))
                names = []
                for fid in df.equality_ids:
                    f = schema.field_by_id(int(fid))
                    if f is None:
                        raise ValueError(
                            f"equality delete {df.file_path} references "
                            f"unknown field id {fid}")
                    names.append(f.name)
                # delete files may carry historical column names; match
                # columns by embedded field id first, then by name
                cols = []
                for fid, name in zip(df.equality_ids, names):
                    idx = None
                    for j, pf in enumerate(tab.schema):
                        md = pf.metadata or {}
                        if md.get(b"PARQUET:field_id") == \
                                str(fid).encode():
                            idx = j
                            break
                    if idx is None:
                        idx = tab.column_names.index(name) \
                            if name in tab.column_names else None
                    if idx is None:
                        raise ValueError(
                            f"equality delete {df.file_path} lacks a "
                            f"column for field id {fid} ({name})")
                    cols.append(tab.column(idx).to_pylist())
                keys = set(zip(*cols)) if cols else set()
                out.append((df.sequence_number, names, keys))
        return out

    @staticmethod
    def _apply_equality_deletes(tab: pa.Table, file_seq: int,
                                eq_deletes) -> pa.Table:
        for seq, names, keys in eq_deletes:
            if not keys or (file_seq and seq <= file_seq):
                continue  # delete is not newer than the data
            vals = list(zip(*[tab[n].to_pylist() for n in names]))
            mask = pa.array([t not in keys for t in vals],
                            type=pa.bool_())
            tab = tab.filter(mask)
        return tab

    def _delete_position_map(self, snap: IceSnapshot,
                             pos_files=None) -> Dict[str, set]:
        """All position deletes for the snapshot, read ONCE per scan:
        {data_file_path: {deleted row positions}}."""
        from .metadata import normalize_data_path
        out: Dict[str, set] = {}
        for df in (pos_files if pos_files is not None
                   else self._delete_files(snap)):
            tab = pq.read_table(os.path.join(self.path, df.file_path))
            for fp, p in zip(tab["file_path"].to_pylist(),
                             tab["pos"].to_pylist()):
                # real delete files reference data files by full URI
                out.setdefault(normalize_data_path(fp, self.path),
                               set()).add(int(p))
        return out

    def _prune_files(self, files: List[DataFile],
                     filters: Sequence[Tuple[str, str, Any]],
                     schema: IceSchema) -> List[DataFile]:
        """Partition-transform pruning + column-bound (min/max) skipping —
        the planning the reference does via Iceberg's
        ``ManifestEvaluator``/``InclusiveMetricsEvaluator``."""
        if not filters:
            return files
        spec_cache: Dict[int, PartitionSpec] = {}
        out = []
        for df in files:
            spec = spec_cache.setdefault(df.spec_id,
                                         self.meta.spec(df.spec_id))
            keep = True
            for col, op, lit in filters:
                f = schema.field_by_name(col)
                if f is None:
                    continue
                # partition pruning
                for pi, pf in enumerate(spec.fields):
                    if pf.source_id == f.field_id and pi < len(df.partition):
                        tr = parse_transform(pf.transform)
                        if not tr.possible(df.partition[pi], op, lit):
                            keep = False
                            break
                if not keep:
                    break
                # min/max skipping (same overlap predicate as parquet
                # row-group pruning)
                from ..io_.pushdown import stats_possible
                lo = df.lower_bounds.get(f.field_id)
                hi = df.upper_bounds.get(f.field_id)
                if lo is not None and hi is not None and \
                        op in ("=", "<", "<=", ">", ">=", "in") and \
                        not stats_possible(lo, hi, op, lit):
                    keep = False
                if not keep:
                    break
            if keep:
                out.append(df)
        return out

    def _read_data_file(self, df: DataFile, schema: IceSchema) -> pa.Table:
        """Read one data file projecting the snapshot schema BY FIELD ID:
        renamed columns resolve to their old physical name, dropped columns
        are skipped, added columns null-fill."""
        full = os.path.join(self.path, df.file_path)
        ptab = pq.read_table(full)
        file_ids: Dict[int, str] = {}
        for af in ptab.schema:
            meta = af.metadata or {}
            if _FIELD_ID_KEY in meta:
                file_ids[int(meta[_FIELD_ID_KEY])] = af.name
        if not file_ids:
            # file carries no field ids (imported data): fall back to
            # name mapping, which is exactly Iceberg's
            # `schema.name-mapping.default` behavior for such files
            names = set(ptab.schema.names)
            file_ids = {f.field_id: f.name for f in schema.fields
                        if f.name in names}
        arrays, fields = [], []
        n = ptab.num_rows
        for f in schema.fields:
            atype = T.to_arrow(ice_to_type_cached(f.type_str))
            phys = file_ids.get(f.field_id)
            if phys is not None:
                col = ptab[phys].combine_chunks()
                if col.type != atype:
                    col = col.cast(atype)
                arrays.append(col)
            else:
                arrays.append(pa.nulls(n, type=atype))
            fields.append(pa.field(f.name, atype, not f.required))
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    def _select_snapshot(self, snapshot_id: Optional[int],
                         as_of_timestamp_ms: Optional[int]
                         ) -> Tuple[Optional[IceSnapshot], Optional[int]]:
        """(snapshot, schema_id-to-read-with).  Current reads use the
        table's CURRENT schema (Iceberg semantics: schema evolves
        independently of snapshots); explicit time travel reads with the
        schema the snapshot was committed under."""
        if as_of_timestamp_ms is not None:
            snap = self.meta.snapshot_as_of(as_of_timestamp_ms)
        else:
            snap = self.meta.snapshot(snapshot_id)
        if snap is None:
            return None, None
        time_travel = (snapshot_id is not None
                       or as_of_timestamp_ms is not None)
        return snap, (snap.schema_id if time_travel else None)

    def scan(self, filters: Sequence[Tuple[str, str, Any]] = (),
             snapshot_id: Optional[int] = None,
             as_of_timestamp_ms: Optional[int] = None) -> List[pa.Table]:
        """Plan + execute the host-side read: returns one pa.Table per
        surviving data file (deletes applied, schema projected)."""
        snap, schema_id = self._select_snapshot(snapshot_id,
                                                as_of_timestamp_ms)
        if snap is None:
            return []
        schema = self.meta.schema(schema_id)
        data_files, pos_files, eq_files = self._snapshot_files(snap)
        files = self._prune_files(data_files, filters, schema)
        delete_map = self._delete_position_map(snap, pos_files)
        eq_deletes = self._equality_deletes(snap, schema, eq_files)
        out = []
        for df in files:
            tab = self._read_data_file(df, schema)
            dels = delete_map.get(df.file_path)
            if dels:
                keep = np.setdiff1d(np.arange(tab.num_rows),
                                    np.fromiter(dels, dtype=np.int64))
                tab = tab.take(pa.array(keep, type=pa.int64()))
            if eq_deletes:
                tab = self._apply_equality_deletes(
                    tab, df.sequence_number, eq_deletes)
            out.append(tab)
        return out

    def planned_files(self, filters: Sequence[Tuple[str, str, Any]] = ()
                      ) -> List[str]:
        """File list after pruning (for tests / EXPLAIN)."""
        snap = self.meta.snapshot()
        if snap is None:
            return []
        schema = self.meta.schema(snap.schema_id)
        return [f.file_path for f in
                self._prune_files(self._live_data_files(snap), filters,
                                  schema)]

    def _device_scan_df(self, filters, snapshot_id, as_of_timestamp_ms):
        """Per-FILE device decode with schema-evolution projection
        (the round-4 gate declined the whole scan when
        ANY column mismatched).  Each delete-free file becomes a
        ``read.parquet`` frame projected to the snapshot schema:

          * field-id (+ arrow-type) matches select the file column,
            renamed if the snapshot renamed it;
          * ids absent from the file (dropped+re-added columns allocate
            fresh ids, so stale same-NAME columns are skipped) null-fill
            via ``lit(NULL) CAST``;
          * a type mismatch (promotion) sends THAT FILE — not the scan —
            to the host id-resolving reader.

        Frames union into one plan; matching files keep riding
        ``io_/device_parquet.py``.  Returns the DataFrame, or None when
        deletes force the host assembly path.  ``last_scan_file_stats``
        reports the device/host file split for tests/EXPLAIN."""
        self.last_scan_file_stats = None  # host-assembly scans report None
        snap, schema_id = self._select_snapshot(snapshot_id,
                                                as_of_timestamp_ms)
        if snap is None:
            return None
        schema = self.meta.schema(schema_id)
        data_files, pos_files, eq_files = self._snapshot_files(snap)
        if pos_files or eq_files:
            return None
        files = self._prune_files(data_files, filters, schema)
        if not files:
            return None
        want = [(f.name, f.field_id, ice_to_type_cached(f.type_str))
                for f in schema.fields]
        # schema_id alone is not a valid cache key: in-place evolution
        # (add/rename/drop) can keep the id while changing the fields —
        # fingerprint the resolved field tuple instead
        fp = tuple((f.name, f.field_id, f.type_str)
                   for f in schema.fields)
        specs = []
        for df in files:
            full = os.path.join(self.path, df.file_path)
            key = (df.file_path, fp, "resolve")
            spec = self._schema_match_cache.get(key)
            if spec is None:
                try:
                    fs = pq.read_schema(full)
                except OSError:
                    return None
                by_id = {}
                by_name = {}
                has_ids = False
                for af in fs:
                    meta = af.metadata or {}
                    if _FIELD_ID_KEY in meta:
                        has_ids = True
                        by_id[int(meta[_FIELD_ID_KEY])] = af
                    by_name[af.name] = af
                cols = []
                host = False
                for name, fid, dt in want:
                    af = by_id.get(fid) if has_ids else by_name.get(name)
                    if af is None:
                        cols.append(("null", name))
                    elif af.type == T.to_arrow(dt):
                        cols.append(("col", af.name, name))
                    else:
                        host = True  # type promotion: host id-resolution
                        break
                if host:
                    spec = "host"
                elif (fs.names == [c[1] for c in cols if c[0] == "col"]
                        and all(c[0] == "col" and c[1] == c[2]
                                for c in cols)):
                    spec = "identity"
                else:
                    spec = cols
                self._schema_match_cache[key] = spec
            specs.append((df, full, spec))
        if all(s == "identity" for _, _, s in specs):
            self.last_scan_file_stats = {"device": len(specs), "host": 0}
            return self._session.read.parquet(*[p for _, p, _ in specs])
        from ..sql import functions as F
        # files sharing a projection spec share ONE multi-path scan node
        # (a 1000-file table after one rename is one scan + one select,
        # not a 999-deep union chain)
        groups: List[Tuple[Any, List]] = []   # (spec, [paths|data_files])
        for df, full, spec in specs:
            k = spec if isinstance(spec, str) else tuple(spec)
            if groups and groups[-1][0] == k:
                groups[-1][2].append(df if spec == "host" else full)
            else:
                groups.append((k, spec, [df if spec == "host" else full]))
        frames = []
        ndev = nhost = 0
        for _k, spec, members in groups:
            if spec == "host":
                for df in members:
                    frames.append(self._session.create_dataframe(
                        self._read_data_file(df, schema)))
                nhost += len(members)
                continue
            base = self._session.read.parquet(*members)
            ndev += len(members)
            if spec == "identity":
                frames.append(base)
                continue
            sel = []
            for item, (name, _fid, dt) in zip(spec, want):
                if item[0] == "null":
                    sel.append(F.lit(None).cast(dt).alias(name))
                else:
                    sel.append(F.col(item[1]).alias(item[2]))
            frames.append(base.select(*sel))
        out = frames[0]
        for f in frames[1:]:
            out = out.union(f)
        self.last_scan_file_stats = {"device": ndev, "host": nhost}
        return out

    def to_df(self, filters: Sequence[Tuple[str, str, Any]] = (),
              snapshot_id: Optional[int] = None,
              as_of_timestamp_ms: Optional[int] = None):
        """DataFrame over the scan: partitions = data files, so the engine
        parallelizes per-file like FileScanExec."""
        device = self._device_scan_df(filters, snapshot_id,
                                      as_of_timestamp_ms)
        if device is not None:
            return device
        parts = self.scan(filters, snapshot_id, as_of_timestamp_ms)
        if not parts:
            _snap, schema_id = self._select_snapshot(snapshot_id,
                                                     as_of_timestamp_ms)
            schema = self.meta.schema(schema_id).to_struct_type()
            empty = pa.schema([
                pa.field(f.name, T.to_arrow(f.data_type), f.nullable)
                for f in schema.fields]).empty_table()
            return self._session.create_dataframe(empty)
        whole = pa.concat_tables(parts)
        return self._session.create_dataframe(whole, partitions=parts)

    # ------------------------------------------------------------------
    # maintenance
    # ------------------------------------------------------------------
    def history(self) -> List[dict]:
        return [{"version": i, "snapshot_id": s.snapshot_id,
                 "timestamp_ms": s.timestamp_ms,
                 "operation": s.summary.get("operation")}
                for i, s in enumerate(self.meta.snapshots)]

    # --- metadata tables (Spark's `db.table.snapshots` / `.files`) --------
    def snapshots_df(self):
        """The `<table>.snapshots` metadata table as a DataFrame
        (reference exposes these through its Iceberg read path)."""
        rows = {
            "snapshot_id": [], "parent_id": [], "timestamp_ms": [],
            "operation": [], "schema_id": [],
        }
        for s in self.meta.snapshots:
            rows["snapshot_id"].append(s.snapshot_id)
            rows["parent_id"].append(s.parent_id)
            rows["timestamp_ms"].append(s.timestamp_ms)
            rows["operation"].append(s.summary.get("operation"))
            rows["schema_id"].append(s.schema_id)
        return self._session.create_dataframe(pa.table({
            "snapshot_id": pa.array(rows["snapshot_id"], pa.int64()),
            "parent_id": pa.array(rows["parent_id"], pa.int64()),
            "timestamp_ms": pa.array(rows["timestamp_ms"], pa.int64()),
            "operation": pa.array(rows["operation"], pa.string()),
            "schema_id": pa.array(rows["schema_id"], pa.int32()),
        }))

    def files_df(self):
        """The `<table>.files` metadata table: live data files of the
        current snapshot with record counts, sizes and partition values."""
        snap = self.meta.snapshot()
        files = self._live_data_files(snap) if snap is not None else []
        return self._session.create_dataframe(pa.table({
            "file_path": pa.array([f.file_path for f in files],
                                  pa.string()),
            "record_count": pa.array([f.record_count for f in files],
                                     pa.int64()),
            "file_size_bytes": pa.array([f.file_size for f in files],
                                        pa.int64()),
            "partition": pa.array([str(f.partition) for f in files],
                                  pa.string()),
        }))

    def rewrite_data_files(self, target_files: int = 1) -> int:
        """Compaction (`rewrite_data_files` action): concatenate the
        current snapshot's live rows (position deletes applied) into
        ``target_files`` new files and commit a REPLACE snapshot.
        Returns the number of files compacted away."""
        snap = self.meta.snapshot()
        if snap is None:
            return 0
        old_files = self._live_data_files(snap)
        if len(old_files) <= target_files:
            return 0
        schema = self.meta.schema(snap.schema_id)
        parts = self.scan()
        if not parts:
            return 0
        whole = pa.concat_tables(parts)
        n = max(1, int(target_files))
        per = -(-whole.num_rows // n)
        entries: List[ManifestEntry] = []
        for off in range(0, whole.num_rows, per):
            piece = whole.slice(off, min(per, whole.num_rows - off))
            rel = self._write_parquet(piece, schema)
            lower, upper, nulls = self._column_bounds(schema, piece)
            entries.append(ManifestEntry(STATUS_ADDED, 0, DataFile(
                file_path=rel, content=DATA, record_count=piece.num_rows,
                file_size=os.path.getsize(os.path.join(self.path, rel)),
                spec_id=self.meta.spec().spec_id,
                lower_bounds=lower, upper_bounds=upper,
                null_counts=nulls)))
        # REPLACE: no carried manifests — old data + delete files retire
        self._commit_snapshot(entries, [], "replace")
        return len(old_files)

    def expire_snapshots(self, older_than_ms: int) -> int:
        """Drop snapshot metadata older than the cutoff (keeping current);
        returns count removed."""
        cur = self.meta.current_snapshot_id
        before = len(self.meta.snapshots)
        self.meta.snapshots = [
            s for s in self.meta.snapshots
            if s.snapshot_id == cur or s.timestamp_ms >= older_than_ms]
        keep_ids = {s.snapshot_id for s in self.meta.snapshots}
        self.meta.snapshot_log = [
            e for e in self.meta.snapshot_log
            if e["snapshot-id"] in keep_ids]
        removed = before - len(self.meta.snapshots)
        if removed:
            write_table_metadata(self.path, self.meta)
        return removed


_ICE_CACHE: Dict[str, Any] = {}


def ice_to_type_cached(s: str):
    from .metadata import ice_to_type
    v = _ICE_CACHE.get(s)
    if v is None:
        v = _ICE_CACHE[s] = ice_to_type(s)
    return v
