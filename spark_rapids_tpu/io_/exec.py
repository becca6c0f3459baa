"""File scan exec with the reference's multi-file reader strategies
(``GpuMultiFileReader.scala:176-373``): PERFILE (one file per batch),
MULTITHREADED (thread-pool prefetch, cloud-friendly), COALESCING (combine
small files into one batch before upload).  Parquet reads add the
reference's host-side scan pipeline: path replacement + file cache
(``filecache.py``), footer-statistics row-group pruning against pushed
filter conjuncts (``pushdown.py``; ``GpuParquetScan.scala:2765``), and
chunked multi-batch reads (``spark.rapids.sql.reader.chunked``,
``RapidsConf.scala:568``).

A scan the planner narrowed (``ScanRelation.narrowed``,
``sql/column_pruning.py``) reads, decodes and uploads the columns of its
output and no other, on every path below: a file's columns are found by
their position in the whole schema, as the whole scan binds them."""

from __future__ import annotations

import threading
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional

import numpy as np

from .. import types as T
from ..columnar.convert import arrow_to_device
from ..config import (CSV_DEVICE_DECODE, JSON_DEVICE_DECODE,
                      MULTITHREAD_READ_NUM_THREADS,
                      ORC_DEVICE_DECODE, PARQUET_DEVICE_DECODE,
                      PARQUET_PUSHDOWN_ENABLED, PARQUET_READER_TYPE,
                      READER_CHUNKED, READER_CHUNKED_TARGET_ROWS,
                      RapidsConf)
from ..observability import tracer as _trace
from ..sql.physical.base import (CPU, TPU, PhysicalPlan, ScanColumnCounter,
                                 TaskContext)
from . import registry
from .filecache import resolve_read_path


#: ``FileScanExec._read``: cut the file to its own columns at the output's
#: positions
_OWN = object()


class FileScanExec(PhysicalPlan):
    def __init__(self, node, backend=TPU, conf: Optional[RapidsConf] = None,
                 files_per_partition: int = 1):
        super().__init__()
        self.backend = backend
        self.node = node
        self.conf = conf or RapidsConf.get_global()
        self.files = registry.expand_paths(node.paths)
        self.reader_type = str(self.conf.get(PARQUET_READER_TYPE)).upper()
        if self.reader_type == "AUTO":
            self.reader_type = "MULTITHREADED" if len(self.files) > 1 else "PERFILE"
        self._pool: Optional[ThreadPoolExecutor] = None
        self._pool_lock = threading.Lock()
        #: (col, op, literal) conjuncts attached by the planner from a
        #: scan-adjacent filter; used for row-group pruning only — the
        #: device filter above still applies the full predicate
        self.pushed_filters: List = []
        #: positions of the whole schema this scan hands on (None: all)
        self._keep = node.columns
        self._columns_counted = ScanColumnCounter()

    @property
    def output(self):
        return self.node.output

    def _names_in(self, file_names) -> Optional[List[str]]:
        """A file's own names of the columns this scan hands on, in the
        output's order (None: the file is read whole)."""
        if self._keep is None:
            return None
        return [file_names[i] for i in self._keep]

    def _narrow(self, table):
        """What a reader with no ``columns=`` of its own read, cut to the
        output's columns before upload."""
        if self._keep is None:
            return table
        return table.select(list(self._keep))

    def num_partitions(self):
        if self.reader_type == "COALESCING":
            return 1
        return max(1, len(self.files))

    def _read(self, path, tctx: Optional[TaskContext] = None,
              wanted=_OWN):
        """One file read on the host and cut to the output's columns: this
        file's own (``_OWN``), or those of the names ``wanted`` that it has
        (None: the file whole) where COALESCING lines files up by name."""
        path = resolve_read_path(path, self.conf)
        fmt = self.node.fmt
        names = None
        if fmt in ("parquet", "orc") and wanted is not None \
                and self._keep is not None:
            # a reader with a ``columns=`` of its own decodes no other
            have = registry.schema_names(fmt, path)
            names = self._names_in(have) if wanted is _OWN \
                else [n for n in wanted if n in have]
        if fmt == "parquet" and self.pushed_filters and \
                bool(self.conf.get(PARQUET_PUSHDOWN_ENABLED)):
            import pyarrow.parquet as pq
            from .pushdown import prune_row_groups
            pf = pq.ParquetFile(path)
            keep = prune_row_groups(pf, self.pushed_filters)
            if keep is not None:
                self._emit_prune_stats(
                    (pf.metadata.num_row_groups, len(keep)), tctx)
                if not keep:
                    empty = pf.schema_arrow.empty_table()
                    return empty if names is None else empty.select(names)
                return self._host_decode(pf, keep, names=names)
        with _trace.span("scan", "host_decode", fmt=fmt):
            table = registry.read_file(fmt, path, self.node.options,
                                       columns=names)
        if names is None and wanted is _OWN:
            table = self._narrow(table)
        return table

    def _read_chunked_orc(self, path, tctx: TaskContext):
        """ORC chunked reads: one pa.Table per stripe run up to the
        chunk-row target (pyarrow exposes per-stripe reads but not stripe
        statistics, so there is no ORC pruning — parity note vs parquet)."""
        import pyarrow as pa
        import pyarrow.orc as orc
        path = resolve_read_path(path, self.conf)
        f = orc.ORCFile(path)
        names = self._names_in(f.schema.names)
        if tctx is not None:
            tctx.inc_metric("orcStripesTotal", f.nstripes)
        target = int(self.conf.get(READER_CHUNKED_TARGET_ROWS))
        run: List = []
        rows = 0
        for i in range(f.nstripes):
            run.append(pa.Table.from_batches(
                [f.read_stripe(i, columns=names)]))
            rows += run[-1].num_rows
            if rows >= target:
                yield pa.concat_tables(run)
                run, rows = [], 0
        if run:
            yield pa.concat_tables(run)
        if f.nstripes == 0:
            yield f.read(columns=names)

    def _read_chunked(self, path, tctx: TaskContext):
        """Yield one pa.Table per run of row groups up to the chunk-row
        target (parquet PERFILE path only): peak memory is bounded by the
        chunk, not the file."""
        path = resolve_read_path(path, self.conf)
        pf, runs, prune_stats = self._parquet_runs(path)
        self._emit_prune_stats(prune_stats, tctx)
        if not runs:
            yield self._narrow(pf.schema_arrow.empty_table())
            return
        names = self._names_in(pf.schema_arrow.names)
        for run in runs:
            yield self._host_decode(pf, run, names=names)

    @staticmethod
    def _host_decode(pf, run, declined: str = "", names=None):
        """pyarrow's read of one row-group run (of the columns ``names``;
        None: all), as its own span: the caller hands the table to
        ``upload`` afterwards, so decode and H2D are told apart
        (``declined``: why the device decoder gave the run up, '' when it
        was never asked)."""
        from .device_parquet import chunk_bytes
        with _trace.span("scan", "host_decode", row_groups=len(run),
                         bytes=chunk_bytes(pf.metadata, run, names),
                         declined=declined):
            return pf.read_row_groups(run, columns=names)

    def _parquet_runs(self, path: str):
        """The ONE implementation of prune-then-split for parquet reads
        (both the host chunked path and the device-decode path use it, so
        the two can't drift): pushdown pruning, then row-group runs sized
        by the chunked-read row target (a single run when chunked reads
        are off).  Returns ``(pf, runs, prune_stats)`` with prune_stats
        either None or ``(total_groups, kept_groups)`` — the caller that
        commits to a path emits the metrics exactly once."""
        with _trace.span("scan", "footer"):
            import pyarrow.parquet as pq
            pf = pq.ParquetFile(path)
            keep = None
            stats = None
            if self.pushed_filters and bool(
                    self.conf.get(PARQUET_PUSHDOWN_ENABLED)):
                from .pushdown import prune_row_groups
                keep = prune_row_groups(pf, self.pushed_filters)
                if keep is not None:
                    stats = (pf.metadata.num_row_groups, len(keep))
            groups = list(range(pf.metadata.num_row_groups)) \
                if keep is None else keep
            if not bool(self.conf.get(READER_CHUNKED)):
                return pf, ([groups] if groups else []), stats
            target = int(self.conf.get(READER_CHUNKED_TARGET_ROWS))
            runs: List[List[int]] = []
            run: List[int] = []
            rows = 0
            for rg in groups:
                run.append(rg)
                rows += pf.metadata.row_group(rg).num_rows
                if rows >= target:
                    runs.append(run)
                    run, rows = [], 0
            if run:
                runs.append(run)
            return pf, runs, stats

    @staticmethod
    def _emit_prune_stats(stats, tctx: Optional[TaskContext]) -> None:
        if stats is not None and tctx is not None:
            total, kept = stats
            tctx.inc_metric("rowGroupsTotal", total)
            tctx.inc_metric("rowGroupsPruned", total - kept)

    def _execute_parquet_device(self, path: str, tctx: TaskContext,
                                upload):
        """Unified parquet partition executor when device decode is on:
        ONE footer parse + prune (``_parquet_runs``), then per-run device
        decode with per-run host fallback — the fallback reuses the open
        ``pf`` and goes through ``upload`` so the ragged-string width-class
        splitting applies exactly as on the host pipeline."""
        import jax

        from .device_parquet import chunk_bytes, decode_file

        path = resolve_read_path(path, self.conf)
        pf, runs, prune_stats = self._parquet_runs(path)
        self._emit_prune_stats(prune_stats, tctx)
        chunked = bool(self.conf.get(READER_CHUNKED))
        if not runs:
            yield from upload(self._narrow(pf.schema_arrow.empty_table()))
            return
        from . import decode_stats as DS
        names = self._names_in(pf.schema_arrow.names)
        declined = False   # a whole-file decline holds for every run
        for run in runs:
            if chunked:
                tctx.inc_metric("chunkedReadBatches")
            run_bytes = chunk_bytes(pf.metadata, run, names)
            batch = None
            if not declined:
                with _trace.span("scan", "device_decode", bytes=run_bytes,
                                 row_groups=len(run)):
                    batch = decode_file(path, run, tctx, pf=pf,
                                        conf=self.conf, columns=names)
            if batch is None:
                reason = DS.record_declined(
                    "parquet", run_bytes,
                    reason="prior-decline" if declined else None)
                declined = True
                yield from upload(self._host_decode(pf, run, reason, names))
            else:
                DS.record_engaged("parquet", run_bytes)
                yield batch if self.backend != CPU \
                    else jax.device_get(batch)

    def _execute_orc_device(self, path: str, tctx: TaskContext, upload):
        """ORC partition executor when device decode is on: stripe-run
        batching per the chunked-read target, per-run device decode with
        per-run host fallback (mirrors ``_execute_parquet_device``)."""
        import jax
        import pyarrow as pa
        import pyarrow.orc as pa_orc

        from .device_orc import decode_file

        path = resolve_read_path(path, self.conf)
        f = pa_orc.ORCFile(path)
        names = self._names_in(f.schema.names)
        if tctx is not None:
            tctx.inc_metric("orcStripesTotal", f.nstripes)
        stripes = list(range(f.nstripes))
        if not stripes:
            yield from upload(f.read(columns=names))
            return
        if bool(self.conf.get(READER_CHUNKED)):
            target = int(self.conf.get(READER_CHUNKED_TARGET_ROWS))
            runs: List[List[int]] = []
            run: List[int] = []
            # pyarrow exposes only file-level nrows, so batch stripes by
            # the average rows-per-stripe (uniform-stripe approximation)
            per = max(1, target // max(1, f.nrows // max(f.nstripes, 1)))
            for s in stripes:
                run.append(s)
                if len(run) >= per:
                    runs.append(run)
                    run = []
            if run:
                runs.append(run)
        else:
            runs = [stripes]
        from . import decode_stats as DS
        import os as _os
        try:
            fsize = _os.path.getsize(path)
        except OSError:
            fsize = 0
        declined = False
        for run in runs:
            if len(runs) > 1:
                tctx.inc_metric("chunkedReadBatches")
            run_bytes = fsize * len(run) // max(f.nstripes, 1)
            batch = None
            if not declined:
                with _trace.span("scan", "device_decode", bytes=run_bytes,
                                 stripes=len(run)):
                    batch = decode_file(
                        path, run if len(runs) > 1 else None, tctx,
                        orc_file=f, conf=self.conf, columns=names)
            if batch is None:
                reason = DS.record_declined(
                    "orc", run_bytes,
                    reason="prior-decline" if declined else None)
                declined = True
                with _trace.span("scan", "host_decode", bytes=run_bytes,
                                 stripes=len(run), declined=reason):
                    if len(runs) > 1:
                        table = pa.concat_tables(
                            [pa.Table.from_batches(
                                [f.read_stripe(s, columns=names)])
                             for s in run])
                    else:
                        table = f.read(columns=names)
                yield from upload(table)
            else:
                DS.record_engaged("orc", run_bytes)
                if self.backend == CPU:
                    batch = jax.device_get(batch)
                yield batch

    def _coalescing_device(self, infos, schema0, tctx: TaskContext,
                           upload):
        """COALESCING with device decode: decode each (pruned) file and
        concat on device.  Ragged-string fallbacks that split into width
        classes stay separate batches — re-concatenating them into one
        max-width matrix would rebuild exactly the blow-up the split
        exists to prevent."""
        import jax

        from ..columnar.batch import ColumnarBatch
        from .device_parquet import chunk_bytes, decode_file
        batches = []
        extra = []
        names = self._names_in(schema0.names)   # every file's schema
        for path, pf, groups, prune_stats in infos:
            self._emit_prune_stats(prune_stats, tctx)
            if not groups:
                continue
            from . import decode_stats as DS
            nb = chunk_bytes(pf.metadata, groups, names)
            with _trace.span("scan", "device_decode", bytes=nb,
                             row_groups=len(groups)):
                batch = decode_file(path, groups, tctx, pf=pf,
                                    conf=self.conf, columns=names)
            if batch is None:
                reason = DS.record_declined("parquet", nb)
                pieces = upload(self._host_decode(pf, groups, reason,
                                                  names))
                if len(pieces) == 1:
                    batches.append(pieces[0])
                else:
                    extra.extend(pieces)
            else:
                DS.record_engaged("parquet", nb)
                batches.append(batch)
        if batches:
            tctx.inc_metric("coalescedDeviceConcat")
            out = ColumnarBatch.concat(batches)
            if self.backend == CPU:
                out = jax.device_get(out)
            yield out
        elif not extra:
            # everything pruned away: same empty-schema batch the host
            # path produces
            yield from upload(self._narrow(schema0.empty_table()))
        yield from extra

    def _text_device_scan(self, pid, tctx, upload, opts, decode_fn,
                          host_read_fn, **narrowing):
        """Shared read-decode-decline protocol for the text-format device
        parsers (CSV and JSON): read the bytes once, try the device
        decoder, and on decline re-parse the SAME bytes on host — no
        second disk/cloud read.  Yields the batches and returns True when
        this path served the partition; False (unreadable file / decoder
        wants the full host machinery) lets the caller's host path run
        and raise its own errors."""
        import io as _io

        import jax
        path = resolve_read_path(self.files[pid], self.conf)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return False
        from . import decode_stats as DS
        fmt = registry._normalize_fmt(self.node.fmt, opts)
        batch = decode_fn(path, opts, self.node.output, tctx, self.conf,
                          raw=raw, **narrowing)
        if batch is not None:
            DS.record_engaged(fmt, len(raw))
            if self.backend == CPU:
                batch = jax.device_get(batch)
            yield batch
            return True
        DS.record_declined(fmt, len(raw))
        for piece in upload(self._narrow(
                host_read_fn(_io.BytesIO(raw), opts))):
            yield piece
        return True

    def execute(self, pid: int, tctx: TaskContext):
        import jax

        read = len(self.node.output)
        self._columns_counted.count(tctx, read,
                                    self.node.file_width or read)

        def upload_one(table):
            batch = arrow_to_device(table, conf=self.conf)
            if self.backend == CPU:
                batch = jax.device_get(batch)
            return batch

        def upload(table):
            """One batch per string-width class (split_for_upload);
            single-batch for the overwhelmingly common case."""
            from ..columnar.convert import split_for_upload
            pieces = split_for_upload(table, self.conf)
            if len(pieces) > 1:
                tctx.inc_metric("raggedStringSplits")
            return [upload_one(p) for p in pieces]

        if self.reader_type == "COALESCING":
            import pyarrow as pa
            # device decode per file + device concat (round 5): small
            # files combine ON DEVICE; per-file declines host-read and
            # join the same concat.  Footer-only schema agreement is
            # checked BEFORE any decode (a late mismatch must not throw
            # completed device work away); mismatched schemas take the
            # host promote-concat path below.
            if self.node.fmt == "parquet" and self.files and bool(
                    self.conf.get(PARQUET_DEVICE_DECODE)):
                infos = []
                schema0 = None
                ok = True
                for p in self.files:
                    path = resolve_read_path(p, self.conf)
                    try:
                        # honors pushdown row-group pruning, like _read
                        pf, runs, prune_stats = self._parquet_runs(path)
                    except OSError:
                        ok = False
                        break
                    if schema0 is None:
                        schema0 = pf.schema_arrow
                    elif pf.schema_arrow != schema0:
                        ok = False  # promotion needed: host concat path
                        break
                    groups = [g for run in runs for g in run]
                    infos.append((path, pf, groups, prune_stats))
                if ok:
                    yield from self._coalescing_device(infos, schema0,
                                                       tctx, upload)
                    return
            # the promote-concat lines files up by name: each is asked for
            # the first file's names of the output's columns, where its
            # reader can select; what is left is cut after the concat
            wanted = None
            if self._keep is not None and self.files and \
                    self.node.fmt in ("parquet", "orc"):
                wanted = self._names_in(registry.schema_names(
                    self.node.fmt,
                    resolve_read_path(self.files[0], self.conf)))
            n_threads = int(self.conf.get(MULTITHREAD_READ_NUM_THREADS))
            with ThreadPoolExecutor(max_workers=n_threads) as pool:
                tables = list(pool.map(
                    lambda p: self._read(p, tctx, wanted), self.files))
            if tables:
                table = pa.concat_tables(tables, promote_options="default")
                yield from upload(self._narrow(table) if wanted is None
                                  else table.select(wanted))
            return

        if pid >= len(self.files):
            return
        # input_file_name()/block expressions read these off the task
        # (reference InputFileName gated by InputFileBlockRule)
        tctx.input_file = self.files[pid]
        tctx.input_block_start = 0
        try:
            import os as _os
            tctx.input_block_length = _os.path.getsize(self.files[pid])
        except OSError:
            tctx.input_block_length = -1
        # device decode covers PERFILE and MULTITHREADED parquet scans
        # (COALESCING concatenates host tables first); with it, the heavy
        # per-value work is on the device, so losing the host-decode
        # prefetch overlap in the MULTITHREADED case is a win, not a loss
        if self.node.fmt == "parquet" and bool(
                self.conf.get(PARQUET_DEVICE_DECODE)):
            yield from self._execute_parquet_device(self.files[pid], tctx,
                                                    upload)
            return
        if self.node.fmt == "orc" and bool(
                self.conf.get(ORC_DEVICE_DECODE)):
            yield from self._execute_orc_device(self.files[pid], tctx,
                                                upload)
            return
        opts = dict(self.node.options)
        text_fmt = registry._normalize_fmt(self.node.fmt, opts)
        if text_fmt == "csv" and bool(self.conf.get(CSV_DEVICE_DECODE)):
            from .device_csv import decode_file as _decode
            # a line's fields are found by position; JSON's by name
            done = yield from self._text_device_scan(
                pid, tctx, upload, opts, _decode,
                registry.read_csv_source, columns=self._keep,
                width=self.node.file_width)
            if done:
                return
        if text_fmt == "json" and bool(self.conf.get(JSON_DEVICE_DECODE)):
            from .device_json import decode_file as _decode
            done = yield from self._text_device_scan(
                pid, tctx, upload, opts, _decode,
                registry.read_json_source)
            if done:
                return
        if self.reader_type == "MULTITHREADED":
            # per-partition prefetch through a shared pool: submit this file
            # read on a worker thread so decode overlaps device compute.
            # Lazy init is locked: under the parallel partition scheduler
            # several partitions race in here, and a lost pool would leak
            # its threads for the process lifetime.
            if self._pool is None:
                with self._pool_lock:
                    if self._pool is None:
                        self._pool = ThreadPoolExecutor(
                            max_workers=int(self.conf.get(
                                MULTITHREAD_READ_NUM_THREADS)))
            fut = self._pool.submit(self._read, self.files[pid], tctx)
            yield from upload(fut.result())
            return
        if self.node.fmt == "parquet" and bool(
                self.conf.get(READER_CHUNKED)):
            for table in self._read_chunked(self.files[pid], tctx):
                tctx.inc_metric("chunkedReadBatches")
                yield from upload(table)
            return
        if self.node.fmt == "orc" and bool(self.conf.get(READER_CHUNKED)):
            for table in self._read_chunked_orc(self.files[pid], tctx):
                tctx.inc_metric("chunkedReadBatches")
                yield from upload(table)
            return
        yield from upload(self._read(self.files[pid], tctx))

    def simple_string(self):
        extra = ""
        if self.pushed_filters:
            fs = ", ".join(f"{c} {op} {v!r}" for c, op, v in
                           self.pushed_filters)
            extra = f" pushed=[{fs}]"
        if self._keep is not None:
            extra += (f" columns=[{', '.join(a.name for a in self.output)}]"
                      f" of {self.node.file_width}")
        return (f"{self.node_name()} {self.node.fmt} "
                f"[{len(self.files)} files, {self.reader_type}]{extra}")
