"""Basic physical operators: scan/project/filter/range/union/limit/sample/
expand (reference ``basicPhysicalOperators.scala``, ``GpuExpandExec.scala``,
``limit.scala``)."""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ...columnar.batch import ColumnarBatch
from ...observability import tracer as _trace
from ...columnar.column import DeviceColumn
from ...columnar.encoded import RLEColumn
from ... import types as T
from ..expressions.core import (Alias, AttributeReference, BoundReference,
                                EvalContext, Expression, bind_references)
from ..plan import SortOrder
from .base import (CPU, TPU, PhysicalPlan, ScanColumnCounter,
                   TaskContext)


def _to_backend_batch(batch: ColumnarBatch, backend: str) -> ColumnarBatch:
    """Move a batch's arrays to the target backend (device upload / fetch).
    Fetches go through ONE device_get (concurrent copies — per-leaf pulls
    each cost a full host<->device round trip)."""
    import jax
    import jax.numpy as jnp
    if backend == TPU:
        from ...shims import tree_map
        return tree_map(jnp.asarray, batch)
    return jax.device_get(batch)


def compact_batch(xp, batch: ColumnarBatch, keep) -> ColumnarBatch:
    """Stable-compact live ``keep`` rows to the front (cuDF
    ``apply_boolean_mask`` analog; O(n) cumsum+scatter, no sort)."""
    from ...ops.join import compact_indices
    new_n = xp.sum(keep).astype(xp.int32)
    perm = compact_indices(xp, keep)
    valid = xp.arange(batch.capacity, dtype=xp.int32) < new_n
    cols = tuple(c.gather(perm, valid) for c in batch.columns)
    return ColumnarBatch(batch.names, cols, new_n)


_UPLOAD_CACHE: dict = {}
#: guards the cache maps under concurrent sessions (the serving tier runs
#: N driver threads against this one process-scoped cache); uploads
#: themselves run OUTSIDE the lock, with per-entry events so two sessions
#: scanning the same relation share one decode+upload instead of racing
#: two and dropping one (a lost entry would double HBM residency)
import threading as _threading
_UPLOAD_LOCK = _threading.Lock()


class _PendingUpload:
    __slots__ = ("event", "error")

    def __init__(self):
        self.event = _threading.Event()
        self.error = None


def _cached_upload(table, backend: str, conf=None, chip=None) -> list:
    """Decode+pad+upload a pyarrow table once per (table, backend); repeat
    scans of the same in-memory relation reuse the resident batches (the
    engine-side analog of Spark's InMemoryRelation staying cached — and the
    TPU-idiomatic move: keep hot data in HBM instead of re-uploading).
    Ragged string tables split into width classes first (one long string
    must not make every row pay its padded width).  Thread-safe: the
    entry keyed by (table identity, backend, split/encode params) is
    claimed under a lock and built outside it; concurrent scanners of the
    same relation wait on the builder instead of uploading twice.
    ``chip`` (``parallel/placement.py``: the partition's home chip where
    partitions are spread) is where the batches are uploaded to and kept:
    it is part of the entry's key."""
    import weakref
    from ...config import RAGGED_STRING_SPLIT_BYTES, RapidsConf
    from ...columnar.convert import arrow_to_device, split_for_upload
    # the split decision depends on the threshold conf — key it in, so
    # changing raggedSplitBytes takes effect on already-scanned relations
    thr = int((conf or RapidsConf.get_global())
              .get(RAGGED_STRING_SPLIT_BYTES))
    # the encoded-retention decision changes the cached batches' column
    # representation — key it in, so flipping the encoded kill switch
    # takes effect on already-scanned relations
    from ...columnar.encoded import encode_params
    key = id(table)
    ck = (backend, thr, encode_params(conf)) + (
        () if chip is None else (chip.id,))
    with _UPLOAD_LOCK:
        ent = _UPLOAD_CACHE.get(key)
        if ent is None or ent[0]() is not table:
            ref = weakref.ref(
                table, lambda _r, k=key: _UPLOAD_CACHE.pop(k, None))
            ent = (ref, {})
            _UPLOAD_CACHE[key] = ent
        per_backend = ent[1]
        got = per_backend.get(ck)
        if got is None:
            got = per_backend[ck] = _PendingUpload()
            builder = True
        else:
            builder = False
    if isinstance(got, _PendingUpload):
        if not builder:
            got.event.wait()
            if got.error is not None:
                raise got.error
            with _UPLOAD_LOCK:
                return per_backend[ck]
        try:
            if chip is not None and backend == TPU:
                import jax
                from ...parallel import placement
                # built on the chip, then committed to it (no copy), so
                # every program over these batches runs there
                with jax.default_device(chip), _trace.span(
                        "placement", "upload", chip=placement.label(chip),
                        bytes=table.nbytes):
                    batches = [placement.put(arrow_to_device(p, conf=conf),
                                             chip)
                               for p in split_for_upload(table, conf)]
            else:
                batches = [
                    _to_backend_batch(arrow_to_device(p, conf=conf), backend)
                    for p in split_for_upload(table, conf)]
        except BaseException as e:
            # failed build must not wedge waiters or poison the entry
            with _UPLOAD_LOCK:
                if per_backend.get(ck) is got:
                    del per_backend[ck]
            got.error = e
            got.event.set()
            raise
        from ...memory import retention as _ret
        # resident batches are served to EVERY rescan of this relation:
        # pin them so a downstream fused stage never donates their
        # buffers (memory/retention.py donation-safety contract)
        for b in batches:
            _ret.pin_batch(b)
        with _UPLOAD_LOCK:
            per_backend[ck] = batches
        got.event.set()
        return batches
    return got


class InMemoryScanExec(PhysicalPlan):
    """Scan over pre-partitioned pyarrow tables (Relation leaf +
    HostColumnarToGpu fused: decode on host, upload once).

    ``attrs`` may name only some of the tables' columns (the planner's
    column pruning, ``sql/column_pruning.py``).  The upload cache still holds
    each table whole, once; the scan hands on the named columns of every
    cached batch, a selection on the host that launches nothing, so a column
    nobody reads is never expanded, gathered or exchanged."""

    def __init__(self, attrs, partitions, backend=TPU):
        super().__init__()
        self.backend = backend
        self._attrs = list(attrs)
        self._parts = partitions  # List[pa.Table]
        self._names = tuple(a.name for a in self._attrs)
        self._bytes: Optional[int] = None
        self._columns_counted = ScanColumnCounter()

    @property
    def output(self):
        return self._attrs

    def num_partitions(self):
        return len(self._parts)

    def estimate_bytes(self):
        """The Arrow bytes of the columns handed on: what a join above would
        really move (Catalyst's statistics after ``ColumnPruning``)."""
        if self._bytes is None:
            self._bytes = sum(
                t.nbytes if t.num_columns == len(self._names)
                else sum(t.column(n).nbytes for n in self._names)
                for t in self._parts)
        return self._bytes

    def execute(self, pid: int, tctx: TaskContext):
        from ...parallel.placement import home_chip
        self._columns_counted.count(tctx, len(self._names),
                                    self._parts[0].num_columns)
        for batch in _cached_upload(
                self._parts[pid], self.backend, tctx.conf,
                chip=home_chip(pid, tctx.conf) if self.backend == TPU
                else None):
            if len(batch.names) > len(self._names):
                batch = batch.select(
                    [batch.names.index(n) for n in self._names])
            tctx.inc_metric("scanRleColumns", sum(
                isinstance(c, RLEColumn) for c in batch.columns))
            yield batch

    def simple_string(self):
        return f"{self.node_name()} [{', '.join(a.name for a in self._attrs)}]"


class ProjectExec(PhysicalPlan):
    def __init__(self, exprs: Sequence[Expression], child: PhysicalPlan,
                 backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.exprs = list(exprs)
        self._bound = [bind_references(e, child.output) for e in self.exprs]
        self._out = []
        for e in self.exprs:
            if isinstance(e, Alias):
                self._out.append(e.to_attribute())
            elif isinstance(e, AttributeReference):
                self._out.append(e)
            else:
                self._out.append(AttributeReference(e.sql(), e.data_type,
                                                    e.nullable))
        from .kernel_cache import exprs_key
        # program built lazily on first execute: a fused/discarded plan
        # (whole-stage member, AQE re-plan, CPU fallback) must register
        # nothing in the kernel cache
        self._fn = None
        self._fn_key = (exprs_key(self._bound),
                        tuple(a.name for a in self._out))

    @property
    def output(self):
        return self._out

    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        ctx = EvalContext(batch, xp=self.xp)
        cols = [e.eval(ctx) for e in self._bound]
        return ColumnarBatch(tuple(a.name for a in self._out), tuple(cols),
                             batch.num_rows)

    # --- whole-stage fusion protocol --------------------------------------
    def _fuse_step(self, batch: ColumnarBatch, mask, xp):
        ctx = EvalContext(batch, xp=xp)
        cols = [e.eval(ctx) for e in self._bound]
        return (ColumnarBatch(tuple(a.name for a in self._out), tuple(cols),
                              batch.num_rows), mask)

    def _fuse_key(self):
        from .kernel_cache import exprs_key
        return ("P", exprs_key(self._bound), tuple(a.name for a in self._out))

    def execute(self, pid, tctx):
        fn = self._fn
        if fn is None:
            fn = self._fn = self._jit(self._compute, key=self._fn_key)
        for batch in self.children[0].execute(pid, tctx):
            tctx.inc_metric("stageOpDispatches")
            yield fn(batch)

    def simple_string(self):
        return f"{self.node_name()} [{', '.join(e.sql() for e in self.exprs)}]"


#: expression modules safe for dictionary-space predicate evaluation:
#: deterministic, row-local (value-in -> value-out).  Excluded by absence:
#: context_fns (rand/partition-id/input-file), udf/hive_udf (opaque),
#: aggregates/windows (not row-local), subquery placeholders.
_DICT_FILTER_MODULES = frozenset({
    "core", "predicates", "strings", "arithmetic", "math_fns",
    "conditional", "cast", "regexp", "datetime", "json_fns", "hashing",
    "collections"})


def _dict_filter_plan(bound: Expression, batch: ColumnarBatch):
    """Trace-time eligibility for the filter-on-dictionary fast path: the
    predicate references exactly ONE column, that column arrives
    dict-encoded, and every node is a deterministic row-local expression.
    Returns (ordinal, column) or None."""
    from ...columnar.encoded import DictEncodedColumn
    ords = set()
    stack = [bound]
    while stack:
        e = stack.pop()
        if isinstance(e, BoundReference):
            ords.add(e.ordinal)
            continue
        mod = type(e).__module__.rsplit(".", 1)[-1]
        if mod not in _DICT_FILTER_MODULES:
            return None
        stack.extend(e.children)
    if len(ords) != 1:
        return None
    i = ords.pop()
    col = batch.columns[i]
    if not isinstance(col, DictEncodedColumn):
        return None
    return i, col


class FilterExec(PhysicalPlan):
    """Predicate + row compaction (stable partition of live rows to the
    front, the static-shape analog of cudf ``Table.filter``).

    Dictionary fast path (docs/encoded_columns.md): an eligible predicate
    over one dict-encoded column evaluates ONCE over the dictionary's
    |dict|+1 entries (the spare all-null row supplies the predicate's
    null-input verdict exactly) and each data row just looks its verdict
    up by code — O(|dict|) predicate work instead of O(rows), and the
    selection gather keeps every pass-through column encoded."""

    def __init__(self, condition: Expression, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.condition = condition
        self._bound = bind_references(condition, child.output)
        from ...columnar.encoded import op_enabled
        self._enc_filter = op_enabled("filter")
        from .kernel_cache import expr_key
        # lazy program (see ProjectExec.__init__)
        self._fn = None
        self._fn_key = (expr_key(self._bound), self._enc_filter)

    @property
    def output(self):
        return self.children[0].output

    def _dict_keep(self, batch: ColumnarBatch, xp):
        """Per-row keep verdict via dictionary lookup, or None when the
        fast path does not apply (decided at trace time from the batch's
        static structure)."""
        if not self._enc_filter:
            return None
        plan = _dict_filter_plan(self._bound, batch)
        if plan is None:
            return None
        from ...columnar.column import null_column
        from ...columnar.encoded import _bump
        i, col = plan
        d = col.dictionary
        dcol = d.column
        dcap = dcol.capacity
        child_out = self.children[0].output
        cols = tuple(dcol if j == i else null_column(a.dtype, dcap)
                     for j, a in enumerate(child_out))
        dict_batch = ColumnarBatch.make(
            tuple(a.name for a in child_out), cols, dcap)
        ctx = EvalContext(dict_batch, xp=xp)
        v = self._bound.eval(ctx)
        dict_keep = v.data & v.validity
        # valid rows look up their code's verdict; null rows look up the
        # spare all-null entry at index d.size — the exact null-input
        # verdict of the predicate, whatever its null semantics
        sel = xp.where(col.validity, col.codes, d.size)
        _bump("dict_filters")
        return dict_keep[xp.clip(sel, 0, dcap - 1)]

    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        xp = self.xp
        keep = self._dict_keep(batch, xp)
        if keep is None:
            ctx = EvalContext(batch, xp=xp)
            cond = self._bound.eval(ctx)
            keep = cond.validity & cond.data
        return compact_batch(xp, batch, keep & batch.row_mask())

    # --- whole-stage fusion protocol --------------------------------------
    def _fuse_step(self, batch: ColumnarBatch, mask, xp):
        """Fused filters never compact: the predicate just ANDs into the
        live mask; the stage terminal (agg mask / one final compaction)
        realizes it."""
        keep = self._dict_keep(batch, xp)
        if keep is None:
            ctx = EvalContext(batch, xp=xp)
            cond = self._bound.eval(ctx)
            keep = cond.validity & cond.data
        return batch, mask & keep

    def _fuse_key(self):
        from .kernel_cache import expr_key
        return ("F", expr_key(self._bound), self._enc_filter)

    def execute(self, pid, tctx):
        fn = self._fn
        if fn is None:
            fn = self._fn = self._jit(self._compute, key=self._fn_key)
        for batch in self.children[0].execute(pid, tctx):
            tctx.inc_metric("stageOpDispatches")
            yield fn(batch)

    def simple_string(self):
        return f"{self.node_name()} ({self.condition.sql()})"


class RangeExec(PhysicalPlan):
    def __init__(self, start, end, step, num_slices, backend=TPU,
                 batch_rows: int = 1 << 20):
        super().__init__()
        self.backend = backend
        self.start, self.end, self.step = start, end, step
        self.num_slices = max(1, num_slices)
        self.batch_rows = batch_rows
        self._attrs = [AttributeReference("id", T.LONG, False)]

    @property
    def output(self):
        return self._attrs

    def num_partitions(self):
        return self.num_slices

    def execute(self, pid, tctx):
        from ...columnar.column import bucket_capacity
        total = max(0, -(-(self.end - self.start) // self.step))
        per = -(-total // self.num_slices)
        lo = min(pid * per, total)
        hi = min(lo + per, total)
        xp = self.xp
        pos = lo
        from ...memory.retention import mark_transient
        while pos < hi:
            n = min(self.batch_rows, hi - pos)
            cap = bucket_capacity(n)
            ids = (self.start
                   + (xp.arange(cap, dtype=xp.int64) + pos) * self.step)
            col = DeviceColumn(T.LONG, ids, xp.ones(cap, dtype=bool))
            # freshly generated, single-owner buffers: donation-eligible
            yield mark_transient(ColumnarBatch.make(["id"], [col], n))
            pos += n

    def simple_string(self):
        return f"{self.node_name()} ({self.start}, {self.end}, {self.step})"


class UnionExec(PhysicalPlan):
    def __init__(self, children: Sequence[PhysicalPlan], backend=TPU):
        super().__init__(*children)
        self.backend = backend

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return sum(c.num_partitions() for c in self.children)

    def execute(self, pid, tctx):
        for c in self.children:
            n = c.num_partitions()
            if pid < n:
                out_names = tuple(a.name for a in self.output)
                for b in c.execute(pid, tctx):
                    yield ColumnarBatch(out_names, b.columns, b.num_rows)
                return
            pid -= n
        raise IndexError("partition out of range")


class LocalLimitExec(PhysicalPlan):
    def __init__(self, n: int, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.n = n

    @property
    def output(self):
        return self.children[0].output

    def execute(self, pid, tctx):
        remaining = self.n
        for batch in self.children[0].execute(pid, tctx):
            if remaining <= 0:
                return
            rows = batch.num_rows_int
            if rows <= remaining:
                remaining -= rows
                yield batch
            else:
                yield batch.sliced(0, remaining)
                return

    def simple_string(self):
        return f"{self.node_name()} {self.n}"


class GlobalLimitExec(PhysicalPlan):
    """Single-partition limit with offset (planner inserts a gather-to-one
    exchange below)."""

    def __init__(self, n: int, offset: int, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.n, self.offset = n, offset

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return 1

    def execute(self, pid, tctx):
        skipped = 0
        remaining = self.n
        for batch in self.children[0].execute(pid, tctx):
            rows = batch.num_rows_int
            if skipped < self.offset:
                drop = min(rows, self.offset - skipped)
                skipped += drop
                if drop == rows:
                    continue
                batch = batch.sliced(drop, rows - drop)
                rows = batch.num_rows_int
            if remaining <= 0:
                return
            if rows <= remaining:
                remaining -= rows
                yield batch
            else:
                yield batch.sliced(0, remaining)
                return


class SampleExec(PhysicalPlan):
    """Bernoulli sampling without replacement (reference SampleExec uses
    per-row uniforms; with-replacement via GpuPoissonSampler is host-side)."""

    def __init__(self, lower, upper, seed, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.lower, self.upper, self.seed = lower, upper, seed
        self._fn = (self._jit(self._compute, key=(self.lower, self.upper))
                    if backend == TPU else self._compute)

    @property
    def output(self):
        return self.children[0].output

    def _uniforms(self, batch, pid, batch_idx):
        cap = batch.capacity
        if self.backend == TPU:
            import jax
            key = jax.random.key(self.seed + pid * 1000003 + batch_idx)
            return jax.random.uniform(key, (cap,))
        rng = np.random.default_rng(self.seed + pid * 1000003 + batch_idx)
        return rng.random(cap)

    def _compute(self, batch, u):
        xp = self.xp
        keep = (u >= self.lower) & (u < self.upper) & batch.row_mask()
        return compact_batch(xp, batch, keep)

    def execute(self, pid, tctx):
        for i, batch in enumerate(self.children[0].execute(pid, tctx)):
            u = self._uniforms(batch, pid, i)
            yield self._fn(batch, u) if self.backend == TPU else \
                self._compute(batch, u)


class ExpandExec(PhysicalPlan):
    """N projections per input row (grouping sets / rollup / cube)."""

    def __init__(self, projections, out_attrs, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.projections = [
            [bind_references(e, child.output) for e in proj]
            for proj in projections]
        self._out = list(out_attrs)
        from .kernel_cache import exprs_key
        out_names = tuple(a.name for a in self._out)
        self._fns = [self._jit(self._make_compute(p),
                               key=(exprs_key(p), out_names))
                     for p in self.projections]

    @property
    def output(self):
        return self._out

    def _make_compute(self, bound_proj):
        def compute(batch):
            ctx = EvalContext(batch, xp=self.xp)
            cols = [e.eval(ctx) for e in bound_proj]
            return ColumnarBatch(tuple(a.name for a in self._out),
                                 tuple(cols), batch.num_rows)
        return compute

    def execute(self, pid, tctx):
        for batch in self.children[0].execute(pid, tctx):
            for fn in self._fns:
                yield fn(batch)


class CoalescePartitionsExec(PhysicalPlan):
    """Collapse N partitions into one (CoalesceExec with shuffle=false)."""

    def __init__(self, n: int, child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.n = max(1, n)

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return min(self.n, self.children[0].num_partitions())

    def execute(self, pid, tctx):
        child_n = self.children[0].num_partitions()
        mine = range(pid, child_n, self.num_partitions())
        for cpid in mine:
            yield from self.children[0].execute(cpid, tctx)
