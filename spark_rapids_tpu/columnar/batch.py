"""ColumnarBatch — the unit of execution, analog of the reference's
``ColumnarBatch`` of ``GpuColumnVector`` (``GpuColumnVector.java``) and cuDF
``Table``.  A batch is a set of equally-padded device columns plus a traced
``num_rows`` scalar; the padded capacity is the XLA shape key.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import tracer as _trace
from ..types import DataType, StructField, StructType
from .column import DeviceColumn, bucket_capacity

#: ``num_rows_int`` memo misses of the process, each one blocking readback
#: (``syncReadbacks`` of last_query_metrics is a query's delta); pool and
#: prefetch threads miss too, so the count is taken under a lock
SYNC_STATS = {"readbacks": 0}
_SYNC_LOCK = threading.Lock()


@jax.tree_util.register_pytree_node_class
@dataclass
class ColumnarBatch:
    names: Tuple[str, ...]
    columns: Tuple[DeviceColumn, ...]
    #: traced 0-d int32 — keeps one compiled program per capacity bucket
    num_rows: jnp.ndarray

    def tree_flatten(self):
        return ((self.columns, self.num_rows), self.names)

    @classmethod
    def tree_unflatten(cls, names, leaves):
        columns, num_rows = leaves
        return cls(names, columns, num_rows)

    # --- construction -----------------------------------------------------
    @staticmethod
    def make(names: Sequence[str], columns: Sequence[DeviceColumn],
             num_rows) -> "ColumnarBatch":
        known = None
        if not isinstance(num_rows, jnp.ndarray):
            known = int(num_rows)
            num_rows = jnp.asarray(num_rows, dtype=jnp.int32)
        b = ColumnarBatch(tuple(names), tuple(columns), num_rows)
        if known is not None:
            # host-constructed count: num_rows_int must not pay a device
            # round trip to read back what the host just wrote
            b._nrows_host = known
        return b

    @staticmethod
    def empty(schema: StructType) -> "ColumnarBatch":
        from .column import null_column
        cap = bucket_capacity(0)
        with _trace.eager("batch.empty", columns=len(schema.fields)):
            cols = tuple(null_column(f.data_type, cap)
                         for f in schema.fields)
            return ColumnarBatch.make(schema.names, cols, 0)

    # --- shape ------------------------------------------------------------
    @property
    def num_cols(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_rows_int(self) -> int:
        """Host-side row count.  Forces ONE device sync per batch, then
        memoizes — every sync stalls the host until the device catches
        up, so producers that already know the count on the host
        (two-phase aggregate, slicing) pre-seed it via
        :meth:`with_known_rows`."""
        cached = getattr(self, "_nrows_host", None)
        if cached is None:
            with _trace.span("sync", "batch.num_rows"):
                cached = int(self.num_rows)
            with _SYNC_LOCK:
                SYNC_STATS["readbacks"] += 1
            self._nrows_host = cached
        return cached

    def with_known_rows(self, n: int) -> "ColumnarBatch":
        """Record the host-known row count (skips the sync in
        ``num_rows_int``).  Caller contract: ``n == int(self.num_rows)``."""
        self._nrows_host = int(n)
        return self

    @property
    def num_rows_bound(self) -> int:
        """Host-known UPPER BOUND on the row count, without ever pulling
        from the device: the exact count when known, a producer-recorded
        bound (``with_rows_bound``), else the padded capacity.  Use for
        conservative control-flow decisions (out-of-core engagement,
        coalescing) where a sync per batch would serialize the dispatch
        pipeline."""
        cached = getattr(self, "_nrows_host", None)
        if cached is not None:
            return cached
        bound = getattr(self, "_nrows_bound", None)
        if bound is not None:
            return bound
        return self.capacity

    def with_rows_bound(self, n: int) -> "ColumnarBatch":
        """Record a host-known row-count upper bound (e.g. the speculated
        group-table size) for pull-free sizing decisions."""
        self._nrows_bound = int(n)
        return self

    def row_mask(self) -> jnp.ndarray:
        """bool[capacity]: True for live rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    @property
    def schema(self) -> StructType:
        return StructType(tuple(
            StructField(n, c.dtype, True)
            for n, c in zip(self.names, self.columns)))

    # --- access -----------------------------------------------------------
    def column(self, i) -> DeviceColumn:
        if isinstance(i, str):
            i = self.names.index(i)
        return self.columns[i]

    def with_columns(self, names: Sequence[str],
                     columns: Sequence[DeviceColumn]) -> "ColumnarBatch":
        return ColumnarBatch.make(names, columns, self.num_rows)

    def select(self, indices: Sequence[int]) -> "ColumnarBatch":
        """Some of the columns, the same rows: a new tuple over the same
        arrays, on the host (no program runs)."""
        b = ColumnarBatch.make(
            [self.names[i] for i in indices],
            [self.columns[i] for i in indices], self.num_rows)
        for known in ("_nrows_host", "_nrows_bound"):
            if getattr(self, known, None) is not None:
                setattr(b, known, getattr(self, known))
        return b

    # --- reshaping (host-orchestrated, device-executed) -------------------
    def repadded(self, new_capacity: int) -> "ColumnarBatch":
        with _trace.eager("batch.repadded", columns=self.num_cols):
            cols = tuple(c.slice_capacity(new_capacity)
                         for c in self.columns)
        b = ColumnarBatch(self.names, cols, self.num_rows)
        cached = getattr(self, "_nrows_host", None)
        if cached is not None:
            b._nrows_host = cached
        return b

    #: capacities at or below this skip shrinking entirely: the serializer
    #: ships live rows only, so small padding is free — while the
    #: num_rows sync shrunk() needs costs a full host<->device round trip
    _SHRINK_MIN_CAPACITY = 4096

    def shrunk_capacity(self, num_rows: int) -> int:
        """The capacity :meth:`shrunk` leaves a batch of this capacity
        that holds ``num_rows`` live rows."""
        if self.capacity <= self._SHRINK_MIN_CAPACITY:
            return self.capacity
        return min(bucket_capacity(num_rows), self.capacity)

    def shrunk(self) -> "ColumnarBatch":
        """Drop excess capacity padding down to the row count's bucket.
        Host-side decision (syncs on num_rows); call at exec boundaries
        where the live row count can collapse (post-agg, post-split) so
        downstream kernels/serializers don't chew dead padding."""
        if self.capacity <= self._SHRINK_MIN_CAPACITY:
            return self
        with _trace.eager("batch.shrunk", columns=self.num_cols):
            cap = self.shrunk_capacity(self.num_rows_int)
            return self if cap >= self.capacity else self.repadded(cap)

    def window(self, start, num_rows, capacity: int,
               xp=jnp) -> "ColumnarBatch":
        """Rows ``[start, start + num_rows)`` as a batch of ``capacity``
        (>= num_rows): contiguous copies, for use inside a program —
        ``start`` and ``num_rows`` may be traced int32."""
        live = xp.arange(capacity, dtype=xp.int32) < num_rows
        cols = tuple(c.window(start, capacity, live) for c in self.columns)
        return ColumnarBatch(self.names, cols, num_rows)

    def sliced(self, start: int, length: int) -> "ColumnarBatch":
        """Host-side slice: returns a batch viewing rows [start, start+len).
        Implemented as a gather so the result is bucket-padded."""
        from ..parallel import placement
        with _trace.eager("batch.sliced", columns=self.num_cols):
            n = self.num_rows_int
            length = max(0, min(length, n - start))
            cap = bucket_capacity(length)
            with placement.beside(self.columns):
                idx = jnp.arange(cap, dtype=jnp.int32) + start
                valid = jnp.arange(cap, dtype=jnp.int32) < length
                cols = tuple(c.gather(idx, valid) for c in self.columns)
                return ColumnarBatch.make(self.names, cols, length)

    def gather(self, idx: jnp.ndarray, idx_valid: Optional[jnp.ndarray],
               out_rows) -> "ColumnarBatch":
        cols = tuple(c.gather(idx, idx_valid) for c in self.columns)
        return ColumnarBatch.make(self.names, cols, out_rows)

    @staticmethod
    def concat(batches: Sequence["ColumnarBatch"]) -> "ColumnarBatch":
        """Concatenate batches (cudf ``Table.concatenate`` analog), row
        order = piece order then row order, string widths re-aligned to
        the widest.  Plain device columns go through ONE cached program
        per call (:func:`_concat_program`); column kinds it cannot express
        (:func:`concat_declined`) take the per-array path."""
        if not batches:
            raise ValueError("ColumnarBatch.concat requires at least one batch")
        with _trace.eager("batch.concat", pieces=len(batches)):
            return ColumnarBatch._concat(batches)

    @staticmethod
    def _concat(batches: Sequence["ColumnarBatch"]) -> "ColumnarBatch":
        batches = [b for b in batches if b.num_rows_int > 0] or list(batches[:1])
        if len(batches) == 1:
            return batches[0]
        from ..parallel import placement
        # pieces of several chips: a caller that gathers by its nature has
        # brought them together already; anything else is counted
        batches = placement.gather(batches, terminal=False)
        counts = [b.num_rows_int for b in batches]
        total = sum(counts)
        cap = bucket_capacity(total)
        names = batches[0].names
        if concat_declined(batches):
            CONCAT_STATS["eager"] += 1
            rows = _EagerRows(counts, cap)
            out_cols = tuple(
                _concat_columns([b.columns[ci] for b in batches], rows)
                for ci in range(batches[0].num_cols))
            out = ColumnarBatch.make(names, out_cols, total)
        else:
            CONCAT_STATS["programs"] += 1
            out_cols, num_rows = _concat_program(batches, cap)(
                tuple(tuple(_codes_column(c) for c in b.columns)
                      for b in batches),
                np.asarray(counts, dtype=np.int32))
            out_cols = tuple(_dict_column(c, out) for c, out
                             in zip(batches[0].columns, out_cols))
            out = ColumnarBatch(names, out_cols,
                                num_rows).with_known_rows(total)
        # a real multi-batch concat writes fresh buffers: mark it
        # donation-eligible (memory/retention.py) — EXCEPT when an input
        # was encoded (dictionary objects are shared with the inputs);
        # may_donate declines encoded batches structurally anyway, but an
        # unmarked batch is the cheaper decline
        from ..memory.retention import mark_transient
        from .encoded import DictEncodedColumn, RLEColumn
        if not any(isinstance(c, (DictEncodedColumn, RLEColumn))
                   for c in out_cols):
            mark_transient(out)
        return out

    def __repr__(self) -> str:  # pragma: no cover
        return (f"ColumnarBatch(rows={self.num_rows_int}, cap={self.capacity}, "
                f"cols={list(zip(self.names, [c.dtype for c in self.columns]))})")


#: how :meth:`ColumnarBatch.concat` ran, per call of two or more live
#: pieces: as its one cached program, or per array (``concat_declined``)
CONCAT_STATS = {"programs": 0, "eager": 0}


def concat_declined(batches: Sequence[ColumnarBatch]) -> str:
    """Why ``concat`` cannot run these pieces as one program — ``encoded``
    (dictionaries that differ are unified on the host; RLE and nested
    encoded columns materialize), ``object`` (host nested columns),
    ``numpy`` (host-backend arrays) — or ``""`` when it can.  Read off
    the columns themselves."""
    from .encoded import same_dictionary
    for cols in zip(*(b.columns for b in batches)):
        if same_dictionary(cols):
            continue    # the codes concatenate like any int32 column
        for c in cols:
            why = _column_declined(c)
            if why:
                return why
    return ""


def _column_declined(col: DeviceColumn) -> str:
    from .encoded import DictEncodedColumn, RLEColumn
    if isinstance(col, (DictEncodedColumn, RLEColumn)):
        return "encoded"
    for arr in (col.data, col.validity, col.lengths, col.aux):
        if arr is None or isinstance(arr, jax.Array):
            continue
        return "object" if getattr(arr, "dtype", None) == object else "numpy"
    for child in col.children:
        why = _column_declined(child)
        if why:
            return why
    return ""


def _column_signature(col: DeviceColumn) -> Tuple:
    """What of a plain device column shapes the concat program besides
    its capacity: types, string width, array slot width (by the child's
    rows), presence of lengths/aux."""
    return (repr(col.dtype),
            None if col.data is None
            else (str(col.data.dtype),) + tuple(col.data.shape[1:]),
            col.lengths is not None, col.aux is not None,
            tuple((ch.capacity, _column_signature(ch))
                  for ch in col.children))


def _codes_column(col: DeviceColumn) -> DeviceColumn:
    """A dict-encoded column as the program sees it: its codes, a plain
    int32 column (the dictionary stays outside, shared, uncopied)."""
    from ..types import IntegerType
    from .encoded import DictEncodedColumn
    if isinstance(col, DictEncodedColumn):
        return DeviceColumn(IntegerType(), col.codes, col.validity)
    return col


def _dict_column(first: DeviceColumn, out: DeviceColumn) -> DeviceColumn:
    """The program's output column, over ``first``'s dictionary again
    where the pieces were dict-encoded."""
    from .encoded import DictEncodedColumn, _bump
    if isinstance(first, DictEncodedColumn):
        _bump("concat_unified")
        return DictEncodedColumn(first.dtype, out.data, first.dictionary,
                                 out.validity)
    return out


def _concat_program(batches: Sequence[ColumnarBatch], out_capacity: int):
    """The cached program that concatenates pieces of these shapes:
    ``(columns of each piece, int32[k] live row counts) -> (columns,
    total rows)``.  The counts are data, so they are an operand."""
    from ..sql.physical.kernel_cache import cached_jit
    caps = tuple(b.capacity for b in batches)
    sig = tuple(tuple(_column_signature(_codes_column(c)) for c in b.columns)
                for b in batches)

    def concat(pieces, counts):
        rows = _TracedRows(counts, caps, out_capacity)
        cols = tuple(_concat_columns([p[ci] for p in pieces], rows)
                     for ci in range(len(pieces[0])))
        return cols, rows.total

    return cached_jit(("ColumnarBatch", "concat", sig, caps, out_capacity),
                      concat)


class _EagerRows:
    """Row placement for the per-array path: every piece is sliced to its
    live rows on the host's word, concatenated and padded — three eager
    launches per array per call."""

    def __init__(self, counts: Sequence[int], out_capacity: int):
        self.counts = list(counts)
        self.out_capacity = out_capacity

    def scaled(self, width: int) -> "_EagerRows":
        return _EagerRows([n * width for n in self.counts],
                          self.out_capacity * width)

    def cat(self, arrs, fill):
        if getattr(arrs[0], "dtype", None) == object:  # host nested columns
            return _concat_object(arrs, self.counts, self.out_capacity)
        cat = jnp.concatenate([a[:n] for a, n in zip(arrs, self.counts)],
                              axis=0)
        pad = ([(0, self.out_capacity - cat.shape[0])]
               + [(0, 0)] * (cat.ndim - 1))
        return jnp.pad(cat, pad, constant_values=fill)


class _TracedRows:
    """Row placement inside the concat program: ``counts`` is a traced
    int32[k].  Each piece is written whole (at its full capacity) at the
    running offset of the live rows before it, in piece order, so the
    dead tail of one piece is overwritten by the next piece's rows; rows
    at and past the total get the fill."""

    def __init__(self, counts, caps: Tuple[int, ...], out_capacity: int):
        self.counts = counts
        self.caps = caps
        self.out_capacity = out_capacity
        self.offsets = jnp.cumsum(counts, dtype=jnp.int32) - counts
        self.total = jnp.sum(counts, dtype=jnp.int32)
        self.live = jnp.arange(out_capacity, dtype=jnp.int32) < self.total

    def scaled(self, width: int) -> "_TracedRows":
        return _TracedRows(self.counts * width,
                           tuple(c * width for c in self.caps),
                           self.out_capacity * width)

    def cat(self, arrs, fill):
        tail = arrs[0].shape[1:]
        # room past the end: a piece written at its offset never clamps
        buf = jnp.full((self.out_capacity + max(self.caps),) + tail, fill,
                       dtype=arrs[0].dtype)
        for i, a in enumerate(arrs):
            buf = jax.lax.dynamic_update_slice_in_dim(
                buf, a, self.offsets[i], axis=0)
        live = self.live.reshape((-1,) + (1,) * len(tail))
        return jnp.where(live, buf[:self.out_capacity],
                         jnp.asarray(fill, dtype=buf.dtype))


def _concat_columns(cols: Sequence[DeviceColumn], rows) -> DeviceColumn:
    """One output column from the pieces' columns; ``rows`` places the
    arrays (:class:`_EagerRows` or, inside the program, :class:`_TracedRows`)."""
    from .column import DeviceColumn as DC
    from .encoded import DictEncodedColumn, try_concat_dict_columns
    if any(isinstance(c, DictEncodedColumn) for c in cols):
        if all(isinstance(c, DictEncodedColumn) for c in cols):
            enc = try_concat_dict_columns(cols, rows.counts,
                                          rows.out_capacity)
            if enc is not None:
                return enc
        # mixed / over-budget: fall through — the .data/.lengths property
        # accesses below materialize the encoded pieces (decline path)
    dtype = cols[0].dtype
    if cols[0].is_array_like:
        # align slot widths, then concat children at width-scaled counts
        # (each parent row owns a contiguous width-sized child block)
        width = max(c.array_width for c in cols)
        cols = [c.with_array_width(width) for c in cols]
        child_rows = rows.scaled(width)
        children = tuple(
            _concat_columns([c.children[k] for c in cols], child_rows)
            for k in range(len(cols[0].children)))
        return DC(dtype, None, rows.cat([c.validity for c in cols], False),
                  rows.cat([c.lengths for c in cols], 0), None, children)
    if cols[0].data is None:  # struct
        children = tuple(
            _concat_columns([c.children[k] for c in cols], rows)
            for k in range(len(cols[0].children)))
        return DC(dtype, None, rows.cat([c.validity for c in cols], False),
                  children=children)
    datas = [c.data for c in cols]
    if datas[0].ndim == 2:
        width = max(d.shape[1] for d in datas)
        datas = [jnp.pad(d, ((0, 0), (0, width - d.shape[1]))) if d.shape[1] < width
                 else d for d in datas]
    data = rows.cat(datas, 0)
    validity = rows.cat([c.validity for c in cols], False)
    lengths = (rows.cat([c.lengths for c in cols], 0)
               if cols[0].lengths is not None else None)
    aux = (rows.cat([c.aux for c in cols], 0)
           if cols[0].aux is not None else None)
    return DC(dtype, data, validity, lengths, aux)


def _concat_object(arrs, counts, out_capacity):
    out = np.empty(out_capacity, dtype=object)
    pos = 0
    for a, n in zip(arrs, counts):
        out[pos:pos + n] = np.asarray(a)[:n]
        pos += n
    return out
