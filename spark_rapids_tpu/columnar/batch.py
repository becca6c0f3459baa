"""ColumnarBatch — the unit of execution, analog of the reference's
``ColumnarBatch`` of ``GpuColumnVector`` (``GpuColumnVector.java``) and cuDF
``Table``.  A batch is a set of equally-padded device columns plus a traced
``num_rows`` scalar; the padded capacity is the XLA shape key.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..types import DataType, StructField, StructType
from .column import DeviceColumn, bucket_capacity


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
        cols = tuple(null_column(f.data_type, cap) for f in schema.fields)
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
            cached = int(self.num_rows)
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
        return ColumnarBatch.make(
            [self.names[i] for i in indices],
            [self.columns[i] for i in indices], self.num_rows)

    # --- reshaping (host-orchestrated, device-executed) -------------------
    def repadded(self, new_capacity: int) -> "ColumnarBatch":
        cols = tuple(c.slice_capacity(new_capacity) for c in self.columns)
        b = ColumnarBatch(self.names, cols, self.num_rows)
        cached = getattr(self, "_nrows_host", None)
        if cached is not None:
            b._nrows_host = cached
        return b

    #: capacities at or below this skip shrinking entirely: the serializer
    #: ships live rows only, so small padding is free — while the
    #: num_rows sync shrunk() needs costs a full host<->device round trip
    _SHRINK_MIN_CAPACITY = 4096

    def shrunk(self) -> "ColumnarBatch":
        """Drop excess capacity padding down to the row count's bucket.
        Host-side decision (syncs on num_rows); call at exec boundaries
        where the live row count can collapse (post-agg, post-split) so
        downstream kernels/serializers don't chew dead padding."""
        if self.capacity <= self._SHRINK_MIN_CAPACITY:
            return self
        cap = bucket_capacity(self.num_rows_int)
        if cap >= self.capacity:
            return self
        return self.repadded(cap)

    def sliced(self, start: int, length: int) -> "ColumnarBatch":
        """Host-side slice: returns a batch viewing rows [start, start+len).
        Implemented as a gather so the result is bucket-padded."""
        n = self.num_rows_int
        length = max(0, min(length, n - start))
        cap = bucket_capacity(length)
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
        """Concatenate batches (cudf ``Table.concatenate`` analog).  Uses a
        gather per input into a fresh bucket so string widths re-align."""
        if not batches:
            raise ValueError("ColumnarBatch.concat requires at least one batch")
        batches = [b for b in batches if b.num_rows_int > 0] or list(batches[:1])
        if len(batches) == 1:
            return batches[0]
        total = sum(b.num_rows_int for b in batches)
        cap = bucket_capacity(total)
        out_cols: List[DeviceColumn] = []
        names = batches[0].names
        for ci in range(batches[0].num_cols):
            pieces = [b.columns[ci] for b in batches]
            out_cols.append(_concat_columns(pieces, [b.num_rows_int for b in batches], cap))
        out = ColumnarBatch.make(names, out_cols, total)
        # a real multi-batch concat gathers into fresh buffers: mark it
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


def _concat_columns(cols: Sequence[DeviceColumn], counts: Sequence[int],
                    out_capacity: int) -> DeviceColumn:
    from .column import DeviceColumn as DC
    from .encoded import DictEncodedColumn, try_concat_dict_columns
    if any(isinstance(c, DictEncodedColumn) for c in cols):
        if all(isinstance(c, DictEncodedColumn) for c in cols):
            enc = try_concat_dict_columns(cols, counts, out_capacity)
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
        children = tuple(
            _concat_columns([c.children[k] for c in cols],
                            [n * width for n in counts],
                            out_capacity * width)
            for k in range(len(cols[0].children)))
        validity = _concat_1d([c.validity for c in cols], counts,
                              out_capacity, False)
        lengths = _concat_1d([c.lengths for c in cols], counts,
                             out_capacity, 0)
        return DC(dtype, None, validity, lengths, None, children)
    if cols[0].data is None:  # struct
        children = tuple(
            _concat_columns([c.children[k] for c in cols], counts, out_capacity)
            for k in range(len(cols[0].children)))
        validity = _concat_1d([c.validity for c in cols], counts, out_capacity, False)
        return DC(dtype, None, validity, children=children)
    datas = [c.data for c in cols]
    if datas[0].ndim == 2:
        width = max(d.shape[1] for d in datas)
        datas = [jnp.pad(d, ((0, 0), (0, width - d.shape[1]))) if d.shape[1] < width
                 else d for d in datas]
    data = _concat_nd(datas, counts, out_capacity)
    validity = _concat_1d([c.validity for c in cols], counts, out_capacity, False)
    lengths = (_concat_1d([c.lengths for c in cols], counts, out_capacity, 0)
               if cols[0].lengths is not None else None)
    aux = (_concat_1d([c.aux for c in cols], counts, out_capacity, 0)
           if cols[0].aux is not None else None)
    return DC(dtype, data, validity, lengths, aux)


def _concat_1d(arrs, counts, out_capacity, fill):
    if getattr(arrs[0], "dtype", None) == object:  # host nested columns
        return _concat_object(arrs, counts, out_capacity)
    live = [a[:n] for a, n in zip(arrs, counts)]
    cat = jnp.concatenate(live) if live else arrs[0][:0]
    pad = out_capacity - cat.shape[0]
    return jnp.pad(cat, (0, pad), constant_values=fill)


def _concat_nd(arrs, counts, out_capacity):
    if getattr(arrs[0], "dtype", None) == object:  # host nested columns
        return _concat_object(arrs, counts, out_capacity)
    live = [a[:n] for a, n in zip(arrs, counts)]
    cat = jnp.concatenate(live, axis=0) if live else arrs[0][:0]
    pad = [(0, out_capacity - cat.shape[0])] + [(0, 0)] * (cat.ndim - 1)
    return jnp.pad(cat, pad)


def _concat_object(arrs, counts, out_capacity):
    import numpy as np
    out = np.empty(out_capacity, dtype=object)
    pos = 0
    for a, n in zip(arrs, counts):
        out[pos:pos + n] = np.asarray(a)[:n]
        pos += n
    return out
