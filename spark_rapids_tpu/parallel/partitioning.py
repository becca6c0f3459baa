"""Partitioning strategies — the 4 ``part[...]`` rules of the reference
(``GpuOverrides.scala:3682``; impls ``GpuHashPartitioningBase.scala``,
``GpuRangePartitioner.scala``, ``GpuRoundRobinPartitioning.scala``,
``GpuSinglePartitioning.scala``).

Each returns a per-row int32 partition id column; the exchange splits rows by
id with compaction gathers (the static-shape analog of cudf
``Table.contiguousSplit``).  Hash partitioning is murmur3+pmod — bit-equal to
Spark's, so shuffles land rows exactly where CPU Spark would.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from ..columnar.batch import ColumnarBatch
from ..columnar.column import DeviceColumn
from ..ops.sorting import sort_permutation
from ..sql.expressions.core import EvalContext, Expression, bind_references
from ..sql.expressions.hashing import Murmur3Hash


class Partitioning:
    num_partitions: int = 1

    def bind(self, attrs):
        return self

    def partition_ids(self, ctx: EvalContext, batch: ColumnarBatch, pid: int):
        """-> int32[capacity] target partition per row."""
        raise NotImplementedError

    def simple_string(self):
        return f"{type(self).__name__}({self.num_partitions})"


class SinglePartitioning(Partitioning):
    num_partitions = 1

    def partition_ids(self, ctx, batch, pid):
        return ctx.xp.zeros(batch.capacity, dtype=ctx.xp.int32)


class HashPartitioning(Partitioning):
    def __init__(self, exprs: Sequence[Expression], num_partitions: int):
        self.exprs = list(exprs)
        self.num_partitions = num_partitions
        self._hash = Murmur3Hash(*self.exprs)

    def bind(self, attrs):
        p = HashPartitioning([bind_references(e, attrs) for e in self.exprs],
                             self.num_partitions)
        return p

    def partition_ids(self, ctx, batch, pid):
        xp = ctx.xp
        h = self._hash.eval(ctx).data  # int32
        n = xp.asarray(self.num_partitions, dtype=xp.int32)
        r = h % n
        return xp.where(r < 0, r + n, r)  # pmod


class RoundRobinPartitioning(Partitioning):
    def __init__(self, num_partitions: int):
        self.num_partitions = num_partitions

    def partition_ids(self, ctx, batch, pid):
        xp = ctx.xp
        idx = xp.arange(batch.capacity, dtype=xp.int32)
        return (idx + xp.asarray(pid, dtype=xp.int32)) % self.num_partitions


class RangePartitioning(Partitioning):
    """Range partitioning for global sort.  Bounds are computed by the
    exchange from a sample of the input (reference GpuRangePartitioner)."""

    def __init__(self, orders, num_partitions: int):
        from ..sql.plan import SortOrder
        self.orders = list(orders)
        self.num_partitions = num_partitions
        self._bounds_batch: Optional[ColumnarBatch] = None

    def bind(self, attrs):
        from ..sql.plan import SortOrder
        p = RangePartitioning(
            [SortOrder(bind_references(o.child, attrs), o.ascending,
                       o.nulls_first) for o in self.orders],
            self.num_partitions)
        return p

    def set_bounds(self, bounds_batch: ColumnarBatch):
        """bounds_batch: one row per boundary (num_partitions-1 rows),
        sorted; columns = sort key values."""
        self._bounds_batch = bounds_batch

    def partition_ids(self, ctx, batch, pid):
        # binary-search-free approach: count how many bounds each row is
        # greater than -> partition id.  O(n_bounds) vector compares.
        from ..sql.expressions.predicates import compare_columns
        from .. import types as T
        xp = ctx.xp
        assert self._bounds_batch is not None, "range bounds not set"
        key_cols = [o.child.eval(ctx) for o in self.orders]
        nb = self._bounds_batch.num_rows_int
        pid_out = xp.zeros(batch.capacity, dtype=xp.int32)
        # a string key's byte matrix and its bounds' at one width: the
        # bounds come from a sample of every map output, this batch from
        # one of them
        bounds = list(self._bounds_batch.columns[:len(self.orders)])
        for ci, bc in enumerate(bounds):
            col = key_cols[ci]
            if bc.data is None or bc.data.ndim < 2 \
                    or bc.data.shape[1] == col.data.shape[1]:
                continue
            w = max(int(bc.data.shape[1]), int(col.data.shape[1]))
            key_cols[ci], bounds[ci] = (
                DeviceColumn(c.dtype, xp.pad(
                    c.data, ((0, 0), (0, w - int(c.data.shape[1])))),
                    c.validity, c.lengths, c.aux, c.children)
                for c in (col, bc))
        for b in range(nb):
            gt = xp.zeros(batch.capacity, dtype=bool)
            decided = xp.zeros(batch.capacity, dtype=bool)
            for ci, o in enumerate(self.orders):
                col = key_cols[ci]
                bc = bounds[ci]
                bval = DeviceColumn(
                    bc.dtype,
                    None if bc.data is None else
                    xp.broadcast_to(bc.data[b][None, ...] if bc.data.ndim > 1
                                    else bc.data[b], col.data.shape),
                    xp.broadcast_to(bc.validity[b], col.validity.shape),
                    None if bc.lengths is None else
                    xp.broadcast_to(bc.lengths[b], col.lengths.shape),
                    None if bc.aux is None else
                    xp.broadcast_to(bc.aux[b], col.aux.shape))
                lt, eq, gtc = compare_columns(
                    None or ctx, col, bval, T.is_floating(col.dtype))
                if not o.ascending:
                    lt, gtc = gtc, lt
                # null ordering — applied AFTER the direction swap, since
                # nulls_first is a sort-POSITION property: a null key must
                # override the data-compare of its zeroed backing storage
                # in BOTH directions (caught by the pandas-oracle sorts)
                cn, bn = ~col.validity, ~bval.validity
                if o.nulls_first:
                    lt = xp.where(cn & ~bn, True, lt)
                    gtc = xp.where(cn & ~bn, False, gtc)
                    gtc = xp.where(~cn & bn, True, gtc)
                    lt = xp.where(~cn & bn, False, lt)
                else:
                    lt = xp.where(~cn & bn, True, lt)
                    gtc = xp.where(~cn & bn, False, gtc)
                    gtc = xp.where(cn & ~bn, True, gtc)
                    lt = xp.where(cn & ~bn, False, lt)
                eq = xp.where(cn & bn, True, eq & col.validity & bval.validity)
                gt = gt | (~decided & gtc)
                decided = decided | gtc | lt
            pid_out = pid_out + gt.astype(xp.int32)
        return pid_out
