"""Window exec — the analog of ``GpuWindowExec.scala`` (SURVEY §2.3).

The planner guarantees the child is hash-partitioned on the partition keys
and sorted by (partition, order).  This exec concatenates the partition's
batches (the reference's RequireSingleBatch / double-pass strategy;
``GpuCachedDoublePassWindowIterator:1720``) and computes every window
expression with static-shape kernels:

* segment/peer bounds from boundary flags + cumulative min/max scans,
* frame bounds as per-row [start, end) index ranges (ROWS arithmetic /
  RANGE via order-key searchsorted with a per-segment composite offset),
* aggregations as prefix-sum differences or sparse-table range queries.
"""

from __future__ import annotations

from typing import List, Sequence

import numpy as np

from ... import types as T
from ...columnar.batch import ColumnarBatch
from ...columnar.column import DeviceColumn
from ...observability import tracer as _trace
from ...ops import window_ops as W
from ...ops.ranks import column_sort_keys
from ..expressions import aggregates as AGG
from ..expressions.core import (Alias, EvalContext, bind_references)
from ..expressions.windows import (CURRENT_ROW, CumeDist, DenseRank, Lag,
                                   Lead, NTile, NthValue, PercentRank, Rank,
                                   RankLike, RowNumber, UNBOUNDED_FOLLOWING,
                                   UNBOUNDED_PRECEDING, WindowExpression,
                                   WindowFrame)
from ..plan import SortOrder
from .base import TPU, PhysicalPlan, count_stage_dispatch


def _select_column(xp, mask, a: DeviceColumn, b: DeviceColumn) -> DeviceColumn:
    """Row-wise select: a where mask else b.  Handles the 2-D byte-matrix
    string layout (aligning widths) and fixed-width columns."""
    if a.data is not None and a.data.ndim == 2:
        wa, wb = a.data.shape[1], b.data.shape[1]
        w = max(wa, wb)
        da = xp.pad(a.data, ((0, 0), (0, w - wa))) if wa < w else a.data
        db = xp.pad(b.data, ((0, 0), (0, w - wb))) if wb < w else b.data
        data = xp.where(mask[:, None], da, db)
    elif a.data is not None:
        data = xp.where(mask, a.data, b.data)
    else:
        data = None
    validity = xp.where(mask, a.validity, b.validity)
    lengths = None if a.lengths is None else xp.where(mask, a.lengths,
                                                      b.lengths)
    aux = None if a.aux is None else xp.where(mask, a.aux, b.aux)
    children = tuple(_select_column(xp, mask, ca, cb)
                     for ca, cb in zip(a.children, b.children))
    return DeviceColumn(a.dtype, data, validity, lengths, aux, children)


def _minmax_identity(xp, dtype: T.DataType, is_min: bool):
    if isinstance(dtype, (T.FloatType, T.DoubleType)):
        return xp.inf if is_min else -xp.inf
    info = np.iinfo(dtype.np_dtype)
    return info.max if is_min else info.min


class WindowExec(PhysicalPlan):
    def __init__(self, window_exprs: Sequence[Alias],
                 partition_spec, order_spec: Sequence[SortOrder],
                 child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.window_exprs = list(window_exprs)
        self.partition_spec = list(partition_spec)
        self.order_spec = list(order_spec)
        out = child.output
        self._bound_exprs = [
            Alias(bind_references(a.child, out), a.name, a.expr_id)
            for a in self.window_exprs]
        self._bound_parts = [bind_references(e, out)
                             for e in self.partition_spec]
        self._bound_orders = [
            SortOrder(bind_references(o.child, out), o.ascending,
                      o.nulls_first) for o in self.order_spec]
        #: whole-stage window terminal (docs/whole_stage.md): the
        #: planner-inserted partition sort below this node, absorbed by
        #: fuse_stages so single-chunk inputs evaluate chain + sort +
        #: window in ONE program (kept as an exec for the key-batched
        #: large-input fallback)
        self._sorter = None
        self._in_attrs = None
        # programs built lazily on first use (whole-stage laziness
        # contract — plan construction registers nothing)
        self._fn_cache = None
        self._fused_fn_cache = None
        self._boundary_fn_cache = None

    def _win_key(self):
        from .kernel_cache import exprs_key
        return (exprs_key(a.child for a in self._bound_exprs),
                tuple(a.name for a in self.window_exprs),
                exprs_key(self._bound_parts),
                exprs_key(self._bound_orders))

    @property
    def _fn(self):
        if self._fn_cache is None:
            self._fn_cache = self._jit(self._compute, key=self._win_key())
        return self._fn_cache

    @property
    def _fused_fn(self):
        """All-in-one stage-terminal program: absorbed chain + compaction
        + partition sort + window evaluation, one launch.  Correct only
        for a single key-complete chunk (sorting inside the program is
        then exactly the planner's sort) — the caller guarantees it."""
        if self._fused_fn_cache is None:
            def impl(batch):
                return self._compute(self._sorter._stage_compute(batch))
            self._fused_fn_cache = self._jit(
                impl,
                key=("wstage",) + self._win_key() + self._sorter._fuse_sig())
        return self._fused_fn_cache

    def can_absorb_sort(self, sort_exec) -> bool:
        """The sort below must be exactly the partition sort the planner
        inserts for this window — (partition keys asc nulls-first, then
        the order spec) — or absorbing it would change what the window's
        segment scan sees."""
        from .kernel_cache import exprs_key
        want = exprs_key(
            [SortOrder(e, True, True) for e in self._bound_parts]
            + self._bound_orders)
        return exprs_key(sort_exec._bound) == want

    def absorb_sort(self, sort_exec) -> None:
        """Absorb the planner's partition sort (fusion.py window
        terminal).  The sort exec is retained to drive the key-batched
        fallback for inputs too large for one chunk."""
        self._sorter = sort_exec
        self._in_attrs = list(sort_exec.output)
        self.children = tuple(sort_exec.children)
        self._fn_cache = None
        self._fused_fn_cache = None
        self._boundary_fn_cache = None

    @property
    def output(self):
        base = (self._in_attrs if self._sorter is not None
                else list(self.children[0].output))
        return list(base) + [
            a.to_attribute() for a in self.window_exprs]

    # ------------------------------------------------------------------
    def _partition_seg_keys(self, ctx, live):
        """Sort-key words identifying the row's window PARTITION — the
        one recipe shared by the compute kernel and the key-batching cut
        scan, so chunk boundaries can never disagree with segments."""
        xp = ctx.xp
        seg_keys: List = [(~live).astype(xp.int64)]
        for e in self._bound_parts:
            c = e.eval(ctx)
            seg_keys.append((~c.validity).astype(xp.int64))
            seg_keys.extend(column_sort_keys(xp, c))
        return seg_keys

    def _launch(self, fn, batch: ColumnarBatch, tctx) -> ColumnarBatch:
        """One window program over one key-complete batch: the span and the
        counters of every launch.  Rows and partitions stay on the device
        until the task has ended (``inc_metric_late``)."""
        count_stage_dispatch()
        with _trace.span("window", "compute"):
            out, partitions = fn(batch)
        tctx.inc_metric_late("windowRows", out.num_rows)
        tctx.inc_metric_late("windowPartitions", partitions)
        return out

    def _compute(self, batch: ColumnarBatch):
        """(the batch with the window columns appended, the number of
        window partitions among its live rows)."""
        xp = self.xp
        ctx = EvalContext(batch, xp=xp)
        n = batch.capacity
        idx = xp.arange(n, dtype=xp.int32)
        live = idx < batch.num_rows

        # --- segment (partition) and peer (order-tie) bounds -----------
        seg_keys = self._partition_seg_keys(ctx, live)
        is_seg_start = W.boundary_flags(xp, seg_keys)
        seg_start, seg_end = W.segment_bounds(xp, is_seg_start)

        order_cols = [o.child.eval(ctx) for o in self._bound_orders]
        peer_keys = list(seg_keys)
        for c in order_cols:
            peer_keys.append((~c.validity).astype(xp.int64))
            peer_keys.extend(column_sort_keys(xp, c))
        is_peer_start = W.boundary_flags(xp, peer_keys)
        peer_start, peer_end = W.segment_bounds(xp, is_peer_start)

        seg_len = seg_end - seg_start
        pos = idx - seg_start

        new_cols = []
        for alias in self._bound_exprs:
            wexpr: WindowExpression = alias.child  # type: ignore
            fn = wexpr.function
            frame = wexpr.spec.effective_frame(fn)
            col = self._eval_window_fn(
                ctx, fn, frame, idx, live, seg_start, seg_end, seg_len, pos,
                peer_start, peer_end, is_peer_start, order_cols)
            new_cols.append(col.mask_dead_rows(live))

        names = tuple(a.name for a in self.output)
        partitions = xp.sum(is_seg_start & live, dtype=xp.int32)
        return ColumnarBatch(names, tuple(batch.columns) + tuple(new_cols),
                             batch.num_rows), partitions

    # ------------------------------------------------------------------
    def _frame_bounds(self, frame: WindowFrame, xp, idx, seg_start, seg_end,
                      peer_start, peer_end, order_cols):
        """Per-row [start, end) row-index range for the frame."""
        if frame.frame_type == "rows":
            if frame.lower == UNBOUNDED_PRECEDING:
                fs = seg_start
            else:
                fs = xp.clip(idx + frame.lower, seg_start, seg_end)
            if frame.upper == UNBOUNDED_FOLLOWING:
                fe = seg_end
            else:
                fe = xp.clip(idx + frame.upper + 1, seg_start, seg_end)
            return fs, xp.maximum(fe, fs)

        # RANGE frame
        lo, up = frame.lower, frame.upper
        simple = {UNBOUNDED_PRECEDING: "up", UNBOUNDED_FOLLOWING: "uf",
                  CURRENT_ROW: "cur"}
        if lo in simple and up in simple:
            fs = seg_start if lo == UNBOUNDED_PRECEDING else peer_start
            fe = peer_end if up == CURRENT_ROW else seg_end
            return fs, xp.maximum(fe, fs)

        # numeric RANGE offsets over the single numeric order key.  Integral
        # keys stay in exact int64 arithmetic (epoch-micro timestamps exceed
        # float64's 2^53 integer range); floats use float64.
        oc = order_cols[0]
        asc = self._bound_orders[0].ascending
        integral = not isinstance(oc.dtype, (T.FloatType, T.DoubleType))
        seg_id = xp.cumsum(W.boundary_flags(
            xp, [seg_start.astype(xp.int64)]).astype(xp.int64)) - 1
        if integral:
            v = oc.data.astype(xp.int64)
            v = v if asc else -v
            big = xp.asarray(np.iinfo(np.int64).max, xp.int64)
            vmax = xp.max(xp.where(oc.validity, v, -big))
            vmin = xp.min(xp.where(oc.validity, v, big))
            has_valid = xp.any(oc.validity)
            vmax = xp.where(has_valid, vmax, 0)
            vmin = xp.where(has_valid, vmin, 0)
            pad = (abs(int(lo)) if lo not in simple else 0) + \
                  (abs(int(up)) if up not in simple else 0) + 1
            span = (vmax - vmin) + 2 * pad
            null_v = (vmin - pad) if self._bound_orders[0].nulls_first \
                else (vmax + pad)
            comp = xp.where(oc.validity, v, null_v) + seg_id * span
        else:
            v = oc.data.astype(xp.float64)
            v = v if asc else -v
            vmax = xp.max(xp.where(oc.validity, v, -xp.inf))
            vmin = xp.min(xp.where(oc.validity, v, xp.inf))
            pad = (abs(lo) if lo not in simple else 0) + \
                  (abs(up) if up not in simple else 0) + 1.0
            span = xp.where(xp.isfinite(vmax - vmin), vmax - vmin, 0.0) \
                + 2 * pad
            # null order rows sit at whichever end the sort put them; give
            # them a composite value beyond the live range on that side
            null_v = (vmin - pad) if self._bound_orders[0].nulls_first \
                else (vmax + pad)
            null_v = xp.where(xp.isfinite(null_v), null_v, 0.0)
            comp = xp.where(oc.validity, v, null_v) + \
                seg_id.astype(xp.float64) * span

        if lo == UNBOUNDED_PRECEDING:
            fs = seg_start
        elif lo == CURRENT_ROW:
            fs = peer_start
        else:
            # v is already direction-normalized (negated for desc), so the
            # offset applies unchanged in v-space
            fs = xp.searchsorted(comp, comp + lo, side="left"
                                 ).astype(xp.int32)
            fs = xp.clip(fs, seg_start, seg_end)
        if up == UNBOUNDED_FOLLOWING:
            fe = seg_end
        elif up == CURRENT_ROW:
            fe = peer_end
        else:
            fe = xp.searchsorted(comp, comp + up, side="right"
                                 ).astype(xp.int32)
            fe = xp.clip(fe, seg_start, seg_end)
        # null order rows keep their peer group as the frame
        fs = xp.where(oc.validity, fs, peer_start)
        fe = xp.where(oc.validity, fe, peer_end)
        return fs, xp.maximum(fe, fs)

    # ------------------------------------------------------------------
    def _eval_window_fn(self, ctx, fn, frame, idx, live, seg_start, seg_end,
                        seg_len, pos, peer_start, peer_end, is_peer_start,
                        order_cols):
        xp = self.xp

        if isinstance(fn, RankLike):
            if isinstance(fn, RowNumber):
                return DeviceColumn(T.INT, (pos + 1).astype(xp.int32),
                                    live)
            if isinstance(fn, Rank):
                return DeviceColumn(
                    T.INT, (peer_start - seg_start + 1).astype(xp.int32), live)
            if isinstance(fn, DenseRank):
                cpeer = xp.cumsum(is_peer_start.astype(xp.int32))
                dr = cpeer - cpeer[xp.clip(seg_start, 0, None)] + 1
                return DeviceColumn(T.INT, dr.astype(xp.int32), live)
            if isinstance(fn, PercentRank):
                rank = (peer_start - seg_start).astype(xp.float64)
                denom = xp.maximum(seg_len - 1, 1).astype(xp.float64)
                pr = xp.where(seg_len > 1, rank / denom, 0.0)
                return DeviceColumn(T.DOUBLE, pr, live)
            if isinstance(fn, CumeDist):
                cd = (peer_end - seg_start).astype(xp.float64) / \
                    xp.maximum(seg_len, 1).astype(xp.float64)
                return DeviceColumn(T.DOUBLE, cd, live)
            if isinstance(fn, NTile):
                nt = fn.n
                c = seg_len.astype(xp.int64)
                bs = c // nt
                r = c % nt
                cut = r * (bs + 1)
                p = pos.astype(xp.int64)
                in_big = p < cut
                bucket = xp.where(
                    in_big, p // xp.maximum(bs + 1, 1),
                    r + (p - cut) // xp.maximum(bs, 1))
                return DeviceColumn(T.INT, (bucket + 1).astype(xp.int32),
                                    live)
            raise NotImplementedError(type(fn).__name__)

        if isinstance(fn, (Lead, Lag)):
            val = fn.child.eval(ctx)
            target = idx + fn.offset_sign * fn.offset
            ok = (target >= seg_start) & (target < seg_end)
            out = val.gather(xp.clip(target, 0, idx.shape[0] - 1), ok)
            if fn.default is not None:
                from ..expressions.core import literal_column
                d = literal_column(ctx, val.dtype, fn.default)
                out = _select_column(xp, ok, out, d)
            return out

        fs, fe = self._frame_bounds(frame, xp, idx, seg_start, seg_end,
                                    peer_start, peer_end, order_cols)

        if isinstance(fn, NthValue):
            val = fn.child.eval(ctx)
            if fn.ignore_nulls:
                cs = xp.cumsum(val.validity.astype(xp.int32))
                cspad = xp.concatenate([xp.zeros((1,), xp.int32), cs])
                target_cnt = cspad[fs] + fn.n
                j = xp.searchsorted(cs, target_cnt, side="left"
                                    ).astype(xp.int32)
                ok = j < fe
            else:
                j = fs + fn.n - 1
                ok = j < fe
            return val.gather(xp.clip(j, 0, idx.shape[0] - 1), ok)

        if isinstance(fn, AGG.Count):
            if not fn.children:
                cnt = (fe - fs).astype(xp.int64)
            else:
                val = fn.children[0].eval(ctx)
                cnt = W.frame_count(xp, val.validity, fs, fe)
            return DeviceColumn(T.LONG, cnt, live)

        if isinstance(fn, AGG.Sum):
            val = fn.children[0].eval(ctx)
            dt = fn.data_type
            s = W.frame_sum(xp, val.data, val.validity, fs, fe,
                            out_dtype=dt.np_dtype, seg_start=seg_start)
            has = W.frame_count(xp, val.validity, fs, fe) > 0
            return DeviceColumn(dt, s, has)

        if isinstance(fn, AGG.Average):
            val = fn.children[0].eval(ctx)
            s = W.frame_sum(xp, val.data.astype(xp.float64), val.validity,
                            fs, fe, out_dtype=xp.float64,
                            seg_start=seg_start)
            c = W.frame_count(xp, val.validity, fs, fe)
            avg = s / xp.maximum(c, 1).astype(xp.float64)
            return DeviceColumn(T.DOUBLE, avg, c > 0)

        if isinstance(fn, (AGG.Min, AGG.Max)):
            val = fn.children[0].eval(ctx)
            is_min = isinstance(fn, AGG.Min)
            ident = _minmax_identity(xp, val.dtype, is_min)
            red = W.frame_min if is_min else W.frame_max
            out, has = red(xp, val.data, val.validity, fs, fe, ident)
            return DeviceColumn(val.dtype, out.astype(val.data.dtype), has)

        if isinstance(fn, AGG._FirstLast):
            val = fn.children[0].eval(ctx)
            is_first = isinstance(fn, AGG.First)
            if fn.ignore_nulls:
                finder = (W.frame_first_valid_index if is_first
                          else W.frame_last_valid_index)
                j, ok = finder(xp, val.validity, fs, fe)
            else:
                j = fs if is_first else fe - 1
                ok = fe > fs
                j = xp.clip(j, 0, idx.shape[0] - 1)
            return val.gather(j, ok)

        raise NotImplementedError(
            f"window function {type(fn).__name__} not supported")

    # ------------------------------------------------------------------
    # --- key-batched out-of-core path ---------------------------------
    def _boundary_fn(self):
        """(last partition start <= limit, first partition start > 0) of
        a sorted batch — the two cut candidates for key-complete
        chunking.  -1 / num_rows when absent."""
        def impl(batch, limit):
            xp = self.xp
            ctx = EvalContext(batch, xp=xp)
            n = batch.capacity
            idx = xp.arange(n, dtype=xp.int32)
            live = idx < batch.num_rows
            is_start = W.boundary_flags(
                xp, self._partition_seg_keys(ctx, live)) & live
            last_le = xp.max(xp.where(is_start & (idx <= limit), idx, -1))
            first_gt = xp.min(xp.where(is_start & (idx > 0), idx,
                                       batch.num_rows))
            return last_le, first_gt
        if self._boundary_fn_cache is None:
            from .kernel_cache import exprs_key
            self._boundary_fn_cache = self._jit(
                impl, key=("wbound", exprs_key(self._bound_parts)))
        return self._boundary_fn_cache

    def _execute_key_batched(self, pid, tctx, target: int, source=None):
        """Process sorted input in key-complete chunks (reference
        ``GpuKeyBatchingIterator.scala``): every chunk holds whole
        partitions and at most ~``target`` rows (grown to the largest
        single partition when one exceeds it), with carried tails held
        spillable between chunks."""
        from ...memory.retry import with_retry
        from ...memory.spill import (ACTIVE_BATCHING_PRIORITY,
                                     ACTIVE_ON_DECK_PRIORITY,
                                     SpillableColumnarBatch)
        boundary = self._boundary_fn()
        carry: List[SpillableColumnarBatch] = []
        carry_rows = 0

        def split_at_partition(sb):
            """SplitAndRetryOOM handler: a head batch holds WHOLE window
            partitions, so cutting at an interior partition boundary
            halves the work without breaking any frame (row-halving, the
            generic splitter, would).  A single-partition head cannot
            split — spill everything and requeue it for a plain retry
            (split_spillable_in_half's unsplittable convention; bounded
            by the retry cap)."""
            b = sb.get()
            m = b.num_rows_int
            last_le, first_gt = boundary(b, np.int32(max(m // 2 - 1, 0)))
            cut = int(last_le)
            if cut <= 0:
                # a hot partition spans past the midpoint: cut right
                # after it instead (same fallback emit_chunks uses)
                cut = int(first_gt)
            if cut <= 0 or cut >= m:
                sb.catalog.spill_all_device()
                return [sb]
            out = [SpillableColumnarBatch.create(
                       b.sliced(0, cut), ACTIVE_ON_DECK_PRIORITY),
                   SpillableColumnarBatch.create(
                       b.sliced(cut, m - cut), ACTIVE_ON_DECK_PRIORITY)]
            sb.close()
            return out

        def run_window(s):
            return self._launch(self._fn, s.get(), tctx)

        def process(head):
            sb = SpillableColumnarBatch.create(head,
                                               ACTIVE_ON_DECK_PRIORITY)
            return with_retry([sb], run_window,
                              split=split_at_partition)

        def emit_chunks(final: bool):
            nonlocal carry, carry_rows
            while carry_rows >= target:
                pieces = [sb.get() for sb in carry]
                merged = (ColumnarBatch.concat(pieces)
                          if len(pieces) > 1 else pieces[0])
                m = merged.num_rows_int
                last_le, first_gt = boundary(
                    merged, np.int32(min(target, m - 1)))
                cut = int(last_le)
                if cut <= 0:
                    cut = int(first_gt)  # first partition exceeds target
                if cut <= 0 or cut >= m:
                    # one partition spans the whole carry: grow.  Keep the
                    # CONCATENATED batch as the single carry piece so the
                    # next round doesn't re-merge and re-scan these rows
                    # (a P-row partition would otherwise cost O(P^2))
                    if len(carry) > 1:
                        for sb in carry:
                            sb.close()
                        carry = [SpillableColumnarBatch.create(
                            merged, ACTIVE_BATCHING_PRIORITY)]
                    break
                head = merged.sliced(0, cut)
                tail = merged.sliced(cut, m - cut)
                for sb in carry:
                    sb.close()
                carry = [SpillableColumnarBatch.create(
                    tail, ACTIVE_BATCHING_PRIORITY)]
                carry_rows = m - cut
                tctx.inc_metric("windowKeyBatches")
                yield from process(head)
            if final and carry:
                pieces = [sb.get() for sb in carry]
                merged = (ColumnarBatch.concat(pieces)
                          if len(pieces) > 1 else pieces[0])
                for sb in carry:
                    sb.close()
                carry, carry_rows = [], 0
                tctx.inc_metric("windowKeyBatches")
                yield from process(merged)

        if source is None:
            source = self.children[0].execute(pid, tctx)
        try:
            for batch in source:
                n = batch.num_rows_int
                if n == 0:
                    continue
                carry.append(SpillableColumnarBatch.create(
                    batch, ACTIVE_BATCHING_PRIORITY))
                carry_rows += n
                yield from emit_chunks(final=False)
            yield from emit_chunks(final=True)
        finally:
            for sb in carry:
                sb.close()

    def execute(self, pid, tctx):
        from ...config import WINDOW_BATCH_TARGET_ROWS
        target = int(tctx.conf.get(WINDOW_BATCH_TARGET_ROWS))
        if self._sorter is not None:
            yield from self._execute_stage_terminal(pid, tctx, target)
            return
        if self._bound_parts:
            yield from self._execute_key_batched(pid, tctx, target)
            return
        # no partition keys: every row is one global window partition —
        # key batching cannot cut anywhere
        batches = list(self.children[0].execute(pid, tctx))
        if not batches:
            return
        merged = (ColumnarBatch.concat(batches) if len(batches) > 1
                  else batches[0])
        yield self._launch(self._fn, merged, tctx)

    def _execute_stage_terminal(self, pid, tctx, target: int):
        """Sort/window stage terminal: the absorbed partition sort (and
        any chain absorbed into it) rides the window's program.  A
        single key-complete chunk — the whole input fits ``target`` rows,
        or there are no partition keys to cut on — evaluates chain +
        sort + window in ONE launch; larger inputs run the sort's stage
        program once and feed the sorted stream to the key-complete
        chunker (still dropping every per-op boundary dispatch)."""
        s = self._sorter
        # re-sync like FusedStageExec._execute_terminal: planner rewrites
        # above this node must stay visible to the retained sort
        s.children = self.children
        batches = list(self.children[0].execute(pid, tctx))
        if not batches:
            return
        total = sum(b.num_rows_bound for b in batches)
        if not self._bound_parts or total <= target:
            merged = (ColumnarBatch.concat(batches) if len(batches) > 1
                      else batches[0])
            tctx.inc_metric("windowStageFusedBatches")
            yield self._launch(self._fused_fn, merged, tctx)
            return
        yield from self._execute_key_batched(
            pid, tctx, target, source=s.execute_batches(batches, tctx))

    def simple_string(self):
        s = (f"{self.node_name()} "
             f"[{', '.join(a.child.sql() for a in self.window_exprs)}]")
        if self._sorter is not None:
            s += f" [fusedSort: {self._sorter.simple_string()}]"
        return s


class WindowGroupLimitExec(PhysicalPlan):
    """Rank-limit pushdown (reference: shim ``WindowGroupLimitExec``,
    Spark 3.5+, merged via ``SparkShimImpl.getExecs``): when a filter
    ``rank_like <= k`` sits above a window, each map-side partition only
    needs its per-group top-k rows — everything ranked deeper can never
    pass the filter, whatever the other partitions hold.  The planner
    inserts this BELOW the window's exchange, shrinking shuffle volume;
    the window + filter above still compute exact results.

    Kept rows per (partition-keys) group, ordered by the window order:
    row_number keeps k rows; rank/dense_rank keep every row whose rank
    <= k (ties may keep more).
    """

    def __init__(self, partition_spec, order_spec: Sequence[SortOrder],
                 rank_kind: str, limit: int, child: PhysicalPlan,
                 backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.partition_spec = list(partition_spec)
        self.order_spec = list(order_spec)
        self.rank_kind = rank_kind  # row_number | rank | dense_rank
        self.limit = int(limit)
        out = child.output
        self._bound_parts = [bind_references(e, out)
                             for e in self.partition_spec]
        self._bound_orders = [SortOrder(bind_references(o.child, out),
                                        o.ascending, o.nulls_first)
                              for o in self.order_spec]
        from .kernel_cache import exprs_key
        self._fn = self._jit(
            self._compute,
            key=("wgl", exprs_key(self._bound_parts),
                 exprs_key(self._bound_orders), rank_kind, self.limit))

    @property
    def output(self):
        return self.children[0].output

    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        from ...ops.sorting import sort_permutation
        from .basic import compact_batch
        xp = self.xp
        ctx = EvalContext(batch, xp=xp)
        live0 = batch.row_mask()
        # sort by (partition keys asc, order spec) so groups are contiguous
        specs = [(e.eval(ctx), True, True) for e in self._bound_parts]
        specs += [(o.child.eval(ctx), o.ascending, o.nulls_first)
                  for o in self._bound_orders]
        perm = sort_permutation(xp, specs, live0)
        n = batch.capacity
        valid = xp.arange(n, dtype=xp.int32) < batch.num_rows
        cols = tuple(c.gather(perm, valid) for c in batch.columns)
        sorted_b = ColumnarBatch(batch.names, cols, batch.num_rows)

        sctx = EvalContext(sorted_b, xp=xp)
        idx = xp.arange(n, dtype=xp.int32)
        live = idx < sorted_b.num_rows
        seg_keys: List = [(~live).astype(xp.int64)]
        for e in self._bound_parts:
            c = e.eval(sctx)
            seg_keys.append((~c.validity).astype(xp.int64))
            seg_keys.extend(column_sort_keys(xp, c))
        is_seg_start = W.boundary_flags(xp, seg_keys)
        seg_start, _seg_end = W.segment_bounds(xp, is_seg_start)
        if self.rank_kind == "row_number":
            rank = idx - seg_start + 1
        else:
            peer_keys = list(seg_keys)
            for o in self._bound_orders:
                c = o.child.eval(sctx)
                peer_keys.append((~c.validity).astype(xp.int64))
                peer_keys.extend(column_sort_keys(xp, c))
            is_peer_start = W.boundary_flags(xp, peer_keys)
            peer_start, _pe = W.segment_bounds(xp, is_peer_start)
            if self.rank_kind == "rank":
                rank = peer_start - seg_start + 1
            else:  # dense_rank
                cpeer = xp.cumsum(is_peer_start.astype(xp.int32))
                rank = cpeer - cpeer[xp.clip(seg_start, 0, None)] + 1
        keep = live & (rank <= self.limit)
        return compact_batch(xp, sorted_b, keep)

    def execute(self, pid, tctx):
        for batch in self.children[0].execute(pid, tctx):
            tctx.inc_metric("windowGroupLimitBatches")
            yield self._fn(batch)

    def simple_string(self):
        return (f"{self.node_name()} [{self.rank_kind} <= {self.limit}]")
