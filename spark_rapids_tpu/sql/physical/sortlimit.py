"""Sort exec (reference ``GpuSortExec.scala``: full + out-of-core sort).

Two paths:

* full sort — concat the partition's batches, one permutation gather;
* out-of-core (``GpuOutOfCoreSortIterator`` analog, ``GpuSortExec.scala:242``)
  — when the input exceeds ``spark.rapids.sql.sort.outOfCore.targetRows``:
  each batch is sorted under the OOM-retry framework and cut into
  target-row SPILLABLE chunks (runs); output is produced by a k-way
  prefix merge that only ever holds one chunk per run on device: the
  first T rows of the union of run-head chunks are globally the smallest
  T rows (each head is its run's prefix), so every merge step emits one
  target-sized sorted batch and advances the consumed runs.
"""

from __future__ import annotations

from collections import deque
from typing import Sequence

import numpy as np

from ...columnar.batch import ColumnarBatch
from ...observability import tracer as _trace
from ...ops.sorting import sort_permutation
from ..expressions.core import EvalContext, bind_references
from ..plan import SortOrder
from .base import TPU, PhysicalPlan, count_stage_dispatch

#: observability for tests: counts of out-of-core engagements
STATS = {"ooc_sorts": 0, "merge_steps": 0}


class SortExec(PhysicalPlan):
    def __init__(self, orders: Sequence[SortOrder], child: PhysicalPlan,
                 backend=TPU, is_global: bool = True):
        super().__init__(child)
        self.backend = backend
        #: False for sortWithinPartitions — a following Limit must NOT
        #: compose into a global TopN over a merely-local sort
        self.is_global = is_global
        self.orders = list(orders)
        self._bound = [SortOrder(bind_references(o.child, child.output),
                                 o.ascending, o.nulls_first)
                       for o in self.orders]
        #: whole-stage sort terminal (docs/whole_stage.md): an absorbed
        #: upstream Filter/Project chain rides the first-touch sort
        #: program (_stage_fn); the pure-sort program (_fn) stays
        #: separate because the out-of-core merge re-sorts batches the
        #: chain already processed (its steps are not idempotent)
        self._pre_steps: tuple = ()
        self._out_attrs = None
        # programs built lazily on first use (whole-stage laziness
        # contract — plan construction registers nothing)
        self._fn_cache = None
        self._stage_fn_cache = None

    @property
    def _fn(self):
        """Pure-sort program: merge-safe (no absorbed steps)."""
        if self._fn_cache is None:
            from .kernel_cache import exprs_key
            self._fn_cache = self._jit(self._compute,
                                       key=(exprs_key(self._bound),))
        return self._fn_cache

    @property
    def _stage_fn(self):
        """First-touch program: absorbed chain + compaction + sort, one
        launch.  Without absorbed steps this IS the pure-sort program."""
        if not self._pre_steps:
            return self._fn
        if self._stage_fn_cache is None:
            self._stage_fn_cache = self._jit(self._stage_compute,
                                             key=self._fuse_sig())
        return self._stage_fn_cache

    def _fuse_sig(self):
        from .kernel_cache import exprs_key
        return (exprs_key(self._bound),
                ("stage",) + tuple(s._fuse_key() for s in self._pre_steps))

    def absorb_pre_steps(self, steps, new_child) -> None:
        """Fuse an upstream Filter/Project chain into this sort's
        first-touch program (fusion.py sort/window terminal).  The chain
        reproduced the schema the orders were bound against, so the bound
        sort keys stay valid; fused filters compact INSIDE the program
        (the sort gather consumes the survivors directly)."""
        self._pre_steps = tuple(steps)
        self._out_attrs = list(steps[-1].output)
        self.children = (new_child,)
        self._fn_cache = None
        self._stage_fn_cache = None

    @property
    def output(self):
        if self._pre_steps:
            return self._out_attrs
        return self.children[0].output

    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        xp = self.xp
        ctx = EvalContext(batch, xp=xp)
        specs = [(o.child.eval(ctx), o.ascending, o.nulls_first)
                 for o in self._bound]
        perm = sort_permutation(xp, specs, batch.row_mask())
        live = xp.arange(batch.capacity, dtype=xp.int32) < batch.num_rows
        cols = tuple(c.gather(perm, live) for c in batch.columns)
        return ColumnarBatch(batch.names, cols, batch.num_rows)

    def _stage_compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Absorbed chain + compaction + sort, inside one program — the
        compaction's gather and the sort's permutation gather fuse."""
        from .basic import compact_batch
        xp = self.xp
        mask = batch.row_mask()
        for s in self._pre_steps:
            batch, mask = s._fuse_step(batch, mask, xp)
        if self._pre_steps:
            batch = compact_batch(xp, batch, mask)
        return self._compute(batch)

    def _launch(self, fn, batch: ColumnarBatch, tctx) -> ColumnarBatch:
        """One sort program over one batch: the span and the counter of
        every launch (the row count stays on the device until the task has
        ended)."""
        count_stage_dispatch()
        with _trace.span("sort", "compute"):
            out = fn(batch)
        tctx.inc_metric_late("sortRows", out.num_rows)
        return out

    def execute(self, pid, tctx):
        yield from self.execute_batches(
            list(self.children[0].execute(pid, tctx)), tctx)

    def execute_batches(self, batches, tctx):
        """Sort an already-materialized batch list (WindowExec's stage
        terminal feeds its key-batched fallback from here so the absorbed
        chain still rides the sort program)."""
        if not batches:
            return
        from ...config import SORT_OOC_TARGET_ROWS
        target = int(tctx.conf.get(SORT_OOC_TARGET_ROWS))
        # pull-free conservative sizing: the bound is exact when known,
        # else the padded capacity — engaging out-of-core a bit early is
        # cheaper than one device sync per batch
        total = sum(b.num_rows_bound for b in batches)
        if total > target:
            yield from self._out_of_core(batches, target, tctx)
            return
        merged = ColumnarBatch.concat(batches) if len(batches) > 1 else batches[0]
        out = self._launch(self._stage_fn, merged, tctx)
        if self._pre_steps:
            # absorbed filters can drop rows, so the count is no longer
            # host-known — only bounded by the pre-filter total
            out.with_rows_bound(total)
            yield out
            return
        known = getattr(merged, "_nrows_host", None)
        if known is not None:
            out.with_known_rows(known)  # sort permutes, never drops rows
        else:
            bound = getattr(merged, "_nrows_bound", None)
            if bound is not None:
                out.with_rows_bound(bound)
        yield out

    # --- out-of-core path -------------------------------------------------
    def _out_of_core(self, batches, target: int, tctx):
        from ...memory.retry import split_spillable_in_half, with_retry
        from ...memory.spill import (ACTIVE_BATCHING_PRIORITY,
                                     SpillableColumnarBatch)
        STATS["ooc_sorts"] += 1

        # phase 1: sort each input under retry; cut sorted runs into
        # target-row spillable chunks (a SplitAndRetryOOM halves an input,
        # which simply yields two smaller sorted runs).  Chunks created
        # before a later failure are closed by the phase-2 finally below.
        spillables = [SpillableColumnarBatch.create(
            b, ACTIVE_BATCHING_PRIORITY) for b in batches
            if b.num_rows_int > 0]
        runs: list = []
        try:
            # first touch runs the STAGE program (absorbed chain + sort);
            # the phase-2 merge below re-sorts already-processed rows and
            # must use the pure-sort program only
            def run_sort(sb):
                return self._launch(self._stage_fn, sb.get(), tctx)

            for sorted_b in with_retry(spillables, run_sort,
                                       split_spillable_in_half):
                run: deque = deque()
                n = sorted_b.num_rows_int
                for off in range(0, n, target):
                    piece = sorted_b.sliced(off, min(target, n - off))
                    run.append(SpillableColumnarBatch.create(
                        piece, ACTIVE_BATCHING_PRIORITY))
                if run:
                    runs.append(run)

            if len(runs) == 1:
                # one sorted run: its chunks ARE the output, no merge
                run = runs[0]
                while run:
                    yield run.popleft().get_and_close()
                return
        except BaseException:
            for r in runs:
                for sb in r:
                    sb.close()
            raise

        # phase 2: k-way prefix merge.  Each run contributes a prefix of at
        # least ``target`` rows (or its whole remainder) — that invariant
        # makes the first <=target rows of the sorted union globally the
        # smallest.  Tag prefixes with their run id, sort the union, emit,
        # advance each run by its consumed count.  The finally-close keeps
        # catalog accounting honest when the consumer abandons the
        # generator or a merge step raises (with_retry's ownership model).
        xp = self.xp
        run_col = "__ooc_run__"
        from ... import types as T
        from ...columnar.column import DeviceColumn
        try:
            while runs:
                runs = [r for r in runs if r]
                if not runs:
                    break
                STATS["merge_steps"] += 1
                heads = []
                for ridx, r in enumerate(runs):
                    # top up the prefix to >= target rows (or the whole run)
                    pieces = [r.popleft()]
                    rows = pieces[0].num_rows
                    while rows < target and r:
                        pieces.append(r.popleft())
                        rows += pieces[-1].num_rows
                    got = [p.get_and_close() for p in pieces]
                    hb = ColumnarBatch.concat(got) if len(got) > 1 else got[0]
                    rid = DeviceColumn(
                        T.INT, xp.full(hb.capacity, ridx, dtype=xp.int32),
                        xp.ones(hb.capacity, dtype=bool))
                    heads.append(ColumnarBatch(
                        hb.names + (run_col,), hb.columns + (rid,),
                        hb.num_rows))
                union = (ColumnarBatch.concat(heads) if len(heads) > 1
                         else heads[0])
                merged = self._launch(self._fn, union, tctx)
                e = min(target, merged.num_rows_int)
                emit = merged.sliced(0, e)
                # consumed rows per run (host bincount over emitted prefix)
                rid_sorted = np.asarray(merged.column(run_col).data[:e])
                consumed = np.bincount(rid_sorted, minlength=len(runs))
                survivors = []
                for ridx, (r, head) in enumerate(zip(runs, heads)):
                    c = int(consumed[ridx])
                    n_head = head.num_rows_int
                    if c < n_head:
                        rest = head.sliced(c, n_head - c)
                        names = tuple(n for n in rest.names if n != run_col)
                        cols = tuple(cc for n, cc
                                     in zip(rest.names, rest.columns)
                                     if n != run_col)
                        r.appendleft(SpillableColumnarBatch.create(
                            ColumnarBatch(names, cols, rest.num_rows),
                            ACTIVE_BATCHING_PRIORITY))
                    if r:
                        survivors.append(r)
                runs = survivors
                names = tuple(n for n in emit.names if n != run_col)
                cols = tuple(c for n, c in zip(emit.names, emit.columns)
                             if n != run_col)
                yield ColumnarBatch(names, cols, emit.num_rows)
        finally:
            for r in runs:
                for sb in r:
                    sb.close()

    def simple_string(self):
        s = f"{self.node_name()} [{', '.join(o.sql() for o in self.orders)}]"
        if self._pre_steps:
            chain = " -> ".join(st.node_name() for st in self._pre_steps)
            s += f" [fusedPre: {chain}]"
        return s


class TakeOrderedAndProjectExec(PhysicalPlan):
    """ORDER BY + LIMIT fusion (reference composes TopN in the rule,
    ``GpuOverrides.scala:3880-3904``)."""

    def __init__(self, n: int, orders, project_exprs, child, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.n = n
        self.orders = list(orders)
        self.project_exprs = project_exprs
        self._sort_cache: "SortExec" = None

    @property
    def _sort(self) -> "SortExec":
        """Derived lazily from the CURRENT child: planner passes that
        rewrite ``self.children`` (backend transitions, stage fusion)
        must flow into the internal sort, not a child frozen at
        construction time."""
        child = self.children[0]
        if self._sort_cache is None or \
                self._sort_cache.children[0] is not child:
            self._sort_cache = SortExec(self.orders, child, self.backend)
        return self._sort_cache

    @property
    def output(self):
        if self.project_exprs is None:
            return self.children[0].output
        from .basic import ProjectExec
        return ProjectExec(self.project_exprs, self.children[0],
                           self.backend).output

    def num_partitions(self):
        return 1

    def execute(self, pid, tctx):
        # local top-n per child partition, then merge
        tops = []
        for cpid in range(self.children[0].num_partitions()):
            for b in self._sort.execute(cpid, tctx):
                tops.append(b.sliced(0, min(self.n, b.num_rows_int)))
        if not tops:
            return
        with _trace.eager("top_n.merge", pieces=len(tops)):
            final = self._merge(tops)
        yield final

    def _merge(self, tops) -> ColumnarBatch:
        # every partition's top rows come to one chip: a limit's nature
        from ...parallel import placement
        tops = placement.gather(tops)
        merged = ColumnarBatch.concat(tops) if len(tops) > 1 else tops[0]
        final = self._sort._fn(merged)
        final = final.sliced(0, min(self.n, final.num_rows_int))
        if self.project_exprs is not None:
            bound = [bind_references(e, self.children[0].output)
                     for e in self.project_exprs]
            ctx = EvalContext(final, xp=self.xp)
            cols = tuple(e.eval(ctx) for e in bound)
            names = tuple(a.name for a in self.output)
            final = ColumnarBatch(names, cols, final.num_rows)
        return final
