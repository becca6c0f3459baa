"""Hash aggregate exec (reference ``aggregate.scala`` GpuHashAggregateExec).

TPU algorithm — no hash table, all static shapes:
1. group keys -> exact dense ranks (ops/ranks.py: integer sorts + pair
   densification); the rank IS the segment id;
2. every aggregate buffer slot reduces by rank (ops/segmented.py): a dense
   masked reduction into a small group table, a scatter into a large one;
3. group key values are gathered from each group's first row;
4. output batch keeps the input capacity, ``num_rows`` = #groups (traced).

Two-phase distributed aggregation (partial -> exchange -> final/merge) reuses
the same kernel with each slot's merge op, like the reference's
Partial/PartialMerge modes.
"""

from __future__ import annotations

import threading
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from ... import types as T
from ...columnar.batch import ColumnarBatch
from ...columnar.column import DeviceColumn
from ...observability import tracer as _trace
from ...ops.ranks import dense_rank_columns, dense_rank_pairs
from ...ops.segmented import seg_count, seg_max, seg_min, seg_sum
from ..expressions.aggregates import (COUNT, FIRST, LAST, MAX, MIN, SUM,
                                      AggregateExpression, AggregateFunction,
                                      BufferSlot)
from ..expressions.core import (Alias, AttributeReference, BoundReference,
                                EvalContext, Expression, bind_references)
from .base import TPU, PhysicalPlan, TaskContext


def _min_sentinel(xp, dtype: T.DataType):
    if T.is_floating(dtype):
        return float("inf")
    if isinstance(dtype, T.BooleanType):
        return True
    return np.iinfo(dtype.np_dtype).max


def _max_sentinel(xp, dtype: T.DataType):
    if T.is_floating(dtype):
        return float("-inf")
    if isinstance(dtype, T.BooleanType):
        return False
    return np.iinfo(dtype.np_dtype).min


def _gather_col(col: DeviceColumn, idx, idx_valid):
    return col.gather(idx, idx_valid)


def _reduce_slot(xp, col: DeviceColumn, contrib, op: str, rank, n_seg,
                 row_idx, cap):
    """Reduce one buffer slot by group rank; returns a DeviceColumn indexed
    by group id.  ``n_seg`` is the output group-table size (may be smaller
    than the row capacity ``cap`` on the two-phase device path)."""
    any_contrib = seg_sum(xp, contrib.astype(xp.int32), rank, n_seg) > 0
    if op == SUM:
        z = xp.asarray(0, dtype=col.data.dtype)
        data = seg_sum(xp, xp.where(contrib, col.data, z), rank, n_seg)
        return DeviceColumn(col.dtype, data, any_contrib)
    if op == COUNT:
        data = seg_sum(xp, contrib.astype(xp.int64), rank, n_seg)
        return DeviceColumn(T.LONG, data, xp.ones_like(any_contrib))
    if op in (MIN, MAX):
        if col.lengths is not None or col.children:
            # order via dense rank then argmin/argmax of (rank, row) pairs
            from ...ops.ranks import dense_rank_columns as drc
            r = drc(xp, [col])
            combined = r * cap + row_idx
            if op == MIN:
                combined = xp.where(contrib, combined, cap * cap)
                best = seg_min(xp, combined, rank, n_seg, cap * cap)
            else:
                combined = xp.where(contrib, combined, -1)
                best = seg_max(xp, combined, rank, n_seg, -1)
            widx = (best % cap).astype(xp.int32)
            ok = any_contrib
            return _gather_col(col, xp.clip(widx, 0, cap - 1), ok)
        if op == MIN:
            s = xp.asarray(_min_sentinel(xp, col.dtype), dtype=col.data.dtype)
            data = seg_min(xp, xp.where(contrib, col.data, s), rank, n_seg, s)
        else:
            s = xp.asarray(_max_sentinel(xp, col.dtype), dtype=col.data.dtype)
            data = seg_max(xp, xp.where(contrib, col.data, s), rank, n_seg, s)
        return DeviceColumn(col.dtype, data, any_contrib)
    if op in (FIRST, LAST):
        if op == FIRST:
            widx = seg_min(xp, xp.where(contrib, row_idx, cap), rank, n_seg,
                           cap)
        else:
            widx = seg_max(xp, xp.where(contrib, row_idx, -1), rank, n_seg,
                           -1)
        ok = any_contrib
        return _gather_col(col, xp.clip(widx, 0, cap - 1).astype(xp.int32), ok)
    raise ValueError(op)


def _use_batched_reduce(xp) -> bool:
    """Batched 2-D scatters win on TPU (vectorized row scatter) but lose to
    per-slot 1-D scatters on XLA CPU — measured 58ms vs 34ms for 8 f32
    slots at 1M rows — so batch only on real device backends.  Module-level
    so tests can force the batched path on CPU."""
    if xp.__name__ == "numpy":
        return False
    import jax
    return jax.default_backend() not in ("cpu",)


#: largest group table the chip reduces densely (ops/segmented.py); above
#: it the scatter stays.  From one v5e run at 2^21 rows (PERF.md §6 PR 30):
#: 7 float64 slots reduce densely in 2.1 / 7.3 / 24 / 48 / 93 / 186 ms into
#: 8 / 64 / 256 / 512 / 1024 / 2048 groups where the scatter takes 185-196
#: ms at every size, so 64-bit slots cross over near 2048; 2 int32 slots,
#: whose scatter is a native 16.6 ms, take 4.2 / 7.6 / 22 ms at 256 / 512 /
#: 1024 and cross near 800.  256 is well inside both (every dtype measured
#: wins there by 4x or more) and is where the one-hot matmul's envelope
#: ends too: it bounds the [groups, rows] predicate, which the compiler
#: materialises (2 MiB a group at 2^21 rows), to 512 MiB.
_DENSE_MAX_GROUPS = 256


def _use_dense_reduce(xp, n_seg: int) -> bool:
    """Which form a reduction into an ``n_seg``-row group table takes, from
    what trace time can see: the platform (XLA CPU keeps its scatters, fast
    there) and the static table size.  The execs ask the same question on
    the host to count batches by form."""
    return n_seg <= _DENSE_MAX_GROUPS and _use_batched_reduce(xp)


def reduce_form_metric(xp, n_seg: int) -> str:
    """The task metric a batch reduced into an ``n_seg``-row table counts
    under."""
    return ("aggDenseReduceBatches" if _use_dense_reduce(xp, n_seg)
            else "aggScatterReduceBatches")


def group_table_floor(xp, grouped: bool) -> int:
    """Smallest group table a device aggregate is sized to.  Grouped
    aggregates keep a floor so fluctuating group counts share one compiled
    program (the size is in the program's key; a first compile on the chip
    is 20-40 s): 64 rows where the table is scattered into, whose cost does
    not depend on the size, and 8 (one sublane tile) where it is reduced
    densely, whose cost grows with it.  A global aggregate has exactly one
    group."""
    if not grouped:
        return 1
    return 8 if _use_dense_reduce(xp, 8) else 64


def group_phase(xp, key_cols: Sequence[DeviceColumn], row_mask,
                expected_groups: Optional[int] = None):
    """Phase A of the two-phase device aggregate: group ids + count.
    Splitting this from the reductions lets the host size the output
    table to the OBSERVED group count: a small table is reduced into
    densely (and float32/flag sums by a one-hot matmul on the MXU), which
    the chip does 25-90x faster than a float64 scatter of the same rows.

    ``expected_groups`` (the speculated table size) switches the id
    kernel to a small-table bounded probe whose overflow inflates the
    observed count past the speculation — detected by the same check
    that validates table sizing (hash_group.group_ids_small)."""
    if key_cols:
        from ...ops.hash_group import group_ids, group_ids_small
        if expected_groups is not None:
            rank64 = group_ids_small(xp, key_cols, row_mask,
                                     expected_groups)
        else:
            rank64 = group_ids(xp, key_cols, row_mask)
    else:
        rank64 = xp.where(row_mask, 0, 1).astype(xp.int64)  # one global group
    live_rank = xp.where(row_mask, rank64, -1)
    n_groups = (xp.max(live_rank) + 1).astype(xp.int32)
    if not key_cols:
        # global aggregate: always exactly one output row, even with empty
        # input (SQL semantics: SELECT sum(x) over zero rows -> one null row)
        n_groups = xp.maximum(n_groups, 1)
    return rank64, n_groups


#: speculated group-table size per partial-program key: after the first
#: batch of a query reveals its group count, later batches fuse group+
#: reduce into one program sized to it (bounded: keys embed literals, so
#: reuse the kernel cache's eviction philosophy at small scale)
_OUT_SPECULATION: dict = {}
#: guards the speculation dict against concurrent sessions (and a clear
#: racing a record — same contract as join._SEL_LOCK, docs/serving.md)
_SPEC_LOCK = threading.Lock()


def record_speculation(spec_key, ng_host: int, minimum: int) -> None:
    """Record an observed group count as the speculated table size for
    this program key (max-join: a small tail batch must not clobber the
    size a large batch needs, which would make every later large batch
    mis-speculate and execute twice, forever)."""
    from ...columnar.column import bucket_capacity
    with _SPEC_LOCK:
        prev = _OUT_SPECULATION.get(spec_key, 0)
        if len(_OUT_SPECULATION) > 1024:
            _OUT_SPECULATION.clear()  # unbounded keys embed literals
        _OUT_SPECULATION[spec_key] = max(
            prev, bucket_capacity(max(int(ng_host), 1), minimum=minimum))


def lookup_speculation(spec_key):
    with _SPEC_LOCK:
        return _OUT_SPECULATION.get(spec_key)


def clear_speculation() -> None:
    """Called by kernel_cache.clear_cache (after its generation bump)."""
    with _SPEC_LOCK:
        _OUT_SPECULATION.clear()

#: largest group table served by the one-hot matmul reduction (the
#: [rows, OUT] one-hot must stay cheap even if XLA doesn't fuse it away)
_MATMUL_MAX_GROUPS = 256


def groupby_reduce(xp, key_cols: Sequence[DeviceColumn],
                   slot_cols: Sequence[Tuple[DeviceColumn, "object"]],
                   ops: Sequence[str], row_mask, rank64=None,
                   n_groups=None, out_size: Optional[int] = None):
    """Core groupby: returns (grouped_key_cols, reduced_slot_cols, n_groups).
    Output arrays are ``out_size``-sized (default: input capacity); group g
    lives at index g.  ``rank64``/``n_groups`` may be precomputed by
    :func:`group_phase` (two-phase device path); both reduction forms
    (ops/segmented.py) silently drop out-of-bounds dead-row ranks, which is
    exactly the semantics needed when ``out_size`` < capacity."""
    cap = row_mask.shape[0]
    # int32 ids and row numbers: TPU int64 is emulated (pairs of int32
    # ops), so every 64-bit compare or scatter costs roughly double
    row_idx = xp.arange(cap, dtype=xp.int32)
    if rank64 is None:
        rank64, n_groups = group_phase(xp, key_cols, row_mask)
    rank = rank64.astype(xp.int32)
    OUT = out_size or cap
    # one form for every reduction of this program, chosen by table size
    dense = _use_dense_reduce(xp, OUT)

    first_idx = seg_min(xp, xp.where(row_mask, row_idx, cap), rank, OUT,
                        np.int32(cap), dense=dense)
    first_idx = xp.clip(first_idx, 0, cap - 1).astype(xp.int32)
    group_ok = xp.arange(OUT, dtype=xp.int32) < n_groups
    out_keys = [_gather_col(k, first_idx, group_ok) for k in key_cols]

    # Split slots into "simple" (plain 1-D numeric data + batchable op) and
    # the general path.  Simple slots of one (op-kind, dtype) reduce as ONE
    # [rows, s] matrix — s slots per pass instead of 2 scatters per slot
    # (one kernel launch per op per batch, SURVEY §3.3): densely into a
    # table of at most _DENSE_MAX_GROUPS rows, by a 2-D scatter above.
    from ...ops.segmented import seg_max2, seg_min2, seg_sum2
    n_slots = len(slot_cols)
    out_slots: List = [None] * n_slots
    batch_ok = _use_batched_reduce(xp)
    simple = []  # (slot_idx, op, col, contrib)
    for i, ((col, contrib), op) in enumerate(zip(slot_cols, ops)):
        contrib = contrib & row_mask
        if (batch_ok and op in (SUM, COUNT, MIN, MAX) and col.data is not None
                and col.data.ndim == 1 and col.lengths is None
                and col.aux is None and not col.children):
            simple.append((i, op, col, contrib))
        else:
            r = _reduce_slot(xp, col, contrib, op, rank, OUT, row_idx,
                             cap)
            out_slots[i] = r.with_validity(r.validity & group_ok)

    # MXU fast path: with a host-sized small group table, additive
    # reductions become ONE one-hot matmul (f32 accumulation).  ONLY f32
    # sums (same error class as any float sum order) and 0/1 FLAG sums
    # bounded by cap < 2^24 (exact in f32) may ride it.  Everything else —
    # float64 sums (TPC-H's: the chip's double-float pair), integer SUM
    # data of arbitrary magnitude — keeps its own dtype as the accumulator
    # and is exact or float64: reduced densely into a small table, and by
    # the scatter into a large one (on the chip a scatter over a 32-bit
    # pair, applied one update after another).
    # MXU path only where a matmul engine exists: on XLA CPU the [rows, OUT]
    # one-hot is materialized (no fusion into the GEMM), costing OUT/8 bytes
    # of traffic per row — measured 0.37s vs 0.02s scatter at 1M rows x 64
    use_matmul = (out_size is not None and OUT <= _MATMUL_MAX_GROUPS
                  and _use_batched_reduce(xp))
    onehot = None
    if use_matmul:
        onehot = (rank[:, None] == xp.arange(OUT, dtype=xp.int32)[None, :]
                  ).astype(xp.float32)

    def _additive(cols2, dt, flags=False):
        if onehot is not None and (
                dt == np.dtype(np.float32)
                or (flags and cap < (1 << 24))):
            from ...ops import pallas_kernels as PK
            if PK.on_tpu() and PK.seg_sum_available():
                # explicit MXU program (same accumulation error class as
                # the one-hot matmul below, same dead-rank convention);
                # availability probed end-to-end once per backend —
                # lowering gaps surface at compile time, outside any
                # try/except around this traced call
                stacked = xp.stack([c.astype(xp.float32) for c in cols2],
                                   axis=0)
                return PK.seg_sum_f32_pallas(
                    stacked, rank, OUT).T.astype(dt)
            stacked = xp.stack([c.astype(xp.float32) for c in cols2],
                               axis=1)
            return (onehot.T @ stacked).astype(dt)
        return seg_sum2(xp, xp.stack(cols2, axis=1), rank, OUT, dense=dense)

    if simple:
        contrib_mat = [c.astype(xp.int32) for (_, _, _, c) in simple]
        any_mat = _additive(contrib_mat, np.dtype(np.int32),
                            flags=True) > 0
        by_kind: dict = {}
        for j, (i, op, col, contrib) in enumerate(simple):
            if op == COUNT:
                kind = ("count", np.dtype(np.int64))
            elif op == SUM:
                kind = ("add", np.dtype(col.data.dtype))
            else:
                kind = ("min" if op == MIN else "max",
                        np.dtype(col.data.dtype))
            by_kind.setdefault(kind, []).append((j, i, op, col, contrib))
        for (kind, dt), items in by_kind.items():
            if kind == "count":
                # 0/1 flag sums: bounded by cap, exact on the matmul path
                cols2 = [contrib.astype(dt)
                         for (_, _, op, col, contrib) in items]
                red = _additive(cols2, dt, flags=True)
            elif kind == "add":
                cols2 = [xp.where(contrib, col.data,
                                  xp.asarray(0, dtype=dt))
                         for (_, _, op, col, contrib) in items]
                red = _additive(cols2, dt)
            else:
                is_min = kind == "min"
                sent = (_min_sentinel if is_min else _max_sentinel)(
                    xp, items[0][3].dtype)
                sent = xp.asarray(sent, dtype=dt)
                cols2 = [xp.where(contrib, col.data, sent)
                         for (_, _, op, col, contrib) in items]
                stacked = xp.stack(cols2, axis=1)
                red = (seg_min2 if is_min else seg_max2)(
                    xp, stacked, rank, OUT, sent, dense=dense)
            for out_col, (j, i, op, col, contrib) in enumerate(items):
                if op == COUNT:
                    out_slots[i] = DeviceColumn(
                        T.LONG, red[:, out_col],
                        xp.ones(OUT, dtype=bool) & group_ok)
                else:
                    out_slots[i] = DeviceColumn(
                        col.dtype, red[:, out_col],
                        any_mat[:, j] & group_ok)
    return out_keys, out_slots, n_groups


class HashAggregateExec(PhysicalPlan):
    """mode: complete | partial | final.

    Output contract for partial mode: [key cols...] + [slot cols...] with
    generated names; final mode consumes that layout.
    """

    def __init__(self, grouping: Sequence[Expression],
                 agg_out: Sequence[Expression], mode: str,
                 child: PhysicalPlan, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.mode = mode
        self.grouping = list(grouping)
        self.agg_out = list(agg_out)

        # split outputs into group refs, plain aggregates, and COMPOUND
        # post-aggregation expressions (e.g. sum(a) * 100 / sum(b)): the
        # latter register every contained aggregate as a slot source and
        # keep the surrounding tree, re-evaluated over the finalized
        # results (reference: Spark's resultExpressions on HashAggregate)
        self._agg_funcs: List[AggregateFunction] = []
        self._out_spec: List[Tuple[str, object, str]] = []  # (kind, idx, name)
        self._post_exprs: List[Expression] = []  # for kind == "expr"
        group_keys = [g.semantic_key() for g in self.grouping]
        nk_out = len(self.grouping)

        seen_funcs: dict = {}

        def register_agg(x) -> int:
            """Returns the slot-source index, deduplicating semantically
            identical aggregates (Spark's distinct aggregateExpressions:
            count(*) repeated across outputs computes/ships ONE slot)."""
            func = x
            fk = func.semantic_key()
            if isinstance(x, AggregateExpression):
                if x.is_distinct:
                    raise NotImplementedError(
                        "DISTINCT aggregate reached the exec without "
                        "the planner's dedup rewrite")
                func = x.func
                # FILTER (WHERE ...) clauses make otherwise-equal funcs
                # distinct slot sources
                fk = (func.semantic_key(),
                      x.filter.semantic_key() if x.filter is not None
                      else None)
            else:
                fk = (fk, None)
            if fk in seen_funcs:
                return seen_funcs[fk]
            idx = len(self._agg_funcs)
            seen_funcs[fk] = idx
            self._agg_funcs.append(func)
            return idx

        def rewrite_post(x) -> Expression:
            """Top-down: aggregate nodes -> bound refs into the finalized
            layout [keys..., agg results...]; grouping subtrees -> key
            refs.  Never descends INTO an aggregate (its children are
            pre-aggregation inputs)."""
            if isinstance(x, (AggregateExpression, AggregateFunction)):
                idx = register_agg(x)
                return BoundReference(nk_out + idx,
                                      self._agg_funcs[idx].data_type, True)
            sk = x.semantic_key()
            if sk in group_keys:
                gi = group_keys.index(sk)
                g = self.grouping[gi]
                return BoundReference(gi, g.data_type, True)
            if isinstance(x, AttributeReference):
                raise ValueError(
                    f"column {x.name!r} in aggregate output is neither "
                    "inside an aggregate nor a grouping expression")
            if not x.children:
                return x
            return x.with_children(tuple(rewrite_post(c)
                                         for c in x.children))

        for e in self.agg_out:
            name = e.name if isinstance(e, Alias) else (
                e.name if isinstance(e, AttributeReference) else e.sql())
            inner = e.children[0] if isinstance(e, Alias) else e
            aggs = inner.collect(lambda x: isinstance(x, (AggregateExpression,
                                                          AggregateFunction)))
            if aggs and inner is aggs[0]:
                # plain aggregate output (possibly AggregateExpression-
                # wrapped): one slot source, no surrounding arithmetic
                self._out_spec.append(("agg", register_agg(inner), name))
            elif aggs:
                self._out_spec.append(("expr", len(self._post_exprs), name))
                self._post_exprs.append(rewrite_post(inner))
            else:
                sk = inner.semantic_key()
                if sk in group_keys:
                    self._out_spec.append(("group", group_keys.index(sk), name))
                else:
                    # aggregate-free expression OVER grouping keys (e.g.
                    # rollup's grouping() bit math): post-evaluate it;
                    # rewrite_post raises if any column is not a key
                    try:
                        rewritten = rewrite_post(inner)
                    except ValueError:
                        raise ValueError(
                            f"aggregate output {e.sql()} is neither a "
                            "grouping expression nor an aggregate") from None
                    self._out_spec.append(
                        ("expr", len(self._post_exprs), name))
                    self._post_exprs.append(rewritten)

        child_attrs = child.output
        if mode in ("final", "merge"):
            # child emits [keys..., slots...]
            nk = len(self.grouping)
            self._key_refs = child_attrs[:nk]
            self._slot_attrs = child_attrs[nk:]
        else:
            self._bound_grouping = [bind_references(g, child_attrs)
                                    for g in self.grouping]
            self._bound_inputs = [
                [bind_references(c, child_attrs) for c in f.children]
                for f in self._agg_funcs]

        #: indices of shuffle-complete aggregates (collect_list/set,
        #: approx_percentile): grouped results built from raw rows, no
        #: mergeable slots — planner shuffles rows by key and runs ONE
        #: complete pass (reference cuDF collect/t-digest aggregations)
        self._special = [i for i, f in enumerate(self._agg_funcs)
                         if getattr(f, "requires_shuffle_complete", False)]

        from .kernel_cache import exprs_key
        self._pre_steps: List = []  # fused upstream filter/project chain
        slots_key = tuple(
            # result dtype is program identity: evaluate() bakes
            # dtype-derived Python constants (decimal128 rescale factors,
            # precision bounds) into the traced finalize program, and the
            # chunked-decimal slots are all LONG — without the result
            # dtype two decimal aggs of different (p, s) would share a
            # compiled finalize (observed: avg's 10^4 rescale applied to
            # a different query's sum)
            (type(f).__name__, f._key_extras(), str(f.data_type),
             tuple(str(c.data_type) for c in f.children),
             tuple((s.op, s.merge_op, s.dtype) for s in f.slots()))
            for f in self._agg_funcs)
        self._slots_key = slots_key
        if mode not in ("final", "merge"):
            self._partial_key = (
                "partial", exprs_key(self._bound_grouping),
                tuple(zip(slots_key,
                          (exprs_key(i) for i in self._bound_inputs))))
            # programs built lazily on first use (whole-stage laziness
            # contract): plan construction, AQE re-plans and CPU-fallback
            # discards must register nothing in the kernel cache
            self._partial_fn = None
            self._group_fn = None
            self._reduce_fns: dict = {}
            self._fused_fns: dict = {}
            self._fused_complete_fns: dict = {}
            self._spec_key = self._partial_key  # no pre-steps yet
        self._merge_key = ("merge", len(self.grouping), slots_key)
        self._merge_fn = None
        from .kernel_cache import exprs_key as _ek
        self._finalize_key = (
            "finalize", len(self.grouping), slots_key,
            tuple((k, _ek([self._post_exprs[i]]) if k == "expr" else i, n)
                  for k, i, n in self._out_spec))

    def _make_partial_fn(self, steps):
        """Build the partial kernel over an IMMUTABLE pre-step tuple.  The
        steps must be baked into the closure (not read from self) because
        the jitted wrapper is shared process-wide under its cache key —
        mutating instance state after registration would change the cached
        program's behavior for unrelated queries."""
        steps = tuple(steps)

        def fn(batch):
            return self._partial_compute(batch, steps)
        return fn

    def absorb_pre_steps(self, steps, new_child):
        """Whole-stage fusion: inline an upstream Filter/Project chain into
        the partial kernel (fusion.py).  The chain reproduces the old
        child's schema, so existing bound expressions stay valid; fused
        filters contribute a live-row mask instead of compacting.  The
        stage becomes the unit of the kernel cache: one stage-signature
        key (partial key + member fuse keys) replaces the members' per-op
        keys, and the programs stay lazy — nothing registers until the
        first batch executes."""
        self._pre_steps = list(steps)
        self.children = (new_child,)
        self._partial_fn = None
        self._group_fn = None
        self._reduce_fns = {}
        self._fused_fns = {}
        self._fused_complete_fns = {}
        self._spec_key = self._partial_key + tuple(
            s._fuse_key() for s in steps)

    def _stage_partial_key(self):
        return self._partial_key + tuple(
            s._fuse_key() for s in self._pre_steps)

    def _get_partial_fn(self):
        if self._partial_fn is None:
            self._partial_fn = self._jit(
                self._make_partial_fn(self._pre_steps),
                key=self._stage_partial_key())
        return self._partial_fn

    def _get_group_fn(self):
        if self._group_fn is None:
            self._group_fn = self._jit(
                self._make_group_fn(self._pre_steps),
                key=("grp",) + self._stage_partial_key())
        return self._group_fn

    def _get_merge_fn(self):
        if self._merge_fn is None:
            self._merge_fn = self._jit(self._merge_compute,
                                       key=self._merge_key)
        return self._merge_fn

    # --- schema -----------------------------------------------------------
    @property
    def output(self):
        if self.mode == "merge":
            return list(self.children[0].output)
        if self.mode == "partial":
            out = []
            for i, g in enumerate(self.grouping):
                out.append(AttributeReference(f"_g{i}", g.data_type, True))
            si = 0
            for f in self._agg_funcs:
                for s in f.slots():
                    out.append(AttributeReference(f"_s{si}", s.dtype, True))
                    si += 1
            return out
        out = []
        for kind, idx, name in self._out_spec:
            if kind == "group":
                g = self.grouping[idx]
                out.append(AttributeReference(name, g.data_type, g.nullable))
            elif kind == "expr":
                e = self._post_exprs[idx]
                out.append(AttributeReference(name, e.data_type, True))
            else:
                f = self._agg_funcs[idx]
                out.append(AttributeReference(name, f.data_type, f.nullable))
        return out

    # --- compute ----------------------------------------------------------
    def _partial_compute(self, batch: ColumnarBatch, pre_steps=()):
        """update + first reduce over one input batch -> [keys..., slots...]
        (with any fused upstream filter/project chain applied inline)"""
        xp = self.xp
        mask = batch.row_mask()
        for step in pre_steps:
            batch, mask = step._fuse_step(batch, mask, xp)
        ctx = EvalContext(batch, xp=xp)
        keys = [g.eval(ctx) for g in self._bound_grouping]
        slot_pairs, ops = self._eval_slots(ctx)
        gk, gs, n = groupby_reduce(xp, keys, slot_pairs, ops, mask)
        names = tuple(f"_g{i}" for i in range(len(gk))) + \
            tuple(f"_s{i}" for i in range(len(gs)))
        return ColumnarBatch(names, tuple(gk) + tuple(gs), n)

    def _eval_slots(self, ctx):
        slot_pairs = []
        ops = []
        for f, inputs in zip(self._agg_funcs, self._bound_inputs):
            in_cols = [e.eval(ctx) for e in inputs]
            pairs = f.update_values(ctx, in_cols)
            slot_pairs.extend(pairs)
            ops.extend(s.op for s in f.slots())
        return slot_pairs, ops

    # --- two-phase device path (see group_phase) ---------------------------
    def _make_group_fn(self, steps):
        steps = tuple(steps)

        def fn(batch):
            xp = self.xp
            mask = batch.row_mask()
            for step in steps:
                batch, mask = step._fuse_step(batch, mask, xp)
            ctx = EvalContext(batch, xp=xp)
            keys = [g.eval(ctx) for g in self._bound_grouping]
            rank64, n_groups = group_phase(xp, keys, mask)
            return batch, mask, rank64, n_groups
        return fn

    def _reduce_fn(self, out_size: int):
        fn = self._reduce_fns.get(out_size)
        if fn is None:
            def impl(batch, mask, rank64, n_groups):
                xp = self.xp
                ctx = EvalContext(batch, xp=xp)
                keys = [g.eval(ctx) for g in self._bound_grouping]
                slot_pairs, ops = self._eval_slots(ctx)
                gk, gs, n = groupby_reduce(
                    xp, keys, slot_pairs, ops, mask, rank64=rank64,
                    n_groups=n_groups, out_size=out_size)
                names = tuple(f"_g{i}" for i in range(len(gk))) + \
                    tuple(f"_s{i}" for i in range(len(gs)))
                return ColumnarBatch(names, tuple(gk) + tuple(gs), n)
            fn = self._jit(impl, key=("reduce", out_size)
                           + self._partial_key)
            self._reduce_fns[out_size] = fn
        return fn

    def _fused_partial_fn(self, out_size: int):
        """Speculative ONE-program partial: group phase + reductions fused
        under a host-guessed group-table size.  Returns (partial, ng); the
        caller validates ng <= out_size on the host and falls back to the
        exact two-phase path on mis-speculation (scatters past out_size
        drop, so a mis-speculated result is discarded, never used)."""
        steps = tuple(self._pre_steps)

        def impl(batch):
            xp = self.xp
            mask = batch.row_mask()
            for step in steps:
                batch, mask = step._fuse_step(batch, mask, xp)
            ctx = EvalContext(batch, xp=xp)
            keys = [g.eval(ctx) for g in self._bound_grouping]
            rank64, ng = group_phase(xp, keys, mask,
                                     expected_groups=out_size)
            slot_pairs, ops = self._eval_slots(ctx)
            gk, gs, n = groupby_reduce(xp, keys, slot_pairs, ops, mask,
                                       rank64=rank64, n_groups=ng,
                                       out_size=out_size)
            names = tuple(f"_g{i}" for i in range(len(gk))) + \
                tuple(f"_s{i}" for i in range(len(gs)))
            return ColumnarBatch(names, tuple(gk) + tuple(gs), n), ng
        key = ("fusedpartial", out_size, self._partial_key) + \
            tuple(s._fuse_key() for s in self._pre_steps)
        return self._jit(impl, key=key)

    def _fused_complete_body(self, out_size: int):
        """TRACEABLE speculative complete aggregate: fused pre-steps +
        group phase + reductions + finalize under a host-guessed
        group-table size.  Returns (result, ng).  Composable into larger
        programs (whole-query tail fusion) or jitted alone."""
        steps = tuple(self._pre_steps)

        def impl(batch):
            xp = self.xp
            mask = batch.row_mask()
            for step in steps:
                batch, mask = step._fuse_step(batch, mask, xp)
            ctx = EvalContext(batch, xp=xp)
            keys = [g.eval(ctx) for g in self._bound_grouping]
            rank64, ng = group_phase(xp, keys, mask,
                                     expected_groups=out_size)
            slot_pairs, ops = self._eval_slots(ctx)
            gk, gs, n = groupby_reduce(xp, keys, slot_pairs, ops, mask,
                                       rank64=rank64, n_groups=ng,
                                       out_size=out_size)
            names = tuple(f"_g{i}" for i in range(len(gk))) + \
                tuple(f"_s{i}" for i in range(len(gs)))
            partial = ColumnarBatch(names, tuple(gk) + tuple(gs), n)
            # a single batch's partial has unique keys by construction, so
            # the cross-batch merge is an identity — finalize directly
            return self._finalize(partial), ng
        return impl

    def _fused_complete_key(self, out_size: int):
        return ("fusedcomplete", out_size, self._partial_key,
                self._finalize_key) + \
            tuple(s._fuse_key() for s in self._pre_steps)

    def _fused_complete_fn(self, out_size: int):
        """Jitted :meth:`_fused_complete_body`.  With deferred validation
        (speculation.py) the whole query needs ZERO host pulls until the
        final D2H fetch, which bundles ``ng`` — mis-speculation is
        detected there and the query re-runs on the exact path."""
        return self._jit(self._fused_complete_body(out_size),
                         key=self._fused_complete_key(out_size))

    def _try_deferred_complete(self, batches):
        """Zero-pull complete aggregate over a single input batch (the
        common single-partition shape).  Returns the result batch or None
        when the speculative path does not apply (no recorded size yet,
        multiple batches, specials, or deferral disabled)."""
        from . import speculation as SPEC
        if self.backend != TPU or self._special:
            return None
        if not SPEC.deferral_enabled():
            return None
        live = [b for b in batches if b.num_rows_bound > 0]
        if len(live) != 1:
            return None
        batch = live[0]
        spec = lookup_speculation(self._spec_key)
        if spec is None or spec > batch.capacity:
            return None
        fused = self._fused_complete_fns.get(spec)
        if fused is None:
            fused = self._fused_complete_fns[spec] = \
                self._fused_complete_fn(spec)
        from ...memory.retry import SplitAndRetryOOM
        from .base import count_stage_dispatch
        count_stage_dispatch()
        try:
            out, ng = fused(batch)
        except SplitAndRetryOOM:
            return None  # memory pressure: take the spillable exact path
        spec_key = self._spec_key
        minimum = self._table_floor()
        SPEC.register(spec, ng,
                      lambda ng_host, sk=spec_key, m=minimum:
                      record_speculation(sk, ng_host, m))
        return out.with_rows_bound(spec)

    def _run_partial(self, batch: ColumnarBatch) -> ColumnarBatch:
        """One input batch -> partial [keys..., slots...].  On the device
        backend this is the two-phase path: group ids first, ONE host sync
        for the observed group count, then reductions into a group table
        sized to it.  On the chip a table of at most _DENSE_MAX_GROUPS rows
        (TPC-H Q1: 8, Q6: 1) is reduced into densely, float32 and flag sums
        by the one-hot matmul; a larger one (Q3: 2^14) by scatters.
        Once a query has observed its group count, later batches SPECULATE
        that size and run group+reduce as ONE program with ONE sync —
        every extra program boundary and sync is a host<->device round
        trip the query waits on."""
        from .base import count_stage_dispatch
        if self.backend != TPU:
            count_stage_dispatch()
            return self._get_partial_fn()(batch)
        from ...columnar.column import bucket_capacity
        spec_key = self._spec_key
        spec = lookup_speculation(spec_key)
        if spec is not None and spec <= batch.capacity:
            fused = self._fused_fns.get(spec)
            if fused is None:
                fused = self._fused_fns[spec] = self._fused_partial_fn(spec)
            count_stage_dispatch()
            out, ng = fused(batch)
            # the host waits here for the program it just launched
            with _trace.span("sync", "agg.group_count"):
                ng_host = int(ng)
            if ng_host <= spec:
                return out.with_known_rows(ng_host)
            # mis-speculation: groups past `spec` were dropped — discard
            # and take the exact path below (which re-records the size)
        count_stage_dispatch(2)  # group phase + sized reduce
        batch2, mask, rank64, ng = self._get_group_fn()(batch)
        with _trace.span("sync", "agg.group_count"):
            ng_host = int(ng)
        n = max(ng_host, 1)
        out_size = min(bucket_capacity(n, minimum=self._table_floor()),
                       batch2.capacity)
        # max-join: a small tail batch must not clobber the spec a large
        # batch needs (that would make every later large batch
        # mis-speculate and execute twice, forever)
        with _SPEC_LOCK:
            prev = _OUT_SPECULATION.get(spec_key, 0)
            if len(_OUT_SPECULATION) > 1024:
                _OUT_SPECULATION.clear()  # unbounded keys embed literals
            _OUT_SPECULATION[spec_key] = max(prev, out_size)
        out = self._reduce_fn(out_size)(batch2, mask, rank64, ng)
        # output row count == observed group count (ng already folds in the
        # one-row floor for global aggregates), known on the host — seed it
        # so downstream num_rows_int (spill registration, sort sizing)
        # doesn't pay another device sync
        return out.with_known_rows(ng_host)

    def _table_floor(self) -> int:
        return group_table_floor(self.xp, bool(self.grouping))

    def _merge_finalize_fn(self):
        if getattr(self, "_mf_jit", None) is None:
            def fused(batch):
                return self._finalize(self._merge_compute(batch))
            self._mf_jit = self._jit(
                fused, key=("mergefin",) + self._finalize_key)
        return self._mf_jit

    def _merge_compute(self, batch: ColumnarBatch):
        """merge partial layout [keys..., slots...] -> same layout."""
        xp = self.xp
        nk = len(self.grouping)
        keys = list(batch.columns[:nk])
        slots = list(batch.columns[nk:])
        ops, contribs = [], []
        si = 0
        for f in self._agg_funcs:
            for s in f.slots():
                ops.append(s.merge_op)
                col = slots[si]
                if s.merge_op in (FIRST, LAST) \
                        and not s.merge_valid_only:
                    contribs.append(batch.row_mask())
                else:
                    contribs.append(col.validity)
                si += 1
        pairs = list(zip(slots, contribs))
        gk, gs, n = groupby_reduce(xp, keys, pairs, ops, batch.row_mask())
        return ColumnarBatch(batch.names, tuple(gk) + tuple(gs), n)

    def _finalize(self, batch: ColumnarBatch):
        """evaluate result expressions over merged [keys..., slots...]"""
        xp = self.xp
        ctx = EvalContext(batch, xp=xp)
        nk = len(self.grouping)
        keys = list(batch.columns[:nk])
        slots = list(batch.columns[nk:])
        # per-func slot ranges
        results = []
        si = 0
        func_results = []
        for f in self._agg_funcs:
            cnt = len(f.slots())
            func_results.append(f.evaluate(ctx, slots[si:si + cnt]))
            si += cnt
        post_ctx = None
        if any(kind == "expr" for kind, _, _ in self._out_spec):
            # compound outputs evaluate over the finalized layout
            # [keys..., agg results...] via pre-bound references
            synth = ColumnarBatch(
                tuple(f"__fin{i}" for i in
                      range(len(keys) + len(func_results))),
                tuple(keys) + tuple(func_results), batch.num_rows)
            post_ctx = EvalContext(synth, xp=xp)
        cols, names = [], []
        for kind, idx, name in self._out_spec:
            names.append(name)
            if kind == "group":
                cols.append(keys[idx])
            elif kind == "agg":
                cols.append(func_results[idx])
            else:
                cols.append(self._post_exprs[idx].eval(post_ctx))
        return ColumnarBatch(tuple(names), tuple(cols), batch.num_rows)

    _finalize_jit = None

    def _merge_spillables(self, spillables, fanin=8, tctx=None):
        """Tree-merge partial layouts under the retry framework, bounding
        peak device residency to ``fanin`` batches per attempt — the TPU
        answer to the reference's incremental merge with sort/repartition
        fallbacks (``aggregate.scala:711-792``).  A SplitAndRetryOOM halves
        the failing group (or the batch itself when the group is one batch),
        so recovery degrades gracefully down to two-row merges.  With a
        ``tctx`` (merge mode) every pass counts under its reduction form."""
        from ...memory.retry import split_spillable_in_half, with_retry
        from ...memory.spill import (ACTIVE_BATCHING_PRIORITY,
                                     SpillableColumnarBatch)

        class _Group:
            def __init__(self, parts):
                self.parts = list(parts)

            def close(self):
                for p in self.parts:
                    p.close()
                self.parts = []

        def merge_group(g: "_Group"):
            # NB: a single batch still needs the merge pass — a shuffled
            # batch is a host-concat of several maps' partial rows with
            # duplicate keys (merging already-merged groups is idempotent)
            batches = [p.get() for p in g.parts]
            merged = batches[0] if len(batches) == 1 else \
                ColumnarBatch.concat(batches)
            if tctx is not None:
                # a merge reduces into a table as large as its input
                tctx.inc_metric(reduce_form_metric(self.xp, merged.capacity))
            return self._get_merge_fn()(merged).shrunk()

        def split_group(g: "_Group"):
            if len(g.parts) >= 2:
                mid = len(g.parts) // 2
                out = [_Group(g.parts[:mid]), _Group(g.parts[mid:])]
            else:
                halves = split_spillable_in_half(g.parts[0])
                out = [_Group([h]) for h in halves]
            g.parts = []  # ownership moved to the pieces
            return out

        level = list(spillables)
        needs_pass = True  # even one batch may hold unmerged duplicate keys
        while len(level) > 1 or needs_pass:
            needs_pass = False
            groups = [_Group(level[i:i + fanin])
                      for i in range(0, len(level), fanin)]
            level = [SpillableColumnarBatch.create(out, ACTIVE_BATCHING_PRIORITY)
                     for out in with_retry(groups, merge_group,
                                           split=split_group)]
        return level[0]

    # --- shuffle-complete (collect/percentile) path ------------------------
    def _special_impl(self, OUT: int, widths):
        """Kernel over (batch, mask, rank64, ng) with static OUT + per-
        special array widths: grouped keys + normal slots via
        groupby_reduce, specials via their compute_grouped."""
        special = set(self._special)

        def impl(batch, mask, rank64, ng):
            xp = self.xp
            ctx = EvalContext(batch, xp=xp)
            keys = [g.eval(ctx) for g in self._bound_grouping]
            slot_pairs, ops = [], []
            ranges = {}
            for fi, (f, inputs) in enumerate(zip(self._agg_funcs,
                                                 self._bound_inputs)):
                if fi in special:
                    continue
                in_cols = [e.eval(ctx) for e in inputs]
                pairs = f.update_values(ctx, in_cols)
                ranges[fi] = (len(slot_pairs), len(slot_pairs) + len(pairs))
                slot_pairs.extend(pairs)
                ops.extend(s.op for s in f.slots())
            gk, gs, n = groupby_reduce(xp, keys, slot_pairs, ops, mask,
                                       rank64=rank64, n_groups=ng,
                                       out_size=OUT)
            group_ok = xp.arange(OUT, dtype=xp.int32) < n
            rank = rank64.astype(xp.int32)
            results = {}
            for fi, f in enumerate(self._agg_funcs):
                if fi in special:
                    in_col = self._bound_inputs[fi][0].eval(ctx)
                    results[fi] = f.compute_grouped(
                        ctx, in_col, rank, OUT, widths[fi], mask, group_ok)
                else:
                    lo, hi = ranges[fi]
                    r = f.evaluate(ctx, gs[lo:hi])
                    results[fi] = r.with_validity(r.validity & group_ok)
            post_ctx = None
            if self._post_exprs:
                # compound outputs: evaluate over [keys..., agg results...]
                synth = ColumnarBatch(
                    tuple(f"__fin{i}" for i in
                          range(len(gk) + len(self._agg_funcs))),
                    tuple(gk) + tuple(results[fi]
                                      for fi in range(len(self._agg_funcs))),
                    n)
                post_ctx = EvalContext(synth, xp=xp)
            cols, names = [], []
            for kind, idx, name in self._out_spec:
                names.append(name)
                if kind == "group":
                    cols.append(gk[idx])
                elif kind == "expr":
                    cols.append(self._post_exprs[idx].eval(post_ctx))
                else:
                    cols.append(results[idx])
            return ColumnarBatch(tuple(names), tuple(cols), n)
        return impl

    def _try_special_tdigest(self, batches, tctx):
        """Digest-per-batch + centroid-merge execution for percentile-only
        special aggregates.  Returns the output batch, or None when the
        shape doesn't qualify (mixed aggregates, non-sketchable dtypes,
        strategy says exact)."""
        from ...columnar.column import bucket_capacity
        from ...ops import tdigest as TD
        from ..expressions.aggregates import ApproximatePercentile
        funcs = self._agg_funcs
        if set(self._special) != set(range(len(funcs))):
            return None
        if not all(isinstance(f, ApproximatePercentile) for f in funcs):
            return None
        total_cap = sum(b.capacity for b in batches)
        if not all(f.use_tdigest(total_cap) and f._dtype_sketchable()
                   for f in funcs):
            return None
        xp = self.xp
        delta = max(TD.delta_for_accuracy(f.accuracy) for f in funcs)
        C = TD.n_centroids(delta)
        nf = len(funcs)
        nk = len(self._bound_grouping)
        key_names = tuple(f"__k{i}" for i in range(nk))
        st_names = ("__anchor",) + tuple(f"__{t}{fi}" for fi in range(nf)
                                         for t in ("v", "w", "lo", "hi"))

        def digest_kernel(OUT):
            def impl(batch2, mask, rank64, ng):
                ctx = EvalContext(batch2, xp=xp)
                keys = [g.eval(ctx) for g in self._bound_grouping]
                gk, _gs, n = groupby_reduce(xp, keys, [], [], mask,
                                            rank64=rank64, n_groups=ng,
                                            out_size=OUT)
                group_ok = xp.arange(OUT, dtype=xp.int32) < n
                rank = rank64.astype(xp.int32)
                cap = int(rank.shape[0])
                slot = xp.arange(OUT * C, dtype=xp.int32)
                gidx = slot // np.int32(C)
                ok_row = group_ok[gidx]
                cols = [k.gather(gidx, ok_row) for k in gk]
                # anchor: one guaranteed-live row per live group, so a
                # group whose percentile inputs are ALL NULL (every
                # weight 0) still reaches the merge grouping and emits
                # its (key, NULL) output row like the exact path does
                anchor = (slot % np.int32(C) == 0) & ok_row
                cols.append(DeviceColumn(T.BOOLEAN, anchor,
                                         xp.ones(OUT * C, dtype=bool)))
                for fi, f in enumerate(funcs):
                    in_col = self._bound_inputs[fi][0].eval(ctx)
                    valid = (in_col.validity if in_col.validity is not None
                             else xp.ones(cap, dtype=bool))
                    means, wts, vmin, vmax, _tot = TD.build_grouped(
                        xp, in_col.data, xp.ones(cap, dtype=xp.float64),
                        valid, rank, mask, OUT, delta)
                    w = xp.where(ok_row, wts.reshape(-1), 0.0)
                    live = w > 0
                    for arr in (means.reshape(-1), w,
                                vmin[gidx], vmax[gidx]):
                        cols.append(DeviceColumn(T.DOUBLE,
                                                 arr.astype(xp.float64),
                                                 live))
                return ColumnarBatch(
                    key_names + st_names, tuple(cols),
                    xp.asarray(OUT * C, dtype=xp.int32))
            return impl

        pseudo = []
        total_groups = 0
        for b in batches:
            batch2, mask, rank64, ng = self._get_group_fn()(b)
            ng0 = int(ng)
            total_groups += max(ng0, 1)
            OUT = min(bucket_capacity(max(ng0, 1),
                                      minimum=self._table_floor()),
                      batch2.capacity)
            key = ("tdigest-batch", OUT, C, self._partial_key,
                   tuple(f._key_extras() for f in funcs))
            fn = self._jit(digest_kernel(OUT), key=key)
            pseudo.append(fn(batch2, mask, rank64, ng))
        big = ColumnarBatch.concat(pseudo)
        # merge: total distinct groups is bounded by the per-batch sum
        OUTM = min(bucket_capacity(max(total_groups, 1),
                                   minimum=self._table_floor()),
                   big.capacity)

        def merge_kernel(bigb):
            mask = bigb.row_mask()
            kcols = [bigb.column(nm) for nm in key_names]
            any_w = bigb.column("__anchor").data
            for fi in range(nf):
                w = bigb.column(f"__w{fi}").data
                any_w = any_w | (w > 0)
            live = mask & any_w
            rank64m, ngm = group_phase(xp, kcols, live,
                                       expected_groups=OUTM)
            gk, _gs, n = groupby_reduce(xp, kcols, [], [], live,
                                        rank64=rank64m, n_groups=ngm,
                                        out_size=OUTM)
            group_ok = xp.arange(OUTM, dtype=xp.int32) < n
            rank = rank64m.astype(xp.int32)
            results = {}
            for fi, f in enumerate(funcs):
                cols_f, counts = f.tdigest_from_weighted(
                    xp, bigb.column(f"__v{fi}").data,
                    xp.where(bigb.column(f"__w{fi}").validity,
                             bigb.column(f"__w{fi}").data, 0.0),
                    bigb.column(f"__lo{fi}").data,
                    bigb.column(f"__hi{fi}").data,
                    rank, live, OUTM, delta, group_ok)
                results[fi] = f.assemble_output(xp, cols_f, counts,
                                                group_ok)
            post_ctx = None
            if self._post_exprs:
                synth = ColumnarBatch(
                    tuple(f"__fin{i}" for i in range(len(gk) + nf)),
                    tuple(gk) + tuple(results[fi] for fi in range(nf)), n)
                post_ctx = EvalContext(synth, xp=xp)
            cols, names = [], []
            for kind, idx, name in self._out_spec:
                names.append(name)
                if kind == "group":
                    cols.append(gk[idx])
                elif kind == "expr":
                    cols.append(self._post_exprs[idx].eval(post_ctx))
                else:
                    cols.append(results[idx])
            return ColumnarBatch(tuple(names), tuple(cols), n), ngm

        mkey = ("tdigest-merge", OUTM, C, big.capacity,
                self._finalize_key,
                tuple(f._key_extras() for f in funcs))
        out, ngm = self._jit(merge_kernel, key=mkey)(big)
        if int(ngm) > OUTM:
            # the bounded group probe gave up (pathologically clustered
            # keys) and inflated the count — same overflow signal the
            # speculation layer validates; discard and let the caller run
            # the exact concat path
            return None
        return out.with_known_rows(int(out.num_rows))

    def _execute_special(self, pid: int, tctx: TaskContext):
        from ...columnar.column import bucket_capacity, bucket_width
        child = self.children[0]
        batches = list(child.execute(pid, tctx))
        batches = [b for b in batches if b.num_rows_int > 0]
        if not batches:
            if self.grouping:
                yield self._empty_output()
                return
            # global aggregate over empty input: one row (empty arrays /
            # null percentiles / zero counts) — run the kernel on an
            # empty batch; _ShuffleCompleteAggregate can't finalize from
            # scalar slots so _empty_output's path would raise
            from .exchange import empty_batch_for
            batches = [empty_batch_for(child.output)]
        if self.backend == TPU and len(batches) > 1:
            # percentile-only aggregates over many batches: digest each
            # batch into fixed [groups, C] centroid state and merge the
            # digests — the concat of raw rows (the memory cliff of the
            # shuffle-complete path) never happens (ops/tdigest.py;
            # reference GpuApproximatePercentile merge path)
            out = self._try_special_tdigest(batches, tctx)
            if out is not None:
                tctx.inc_metric("aggTdigestMergedBatches", len(batches))
                yield out
                return
        merged = ColumnarBatch.concat(batches) if len(batches) > 1 \
            else batches[0]
        tctx.inc_metric("aggSpecialBatches")
        if self.backend != TPU:
            # eager numpy path: exact sizes, no bucketing needed
            mask = np.asarray(merged.row_mask()) \
                if hasattr(merged, "row_mask") else None
            b2 = merged
            for step in self._pre_steps:
                b2, mask = step._fuse_step(b2, mask, self.xp)
            from .aggregate import group_phase  # self-module (clarity)
            rank64, ng = group_phase(self.xp, [
                g.eval(EvalContext(b2, xp=self.xp))
                for g in self._bound_grouping], mask)
            OUT = max(int(ng), 1)
            maxc = self._max_group_count(self.xp, rank64, mask, OUT)
            widths = {fi: max(self._agg_funcs[fi].max_width(maxc), 1)
                      for fi in self._special}
            yield self._special_impl(OUT, widths)(b2, mask, rank64, ng)
            return
        from .base import count_stage_dispatch
        count_stage_dispatch(2)  # group phase + special reduce
        batch2, mask, rank64, ng = self._get_group_fn()(merged)
        ng0 = int(ng)  # ONE sync; global aggregates already floored to 1
        maxc = self._max_group_count(self.xp, rank64, mask,
                                     batch2.capacity)
        # grouped queries keep a floor (group_table_floor); the global
        # path sizes exactly
        OUT = min(bucket_capacity(max(ng0, 1),
                                  minimum=self._table_floor()),
                  batch2.capacity)
        widths = {fi: bucket_width(
            max(self._agg_funcs[fi].max_width(maxc), 1))
            for fi in self._special}
        from .kernel_cache import exprs_key as _ek
        key = ("special", OUT, tuple(sorted(widths.items())),
               tuple(self._out_spec), _ek(self._post_exprs),
               self._partial_key)
        fn = self._jit(self._special_impl(OUT, widths), key=key)
        out = fn(batch2, mask, rank64, ng)
        # unfloored: a fully-filtered partition reports 0 rows, not 1
        yield out.with_known_rows(ng0)

    def _max_group_count(self, xp, rank64, mask, bound: int) -> int:
        """Host-synced max rows in any one group (sizes collect widths)."""
        counts = xp.zeros(bound, dtype=xp.int32)
        tgt = xp.where(mask, rank64, bound)
        if xp.__name__ == "numpy":
            import numpy as np_
            sel = np_.asarray(tgt) < bound
            np_.add.at(counts, np_.asarray(tgt)[sel], 1)
            return int(counts.max()) if bound else 0
        counts = counts.at[tgt].add(1)
        return int(xp.max(counts))

    # --- execute ----------------------------------------------------------
    def execute(self, pid: int, tctx: TaskContext):
        """Counts the rows of the group table a final or complete aggregate
        hands on (``aggGroupRows`` of last_query_metrics; the count is read
        when the task has ended)."""
        if self.mode not in ("final", "complete"):
            yield from self._execute(pid, tctx)
            return
        for out in self._execute(pid, tctx):
            tctx.inc_metric_late("aggGroupRows", out.num_rows)
            yield out

    def _execute(self, pid: int, tctx: TaskContext):
        """Out-of-core contract (``GpuMergeAggregateIterator``
        ``aggregate.scala:711-792``): inputs are registered as spillable the
        moment they arrive, and every device kernel runs under the retry
        framework so a RetryOOM spills-and-reruns and a SplitAndRetryOOM
        halves the failing batch."""
        from ...memory.retry import split_spillable_in_half, with_retry
        from ...memory.spill import (ACTIVE_BATCHING_PRIORITY,
                                     ACTIVE_ON_DECK_PRIORITY,
                                     SpillableColumnarBatch)
        child = self.children[0]
        if self._special:
            if self.mode != "complete":
                raise RuntimeError(
                    "collect/percentile aggregates require shuffle-"
                    "complete planning (planner bug)")
            yield from self._execute_special(pid, tctx)
            return
        if self.mode in ("final", "merge"):
            partials = [SpillableColumnarBatch.create(b, ACTIVE_BATCHING_PRIORITY)
                        for b in child.execute(pid, tctx)]
            if not partials:
                if self.mode == "final":
                    yield self._empty_output()
                return
            if self.mode == "merge":
                # merge-only (the mixed-DISTINCT middle stage): group the
                # partial layout by its keys, KEEPING slots mergeable —
                # every (keys...) tuple becomes unique in this partition
                yield self._merge_spillables(partials,
                                             tctx=tctx).get_and_close()
                return
            if len(partials) == 1:
                # single partial (the common post-AQE-coalesce shape):
                # merge+finalize as ONE compiled program — each separate
                # kernel costs its own launch and sync.  The
                # oom_guard inside handles spill+retry; if it escalates to
                # a split, halved-then-finalized pieces would be WRONG, so
                # fall through to the spillable merge path instead.
                from ...memory.retry import SplitAndRetryOOM
                try:
                    out = self._merge_finalize_fn()(partials[0].get())
                except SplitAndRetryOOM:
                    pass  # spillable still owned; use the general path
                else:
                    partials[0].close()
                    yield out
                    return
            merged = self._merge_spillables(partials).get_and_close()
            if self._finalize_jit is None:
                self._finalize_jit = self._jit(self._finalize,
                                               key=self._finalize_key)
            yield self._finalize_jit(merged)
            return

        if self.mode == "complete":
            # zero-pull speculative path (single batch + recorded size +
            # deferral enabled); falls through to the exact path otherwise.
            # Peek ONE batch only — a many-batch child must keep streaming
            # into spillables, not sit pinned on device in a list.
            src = child.execute(pid, tctx)
            first = next(src, None)
            second = next(src, None) if first is not None else None
            if first is not None and second is None:
                fast = self._try_deferred_complete([first])
                if fast is not None:
                    tctx.inc_metric("aggDeferredComplete")
                    tctx.inc_metric(reduce_form_metric(self.xp,
                                                       fast.capacity))
                    yield fast
                    return
            from itertools import chain
            head = [b for b in (first, second) if b is not None]
            source: Iterator = chain(head, src)
        else:
            source = child.execute(pid, tctx)
        partials = []
        try:
            for batch in source:
                sb = SpillableColumnarBatch.create(batch, ACTIVE_ON_DECK_PRIORITY)
                for out in with_retry([sb],
                                      lambda s: self._run_partial(s.get()),
                                      split=split_spillable_in_half):
                    tctx.inc_metric("aggPartialBatches")
                    # the partial's capacity is its group-table size
                    tctx.inc_metric(reduce_form_metric(self.xp,
                                                       out.capacity))
                    partials.append(SpillableColumnarBatch.create(
                        out.shrunk(), ACTIVE_BATCHING_PRIORITY))
        except BaseException:
            for p in partials:
                p.close()
            raise
        if not partials:
            yield self._empty_output()
            return
        if self.mode == "partial" and len(partials) == 1:
            # a single _run_partial output has unique keys by construction
            # (one row per group) — the cross-batch merge pass would be an
            # identity costing one kernel + one row-count sync; downstream
            # final/merge stages handle any cross-partition duplicates
            yield partials[0].get_and_close()
            return
        merged = self._merge_spillables(partials).get_and_close()
        if self.mode == "partial":
            yield merged
        else:  # complete
            if self._finalize_jit is None:
                self._finalize_jit = self._jit(self._finalize,
                                               key=self._finalize_key)
            yield self._finalize_jit(merged)

    def _empty_output(self):
        """Zero-group output; global aggregate over empty input still yields
        one row (Spark semantics) — handled by faking one empty-keyed group."""
        xp = self.xp
        if self.grouping or self.mode == "partial":
            schema = T.StructType(tuple(
                T.StructField(a.name, a.dtype, True) for a in self.output))
            b = ColumnarBatch.empty(schema)
            if self.backend != TPU:
                import jax
                b = jax.device_get(b)
            return b
        # global agg over empty input: evaluate over an all-dead batch
        from ...columnar.column import null_column
        cap = 8
        slots = []
        for f in self._agg_funcs:
            for s in f.slots():
                c = null_column(s.dtype, cap)
                if s.op == COUNT:
                    c = DeviceColumn(T.LONG, xp.zeros(cap, dtype=xp.int64),
                                     xp.ones(cap, dtype=bool))
                slots.append(c)
        names = tuple(f"_s{i}" for i in range(len(slots)))
        fake = ColumnarBatch(names, tuple(slots), xp.asarray(1, dtype=xp.int32))
        return self._finalize(fake)

    def simple_string(self):
        g = ", ".join(e.sql() for e in self.grouping)
        a = ", ".join(e.sql() for e in self.agg_out)
        return f"{self.node_name()}({self.mode}) keys=[{g}] aggs=[{a}]"
