"""Logical -> physical planning with device placement and transition
insertion (the reference splits this across Catalyst planning +
``GpuOverrides.doConvertPlan`` + ``GpuTransitionOverrides``; SURVEY §3.2)."""

from __future__ import annotations

from typing import List, Optional

from ..config import RapidsConf
from ..parallel.partitioning import (HashPartitioning, RangePartitioning,
                                     RoundRobinPartitioning, SinglePartitioning)
from . import column_pruning, join_order
from . import plan as P
from .expressions.core import AttributeReference
from .overrides import PlanMeta, TpuOverrides
from .physical.aggregate import HashAggregateExec
from .physical.base import CPU, TPU, PhysicalPlan
from .physical.basic import (CoalescePartitionsExec, ExpandExec, FilterExec,
                             GlobalLimitExec, InMemoryScanExec, LocalLimitExec,
                             ProjectExec, RangeExec, SampleExec, UnionExec)
from .physical.exchange import BroadcastExchangeExec, ShuffleExchangeExec
from .physical.sortlimit import SortExec, TakeOrderedAndProjectExec
from .physical.transitions import (CoalesceBatchesExec, DeviceToHostExec,
                                   HostToDeviceExec)


class Planner:
    def __init__(self, conf: Optional[RapidsConf] = None):
        self.conf = conf or RapidsConf.get_global()

    # ------------------------------------------------------------------
    def plan(self, logical: P.LogicalPlan) -> PhysicalPlan:
        # a new tree: everything below reads (and keys by node identity)
        # the reordered and pruned plan, the caller's is left as it was
        logical, self.joins_reordered = join_order.order_joins(logical)
        logical = column_pruning.prune_columns(logical)
        self._window_group_limits = {}
        parents: dict = {}
        _count_parents(logical, parents, set())
        _annotate_window_group_limits(logical, self._window_group_limits,
                                      parents)
        meta = TpuOverrides.apply(logical, self.conf)
        if self.conf.is_explain_only:
            _force_cpu(meta)
        from ..config import OPTIMIZER_ENABLED
        if bool(self.conf.get(OPTIMIZER_ENABLED)):
            from .optimizer import apply_cost_optimizer
            apply_cost_optimizer(meta, self.conf)
        phys = self._convert(meta)
        phys = _insert_transitions(phys)
        from ..config import FUSION_ENABLED
        if bool(self.conf.get(FUSION_ENABLED)):
            from .physical.fusion import fuse_stages
            phys = fuse_stages(phys, self.conf)
        return phys

    def plan_for_collect(self, logical: P.LogicalPlan) -> PhysicalPlan:
        phys = self.plan(logical)
        if phys.backend == TPU:
            phys = DeviceToHostExec(phys)
        from ..config import FUSION_ENABLED
        if bool(self.conf.get(FUSION_ENABLED)):
            from .physical.collect_fusion import fuse_collect_tail
            phys = fuse_collect_tail(phys)
        # async prefetch boundaries go in LAST (after fuse_stages and the
        # collect-tail fusion) so the fusion passes pattern-match the
        # unwrapped tree; see sql/physical/async_exec.py for the seams
        from ..config import PREFETCH_ENABLED
        if bool(self.conf.get(PREFETCH_ENABLED)):
            from .physical.async_exec import insert_prefetch
            phys = insert_prefetch(phys, self.conf)
        # plan-time fusion coverage counters (wholeStageOps/unfusedOps)
        # fold into last_query_metrics via the collect_metrics walk
        from .physical.fusion import annotate_stage_coverage
        phys = annotate_stage_coverage(phys)
        phys.metrics["joinsReordered"] = float(self.joins_reordered)
        return phys

    # ------------------------------------------------------------------
    def _convert(self, meta: PlanMeta) -> PhysicalPlan:
        node = meta.node
        be = meta.backend
        kids = [self._convert(c) for c in meta.children]

        if isinstance(node, P.Relation):
            parts = node.partitions if node.partitions is not None else [node.table]
            exec_ = InMemoryScanExec(node.output, parts, backend=be)
        elif isinstance(node, P.CachedRelation):
            exec_ = InMemoryScanExec(node.output, [node.table], backend=be)
        elif isinstance(node, P.ScanRelation):
            from ..io_.exec import FileScanExec
            exec_ = FileScanExec(node, backend=be, conf=self.conf)
        elif isinstance(node, P.Range):
            exec_ = RangeExec(node.start, node.end, node.step, node.num_slices,
                              backend=be)
        elif isinstance(node, P.Project):
            exec_ = ProjectExec(node.exprs, kids[0], backend=be)
        elif isinstance(node, P.Filter):
            from ..io_.exec import FileScanExec
            if isinstance(kids[0], FileScanExec):
                # scan-adjacent filter: push prunable conjuncts into the
                # scan for footer-statistics row-group skipping (reference
                # predicate pushdown, GpuParquetScan.scala:2765); the
                # device filter above keeps the full predicate
                from ..io_.pushdown import extract_pushable
                kids[0].pushed_filters = extract_pushable(
                    node.condition, kids[0].output)
            exec_ = FilterExec(node.condition, kids[0], backend=be)
        elif isinstance(node, P.Sample):
            exec_ = SampleExec(node.lower, node.upper, node.seed, kids[0],
                               backend=be)
        elif isinstance(node, P.Expand):
            exec_ = ExpandExec(node.projections, node.out_attrs, kids[0],
                               backend=be)
        elif isinstance(node, P.Union):
            kids = [_coerce_backend(k, kids[0].backend) for k in kids]
            exec_ = UnionExec(kids, backend=kids[0].backend)
        elif isinstance(node, P.Aggregate):
            exec_ = self._plan_aggregate(node, kids[0], be)
        elif isinstance(node, P.Window):
            exec_ = self._plan_window(node, kids[0], be)
        elif isinstance(node, P.Generate):
            from .physical.generate import GenerateExec
            exec_ = GenerateExec(node.generator, node.outer,
                                 node.gen_output, kids[0], backend=be)
        elif isinstance(node, P.Sort):
            exec_ = self._plan_sort(node, kids[0], be)
        elif isinstance(node, P.Limit):
            exec_ = self._plan_limit(node, kids[0], be)
        elif isinstance(node, P.Repartition):
            if node.exprs:
                part = HashPartitioning(node.exprs, node.num_partitions)
            else:
                part = RoundRobinPartitioning(node.num_partitions)
            # USER-requested repartitioning is exempt from AQE coalescing
            # (Spark likewise honors explicit repartition under AQE)
            exec_ = ShuffleExchangeExec(part, kids[0],
                                        backend=kids[0].backend,
                                        coalescible=False)
        elif isinstance(node, P.Join):
            from .physical.join import plan_join
            exec_ = plan_join(node, kids[0], kids[1], be, self.conf)
            exec_.kept_share = node.kept_share
        elif isinstance(node, P.MapInPandas):
            from .physical.python_execs import MapInPandasExec
            exec_ = MapInPandasExec(node.func, node.out_schema, kids[0],
                                    backend=be)
        elif isinstance(node, P.AggregateInPandas):
            from .physical.python_execs import AggregateInPandasExec
            child = kids[0]
            if child.num_partitions() > 1:
                part = (HashPartitioning(list(node.grouping),
                                         child.num_partitions())
                        if node.grouping else SinglePartitioning())
                child = ShuffleExchangeExec(part, child,
                                            backend=child.backend)
            names = [getattr(g, "name", str(g)) for g in node.grouping]
            exec_ = AggregateInPandasExec(names, list(node.agg_udfs),
                                          child, backend=be)
        elif isinstance(node, P.FlatMapGroupsInPandas):
            from .physical.python_execs import FlatMapGroupsInPandasExec
            child = kids[0]
            if child.num_partitions() > 1:
                # groups must be complete per partition
                child = ShuffleExchangeExec(
                    HashPartitioning(list(node.grouping),
                                     child.num_partitions()),
                    child, backend=child.backend)
            names = [getattr(g, "name", str(g)) for g in node.grouping]
            exec_ = FlatMapGroupsInPandasExec(names, node.func,
                                              node.out_schema, child,
                                              backend=be)
        elif isinstance(node, P.FlatMapCoGroupsInPandas):
            from .physical.python_execs import FlatMapCoGroupsInPandasExec
            lk, rk = kids
            n = max(lk.num_partitions(), rk.num_partitions())
            if n > 1:
                # co-partition BOTH sides identically; never coalesced
                lk = ShuffleExchangeExec(
                    HashPartitioning(list(node.left_grouping), n), lk,
                    backend=lk.backend, coalescible=False)
                rk = ShuffleExchangeExec(
                    HashPartitioning(list(node.right_grouping), n), rk,
                    backend=rk.backend, coalescible=False)
            lnames = [getattr(g, "name", str(g))
                      for g in node.left_grouping]
            rnames = [getattr(g, "name", str(g))
                      for g in node.right_grouping]
            exec_ = FlatMapCoGroupsInPandasExec(lnames, rnames, node.func,
                                                node.out_schema, lk, rk,
                                                backend=be)
        else:
            raise NotImplementedError(
                f"no physical plan for {type(node).__name__}")

        exec_._placement_reasons = list(dict.fromkeys(meta.reasons))
        return exec_

    # ------------------------------------------------------------------
    def _plan_aggregate(self, node: P.Aggregate, child: PhysicalPlan, be):
        from .expressions.aggregates import AggregateFunction
        distinct, regular = _collect_distinct(node)
        if distinct:
            if distinct_rewrite_applies(node, (distinct, regular)):
                inner, outer = self._rewrite_distinct(node, distinct)
                inner_exec = self._plan_aggregate(inner, child, be)
                return self._plan_aggregate(outer, inner_exec, be)
            if _mixed_distinct_applies(node, distinct, regular):
                return self._plan_mixed_distinct(node, child, be, distinct,
                                                 regular)
            if _expand_distinct_applies(node, distinct, regular):
                return self._plan_expand_distinct(node, child, be,
                                                  distinct, regular)
            raise NotImplementedError(UNSUPPORTED_DISTINCT_MSG)
        nparts = child.num_partitions()
        special = any(
            getattr(f, "requires_shuffle_complete", False)
            for e in node.aggregates
            for f in e.collect(lambda x: isinstance(x, AggregateFunction)))
        if special:
            # collect_list/collect_set/approx_percentile: results build
            # from raw rows (no mergeable partial slots) — shuffle rows by
            # key, then ONE complete aggregate per partition
            if nparts > 1:
                part = (HashPartitioning(list(node.grouping), nparts)
                        if node.grouping else SinglePartitioning())
                child = ShuffleExchangeExec(part, child,
                                            backend=child.backend)
            return HashAggregateExec(node.grouping, node.aggregates,
                                     "complete", child, backend=be)
        if nparts <= 1:
            return HashAggregateExec(node.grouping, node.aggregates,
                                     "complete", child, backend=be)
        partial = HashAggregateExec(node.grouping, node.aggregates, "partial",
                                    child, backend=be)
        if node.grouping:
            key_refs = partial.output[:len(node.grouping)]
            part = HashPartitioning(
                key_refs, int(self.conf.shuffle_partitions))
        else:
            part = SinglePartitioning()
        shuffled = ShuffleExchangeExec(part, partial, backend=be)
        return HashAggregateExec(node.grouping, node.aggregates, "final",
                                 shuffled, backend=be)

    def _rewrite_distinct(self, node: P.Aggregate, distinct):
        """count/sum/avg(DISTINCT x[, y...]) GROUP BY k  ->
        (inner dedup aggregate over (k, x, y...), outer aggregate of the
        plain functions over the deduped rows).  Caller has established
        distinct_rewrite_applies(); ``distinct`` is its collected list."""
        from .expressions.aggregates import AggregateExpression
        from .expressions.core import Alias
        dchildren = list(distinct[0].func.children)
        # inner: dedup via group-by over grouping + distinct children
        # (grouping keys are plain attributes — distinct_rewrite_applies
        # guarantees it, so outer outputs rebind by name)
        inner_outs = list(node.grouping)
        dnames = []
        for j, ch in enumerate(dchildren):
            nm = f"__dv{j}"
            dnames.append(nm)
            inner_outs.append(Alias(ch, nm))
        inner = P.Aggregate(tuple(node.grouping) + tuple(dchildren),
                            tuple(inner_outs), node.children[0])
        inner_attrs = inner.output
        key_attrs = inner_attrs[:len(node.grouping)]
        d_attrs = inner_attrs[len(node.grouping):]

        # outer: original outputs with DISTINCT dropped and children
        # rebound to the deduped columns
        def rewrite(e):
            if isinstance(e, AggregateExpression) and e.is_distinct:
                f = e.func.with_children(tuple(d_attrs))
                return AggregateExpression(f, e.mode, False, e.filter)
            if not getattr(e, "children", ()):  # leaf (incl. grouping ref)
                return e
            return e.with_children(tuple(rewrite(c) for c in e.children))

        outer_outs = []
        for e in node.aggregates:
            if isinstance(e, AttributeReference):
                # grouping passthrough: POSITIONAL rebind (name matching
                # would pick the wrong column under duplicate names)
                idx = [j for j, g in enumerate(node.grouping) if g is e
                       or (isinstance(g, AttributeReference)
                           and g.expr_id == e.expr_id)]
                if not idx:
                    raise NotImplementedError(UNSUPPORTED_DISTINCT_MSG)
                outer_outs.append(key_attrs[idx[0]])
            else:
                outer_outs.append(rewrite(e))
        outer = P.Aggregate(tuple(key_attrs), tuple(outer_outs), inner)
        return inner, outer

    def _plan_mixed_distinct(self, node: P.Aggregate, child, be,
                             distinct, regular):
        """Mixed DISTINCT + plain aggregates, e.g.
        ``agg(countDistinct(v), sum(w)) GROUP BY k``:

        1. INNER partial aggregate grouped by (k, v): plain funcs update
           into their mergeable slot layout; one row per (k, v) group.
        2. Hash-exchange the partial rows by k.
        3. OUTER complete aggregate grouped by k: the distinct funcs run
           as PLAIN funcs over the deduped v values, and each plain func
           re-merges its partial slots via PreMergedAggregate — exactly
           the partial->final layering the engine already trusts, just
           under coarser keys (Spark reaches the same result via Expand).
        """
        from .expressions.aggregates import (AggregateExpression,
                                             AggregateFunction,
                                             PreMergedAggregate)
        from .expressions.core import Alias
        dchildren = list(distinct[0].func.children)
        nk, nd = len(node.grouping), len(dchildren)

        # inner: partial agg grouped by keys + distinct children, with the
        # REGULAR funcs as its aggregates (order = their slot order)
        inner_aggs = tuple(Alias(AggregateExpression(f)
                                 if not isinstance(f, AggregateExpression)
                                 else f, f"__r{i}")
                           for i, f in enumerate(regular))
        inner = HashAggregateExec(
            tuple(node.grouping) + tuple(dchildren), inner_aggs, "partial",
            child, backend=be)
        mid = inner
        if child.num_partitions() > 1:
            key_refs = inner.output[:nk]
            part = (HashPartitioning(key_refs,
                                     int(self.conf.shuffle_partitions))
                    if node.grouping else SinglePartitioning())
            exchanged = ShuffleExchangeExec(part, inner, backend=be)
            # different map partitions each hold their own partial row for
            # the same (keys, distinct-values) tuple: a merge-only stage
            # re-groups by the full tuple so the outer's distinct count
            # sees each tuple exactly once (slots stay mergeable)
            mid = HashAggregateExec(
                tuple(node.grouping) + tuple(dchildren), inner_aggs,
                "merge", exchanged, backend=be)

        key_attrs = inner.output[:nk]
        d_attrs = inner.output[nk:nk + nd]
        slot_attrs = inner.output[nk + nd:]
        # slot range per regular func, in inner_aggs order.  The exec
        # DEDUPS semantically identical aggregates into one slot set
        # (HashAggregateExec.register_agg), so identical funcs must map
        # to the SAME range here (no FILTER clauses on this path —
        # _mixed_distinct_applies rejects them)
        ranges = {}
        seen_ranges = {}
        off = 0
        for f in regular:
            base = f.func if isinstance(f, AggregateExpression) else f
            fk = base.semantic_key()
            if fk not in seen_ranges:
                n = len(base.slots())
                seen_ranges[fk] = (off, off + n)
                off += n
            ranges[id(f)] = seen_ranges[fk]

        def rewrite(e):
            if isinstance(e, AggregateExpression):
                if e.is_distinct:
                    return e.func.with_children(tuple(d_attrs))
                lo, hi = ranges[id(e)]
                base = e.func
                return PreMergedAggregate(base, *slot_attrs[lo:hi])
            if isinstance(e, AggregateFunction):
                if id(e) in ranges:
                    lo, hi = ranges[id(e)]
                    return PreMergedAggregate(e, *slot_attrs[lo:hi])
                return e
            if not getattr(e, "children", ()):
                return e
            return e.with_children(tuple(rewrite(c) for c in e.children))

        outer_outs = []
        for e in node.aggregates:
            if isinstance(e, AttributeReference):
                idx = [j for j, g in enumerate(node.grouping) if g is e
                       or (isinstance(g, AttributeReference)
                           and g.expr_id == e.expr_id)]
                if not idx:
                    raise NotImplementedError(UNSUPPORTED_DISTINCT_MSG)
                outer_outs.append(Alias(key_attrs[idx[0]], e.name))
            else:
                outer_outs.append(rewrite(e))
        return HashAggregateExec(tuple(key_attrs), tuple(outer_outs),
                                 "complete", mid, backend=be)

    def _plan_expand_distinct(self, node: P.Aggregate, child, be,
                              distinct, regular):
        """DISTINCT aggregates over SEVERAL child sets (+ optional plain
        aggregates) — Spark's ``RewriteDistinctAggregates`` Expand
        construction (reference executes the resulting ExpandExec via
        ``GpuExpandExec.scala``):

        1. EXPAND each row into m+1 projections: gid 0 carries the
           regular-aggregate inputs (all child columns) and a constant-1
           marker; gid j carries ONLY group j's distinct child
           expressions (everything else typed-NULL).  Grouping keys stay
           live on every projection.
        2. Partial aggregate grouped by (keys, gid, all distinct cols):
           plain funcs with their inputs masked to gid 0, so gid>0 rows
           contribute identity slots.  count(*) counts the marker.
        3. Hash-exchange by keys, merge on the full grouping tuple (each
           (keys, gid, d-tuple) survives exactly once).
        4. Complete aggregate by keys: distinct funcs run as PLAIN funcs
           over their d-columns masked to their own gid (null inputs from
           other gids are ignored by aggregate semantics); plain funcs
           re-merge their slots via PreMergedAggregate.
        """
        from .expressions.aggregates import (AggregateExpression,
                                             AggregateFunction, Count,
                                             PreMergedAggregate)
        from .expressions.conditional import If
        from .expressions.core import Alias, Literal
        from .expressions.predicates import EqualTo
        from .. import types as T

        # distinct groups, gid 1..m in first-seen order
        group_of: dict = {}
        group_children: list = []
        for d in distinct:
            k = tuple(c.semantic_key() for c in d.func.children)
            if k not in group_of:
                group_of[k] = len(group_children) + 1
                group_children.append(list(d.func.children))

        child_attrs = tuple(child.output)
        # grouping keys must stay live on EVERY projection.  Plain-column
        # keys pass through; expression keys are evaluated into their own
        # expand column (the projection still sees all child columns, so
        # the expression computes even on rows whose other outputs are
        # nulled).
        key_ids = {g.expr_id for g in node.grouping
                   if isinstance(g, AttributeReference)}
        gkey_attrs = []
        gkey_exprs = []            # what to project per grouping key
        for i, g in enumerate(node.grouping):
            if isinstance(g, AttributeReference):
                gkey_attrs.append(g)
            else:
                gkey_attrs.append(AttributeReference(
                    f"__gk{i}", g.data_type, True))
            gkey_exprs.append(g)
        extra_keys = [(a, g) for a, g in zip(gkey_attrs, gkey_exprs)
                      if not isinstance(g, AttributeReference)]
        gid_attr = AttributeReference("__did", T.LONG, False)
        marker_attr = AttributeReference("__d0", T.LONG, True)
        dcol_attrs = []
        dcol_pos: dict = {}        # (gid, child_idx) -> index into dcols
        for j, children in enumerate(group_children, start=1):
            for i, c in enumerate(children):
                dcol_pos[(j, i)] = len(dcol_attrs)
                dcol_attrs.append(AttributeReference(
                    f"__d{j}_{i}", c.data_type, True))
        nd = len(dcol_attrs)

        def null_of(dt):
            return Literal(None, dt)

        # child columns stage 1 actually reads: regular-func inputs (the
        # rest project as typed NULLs everywhere — Spark's rewrite also
        # restricts the regular projection to referenced columns)
        used_ids = set(key_ids)
        for f in regular:
            base = f.func if isinstance(f, AggregateExpression) else f
            for c in base.children:
                for a in c.collect(
                        lambda x: isinstance(x, AttributeReference)):
                    used_ids.add(a.expr_id)

        projections = []
        if regular:     # distinct-only queries need no gid-0 projection
            projections.append(
                tuple(a if a.expr_id in used_ids else null_of(a.data_type)
                      for a in child_attrs)
                + tuple(g for _a, g in extra_keys)
                + tuple(null_of(a.data_type) for a in dcol_attrs)
                + (Literal(0, T.LONG), Literal(1, T.LONG)))
        for j, children in enumerate(group_children, start=1):
            row = [a if a.expr_id in key_ids else null_of(a.data_type)
                   for a in child_attrs]
            dvals = [null_of(a.data_type) for a in dcol_attrs]
            for i, c in enumerate(children):
                dvals[dcol_pos[(j, i)]] = c
            projections.append(tuple(row)
                               + tuple(g for _a, g in extra_keys)
                               + tuple(dvals)
                               + (Literal(j, T.LONG), null_of(T.LONG)))
        expand = ExpandExec(
            projections,
            child_attrs + tuple(a for a, _g in extra_keys)
            + tuple(dcol_attrs) + (gid_attr, marker_attr),
            child, backend=be)

        # stage-1 regular funcs: inputs masked to gid 0 (nulls elsewhere
        # make gid>0 rows identity contributions even for literal inputs)
        gid0 = EqualTo(gid_attr, Literal(0, T.LONG))

        def stage1_base(f):
            base = f.func if isinstance(f, AggregateExpression) else f
            if not base.children:
                return Count(marker_attr)      # count(*) over the marker
            return base.with_children(tuple(
                If(gid0, c, null_of(c.data_type)) for c in base.children))

        inner_aggs = tuple(Alias(AggregateExpression(stage1_base(f)),
                                 f"__r{i}")
                           for i, f in enumerate(regular))
        nk = len(node.grouping)
        g1 = tuple(gkey_attrs) + (gid_attr,) + tuple(dcol_attrs)
        inner = HashAggregateExec(g1, inner_aggs, "partial", expand,
                                  backend=be)
        mid = inner
        if child.num_partitions() > 1:
            key_refs = inner.output[:nk]
            part = (HashPartitioning(key_refs,
                                     int(self.conf.shuffle_partitions))
                    if node.grouping else SinglePartitioning())
            exchanged = ShuffleExchangeExec(part, inner, backend=be)
            mid = HashAggregateExec(
                tuple(inner.output[:nk + 1 + nd]), inner_aggs, "merge",
                exchanged, backend=be)

        key_attrs = inner.output[:nk]
        gid_out = inner.output[nk]
        d_out = inner.output[nk + 1:nk + 1 + nd]
        slot_attrs = inner.output[nk + 1 + nd:]

        # slot range per regular func (dedup identical funcs the same way
        # HashAggregateExec.register_agg does)
        ranges = {}
        seen_ranges = {}
        off = 0
        for f in regular:
            fk = stage1_base(f).semantic_key()
            if fk not in seen_ranges:
                n = len(stage1_base(f).slots())
                seen_ranges[fk] = (off, off + n)
                off += n
            ranges[id(f)] = seen_ranges[fk]

        def masked_distinct(e):
            j = group_of[tuple(c.semantic_key() for c in e.func.children)]
            pred = EqualTo(gid_out, Literal(j, T.LONG))
            cols = tuple(
                If(pred, d_out[dcol_pos[(j, i)]],
                   null_of(d_out[dcol_pos[(j, i)]].data_type))
                for i in range(len(e.func.children)))
            return e.func.with_children(cols)

        gkey_by_sem = {g.semantic_key(): key_attrs[i]
                       for i, g in enumerate(gkey_exprs)}

        def rewrite(e):
            if isinstance(e, AggregateExpression):
                if e.is_distinct:
                    return masked_distinct(e)
                lo, hi = ranges[id(e)]
                return PreMergedAggregate(stage1_base(e),
                                          *slot_attrs[lo:hi])
            if isinstance(e, AggregateFunction):
                if id(e) in ranges:
                    lo, hi = ranges[id(e)]
                    return PreMergedAggregate(stage1_base(e),
                                              *slot_attrs[lo:hi])
                return e
            sk = e.semantic_key()
            if sk in gkey_by_sem:     # (sub)expression IS a grouping key
                return gkey_by_sem[sk]
            if not getattr(e, "children", ()):
                return e
            return e.with_children(tuple(rewrite(c) for c in e.children))

        outer_outs = []
        for e in node.aggregates:
            if isinstance(e, AttributeReference):
                idx = [j for j, g in enumerate(node.grouping) if g is e
                       or (isinstance(g, AttributeReference)
                           and g.expr_id == e.expr_id)]
                if not idx:
                    raise NotImplementedError(UNSUPPORTED_DISTINCT_MSG)
                outer_outs.append(Alias(key_attrs[idx[0]], e.name))
            else:
                outer_outs.append(rewrite(e))
        return HashAggregateExec(tuple(key_attrs), tuple(outer_outs),
                                 "complete", mid, backend=be)

    def _plan_window(self, node: P.Window, child: PhysicalPlan, be):
        from ..sql.plan import SortOrder
        from .physical.window import WindowExec, WindowGroupLimitExec
        gl = getattr(self, "_window_group_limits", {}).get(id(node))
        if gl is not None and be == TPU and child.backend == TPU:
            kind, k = gl
            # below the exchange: per-map-partition top-k per group is a
            # superset of the global top-k, so the window+filter above stay
            # exact while the shuffle moves only surviving rows
            child = WindowGroupLimitExec(list(node.partition_spec),
                                         list(node.order_spec), kind, k,
                                         child, backend=be)
        if child.num_partitions() > 1:
            if node.partition_spec:
                part = HashPartitioning(list(node.partition_spec),
                                        child.num_partitions())
            else:
                part = SinglePartitioning()
            child = ShuffleExchangeExec(part, child, backend=be)
        orders = ([SortOrder(e) for e in node.partition_spec]
                  + list(node.order_spec))
        if orders:
            child = SortExec(orders, child, backend=be)
        return WindowExec(node.window_exprs, node.partition_spec,
                          node.order_spec, child, backend=be)

    def _plan_sort(self, node: P.Sort, child: PhysicalPlan, be):
        if node.is_global and child.num_partitions() > 1:
            part = RangePartitioning(node.orders, child.num_partitions())
            child = ShuffleExchangeExec(part, child, backend=be)
        return SortExec(node.orders, child, backend=be,
                        is_global=node.is_global)

    def _plan_limit(self, node: P.Limit, child: PhysicalPlan, be):
        # TopN composition (the reference builds TakeOrderedAndProject in
        # the rule, GpuOverrides.scala:3880-3904): Limit directly over a
        # Sort becomes per-partition top-n + merge, skipping the range
        # exchange a global sort would otherwise need
        if node.offset == 0 and isinstance(child, SortExec) \
                and child.backend == be and child.is_global:
            inner = child.children[0]
            if isinstance(inner, ShuffleExchangeExec) and isinstance(
                    inner.partitioning, RangePartitioning):
                inner = inner.children[0]  # top-n needs no range exchange
            return TakeOrderedAndProjectExec(node.n, child.orders, None,
                                             inner, backend=be)
        local = LocalLimitExec(node.n + node.offset, child, backend=be)
        if child.num_partitions() > 1:
            gathered = ShuffleExchangeExec(SinglePartitioning(), local,
                                           backend=be)
        else:
            gathered = local
        return GlobalLimitExec(node.n, node.offset, gathered, backend=be)


def _force_cpu(meta: PlanMeta):
    meta.backend = "cpu"
    for c in meta.children:
        _force_cpu(c)


def _coerce_backend(plan: PhysicalPlan, backend: str) -> PhysicalPlan:
    if plan.backend == backend:
        return plan
    return HostToDeviceExec(plan) if backend == TPU else DeviceToHostExec(plan)


def _insert_transitions(plan: PhysicalPlan) -> PhysicalPlan:
    new_children = tuple(_insert_transitions(c) for c in plan.children)
    fixed = []
    for c in new_children:
        if c.backend != plan.backend and not isinstance(
                plan, (DeviceToHostExec, HostToDeviceExec)):
            c = HostToDeviceExec(c) if plan.backend == TPU else DeviceToHostExec(c)
        fixed.append(c)
    plan.children = tuple(fixed)
    return plan


def _count_parents(node, counts, seen_edges) -> None:
    """Parent-edge counts per logical node id (the logical plan is a DAG:
    a DataFrame reused in two branches shares subtree objects)."""
    for c in getattr(node, "children", ()):
        edge = (id(node), id(c))
        if edge not in seen_edges:
            seen_edges.add(edge)
            counts[id(c)] = counts.get(id(c), 0) + 1
        _count_parents(c, counts, seen_edges)


def _annotate_window_group_limits(node, out, parents) -> None:
    """Logical pre-pass: mark Window nodes sitting under a rank-limit
    filter (``rank()/row_number()/dense_rank() <= k``) so _plan_window can
    insert a WindowGroupLimitExec below the exchange (reference: Spark
    3.5's WindowGroupLimitExec, accelerated via the version shims and
    merged through ``SparkShimImpl.getExecs``)."""
    from .expressions.core import AttributeReference, Literal
    from .expressions.predicates import (And, EqualTo, LessThan,
                                         LessThanOrEqual)
    from .expressions.windows import (DenseRank, Rank, RowNumber,
                                      WindowExpression)

    for c in getattr(node, "children", ()):
        _annotate_window_group_limits(c, out, parents)
    if not isinstance(node, P.Filter):
        return
    # see through projections that pass the rank column along untouched
    # (withColumn/select insert these between the filter and the window)
    from .expressions.core import Alias
    below = node.child
    projects = []
    while isinstance(below, P.Project):
        projects.append(below)
        below = below.child

    def resolve_name(name):
        """Map a filter-level column name down through the project chain to
        the window-output name (withColumn aliases `_weN` to the user
        name); None if any projection rebuilds it with an expression."""
        for pr in projects:
            nxt = None
            for e in pr.exprs:
                if getattr(e, "name", None) != name:
                    continue
                if isinstance(e, AttributeReference):
                    nxt = e.name
                elif isinstance(e, Alias) and isinstance(
                        e.child, AttributeReference):
                    nxt = e.child.name
                break
            if nxt is None:
                return None
            name = nxt
        return name
    if not isinstance(below, P.Window):
        return
    win = below
    if not win.order_spec:
        return
    # the pushdown drops rows below the window, which is only sound when
    # EVERY consumer of the window (and of each pass-through project) sits
    # behind this rank filter — a shared unfiltered branch must see all rows
    chain_nodes = [win] + projects
    if any(parents.get(id(n), 0) > 1 for n in chain_nodes):
        return

    def conjuncts(e):
        if isinstance(e, And):
            for ch in e.children:
                yield from conjuncts(ch)
        else:
            yield e

    # Spark's InferWindowGroupLimit precondition: EVERY window expression
    # on the node must be rank-like.  A lead()/full-frame aggregate sharing
    # the spec would be computed over the truncated input and produce wrong
    # values on surviving rows.
    rank_outputs = {}
    for a in win.window_exprs:
        we = a.child
        if not isinstance(we, WindowExpression):
            return
        kind = {RowNumber: "row_number", Rank: "rank",
                DenseRank: "dense_rank"}.get(type(we.function))
        if kind is None:
            return
        rank_outputs[a.name] = kind

    for conj in conjuncts(node.condition):
        if not (isinstance(conj, (LessThan, LessThanOrEqual, EqualTo))
                and isinstance(conj.children[0], AttributeReference)
                and isinstance(conj.children[1], Literal)):
            continue
        name = resolve_name(conj.children[0].name)
        lit = conj.children[1].value
        if name is None or name not in rank_outputs \
                or not isinstance(lit, (int,)) or isinstance(lit, bool):
            continue
        k = lit - 1 if isinstance(conj, LessThan) else lit
        if k <= 0:
            continue
        out[id(win)] = (rank_outputs[name], int(k))
        return


UNSUPPORTED_DISTINCT_MSG = (
    "DISTINCT aggregates need non-empty DISTINCT child lists, no FILTER "
    "clauses, and (when mixed with plain aggregates) slot-based "
    "null-ignoring plain functions — first()/last() without ignoreNulls "
    "and collect/percentile aggregates can't share a node with DISTINCT")


def _expand_distinct_applies(node: "P.Aggregate", distinct, regular) -> bool:
    """The Expand plan (multiple DISTINCT child sets) needs: non-empty
    child lists, no FILTER clauses anywhere, slot-based NULL-IGNORING
    regular funcs, and count(*) as the only zero-child regular function.
    Grouping keys may be expressions (evaluated into their own expand
    column).  first()/last() without ignoreNulls contribute EVERY live
    row — including the injected gid>0 rows whose inputs the plan masks
    to NULL — so they must take another path."""
    from .expressions.aggregates import (AggregateExpression, Count,
                                         _FirstLast)
    if any(d.filter is not None for d in distinct):
        return False
    if not all(d.func.children for d in distinct):
        return False
    for f in regular:
        base = f.func if isinstance(f, AggregateExpression) else f
        if getattr(base, "requires_shuffle_complete", False):
            return False
        if isinstance(f, AggregateExpression) and f.filter is not None:
            return False
        if not base.children and not isinstance(base, Count):
            return False
        if isinstance(base, _FirstLast) and not base.ignore_nulls:
            return False
    return True


def _collect_distinct(node: "P.Aggregate"):
    """(distinct AggregateExpressions, regular agg funcs) in the node."""
    from .expressions.aggregates import (AggregateExpression,
                                         AggregateFunction)
    distinct, regular = [], []
    for e in node.aggregates:
        wrapped = e.collect(lambda x: isinstance(x, AggregateExpression))
        for a in wrapped:
            (distinct if a.is_distinct else regular).append(a)
        wrapped_funcs = {id(a.func) for a in wrapped}
        for a in e.collect(lambda x: isinstance(x, AggregateFunction)):
            if id(a) not in wrapped_funcs:
                regular.append(a)  # bare function, never DISTINCT
    return distinct, regular


def _distinct_shape_ok(node: "P.Aggregate", distinct) -> bool:
    """Checks shared by both DISTINCT plans: no FILTER clauses, plain-
    column grouping keys, one shared non-empty DISTINCT child set."""
    if any(d.filter is not None for d in distinct):
        return False
    if not all(isinstance(g, AttributeReference) for g in node.grouping):
        return False
    keys = {tuple(c.semantic_key() for c in d.func.children)
            for d in distinct}
    return len(keys) == 1 and all(d.func.children for d in distinct)


def distinct_rewrite_applies(node: "P.Aggregate",
                             precollected=None):
    """DISTINCT aggregates plan as dedup-then-aggregate when every
    aggregate in the node is DISTINCT over the SAME child expressions
    with no FILTER clause, and the grouping keys are plain columns (the
    common count(DISTINCT x)/sum(DISTINCT x) shapes).  Anything else —
    mixed DISTINCT+plain (Spark's Expand plan), differing children,
    filtered or expression-keyed forms — raises at planning: no engine
    path computes those correctly yet, and a silent non-distinct answer
    is worse than an error."""
    distinct, regular = (precollected if precollected is not None
                         else _collect_distinct(node))
    if not distinct or regular:
        return False
    return _distinct_shape_ok(node, distinct)


def _mixed_distinct_applies(node: "P.Aggregate", distinct, regular) -> bool:
    """The mixed plan needs: one shared DISTINCT child set, no FILTER
    clauses, plain-column grouping keys, and slot-based regular funcs
    (shuffle-complete collect/percentile aggregates have no mergeable
    slots)."""
    from .expressions.aggregates import AggregateExpression
    if not _distinct_shape_ok(node, distinct):
        return False
    for f in regular:
        base = f.func if isinstance(f, AggregateExpression) else f
        if getattr(base, "requires_shuffle_complete", False):
            return False
        if isinstance(f, AggregateExpression) and f.filter is not None:
            return False
    return True
