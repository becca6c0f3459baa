"""Whole-query tail fusion — ONE compiled program from scan output to the
packed device→host transfer.

Every dependent program launch and every host pull is a host<->device
round trip the query waits on; what each costs on an attached chip has not
been measured yet (PERF.md).  A q1-shaped query planned as
``DeviceToHost(Sort(HashAggregate(complete)))`` pays three launches and a
fetch.  This pass collapses the tail into one exec whose jitted program is

    fused filters/projects -> group phase -> reductions -> finalize
    -> sort permutation -> byte-pack (convert.pack_leaves_traced)

and whose host side does a single overlapped fetch, unpacks numpy leaves,
and resolves the speculation check from the bundled group count — so a
warm collect costs ONE program launch + ONE fetch latency.

Falls back to the wrapped subtree whenever the speculative preconditions
don't hold (multiple input batches, no recorded group-table size, deferral
disabled, first run).  Reference analog: none — the reference's per-op
kernel-launch model (SURVEY §3.3) is the thing this replaces on TPU.
"""

from __future__ import annotations

from typing import List, Optional

import numpy as np

from ...columnar.batch import ColumnarBatch
from .aggregate import (HashAggregateExec, lookup_speculation,
                        record_speculation, reduce_form_metric)
from .base import CPU, PhysicalPlan
from .sortlimit import SortExec
from .transitions import DeviceToHostExec, batch_nbytes

#: observability for tests/metrics
STATS = {"fused_collects": 0, "fallbacks": 0}

#: process-wide (fn, sig, treedef) per tail key — the planner builds a
#: fresh FusedCollectExec per collect, so an instance cache would pay
#: eval_shape + jit-wrapper lookup every query
_TAIL_PROGRAMS: dict = {}


class _ReplaySource(PhysicalPlan):
    """Feeds already-materialized batches to the fallback subtree."""

    def __init__(self, like: PhysicalPlan, batches: List[ColumnarBatch]):
        super().__init__()
        self.backend = like.backend
        self._like = like
        self._batches = batches

    @property
    def output(self):
        return self._like.output

    def execute(self, pid, tctx):
        return iter(self._batches)

    def node_name(self):
        return "Replay"


class FusedCollectExec(PhysicalPlan):
    """``DeviceToHost(Sort?(HashAggregate(complete|final)))`` as one program.

    Children: the aggregate's child (the device-side source).  The wrapped
    original subtree is kept for the fallback path.

    Complete mode runs under a speculated group-table size (deferred
    validation); final mode — the multi-partition shape, where the child
    is the post-exchange coalesced partial — needs NO speculation: the
    merge's group count is exact and rides home inside the same pack.
    """

    backend = CPU  # emits host batches, like the D2H transition it replaces

    def __init__(self, agg: HashAggregateExec, sort: Optional[SortExec],
                 fallback: DeviceToHostExec,
                 topn: Optional["TakeOrderedAndProjectExec"] = None,
                 skip_exchange=None, project=None):
        super().__init__(agg.children[0])
        self._agg = agg
        self._sort = sort
        self._topn = topn
        self._fallback = fallback
        #: device rename/compute Project between the agg and the sort (the
        #: SQL front-end's `__agg_N AS name` layer), composed into the
        #: traced tail
        self._project = project
        #: the orderBy's range exchange between the sort and the final agg,
        #: matched through at plan time; sound to skip only when every
        #: live row lands in ONE reduce partition (decided at pid 0)
        self._skip_ex = skip_exchange
        self._decision: Optional[str] = None

    @property
    def output(self):
        return self._fallback.output

    def _tail_key(self, spec: Optional[int], capacity: int):
        from ...columnar.convert import _f64_as_pair, _pack_f64_enabled
        from .kernel_cache import exprs_key
        sort_key = (exprs_key(self._sort._bound)
                    if self._sort is not None else None)
        topn_key = None
        if self._topn is not None:
            t = self._topn
            topn_key = (int(t.n),
                        exprs_key(t.project_exprs)
                        if t.project_exprs is not None else None,
                        tuple(a.name for a in t.output))
        agg_key = (self._agg._fused_complete_key(spec) if spec is not None
                   else ("mergefin",) + self._agg._finalize_key)
        proj_key = (self._project._fuse_key()
                    if self._project is not None else None)
        return ("tailcollect", spec, capacity, agg_key, proj_key, sort_key,
                topn_key, _f64_as_pair(), _pack_f64_enabled())

    def _build(self, spec: Optional[int], batch: ColumnarBatch, key):
        """Compose agg body + sort + pack into one jitted fn for this
        (speculated size | final-merge, input signature)."""
        import jax

        from ...columnar.convert import pack_leaves_traced
        from .kernel_cache import cached_jit
        agg = self._agg
        if spec is not None:
            agg_body = agg._fused_complete_body(spec)
        else:
            def agg_body(b):
                fin = agg._finalize(agg._merge_compute(b))
                return fin, fin.num_rows
        proj_compute = (self._project._compute
                        if self._project is not None else None)
        sort_compute = self._sort._compute if self._sort is not None else None
        topn_step = (self._topn_step(spec if spec is not None
                                     else batch.capacity)
                     if self._topn is not None else None)

        def tail_body(b):
            fin, ng = agg_body(b)
            if proj_compute is not None:
                fin = proj_compute(fin)
            if sort_compute is not None:
                fin = sort_compute(fin)
            if topn_step is not None:
                fin = topn_step(fin)
            return fin, ng

        # learn the result-tree structure without executing
        fin_sd, ng_sd = jax.eval_shape(tail_body, batch)
        from ...shims import tree_flatten
        leaves_sd, treedef = tree_flatten(fin_sd)
        sig = tuple((tuple(sd.shape), str(sd.dtype)) for sd in leaves_sd)
        sig = sig + ((tuple(ng_sd.shape), str(ng_sd.dtype)),)

        def full(b):
            fin, ng = tail_body(b)
            leaves = tree_flatten(fin)[0] + [ng]
            return pack_leaves_traced(leaves, sig)

        fn = cached_jit(key, full)
        return fn, sig, treedef

    def _topn_step(self, spec: int):
        """Traced TopN tail (TakeOrderedAndProjectExec composed into the
        program): static head-slice of the sorted batch to the limit's
        capacity bucket, then the optional projection."""
        import jax.numpy as jnp

        from ...columnar.column import DeviceColumn, bucket_capacity
        from ..expressions.core import EvalContext, bind_references
        t = self._topn
        n = int(t.n)
        cap2 = min(bucket_capacity(max(n, 1)), spec)
        bound = None
        if t.project_exprs is not None:
            bound = [bind_references(e, t.children[0].output)
                     for e in t.project_exprs]
        out_names = tuple(a.name for a in t.output)

        def step(fin):
            cols = tuple(
                DeviceColumn(c.dtype, c.data[:cap2], c.validity[:cap2])
                for c in fin.columns)
            head = ColumnarBatch(fin.names, cols,
                                 jnp.minimum(fin.num_rows, n))
            if bound is None:
                return head
            ctx = EvalContext(head, xp=jnp)
            pcols = tuple(e.eval(ctx) for e in bound)
            return ColumnarBatch(out_names, pcols, head.num_rows)

        return step

    def execute(self, pid, tctx):
        from . import speculation as SPEC
        agg = self._agg
        is_final = agg.mode == "final"
        if agg._special or (not is_final and not SPEC.deferral_enabled()):
            STATS["fallbacks"] += 1
            yield from self._fallback.execute(pid, tctx)
            return
        if self._skip_ex is not None:
            yield from self._execute_skip(pid, tctx)
            return
        first, second, src, spec, fusable = self._peek_child(pid, tctx)
        if not fusable:
            from itertools import chain
            head = [b for b in (first, second) if b is not None]
            STATS["fallbacks"] += 1
            yield from self._run_fallback_on(chain(head, src), pid, tctx)
            return
        yield from self._fused_single(first, spec, pid, tctx)

    def _peek_child(self, pid, tctx):
        """Peek ONE batch (a many-batch child keeps streaming into the
        fallback subtree's spillables, never pinned in a list) and gate:
        fusable = exactly one live batch AND (final mode, whose group
        count is exact, OR a recorded speculation that fits the batch)."""
        agg = self._agg
        is_final = agg.mode == "final"
        src = self.children[0].execute(pid, tctx)
        first = next(src, None)
        second = next(src, None) if first is not None else None
        spec = None if is_final else lookup_speculation(agg._spec_key)
        single = (first is not None and second is None
                  and first.num_rows_bound > 0)
        fusable = single and (is_final
                              or (spec is not None
                                  and spec <= first.capacity))
        return first, second, src, spec, fusable

    def _execute_skip(self, pid, tctx):
        """Sort-above-exchange shape.  The skipped range exchange only
        redistributes rows for parallel sorting; when the final agg's
        output all sits in one reduce partition (the AQE-coalesce common
        case) a whole-batch sort gives the same global order, so the fused
        single-program tail applies.  Otherwise run the original tree —
        its exchanges are already materialized, so nothing recomputes."""
        if pid > 0:
            if self._decision is None:
                # pid 0 normally decides first (execute_all drives
                # partitions serially); under an out-of-order or parallel
                # driver, don't treat "no decision yet" as fused (that
                # silently dropped this partition's output — advisor r3).
                # The fallback tree is correct for BOTH outcomes: when
                # the fused path applies, every pid>0 partition is empty,
                # so the fallback yields nothing extra.
                STATS["fallbacks"] += 1
                yield from self._fallback.execute(pid, tctx)
                return
            if self._decision == "fallback":
                yield from self._fallback.execute(pid, tctx)
            return
        child = self.children[0]
        first, second, src, spec, fusable = self._peek_child(0, tctx)
        mat = getattr(child, "_materialized", None)
        if mat is None:
            others_live = True  # unknown layout: be conservative
        else:
            others_live = any(
                b.num_rows_bound > 0
                for t in range(1, child.num_partitions())
                for b in (mat[t] or []))
        if not fusable or others_live:
            self._decision = "fallback"
            STATS["fallbacks"] += 1
            yield from self._fallback.execute(0, tctx)
            return
        self._decision = "fused"
        yield from self._fused_single(first, spec, 0, tctx)

    def _fused_single(self, batch, spec, pid, tctx):
        from ...memory.oom_guard import guard_device_oom
        from ...memory.retry import SplitAndRetryOOM
        from ...columnar.convert import unpack_buffers
        from . import speculation as SPEC
        agg = self._agg
        is_final = agg.mode == "final"
        # the input batch's pytree structure joins the key: encoded columns
        # make the traced OUTPUT structure (and so the unpack signature)
        # depend on the input representation, not just the schema/capacity
        from ...shims import tree_flatten
        in_leaves, in_tdef = tree_flatten(batch)
        in_sig = (in_tdef, tuple(
            (getattr(l, "shape", ()), str(getattr(l, "dtype", "")))
            for l in in_leaves))
        pkey = self._tail_key(spec, batch.capacity) + (in_sig,)
        prog = _TAIL_PROGRAMS.get(pkey)
        if prog is None:
            if len(_TAIL_PROGRAMS) > 512:
                _TAIL_PROGRAMS.clear()
            prog = _TAIL_PROGRAMS[pkey] = self._build(spec, batch, pkey)
        fn, sig, treedef = prog
        run = guard_device_oom(fn)
        try:
            bufs = run(batch)
        except SplitAndRetryOOM:
            STATS["fallbacks"] += 1
            yield from self._run_fallback_on([batch], pid, tctx)
            return
        from ...observability import tracer as _trace
        with _trace.span("d2h", "fused_collect.fetch") as sp:
            for b in bufs:  # overlap transfers: one latency, not N
                b.copy_to_host_async()
            host = [np.asarray(b) for b in bufs]
            sp.set_metadata(bytes=sum(b.nbytes for b in host))
        leaves = unpack_buffers(host, sig)
        ng_host = int(leaves[-1])
        if not is_final:
            # record/validate the speculation through the standard registry
            # so the session's post-run validation and re-run loop apply
            minimum = agg._table_floor()
            SPEC.register(spec, None,
                          lambda ng, sk=agg._spec_key, m=minimum:
                          record_speculation(sk, ng, m)).resolve(ng_host)
            if ng_host > spec:
                return  # wrong result discarded; session re-runs
        STATS["fused_collects"] += 1
        tctx.inc_metric("fusedCollects")
        if not is_final:
            # the complete aggregate's one input batch, reduced inside the
            # tail program into a ``spec``-row table
            tctx.inc_metric(reduce_form_metric(agg.xp, spec))
        from ...shims import tree_unflatten
        out = tree_unflatten(treedef, leaves[:-1])
        tctx.inc_metric("d2h_bytes", batch_nbytes(out))
        rows_out = (min(ng_host, int(self._topn.n))
                    if self._topn is not None else ng_host)
        yield out.with_known_rows(rows_out)

    def _run_fallback_on(self, batches, pid, tctx):
        """Run the wrapped subtree, feeding it the already-started child
        stream (the child must not execute twice)."""
        import copy
        replay = _ReplaySource(self.children[0], batches)
        agg2 = copy.copy(self._agg)
        agg2.children = (replay,)
        node: PhysicalPlan = agg2
        if self._project is not None:
            proj2 = copy.copy(self._project)
            proj2.children = (node,)
            node = proj2
        if self._topn is not None:
            topn2 = copy.copy(self._topn)
            topn2.children = (node,)
            topn2._sort_cache = None  # lazily re-derives from the replay
            node = topn2
        elif self._sort is not None:
            sort2 = copy.copy(self._sort)
            sort2.children = (node,)
            node = sort2
        d2h2 = copy.copy(self._fallback)
        d2h2.children = (node,)
        yield from d2h2.execute(pid, tctx)

    def node_name(self):
        return "TpuFusedCollect"

    def simple_string(self):
        inner = self._agg.simple_string()
        if self._topn is not None:
            inner = (f"TakeOrdered(n={self._topn.n}) <- "
                     f"{self._sort.simple_string()} <- {inner}")
        elif self._sort is not None:
            inner = f"{self._sort.simple_string()} <- {inner}"
        return f"{self.node_name()} [{inner}]"

    def tree_string(self, level: int = 0) -> str:
        pad = "  " * level + ("+- " if level else "")
        lines = [pad + self.simple_string()]
        for c in self.children:
            lines.append(c.tree_string(level + 1))
        return "\n".join(lines)


def fuse_collect_tail(phys: PhysicalPlan) -> PhysicalPlan:
    """Planner pass: replace ``DeviceToHost(Sort?(HashAggregate(complete |
    final)))`` or ``DeviceToHost(TakeOrderedAndProject(HashAggregate(...)))``
    (TPU backend throughout) with :class:`FusedCollectExec` — final mode is
    the multi-partition shape (partial aggs + exchange below)."""
    from .exchange import ShuffleExchangeExec
    from .sortlimit import TakeOrderedAndProjectExec
    if not isinstance(phys, DeviceToHostExec):
        return phys
    inner = phys.children[0]
    sort = None
    topn = None
    agg = inner
    if isinstance(inner, TakeOrderedAndProjectExec) and inner.backend != CPU:
        topn = inner
        sort = inner._sort
        agg = inner.children[0]
    elif isinstance(inner, SortExec) and inner.backend != CPU:
        sort = inner
        agg = inner.children[0]
    from .basic import ProjectExec
    from .fusion import FusedStageExec

    def _unwrap_stage(n):
        """A FusedStageExec wrapping an aggregate terminal IS that
        aggregate for tail-fusion purposes: the absorbed pre-steps ride
        inside the aggregate's own fused programs, so the collect tail
        composes them the same way (docs/whole_stage.md)."""
        if isinstance(n, FusedStageExec) \
                and isinstance(n.terminal, HashAggregateExec):
            return n.terminal
        return n

    def _agg_below(n):
        """n, or its child past one device rename/compute Project (the
        SQL front-end's `__agg_N AS name` layer), if a HashAggregateExec
        sits there; else None.  Returns (project|None, agg)."""
        n = _unwrap_stage(n)
        if isinstance(n, HashAggregateExec):
            return None, n
        if isinstance(n, ProjectExec) and n.backend != CPU:
            inner = _unwrap_stage(n.children[0])
            if isinstance(inner, HashAggregateExec):
                return n, inner
        return None, None

    skip_ex = None
    if (sort is not None and isinstance(agg, ShuffleExchangeExec)
            and agg.backend != CPU
            and _agg_below(agg.children[0])[1] is not None):
        # orderBy plants Sort(RangeExchange(...)); the exchange only
        # redistributes rows for parallel sorting, so the fused tail can
        # look through it (skipped at runtime only when every live row
        # sits in one reduce partition — _execute_skip)
        skip_ex = agg
        agg = agg.children[0]
    proj, agg = _agg_below(agg)
    if agg is None:
        return phys
    if (agg.backend == CPU or agg.mode not in ("complete", "final")
            or agg._special):
        return phys
    if topn is not None and (not _topn_fusable(topn) or agg.mode == "final"):
        # final-mode TopN must NOT fuse: TakeOrderedAndProjectExec merges
        # all child partitions itself (num_partitions()==1), while the
        # fused exec runs per exchange partition — each live partition
        # would emit its own top-n (limit violated, order broken)
        return phys
    return FusedCollectExec(agg, sort, phys, topn=topn,
                            skip_exchange=skip_ex, project=proj)


def _topn_fusable(t) -> bool:
    """Only simple 1-D columns head-slice cleanly (strings/arrays use
    flattened slot layouts whose first axis is not rows) — a static plan
    property, so ineligible plans are never wrapped at all."""
    from ... import types as T
    simple = (T.LONG, T.INT, T.SHORT, T.BYTE, T.DOUBLE, T.FLOAT,
              T.BOOLEAN, T.DATE, T.TIMESTAMP)
    attrs = list(t.children[0].output) + list(t.output)
    return all(a.dtype in simple for a in attrs)
