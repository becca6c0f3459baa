"""Whole-stage fusion — one compiled XLA program per pipeline stage.

The reference gets kernel fusion two ways: cuDF fuses within a kernel, and
tiered projection dedups subexpressions (``basicPhysicalOperators.scala:500``).
On TPU the equivalent (and bigger) lever is compiling a whole
scan→filter→project→…→terminal chain as ONE jitted program:

* fused filters don't compact — the predicate ANDs into a live-row mask that
  threads through the stage (one compaction at the stage end, or none at all
  when the terminal is a hash aggregate or a join probe, which consume the
  mask directly);
* XLA fuses the elementwise project math into its consumers;
* no intermediate batch materialization between member ops.

Stage shapes (docs/whole_stage.md):

* **map stage** — a chain of >= 2 Filter/Project ops compiled as one
  program with a single terminal compaction.  The only shape eligible for
  input-buffer DONATION (``wholeStage.donation.enabled``): when the
  retention registry (memory/retention.py) proves the input batch is
  sole-owner, the program is built with ``donate_argnums`` so the output
  reuses the input's HBM.  Terminal stages never donate — their inputs
  are registered with the spill tier for the OOM retry protocol.
* **aggregate terminal** — ``HashAggregateExec`` (partial/complete)
  absorbs the upstream chain into its own partial/group/reduce programs
  (``absorb_pre_steps``) and the whole stage appears as one
  ``FusedStageExec`` node wrapping the aggregate.
* **probe terminal** — a hash join absorbs the probe-side chain
  (``BaseJoinExec.absorb_probe_steps``); the fused filter mask feeds the
  probe search directly and the cached build-side artifact enters the
  program as a cross-call constant.  The join node itself is the stage
  node (wrapping both children would desynchronize the probe/build
  references the async planner pass relies on).
* **sort/window terminal** — ``SortExec`` absorbs the upstream chain
  into its first-touch program (``absorb_pre_steps``); ``WindowExec``
  additionally absorbs the planner's partition sort (``absorb_sort``) so
  single-chunk inputs evaluate chain + sort + window in ONE program.
  Gated by ``wholeStage.sortWindowTerminal.enabled``.

Map stages additionally run the **dispatch coalescer**
(``dispatch.coalesce.{enabled,maxBatches,maxRows}``): consecutive
same-signature small batches are stacked on a leading axis INSIDE one
jitted program and the stage computation is vmapped over them — N
batches, one real device launch (``deviceDispatches`` counts launches;
the ``stage`` trace span carries ``coalesced_n``).

Programs are built LAZILY on first execute under one stage-signature
kernel-cache key (member ``_fuse_key``s + encode params + input layout),
so AQE-replanned or CPU-fallback-discarded plans register nothing.

The planner pass (``fuse_stages``) runs after transition insertion and only
touches same-backend TPU chains; the CPU fallback path keeps per-op
execution, which also keeps it a more independent oracle.
"""

from __future__ import annotations

from typing import List, Optional

from ...columnar.batch import ColumnarBatch
from ...memory import retention as _ret
from ...observability import tracer as _trace
from .base import TPU, PhysicalPlan
from .basic import FilterExec, ProjectExec, compact_batch


def _col_coalesce_sig(c):
    """Structural stack-compatibility signature for one column, or None
    when the column can't coalesce (encoded columns carry per-dictionary
    aux data — content hashes — that break the common treedef)."""
    from ...columnar.column import DeviceColumn
    if type(c) is not DeviceColumn:
        return None
    kids = tuple(_col_coalesce_sig(ch) for ch in c.children)
    if any(k is None for k in kids):
        return None
    return (str(c.dtype),
            None if c.data is None else (tuple(c.data.shape),
                                         str(c.data.dtype)),
            None if c.validity is None else tuple(c.validity.shape),
            None if c.lengths is None else str(c.lengths.dtype),
            None if c.aux is None else (tuple(c.aux.shape),
                                        str(c.aux.dtype)),
            kids)


def coalesce_signature(batch: ColumnarBatch):
    """Batches with equal signatures stack leaf-for-leaf into one
    batch-of-batches launch (same names, capacity bucket, and per-column
    array structure — string widths included).  None = not coalescible."""
    sigs = tuple(_col_coalesce_sig(c) for c in batch.columns)
    if any(s is None for s in sigs):
        return None
    return (batch.names, batch.capacity, sigs)


class FusedStageExec(PhysicalPlan):
    """A whole pipeline stage: a chain of Filter/Project members plus an
    optional terminal (hash aggregate), compiled as one program."""

    def __init__(self, members: List[PhysicalPlan], child: PhysicalPlan,
                 terminal: Optional[PhysicalPlan] = None):
        super().__init__(child)
        self.backend = TPU
        self.members = list(members)  # producer -> consumer order
        #: stage terminal (HashAggregateExec partial/complete) — owns the
        #: fused programs via its absorbed pre-steps; execution delegates
        self.terminal = terminal
        #: donate(bool) -> compiled program; built lazily on first execute
        #: (plan-construction must register nothing in the kernel cache)
        self._fns: dict = {}

    @property
    def output(self):
        if self.terminal is not None:
            return self.terminal.output
        return self.members[-1].output

    def num_partitions(self):
        return self.children[0].num_partitions()

    def _stage_key(self, conf):
        """The ONE stage-signature kernel-cache key replacing the members'
        per-op keys: member fuse keys + encode params + input layout."""
        from ...columnar.encoded import encode_params
        layout = tuple((a.name, str(a.dtype))
                       for a in self.children[0].output)
        return (("stage",) + tuple(m._fuse_key() for m in self.members)
                + (encode_params(conf), layout))

    def _get_fn(self, donate: bool, conf):
        fn = self._fns.get(donate)
        if fn is None:
            key = self._stage_key(conf) + (("donate",) if donate else ())
            fn = self._jit(self._compute, key=key,
                           donate_argnums=(0,) if donate else None)
            self._fns[donate] = fn
        return fn

    def _get_coalesced_fn(self, n: int, conf):
        """One program for N stacked same-signature batches: the stack,
        the vmapped stage computation, AND the unstack all trace into a
        single jitted program — exactly one real device launch replaces
        N (the dispatch coalescer, docs/whole_stage.md).  Coalesced
        groups never donate (N inputs share one program invocation; the
        sole-owner proof is per-batch)."""
        key = ("coalesce", n)
        fn = self._fns.get(key)
        if fn is None:
            def impl(*batches):
                import jax
                xp = self.xp
                stacked = jax.tree_util.tree_map(
                    lambda *ls: xp.stack(ls), *batches)
                outs = jax.vmap(self._compute)(stacked)
                return tuple(
                    jax.tree_util.tree_map(lambda l, i=i: l[i], outs)
                    for i in range(n))
            fn = self._jit(impl,
                           key=self._stage_key(conf) + (("coalesce", n),))
            self._fns[key] = fn
        return fn

    def _compute(self, batch: ColumnarBatch) -> ColumnarBatch:
        xp = self.xp
        mask = batch.row_mask()
        for m in self.members:
            batch, mask = m._fuse_step(batch, mask, xp)
        return compact_batch(xp, batch, mask)

    def _donation_on(self, tctx) -> bool:
        from ...config import WHOLE_STAGE_DONATION
        return (self.terminal is None
                and bool(tctx.conf.get(WHOLE_STAGE_DONATION)))

    def _stage_label(self) -> str:
        inner = "+".join(m.node_name() for m in self.members)
        if self.terminal is not None:
            inner += "+" + self.terminal.node_name()
        return f"stage.{inner}"

    def execute(self, pid, tctx):
        if self.terminal is not None:
            yield from self._execute_terminal(pid, tctx)
            return
        donate_on = self._donation_on(tctx)
        label = self._stage_label()
        from ...config import (DISPATCH_COALESCE_ENABLED,
                               DISPATCH_COALESCE_MAX_BATCHES,
                               DISPATCH_COALESCE_MAX_ROWS)
        co_max = (int(tctx.conf.get(DISPATCH_COALESCE_MAX_BATCHES))
                  if bool(tctx.conf.get(DISPATCH_COALESCE_ENABLED)) else 1)
        co_rows = int(tctx.conf.get(DISPATCH_COALESCE_MAX_ROWS))

        def run_one(batch):
            tctx.inc_metric("fusedStageBatches")
            tctx.inc_metric("wholeStageDispatches")
            tctx.inc_metric("stageOpDispatches")
            donate = False
            if donate_on:
                donate, _why = _ret.may_donate(batch)
                if donate:
                    tctx.inc_metric("wholeStageDonatedBatches")
                    _ret.count_donated()
                else:
                    tctx.inc_metric("wholeStageDonationDeclined")
            fn = self._get_fn(donate, tctx.conf)
            with _trace.span("stage", label, partition=pid):
                out = fn(batch)
            return _ret.mark_transient(out)

        pending: list = []
        pending_sig = None

        def flush():
            nonlocal pending, pending_sig
            group, pending, pending_sig = pending, [], None
            if not group:
                return
            if len(group) == 1:
                yield run_one(group[0])
                return
            n = len(group)
            tctx.inc_metric("fusedStageBatches", n)
            tctx.inc_metric("wholeStageDispatches")
            tctx.inc_metric("stageOpDispatches")
            tctx.inc_metric("dispatchCoalescedBatches", n)
            tctx.inc_metric("dispatchCoalescedLaunches")
            fn = self._get_coalesced_fn(n, tctx.conf)
            with _trace.span("stage", label, partition=pid,
                             coalesced_n=n):
                outs = fn(*group)
            for out in outs:
                yield _ret.mark_transient(out)

        for batch in self.children[0].execute(pid, tctx):
            if co_max > 1 and batch.num_rows_bound <= co_rows:
                sig = coalesce_signature(batch)
                if sig is not None:
                    if pending and sig != pending_sig:
                        yield from flush()
                    pending.append(batch)
                    pending_sig = sig
                    if len(pending) >= co_max:
                        yield from flush()
                    continue
            yield from flush()
            yield run_one(batch)
        yield from flush()

    def _execute_terminal(self, pid, tctx):
        """Delegate to the terminal exec (its absorbed pre-steps ARE the
        fused stage program).  The terminal's child references are re-synced
        from this node's children first, so planner rewrites applied above
        this node (async prefetch wrappers, AQE substitutions) stay
        visible to the delegated execution.  Under the parallel partition
        scheduler every task writes the SAME post-planning tuple, so the
        concurrent re-sync is idempotent."""
        t = self.terminal
        t.children = self.children
        label = self._stage_label()
        it = t.execute(pid, tctx)
        while True:
            try:
                with _trace.span("stage", label, partition=pid):
                    batch = next(it)
            except StopIteration:
                return
            tctx.inc_metric("fusedStageBatches")
            yield batch

    def simple_string(self):
        inner = " -> ".join(m.node_name() for m in self.members)
        if self.terminal is not None:
            inner += (" -> " if inner else "") \
                + self.terminal.simple_string()
        return f"{self.node_name()} [{inner}]"


def _fusible(plan: PhysicalPlan) -> bool:
    return (isinstance(plan, (FilterExec, ProjectExec))
            and plan.backend == TPU
            and not plan._placement_reasons)


def _collect_chain(plan: PhysicalPlan):
    """Walk down through fusible ops; returns (members bottom-up, child)."""
    chain = []
    node = plan
    while _fusible(node):
        chain.append(node)
        node = node.children[0]
    chain.reverse()  # producer first
    return chain, node


def fuse_stages(plan: PhysicalPlan, conf=None) -> PhysicalPlan:
    """Bottom-up rewrite: absorb Filter/Project chains into their terminal
    hash aggregate's partial kernel or a hash join's probe phase (stage
    terminals, gated by ``spark.rapids.tpu.sql.wholeStage.enabled``), and
    collapse remaining chains of >= 2 map ops into a FusedStageExec."""
    from ...config import (WHOLE_STAGE_ENABLED, WHOLE_STAGE_SORT_WINDOW,
                           RapidsConf)
    from .aggregate import HashAggregateExec
    from .join import BroadcastHashJoinExec, ShuffledHashJoinExec
    from .sortlimit import SortExec
    from .window import WindowExec

    conf = conf or RapidsConf.get_global()
    whole = bool(conf.get(WHOLE_STAGE_ENABLED))
    sortwin = whole and bool(conf.get(WHOLE_STAGE_SORT_WINDOW))

    if (whole and isinstance(plan, HashAggregateExec)
            and plan.backend == TPU
            and plan.mode in ("partial", "complete")):
        chain, below = _collect_chain(plan.children[0])
        if chain:
            plan.absorb_pre_steps(chain, below)
            fused = FusedStageExec(chain, below, terminal=plan)
            fused.children = (fuse_stages(below, conf),)
            return fused

    if (sortwin and isinstance(plan, WindowExec) and plan.backend == TPU
            and plan._sorter is None
            and isinstance(plan.children[0], SortExec)
            and plan.children[0].backend == TPU
            and not plan.children[0]._pre_steps
            and plan.can_absorb_sort(plan.children[0])):
        # window terminal: absorb the planner's partition sort (and any
        # chain below it) — single-chunk inputs run chain + sort +
        # window as ONE program
        sort = plan.children[0]
        chain, below = _collect_chain(sort.children[0])
        if chain:
            sort.absorb_pre_steps(chain, below)
        plan.absorb_sort(sort)
        if chain:
            fused = FusedStageExec(chain, below, terminal=plan)
            fused.children = (fuse_stages(below, conf),)
            return fused
        plan.children = tuple(fuse_stages(c, conf) for c in plan.children)
        return plan

    if (sortwin and isinstance(plan, SortExec) and plan.backend == TPU
            and not plan._pre_steps):
        chain, below = _collect_chain(plan.children[0])
        if chain:
            plan.absorb_pre_steps(chain, below)
            fused = FusedStageExec(chain, below, terminal=plan)
            fused.children = (fuse_stages(below, conf),)
            return fused

    if (whole and plan.backend == TPU
            and isinstance(plan, (ShuffledHashJoinExec,
                                  BroadcastHashJoinExec))):
        pi = 1 if plan._flipped else 0
        chain, below = _collect_chain(plan.children[pi])
        if chain:
            plan.absorb_probe_steps(chain, below)

    if _fusible(plan):
        chain, below = _collect_chain(plan)
        if len(chain) >= 2:
            fused = FusedStageExec(chain, below)
            fused.children = (fuse_stages(below, conf),)
            return fused

    plan.children = tuple(fuse_stages(c, conf) for c in plan.children)
    return plan


def annotate_stage_coverage(plan: PhysicalPlan) -> PhysicalPlan:
    """Record plan-time fusion coverage on the root's metrics:
    ``wholeStageOps`` counts ops executing inside a fused stage program
    (map members + terminals), ``unfusedOps`` counts stage-eligible ops
    (Filter/Project/partial-or-complete HashAggregate/hash-join probes)
    left on per-op dispatch.  Folded into last_query_metrics via the
    standard collect_metrics walk."""
    from .aggregate import HashAggregateExec
    from .collect_fusion import FusedCollectExec
    from .join import BaseJoinExec, NestedLoopJoinExec
    from .window import WindowExec

    fused = unfused = 0
    stack = [plan]
    while stack:
        n = stack.pop()
        if isinstance(n, FusedStageExec):
            fused += len(n.members) + (1 if n.terminal is not None else 0)
            if getattr(n.terminal, "_sorter", None) is not None:
                fused += 1  # the window terminal's absorbed partition sort
        elif isinstance(n, WindowExec) \
                and getattr(n, "_sorter", None) is not None:
            fused += 2  # sort-only absorption: window + its partition sort
        elif isinstance(n, FusedCollectExec):
            fused += 1 + len(getattr(n._agg, "_pre_steps", ()))
        elif isinstance(n, (FilterExec, ProjectExec)):
            unfused += 1
        elif isinstance(n, HashAggregateExec) \
                and n.mode in ("partial", "complete"):
            if n._pre_steps:
                fused += 1 + len(n._pre_steps)
            else:
                unfused += 1
        elif isinstance(n, BaseJoinExec) \
                and not isinstance(n, NestedLoopJoinExec):
            steps = getattr(n, "_probe_steps", ())
            if steps:
                fused += 1 + len(steps)
            else:
                unfused += 1
        stack.extend(n.children)
    plan.metrics["wholeStageOps"] = float(fused)
    plan.metrics["unfusedOps"] = float(unfused)
    return plan
