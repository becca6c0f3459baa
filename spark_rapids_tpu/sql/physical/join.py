"""Join execs — the analog of the reference's join family (SURVEY §2.3):
``GpuShuffledHashJoinExec`` (440 LoC), ``GpuBroadcastHashJoinExecBase``,
``GpuBroadcastNestedLoopJoinExecBase``, ``GpuCartesianProductExec``,
``ExistenceJoin``, with gather-map construction in ``GpuHashJoin.scala:298``
and chunked output via ``JoinGatherer.scala``.

TPU shape discipline: phase 1 (``ops/join.join_build``) is one compiled
program per (probe-cap, build-cap); the host reads three scalar totals to
pick an output capacity bucket; phase 2 gathers + evaluates any residual
(non-equi) condition + assembles the join-type-specific output, one compiled
program per (caps, out-cap).  Sort-merge joins are replaced by shuffled hash
joins exactly like the reference (``GpuSortMergeJoinMeta.scala``).
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ... import types as T
from ...columnar.batch import ColumnarBatch
from ...observability import tracer as _tracer
from ...columnar.column import bucket_capacity
from ...ops.join import (JoinBuildSide, JoinInfo, compact_indices,
                         cross_pairs, fastpath_supported, gather_pairs,
                         join_build, matched_per_row, PairMaps,
                         prepare_build_side, probe_join_info)
from ..expressions.core import (AttributeReference, EvalContext, Expression,
                                bind_references)
from ..join_order import share_note
from .base import TPU, PhysicalPlan, TaskContext
from .exchange import BroadcastExchangeExec

_PAIR_JOINS = ("inner", "left", "full", "cross")
_FILTER_JOINS = ("left_semi", "left_anti", "existence")

#: observability for tests: build_sorts counts ACTUAL build-side sort
#: program executions (a broadcast join with B probe batches must show 1,
#: not B); host_readbacks counts blocking device->host scalar fetches on
#: the sizing path; spec_hits/spec_misses track speculative output sizing
STATS = {"chunked_joins": 0, "build_sorts": 0, "fastpath_probes": 0,
         "fallback_probes": 0, "spec_hits": 0, "spec_misses": 0,
         "host_readbacks": 0, "fused_probes": 0}

#: realized join selectivity (inner pairs per probe row) per program
#: identity — the speculative output-sizing seed, learned from the first
#: batch so later batches dispatch their gather without waiting for the
#: count readback (aggregate.py _OUT_SPECULATION analog; cleared with the
#: kernel cache)
_JOIN_SELECTIVITY: Dict[tuple, float] = {}
#: guards the selectivity dict against concurrent sessions — a plain-dict
#: read-modify-write racing a clear could resurrect state for a dead
#: kernel-cache generation (docs/serving.md clearing contract)
_SEL_LOCK = threading.Lock()


def _join_estimate(children) -> Optional[int]:
    """A join's size for the side choice of the join above it: its larger
    child's (a key join keeps about its probe side's rows; the filters
    that shrink it are not estimated), unknown where neither is known."""
    known = [b for b in (c.estimate_bytes() for c in children)
             if b is not None]
    return max(known) if known else None


def record_selectivity(spec_key, sel: float,
                       generation: Optional[int] = None) -> None:
    """Record observed selectivity, max-joined: a low-match tail batch
    must not shrink the prediction a dense batch needs (which would make
    every later dense batch mis-speculate and gather twice, forever).

    ``generation`` is the kernel-cache generation the caller captured
    when it LOOKED UP the prediction; if the cache was cleared in
    between, the write is dropped — a concurrent clearKernelCache must
    never be repopulated with learning from the dead generation."""
    from .kernel_cache import cache_generation
    with _SEL_LOCK:
        if generation is not None and generation != cache_generation():
            STATS["stale_selectivity_drops"] = \
                STATS.get("stale_selectivity_drops", 0) + 1
            return
        if len(_JOIN_SELECTIVITY) > 1024:
            # keys embed literals (kernel-cache rule)
            _JOIN_SELECTIVITY.clear()
        prev = _JOIN_SELECTIVITY.get(spec_key, 0.0)
        _JOIN_SELECTIVITY[spec_key] = max(prev, sel)


def lookup_selectivity(spec_key) -> Optional[float]:
    with _SEL_LOCK:
        return _JOIN_SELECTIVITY.get(spec_key)


def clear_selectivity() -> None:
    """Called by kernel_cache.clear_cache AFTER the generation bump —
    the bump-then-clear order is what makes racing recorders drop."""
    with _SEL_LOCK:
        _JOIN_SELECTIVITY.clear()


#: ``_strategy_counted_in`` before any collect (a query context may be None)
_NO_QUERY = object()

#: two shuffled sides within this factor of each other are of like size, and
#: their join keeps the text's order.  Flipped at 4.5x (TPC-H Q3's joined
#: customer and orders under ``lineitem``, 16 columns, every side shuffled)
#: a collect went from 0.849 to 1.314 s on four chips and from 2.25 to 1.91 s
#: on one (PERF.md section 6, PR 33); a star join's dimension is 14x to
#: 1000x smaller than its fact table
_LIKE_SIZE = 8


class BaseJoinExec(PhysicalPlan):
    """Shared machinery: side normalization (right joins flip to left),
    output schema, pair gathering, residual-condition assembly."""

    #: the planner's estimate of the fact rows a star's dimension keeps, for
    #: explain alone (``sql/join_order.py``)
    kept_share: Optional[float] = None

    def __init__(self, how: str, left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 condition: Optional[Expression],
                 left: PhysicalPlan, right: PhysicalPlan, backend=TPU,
                 build_left: bool = False):
        super().__init__(left, right)
        self.backend = backend
        self.how = how
        self.condition = condition
        #: one-shot per-join setup (bloom install, AQE choice) must run
        #: exactly once even when the parallel partition scheduler drives
        #: several probe partitions into execute concurrently
        self._setup_lock = threading.Lock()
        self._strategy_counted_in = _NO_QUERY
        if build_left and how != "inner":
            raise ValueError(f"build_left is for inner joins, not {how!r}")
        self._flipped = how == "right" or build_left
        if self._flipped:
            # right outer == left outer with sides swapped + column reorder;
            # an inner join that builds on its left child (the smaller one,
            # plan_join) is the same swap with nothing to null-extend
            self._probe, self._build = right, left
            self._probe_keys, self._build_keys = list(right_keys), list(left_keys)
            self._norm_how = "inner" if build_left else "left"
        else:
            self._probe, self._build = left, right
            self._probe_keys, self._build_keys = list(left_keys), list(right_keys)
            self._norm_how = how

        self._out_left = list(left.output)
        self._out_right = list(right.output)
        #: pair-layout schemas, frozen at construction: absorb_probe_steps
        #: rewires self._probe BELOW the fused chain, but the pair batch is
        #: built from the POST-chain probe the join was bound against
        self._probe_attrs = list(self._probe.output)
        self._build_attrs = list(self._build.output)
        self._bound_pkeys = [bind_references(e, self._probe.output)
                             for e in self._probe_keys]
        self._bound_bkeys = [bind_references(e, self._build.output)
                             for e in self._build_keys]
        # pair-batch layout: [probe cols][build cols]
        pair_attrs = list(self._probe.output) + list(self._build.output)
        self._bound_cond = (bind_references(condition, pair_attrs)
                            if condition is not None else None)
        from .kernel_cache import expr_key, exprs_key
        self._sig = (self._norm_how, self._flipped,
                     exprs_key(self._bound_pkeys),
                     exprs_key(self._bound_bkeys),
                     expr_key(self._bound_cond)
                     if self._bound_cond is not None else None,
                     tuple(a.name for a in self.output))
        #: whole-stage probe terminal (docs/whole_stage.md): a fused
        #: upstream Filter/Project chain applied INSIDE every probe-side
        #: program — the fused filter mask feeds the probe search
        #: directly, nothing compacts or materializes between the scan
        #: and the search
        self._probe_steps: tuple = ()
        self._gather_cache: Dict[int, object] = {}
        # programs built lazily on first use (whole-stage laziness
        # contract — AQE shape-only instances register nothing)
        self._build_fn = None
        self._prep_fn = None
        self._probe_fn = None
        # join fast path: build-side sort cached per build batch + probe-only
        # tuple search; array/map keys keep the union-rank fallback
        self._fast_ok = fastpath_supported(
            [e.data_type for e in self._bound_pkeys + self._bound_bkeys])
        self._bs_key = ("bs", exprs_key(self._bound_bkeys))

    def estimate_bytes(self):
        return _join_estimate(self.children)

    def _count_strategy(self, tctx: TaskContext, name: str) -> None:
        """``joinStrategy<Name>`` of last_query_metrics: once a join and
        collect, whichever partition comes first."""
        with self._setup_lock:
            if self._strategy_counted_in is not tctx.query_ctx:
                self._strategy_counted_in = tctx.query_ctx
                tctx.inc_metric("joinStrategy" + name)

    # --- whole-stage probe fusion ----------------------------------------
    def absorb_probe_steps(self, steps, new_probe: PhysicalPlan) -> None:
        """Fuse an upstream probe-side Filter/Project chain into this
        join's probe phase (fusion.py).  The chain reproduced the probe
        schema this join was bound against, so bound keys/conditions and
        the output layout stay valid; fused filters contribute a live-row
        mask consumed by the probe search instead of compacting.  The
        stage signature joins ``_sig``, so probe/gather programs never
        alias their unfused counterparts, and the compiled-fn caches are
        reset (they are lazy, so nothing was registered yet at plan
        time)."""
        self._probe_steps = tuple(steps)
        self._probe = new_probe
        kids = list(self.children)
        kids[1 if self._flipped else 0] = new_probe
        self.children = tuple(kids)
        self._sig = self._sig + (
            ("stage",) + tuple(s._fuse_key() for s in steps),)
        self._build_fn = None
        self._probe_fn = None
        self._gather_cache = {}

    def _apply_probe_steps(self, probe: ColumnarBatch, xp):
        """(post-chain batch, live mask) — runs INSIDE jitted programs;
        elementwise step math re-evaluated per program fuses into its
        consumer, costing zero extra dispatches."""
        mask = probe.row_mask()
        for s in self._probe_steps:
            probe, mask = s._fuse_step(probe, mask, xp)
        return probe, mask

    def _get_build_fn(self):
        if self._build_fn is None:
            self._build_fn = self._jit(self._build_info,
                                       key=("build", self._sig))
        return self._build_fn

    def _get_prep_fn(self):
        if self._prep_fn is None:
            self._prep_fn = self._jit(self._prepare_build,
                                      key=("prep", self._bs_key))
        return self._prep_fn

    def _get_probe_fn(self):
        if self._probe_fn is None:
            self._probe_fn = self._jit(self._probe_info,
                                       key=("probesearch", self._sig))
        return self._probe_fn

    # --- schema -----------------------------------------------------------
    @property
    def output(self) -> List[AttributeReference]:
        how = self.how
        lo = list(self._out_left)
        ro = list(self._out_right)
        if how in ("left_semi", "left_anti"):
            return lo
        if how == "existence":
            return lo + [AttributeReference("exists", T.BOOLEAN, False)]
        def _nullable(attrs):
            return [AttributeReference(a.name, a.dtype, True, a.expr_id)
                    for a in attrs]
        if how == "left":
            ro = _nullable(ro)
        elif how == "right":
            lo = _nullable(lo)
        elif how == "full":
            lo, ro = _nullable(lo), _nullable(ro)
        return lo + ro

    # --- phase 1 ----------------------------------------------------------
    def _build_info(self, probe: ColumnarBatch, build: ColumnarBatch
                    ) -> JoinInfo:
        xp = self.xp
        probe, lmask = self._apply_probe_steps(probe, xp)
        pctx = EvalContext(probe, xp=xp)
        bctx = EvalContext(build, xp=xp)
        pkeys = [e.eval(pctx) for e in self._bound_pkeys]
        bkeys = [e.eval(bctx) for e in self._bound_bkeys]
        return join_build(xp, pkeys, bkeys, lmask, build.row_mask())

    def _prepare_build(self, build: ColumnarBatch) -> JoinBuildSide:
        """Fast-path phase 0: sort the build side's key tuples (one jitted
        program per build capacity, result cached on the build batch)."""
        xp = self.xp
        bctx = EvalContext(build, xp=xp)
        bkeys = [e.eval(bctx) for e in self._bound_bkeys]
        return prepare_build_side(xp, bkeys, build.row_mask())

    def _probe_info(self, probe: ColumnarBatch, build: ColumnarBatch,
                    bs: JoinBuildSide) -> JoinInfo:
        """Fast-path phase 1: probe-only — fused probe steps + key
        transform + one multi-key binary search against the pre-sorted
        build side (plus run-end lookups).  With absorbed probe steps the
        fused filter mask IS the probe live mask: filtered-out rows are
        dead rows to the search, exactly like compaction padding.
        Build-unmatched flags are only materialized for full joins, the
        one type that emits them (_norm_how is in the jit sig, so the
        static flag can't alias programs)."""
        xp = self.xp
        probe, lmask = self._apply_probe_steps(probe, xp)
        pctx = EvalContext(probe, xp=xp)
        pkeys = [e.eval(pctx) for e in self._bound_pkeys]
        return probe_join_info(
            xp, pkeys, lmask, build.row_mask(), bs,
            need_b_matched=self._norm_how == "full",
            need_l_unmatched=self._norm_how in ("left", "full"))

    #: tracer category per join stage: the sizing readback is a blocking
    #: device sync; every other stage is host-side dispatch work
    _STAGE_CAT = {"readback": "sync"}

    @contextmanager
    def _stage(self, tctx: Optional[TaskContext], name: str):
        """Per-stage join profiling: a tracer span around the host-side
        stage (dispatch or blocking fetch; cat ``sync`` for the sizing
        readback) plus a wall-time metric in last_query_metrics
        (joinStage<Name>Ms)."""
        t0 = time.perf_counter()
        try:
            with _tracer.span(self._STAGE_CAT.get(name, "op"),
                              f"join.{name}"):
                yield
        finally:
            if tctx is not None:
                tctx.inc_metric(f"joinStage{name[0].upper()}{name[1:]}Ms",
                                (time.perf_counter() - t0) * 1e3)

    def _fast_path_on(self, tctx: Optional[TaskContext]) -> bool:
        if not self._fast_ok:
            return False
        from ...config import JOIN_BUILD_CACHE_ENABLED
        conf = tctx.conf if tctx is not None else None
        if conf is None:
            from ...config import RapidsConf
            conf = RapidsConf.get_global()
        return bool(conf.get(JOIN_BUILD_CACHE_ENABLED))

    def _fused_probe_on(self, tctx: Optional[TaskContext]) -> bool:
        """Single-program probe pipeline kill switch: probe search +
        run-end expansion + pair generation + the all-columns gather ride
        ONE compiled program that also returns the sizing scalars."""
        from ...config import JOIN_FUSED_PROBE
        conf = tctx.conf if tctx is not None else None
        if conf is None:
            from ...config import RapidsConf
            conf = RapidsConf.get_global()
        return bool(conf.get(JOIN_FUSED_PROBE))

    def _lower_encoded_keys(self, probe: ColumnarBatch, build: ColumnarBatch,
                            tctx: Optional[TaskContext]
                            ) -> Tuple[ColumnarBatch, ColumnarBatch]:
        """Encoded join lowering (docs/encoded_columns.md): for every key
        pair that is a bare column reference to a dict-encoded string
        column on BOTH sides, remap the probe side's codes into the build
        dictionary's (sorted) code space and mark both columns with
        ``join_codes`` — the jitted join programs then sort/search ONE
        int32 key per string key instead of width/8 byte-chunk keys.

        Invariant kept pairwise: a key position either carries join_codes
        on BOTH sides or on NEITHER (a one-sided marking would make
        ``join_search_keys`` emit mismatched key structures).  The lowered
        build batch shares the original's build-side artifact cache; its
        lowering signature joins the cache key so code-space and raw sorts
        never alias."""
        from ...columnar import encoded as E
        from ..expressions.core import BoundReference
        conf = tctx.conf if tctx is not None else None
        if not (E.op_enabled("join", conf) and self._fast_ok):
            return probe, build
        from .basic import ProjectExec
        if any(isinstance(s, ProjectExec) for s in self._probe_steps):
            # fused probe projections change the probe schema, so the
            # bound key ordinals no longer address the PRE-chain batch
            # this host-side lowering inspects; decline (bit-identical by
            # the decline-to-materialize property, docs/encoded_columns.md)
            E._bump("join_code_declines")
            return probe, build
        lowered: List[Tuple[int, int, object, object]] = []
        for pk, bk in zip(self._bound_pkeys, self._bound_bkeys):
            if not (isinstance(pk, BoundReference)
                    and isinstance(bk, BoundReference)):
                continue
            pcol = probe.columns[pk.ordinal]
            bcol = build.columns[bk.ordinal]
            if not (isinstance(pcol, E.DictEncodedColumn)
                    and isinstance(bcol, E.DictEncodedColumn)) \
                    or pcol.dtype != bcol.dtype:
                continue
            pair = E.lower_join_codes(pcol, bcol)
            if pair is None:
                E._bump("join_code_declines")
                continue
            lowered.append((pk.ordinal, bk.ordinal) + pair)
        if not lowered:
            return probe, build
        pcols = list(probe.columns)
        bcols = list(build.columns)
        for po, bo, p2, b2 in lowered:
            pcols[po] = p2
            bcols[bo] = b2
        new_probe = ColumnarBatch(probe.names, tuple(pcols), probe.num_rows)
        new_build = ColumnarBatch(build.names, tuple(bcols), build.num_rows)
        for src, dst in ((probe, new_probe), (build, new_build)):
            cached = getattr(src, "_nrows_host", None)
            if cached is not None:
                dst._nrows_host = cached
        # share the artifact cache so the build sort still happens once per
        # (build batch, lowering signature) across all probe batches
        cache = getattr(build, "_join_build_sides", None)
        if cache is None:
            cache = build._join_build_sides = {}
        new_build._join_build_sides = cache
        new_build._enc_lower_sig = tuple(
            (bo, bcols[bo].dictionary.content_hash)
            for _, bo, _, _ in lowered)
        E._bump("join_code_lowerings", len(lowered))
        if tctx is not None:
            tctx.inc_metric("joinCodeLowerings", len(lowered))
        return new_probe, new_build

    def _get_build_side(self, build: ColumnarBatch,
                        tctx: Optional[TaskContext]) -> JoinBuildSide:
        """The build batch's cached :class:`JoinBuildSide` for this join's
        build keys, computing (and caching) it on first use — a broadcast
        build side shared by B probe batches/partitions sorts exactly
        once."""
        cache = getattr(build, "_join_build_sides", None)
        if cache is None:
            cache = {}
            build._join_build_sides = cache
        key = (self.backend,) + self._bs_key \
            + (getattr(build, "_enc_lower_sig", None),)
        bs = cache.get(key)
        if bs is None:
            with self._stage(tctx, "buildSort"):
                bs = self._get_prep_fn()(build)
            STATS["build_sorts"] += 1
            if tctx is not None:
                tctx.inc_metric("joinBuildSorts")
            cache[key] = bs
        return bs

    def _join_info(self, probe: ColumnarBatch, build: ColumnarBatch,
                   tctx: Optional[TaskContext]) -> JoinInfo:
        """Phase 1 dispatch: cached-build-side probe search when the key
        shapes support it, union-rank fallback otherwise.  Both produce
        the same :class:`JoinInfo` contract (parity-tested).  One device
        dispatch either way — the stage-scope dispatch counter's probe
        terminal (fused probe steps ride the same program)."""
        from .base import count_stage_dispatch
        count_stage_dispatch()
        if self._fast_path_on(tctx):
            bs = self._get_build_side(build, tctx)
            STATS["fastpath_probes"] += 1
            if tctx is not None:
                tctx.inc_metric("joinFastpathProbes")
            with self._stage(tctx, "probeSearch"):
                return self._get_probe_fn()(probe, build, bs)
        STATS["fallback_probes"] += 1
        if tctx is not None:
            tctx.inc_metric("joinFallbackProbes")
        with self._stage(tctx, "unionRankBuild"):
            return self._get_build_fn()(probe, build)

    def _fetch_totals(self, info: JoinInfo,
                      tctx: Optional[TaskContext]) -> Tuple[int, int, int]:
        """The ONE blocking host readback per probe batch: all three sizing
        scalars ride a single batched ``jax.device_get`` instead of three
        per-scalar ``int()`` syncs (each a full host<->device round trip)."""
        STATS["host_readbacks"] += 1
        if tctx is not None:
            tctx.inc_metric("joinHostReadbacks")
        with self._stage(tctx, "readback"):
            scalars = list(info.sizing_scalars()) + [info.n_null_keys]
            if self.backend == TPU:
                import jax
                scalars = jax.device_get(scalars)
            tot, unl, unb, nulls = scalars
        if tctx is not None:
            tctx.inc_metric("joinNullKeyRows", int(nulls))
        return int(tot), int(unl), int(unb)

    # --- phase 2 ----------------------------------------------------------
    def _gather_fn(self, out_cap: int):
        fn = self._gather_cache.get(out_cap)
        if fn is None:
            def impl(probe, build, info):
                return self._gather_impl(probe, build, info, out_cap)
            fn = self._jit(impl, key=("gather", self._sig, out_cap))
            self._gather_cache[out_cap] = fn
        return fn

    def _fused_probe_fn(self, out_cap: int):
        """The single-program probe pipeline (ISSUE 14 tentpole): fused
        probe steps + key transform + multi-key tuple search + run-end
        expansion + pair generation + the pytree-at-once gather of every
        output column on both sides, ONE compiled program per (sig,
        out_cap).  It also returns the :class:`JoinInfo` pytree so the
        sizing scalars for the one batched readback — and the overflow
        re-gather's inputs — ride the same launch instead of a separate
        probe program."""
        key = ("fusedprobe", out_cap)
        fn = self._gather_cache.get(key)
        if fn is None:
            def impl(probe, build, bs):
                info = self._probe_info(probe, build, bs)
                out = self._gather_impl(probe, build, info, out_cap)
                return out, info
            # named jit_srt_<Exec>_probe_<digest> on the device's trace:
            # a broadcast join's probes apart from a shuffled join's
            fn = self._jit(impl, key=("probe", self._sig, out_cap))
            self._gather_cache[key] = fn
        return fn

    def _pair_batch(self, probe: ColumnarBatch, build: ColumnarBatch,
                    maps: PairMaps) -> ColumnarBatch:
        lb = probe.gather(maps.l_idx, maps.l_ok, maps.num_out)
        rb = build.gather(maps.r_idx, maps.r_ok, maps.num_out)
        names = tuple(a.name for a in self._probe_attrs) + \
            tuple(a.name for a in self._build_attrs)
        return ColumnarBatch(names, lb.columns + rb.columns, maps.num_out)

    def _eval_condition(self, pair: ColumnarBatch, inner_ok):
        xp = self.xp
        ctx = EvalContext(pair, xp=xp)
        c = self._bound_cond.eval(ctx)
        return c.data & c.validity & inner_ok

    def _gather_impl(self, probe: ColumnarBatch, build: ColumnarBatch,
                     info: JoinInfo, out_cap: int) -> ColumnarBatch:
        xp = self.xp
        how = self._norm_how
        cond = self._bound_cond
        # fused probe steps re-applied inside this program: the pair
        # gather reads POST-chain columns and the live mask excludes
        # filtered-out probe rows (elementwise recompute, zero extra
        # dispatches — XLA fuses it into the gathers)
        probe, lmask = self._apply_probe_steps(probe, xp)
        lcap, rcap = probe.capacity, build.capacity

        if how in _FILTER_JOINS and cond is None:
            matched = info.counts > 0
            return self._emit_filter_join(probe, matched, lmask)

        if cond is None:
            maps = gather_pairs(xp, info, out_cap,
                                with_unmatched_left=how in ("left", "full"),
                                with_unmatched_right=how == "full")
            pair = self._pair_batch(probe, build, maps)
            return self._project_output(pair, maps)

        # residual condition: inner pairs -> pass mask -> reassemble
        maps = gather_pairs(xp, info, out_cap)
        pair = self._pair_batch(probe, build, maps)
        pass_mask = self._eval_condition(pair, maps.l_ok)

        if how in _FILTER_JOINS:
            matched = matched_per_row(xp, pass_mask, maps.l_idx, lcap) > 0
            return self._emit_filter_join(probe, matched, lmask)

        final = self._assemble_with_pass(probe, build, maps, pass_mask,
                                         out_cap, lmask)
        pair = self._pair_batch(probe, build, final)
        return self._project_output(pair, final)

    def _assemble_with_pass(self, probe: ColumnarBatch, build: ColumnarBatch,
                            maps: PairMaps, pass_mask, out_cap: int,
                            lmask=None) -> PairMaps:
        """Compact pairs surviving the residual condition to the front, then
        append unmatched-left/right rows per the (normalized) join type.
        ``lmask`` is the probe live mask (the fused-stage mask when probe
        steps are absorbed; defaults to the batch's row mask)."""
        xp = self.xp
        how = self._norm_how
        lcap, rcap = probe.capacity, build.capacity
        if lmask is None:
            lmask = probe.row_mask()
        cp = compact_indices(xp, pass_mask)
        n_pass = xp.sum(pass_mask).astype(xp.int64)
        k = xp.arange(out_cap, dtype=xp.int64)
        sel_pair = k < n_pass
        src = cp[xp.clip(k, 0, cp.shape[0] - 1).astype(xp.int32)]
        l_idx = xp.where(sel_pair, maps.l_idx[src], 0)
        r_idx = xp.where(sel_pair, maps.r_idx[src], 0)
        l_ok = sel_pair
        r_ok = sel_pair
        num_out = n_pass

        if how in ("left", "full"):
            m = matched_per_row(xp, pass_mask, maps.l_idx, lcap) > 0
            unl = lmask & ~m
            n_unl = xp.sum(unl.astype(xp.int64))
            ul = compact_indices(xp, unl)
            sel = (k >= num_out) & (k < num_out + n_unl)
            t = xp.clip(k - num_out, 0, lcap - 1).astype(xp.int32)
            l_idx = xp.where(sel, ul[t], l_idx)
            l_ok = l_ok | sel
            num_out = num_out + n_unl
        if how == "full":
            mb = matched_per_row(xp, pass_mask, maps.r_idx, rcap) > 0
            unb = build.row_mask() & ~mb
            n_unb = xp.sum(unb.astype(xp.int64))
            ub = compact_indices(xp, unb)
            sel = (k >= num_out) & (k < num_out + n_unb)
            t = xp.clip(k - num_out, 0, rcap - 1).astype(xp.int32)
            r_idx = xp.where(sel, ub[t], r_idx)
            r_ok = r_ok | sel
            num_out = num_out + n_unb

        return PairMaps(l_idx.astype(xp.int32), r_idx.astype(xp.int32),
                        l_ok, r_ok, num_out.astype(xp.int32))

    def _emit_filter_join(self, probe: ColumnarBatch, matched, lmask=None):
        """semi/anti/existence output (left rows only).  ``lmask`` is the
        probe live mask (the fused-stage mask when probe steps are
        absorbed — filtered-out rows must not resurface here)."""
        xp = self.xp
        how = self._norm_how
        if lmask is None:
            lmask = probe.row_mask()
        if how == "existence":
            from ...columnar.column import DeviceColumn
            ex = DeviceColumn(T.BOOLEAN, matched & lmask,
                              xp.ones_like(matched))
            names = tuple(a.name for a in self._out_left) + ("exists",)
            out = ColumnarBatch(names, probe.columns + (ex,),
                                probe.num_rows)
            if self._probe_steps:
                # fused filters never compacted upstream — rows they
                # dropped must not ride the existence passthrough out
                from .basic import compact_batch
                out = compact_batch(xp, out, lmask)
            return out
        keep = lmask & (matched if how == "left_semi" else ~matched)
        n = xp.sum(keep).astype(xp.int32)
        perm = compact_indices(xp, keep)
        cols = tuple(c.gather(perm, keep[perm]) for c in probe.columns)
        return ColumnarBatch(tuple(a.name for a in self._out_left), cols, n)

    def _project_output(self, pair: ColumnarBatch, maps: PairMaps
                        ) -> ColumnarBatch:
        """Reorder pair columns [probe][build] into [left][right] output."""
        np_, nb = len(self._probe_attrs), len(self._build_attrs)
        if self._flipped:
            idx = list(range(np_, np_ + nb)) + list(range(np_))
        else:
            idx = list(range(np_ + nb))
        names = tuple(a.name for a in self.output)
        cols = tuple(pair.columns[i] for i in idx)
        return ColumnarBatch(names, cols, maps.num_out)

    # --- sizing -----------------------------------------------------------
    def _out_capacity(self, info: JoinInfo, n_probe: int, n_build: int,
                      tctx: Optional[TaskContext] = None) -> int:
        how = self._norm_how
        if how in _FILTER_JOINS and self._bound_cond is None:
            return 8  # unused; filter joins reuse the probe capacity
        total, unl, unb = self._fetch_totals(info, tctx)
        if self._bound_cond is not None:
            extra = (n_probe if how in ("left", "full") else 0) + \
                (n_build if how == "full" else 0)
            return bucket_capacity(total + extra)
        extra = (unl if how in ("left", "full") else 0) + \
            (unb if how == "full" else 0)
        return bucket_capacity(total + extra)

    def _speculative_capacity(self, probe: ColumnarBatch,
                              build: ColumnarBatch,
                              tctx: TaskContext) -> Optional[int]:
        """Predicted output bucket from the learned (or configured initial)
        selectivity — host-only arithmetic on row-count BOUNDS, zero device
        syncs.  Outer-join null-extension slack is bounded exactly (≤ live
        probe/build rows), so only the inner-pair count is a guess."""
        from ...config import (JOIN_INITIAL_SELECTIVITY,
                               JOIN_SPECULATIVE_SIZING)
        if not bool(tctx.conf.get(JOIN_SPECULATIVE_SIZING)):
            return None
        how = self._norm_how
        n_probe = probe.num_rows_bound
        # capture the cache generation WITH the prediction: if a
        # concurrent clearKernelCache lands before this batch's observed
        # selectivity records, the record is dropped instead of seeding
        # the fresh generation with learning from dead programs
        from .kernel_cache import cache_generation
        self._sel_generation = cache_generation()
        sel = lookup_selectivity(self._sig)
        if sel is None:
            sel = float(tctx.conf.get(JOIN_INITIAL_SELECTIVITY))
        pred = int(sel * max(n_probe, 1)) + 1
        pred += (n_probe if how in ("left", "full") else 0)
        pred += (build.num_rows_bound if how == "full" else 0)
        return bucket_capacity(pred)

    def _record_selectivity(self, probe: ColumnarBatch, total: int) -> None:
        record_selectivity(self._sig,
                           total / max(probe.num_rows_bound, 1),
                           generation=getattr(self, "_sel_generation",
                                              None))

    def _cached_kernel(self, tag: str, chunk_cap: int, make_impl):
        """Get-or-build the jitted windowed kernel for (tag, chunk_cap) —
        shared by the hash-join and nested-loop chunked gathers."""
        key = (tag, chunk_cap)
        fn = self._gather_cache.get(key)
        if fn is None:
            fn = self._jit(make_impl(), key=(tag, self._sig, chunk_cap))
            self._gather_cache[key] = fn
        return fn

    def _chunk_fn(self, chunk_cap: int):
        """Windowed gather (JoinGatherer.scala:730 analog): one compiled
        program per chunk capacity; the window offset is a traced scalar."""
        how = self._norm_how

        def make():
            def impl(probe, build, info, offset):
                probe, _lmask = self._apply_probe_steps(probe, self.xp)
                maps = gather_pairs(
                    self.xp, info, chunk_cap,
                    with_unmatched_left=how in ("left", "full"),
                    with_unmatched_right=how == "full",
                    offset=offset)
                pair = self._pair_batch(probe, build, maps)
                return self._project_output(pair, maps)
            return impl
        return self._cached_kernel("gather_chunk", chunk_cap, make)

    def _join_one(self, probe: ColumnarBatch, build: ColumnarBatch,
                  tctx: Optional[TaskContext] = None) -> ColumnarBatch:
        info = self._join_info(probe, build, tctx)
        out_cap = self._out_capacity(info, probe.num_rows_int,
                                     build.num_rows_int, tctx)
        with self._stage(tctx, "gather"):
            return self._gather_fn(out_cap)(probe, build, info)

    def _join_batches(self, probe: ColumnarBatch, build: ColumnarBatch,
                      tctx: TaskContext):
        """Join output with donation provenance: gather-built outputs are
        freshly computed device buffers, so they are marked transient for
        downstream fused-stage donation (memory/retention.py).  Existence
        outputs may alias probe columns (passthrough) and stay unmarked."""
        from ...memory.retention import mark_transient
        passthrough = self._norm_how == "existence"
        for b in self._join_batches_impl(probe, build, tctx):
            yield b if passthrough else mark_transient(b)

    def _join_batches_impl(self, probe: ColumnarBatch,
                           build: ColumnarBatch, tctx: TaskContext):
        """Yield the join output, chunked when it exceeds the configured
        chunk rows (condition/filter joins keep the single-buffer path —
        their residual bookkeeping spans the whole pair space).

        Non-blocking output sizing: the gather for the PREDICTED output
        bucket dispatches before any host readback, so the one batched
        sizing fetch overlaps the gather's device execution instead of
        serializing build -> readback -> gather.  Only an overflow of the
        predicted bucket (realized rows > capacity) pays a re-gather."""
        probe, build = self._lower_encoded_keys(probe, build, tctx)
        how = self._norm_how
        if (self._bound_cond is not None or how in _FILTER_JOINS):
            yield self._join_one(probe, build, tctx)
            return
        from ...config import JOIN_OUTPUT_CHUNK_ROWS
        chunk = int(tctx.conf.get(JOIN_OUTPUT_CHUNK_ROWS))
        spec_cap = self._speculative_capacity(probe, build, tctx)
        speculating = spec_cap is not None \
            and spec_cap <= bucket_capacity(chunk)

        def total_out_of(tot, unl, unb):
            return tot + (unl if how in ("left", "full") else 0) + \
                (unb if how == "full" else 0)

        if speculating and self._fused_probe_on(tctx) \
                and self._fast_path_on(tctx):
            # single-program probe pipeline: search + expansion + pair
            # generation + the all-columns gather are ONE launch, with the
            # JoinInfo returned alongside for the one batched sizing
            # readback.  At most a second launch (the exact re-gather) on
            # bucket overflow — the fused-vs-two-program choice is a host
            # decision, so outputs stay bit-identical either way.
            from .base import count_stage_dispatch
            count_stage_dispatch()
            bs = self._get_build_side(build, tctx)
            STATS["fastpath_probes"] += 1
            STATS["fused_probes"] += 1
            tctx.inc_metric("joinFastpathProbes")
            tctx.inc_metric("joinFusedProbes")
            with self._stage(tctx, "fusedProbe"), \
                    _tracer.span("join", "probe"):
                out, info = self._fused_probe_fn(spec_cap)(probe, build, bs)
        else:
            with _tracer.span("join", "probe"):
                info = self._join_info(probe, build, tctx)
                if speculating:
                    with self._stage(tctx, "gather"):
                        out = self._gather_fn(spec_cap)(probe, build, info)

        tot, unl, unb = self._fetch_totals(info, tctx)
        self._record_selectivity(probe, tot)
        total_out = total_out_of(tot, unl, unb)
        tctx.inc_metric("joinProbeRows", probe.num_rows_bound)
        tctx.inc_metric("joinOutputRows", total_out)
        if speculating:
            if total_out <= spec_cap:
                STATS["spec_hits"] += 1
                tctx.inc_metric("joinSpecHits")
                out = out.with_known_rows(total_out)
                # a first batch is sized before any selectivity is known
                # (1.0: twice the probe's capacity); handed on as it is,
                # every exec above would compile a program of its own for
                # that one oversized shape (a 2^19-row group-id program
                # where the learned batches are 2^14).  Cut it to its rows'
                # bucket, as the learned batches come
                if bucket_capacity(total_out) * 4 <= spec_cap:
                    out = out.shrunk()
                yield out
                return
            # overflow: the realized output exceeds the predicted bucket —
            # re-gather at the exact capacity (the totals are on the host
            # already, so this costs no extra readback)
            STATS["spec_misses"] += 1
            tctx.inc_metric("joinSpecMisses")
        if total_out <= chunk:
            out_cap = bucket_capacity(total_out)
            with self._stage(tctx, "gather"):
                out = self._gather_fn(out_cap)(probe, build, info)
            yield out.with_known_rows(total_out)
            return
        STATS["chunked_joins"] += 1
        chunk_cap = bucket_capacity(chunk)
        fn = self._chunk_fn(chunk_cap)
        xp = self.xp
        for off in range(0, total_out, chunk_cap):
            with self._stage(tctx, "gather"):
                got = fn(probe, build, info,
                         xp.asarray(off, dtype=xp.int64))
            # chunk row counts are host arithmetic — shrunk() must not pay
            # a per-chunk num_rows sync (a hidden second blocking readback)
            yield got.with_known_rows(
                min(chunk_cap, total_out - off)).shrunk()

    # --- helpers ----------------------------------------------------------
    def _empty_batch(self, attrs) -> ColumnarBatch:
        schema = T.StructType(tuple(
            T.StructField(a.name, a.dtype, True) for a in attrs))
        b = ColumnarBatch.empty(schema)
        if self.backend != TPU:
            import jax
            b = jax.device_get(b)
        return b

    def _concat_or_empty(self, batches, attrs) -> ColumnarBatch:
        if not batches:
            return self._empty_batch(attrs)
        return ColumnarBatch.concat(batches) if len(batches) > 1 else batches[0]

    def simple_string(self):
        keys = ", ".join(f"{l.sql()}={r.sql()}" for l, r in
                         zip(self._probe_keys, self._build_keys))
        c = f" cond={self.condition.sql()}" if self.condition is not None else ""
        if self._probe_steps:
            chain = " -> ".join(s.node_name() for s in self._probe_steps)
            c += f" [fusedProbe: {chain}]"
        return (f"{self.node_name()} {self.how} [{keys}]{c}"
                f"{share_note(self.kept_share)}")


class ShuffledHashJoinExec(BaseJoinExec):
    """Both sides co-partitioned by key hash (planner inserts the
    exchanges); per partition the build side is concatenated and each probe
    batch is joined against it (reference ``GpuShuffledHashJoinExec``).

    Probe-filtering joins (inner/left-semi) additionally build a bloom
    filter from the materialized build exchange and install it as the
    probe exchange's map-side filter — the reference's AQE-gated
    runtime-filter pushdown (``GpuBloomFilterMightContain.scala:1``),
    re-shaped for this engine's eager exchange materialization: the build
    exchange always materializes fully before the probe's map stage runs,
    so the filter needs no separate aggregation plan."""

    _bloom_tried = False

    def num_partitions(self):
        return self._probe.num_partitions()

    def _maybe_install_bloom(self, tctx: TaskContext) -> None:
        from ...config import (BLOOM_JOIN_BITS_PER_ROW, BLOOM_JOIN_ENABLED,
                               BLOOM_JOIN_MAX_BUILD_ROWS)
        from ...ops import bloom as B
        from .basic import compact_batch
        from .exchange import ShuffleExchangeExec
        from .kernel_cache import exprs_key
        from ..expressions.hashing import XxHash64
        if self._bloom_tried:
            return
        self._bloom_tried = True
        probe, build = self._probe, self._build
        if (self._norm_how not in ("inner", "left_semi")
                or self.backend != TPU
                or not isinstance(probe, ShuffleExchangeExec)
                or not isinstance(build, ShuffleExchangeExec)
                or probe._materialized is not None
                or probe.map_side_filter is not None
                or not bool(tctx.conf.get(BLOOM_JOIN_ENABLED))):
            return
        # multi-slice shuffles materialize only the slice-LOCAL reduce
        # partitions here (peer-owned slots come back empty), so a bloom
        # built from them would cover a SUBSET of build rows and its
        # map-side filter would drop probe rows whose matches live in
        # peer-owned partitions — a false negative.  Same guard as the
        # AQE partition-coalescing one in exchange.py.
        from ...shuffle.manager import get_shuffle_manager
        topo = get_shuffle_manager(tctx.conf).topology
        if topo is not None and topo.multi_slice:
            return
        # equal join-key values must hash identically on both sides; a
        # dtype mismatch (missing analyzer cast) would make that false and
        # a bloom false NEGATIVE drops matching rows — so require it
        if any(p.data_type != b.data_type
               for p, b in zip(self._bound_pkeys, self._bound_bkeys)):
            return
        build._ensure_materialized(tctx)
        parts = [b for ps in build._materialized for b in ps
                 if b is not None]
        total = sum(b.num_rows_int for b in parts)
        if total == 0 or total > int(tctx.conf.get(BLOOM_JOIN_MAX_BUILD_ROWS)):
            return
        xp = self.xp
        bits_per_row = int(tctx.conf.get(BLOOM_JOIN_BITS_PER_ROW))
        m, k = B.bloom_params(total, bits_per_row)
        hb = XxHash64(*self._bound_bkeys)
        hp = XxHash64(*self._bound_pkeys)

        def build_step(bits, batch):
            ctx = EvalContext(batch, xp=xp)
            return B.bloom_build(xp, bits, hb.eval(ctx).data,
                                 batch.row_mask(), k)

        bkey = ("bloomb", m, k, exprs_key(self._bound_bkeys))
        step = self._jit(build_step, key=bkey)
        from ...parallel import placement
        spread = self.backend == TPU and len(placement.chips(tctx.conf)) > 1
        if not spread:
            bits = xp.zeros(m, dtype=bool)
            for b in parts:
                bits = step(bits, b)
        else:
            # the build partitions lie on several chips: each chip folds
            # its own into a bitset there, and every chip gets the OR of
            # them all (a runtime filter is broadcast by its nature)
            partial: dict = {}
            for b in parts:
                chip = placement.chip_of(b)
                partial[chip] = step(
                    partial.get(chip, np.zeros(m, dtype=bool)), b)
            union = self._jit(lambda *bs: xp.stack(bs).any(axis=0),
                              key=("bloomor", m, len(partial)))
            bits_on = {chip: union(*(placement.put(p, chip)
                                     for p in partial.values()))
                       for chip in placement.chips(tctx.conf)}

        # bits is an ARGUMENT, not a closure: the kernel cache shares
        # compiled programs by key across joins, so baking the bitset in
        # as a trace constant would let a second join with the same key
        # silently reuse the first join's filter
        def probe_filter(bits_, batch):
            ctx = EvalContext(batch, xp=xp)
            keep = B.bloom_might_contain(xp, bits_, hp.eval(ctx).data, k) \
                & batch.row_mask()
            return compact_batch(xp, batch, keep)

        fkey = ("bloomp", m, k, exprs_key(self._bound_pkeys))
        filt = self._jit(probe_filter, key=fkey)

        def map_filter(batch):
            mine = bits_on[placement.chip_of(batch)] if spread else bits
            out = filt(mine, batch).shrunk()
            B.STATS["probe_rows_in"] += batch.num_rows_int
            B.STATS["probe_rows_kept"] += out.num_rows_int
            tctx.inc_metric("bloomFilteredRows",
                            batch.num_rows_int - out.num_rows_int)
            return out

        probe.map_side_filter = map_filter
        B.STATS["blooms_built"] += 1
        tctx.inc_metric("bloomFiltersBuilt")

    def execute(self, pid: int, tctx: TaskContext):
        self._count_strategy(tctx, "Shuffle")
        with self._setup_lock:
            self._maybe_install_bloom(tctx)
        btctx = TaskContext(pid, tctx.conf, parent=tctx)
        with btctx.as_current():
            build_batches = list(self._build.execute(pid, btctx))
        build = self._concat_or_empty(build_batches, self._build.output)
        probes = list(self._probe.execute(pid, tctx))
        how = self._norm_how
        if how == "full" and len(probes) > 1:
            # unmatched-build rows must be emitted once per partition,
            # not once per probe batch
            probes = [ColumnarBatch.concat(probes)]
        if not probes:
            probes = [self._empty_batch(self._probe.output)]
        for probe in probes:
            yield from self._join_batches(probe, build, tctx)


class BroadcastHashJoinExec(BaseJoinExec):
    """Build side is a broadcast exchange shared across all probe
    partitions (reference ``GpuBroadcastHashJoinExecBase``).  Only valid
    for join types whose build side is not preserved (inner/left/semi/
    anti/existence with build=right) — the planner enforces this."""

    def num_partitions(self):
        return self._probe.num_partitions()

    def execute(self, pid: int, tctx: TaskContext):
        assert isinstance(self._build, BroadcastExchangeExec)
        self._count_strategy(tctx, "Broadcast")
        build = self._build.broadcast_batch(tctx)
        probes = list(self._probe.execute(pid, tctx))
        if not probes:
            probes = [self._empty_batch(self._probe.output)]
        for probe in probes:
            yield from self._join_batches(probe, build, tctx)


class NestedLoopJoinExec(BaseJoinExec):
    """Cartesian product + optional condition (reference
    ``GpuBroadcastNestedLoopJoinExecBase`` / ``GpuCartesianProductExec``).
    The build side is broadcast; pair space is all (i, j) combinations."""

    def num_partitions(self):
        return self._probe.num_partitions()

    def _build_info(self, probe, build):  # not used
        raise NotImplementedError

    def _join_one(self, probe: ColumnarBatch, build: ColumnarBatch,
                  tctx: Optional[TaskContext] = None) -> ColumnarBatch:
        n_probe = probe.num_rows_int
        n_build = build.num_rows_int
        how = self._norm_how
        # outer no-key joins need slack for null-extended rows even without
        # a condition (e.g. left join against an empty build side)
        extra = (n_probe if how in ("left", "full") else 0) + \
            (n_build if how == "full" else 0)
        out_cap = bucket_capacity(n_probe * n_build + extra)
        return self._nl_fn(out_cap)(probe, build)

    def _nl_fn(self, out_cap: int):
        fn = self._gather_cache.get(out_cap)
        if fn is None:
            def impl(probe, build):
                return self._nl_impl(probe, build, out_cap)
            fn = self._jit(impl, key=("nl", self._sig, out_cap))
            self._gather_cache[out_cap] = fn
        return fn

    def _join_batches_impl(self, probe: ColumnarBatch,
                           build: ColumnarBatch, tctx: TaskContext):
        """Chunk the (probe x build) pair space for condition-free
        inner/cross products; everything else keeps the one-buffer path."""
        how = self._norm_how
        if self._bound_cond is not None or how not in ("inner", "cross"):
            yield self._join_one(probe, build, tctx)
            return
        from ...config import JOIN_OUTPUT_CHUNK_ROWS
        chunk = int(tctx.conf.get(JOIN_OUTPUT_CHUNK_ROWS))
        total = probe.num_rows_int * build.num_rows_int
        if total <= chunk:
            yield self._join_one(probe, build, tctx)
            return
        STATS["chunked_joins"] += 1
        chunk_cap = bucket_capacity(chunk)

        def make():
            def impl(probe_, build_, offset):
                maps = cross_pairs(self.xp, probe_.num_rows,
                                   build_.num_rows, chunk_cap, offset=offset)
                pair = self._pair_batch(probe_, build_, maps)
                return self._project_output(pair, maps)
            return impl
        fn = self._cached_kernel("nl_chunk", chunk_cap, make)
        xp = self.xp
        for off in range(0, total, chunk_cap):
            yield fn(probe, build, xp.asarray(off, dtype=xp.int64)).shrunk()

    def _nl_impl(self, probe: ColumnarBatch, build: ColumnarBatch,
                 out_cap: int) -> ColumnarBatch:
        xp = self.xp
        how = self._norm_how
        lcap, rcap = probe.capacity, build.capacity
        maps = cross_pairs(xp, probe.num_rows, build.num_rows, out_cap)
        pair = self._pair_batch(probe, build, maps)
        if self._bound_cond is None and how in ("inner", "cross"):
            return self._project_output(pair, maps)
        pass_mask = (self._eval_condition(pair, maps.l_ok)
                     if self._bound_cond is not None else maps.l_ok)

        if how in _FILTER_JOINS:
            matched = matched_per_row(xp, pass_mask, maps.l_idx, lcap) > 0
            return self._emit_filter_join(probe, matched)

        final = self._assemble_with_pass(probe, build, maps, pass_mask,
                                         out_cap)
        pair = self._pair_batch(probe, build, final)
        return self._project_output(pair, final)

    def execute(self, pid: int, tctx: TaskContext):
        if isinstance(self._build, BroadcastExchangeExec):
            build = self._build.broadcast_batch(tctx)
        else:
            # every probe partition needs the whole build stream
            batches = []
            for bpid in range(self._build.num_partitions()):
                btctx = TaskContext(bpid, tctx.conf)
                with btctx.as_current():
                    batches.extend(self._build.execute(bpid, btctx))
            build = self._concat_or_empty(batches, self._build.output)
        probes = list(self._probe.execute(pid, tctx))
        how = self._norm_how
        if how == "full" and len(probes) > 1:
            probes = [ColumnarBatch.concat(probes)]
        if not probes:
            probes = [self._empty_batch(self._probe.output)]
        for probe in probes:
            yield from self._join_batches(probe, build, tctx)


def _release_catalog_handles(catalog, handles) -> None:
    """weakref.finalize target (must not reference the finalized object):
    drop the spill-catalog registrations a dead MaterializedExec owned.
    ``remove`` is a no-op for handles already gone (catalog reset)."""
    for h in handles:
        try:
            catalog.remove(h)
        except Exception:  # pragma: no cover - teardown must never raise
            pass


def _live_bytes(parts) -> int:
    """What a broadcast of these batches would hold: each batch's arrays at
    its live rows' share of its capacity (a filter's output keeps its
    input's capacity; a concat drops the padding).  Reads each batch's row
    count, so it waits for the programs that made them."""
    from ...memory.spill import batch_device_bytes
    return sum(batch_device_bytes(b) * b.num_rows_int // max(b.capacity, 1)
               for bs in parts for b in bs)


class MaterializedExec(PhysicalPlan):
    """Leaf serving pre-computed batches per partition — the runtime-stats
    carrier AQE re-plans over (GpuCustomShuffleReaderExec's shuffle-stage
    analog).  Batches are registered with the spill catalog so the stage's
    working set can be demoted off-device between the size observation and
    the chosen plan's execution (the reference keeps materialized stages
    in the spillable shuffle catalog for the same reason)."""

    def __init__(self, attrs, parts: List[List[ColumnarBatch]], backend=TPU):
        super().__init__()
        self.backend = backend
        self._attrs = list(attrs)
        self._nbytes = 0
        if backend == TPU:
            import weakref
            from ...memory.spill import (BufferCatalog,
                                         OUTPUT_FOR_SHUFFLE_PRIORITY,
                                         SpillableColumnarBatch,
                                         batch_device_bytes)
            self._nbytes = sum(batch_device_bytes(b)
                               for bs in parts for b in bs)
            self._parts = [[SpillableColumnarBatch.create(
                b, OUTPUT_FOR_SHUFFLE_PRIORITY) for b in bs]
                for bs in parts]
            # the spillables live as long as this node (AQE may re-serve
            # them to every probe partition), so their catalog handles
            # are released when the PLAN dies — without this every
            # adaptive join leaked its materialized build side until
            # process exit (found by tools/leak_sentinel.py)
            catalog = BufferCatalog.get()
            handles = [sb._handle for bs in self._parts for sb in bs]
            self._finalizer = weakref.finalize(
                self, _release_catalog_handles, catalog, handles)
        else:
            self._parts = parts

    @property
    def output(self):
        return self._attrs

    def num_partitions(self):
        return max(1, len(self._parts))

    def estimate_bytes(self):
        if self.backend != TPU:
            from ...memory.spill import batch_device_bytes
            return sum(batch_device_bytes(b)
                       for bs in self._parts for b in bs)
        return self._nbytes

    def execute(self, pid, tctx):
        if pid < len(self._parts):
            for item in self._parts[pid]:
                yield item.get() if hasattr(item, "get") else item


class AdaptiveJoinExec(PhysicalPlan):
    """AQE join: defer the broadcast-vs-shuffle decision until the build
    side's ACTUAL size is observed at execution time (the reference's AQE
    integration re-plans query stages from materialized shuffle statistics,
    ``GpuOverrides.scala:4392-4452``).  The static planner falls back to
    this when its estimates say "shuffle"; if the materialized build side
    turns out to fit the broadcast threshold, the cheaper broadcast hash
    join is picked instead — a provably different plan on mis-estimated
    inputs.  The side that is measured, and built on, is the child the
    static estimates call the smaller (``build_left``: an inner join whose
    left child is; ``plan_join``), and its size is that of its live rows:
    a filter's output still has its input's capacity."""

    kept_share: Optional[float] = None

    def __init__(self, node, left: PhysicalPlan, right: PhysicalPlan,
                 backend, conf, build_left: bool = False):
        super().__init__(left, right)
        self.backend = backend
        self._node = node
        self._conf = conf
        self._build_left = build_left
        self._chosen: Optional[PhysicalPlan] = None
        self._choose_lock = threading.Lock()
        self.chosen_strategy: Optional[str] = None
        # static shape only (output schema / explain); never executed
        self._shape = ShuffledHashJoinExec(
            node.how, node.left_keys, node.right_keys, node.condition,
            left, right, backend=backend, build_left=build_left)

    @property
    def output(self):
        return self._shape.output

    def num_partitions(self):
        return int(self._conf.shuffle_partitions)

    def estimate_bytes(self):
        return _join_estimate(self.children)

    def _choose(self, tctx: TaskContext):
        if self._chosen is not None:
            return
        with self._choose_lock:
            if self._chosen is None:
                self._choose_locked(tctx)

    def _choose_locked(self, tctx: TaskContext):
        from ...config import AUTO_BROADCAST_THRESHOLD
        node, build_left = self._node, self._build_left
        build = self.children[0 if build_left else 1]
        parts = []
        with _tracer.span("join", "adaptive.materialize"):
            for p in range(build.num_partitions()):
                btctx = TaskContext(p, tctx.conf, parent=tctx)
                with btctx.as_current():
                    parts.append(list(build.execute(p, btctx)))
            threshold = int(self._conf.get(AUTO_BROADCAST_THRESHOLD))
            # measured only where a broadcast can follow (a negative
            # threshold turns broadcasts off): the read waits for the device
            can_broadcast = (node.how in ("inner", "left", "left_semi",
                                          "left_anti", "existence")
                             and threshold >= 0
                             and _live_bytes(parts) <= threshold)
            build_m = MaterializedExec(build.output, parts,
                                       backend=self.backend)
        sides = dict(backend=self.backend, build_left=build_left)
        if can_broadcast:
            bx = BroadcastExchangeExec(build_m, backend=self.backend)
            left, right = ((bx, self.children[1]) if build_left
                           else (self.children[0], bx))
            self._chosen = BroadcastHashJoinExec(
                node.how, node.left_keys, node.right_keys, node.condition,
                left, right, **sides)
            self.chosen_strategy = "broadcast"
        else:
            n = self.num_partitions()
            from ...parallel.partitioning import HashPartitioning
            from .exchange import ShuffleExchangeExec
            left, right = ((build_m, self.children[1]) if build_left
                           else (self.children[0], build_m))
            lx = ShuffleExchangeExec(
                HashPartitioning(node.left_keys, n), left,
                backend=self.backend, coalescible=False,
                skew_splittable=not build_left and node.how != "full")
            rx = ShuffleExchangeExec(
                HashPartitioning(node.right_keys, n), right,
                backend=self.backend, coalescible=False,
                skew_splittable=build_left)
            self._chosen = ShuffledHashJoinExec(
                node.how, node.left_keys, node.right_keys, node.condition,
                lx, rx, **sides)
            self.chosen_strategy = "shuffle"

    def execute(self, pid, tctx):
        self._choose(tctx)
        n = self.num_partitions()
        m = self._chosen.num_partitions()
        # serve the chosen plan's m partitions through our fixed n pids
        for p in range(pid, m, n) if m > n else (
                [pid] if pid < m else []):
            ctctx = TaskContext(p, tctx.conf, parent=tctx)
            with ctctx.as_current():
                got = list(self._chosen.execute(p, ctctx))
            yield from got

    def simple_string(self):
        tag = self.chosen_strategy or "undecided"
        side = ", build=left" if self._build_left else ""
        return (f"{self.node_name()} {self._node.how} [aqe: {tag}{side}]"
                f"{share_note(self.kept_share)}")


# --------------------------------------------------------------------------
# planning
# --------------------------------------------------------------------------

def plan_join(node, left: PhysicalPlan, right: PhysicalPlan, backend,
              conf) -> PhysicalPlan:
    """Join strategy selection (the reference's exec rules for
    BroadcastHashJoinExec / ShuffledHashJoinExec / SortMergeJoinExec /
    CartesianProductExec / BroadcastNestedLoopJoinExec)."""
    from ...parallel.partitioning import HashPartitioning, SinglePartitioning
    from .exchange import ShuffleExchangeExec

    how = node.how
    if not node.left_keys:
        # condition-only / cross join -> nested loop with broadcast build.
        # right/full preserve the build side, so the probe must see the
        # whole stream exactly once -> coalesce to a single partition.
        if how in ("right", "full") and left.num_partitions() > 1:
            left = ShuffleExchangeExec(SinglePartitioning(), left,
                                       backend=backend)
        build = BroadcastExchangeExec(right, backend=backend)
        return NestedLoopJoinExec(how, (), (), node.condition, left, build,
                                  backend=backend)

    from ...config import AUTO_BROADCAST_THRESHOLD
    threshold = int(conf.get(AUTO_BROADCAST_THRESHOLD))
    hinted = bool(getattr(node, "broadcast_hint", False))
    # the side choice: an inner equi-join builds on the child that is
    # smaller by what can be observed here, whichever side of the text it
    # stands on (Spark's JoinSelection picks its build side the same way):
    # a left child that may be broadcast, or one a shuffled join's right
    # child dwarfs.  Two sides of like size that would both be shuffled
    # keep the text's order (_LIKE_SIZE), a hint names the right child,
    # and unknown sizes keep the text's order too
    left_bytes, right_bytes = left.estimate_bytes(), right.estimate_bytes()
    build_left = (how == "inner" and not hinted
                  and left_bytes is not None and right_bytes is not None
                  and left_bytes < right_bytes
                  and (left_bytes <= threshold
                       or left_bytes * _LIKE_SIZE <= right_bytes))
    probe, build = (right, left) if build_left else (left, right)
    probe_keys, build_keys = ((node.right_keys, node.left_keys) if build_left
                              else (node.left_keys, node.right_keys))
    build_bytes = left_bytes if build_left else right_bytes
    sides = dict(backend=backend, build_left=build_left)
    can_broadcast = (how in ("inner", "left", "left_semi", "left_anti",
                             "existence")
                     and (hinted
                          or (build_bytes is not None
                              and build_bytes <= threshold)))
    if can_broadcast and (hinted or probe.num_partitions() > 1):
        bx = BroadcastExchangeExec(build, backend=backend)
        # dynamic partition pruning: a hive-partitioned probe scan joined
        # on its partition column skips files the broadcast keys rule out.
        # ONLY probe-filtering joins qualify — outer/anti/existence joins
        # must emit probe rows with NO build match, which are exactly the
        # rows pruning would drop
        if how in ("inner", "left_semi"):
            from .dpp import apply_dpp
            probe = apply_dpp(probe, probe_keys, build_keys, bx)
        left, right = (bx, probe) if build_left else (probe, bx)
        return BroadcastHashJoinExec(how, node.left_keys, node.right_keys,
                                     node.condition, left, right, **sides)

    from ...config import ADAPTIVE_ENABLED
    nparts = max(left.num_partitions(), right.num_partitions())
    if (bool(conf.get(ADAPTIVE_ENABLED)) and nparts > 1
            and how in ("inner", "left", "left_semi", "left_anti",
                        "existence")):
        # the static estimate said "shuffle" (or was unknown): let AQE
        # re-decide from the materialized build side at runtime
        return AdaptiveJoinExec(node, left, right, backend, conf,
                                build_left=build_left)
    if nparts > 1:
        n = int(conf.shuffle_partitions)
        # the PROBE side gets skew splitting; right joins and inner joins
        # that build on the left flip sides in BaseJoinExec (probe=right,
        # build=left), full joins concat their probe batches back (join.py
        # execute), so neither benefits
        left = ShuffleExchangeExec(
            HashPartitioning(node.left_keys, n), left, backend=backend,
            coalescible=False,
            skew_splittable=how not in ("full", "right") and not build_left)
        right = ShuffleExchangeExec(
            HashPartitioning(node.right_keys, n), right, backend=backend,
            coalescible=False, skew_splittable=how == "right" or build_left)
    return ShuffledHashJoinExec(how, node.left_keys, node.right_keys,
                                node.condition, left, right, **sides)
