"""TpuSession — the SparkSession-equivalent entry point (reference:
``SQLPlugin`` + driver/executor plugin init, SURVEY §2.1, recast for a
standalone engine: device init happens lazily on first TPU exec)."""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa

from .. import types as T
from ..config import RapidsConf
from . import plan as P
from .dataframe import DataFrame
from .planner import Planner


class SessionConf:
    def __init__(self, conf: RapidsConf):
        self._conf = conf

    def set(self, key: str, value) -> None:
        self._conf.set(key, value)

    def get(self, key: str, default=None):
        return self._conf.get(key, default)


def _aux_stats_snapshot() -> dict:
    """Flat snapshot of the process-wide encoded/prepack/decode counters
    whose per-query deltas fold into last_query_metrics (the robustness
    stats_snapshot pattern)."""
    from ..columnar import batch as _batch
    from ..columnar import encoded as _enc
    from ..columnar import prepack as _pp
    from ..io_ import decode_stats as _ds
    out = dict(_ds.snapshot())
    out["syncReadbacks"] = _batch.SYNC_STATS["readbacks"]
    es = _enc.stats_snapshot()
    out.update({
        "encodedColumnsEncoded": es["columns_encoded"]
        + es["rle_columns_encoded"],
        "encodedColumnsDeclined": es["columns_declined"],
        "encodedMaterializations": es["materializations"],
        "encodedDictFilters": es["dict_filters"],
        "encodedConcatUnified": es["concat_unified"],
        "encodedWireDictInline": es["wire_dict_inline"],
        "encodedWireDictRefs": es["wire_dict_refs"],
        "encodedWireBytesSaved": es["wire_bytes_saved"],
    })
    out.update({
        "prepackBytesOnWire": _pp.STATS["bytes_on_wire"],
        "prepackBytesNaive": _pp.STATS["bytes_naive"],
        "prepackFetches": _pp.STATS["prepacked_fetches"],
    })
    return out


class TpuSession:
    _lock = threading.Lock()
    _active: Optional["TpuSession"] = None
    #: atomic under the GIL (a plain int += under _lock would deadlock:
    #: get_or_create constructs sessions while already holding _lock)
    _session_seq = __import__("itertools").count(1)

    def __init__(self, conf: Optional[RapidsConf] = None, **conf_kwargs):
        base = conf or RapidsConf.get_global()
        self._conf = base.copy(conf_kwargs or None)
        self.conf = SessionConf(self._conf)
        self.last_query_metrics: dict = {}
        #: compact tracer summary of the last traced query (sync count/ms,
        #: compile ms, bytes on the wire); None when tracing was off
        self.last_query_trace_summary: Optional[dict] = None
        #: drain latency of the most recent cancelled/deadline-expired
        #: query (cancel issue -> worker threads unwound), ms; None
        #: until a cancellation happens (serving/lifecycle.py)
        self.last_cancel_latency_ms: Optional[float] = None
        self._temp_views: dict = {}
        #: name -> implementation object (Hive UDF bridge; hiveUDFs.scala
        #: analog — populated by CREATE TEMPORARY FUNCTION or the API)
        self._hive_udfs: dict = {}
        #: stable session identity stamped on every span, metric series
        #: and flight-recorder record (groundwork for per-tenant metrics,
        #: ROADMAP item 1); also exported as a Chrome-trace process label
        import os as _os
        self.session_id = (f"sess-{_os.getpid()}-"
                           f"{next(TpuSession._session_seq)}")
        self._history = None  # lazily built from conf on first record
        #: tenant identity (spark.rapids.tpu.serving.tenant): stamped on
        #: metric series, trace spans and flight-recorder records; the
        #: serving tier's admission queue schedules and budgets by it
        from ..config import SERVING_TENANT
        self.tenant = str(self._conf.get(SERVING_TENANT) or "")
        #: owning ServingEngine when this session runs in serving mode
        #: (set by ServingEngine.session); None = classic single-driver
        self._serving = None
        #: embedded telemetry server (observability/server.py) when
        #: spark.rapids.tpu.telemetry.enabled and this session is NOT
        #: under a ServingEngine (the engine owns the plane there and
        #: forces the conf off for its sessions); stop with
        #: :meth:`close_telemetry` — leak-free by contract
        self.telemetry = None
        from ..config import TELEMETRY_ENABLED
        if bool(self._conf.get(TELEMETRY_ENABLED)):
            self._start_telemetry()

    # ------------------------------------------------------------------
    @classmethod
    def get_or_create(cls, conf=None, **conf_kwargs) -> "TpuSession":
        with cls._lock:
            if cls._active is None or conf is not None or conf_kwargs:
                cls._active = TpuSession(conf, **conf_kwargs)
            return cls._active

    # ------------------------------------------------------------------
    # data sources
    # ------------------------------------------------------------------
    def create_dataframe(self, data, schema=None, num_partitions: int = 1,
                         partitions=None) -> DataFrame:
        table = _to_arrow_table(data, schema)
        if partitions is not None:
            parts = list(partitions)
        else:
            # split through the process-wide dedupe cache: repeated
            # create_dataframe calls over the SAME table object yield the
            # same partition slice objects, so the scan upload cache (and
            # the serving tier's content-keyed result/broadcast caches,
            # which key in-memory leaves by table identity) hit across
            # queries and sessions instead of re-uploading per query
            parts = _split_table_cached(table, num_partitions) \
                if num_partitions > 1 else None
        rel = P.Relation(table, parts)
        return DataFrame(rel, self)

    createDataFrame = create_dataframe

    def range(self, start: int, end: Optional[int] = None, step: int = 1,
              num_slices: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(P.Range(start, end, step, num_slices), self)

    @property
    def read(self) -> "DataFrameReader":
        return DataFrameReader(self)

    # ------------------------------------------------------------------
    # SQL surface (Catalyst-parser analog; sqlparser.py)
    # ------------------------------------------------------------------
    def sql(self, query: str) -> DataFrame:
        """Run a SQL query over registered temp views — the same planning
        and execution path as the DataFrame API."""
        from ..config import TRACE_ENABLED
        from ..observability import tracer as OT
        from .sqlparser import parse_query
        # parsing runs before _execute arms the flags: arm the profiler
        # sink around it the same way (a serving engine armed it for its
        # lifetime; its sessions flip nothing)
        prev = OT.TRACING["profiler"]
        if self._serving is None:
            OT.TRACING["profiler"] = bool(self._conf.get(TRACE_ENABLED))
        try:
            with OT.span("plan", "parse"):
                return parse_query(self, query)
        finally:
            if self._serving is None:
                OT.TRACING["profiler"] = prev

    def register_hive_function(self, name: str, impl) -> None:
        """Register a Hive-style function (the CREATE TEMPORARY FUNCTION
        surface): ``impl`` is an object/class with ``return_type`` and
        ``evaluate(*row)`` (row-based, host) or
        ``evaluate_columnar(ctx, *cols)`` (device SPI), or a
        'module.Class' string resolved by import."""
        from .expressions.hive_udf import (_impl_return_type,
                                           resolve_hive_class)
        if isinstance(impl, str):
            impl = resolve_hive_class(impl)
        elif isinstance(impl, type):
            impl = impl()
        _impl_return_type(impl)  # validate the declaration up front
        self._hive_udfs[name.lower()] = impl

    def table(self, name: str) -> DataFrame:
        view = self._temp_views.get(name.lower())
        if view is None:
            raise ValueError(f"table or view not found: {name}")
        return DataFrame(view._plan, self)

    @property
    def catalog(self) -> "Catalog":
        return Catalog(self)

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def _execute(self, logical: P.LogicalPlan) -> pa.Table:
        if self._serving is not None:
            # serving mode: no per-query global flag flips (the engine
            # armed them for its lifetime), admission-gated execution,
            # thread-scoped tenant attribution — see _execute_serving
            return self._execute_serving(logical)
        import time as _time
        from ..columnar.convert import device_to_arrow
        from ..config import (METRICS_ENABLED, METRICS_MAX_SERIES,
                              PROFILE_ENABLED, SERVING_RESULT_CACHE_ENABLED,
                              TRACE_BUFFER_EVENTS, TRACE_ENABLED, TRACE_SINK)
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        from ..robustness import faults as _faults
        from ..robustness import stats_snapshot
        from .physical import speculation
        from .physical.base import PROFILING
        from .physical.kernel_cache import cache_stats
        # cross-query result cache (docs/serving.md): a content-key hit
        # short-circuits the whole query — no flag arming, no execution
        rc_key = None
        if bool(self._conf.get(SERVING_RESULT_CACHE_ENABLED)):
            from ..serving import result_cache as RC
            rc_key, hit = RC.lookup_logical(logical, self._conf)
            if hit is not None:
                self._note_result_cache_hit(hit)
                return hit
        # arm/disarm the seeded chaos registry from this session's conf
        # for the duration of THIS query, restore-on-exit like the
        # tracing flags below (a disabled conf only undoes a conf-driven
        # arming, so tests arming chaos directly keep their schedule)
        prev_chaos = _faults.snapshot_arming()
        _faults.apply_conf(self._conf)
        rob0 = stats_snapshot()
        aux0 = _aux_stats_snapshot()
        profiling = bool(self._conf.get(PROFILE_ENABLED))
        sink = str(self._conf.get(TRACE_SINK) or "").strip()
        # profile.enabled implies an in-memory trace so the profile report
        # carries sync/compile/transfer attribution, not just wall time
        tracing = profiling or bool(sink)
        metrics_on = bool(self._conf.get(METRICS_ENABLED))
        # save/restore the process-wide flags (finally-guarded): a query
        # raising mid-flight, or one session enabling profiling, must not
        # leak the flags into a later query or another session's.  The
        # flags being process-global at all rests on the single-driver
        # model — see PROFILING in physical/base.py.
        prev_prof, prev_trace = PROFILING["on"], dict(OT.TRACING)
        prev_metrics = OM.METRICS["on"]
        PROFILING["on"] = profiling or tracing
        self._query_seq = getattr(self, "_query_seq", 0) + 1
        qctx = self._new_query_ctx()
        if tracing:
            OT.get_tracer().reset(int(self._conf.get(TRACE_BUFFER_EVENTS)),
                                  session=self.session_id)
        OT.TRACING["on"] = tracing
        OT.TRACING["profiler"] = bool(self._conf.get(TRACE_ENABLED))
        if metrics_on:
            reg = OM.get_registry()
            reg.max_series = int(self._conf.get(METRICS_MAX_SERIES))
            labels = {"query": self._query_seq,
                      "session": self.session_id}
            if self.tenant:
                labels["tenant"] = self.tenant
            reg.set_default_labels(**labels)
        OM.METRICS["on"] = metrics_on
        cache_stats0 = cache_stats()
        ok = False
        err: Optional[BaseException] = None
        t0 = _time.perf_counter()
        try:
            from ..serving import lifecycle as _lc
            with _lc.installed(qctx), OT.span(
                    "query", "collect", query=self._query_seq,
                    session=self.session_id):
                out = self._execute_traced(logical, device_to_arrow,
                                           speculation)
            ok = True
            if rc_key is not None:
                from ..serving import result_cache as RC
                RC.store(rc_key, out)
            return out
        except BaseException as e:
            err = e
            raise
        finally:
            duration_s = _time.perf_counter() - t0
            PROFILING["on"] = prev_prof
            OT.TRACING.update(prev_trace)
            OM.METRICS["on"] = prev_metrics
            _faults.restore_arming(prev_chaos)
            self._finish_query_ctx(qctx)
            self._finish_trace(tracing, sink, cache_stats0, rob0, ok,
                               aux0=aux0, duration_s=duration_s, err=err,
                               metrics_on=metrics_on)

    def _execute_serving(self, logical: P.LogicalPlan) -> pa.Table:
        """Serving-mode execution (docs/serving.md): result-cache
        short-circuit, degraded-engine/quarantine gate, admission slot
        (weighted-fair + tenant budget, cancellable), pressure-aware
        plan degradation, thread-scoped tenant/session attribution on
        metrics and trace spans, shared flight-recorder record — and NO
        per-query global flag churn: tracing/profiling/metrics/chaos
        were armed once by the owning ServingEngine, because N driver
        threads saving and restoring process flags would race each
        other.

        Per-query kernel-cache deltas are deliberately absent here
        (concurrent queries would smear each other's compiles); use the
        engine-scoped registry/cache_stats views instead."""
        import time as _time
        from ..columnar.convert import device_to_arrow
        from ..memory.fatal import FatalDeviceError
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        from ..serving import lifecycle as _lc
        from .physical import speculation
        eng = self._serving
        tenant = self.tenant or "default"
        rc_key = None
        if eng.result_cache_enabled:
            # hits bypass admission entirely: a cached result consumes
            # no slot, no budget, no device time
            from ..serving import result_cache as RC
            rc_key, hit = RC.lookup_logical(logical, self._conf)
            if hit is not None:
                self._note_result_cache_hit(hit)
                return hit
        # poison-query gate: only computed when the engine is degraded
        # or has live quarantine entries — the healthy path never pays
        # for a fingerprint (docs/serving.md "query lifecycle")
        qkey = None
        if eng.is_degraded() or eng.quarantine.size():
            qkey = _lc.quarantine_key(logical, self._conf)
            eng.check_admittable(qkey)
        from ..serving.admission import estimate_query_bytes
        est = estimate_query_bytes(logical)
        self._query_seq = getattr(self, "_query_seq", 0) + 1
        # the lifecycle token exists BEFORE admission so a cancel fired
        # while the query is still queued unblocks the admission wait
        # (and rolls the tenant's WFQ virtual finish time back)
        qctx = self._new_query_ctx()
        t_sub = _time.perf_counter()
        try:
            ticket = eng.admission.acquire(tenant, est, cancel=qctx)
        except BaseException:
            self._finish_query_ctx(qctx)
            raise
        wait_s = _time.perf_counter() - t_sub
        if OT.TRACING["on"] and wait_s > 1e-6:
            OT.get_tracer().complete("admission", f"admit.{tenant}",
                                     t_sub, wait_s, tenant=tenant,
                                     est_bytes=est)
        # pressure-aware graceful degradation: a saturated admission
        # queue shrinks THIS query's plan (kill-switched; lifecycle.py)
        conf = self._conf
        pressure_over = eng.pressure.plan_overrides(eng.admission,
                                                    self._conf)
        if pressure_over:
            conf = self._conf.copy(pressure_over)
        OT.set_thread_context(tenant=tenant, sid=self.session_id)
        if OM.METRICS["on"]:
            OM.get_registry().set_thread_labels(
                tenant=tenant, session=self.session_id,
                query=self._query_seq)
        ok = False
        err: Optional[BaseException] = None
        t0 = _time.perf_counter()
        try:
            with _lc.installed(qctx), OT.span(
                    "query", "collect", query=self._query_seq,
                    session=self.session_id):
                out = self._execute_traced(logical, device_to_arrow,
                                           speculation, conf=conf)
            ok = True
        except FatalDeviceError as e:
            # poison query: fail ONLY this query, quarantine its plan
            # fingerprint, mark the engine degraded until a probe
            # succeeds — sibling tenants' in-flight queries finish
            err = e
            eng.note_fatal(e, qkey
                           or _lc.quarantine_key(logical, self._conf),
                           tenant=tenant)
            raise
        except BaseException as e:
            err = e
            raise
        finally:
            duration_s = _time.perf_counter() - t0
            OT.clear_thread_context()
            OM.get_registry().clear_thread_labels()
            eng.admission.release(ticket)
            self._finish_query_ctx(qctx)
            self.last_query_trace_summary = None  # engine-scoped trace
            if ok:
                m = self.last_query_metrics
                m["sessionId"] = self.session_id
                m["tenant"] = tenant
                m["admissionWaitMs"] = round(wait_s * 1e3, 3)
                m["admissionEstBytes"] = est
                if pressure_over:
                    m["pressureDegraded"] = 1
            self._record_history(ok, duration_s, err)
            status = "ok" if ok else "failed"
            OM.observe("query_ms", duration_s * 1e3, status=status,
                       tenant=tenant, session=self.session_id)
            OM.inc("queries_total", status=status, tenant=tenant)
            OM.observe("admission_wait_ms", wait_s * 1e3, tenant=tenant)
        if rc_key is not None:
            from ..serving import result_cache as RC
            RC.store(rc_key, out)
        return out

    # ------------------------------------------------------------------
    # query lifecycle (serving/lifecycle.py, docs/robustness.md)
    # ------------------------------------------------------------------
    def _new_query_ctx(self):
        """Create + register the lifecycle token for query
        ``self._query_seq`` (cooperative cancellation + deadline)."""
        from ..config import QUERY_CANCEL_POLL_SITES, QUERY_DEADLINE_MS
        from ..serving import lifecycle as _lc
        qctx = _lc.QueryContext(
            self._query_seq, session_id=self.session_id,
            tenant=self.tenant,
            deadline_ms=int(self._conf.get(QUERY_DEADLINE_MS)),
            poll_sites=_lc.parse_poll_sites(
                self._conf.get(QUERY_CANCEL_POLL_SITES)))
        _lc.register(qctx)
        return qctx

    def _finish_query_ctx(self, qctx) -> None:
        """Unregister the token; when the query was cancelled (or hit
        its deadline), bank the drain latency — cancel issue to worker
        threads unwound — as the ``cancel_latency_ms`` series and a
        ``cancel`` trace span."""
        import time as _time
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        from ..serving import lifecycle as _lc
        _lc.unregister(qctx)
        if qctx.cancelled_at is None:
            return
        lat_s = _time.perf_counter() - qctx.cancelled_at
        self.last_cancel_latency_ms = lat_s * 1e3
        OM.observe("cancel_latency_ms", lat_s * 1e3,
                   **({"tenant": self.tenant} if self.tenant else {}))
        if OT.TRACING["on"]:
            OT.get_tracer().complete(
                "cancel", "query.drained", qctx.cancelled_at, lat_s,
                query=qctx.query_id, reason=qctx.reason)

    def cancel(self, query_id: Optional[int] = None,
               reason: str = "cancelled by user") -> int:
        """Cooperatively cancel this session's running query (or the
        specific ``query_id``).  Worker threads observe the token at the
        lifecycle poll sites and unwind within the poll bound, releasing
        the device semaphore, retention pins and prefetch queues; the
        waiting ``collect()`` raises :class:`QueryCancelled`.  Returns
        how many live queries were cancelled (0 = nothing running)."""
        from ..serving import lifecycle as _lc
        return _lc.cancel_session(self.session_id, query_id, reason)

    def _note_result_cache_hit(self, table) -> None:
        """Epilogue for a result served from the cross-query cache: the
        query still leaves metrics + a flight-recorder record (hit
        visibility is the contract CI asserts), just no execution."""
        from ..observability import metrics as OM
        self._query_seq = getattr(self, "_query_seq", 0) + 1
        tenant = self.tenant or ""
        self.last_query_metrics = {
            "resultCacheHit": 1, "sessionId": self.session_id,
            "numOutputRows": int(getattr(table, "num_rows", 0)),
        }
        if tenant:
            self.last_query_metrics["tenant"] = tenant
        self.last_query_trace_summary = None
        self._last_phys = None
        self._record_history(True, 0.0, None)
        OM.inc("result_cache_served_total",
               **({"tenant": tenant} if tenant else {}))

    def _finish_trace(self, tracing: bool, sink: str, cache_stats0: dict,
                      rob0: dict, ok: bool, aux0: Optional[dict] = None,
                      duration_s: float = 0.0,
                      err: Optional[BaseException] = None,
                      metrics_on: bool = False) -> None:
        """Per-query trace epilogue: fold kernel-cache and robustness
        deltas into last_query_metrics, snapshot the tracer (the ring is
        process-wide and resets at the next traced query), build the
        compact summary, append the JSONL event log when the sink is a
        directory, land the query in the flight recorder, and feed the
        whole-query metrics series."""
        from ..robustness import stats_snapshot
        from .physical.kernel_cache import cache_stats
        cs1 = cache_stats()
        if ok:  # on failure last_query_metrics is still the prior query's
            m = self.last_query_metrics
            m["sessionId"] = self.session_id
            if self.tenant:
                m["tenant"] = self.tenant
            for src, dst in (("hits", "kernelCacheHits"),
                             ("misses", "kernelCacheMisses"),
                             ("compiles", "kernelCompiles"),
                             ("compile_ms", "kernelCompileMs"),
                             # total compiled-program launches this query
                             # (whole-stage dispatch evidence)
                             ("dispatches", "deviceDispatches")):
                m[dst] = round(cs1[src] - cache_stats0[src], 3)
            # resilience counters: faults injected, fetch retries, lost
            # blocks recomputed, peers blacklisted — per-query deltas of
            # the process-wide robustness stats
            rob1 = stats_snapshot()
            for k, v0 in rob0.items():
                m[k] = rob1[k] - v0
            # encoded-execution / prepack / device-decode engagement
            # deltas (a format's counters only when a scan of that format
            # ran, so in-memory queries don't carry two dozen zero keys;
            # then all of them, so a scan that declined nothing says
            # ``<fmt>DecodeFilesDeclined`` 0)
            if aux0 is not None:
                from ..io_.decode_stats import FORMATS
                aux1 = _aux_stats_snapshot()
                delta = {k: aux1.get(k, v0) - v0 for k, v0 in aux0.items()}
                scanned = tuple(k.split("Decode")[0]
                                for k, d in delta.items()
                                if d and k.endswith(("Engaged", "Declined")))
                for k, d in delta.items():
                    if not k.startswith(FORMATS) or k.startswith(scanned):
                        m[k] = d
        if not tracing:
            self.last_query_trace_summary = None
            # an older traced query's events must not be joined with THIS
            # query's plan by profile_last_query/export_chrome_trace
            self._last_trace_events = None
        else:
            from ..observability import report as OR
            from ..observability import tracer as OT
            tr = OT.get_tracer()
            self._last_trace_events = tr.snapshot()
            self._last_trace_meta = dict(tr.meta(), query=self._query_seq)
            self.last_query_trace_summary = OR.trace_summary(
                self._last_trace_events, tr.counters, tr.dropped_events)
            if ok:
                # a truncated ring can never silently skew doctor
                # attribution: the drop count and how full the ring got
                # ride every traced query's metrics
                self.last_query_metrics["traceDroppedEvents"] = \
                    tr.dropped_events
                self.last_query_metrics["traceRingHighWater"] = \
                    tr.high_water
            if sink and sink != "memory":
                from ..observability import export as OE
                try:
                    OE.write_event_log(
                        OE.event_log_path(sink, self._query_seq),
                        self._last_trace_events, self._last_trace_meta)
                except OSError:  # the sink must never fail the query
                    pass
        self._record_history(ok, duration_s, err)
        if metrics_on:
            from ..observability import metrics as OM
            status = "ok" if ok else "failed"
            OM.get_registry().observe("query_ms", duration_s * 1e3,
                                      status=status)
            OM.get_registry().inc("queries_total", status=status)

    def _record_history(self, ok: bool, duration_s: float,
                        err: Optional[BaseException]) -> None:
        """Land one flight-recorder record (must never fail the query)."""
        from ..config import HISTORY_ENABLED, HISTORY_MAX_QUERIES, \
            HISTORY_PATH
        if not bool(self._conf.get(HISTORY_ENABLED)):
            return
        try:
            from ..observability import history as OH
            if self._history is None:
                # shared per path: concurrent sessions configured with
                # one JSONL ring serialize their appends through a
                # single process-wide instance (docs/serving.md)
                self._history = OH.shared_history(
                    int(self._conf.get(HISTORY_MAX_QUERIES)),
                    str(self._conf.get(HISTORY_PATH) or ""))
            self._history.record(OH.build_record(
                query_id=self._query_seq, session_id=self.session_id,
                ok=ok, duration_ms=duration_s * 1e3,
                phys=getattr(self, "_last_phys", None) if ok else None,
                metrics=self.last_query_metrics if ok else None,
                trace_summary=self.last_query_trace_summary,
                error=f"{type(err).__name__}: {err}" if err else None,
                tenant=self.tenant))
        except Exception:
            pass

    def _execute_traced(self, logical: P.LogicalPlan, device_to_arrow,
                        speculation, conf: Optional[RapidsConf] = None
                        ) -> pa.Table:
        # conf defaults to the session's; the serving path passes a
        # pressure-degraded copy (lifecycle.PressureSignal) so a
        # saturated engine plans smaller without mutating session state
        conf = conf or self._conf
        planner = Planner(conf)
        from ..observability import tracer as OT

        def plan(attempt: int):
            with OT.span("plan", "physical", attempt=attempt):
                return planner.plan_for_collect(logical)
        phys = plan(0)
        # collect has no side effects, so speculative results may be
        # validated AFTER the fetch (zero extra pulls); a mis-speculation
        # recorded the corrected group-table size — re-plan and re-run.
        # Deferral is THREAD-local: under the pipelined execution layer
        # (task.parallelism > 1 / prefetch producer threads) work running
        # off this thread sees deferral OFF and takes the exact paths, so
        # the drain below only ever validates driver-thread speculation —
        # correctness never depends on cross-thread check handoff
        # (docs/async_pipeline.md).
        speculation.clear()
        try:
            oom_retried = False
            attempt = 0
            while True:
                # final attempt runs exact (deferral off) so the loop
                # always terminates with a validated result
                speculation.set_deferral(attempt < 2)
                try:
                    batches = phys.execute_all(conf)
                except Exception as e:
                    # with syncMode=auto a deferred execution-time OOM can
                    # surface at the D2H fetch, where the kernel guard
                    # cannot re-run the producing kernel.  Recovery is a
                    # whole-query retry: the guard already entered its
                    # defensive window (eager per-kernel sync), so the
                    # re-run lands any OOM inside the failing kernel's
                    # own spill-and-retry protocol.
                    from ..memory.oom_guard import is_device_oom
                    from ..memory.retry import RetryOOM, SplitAndRetryOOM
                    retriable = isinstance(e, (RetryOOM, SplitAndRetryOOM)) \
                        or is_device_oom(e)
                    if not retriable or oom_retried:
                        raise
                    oom_retried = True
                    from ..memory.spill import BufferCatalog
                    BufferCatalog.get().spill_all_device()
                    speculation.clear()
                    phys = plan(attempt)
                    continue
                checks = speculation.drain()
                bad = [c for c in checks if c.failed]
                if not bad or attempt >= 2:
                    break
                attempt += 1
                speculation._bump("mis_speculations", len(bad))
                speculation._bump("reruns")
                phys = plan(attempt)
        finally:
            speculation.set_deferral(False)
        from .physical.base import collect_metrics
        self.last_query_metrics = collect_metrics(phys)
        self._last_phys = phys
        self._last_logical = logical
        tables = [device_to_arrow(b) for b in batches if b.num_rows_int > 0]
        arrow_schema = pa.schema([
            pa.field(a.name, T.to_arrow(a.dtype)) for a in logical.output])
        if not tables:
            return arrow_schema.empty_table()
        out = pa.concat_tables([t.cast(arrow_schema) for t in tables])
        return out

    def physical_plan(self, df: DataFrame):
        return Planner(self._conf).plan_for_collect(df._plan)

    def profile_last_query(self) -> str:
        """Per-exec wall-time/batch profile of the most recent collect
        (requires spark.rapids.tpu.profile.enabled during execution).
        With the tracer on (profile.enabled implies it), the report also
        attributes blocking sync/readback time, kernel trace+compile
        time, and H2D/D2H bytes to each exec node."""
        phys = getattr(self, "_last_phys", None)
        if phys is None:
            return "no query executed yet"
        events = getattr(self, "_last_trace_events", None)
        if events:
            from ..observability.report import attribution_table
            meta = getattr(self, "_last_trace_meta", {})
            return attribution_table(phys, events,
                                     int(meta.get("dropped_events", 0)))
        from .physical.base import profile_report
        return profile_report(phys)

    def export_chrome_trace(self, path: str) -> str:
        """Write the last traced query's timeline as Chrome trace-event
        JSON (load in Perfetto / chrome://tracing).  Requires the query to
        have run with spark.rapids.tpu.trace.sink or profile.enabled."""
        events = getattr(self, "_last_trace_events", None)
        if not events:
            raise RuntimeError(
                "no traced query: set spark.rapids.tpu.trace.sink "
                "(or spark.rapids.tpu.profile.enabled) before collect()")
        from ..observability.export import write_chrome_trace
        return write_chrome_trace(path, events,
                                  getattr(self, "_last_trace_meta", None))

    def query_history(self, n: Optional[int] = None) -> List[dict]:
        """Flight-recorder records for this session's queries, oldest
        first (``spark.rapids.tpu.history.enabled``); ``n`` bounds the
        result to the newest n.  The ring may be SHARED (on-disk path /
        serving engine) — filtering by this session's id keeps the view
        per-session either way."""
        if self._history is None:
            return []
        return self._history.tail(n, session=self.session_id)

    def metrics_snapshot(self) -> dict:
        """JSON snapshot of the process-wide metrics registry (series
        recorded while ``spark.rapids.tpu.metrics.enabled`` queries
        ran) — counters, gauges, histograms with p50/p95/p99."""
        from ..observability.metrics import get_registry
        return get_registry().json_snapshot()

    def metrics_prometheus(self) -> str:
        """The metrics registry in Prometheus exposition text format."""
        from ..observability.metrics import get_registry
        return get_registry().prometheus_text()

    def diagnose_last_query(self) -> dict:
        """Ranked bottleneck diagnosis of the most recent traced query
        (observability/doctor.py): named verdict + supporting exec-level
        spans and counters.  Requires the query to have run with
        spark.rapids.tpu.trace.sink or profile.enabled."""
        events = getattr(self, "_last_trace_events", None)
        if not events:
            raise RuntimeError(
                "no traced query: set spark.rapids.tpu.trace.sink "
                "(or spark.rapids.tpu.profile.enabled) before collect()")
        from ..observability import doctor as OD
        meta = getattr(self, "_last_trace_meta", {})
        hist = self.query_history(1)
        wall = hist[-1]["duration_ms"] if hist else None
        return OD.diagnose(events, counters=meta.get("counters"),
                           metrics=self.last_query_metrics,
                           wall_ms=wall,
                           dropped_events=int(
                               meta.get("dropped_events", 0)))

    # --- telemetry plane (observability/server.py) --------------------
    def _start_telemetry(self) -> None:
        from ..config import TELEMETRY_PORT
        from ..observability import slo as OSLO
        from ..observability.server import TelemetryServer
        tracker = OSLO.configure(self._conf)
        self.telemetry = TelemetryServer(
            metrics_text=self.metrics_prometheus,
            healthz=self._telemetry_healthz,
            queries=self.query_history,
            doctor=self._telemetry_doctor,
            slo=lambda: tracker.report(),
            port=int(self._conf.get(TELEMETRY_PORT)))

    def close_telemetry(self) -> None:
        """Stop this session's embedded telemetry server (no-op when it
        never started); leak-free — the serve thread joins and the port
        rebinds."""
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None

    def _telemetry_healthz(self):
        """(healthy, payload) for a classic session: no engine, so no
        degraded state — liveness plus semaphore saturation."""
        from ..memory.semaphore import TpuSemaphore
        sem = TpuSemaphore.get()
        active = sem.active_tasks()
        return True, {
            "status": "ok", "session": self.session_id,
            "semaphore": {"active": active, "permits": sem.permits,
                          "saturation": round(
                              active / max(1, sem.permits), 4)},
        }

    def _telemetry_doctor(self):
        from ..observability import doctor as OD
        try:
            return {"last": OD.LAST_VERDICT,
                    "query": self.diagnose_last_query()}
        except RuntimeError as e:
            return {"last": OD.LAST_VERDICT, "note": str(e)}

    def explain(self, df: Optional[DataFrame] = None,
                all_ops: bool = True) -> str:
        """Placement report (spark.rapids.sql.explain=ALL equivalent) plus
        the physical tree — of ``df``, or of the most recently collected
        query when ``df`` is None."""
        from .overrides import TpuOverrides
        logical = df._plan if df is not None else getattr(
            self, "_last_logical", None)
        if logical is None:
            raise RuntimeError("no query has been collected yet")
        from .column_pruning import prune_columns
        from .join_order import order_joins
        # the placement report describes the plan that executes
        meta = TpuOverrides.apply(prune_columns(order_joins(logical)[0]),
                                  self._conf)
        from ..config import OPTIMIZER_ENABLED
        if bool(self._conf.get(OPTIMIZER_ENABLED)):
            # keep the placement report consistent with the physical plan
            from .optimizer import apply_cost_optimizer
            apply_cost_optimizer(meta, self._conf)
        try:
            phys_str = Planner(self._conf).plan_for_collect(
                logical).tree_string()
        except NotImplementedError as e:
            # diagnostics must not crash on unplannable queries (e.g.
            # unsupported DISTINCT shapes) — report the reason instead
            phys_str = f"<unplannable: {e}>"
        return (meta.explain(all_ops) + "\n\nPhysical plan:\n"
                + phys_str)


class DataFrameReader:
    def __init__(self, session: TpuSession):
        self._session = session
        self._options: Dict[str, Any] = {}
        self._schema: Optional[T.StructType] = None

    def option(self, key: str, value) -> "DataFrameReader":
        self._options[key] = value
        return self

    def options(self, **kwargs) -> "DataFrameReader":
        self._options.update(kwargs)
        return self

    def schema(self, s: T.StructType) -> "DataFrameReader":
        self._schema = s
        return self

    def _scan(self, fmt: str, paths) -> DataFrame:
        if isinstance(paths, str):
            paths = [paths]
        rel = P.ScanRelation(fmt, tuple(paths), self._schema,
                             dict(self._options))
        return DataFrame(rel, self._session)

    def parquet(self, *paths) -> DataFrame:
        return self._scan("parquet", list(paths))

    def orc(self, *paths) -> DataFrame:
        return self._scan("orc", list(paths))

    def csv(self, *paths) -> DataFrame:
        return self._scan("csv", list(paths))

    def json(self, *paths) -> DataFrame:
        return self._scan("json", list(paths))

    def avro(self, *paths) -> DataFrame:
        return self._scan("avro", list(paths))

    def format(self, fmt: str):
        reader = self

        class _F:
            def option(self_inner, key, value):
                reader._options[key] = value
                return self_inner

            def load(self_inner, *paths):
                if fmt == "delta":
                    from ..delta import DeltaTable
                    version = reader._options.get("versionAsOf")
                    ts = reader._options.get("timestampAsOf")
                    dt = DeltaTable.forPath(reader._session, paths[0])
                    return dt.toDF(
                        int(version) if version is not None else None,
                        timestamp_ms=_parse_ts_ms(ts, reader._session)
                        if ts is not None else None)
                if fmt == "iceberg":
                    from ..iceberg import IcebergTable
                    it = IcebergTable.for_path(reader._session, paths[0])
                    snap = reader._options.get("snapshot-id")
                    ts = reader._options.get("as-of-timestamp")
                    return it.to_df(
                        snapshot_id=int(snap) if snap is not None else None,
                        as_of_timestamp_ms=int(ts) if ts is not None
                        else None)
                return reader._scan(fmt, list(paths))
        return _F()


def _parse_ts_ms(ts, session=None) -> int:
    """timestampAsOf accepts epoch millis or 'YYYY-MM-DD[ HH:MM:SS]'
    strings.  Date strings parse in the SESSION timezone like Spark
    (spark.sql.session.timeZone), not hardcoded UTC."""
    if isinstance(ts, (int, float)):
        return int(ts)
    import datetime as _dt
    s = str(ts).strip()
    try:
        return int(s)
    except ValueError:
        pass
    tz = _dt.timezone.utc
    if session is not None:
        from ..config import SESSION_TIMEZONE
        name = str(session._conf.get(SESSION_TIMEZONE))
        if name and name.upper() != "UTC":
            from zoneinfo import ZoneInfo
            tz = ZoneInfo(name)
    for fmt in ("%Y-%m-%d %H:%M:%S", "%Y-%m-%d"):
        try:
            d = _dt.datetime.strptime(s, fmt)
            return int(d.replace(tzinfo=tz).timestamp() * 1000)
        except ValueError:
            continue
    raise ValueError(f"cannot parse timestampAsOf value {ts!r}")


def _to_arrow_table(data, schema) -> pa.Table:
    if isinstance(data, pa.Table):
        return data
    if isinstance(data, dict):
        return pa.table(data)
    try:
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            return pa.Table.from_pandas(data, preserve_index=False)
    except ImportError:  # pragma: no cover
        pass
    if isinstance(data, list):
        if schema is None:
            raise ValueError("schema required for list-of-rows input")
        if isinstance(schema, str):
            # DDL string 'name type, name type' (pyspark createDataFrame)
            from .dataframe import _to_struct_type
            schema = _to_struct_type(schema)
        if isinstance(schema, (list, tuple)):
            names = list(schema)
            cols = list(zip(*data)) if data else [[] for _ in names]
            return pa.table({n: list(c) for n, c in zip(names, cols)})
        arrow_schema = pa.schema([
            pa.field(f.name, T.to_arrow(f.data_type), f.nullable)
            for f in schema.fields])
        cols = list(zip(*data)) if data else [[] for _ in schema.fields]
        arrays = [pa.array(list(c), type=fldt.type)
                  for c, fldt in zip(cols, arrow_schema)]
        return pa.Table.from_arrays(arrays, schema=arrow_schema)
    raise TypeError(f"cannot create DataFrame from {type(data)}")


def _share_dictionaries(table: pa.Table, per: int) -> pa.Table:
    """String columns of few distinct values, dictionary-encoded once for
    the whole table before it is cut: every partition's codes then refer
    to ONE dictionary (``columnar/encoded.py`` keeps an Arrow dictionary
    as it comes), so pieces of different partitions concatenate as codes
    inside one program (``ColumnarBatch.concat``: a broadcast build, an
    exchange's read) instead of being unified on the host and joined
    array by array.  The rule is the scan's own (``_cardinality_ok`` at a
    partition's rows); a column over it is left for each partition to
    decide, as before."""
    import pyarrow.compute as pc
    from ..columnar import encoded as E
    if not E.enabled() or per <= 0:
        return table
    limit = E._max_cardinality()
    for i, field in enumerate(table.schema):
        if not (pa.types.is_string(field.type)
                or pa.types.is_large_string(field.type)):
            continue
        column = table.column(i)
        # a first look at a prefix: a long text column is not encoded whole
        head = column.slice(0, 1 << 16)
        if pc.count_distinct(head).as_py() > limit:
            continue
        encoded = pc.dictionary_encode(column.combine_chunks())
        if E._cardinality_ok(len(encoded.dictionary), per, limit):
            table = table.set_column(i, field.name, encoded)
    return table


def _split_table(table: pa.Table, n: int) -> List[pa.Table]:
    n = max(1, n)
    rows = table.num_rows
    per = -(-rows // n) if rows else 0
    if n > 1:
        table = _share_dictionaries(table, per)
    parts = []
    for i in range(n):
        lo = min(i * per, rows)
        hi = min(lo + per, rows)
        parts.append(table.slice(lo, hi - lo))
    return parts


#: (id(table) -> (weakref(table), {n: [slices]})) — slice identity dedupe
#: (see create_dataframe).  Entries die with their table; slices are
#: zero-copy views, so retaining them costs metadata only.
_SPLIT_CACHE: dict = {}
_SPLIT_LOCK = threading.Lock()


def _split_table_cached(table: pa.Table, n: int) -> List[pa.Table]:
    import weakref
    key = id(table)
    with _SPLIT_LOCK:
        ent = _SPLIT_CACHE.get(key)
        if ent is None or ent[0]() is not table:
            ref = weakref.ref(
                table, lambda _r, k=key: _SPLIT_CACHE.pop(k, None))
            ent = (ref, {})
            _SPLIT_CACHE[key] = ent
        parts = ent[1].get(n)
        if parts is None:
            parts = ent[1][n] = _split_table(table, n)
        return parts


class Catalog:
    """Minimal pyspark-Catalog surface over the session's temp views."""

    def __init__(self, session: TpuSession):
        self._session = session

    def listTables(self) -> List[str]:
        return sorted(self._session._temp_views)

    def tableExists(self, name: str) -> bool:
        return name.lower() in self._session._temp_views

    def dropTempView(self, name: str) -> bool:
        return self._session._temp_views.pop(name.lower(), None) is not None
