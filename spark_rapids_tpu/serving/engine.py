"""ServingEngine — N concurrent tenant sessions against one engine
process (docs/serving.md, ROADMAP item 1).

The engine owns everything that is PROCESS-scoped under concurrency and
was previously armed per query by a single driver:

* **flags** — tracing/profiling/metrics switches flip ONCE for the
  engine's lifetime (save/restore around ``close()``); per-query
  identity rides thread-local labels (metrics registry) and per-event
  ``tenant``/``sid`` stamps (tracer) instead of global per-query resets.
* **chaos arming** — a chaos-confed engine arms the seeded fault
  registry once; serving sessions skip the per-query snapshot/restore
  dance that would race across driver threads.
* **admission** — one :class:`AdmissionController` gates every session's
  collects with weighted-fair scheduling and per-tenant memory budgets.
* **history** — one shared flight recorder; every record stamps
  ``tenant`` + ``session`` so ``sess.query_history()`` filters per
  session and ``engine.query_history()`` sees the whole fleet.
* **sharing tiers** — the process-scoped kernel cache and learned
  selectivities already hit across sessions (kernel_cache.py); the
  engine additionally sizes/enables the result cache and the shared
  broadcast cache from its conf.

Sessions handed out by :meth:`session` are ordinary
:class:`~spark_rapids_tpu.sql.session.TpuSession` objects in serving
mode: one session per submitting thread (a session's per-query state —
``last_query_metrics``, ``_last_phys`` — is not itself thread-safe).
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, List, Optional

from ..config import RapidsConf
from .admission import AdmissionController


class ServingEngine:
    """One per process (several can exist for tests, but they share the
    process-scoped caches and flags — last close wins the restore)."""

    def __init__(self, conf: Optional[RapidsConf] = None, **conf_kwargs):
        from ..config import (METRICS_ENABLED, METRICS_MAX_SERIES,
                              PROFILE_ENABLED,
                              SERVING_BROADCAST_SHARE_MAX_BYTES,
                              SERVING_RESULT_CACHE_ENABLED,
                              SERVING_RESULT_CACHE_MAX_BYTES,
                              TRACE_BUFFER_EVENTS, TRACE_ENABLED,
                              TRACE_SINK)
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        from ..robustness import faults as _faults
        from ..sql.physical.base import PROFILING
        from . import broadcast_cache as BC
        from . import result_cache as RC
        base = conf or RapidsConf.get_global()
        self._conf = base.copy(conf_kwargs or None)
        self.engine_id = f"engine-{os.getpid()}-{id(self) & 0xFFFF:04x}"
        self.admission = AdmissionController.from_conf(self._conf)
        # --- query lifecycle (serving/lifecycle.py) ---------------------
        from ..config import DEGRADED_PROBE_INTERVAL_MS
        from . import lifecycle as _lc
        #: pressure-aware plan degradation (kill-switched)
        self.pressure = _lc.PressureSignal(self._conf)
        #: plan fingerprints that produced a FatalDeviceError (TTL'd)
        self.quarantine = _lc.QuarantineRegistry.from_conf(self._conf)
        #: degraded-engine state: reason string while degraded, None
        #: when healthy; new admissions are refused until a probe query
        #: succeeds (EngineDegraded)
        self._degraded: Optional[str] = None
        self._probe_interval_s = max(
            0.0, int(self._conf.get(DEGRADED_PROBE_INTERVAL_MS)) / 1e3)
        self._next_probe = 0.0
        # tenant-aware spill: the admission memory budgets double as the
        # catalog's eviction-priority budgets (over-budget tenants'
        # batches spill first, memory/spill.py)
        from ..memory.spill import BufferCatalog
        BufferCatalog.get().set_tenant_budgets(
            dict(self.admission.budgets), self.admission.default_budget)
        self.result_cache_enabled = bool(
            self._conf.get(SERVING_RESULT_CACHE_ENABLED))
        RC.set_max_bytes(int(self._conf.get(SERVING_RESULT_CACHE_MAX_BYTES)))
        BC.set_max_bytes(int(
            self._conf.get(SERVING_BROADCAST_SHARE_MAX_BYTES)))
        self._closed = False
        self._lock = threading.Lock()
        self._sessions: List[Any] = []
        # shared flight recorder: one ring (and one on-disk lock) for all
        # tenant sessions; records stamp tenant + session for filtering
        from ..config import HISTORY_MAX_QUERIES, HISTORY_PATH
        from ..observability import history as OH
        self.history = OH.shared_history(
            int(self._conf.get(HISTORY_MAX_QUERIES)),
            str(self._conf.get(HISTORY_PATH) or ""))
        # --- engine-scoped flag arming (save/restore in close()) ---------
        self._prev_flags = (PROFILING["on"], dict(OT.TRACING),
                            OM.METRICS["on"])
        self._prev_chaos = _faults.snapshot_arming()
        _faults.apply_conf(self._conf)
        profiling = bool(self._conf.get(PROFILE_ENABLED))
        sink = str(self._conf.get(TRACE_SINK) or "").strip()
        self._tracing = profiling or bool(sink)
        metrics_on = bool(self._conf.get(METRICS_ENABLED))
        if metrics_on:
            reg = OM.get_registry()
            reg.max_series = int(self._conf.get(METRICS_MAX_SERIES))
        if self._tracing:
            OT.get_tracer().reset(int(self._conf.get(TRACE_BUFFER_EVENTS)),
                                  session=self.engine_id)
        PROFILING["on"] = profiling or self._tracing
        OT.TRACING["on"] = self._tracing
        OT.TRACING["profiler"] = bool(self._conf.get(TRACE_ENABLED))
        OM.METRICS["on"] = metrics_on
        # --- telemetry plane (observability/server.py + slo.py) ----------
        # SLO objectives always get a tracker (cheap; /slo and the
        # slo-burn doctor read it), and the admission controller gets the
        # hook point it may consult in a later PR
        from ..observability import slo as OSLO
        self.slo = OSLO.configure(self._conf)
        self.admission.slo_hook = self.slo.admission_hint
        self.telemetry = None
        from ..config import TELEMETRY_ENABLED, TELEMETRY_PORT
        if bool(self._conf.get(TELEMETRY_ENABLED)):
            from ..observability.server import TelemetryServer
            self.telemetry = TelemetryServer(
                metrics_text=self.metrics_prometheus,
                healthz=self._healthz,
                queries=self.query_history,
                doctor=self._doctor_payload,
                slo=lambda: self.slo.report(),
                port=int(self._conf.get(TELEMETRY_PORT)))

    # --- sessions -----------------------------------------------------------
    def session(self, tenant: str = "default", **conf_overrides):
        """A serving-mode session bound to ``tenant``.  Use one session
        per submitting thread; sessions are cheap (they share every
        process-scoped cache)."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        from ..config import SERVING_TENANT, TELEMETRY_ENABLED
        from ..sql.session import TpuSession
        overrides = dict(conf_overrides)
        overrides[SERVING_TENANT.key] = tenant
        # the engine owns the one telemetry server; tenant sessions must
        # not each spin their own off the inherited engine conf
        overrides.setdefault(TELEMETRY_ENABLED.key, False)
        sess = TpuSession(self._conf.copy(overrides))
        sess._serving = self
        sess._history = self.history
        with self._lock:
            self._sessions.append(sess)
        return sess

    # --- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        """Restore the process flags and chaos arming this engine set.
        Sessions keep working afterwards as plain single-driver sessions
        (their ``_serving`` ref is cleared)."""
        if self._closed:
            return
        self._closed = True
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        from ..robustness import faults as _faults
        from ..sql.physical.base import PROFILING
        if self.telemetry is not None:
            self.telemetry.close()
            self.telemetry = None
        with self._lock:
            for s in self._sessions:
                s._serving = None
        PROFILING["on"], prev_trace, OM.METRICS["on"] = self._prev_flags
        OT.TRACING.update(prev_trace)
        _faults.restore_arming(self._prev_chaos)

    def __enter__(self) -> "ServingEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # --- query lifecycle ----------------------------------------------------
    def cancel_tenant(self, tenant: str,
                      reason: str = "tenant cancelled") -> int:
        """Cooperatively cancel every live query of ``tenant`` across
        all this engine's sessions (admission waiters included); each
        raises :class:`QueryCancelled` within the poll bound.  Returns
        how many queries were cancelled."""
        from . import lifecycle as _lc
        return _lc.cancel_tenant(tenant, reason)

    def is_degraded(self) -> bool:
        return self._degraded is not None

    def note_fatal(self, exc: BaseException, fingerprint: str,
                   tenant: str = "") -> None:
        """A serving query died with a fatal device error: quarantine
        its plan fingerprint (bounded TTL) and mark the engine degraded
        so new admissions are refused until a probe succeeds.  Only the
        offending query fails — in-flight siblings run to completion."""
        from . import lifecycle as _lc
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        if fingerprint:
            self.quarantine.add(fingerprint)
        self._degraded = (f"fatal device error in tenant "
                          f"{tenant or 'unknown'}: {exc}")
        self._next_probe = 0.0  # first probe attempt is immediate
        _lc.STATS["degraded_marks"] += 1
        OM.inc("engine_degraded_total",
               **({"tenant": tenant} if tenant else {}))
        if OT.TRACING["on"]:
            import time as _t
            OT.get_tracer().complete(
                "fatal", "engine.degraded", _t.perf_counter(), 0.0,
                **({"tenant": tenant} if tenant else {}))

    def check_admittable(self, fingerprint: str = "") -> None:
        """Refuse quarantined plans and — while degraded — everything
        until a probe query proves the device answers again.  Raises
        :class:`QueryQuarantined` / :class:`EngineDegraded`."""
        from . import lifecycle as _lc
        if self._degraded is not None and not self._probe():
            raise _lc.EngineDegraded(
                f"engine refusing admissions while degraded "
                f"({self._degraded}); next probe in "
                f"<= {self._probe_interval_s:.1f}s")
        if fingerprint and self.quarantine.quarantined(fingerprint):
            raise _lc.QueryQuarantined(
                f"plan fingerprint {fingerprint[:16]}... is quarantined "
                f"after a fatal device error (TTL "
                f"{self.quarantine.ttl_s:.0f}s); retrying it now would "
                f"likely re-kill the device")

    def _probe(self) -> bool:
        """One throttled device probe: a trivial compiled computation
        must round-trip.  Success clears the degraded mark (and traces
        ``probe``); failure re-arms the probe interval."""
        import time as _t
        from . import lifecycle as _lc
        from ..observability import metrics as OM
        from ..observability import tracer as OT
        with self._lock:
            if self._degraded is None:
                return True
            now = _t.monotonic()
            if now < self._next_probe:
                return False
            self._next_probe = now + self._probe_interval_s
        t0 = _t.perf_counter()
        try:
            import jax
            import jax.numpy as jnp
            got = jax.device_get(jnp.add(jnp.int32(20), jnp.int32(22)))
            ok = int(got) == 42
        except Exception:
            ok = False
        if ok:
            with self._lock:
                self._degraded = None
            _lc.STATS["probe_recoveries"] += 1
            OM.inc("engine_probe_recoveries_total")
        if OT.TRACING["on"]:
            OT.get_tracer().complete(
                "fatal", "engine.probe", t0, _t.perf_counter() - t0,
                ok=ok)
        return ok

    # --- fleet observability ------------------------------------------------
    def query_history(self, n: Optional[int] = None,
                      tenant: Optional[str] = None) -> List[dict]:
        """Flight-recorder records across ALL tenant sessions (newest
        last); ``tenant`` filters to one tenant."""
        return self.history.tail(n, tenant=tenant)

    def diagnose_tenants(self) -> Dict[str, Any]:
        """Per-tenant bottleneck verdicts over the engine's recorded
        queries (observability/doctor.py): admission-wait joins the
        ranking, so a starved tenant reads ``admission-bound``."""
        from ..observability import doctor as OD
        return OD.diagnose_tenants(self.history.tail())

    def admission_stats(self) -> Dict[str, Any]:
        return self.admission.snapshot()

    def slo_report(self) -> Dict[str, Any]:
        """Per-tenant multi-window SLO burn rates (observability/slo.py)."""
        return self.slo.report()

    # --- telemetry-server sources -------------------------------------------
    def _healthz(self):
        """(healthy, payload) for the /healthz route: degraded state,
        quarantine size, admission queue depth and device-semaphore
        saturation — a load balancer drains on the 503 alone."""
        from ..memory.semaphore import TpuSemaphore
        adm = self.admission.snapshot()
        sem = TpuSemaphore.get()
        active = sem.active_tasks()
        degraded = self.is_degraded()
        payload = {
            "status": "degraded" if degraded else "ok",
            "engine": self.engine_id,
            "degraded_reason": self._degraded,
            "quarantine_entries": self.quarantine.size(),
            "admission": {"queued": adm.get("queued", 0),
                          "running": adm.get("running", 0),
                          "max_concurrent": adm.get("max_concurrent", 0)},
            "semaphore": {"active": active, "permits": sem.permits,
                          "saturation": round(
                              active / max(1, sem.permits), 4)},
        }
        # peer liveness (pod-scale fault domain): surfaced only when a
        # shuffle manager is live — building one from /healthz would
        # side-effect the engine's shuffle topology
        from ..shuffle.manager import _global_manager
        if _global_manager is not None:
            try:
                live = _global_manager.peer_liveness()
                payload["peers"] = {
                    "alive": len(live.get("alive", ())),
                    "suspect": list(live.get("suspect", ())),
                    "dead": list(live.get("dead", ())),
                    "epoch": live.get("epoch", 0),
                    "detector_armed": bool(live.get("armed", False)),
                }
            except Exception:  # noqa: BLE001 — liveness is advisory;
                pass           # /healthz must never 500 on it
        return (not degraded), payload

    def _doctor_payload(self) -> Dict[str, Any]:
        """Last ranked verdicts for the /doctor route: the most recent
        per-query diagnosis, the per-tenant fleet view, and the SLO burn
        verdict (which names any burning tenant)."""
        from ..observability import doctor as OD
        tenants = self.diagnose_tenants()
        return {"last": OD.LAST_VERDICT,
                "tenants": tenants,
                "slo": self.slo.doctor_verdict(
                    tenant_diagnoses=tenants)}

    def metrics_snapshot(self) -> dict:
        from ..observability.metrics import get_registry
        return get_registry().json_snapshot()

    def metrics_prometheus(self) -> str:
        from ..observability.metrics import get_registry
        return get_registry().prometheus_text()

    def export_chrome_trace(self, path: str) -> str:
        """Write the ENGINE-scoped trace ring (all sessions' spans, each
        stamped with tenant + sid) as Chrome trace-event JSON."""
        if not self._tracing:
            raise RuntimeError(
                "engine tracing off: set spark.rapids.tpu.trace.sink or "
                "spark.rapids.tpu.profile.enabled on the engine conf")
        from ..observability import export as OE
        from ..observability import tracer as OT
        tr = OT.get_tracer()
        return OE.write_chrome_trace(path, tr.snapshot(), tr.meta())

    def cache_stats(self) -> Dict[str, Any]:
        """One snapshot of every cross-query sharing tier."""
        from ..sql.physical.kernel_cache import cache_stats
        from . import broadcast_cache as BC
        from . import result_cache as RC
        return {"kernel": cache_stats(), "result": RC.stats(),
                "broadcast": BC.stats()}
