"""Structured query-timeline tracer: one span primitive, two sinks.

:func:`span` is the only way the program marks host work.  It feeds

* the **profiler** sink (``TRACING["profiler"]``, armed per query from
  ``spark.rapids.tpu.trace.enabled``): the span is a
  ``jax.profiler.TraceAnnotation`` named ``srt:<cat>:<name>`` carrying the
  span's args and ``query=<id>``.  It lies on the profiler's clock, on the
  thread that did the work, beside the device plane of a
  ``jax.profiler.trace``; nothing is written to the ring and no lock is
  taken.  While no profiler session is open an annotation costs well
  under a microsecond.  A span never syncs the device.
* the **ring** sink (``TRACING["on"]``, armed per query from
  ``spark.rapids.tpu.trace.sink`` / ``profile.enabled``): one process-wide
  :class:`QueryTracer` holds a thread-safe bounded ring buffer of events
  (Chrome export, JSONL event log, doctor, ``/metrics``).

Both off, :func:`span` returns the shared null object: the cost is the
flag lookups, nothing else.  :meth:`QueryTracer.complete` (a retroactive
span from a measured ``t0``/duration) can only feed the ring; it remains
for the sites off the query's own path (shuffle transport and
serialization, serving admission, lifecycle, faults), which a profiler
trace therefore does not show.  The waits ON the query's path (the device
semaphore, the prefetch consumer) are spans.

Event categories:

=================  =========================================================
``query``          one collect, root of everything below (``query``,
                   ``session`` args; sql/session.py)
``plan``           ``parse`` (SQL text -> logical) and ``physical`` (every
                   ``plan_for_collect``, re-plans included)
``task``           one partition's task (``<Exec>:task<n>``; base.py)
``op``             exec-node batch production (and join pipeline stages)
``dispatch``       one launch of a kernel-cache program, by program name;
                   ``retraced=1`` when that launch re-traced (a new input
                   signature of a wrapper that had run before)
``compile``        the launch of a program its jit wrapper has not run
                   before (trace + lower + compile or cache load)
``eager``          a block of host work that launches programs past the
                   kernel cache one by one (eager ``jnp`` on batches, a
                   decoder's own ``jax.jit``), ``exec=`` the exec it ran
                   for: ``batch.repadded|shrunk|sliced|concat|empty``
                   (columnar/batch.py), ``top_n.merge``
                   (``TakeOrderedAndProject``),
                   ``encoded.dict_materialize|rle_materialize``
                   (columnar/encoded.py), ``parquet.decode_column``
                   (io_/device_parquet.py); never one span a launch
``scan``           file scans: ``footer`` (open + prune), ``device_decode``
                   and ``host_decode`` (pyarrow) per row-group run; inside
                   ``device_decode``, per column, ``chunk_read`` (the
                   chunk's compressed bytes off the file, ``bytes=``) and
                   ``pages`` (page headers, decompression, the hybrid run
                   walk, dictionary union: ``pages=``, ``bytes=``,
                   ``out_bytes=``)
``sync``           blocking scalar readbacks: ``join.readback`` (join
                   sizing), ``agg.group_count`` (the aggregate waits for
                   the program it launched to learn its group count),
                   ``batch.num_rows`` (``ColumnarBatch.num_rows_int`` on a
                   memo miss; counted as ``syncReadbacks``)
``h2d``            host -> device uploads (arrow decode, transitions)
``d2h``            device -> host fetches (bulk/prepacked device_get)
``spill``          spill-catalog tier movement
``shuffle``        exchange materialization (``exchange.materialize`` and,
                   inside it per map, ``exchange.partition_ids`` /
                   ``.split`` / ``.write``, per reduce partition
                   ``exchange.read``; ``mesh_exchange``) + frame
                   (de)serialization
``sem_wait``       device-semaphore acquisition waits
``fault``          chaos fault injections, shuffle fetch retries, peer
                   blacklisting, lost-block recompute (robustness/)
``queue``          async-prefetch queue waits (consumer blocked on the
                   bounded prefetch queue; sql/physical/async_exec.py)
``encode``         encoded-column lifecycle: scan-side dictionary encode
                   and decline-site materializations (columnar/encoded.py)
``admission``      serving admission waits; ``cancel`` drain latency of a
                   cancelled query; ``fatal`` device-fatal quarantine
``sort``           ``compute``: one launch of a sort exec's program over
                   one batch (sql/physical/sortlimit.py); ``range_bounds``:
                   a range exchange sampling its map outputs, sorting the
                   sample and picking the boundary rows (exchange.py)
``window``         ``compute``: one launch of a window exec's program over
                   one key-complete batch (sql/physical/window.py)
``stage``          whole-stage program execution: one span per fused-stage
                   batch (map-chain program call or terminal-stage batch
                   production; sql/physical/fusion.py)
=================  =========================================================

Spans attribute to the *owning exec node* via a thread-local exec stack:
the profiled ``execute`` wrapper (base.py) pushes each node's name around
its own batch production, so the innermost executing exec is always on
top — a ``d2h`` fetch fired while ``DeviceToHost`` pulls a batch lands on
``DeviceToHost`` even though outer nodes are also mid-pull.  The stack
composes with :meth:`TaskContext.as_current` nesting (exchange map-side
tasks): pushes/pops are strictly scoped, so a nested task restores the
outer attribution on exit.

Concurrency model: the tracer is PROCESS-wide, like the reference's
per-executor GpuMetric sinks.  The engine runs a single driver per
process (sessions execute queries serially on the calling thread; only
the shuffle/IO pools fan out, and those belong to the one running query),
so per-query reset-and-snapshot from the session is sound.  Two sessions
collecting *concurrently* from different threads would interleave events
— that configuration is unsupported for tracing, documented in
docs/observability.md.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from . import metrics as _metrics

#: the two sink switches — ``on`` arms the ring, ``profiler`` the
#: profiler annotations.  Flipped per query by the session (restored in a
#: ``finally``, so an exception mid-query cannot leak tracing into the
#: next session's query).  Near-zero overhead when off.
TRACING = {"on": False, "profiler": False}

#: known span categories (exported traces may add more; the checker and
#: the report treat unknown categories as opaque)
CATEGORIES = ("query", "plan", "task", "op", "stage", "dispatch", "compile",
              "eager", "scan", "sync", "h2d", "d2h", "spill", "shuffle",
              "sem_wait", "fault", "queue", "encode", "admission", "cancel",
              "fatal", "broadcast", "join", "sort", "window")

#: every profiler annotation's name starts with this
PROFILER_PREFIX = "srt:"

#: default ring capacity (spark.rapids.tpu.trace.bufferEvents)
DEFAULT_CAPACITY = 65536


# --------------------------------------------------------------------------
# exec-node attribution stack (thread-local)
# --------------------------------------------------------------------------

_tls = threading.local()


def _stack() -> List[str]:
    s = getattr(_tls, "exec_stack", None)
    if s is None:
        s = _tls.exec_stack = []
    return s


def push_exec(name: str) -> None:
    """Mark ``name`` as the exec producing batches on this thread."""
    _stack().append(name)


def pop_exec() -> None:
    s = _stack()
    if s:
        s.pop()


def current_exec() -> str:
    """Innermost exec node executing on this thread ('' outside a plan —
    e.g. the driver's final result fetch)."""
    s = _stack()
    return s[-1] if s else ""


def set_thread_context(tenant: str = "", sid: str = "") -> None:
    """Stamp ``tenant`` (and an overriding ``sid``) on spans emitted from
    THIS thread — the serving tier's per-admitted-query attribution: the
    tracer ring is engine-scoped under concurrent sessions (one reset for
    the engine's lifetime), so per-query identity rides the events
    instead of the ring's single session label.  Spans from pool/prefetch
    helper threads keep the engine-scope label only (docs/serving.md)."""
    _tls.tenant = tenant
    _tls.sid = sid


def clear_thread_context() -> None:
    _tls.tenant = ""
    _tls.sid = ""


def thread_tenant() -> str:
    return getattr(_tls, "tenant", "")


# --------------------------------------------------------------------------
# distributed trace context (cross-process stitching)
# --------------------------------------------------------------------------

_SPAN_SEQ = itertools.count(1)


def next_span_id() -> str:
    """Process-unique span id stamped on wire-crossing spans (shuffle
    remote fetch/serve, frame serialization) so tools/trace_merge.py can
    connect the two sides of a cross-process edge with a flow event."""
    return f"{os.getpid():x}.{next(_SPAN_SEQ)}"


def current_trace_context() -> Optional[Dict[str, Any]]:
    """Trace context of the query running on THIS thread:
    ``{trace, query, tenant}``.  Derived from the installed lifecycle
    token (valid on shuffle reader-pool threads too — the manager
    reinstalls the query context there), falling back to the tracer's
    session label for untracked callers.  None when tracing is off."""
    if not TRACING["on"]:
        return None
    q = _lifecycle().current()
    if q is not None:
        return {"trace": f"{q.session_id}:q{q.query_id}",
                "query": q.query_id,
                "tenant": q.tenant or thread_tenant()}
    sid = getattr(_tls, "sid", "") or _TRACER.session_label
    return {"trace": sid or f"pid-{os.getpid()}", "query": 0,
            "tenant": thread_tenant()}


_LIFECYCLE = None


def _lifecycle():
    """serving/lifecycle.py, imported on first use (it imports this
    module) and kept: a span on the profiler's clock asks it for the
    running query on every entry."""
    global _LIFECYCLE
    if _LIFECYCLE is None:
        from ..serving import lifecycle
        _LIFECYCLE = lifecycle
    return _LIFECYCLE


def set_fetch_trace(ctx: Optional[Dict[str, Any]]) -> None:
    """Install the trace context the transport should propagate on the
    next shuffle fetch from THIS thread (shuffle/manager.py sets it
    around ``transport.fetch``; shuffle/tcp.py reads it).  Riding a
    thread-local keeps the ShuffleTransport SPI ``fetch(peer, block)``
    signature unchanged, so duck-typed test transports keep working."""
    _tls.fetch_trace = ctx


def fetch_trace() -> Optional[Dict[str, Any]]:
    return getattr(_tls, "fetch_trace", None)


# --------------------------------------------------------------------------
# the tracer
# --------------------------------------------------------------------------

class QueryTracer:
    """Bounded ring buffer of trace events (newest kept on overflow, with
    a ``dropped_events`` counter) plus aggregate named counters."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=max(16, int(capacity)))
        self.dropped_events = 0
        #: high-water value last pushed to the metrics registry gauge —
        #: the feed is strided (every 1024 events) so the scrape surface
        #: sees ring fill without one registry write per span
        self._hw_reported = 0
        #: most events the ring ever held this query — with
        #: dropped_events, the evidence that a truncated trace cannot
        #: silently skew doctor attribution (high_water == capacity and
        #: dropped > 0 means the window was too small)
        self.high_water = 0
        #: stable session label stamped on every event (``sid``) — set by
        #: the session at query start, groundwork for per-tenant metrics
        self.session_label = ""
        self._epoch = time.perf_counter()
        self._epoch_wall = time.time()
        self.counters: Dict[str, float] = {}

    # --- lifecycle --------------------------------------------------------
    def reset(self, capacity: Optional[int] = None,
              session: Optional[str] = None) -> None:
        """Start a fresh timeline (called by the session at query start)."""
        with self._lock:
            if capacity is not None and \
                    int(capacity) != self._events.maxlen:
                self._events = deque(maxlen=max(16, int(capacity)))
            else:
                self._events.clear()
            self.dropped_events = 0
            self.high_water = 0
            self._hw_reported = 0
            if session is not None:
                self.session_label = str(session)
            self.counters = {}
            self._epoch = time.perf_counter()
            self._epoch_wall = time.time()
        if _metrics.METRICS["on"]:
            _metrics.get_registry().set_gauge("trace_ring_high_water", 0)

    @property
    def capacity(self) -> int:
        return self._events.maxlen or 0

    # --- emission ---------------------------------------------------------
    def complete(self, cat: str, name: str, t0: float, dur_s: float,
                 exec_: Optional[str] = None, **args: Any) -> None:
        """Record a retroactive complete span: ``t0`` is the
        ``time.perf_counter()`` at span start, ``dur_s`` its duration in
        seconds.  ``exec_`` defaults to the thread's current exec node."""
        ev: Dict[str, Any] = {
            "cat": cat, "name": name,
            "ts": (t0 - self._epoch) * 1e6,          # µs from trace epoch
            "dur": max(dur_s, 0.0) * 1e6,
            "tid": threading.get_ident(),
            "exec": current_exec() if exec_ is None else exec_,
        }
        tsid = getattr(_tls, "sid", "")
        if tsid or self.session_label:
            ev["sid"] = tsid or self.session_label
        tenant = getattr(_tls, "tenant", "")
        if tenant:
            ev["tenant"] = tenant
        if args:
            ev["args"] = args
        with self._lock:
            dropped = len(self._events) == self._events.maxlen
            if dropped:
                self.dropped_events += 1
            self._events.append(ev)
            if len(self._events) > self.high_water:
                self.high_water = len(self._events)
            report_hw = 0
            if self._hw_reported == 0 \
                    or self.high_water >= self._hw_reported + 1024 \
                    or (dropped and self._hw_reported < self.high_water):
                # a full ring always reports at the true high-water:
                # the scraper must see "at capacity" the moment events
                # start dropping, not a stride later
                report_hw = self._hw_reported = self.high_water
        # registry feed: per-category latency distribution, exec-labeled
        # (one dict lookup when the registry is off); ring health rides
        # along so a scrape sees trace truncation without a query
        # epilogue (gauge strided; the drop counter is exact)
        if _metrics.METRICS["on"]:
            reg = _metrics.get_registry()
            reg.observe("trace_span_ms", max(dur_s, 0.0) * 1e3,
                        cat=cat, exec=ev["exec"] or "(driver)")
            if dropped:
                reg.inc("trace_dropped_events_total")
            if report_hw:
                reg.set_gauge("trace_ring_high_water", report_hw)

    def counter(self, name: str, value: float = 1.0) -> None:
        """Accumulate a named aggregate counter (no per-event storage)."""
        with self._lock:
            self.counters[name] = self.counters.get(name, 0.0) + value

    # --- readout ----------------------------------------------------------
    def snapshot(self) -> List[Dict[str, Any]]:
        """Events oldest-first (a copy; safe to hold across resets)."""
        with self._lock:
            return list(self._events)

    def meta(self) -> Dict[str, Any]:
        """Trace metadata for exports: wall-clock epoch + drop stats."""
        import os
        with self._lock:
            out = {"epoch_unix_s": self._epoch_wall,
                   "pid": os.getpid(),
                   "capacity": self._events.maxlen,
                   "dropped_events": self.dropped_events,
                   "ring_high_water": self.high_water,
                   "counters": dict(self.counters)}
            if self.session_label:
                out["session_id"] = self.session_label
            return out


_TRACER = QueryTracer()


def get_tracer() -> QueryTracer:
    return _TRACER


# --------------------------------------------------------------------------
# span context manager (null-object when disabled)
# --------------------------------------------------------------------------

class _NullSpan:
    """Shared no-op span — the disabled-path cost is one flag lookup."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **args):
        pass


_NULL_SPAN = _NullSpan()


_ANNOTATION = None


def _annotation(cat: str, name: str, args: Dict[str, Any]):
    """The span as a ``jax.profiler.TraceAnnotation``.  ``query=`` comes
    from the lifecycle token installed on THIS thread (pool and prefetch
    threads reinstall the driver's), so one request carries one id on
    every thread; the root span passes its own."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    if "query" not in args:
        q = _lifecycle().current()
        if q is not None:
            args["query"] = q.query_id
    return _ANNOTATION(f"{PROFILER_PREFIX}{cat}:{name}", **args)


class _Span:
    """A ring span, and the profiler's annotation around it when both
    sinks are armed."""
    __slots__ = ("cat", "name", "args", "t0", "ann")

    def __init__(self, cat: str, name: str, args: Dict[str, Any],
                 profiler: bool):
        self.cat, self.name, self.args = cat, name, args
        self.ann = _annotation(cat, name, dict(args)) if profiler else None

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def set_metadata(self, **args):
        """Args known only once the work is done (bytes fetched); same
        call as ``TraceAnnotation.set_metadata``, inside the ``with``."""
        self.args.update(args)
        if self.ann is not None:
            self.ann.set_metadata(**args)

    def __exit__(self, exc_type, exc, tb):
        # a pull that ends an iterator produced nothing: not an event
        if exc_type is not StopIteration:
            _TRACER.complete(self.cat, self.name, self.t0,
                             time.perf_counter() - self.t0, **self.args)
        if self.ann is not None:
            self.ann.__exit__(exc_type, exc, tb)
        return False


def span(cat: str, name: str, **args: Any):
    """Context manager marking host work for whichever sinks are armed
    (module docstring); the shared null object when neither is.  Callers
    computing *expensive* span args should guard on the flags themselves.
    Never hold a span open across a ``yield``: it would cover the
    consumer's work."""
    if TRACING["on"]:
        return _Span(cat, name, args, TRACING["profiler"])
    if TRACING["profiler"]:
        return _annotation(cat, name, args)
    return _NULL_SPAN


def eager(site: str, **args: Any):
    """:func:`span` of category ``eager`` over one BLOCK of launches past
    the kernel cache, stamped with the exec it runs for (``exec=``); the
    shared null object, at the cost of the flag lookups, when neither
    sink is armed."""
    if not (TRACING["on"] or TRACING["profiler"]):
        return _NULL_SPAN
    return span("eager", site, exec=current_exec(), **args)
