"""Query-timeline observability — structured tracer, Chrome-trace/JSONL
export, and per-query attribution reports.

The engine's perf story lives or dies on data-movement accounting (the
Theseus / "GPU-era analytical processing" argument): a rows/s number
without knowing how much wall time was blocked readbacks, kernel
trace+compile, or H2D/D2H bytes is not a diagnosis.  This package is the
TPU analog of the reference's SQL-UI GpuMetric plumbing + NVTX ranges +
Spark eventLog, recast as one in-process timeline:

* :mod:`.tracer` — one span primitive with two sinks: a thread-safe
  bounded ring buffer of span/counter events (categories ``op``/
  ``compile``/``sync``/``h2d``/``d2h``/``spill``/``shuffle``/
  ``sem_wait``/...) and ``jax.profiler`` annotations on the profiler's
  clock; near-zero overhead when disabled.
* :mod:`.export` — Chrome trace-event JSON (Perfetto-loadable) and an
  append-only JSONL event log per query (eventLog/history analog).
* :mod:`.report` — per-query attribution: blocking-readback count & ms
  per exec, kernel hit/miss & compile ms, bytes on the wire, spill and
  semaphore-wait time.
* :mod:`.metrics` — process-wide registry (counters / gauges /
  log-bucketed p50/p95/p99 histograms) fed by the tracer, shuffle,
  spill/retention and kernel-cache chokepoints; Prometheus + JSON export.
* :mod:`.history` — bounded query flight recorder (plan fingerprint,
  metrics, trace summary per query; in-memory ring + on-disk JSONL).
* :mod:`.doctor` — ranked bottleneck attribution (sync / compile /
  h2d-d2h / dispatch / sem_wait / spill / shuffle -bound verdicts with
  the exec-level spans and counters that justify them).
"""

from .metrics import METRICS, MetricsRegistry, get_registry
from .tracer import (TRACING, QueryTracer, current_exec, get_tracer,
                     pop_exec, push_exec, span)

__all__ = ["TRACING", "QueryTracer", "get_tracer", "span", "push_exec",
           "pop_exec", "current_exec", "METRICS", "MetricsRegistry",
           "get_registry"]
