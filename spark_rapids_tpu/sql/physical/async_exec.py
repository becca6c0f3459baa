"""Async prefetch boundaries — the pipelined-execution seam exec
(``spark.rapids.tpu.prefetch.enabled``).

:class:`AsyncPrefetchExec` wraps a child iterator with a bounded
background queue: a producer thread pulls the child's batches (host
decode, uploads, exchange reads) while the consumer — the downstream
exec chain — drains the queue, so the expensive seams overlap downstream
compute.  This is the engine-side analog of the reference's
multithreaded reader prefetch (``GpuMultiFileReader.scala:176-373``) and
its stream-overlapped transfer model (SURVEY §2.2), generalized to every
pipeline boundary the planner marks.

Contracts:

* **Order**: the queue is FIFO — per-partition batch order is exactly
  the child's.
* **Exceptions**: anything the child raises (including injected chaos
  faults from robustness/faults.py) is carried through the queue and
  re-raised in the consumer with the original exception OBJECT, so
  ``except ShuffleFetchFailed`` works unchanged and a fault can never
  turn into a queue hang.
* **Backpressure**: the producer blocks once ``prefetch.depth`` batches
  are buffered; an early-closed consumer (LIMIT) cancels the producer,
  which exits within one poll interval.
* **Thread-local seams**: the producer installs the task's TaskContext
  (partition-id expressions keep working) and numpy errstate; speculation
  deferral is thread-local and therefore OFF on the producer, so
  speculative aggregate paths below a prefetch boundary take their exact
  variants — correct by construction (docs/async_pipeline.md).
"""

from __future__ import annotations

import queue
import threading
import time
from typing import List

import numpy as np

from ...observability import tracer as _trace
from .base import PhysicalPlan

#: how often a blocked producer re-checks consumer cancellation (s)
_POLL_S = 0.05

#: observability for tests
STATS = {"prefetch_execs_planned": 0}
_STATS_LOCK = threading.Lock()


class _Raised:
    """Exception carrier: the producer's failure rides the queue to the
    consumer, which re-raises the original object (type + traceback)."""

    __slots__ = ("exc",)

    def __init__(self, exc: BaseException):
        self.exc = exc


_DONE = object()


class AsyncPrefetchExec(PhysicalPlan):
    """Pass-through exec producing its child's batches from a bounded
    background queue (one producer thread per partition per pull)."""

    def __init__(self, child: PhysicalPlan, depth: int = 2):
        super().__init__(child)
        self.backend = child.backend
        self.depth = max(1, int(depth))

    @property
    def output(self):
        return self.children[0].output

    def num_partitions(self):
        return self.children[0].num_partitions()

    def estimate_bytes(self):
        return self.children[0].estimate_bytes()

    def execute(self, pid, tctx):
        child = self.children[0]
        q: "queue.Queue" = queue.Queue(self.depth)
        cancel = threading.Event()

        from ...memory import retention as _ret

        from ...serving import lifecycle as _lc

        def produce():
            try:
                # the task's context must be visible on this thread
                # (spark_partition_id(), input_file_name(), conf reads);
                # errstate is thread-local in numpy, mirror execute_all's
                with tctx.as_current(), np.errstate(all="ignore"):
                    for batch in child.execute(pid, tctx):
                        # lifecycle poll site `prefetch` (producer side):
                        # a cancelled query's producer must stop pulling
                        # the child, not fill the queue to the brim first
                        _lc.check_cancel("prefetch")
                        # pinned while enqueued: a queued batch is held by
                        # TWO parties (queue + eventual consumer) and must
                        # never be donation-eligible in that window
                        _ret.pin_batch(batch)
                        if not _put(q, batch, cancel):
                            _ret.unpin_batch(batch)  # consumer left
                            return
                _put(q, _DONE, cancel)
            except BaseException as e:  # noqa: BLE001 - re-raised consumer-side
                _put(q, _Raised(e), cancel)

        t = threading.Thread(target=produce, daemon=True,
                             name=f"srt-prefetch-p{pid}")
        t.start()
        waited_s = 0.0
        produced = 0
        try:
            while True:
                t0 = time.perf_counter()
                with _trace.span("queue", "prefetch.consumer_wait",
                                 partition=pid, depth=q.qsize()):
                    while True:
                        try:
                            # polled get: a cancel must not leave the
                            # consumer blocked forever on a wedged/slow
                            # producer
                            item = q.get(timeout=_POLL_S)
                            break
                        except queue.Empty:
                            _lc.check_cancel("prefetch")
                waited_s += time.perf_counter() - t0
                if item is _DONE:
                    break
                if isinstance(item, _Raised):
                    raise item.exc
                # handoff complete: the consumer is now the sole holder
                _ret.unpin_batch(item)
                produced += 1
                yield item
        finally:
            cancel.set()
            # deterministic drain (cancel/deadline/early-LIMIT exits):
            # the producer exits within one poll interval, then any
            # batches still enqueued are unpinned HERE — retention
            # accounting returns to baseline without waiting for the GC
            # reaper (the leak-sentinel/race-matrix contract)
            t.join(timeout=4 * _POLL_S)
            while True:
                try:
                    item = q.get_nowait()
                except queue.Empty:
                    break
                if item is not _DONE and not isinstance(item, _Raised):
                    _ret.unpin_batch(item)
            tctx.inc_metric("prefetchBatches", produced)
            tctx.inc_metric("prefetchWaitMs", waited_s * 1e3)
            if _trace.TRACING["on"]:
                _trace.get_tracer().counter("prefetchedBatches", produced)

    def node_name(self):
        return "AsyncPrefetch"

    def simple_string(self):
        return f"{self.node_name()} depth={self.depth}"


def _put(q: "queue.Queue", item, cancel: threading.Event) -> bool:
    """Enqueue with cancellation polling; False when the consumer left."""
    while not cancel.is_set():
        try:
            q.put(item, timeout=_POLL_S)
            return True
        except queue.Full:
            continue
    return False


# --------------------------------------------------------------------------
# planner pass
# --------------------------------------------------------------------------

#: parents that hold DIRECT references to their children (probe/build
#: sides, scan introspection, fused-collect replay) — wrapping such a
#: child would desynchronize the reference from ``children`` and defeat
#: the runtime introspection those execs do, so the pass skips them.
def _no_wrap_parent(plan: PhysicalPlan) -> bool:
    from .collect_fusion import FusedCollectExec
    from .dpp import DppFileScanExec
    from .join import AdaptiveJoinExec, BaseJoinExec
    return isinstance(plan, (BaseJoinExec, AdaptiveJoinExec,
                             FusedCollectExec, DppFileScanExec))


def _wrap_target(plan: PhysicalPlan) -> bool:
    from ...io_.exec import FileScanExec
    from .basic import InMemoryScanExec
    from .exchange import ShuffleExchangeExec
    from .transitions import HostToDeviceExec
    return isinstance(plan, (FileScanExec, InMemoryScanExec,
                             HostToDeviceExec, ShuffleExchangeExec))


def insert_prefetch(plan: PhysicalPlan, conf) -> PhysicalPlan:
    """Planner pass (runs LAST, after ``fuse_stages`` and the collect-tail
    fusion): wrap the expensive seams — file/in-memory scans,
    ``HostToDeviceExec`` uploads, and the reduce side of shuffle
    exchanges — in :class:`AsyncPrefetchExec` so their host work overlaps
    the consumer.  Children directly referenced by joins / DPP / fused
    collects are left alone (see ``_no_wrap_parent``)."""
    from ...config import PREFETCH_DEPTH
    depth = max(1, int(conf.get(PREFETCH_DEPTH)))

    def rewrite(node: PhysicalPlan, parent) -> PhysicalPlan:
        node.children = tuple(rewrite(c, node) for c in node.children)
        if isinstance(node, AsyncPrefetchExec):
            return node  # idempotent under re-planning
        if _wrap_target(node) and (parent is None
                                   or not _no_wrap_parent(parent)):
            with _STATS_LOCK:
                STATS["prefetch_execs_planned"] += 1
            return AsyncPrefetchExec(node, depth)
        return node

    return rewrite(plan, None)
