"""Physical plan base — the analog of the reference's ``GpuExec``
(``GpuExec.scala:197``): an operator DAG whose nodes produce iterators of
columnar batches per partition.

Placement model: every exec carries ``backend`` ∈ {"tpu", "cpu"}.  TPU execs
run jitted jnp kernels on device batches; CPU execs run the *same* kernels
eagerly under numpy on host batches (the per-operator fallback the reference
gets from leaving nodes on CPU Spark).  Transitions (transitions.py) move
batches across.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterator, List, Optional, Sequence

import numpy as np

from ...columnar.batch import ColumnarBatch
from ...config import RapidsConf
from ...observability import tracer as _trace
from ..expressions.core import AttributeReference

TPU, CPU = "tpu", "cpu"


#: metric verbosity ranks (GpuExec.scala:49-141 ESSENTIAL/MODERATE/DEBUG)
_METRIC_RANK = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}


class TaskContext:
    """Per-task context: metrics + conf + partition id (GpuTaskMetrics /
    TaskContext analog).  Metrics above the configured verbosity level
    are dropped at the increment site (spark.rapids.sql.metrics.level)."""

    def __init__(self, partition_id: int, conf: Optional[RapidsConf] = None,
                 parent: Optional["TaskContext"] = None):
        self.partition_id = partition_id
        self.conf = conf or RapidsConf.get_global()
        # contexts spawned INSIDE another task (exchange map side, join
        # build collection) share the parent's metrics dict, so the work
        # below an exchange still shows up in last_query_metrics.  The
        # metrics lock is shared along with the dict: with the pipelined
        # execution layer (task.parallelism / prefetch / double-buffered
        # transfers) one task's metrics may be incremented from its
        # prefetch and transfer helper threads concurrently.
        if parent is not None:
            self.metrics: Dict[str, float] = parent.metrics
            self._metrics_lock = parent._metrics_lock
            self._late_metrics: list = parent._late_metrics
        else:
            self.metrics = {}
            self._metrics_lock = threading.Lock()
            self._late_metrics = []
        from ...config import METRICS_LEVEL, SERVING_TENANT
        self._rank = _METRIC_RANK.get(
            str(self.conf.get(METRICS_LEVEL)).upper(), 1)
        #: tenant identity for tenant-aware spill eviction (the catalog
        #: stamps it on every registered buffer, memory/spill.py)
        self.tenant = (parent.tenant if parent is not None
                       else str(self.conf.get(SERVING_TENANT) or ""))
        #: the owning query's lifecycle token (serving/lifecycle.py):
        #: inherited from the parent task or captured from the creating
        #: thread, so helper threads installing this task via
        #: as_current() poll the right query's cancellation
        if parent is not None:
            self.query_ctx = parent.query_ctx
        else:
            cur = TaskContext.current()
            if cur is not None:
                self.query_ctx = cur.query_ctx
            else:
                from ...serving.lifecycle import ambient
                self.query_ctx = ambient()

    def inc_metric(self, name: str, value: float = 1.0,
                   level: str = "MODERATE"):
        if _METRIC_RANK.get(level, 1) > self._rank:
            return
        with self._metrics_lock:
            self.metrics[name] = self.metrics.get(name, 0.0) + value

    def inc_metric_late(self, name: str, value, level: str = "MODERATE"):
        """Adds a count that is still on the device (a batch's
        ``num_rows``).  It is read by ``settle_metrics`` when the task has
        ended, never here: no counter makes the host wait for a program."""
        if _METRIC_RANK.get(level, 1) > self._rank:
            return
        with self._metrics_lock:
            self._late_metrics.append((name, value))

    def settle_metrics(self) -> None:
        with self._metrics_lock:
            late = list(self._late_metrics)
            del self._late_metrics[:]
        for name, value in late:
            self.inc_metric(name, float(value))

    # --- thread-local current task (Spark TaskContext.get() analog) -------
    _tls = threading.local()

    @classmethod
    def current(cls) -> Optional["TaskContext"]:
        """The task running on this thread (None outside a task).  Used by
        task-context expressions (spark_partition_id(), rand(), ...)."""
        return getattr(cls._tls, "ctx", None)

    @classmethod
    def _set_current(cls, ctx: Optional["TaskContext"]):
        cls._tls.ctx = ctx

    def as_current(self):
        """Context manager installing this task as the thread's current one
        (nested map-side tasks under exchanges/joins restore the outer).

        The restore is CONDITIONAL on this context still being the
        thread's current one: a generator abandoned mid-iteration (LIMIT
        early-close, query cancellation) has its ``finally`` run at
        GC-close time — possibly on a different thread, during a LATER
        query — and an unconditional restore would clobber that thread's
        live context with a stale one."""
        from contextlib import contextmanager

        @contextmanager
        def _cm():
            prev = TaskContext.current()
            TaskContext._set_current(self)
            try:
                yield self
            finally:
                if TaskContext.current() is self:
                    TaskContext._set_current(prev)
        return _cm()


#: process-wide profiling switch, flipped per query by the session from
#: spark.rapids.tpu.profile.enabled (single-driver model, like the
#: reference's per-query GpuMetric wiring).  The session SAVES and
#: RESTORES the previous value around each query (finally-guarded), so a
#: query raising mid-flight — or a session that enables profiling — can
#: never leak the flag into a later query or another session.  The flag
#: being process-wide is sound only under the single-driver model: one
#: query executes at a time per process (sessions run queries serially on
#: the calling thread; the shuffle/IO pools belong to that one query).
#: Concurrent collect() calls from two threads are unsupported for
#: profiling/tracing — see docs/observability.md.
PROFILING = {"on": False}

#: serializes task-metric merges onto a plan's ``metrics`` dict — one
#: process-wide lock (merges are per task, never per batch, so contention
#: is negligible next to the read-modify-write race it closes under the
#: parallel partition scheduler).  Note the per-exec ``_prof_ns``
#: profiling accumulators deliberately stay lock-free: under
#: task.parallelism > 1 their wall-clock attribution is approximate
#: anyway (overlapping tasks double-count inclusive time); use the
#: tracer for parallel-mode timing.
_PLAN_METRICS_LOCK = threading.Lock()


class PhysicalPlan:
    backend: str = TPU

    def __init__(self, *children: "PhysicalPlan"):
        self.children: tuple = tuple(children)
        self.metrics: Dict[str, float] = {}
        self._placement_reasons: List[str] = []
        self._prof_ns = 0       # inclusive time spent producing batches
        self._prof_batches = 0

    def __init_subclass__(cls, **kw):
        """Wrap every exec's ``execute`` with the profiling/tracing shim
        (the SQL-UI per-op metric plumbing of ``GpuExec.scala:49-141``):
        when profiling or tracing is on, time spent pulling each batch
        from this node's iterator (children included) accrues to the
        node; the report derives self-time as inclusive minus children.
        When tracing is on (either sink), each pull additionally runs
        inside an ``op`` span and brackets itself on the tracer's exec
        stack — a nested child pull pushes the child on top, so
        chokepoint spans (sync/h2d/d2h/spill, and the ``exec=`` of the
        ``eager`` spans) fired during the pull attribute to the innermost
        executing exec.  The span closes before the batch is yielded: it
        never covers the consumer."""
        super().__init_subclass__(**kw)
        orig = cls.__dict__.get("execute")
        if orig is None or getattr(orig, "_profiled", False):
            return

        def execute(self, pid, tctx, _orig=orig):
            tr = _trace.TRACING
            if not (PROFILING["on"] or tr["on"] or tr["profiler"]):
                return _orig(self, pid, tctx)
            import time as _t

            def gen():
                traced = tr["on"] or tr["profiler"]
                name = self.node_name() if traced else ""
                t0 = _t.perf_counter_ns()
                it = iter(_orig(self, pid, tctx))
                self._prof_ns += _t.perf_counter_ns() - t0
                while True:
                    t1 = _t.perf_counter_ns()
                    if traced:
                        _trace.push_exec(name)
                    try:
                        with _trace.span("op", name, partition=pid):
                            b = next(it)
                    except StopIteration:
                        self._prof_ns += _t.perf_counter_ns() - t1
                        return
                    finally:
                        if traced:
                            _trace.pop_exec()
                    self._prof_ns += _t.perf_counter_ns() - t1
                    self._prof_batches += 1
                    yield b
            return gen()

        execute._profiled = True
        cls.execute = execute

    # --- schema -----------------------------------------------------------
    @property
    def output(self) -> List[AttributeReference]:
        raise NotImplementedError(type(self).__name__)

    # --- partitioning -----------------------------------------------------
    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions()
        return 1

    def estimate_bytes(self) -> Optional[int]:
        """Size estimate for broadcast decisions (reference relies on
        Spark statistics); None when unknown."""
        ests = [c.estimate_bytes() for c in self.children]
        if len(ests) == 1:
            return ests[0]
        return None

    # --- execution --------------------------------------------------------
    def execute(self, pid: int, tctx: TaskContext) -> Iterator[ColumnarBatch]:
        raise NotImplementedError(type(self).__name__)

    def execute_all(self, conf: Optional[RapidsConf] = None
                    ) -> List[ColumnarBatch]:
        """Run every partition (local mode driver) — serially by default,
        or on a bounded thread pool when
        ``spark.rapids.tpu.task.parallelism`` > 1.  Each task acquires
        the device semaphore, arms test OOM injection (conftest.py:113-265
        analog), and fires completion callbacks.  Each task runs inside a
        ``task`` span (NVTX-range analog; on the profiler's clock with
        ``spark.rapids.tpu.trace.enabled``); task metrics accumulate onto
        ``self.metrics`` for the session to report.

        Ordering guarantee (docs/async_pipeline.md): batches within a
        partition keep their order, and the returned list concatenates
        partitions in pid order — identical to the serial loop in both
        modes.  Nested execute_all calls (map-side subquery / broadcast
        build under an outer exchange task) always run serially: pools
        don't nest, and the outer task owns the thread-local seams
        (TaskContext, OOM arming, speculation deferral)."""
        from ...config import TASK_PARALLELISM
        cfg = conf or RapidsConf.get_global()
        nparts = self.num_partitions()
        par = max(1, int(cfg.get(TASK_PARALLELISM)))
        if par > 1 and nparts > 1 and TaskContext.current() is None:
            return self._execute_all_parallel(conf, cfg, min(par, nparts))
        out: List[ColumnarBatch] = []
        for pid in range(nparts):
            out.extend(self._run_partition(pid, conf))
        return out

    def _run_partition(self, pid: int, conf: Optional[RapidsConf]
                       ) -> List[ColumnarBatch]:
        """The one-task protocol shared by the serial loop and the
        parallel scheduler: TaskContext install, OOM-injection arming
        (thread-local, so each pool worker arms its own), semaphore
        acquire/release, metric merge, completion callbacks."""
        from ...config import (DUMP_ON_ERROR_PATH, TEST_INJECT_RETRY_OOM,
                               TEST_INJECT_SPLIT_OOM)
        from ...memory.completion import ScalableTaskCompletion
        from ...memory.retry import arm_oom_injection
        from ...memory.semaphore import TpuSemaphore
        from ...robustness import faults as _faults
        from ...serving import lifecycle as _lc
        sem = TpuSemaphore.get()
        stc = ScalableTaskCompletion.get()
        out: List[ColumnarBatch] = []
        tctx = TaskContext(pid, conf)
        # save/restore the PREVIOUS context like as_current() does: a
        # nested execute_all (map-side subquery / broadcast build run
        # under an outer exchange task) must not wipe the outer
        # task's thread-local on exit
        prev_ctx = TaskContext.current()
        TaskContext._set_current(tctx)
        failed = False

        def _drain(it) -> None:
            # per-batch poll: a mid-partition cancel drains at batch
            # granularity, unwinding through the finally below (semaphore
            # release, metric merge, completion callbacks)
            for b in it:
                out.append(b)
                _lc.check_cancel("partition")
        try:
            # everything below runs under the finally: the lifecycle
            # poll, the chaos site and the (now cancellable) semaphore
            # acquire can all RAISE, and a raise here must still restore
            # the thread context and release whatever was taken
            # -- lifecycle poll site `partition`: a cancel/deadline
            # landing before the task touches the device costs nothing
            _lc.check_cancel("partition")
            if _faults.CHAOS["on"]:
                from ...memory.fatal import FatalDeviceError
                _faults.maybe_inject("device.fatal", exc=FatalDeviceError,
                                     partition=pid)
            arm_oom_injection(int(tctx.conf.get(TEST_INJECT_RETRY_OOM)),
                              int(tctx.conf.get(TEST_INJECT_SPLIT_OOM)))
            sem.acquire_if_necessary(pid, tctx)
            where = {}
            if _trace.TRACING["on"] or _trace.TRACING["profiler"]:
                from ...parallel import placement
                chip = placement.home_chip(pid, tctx.conf)
                if chip is not None:    # several executors on this host
                    where["device"] = placement.label(chip)
            with np.errstate(all="ignore"), _trace.span(
                    "task", f"{self.node_name()}:task{pid}", partition=pid,
                    **where):
                _drain(self.execute(pid, tctx))
        except BaseException as e:
            failed = True
            dump_dir = str(tctx.conf.get(DUMP_ON_ERROR_PATH))
            if dump_dir:
                _dump_failure(dump_dir, self, pid, e, out)
            raise
        finally:
            # disarm: unconsumed synthetic OOMs must not leak into the
            # next task or into direct with_retry callers (tests)
            arm_oom_injection(0, 0)
            TaskContext._set_current(prev_ctx)
            sem.release_if_necessary(pid)
            if not failed:
                tctx.settle_metrics()
            # merge under a lock: concurrent tasks of the parallel
            # scheduler all land their metrics on this one plan object
            with _PLAN_METRICS_LOCK:
                for k, v in tctx.metrics.items():
                    self.metrics[k] = self.metrics.get(k, 0.0) + v
            try:
                stc.task_completed(pid)
            except Exception:
                # never mask the task's own failure with a cleanup error
                if not failed:
                    raise
        return out

    def _execute_all_parallel(self, conf: Optional[RapidsConf],
                              cfg: RapidsConf, workers: int
                              ) -> List[ColumnarBatch]:
        """Bounded-pool partition scheduler
        (``spark.rapids.tpu.task.parallelism``): independent partitions
        run concurrently, each under the full task protocol.  Device
        admission stays gated by ``spark.rapids.sql.concurrentGpuTasks``
        — the semaphore is (re)sized from THIS query's conf so session
        overrides take effect (the serial path never contends, so it
        keeps whatever instance exists).  Results are assembled in pid
        order; on failure the lowest-failing-pid exception propagates
        with its original type, and not-yet-started tasks are skipped.

        Thread-local seams (speculation deferral, OOM-injection arming,
        the tracer's exec stack) stay correct by construction: pool
        workers start with deferral OFF, so speculative paths fall back
        to their exact variants — see docs/async_pipeline.md."""
        from concurrent.futures import ThreadPoolExecutor
        from ...config import CONCURRENT_TASKS
        from ...memory.semaphore import TpuSemaphore
        from ...serving import lifecycle as _lc
        sem = TpuSemaphore.get()
        want = max(1, int(cfg.get(CONCURRENT_TASKS)))
        if sem.permits != want and sem.active_tasks() == 0:
            TpuSemaphore.initialize(permits=want)
        nparts = self.num_partitions()
        slots: List[Optional[List[ColumnarBatch]]] = [None] * nparts
        errors: Dict[int, BaseException] = {}
        abort = threading.Event()
        # the pool workers must see the driver thread's query context:
        # a cancel/deadline is one token shared by every task
        qctx = _lc.current()

        def run_task(pid: int) -> None:
            if abort.is_set():
                return  # a prior task failed; its exception wins
            try:
                with _lc.installed(qctx):
                    slots[pid] = self._run_partition(pid, conf)
            except BaseException as e:  # noqa: BLE001 - re-raised below
                errors[pid] = e
                abort.set()

        with ThreadPoolExecutor(
                max_workers=workers,
                thread_name_prefix="srt-task") as pool:
            list(pool.map(run_task, range(nparts)))
        if errors:
            raise errors[min(errors)]
        out: List[ColumnarBatch] = []
        for got in slots:
            if got:
                out.extend(got)
        return out

    # --- jit plumbing for device execs ------------------------------------
    def _jit(self, fn, key=None, donate_argnums=None):
        """jit on the tpu backend, eager numpy on cpu.

        When ``key`` is given, the jitted wrapper is shared process-wide via
        the kernel cache (kernel_cache.py) so repeated ``collect()`` calls of
        the same query reuse compiled programs instead of re-tracing — the
        reference's kernel-reuse model (SURVEY §3.3).  The key must capture
        everything that affects the traced computation besides the input
        batch itself (bound expressions, static params, output names).

        ``donate_argnums`` builds a donated-buffer program (whole-stage
        donation): the key must carry a donation marker, the caller must
        clear the arguments through ``retention.may_donate``, and the OOM
        guard runs non-retriable (donated inputs cannot be re-presented).
        """
        if self.backend == TPU:
            from ...memory.oom_guard import guard_device_oom
            if key is not None:
                from .kernel_cache import cached_jit
                return guard_device_oom(
                    cached_jit((type(self).__name__,) + tuple(key), fn,
                               donate_argnums=donate_argnums),
                    retriable=not donate_argnums)
            import jax
            from .kernel_cache import count_unkeyed_jit
            count_unkeyed_jit()
            return guard_device_oom(jax.jit(fn))
        return fn

    @property
    def xp(self):
        if self.backend == TPU:
            import jax.numpy as jnp
            return jnp
        return np

    # --- explain ----------------------------------------------------------
    def node_name(self) -> str:
        base = type(self).__name__.replace("Exec", "")
        return ("Tpu" if self.backend == TPU else "Cpu") + base

    def simple_string(self) -> str:
        return self.node_name()

    def tree_string(self, level: int = 0) -> str:
        pad = "  " * level + ("+- " if level else "")
        lines = [pad + self.simple_string()]
        for r in self._placement_reasons:
            lines.append("  " * (level + 1) + "! " + r)
        for c in self.children:
            lines.append(c.tree_string(level + 1))
        return "\n".join(lines)


class ScanColumnCounter:
    """``scanColumnsRead`` / ``scanColumnsPruned`` of last_query_metrics for
    one scan exec (``sql/column_pruning.py``): counted once a scan and
    collect, whichever partition comes first."""

    _NEVER = object()       # a query context may be None

    def __init__(self):
        self._lock = threading.Lock()
        self._counted_in = self._NEVER

    def count(self, tctx: "TaskContext", read: int, whole: int) -> None:
        with self._lock:
            if self._counted_in is tctx.query_ctx:
                return
            self._counted_in = tctx.query_ctx
        tctx.inc_metric("scanColumnsRead", read)
        tctx.inc_metric("scanColumnsPruned", whole - read)


def count_stage_dispatch(n: float = 1) -> None:
    """Account ``n`` device-program dispatches to the current task's
    ``stageOpDispatches`` metric — the stage-scope dispatch counter
    (docs/whole_stage.md): only ops that whole-stage fusion can absorb
    (filters, projects, aggregate partial programs, join probe programs)
    count here, so the fused-vs-unfused ratio isolates exactly the
    dispatches fusion removes."""
    t = TaskContext.current()
    if t is not None:
        t.inc_metric("stageOpDispatches", n)


def profile_report(phys: "PhysicalPlan") -> str:
    """Formatted per-exec profile of the last execution: inclusive and
    self wall time plus batch counts (the SQL-UI per-op metric view the
    reference publishes via GpuMetric; enable with
    spark.rapids.tpu.profile.enabled)."""
    lines = ["exec                                     incl_ms   self_ms  "
             "batches"]

    def walk(node: "PhysicalPlan", level: int):
        incl = node._prof_ns / 1e6
        self_ms = (node._prof_ns
                   - sum(c._prof_ns for c in node.children)) / 1e6
        name = "  " * level + node.node_name()
        lines.append(f"{name:<40} {incl:>8.2f}  {max(self_ms, 0.0):>8.2f}  "
                     f"{node._prof_batches:>7d}")
        for c in node.children:
            walk(c, level + 1)

    walk(phys, 0)
    return "\n".join(lines)


def collect_metrics(phys: "PhysicalPlan") -> Dict[str, float]:
    """Accumulate every node's metrics over the physical tree (the
    per-query metrics contract shared by session collect and the ML
    handoff)."""
    metrics: Dict[str, float] = {}
    stack = [phys]
    while stack:
        node = stack.pop()
        for k, v in node.metrics.items():
            metrics[k] = metrics.get(k, 0.0) + v
        stack.extend(node.children)
    return metrics


def eval_context(plan: PhysicalPlan, batch: ColumnarBatch, conf=None):
    from ..expressions.core import EvalContext
    return EvalContext(batch, xp=plan.xp, conf=conf)


def _dump_failure(dump_dir: str, plan: PhysicalPlan, pid: int,
                  exc: BaseException, batches: Sequence[ColumnarBatch]):
    """DumpUtils analog: on task failure, write the batches produced so
    far as parquet plus the plan/error text for offline repro."""
    import os
    import time
    try:
        stamp = f"{int(time.time())}-{type(plan).__name__}-p{pid}"
        d = os.path.join(dump_dir, stamp)
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "error.txt"), "w") as fh:
            fh.write(f"{type(exc).__name__}: {exc}\n\nplan:\n"
                     f"{plan.tree_string()}\n")
        import pyarrow.parquet as pq
        from ...columnar.convert import device_to_arrow
        for i, b in enumerate(batches[-4:]):  # last few batches
            pq.write_table(device_to_arrow(b),
                           os.path.join(d, f"batch-{i}.parquet"))
    except Exception:
        pass  # dumping must never mask the original failure
