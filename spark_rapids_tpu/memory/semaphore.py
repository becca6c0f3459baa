"""Device task semaphore — limits how many tasks touch the chip at once,
the ``GpuSemaphore.scala:34-342`` analog.  On TPU the motivation is even
sharper than on GPU: one chip runs one XLA program at a time, so admitting
more tasks than ``spark.rapids.sql.concurrentGpuTasks`` only piles up HBM
working sets.  Tasks acquire before first device use and release around
host-side waits (IO, python) so CPU work overlaps device work.

Reentrant per task: nested acquires by the same task are deduped, matching
the reference's per-task tracking (`GpuSemaphore.scala:106`).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

from ..config import CONCURRENT_TASKS, RapidsConf
from ..observability import metrics as _om
from ..observability import tracer as _trace


class TpuSemaphore:
    _instance: Optional["TpuSemaphore"] = None
    _class_lock = threading.Lock()

    def __init__(self, permits: int):
        self.permits = max(1, int(permits))
        self._sem = threading.Semaphore(self.permits)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._holders: Dict[int, int] = {}  # task id -> acquire depth
        self._acquiring: set = set()        # tasks mid-acquire (race guard)
        self.total_wait_s = 0.0

    # --- lifecycle ---------------------------------------------------------
    @classmethod
    def initialize(cls, conf: Optional[RapidsConf] = None,
                   permits: Optional[int] = None) -> "TpuSemaphore":
        conf = conf or RapidsConf.get_global()
        if permits is None:
            permits = int(conf.get(CONCURRENT_TASKS))
        with cls._class_lock:
            cls._instance = cls(permits)
            return cls._instance

    @classmethod
    def get(cls) -> "TpuSemaphore":
        with cls._class_lock:
            if cls._instance is None:
                cls._instance = cls(int(RapidsConf.get_global()
                                        .get(CONCURRENT_TASKS)))
            return cls._instance

    @classmethod
    def shutdown(cls):
        with cls._class_lock:
            cls._instance = None

    # --- acquire/release ---------------------------------------------------
    def acquire_if_necessary(self, task_id: int, tctx=None):
        from ..serving import lifecycle as _lc
        # lifecycle poll site `sem_wait`: polled BEFORE the first acquire
        # attempt (so a cancel landing pre-wait is honored even when the
        # permit is free) and between 50ms acquire polls while blocked —
        # a cancelled task leaves the wait within one poll interval
        # holding nothing; the raise below the _acquiring guard is safe
        # (the finally clears the guard and notifies)
        _lc.check_cancel("sem_wait")
        with self._lock:
            # wait out another thread of the SAME task that is mid-acquire,
            # so one task never takes two permits
            while task_id in self._acquiring:
                self._cond.wait()
            if task_id in self._holders:
                self._holders[task_id] += 1
                return
            self._acquiring.add(task_id)
        t0 = time.perf_counter()
        acquired = False
        try:
            with _trace.span("sem_wait", "semaphore.acquire", task=task_id):
                while not self._sem.acquire(timeout=_lc.POLL_S):
                    _lc.check_cancel("sem_wait")
            acquired = True
        finally:
            waited = time.perf_counter() - t0
            with self._lock:
                self._acquiring.discard(task_id)
                if acquired:
                    self._holders[task_id] = 1
                self.total_wait_s += waited
                self._cond.notify_all()
        if tctx is not None:
            tctx.inc_metric("semaphoreWaitTime", waited)
        _om.observe("sem_wait_ms", waited * 1e3)

    def release_if_necessary(self, task_id: int):
        with self._lock:
            depth = self._holders.get(task_id)
            if depth is None:
                return
            if depth > 1:
                self._holders[task_id] = depth - 1
                return
            del self._holders[task_id]
        self._sem.release()

    def holds(self, task_id: int) -> bool:
        with self._lock:
            return task_id in self._holders

    def active_tasks(self) -> int:
        with self._lock:
            return len(self._holders)

    class _Scoped:
        def __init__(self, sem: "TpuSemaphore", task_id: int, tctx):
            self.sem, self.task_id, self.tctx = sem, task_id, tctx

        def __enter__(self):
            self.sem.acquire_if_necessary(self.task_id, self.tctx)
            return self

        def __exit__(self, *exc):
            self.sem.release_if_necessary(self.task_id)

    def scoped(self, task_id: int, tctx=None) -> "_Scoped":
        return TpuSemaphore._Scoped(self, task_id, tctx)
