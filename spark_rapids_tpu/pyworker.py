"""Out-of-process Python UDF workers (reference ``python/rapids/daemon.py``
+ ``PythonWorkerSemaphore.scala``).

Pandas UDFs previously ran in-process: a user function that crashed the
interpreter (``os._exit``, a segfaulting extension) took the whole
engine down, and the python-worker semaphore capped sections nothing
contended on.  This pool runs each job in a separate worker PROCESS
(``pyworker_main.py``, launched by file path so it never imports the
package or touches jax/the chip), exchanging batches as Arrow IPC
streams over the stdio pipes:

- crash containment: a dead worker surfaces as :class:`WorkerCrashed`
  on THAT task; the session, the pool, and sibling workers live on;
- concurrency is gated by PythonWorkerSemaphore (every pandas exec
  runs jobs under its permit, cap
  ``spark.rapids.python.concurrentPythonWorkers``) — the permits now
  bound real, contending worker PROCESSES;
- ``spark.rapids.python.worker.isolated=false`` restores the in-process
  fast path (useful for debugging user functions).

The job payload is ONE cloudpickled closure
``job_fn(list[pd.DataFrame]) -> list[pd.DataFrame]`` carrying both the
user function and the exec's shape logic, so every pandas exec
(mapInPandas / applyInPandas / cogrouped / grouped-agg) shares this one
transport."""

from __future__ import annotations

import os
import struct
import subprocess
import sys
import threading
from typing import List, Optional

from .config import CONCURRENT_PYTHON_WORKERS, PYTHON_WORKER_ISOLATED

#: observability for tests
STATS = {"jobs": 0, "spawned": 0, "crashes": 0, "peak_workers": 0}

_WORKER_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "pyworker_main.py")


class WorkerCrashed(RuntimeError):
    """The worker process died mid-job (user code killed the
    interpreter).  The TASK fails; the session does not."""


class UdfError(RuntimeError):
    """User function raised inside the worker; carries its traceback."""


class _Worker:
    def __init__(self):
        self.proc = subprocess.Popen(
            [sys.executable, _WORKER_PATH],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE)
        STATS["spawned"] += 1

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self) -> None:
        try:
            self.proc.kill()
            self.proc.wait(timeout=5)
        except Exception:
            pass

    def run(self, job_fn, tables: List) -> List:
        import cloudpickle
        import pyarrow as pa
        #: True once the response was FULLY consumed — only then may the
        #: pool reuse this worker (half-read frames would leak into the
        #: next job's response)
        self.clean = False
        w = self.proc.stdin
        blob = cloudpickle.dumps(job_fn)
        w.write(struct.pack("<Q", len(blob)))
        w.write(blob)
        w.write(struct.pack("<Q", len(tables)))
        for t in tables:
            sink = pa.BufferOutputStream()
            with pa.ipc.new_stream(sink, t.schema) as wr:
                wr.write_table(t)
            payload = sink.getvalue().to_pybytes()
            w.write(struct.pack("<Q", len(payload)))
            w.write(payload)
        w.flush()

        r = self.proc.stdout

        def read_exact(n: int) -> bytes:
            buf = b""
            while len(buf) < n:
                chunk = r.read(n - len(buf))
                if not chunk:
                    raise WorkerCrashed(
                        "python UDF worker died mid-job (exit code "
                        f"{self.proc.poll()}); the task fails, the "
                        "session survives")
                buf += chunk
            return buf

        status = read_exact(1)[0]
        if status == 1:
            (n,) = struct.unpack("<Q", read_exact(8))
            tb = read_exact(n).decode("utf-8", "replace")
            (m,) = struct.unpack("<Q", read_exact(8))
            blob = read_exact(m) if m else b""
            self.clean = True  # error frame fully consumed
            exc = None
            if blob:
                try:
                    exc = cloudpickle.loads(blob)
                except Exception:
                    exc = None
            if isinstance(exc, Exception):
                # re-raise the ORIGINAL exception type — in-process
                # callers catching e.g. ValueError keep working under
                # the isolated default (never re-raise bare
                # BaseExceptions like SystemExit from user code)
                exc.__udf_traceback__ = tb
                raise exc
            raise UdfError(tb)
        (k,) = struct.unpack("<Q", read_exact(8))
        out = []
        for _ in range(k):
            (n,) = struct.unpack("<Q", read_exact(8))
            with pa.ipc.open_stream(pa.BufferReader(read_exact(n))) as rd:
                out.append(rd.read_all())
        self.clean = True
        return out


class PythonWorkerPool:
    _instance: Optional["PythonWorkerPool"] = None
    _class_lock = threading.Lock()

    def __init__(self, capacity: int):
        import atexit
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._idle: List[_Worker] = []
        self._live = 0
        atexit.register(self.shutdown)

    @classmethod
    def get(cls, conf) -> "PythonWorkerPool":
        cap = int(conf.get(CONCURRENT_PYTHON_WORKERS))
        with cls._class_lock:
            if cls._instance is None or cls._instance.capacity != cap:
                if cls._instance is not None:
                    cls._instance.shutdown()
                cls._instance = cls(cap)
            return cls._instance

    def _checkout(self) -> _Worker:
        with self._lock:
            while self._idle:
                w = self._idle.pop()
                if w.alive():
                    return w
                self._live -= 1
            self._live += 1
            STATS["peak_workers"] = max(STATS["peak_workers"], self._live)
        return _Worker()

    def _checkin(self, w: _Worker) -> None:
        if PythonWorkerPool._instance is not self:
            # the pool was rebuilt (capacity change) while this job ran:
            # never park a worker on an orphaned pool — kill it so no
            # process leaks
            w.kill()
            return
        with self._lock:
            if w.alive():
                self._idle.append(w)
            else:
                self._live -= 1

    def run_job(self, job_fn, tables: List) -> List:
        # concurrency gating comes from PythonWorkerSemaphore: every
        # pandas exec calls this inside _semaphore_released, which holds
        # a permit under the SAME concurrentPythonWorkers cap — a second
        # semaphore here would be dead machinery
        STATS["jobs"] += 1
        w = self._checkout()
        try:
            out = w.run(job_fn, tables)
        except BaseException:
            if getattr(w, "clean", False):
                # user error with the response fully consumed: the
                # worker's pipes are clean, keep it
                self._checkin(w)
                raise
            # crash / interrupt / broken pipe: half-read frames may
            # linger and a reused worker would serve the NEXT job the
            # previous job's leftovers — kill it
            if isinstance(sys.exc_info()[1], WorkerCrashed):
                STATS["crashes"] += 1
            w.kill()
            with self._lock:
                if PythonWorkerPool._instance is self:
                    self._live -= 1
            raise
        self._checkin(w)
        return out

    def shutdown(self) -> None:
        with self._lock:
            for w in self._idle:
                w.kill()
            self._idle.clear()
            self._live = 0


def run_pandas_job(conf, job_fn, tables: List,
                   force_inprocess: bool = False) -> List:
    """Run ``job_fn(list[pd.DataFrame]) -> list[pd.DataFrame]`` over
    Arrow tables — isolated in a worker process (default) or in-process
    when ``spark.rapids.python.worker.isolated=false``.

    ``force_inprocess`` overrides isolation for SIDE-EFFECTING callers
    (df.foreach/foreachPartition): their whole contract is mutations the
    caller observes, which a worker process would silently swallow.

    Arrow in, Arrow out on BOTH paths: the pandas conversion happens
    exactly once, inside the job (worker-side when isolated), so the
    two modes hand user code identical frames (same RangeIndex, same
    dtype normalization) and the isolated path never pays a redundant
    pandas round trip in the parent."""
    import pyarrow as pa
    if force_inprocess or not bool(conf.get(PYTHON_WORKER_ISOLATED)):
        outs = job_fn([t.to_pandas() for t in tables])
        return [o if isinstance(o, pa.Table)
                else pa.Table.from_pandas(o, preserve_index=False)
                for o in outs]
    return PythonWorkerPool.get(conf).run_job(job_fn, tables)
