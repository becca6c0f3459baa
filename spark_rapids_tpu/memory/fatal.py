"""Fatal device-error handling — the ``GpuCoreDumpHandler`` /
fatal-``CudaFatalException`` analog (reference ``Plugin.scala:515-539``:
a fatal CUDA error makes the executor capture a GPU core dump
(``GpuCoreDumpHandler.scala:57+``), log nvidia-smi state, and
self-terminate with exit code 20 so Spark reschedules the work on a
healthy executor; non-fatal errors stay task-local).

TPU analog: a runtime ``XlaRuntimeError`` that is NOT a memory condition
means the device is in an unknown state.  The guard captures a
diagnostics bundle (exception, backend/device info, spill-catalog state,
live config) to ``spark.rapids.tpu.fatalDump.path`` and raises
:class:`FatalDeviceError`; with ``spark.rapids.tpu.fatalErrorExit`` the
process self-terminates with exit code 20 like the reference executor
(off by default — this engine usually runs in the user's process)."""

from __future__ import annotations

import os
import time
import traceback
from typing import Optional

#: reference exit code for fatal device errors (Plugin.scala:515-539)
FATAL_EXIT_CODE = 20

#: observability for tests
STATS = {"fatal_errors": 0, "dumps_written": 0}


class FatalDeviceError(RuntimeError):
    """The device runtime failed outside the OOM protocol; computation
    state is unknown and the query must not be retried in-process."""

    def __init__(self, message: str, dump_path: Optional[str] = None):
        super().__init__(message)
        self.dump_path = dump_path


def is_fatal_device_error(exc: BaseException) -> bool:
    """XlaRuntimeError that is NOT a memory condition (those go through
    the spill/retry protocol in oom_guard).  Chaos-injected faults are
    never fatal: the device did not actually fail, so the fatal handler
    must not dump diagnostics or (with fatalErrorExit) kill the process
    over a synthetic error."""
    from ..robustness.faults import InjectedFault
    if isinstance(exc, InjectedFault):
        return False
    from .oom_guard import is_device_oom
    name = type(exc).__name__
    if "XlaRuntimeError" not in name:
        return False
    return not is_device_oom(exc)


def _diagnostics(exc: BaseException) -> str:
    lines = [f"fatal device error at {time.strftime('%Y-%m-%dT%H:%M:%S')}",
             "", "exception:",
             "".join(traceback.format_exception(exc)).rstrip(), ""]
    # identity stamps: WHICH tenant/session/query hit the fatal — the
    # quarantine protocol (serving/lifecycle.py) fails only that query,
    # so the post-mortem must not have to guess whose plan it was
    try:
        from ..serving import lifecycle as _lc
        from ..sql.physical.base import TaskContext
        t = TaskContext.current()
        q = _lc.current()
        lines.append(
            "query identity: "
            f"tenant={((q.tenant if q else '') or (t.tenant if t else '')) or '(none)'} "
            f"session={(q.session_id if q else '') or '(none)'} "
            f"query={(q.query_id if q else 0) or '(none)'} "
            f"partition={t.partition_id if t else '(none)'}")
        if q is not None and q.cancelled:
            lines.append(f"query was cancelled: {q.reason}")
    except Exception:
        pass
    # the last bottleneck-doctor verdict recorded in this process: what
    # the engine believed it was bound on right before the device died
    try:
        from ..observability import doctor as _doc
        lv = getattr(_doc, "LAST_VERDICT", None)
        if lv:
            lines.append(
                f"last doctor verdict: {lv.get('verdict')} "
                f"(age {time.monotonic() - lv.get('at', 0.0):.1f}s)")
    except Exception:
        pass
    lines.append("")
    try:
        import jax
        lines.append(f"jax {jax.__version__}, backend "
                     f"{jax.default_backend()}")
        for d in jax.devices():
            lines.append(f"  device: {d}")
            stats = getattr(d, "memory_stats", lambda: None)()
            if stats:
                lines.append(f"    memory_stats: {stats}")
    except Exception as e:  # the backend may be the thing that died
        lines.append(f"(device enumeration failed: {type(e).__name__}: {e})")
    try:
        from .spill import BufferCatalog
        cat = BufferCatalog.get()
        lines.append(f"spill catalog: device={cat.device_bytes}B "
                     f"host={cat.host_bytes}B spills={cat.spill_count} "
                     f"unspills={cat.unspill_count}")
    except Exception:
        pass
    return "\n".join(lines) + "\n"


def handle_fatal(exc: BaseException, conf=None) -> "FatalDeviceError":
    """Capture diagnostics and build the FatalDeviceError to raise; exits
    the process instead when fatalErrorExit is set (reference executor
    behavior)."""
    from ..config import (FATAL_DUMP_PATH, FATAL_ERROR_EXIT, RapidsConf)
    conf = conf or RapidsConf.get_global()
    STATS["fatal_errors"] += 1
    dump_path = None
    target = str(conf.get(FATAL_DUMP_PATH) or "")
    if target:
        try:
            os.makedirs(target, exist_ok=True)
            import tempfile
            fd, dump_path = tempfile.mkstemp(
                prefix=f"fatal-{int(time.time())}-{os.getpid()}-",
                suffix=".txt", dir=target)
            with os.fdopen(fd, "w") as fh:
                fh.write(_diagnostics(exc))
            STATS["dumps_written"] += 1
        except OSError:
            dump_path = None
    err = FatalDeviceError(
        f"fatal device error (diagnostics: {dump_path or 'not captured'})"
        f": {type(exc).__name__}: {exc}", dump_path)
    if bool(conf.get(FATAL_ERROR_EXIT)):
        # the reference executor exits so the scheduler replaces it
        import sys
        sys.stderr.write(str(err) + "\n")
        sys.stderr.flush()
        os._exit(FATAL_EXIT_CODE)
    return err
