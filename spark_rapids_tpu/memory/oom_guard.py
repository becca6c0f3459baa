"""Real-allocator hookup — the ``DeviceMemoryEventHandler.scala:37``
analog.  XLA owns HBM, so there is no RMM callback to install; instead
every compiled kernel invocation runs under this guard: a runtime
RESOURCE_EXHAUSTED from the device triggers a synchronous spill of the
catalog's device buffers and ONE retry; a second failure surfaces as
``SplitAndRetryOOM`` so the retry framework can halve the operator's
spillable inputs (``RmmRapidsRetryIterator`` contract).
"""

from __future__ import annotations

from typing import Callable

#: observability for tests/metrics
STATS = {"oom_caught": 0, "oom_retry_ok": 0, "oom_split_raised": 0,
         "eager_syncs": 0, "lazy_dispatches": 0}

#: wall-clock until which the guard stays in eager-sync mode after a real
#: device OOM (a sick device earns per-kernel supervision for a while)
_defensive_until = 0.0
_DEFENSIVE_WINDOW_S = 300.0


def _should_sync() -> bool:
    """Decide whether to pay a blocking device sync after this kernel.

    Every ``block_until_ready`` stalls the host until the device drains,
    and XLA pipelines async dispatches — so blocking after every kernel
    serializes the whole query on host<->device round trips.  ``syncMode=auto`` keeps the
    async pipeline when memory pressure is low and flips to per-kernel
    supervision when an OOM is plausible: accounted pool usage above the
    watermark, armed test injection, or a recent real OOM.  A deferred OOM
    surfaces at the next materialization point (the D2H transition or a
    host pull), where the producing kernel can no longer be re-run; the
    session's collect loop recovers with a WHOLE-QUERY retry — by then the
    guard is in its defensive window, so the re-run syncs eagerly and any
    recurring OOM lands inside the failing kernel's own spill-and-retry.
    """
    import time

    from ..config import OOM_SYNC_MODE, OOM_SYNC_WATERMARK, RapidsConf
    conf = RapidsConf.get_global()
    mode = str(conf.get(OOM_SYNC_MODE)).lower()
    if mode == "always":
        return True
    if mode == "never":
        return False
    # auto:
    if time.monotonic() < _defensive_until:
        return True
    from .retry import injection_state
    st = injection_state()
    if st.retry_ooms or st.split_ooms:
        return True
    try:
        from .device import DeviceManager
        from .spill import BufferCatalog
        cat = BufferCatalog.get()
        limit = DeviceManager.get().pool_limit_bytes()
        if limit > 0 and cat.device_bytes >= limit * float(
                conf.get(OOM_SYNC_WATERMARK)):
            return True
    except Exception:  # pragma: no cover — accounting must never kill a query
        return True
    return False


def is_device_oom(exc: BaseException) -> bool:
    """Heuristic match of PjRt/XLA allocation failures (the error type
    lives in jaxlib and its message carries RESOURCE_EXHAUSTED / OOM)."""
    name = type(exc).__name__
    msg = str(exc)
    if name == "XlaRuntimeError" or "XlaRuntimeError" in name:
        return ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
                or "out of memory" in msg or "OOM" in msg)
    return False


def guard_device_oom(fn: Callable, retriable: bool = True) -> Callable:
    """Wrap a compiled kernel: on device OOM, spill-all + retry once, then
    escalate to SplitAndRetryOOM (input halving).

    ``retriable=False`` is the donated-buffer contract (whole-stage
    donation, docs/whole_stage.md): a call whose inputs were donated to
    XLA cannot be re-run with the same arguments — the donor buffers are
    already invalid — so the guard spills and escalates immediately; the
    session's whole-query retry loop re-materializes the inputs."""

    def _sync(result, force: bool = False):
        # jit dispatch is ASYNC: an execution-time OOM surfaces when the
        # result is consumed, which would be outside this guard — force
        # materialization so the failure lands in our try block.  Under
        # low memory pressure (syncMode=auto) the sync is skipped so the
        # dispatch pipeline stays async; a deferred OOM is
        # caught at the next materialization point and flips the guard
        # into a defensive eager window.
        if not force and not _should_sync():
            STATS["lazy_dispatches"] += 1
            return result
        STATS["eager_syncs"] += 1
        try:
            import jax
            return jax.block_until_ready(result)
        except ImportError:  # pragma: no cover
            return result

    def wrapped(*args, **kwargs):
        try:
            return _sync(fn(*args, **kwargs))
        except Exception as e:  # noqa: BLE001 — filtered below
            if not is_device_oom(e):
                from .fatal import handle_fatal, is_fatal_device_error
                if is_fatal_device_error(e):
                    # device state unknown: capture diagnostics,
                    # don't enter the spill/retry protocol
                    from ..sql.physical.base import TaskContext
                    task = TaskContext.current()
                    raise handle_fatal(
                        e, conf=task.conf if task else None) from e
                raise
            STATS["oom_caught"] += 1
            global _defensive_until
            import time as _time
            _defensive_until = _time.monotonic() + _DEFENSIVE_WINDOW_S
            from .spill import BufferCatalog
            BufferCatalog.get().spill_all_device()
            if not retriable:
                # donated inputs are gone; escalate without a same-args
                # retry (the whole-query retry re-plans and re-runs)
                STATS["oom_split_raised"] += 1
                from .retry import SplitAndRetryOOM
                raise SplitAndRetryOOM(
                    f"device OOM in a donated-buffer program (inputs "
                    f"invalidated, same-args retry impossible): {e}"
                ) from None
            try:
                result = _sync(fn(*args, **kwargs), force=True)
            except Exception as e2:  # noqa: BLE001
                if is_device_oom(e2):
                    STATS["oom_split_raised"] += 1
                    from .retry import SplitAndRetryOOM
                    raise SplitAndRetryOOM(
                        f"device OOM persisted after spilling all "
                        f"buffers: {e2}") from None
                # the retry itself may hit a WEDGED device (the exact
                # scenario fatal handling exists for)
                from .fatal import handle_fatal, is_fatal_device_error
                if is_fatal_device_error(e2):
                    from ..sql.physical.base import TaskContext
                    task = TaskContext.current()
                    raise handle_fatal(
                        e2, conf=task.conf if task else None) from e2
                raise
            STATS["oom_retry_ok"] += 1
            return result

    wrapped.__name__ = getattr(fn, "__name__", "kernel")
    return wrapped
