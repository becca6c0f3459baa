"""Backend transitions + batch coalescing (reference
``GpuRowToColumnarExec``/``GpuColumnarToRowExec``/``HostColumnarToGpu``/
``GpuCoalesceBatches``; SURVEY §2.2).

Here both backends are columnar (host = numpy, device = jnp), so transitions
are pure buffer moves: one ``device_put`` per column upload, one fetch per
download — no row format in the middle.

With ``spark.rapids.tpu.transfer.doubleBuffer.enabled`` both transitions
pipeline: a one-slot stager thread carries transfer N+1 while batch N is
consumed downstream (≤ 1 transfer in flight ahead of the consumer — the
reference's stream-overlapped copy model).  The child is pulled on the
CALLING thread (a one-batch lookahead), so thread-local seams —
speculation registration, OOM-injection arming — stay on the task thread;
only the transfer itself moves to the stager.  Exceptions raised in the
stager (device OOM, injected chaos faults) re-raise on the consumer with
their original type via ``Future.result()``.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, List

import numpy as np

from ...columnar.batch import ColumnarBatch
from ...config import TRANSFER_DOUBLE_BUFFER
from ...observability import tracer as _trace
from .base import CPU, TPU, PhysicalPlan, TaskContext


def batch_nbytes(batch: ColumnarBatch) -> int:
    total = 0
    for c in batch.columns:
        for arr in (c.data, c.validity, c.lengths, c.aux):
            if arr is not None:
                total += arr.size * arr.dtype.itemsize
    return total


def _staged(it, transfer, name: str):
    """Shared double-buffer loop: pull batch N+1 from ``it`` on the
    calling thread, dispatch its ``transfer`` on the one-slot stager,
    THEN yield batch N's completed result — ≤ 1 transfer in flight ahead
    of the consumer.  The stager brackets itself on the tracer's exec
    stack so its spans attribute to the owning transition.  While a
    transfer is in flight its input batch is pinned in the retention
    registry (donation-safety: a staged batch is held by two threads)."""
    from ...memory import retention as _ret

    def _carried(item):
        # D2H pairs each batch with its speculation checks — pin the batch
        return item[0] if isinstance(item, tuple) else item

    def run(batch):
        _trace.push_exec(name)
        try:
            return transfer(batch)
        finally:
            _trace.pop_exec()

    from ...serving import lifecycle as _lc
    with ThreadPoolExecutor(max_workers=1,
                            thread_name_prefix=f"srt-{name}") as stager:
        fut = None
        fut_in = None
        try:
            for batch in it:
                # lifecycle poll site `stager`: a cancelled query stops
                # feeding transfers; the one in-flight transfer completes
                # (bounded) and its pin is released in the finally below
                _lc.check_cancel("stager")
                _ret.pin_batch(_carried(batch))
                nxt = stager.submit(run, batch)
                if fut is not None:
                    out = fut.result()
                    prev_in, fut = fut_in, None
                    _ret.unpin_batch(_carried(prev_in))
                    yield out
                fut, fut_in = nxt, batch
            if fut is not None:
                out = fut.result()
                prev_in, fut = fut_in, None
                _ret.unpin_batch(_carried(prev_in))
                yield out
        finally:
            if fut is not None:
                # cancel/error/early-close with a transfer still staged:
                # wait it out (<= one transfer) and release the pin so
                # retention accounting returns to baseline without the
                # GC reaper; its own failure must not mask the original
                try:
                    fut.result()
                except BaseException:  # noqa: BLE001 - original wins
                    pass
                _ret.unpin_batch(_carried(fut_in))


class HostToDeviceExec(PhysicalPlan):
    backend = TPU

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)

    @property
    def output(self):
        return self.children[0].output

    def execute(self, pid, tctx):
        import jax.numpy as jnp

        from ...shims import tree_map
        from ...robustness import faults as _faults

        from ...memory.retention import mark_transient
        from ...parallel.placement import home_chip, put
        # where partitions are spread, to this partition's home chip
        chip = home_chip(pid, tctx.conf)

        def upload(batch):
            nb = batch_nbytes(batch)
            tctx.inc_metric("h2d_bytes", nb)
            _faults.maybe_inject("transfer.h2d", exc=ConnectionError,
                                 bytes=nb)
            # span covers the upload dispatch only, not downstream
            # consumption of the yielded batch
            with _trace.span("h2d", "HostToDevice.upload", bytes=nb):
                # fresh single-owner device buffers: donation-eligible
                return mark_transient(
                    tree_map(jnp.asarray, batch) if chip is None
                    else put(batch, chip))

        it = self.children[0].execute(pid, tctx)
        if bool(tctx.conf.get(TRANSFER_DOUBLE_BUFFER)):
            tctx.inc_metric("h2dDoubleBuffered", level="DEBUG")
            yield from _staged(it, upload, self.node_name())
            return
        for batch in it:
            yield upload(batch)

    def node_name(self):
        return "HostToDevice"


class DeviceToHostExec(PhysicalPlan):
    backend = CPU

    def __init__(self, child: PhysicalPlan):
        super().__init__(child)

    @property
    def output(self):
        return self.children[0].output

    def execute(self, pid, tctx):
        from ...columnar.prepack import prepacked_device_get
        from ...memory.oom_guard import guard_device_oom
        from . import speculation
        # the fetch is a materialization point: with syncMode=auto a
        # deferred execution-time OOM surfaces HERE, so it runs under the
        # guard's spill-and-retry protocol like any kernel.  The fetch
        # byte-packs the whole batch into ONE device->host transfer, and
        # big batches narrow on device first (columnar/prepack.py)
        fetch = guard_device_oom(prepacked_device_get)

        def fetch_one(batch, pending):
            tctx.inc_metric("d2h_bytes", batch_nbytes(batch))
            # bundle pending speculation scalars into the SAME pull as the
            # result — each separate pull is its own round trip, and
            # this one was happening anyway
            if pending:
                host_b, vals = fetch((batch, [c.ng for c in pending]))
                for c, v in zip(pending, vals):
                    c.resolve(int(v))
                speculation.count_bundled_fetch()
                return host_b
            return fetch(batch)  # ONE concurrent D2H for all leaves

        it = self.children[0].execute(pid, tctx)
        if bool(tctx.conf.get(TRANSFER_DOUBLE_BUFFER)):
            # the pending-check snapshot must happen on the task thread
            # (speculation state is thread-local), so pair each batch with
            # its checks BEFORE handing it to the stager
            def paired():
                for batch in it:
                    yield batch, speculation.unresolved()
            yield from _staged(paired(),
                               lambda bp: fetch_one(bp[0], bp[1]),
                               self.node_name())
            return
        for batch in it:
            yield fetch_one(batch, speculation.unresolved())

    def node_name(self):
        return "DeviceToHost"


class CoalesceBatchesExec(PhysicalPlan):
    """Accumulate small batches up to a target size before handing them to
    size-sensitive operators (the central batching invariant of the
    reference, ``GpuCoalesceBatches.scala`` TargetSize goal)."""

    def __init__(self, child: PhysicalPlan, target_rows: int = 1 << 20,
                 target_bytes: int = 1 << 30, backend=TPU):
        super().__init__(child)
        self.backend = backend
        self.target_rows = target_rows
        self.target_bytes = target_bytes

    @property
    def output(self):
        return self.children[0].output

    def execute(self, pid, tctx):
        pending: List[ColumnarBatch] = []
        rows = 0
        nbytes = 0
        emitted = False
        for batch in self.children[0].execute(pid, tctx):
            n = batch.num_rows_int
            if n == 0:
                continue
            pending.append(batch)
            rows += n
            nbytes += batch_nbytes(batch)
            if rows >= self.target_rows or nbytes >= self.target_bytes:
                emitted = True
                yield (ColumnarBatch.concat(pending) if len(pending) > 1
                       else pending[0])
                pending, rows, nbytes = [], 0, 0
        if pending:
            yield (ColumnarBatch.concat(pending) if len(pending) > 1
                   else pending[0])
        elif not emitted:
            # every input batch was empty (or the child yielded nothing):
            # emit ONE empty batch with the correct schema instead of a
            # zero-batch partition — downstream execs (and the
            # committed-block tracking of the resilient shuffle fetch)
            # must be able to tell "empty partition" from "lost block"
            from .exchange import empty_batch_for
            empty = empty_batch_for(self.output)
            if self.backend == CPU:
                import jax
                empty = jax.device_get(empty)
            yield empty
