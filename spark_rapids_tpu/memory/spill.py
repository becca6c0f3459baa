"""Spill framework — DEVICE→HOST→DISK tiers behind one catalog, the TPU
equivalent of ``RapidsBufferCatalog.scala:62`` + the three stores
(``RapidsDeviceMemoryStore``/``RapidsHostMemoryStore``/``RapidsDiskStore``)
and ``SpillableColumnarBatch.scala:29``.

A registered batch lives in exactly one tier:

* DEVICE — the live jax arrays (accounted against the DeviceManager pool);
* HOST   — numpy copies (accounted against the host spill budget,
  ``spark.rapids.memory.host.spillStorageSize``);
* DISK   — one pickle file per buffer under ``spark.rapids.memory.spillDir``.

``synchronous_spill`` walks buffers lowest-priority-first (the
``SpillPriorities.scala`` contract) device→host, overflowing host→disk when
the host budget is exceeded.  ``get`` transparently unspills
(``RapidsBufferCatalog.unspill`` `:633`).  Everything is thread-safe: the
multithreaded shuffle and IO pools touch the catalog concurrently.
"""

from __future__ import annotations

import errno
import os
import pickle
import threading
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..columnar.batch import ColumnarBatch
from ..config import HOST_SPILL_STORAGE_SIZE, SPILL_DIR, RapidsConf
from ..observability import metrics as _om
from ..observability import tracer as _trace
from ..robustness import faults as _faults
from .device import DeviceManager

#: bounded retries for the disk tier's reads/writes: a transiently torn
#: spill I/O (or a chaos-injected one) re-attempts with a short backoff
#: instead of failing the query; a persistent error still raises
_DISK_IO_ATTEMPTS = 5


class SpillDiskFull(OSError):
    """ENOSPC from the spill disk tier — NON-retriable: a full disk does
    not heal on a millisecond backoff, and retrying five times just
    multiplies the latency of the inevitable.  The overflow path catches
    this and keeps the buffer RESIDENT at host (over-limit but correct)
    instead of failing the query; ``spill_disk_full_total`` counts the
    events."""


def _retry_disk_io(fn, what: str):
    from ..serving import lifecycle as _lc
    delay = 0.001
    for attempt in range(_DISK_IO_ATTEMPTS):
        # lifecycle poll site `spill`: a cancelled query abandons its
        # disk-tier I/O (and the retry backoff) instead of finishing a
        # spill nobody will read
        _lc.check_cancel("spill")
        try:
            return fn()
        except OSError as e:
            if getattr(e, "errno", None) == errno.ENOSPC:
                _om.inc("spill_disk_full_total")
                raise SpillDiskFull(
                    errno.ENOSPC,
                    f"spill disk full during {what}") from e
            if attempt == _DISK_IO_ATTEMPTS - 1:
                raise
            _lc.cancellable_sleep(delay, "spill")
            delay *= 2

# spill order: lower value spills first (SpillPriorities.scala:83 semantics,
# inverted to "priority = keep-on-device desire")
OUTPUT_FOR_SHUFFLE_PRIORITY = -100
HOST_MEMORY_PRIORITY = -50
ACTIVE_BATCHING_PRIORITY = 0
ACTIVE_ON_DECK_PRIORITY = 100

DEVICE, HOST, DISK = "device", "host", "disk"


def batch_device_bytes(batch: ColumnarBatch) -> int:
    """Accounted size: sum of leaf array nbytes."""
    import jax
    total = 0
    from ..shims import tree_flatten
    for leaf in tree_flatten(batch)[0]:
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


@dataclass
class _Buffer:
    handle: int
    tier: str
    size: int
    priority: int
    treedef: Any = None
    leaves: Optional[List[Any]] = None     # device or host arrays
    disk_path: Optional[str] = None
    was_device: bool = True                # False for host-backend batches
    seq: int = 0                           # tie-break: older spills first
    origin: str = ""                       # registration site (debug mode)
    tenant: str = ""                       # registering task's tenant


class BufferCatalog:
    """Handle registry + tiered stores + spill policy (singleton per
    process, like the reference's ``RapidsBufferCatalog.singleton``)."""

    _instance: Optional["BufferCatalog"] = None
    _class_lock = threading.Lock()

    def __init__(self, conf: Optional[RapidsConf] = None):
        conf = conf or RapidsConf.get_global()
        self._lock = threading.RLock()
        self._buffers: Dict[int, _Buffer] = {}
        self._next_handle = 1
        self._seq = 0
        self.host_limit = int(conf.get(HOST_SPILL_STORAGE_SIZE))
        self.spill_dir = str(conf.get(SPILL_DIR))
        self.device_bytes = 0
        self.host_bytes = 0
        self.disk_bytes = 0
        self.spill_count = 0
        self.unspill_count = 0
        from ..config import GPU_DEBUG
        self.debug = bool(conf.get(GPU_DEBUG))
        #: tenant -> device-byte budget for tenant-aware spill ordering
        #: (set by ServingEngine from the admission budgets; 0/absent =
        #: unbudgeted).  Over-budget tenants' buffers spill FIRST.
        self._tenant_budgets: Dict[str, int] = {}
        self._tenant_default_budget = 0

    @classmethod
    def get(cls) -> "BufferCatalog":
        with cls._class_lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    def _debug_enabled(self) -> bool:
        """Live flag: the running task's session conf wins (sessions
        don't mutate the process-global conf — test isolation depends on
        that), else whatever the catalog was constructed with."""
        if self.debug:
            return True
        from ..sql.physical.base import TaskContext
        t = TaskContext.current()
        if t is None:
            return False
        from ..config import GPU_DEBUG
        return bool(t.conf.get(GPU_DEBUG))

    @classmethod
    def reset(cls, conf: Optional[RapidsConf] = None) -> "BufferCatalog":
        with cls._class_lock:
            if cls._instance is not None:
                cls._instance.close_all()
            cls._instance = cls(conf)
            return cls._instance

    def leak_report(self):
        """Still-registered buffers — the MemoryCleaner leak-tracking
        analog (reference Plugin.scala:425-440): after a query finishes
        every SpillableColumnarBatch must have been closed, so anything
        listed here is a leaked handle.  Entries carry the registration
        site when spark.rapids.memory.gpu.debug is on."""
        with self._lock:
            return [{"handle": b.handle, "size": b.size, "tier": b.tier,
                     "origin": b.origin or "(enable "
                     "spark.rapids.memory.gpu.debug for call sites)"}
                    for b in self._buffers.values()]

    # --- registration ------------------------------------------------------
    def add_batch(self, batch: ColumnarBatch,
                  priority: int = ACTIVE_BATCHING_PRIORITY) -> int:
        """Register a batch.  Device-resident batches are charged against the
        accounted pool (spilling others first if needed); host-backend
        (numpy-leaf) batches start at the HOST tier and never count as HBM."""
        import jax
        from ..shims import tree_flatten
        # spill-tier retention pin (donation-safety, memory/retention.py):
        # the registrant's batch shares leaves with the catalog record, so
        # a fused stage must never donate it while registered.  The pin
        # lifts via the registry's GC reaper when the batch object dies.
        from . import retention as _ret
        _ret.pin_batch(batch)
        leaves, treedef = tree_flatten(batch)
        was_device = any(isinstance(l, jax.Array) for l in leaves)
        size = batch_device_bytes(batch)
        if was_device and not self.ensure_headroom(size,
                                                   already_resident=True):
            # even after spilling everything else the batch cannot fit the
            # pool — escalate so the retry framework halves the input
            # (RmmRapidsRetryIterator/GpuOOM contract: the headroom
            # verdict must not be ignored)
            from .retry import SplitAndRetryOOM
            raise SplitAndRetryOOM(
                f"batch of {size} bytes cannot fit the device pool "
                f"(limit {DeviceManager.get().pool_limit_bytes()})")
        origin = ""
        debug = self._debug_enabled()
        if debug:
            import traceback
            for frame in reversed(traceback.extract_stack(limit=8)):
                if "memory/spill.py" not in frame.filename:
                    origin = (f"{frame.filename}:{frame.lineno} "
                              f"{frame.name}")
                    break
        # tenant plumbed from the running task (TaskContext.tenant) so
        # the spill policy can evict the over-budget tenant's batches
        # first (docs/serving.md "pressure-aware degradation")
        from ..sql.physical.base import TaskContext
        _t = TaskContext.current()
        tenant = _t.tenant if _t is not None else ""
        with self._lock:
            h = self._next_handle
            self._next_handle += 1
            self._seq += 1
            tier = DEVICE if was_device else HOST
            self._buffers[h] = _Buffer(h, tier, size, priority, treedef,
                                       list(leaves), was_device=was_device,
                                       seq=self._seq, origin=origin,
                                       tenant=tenant)
            if was_device:
                self.device_bytes += size
            else:
                self.host_bytes += size
        if debug:
            import logging
            logging.getLogger("spark_rapids_tpu.memory").info(
                "buffer +%d %dB tier=%s at %s", h, size, tier, origin)
        return h

    def get_batch(self, handle: int) -> ColumnarBatch:
        """Materialize on the original backend, unspilling if needed."""
        import jax
        with self._lock:
            buf = self._buffers[handle]
            if buf.tier == DISK:
                self._disk_to_host(buf)
            if buf.tier == HOST and buf.was_device:
                self._host_to_device(buf)
            leaves = buf.leaves
            treedef = buf.treedef
        from ..shims import tree_unflatten
        return tree_unflatten(treedef, leaves)

    def remove(self, handle: int):
        with self._lock:
            buf = self._buffers.pop(handle, None)
            if buf is None:
                return
            if buf.tier == DEVICE:
                self.device_bytes -= buf.size
            elif buf.tier == HOST:
                self.host_bytes -= buf.size
            elif buf.tier == DISK:
                self.disk_bytes -= buf.size
                if buf.disk_path and os.path.exists(buf.disk_path):
                    os.unlink(buf.disk_path)

        if self._debug_enabled():
            import logging
            logging.getLogger("spark_rapids_tpu.memory").info(
                "buffer -%d %dB tier=%s", handle, buf.size, buf.tier)

    def close_all(self):
        with self._lock:
            for h in list(self._buffers):
                self.remove(h)

    def tier_of(self, handle: int) -> str:
        with self._lock:
            return self._buffers[handle].tier

    # --- spill policy ------------------------------------------------------
    def set_tenant_budgets(self, budgets: Dict[str, int],
                           default_budget: int = 0) -> None:
        """Install per-tenant device-byte budgets for spill ordering
        (ServingEngine wires the admission budgets here).  Budgets only
        reorder eviction — they never block registration."""
        with self._lock:
            self._tenant_budgets = {k: int(v) for k, v in budgets.items()}
            self._tenant_default_budget = max(0, int(default_budget))

    def _over_budget_tenants(self) -> set:
        """Tenants whose DEVICE-tier registered bytes exceed their budget
        (callers hold the lock).  O(buffers) — spill decisions are rare
        next to the D2H work they trigger."""
        if not self._tenant_budgets and self._tenant_default_budget <= 0:
            return set()
        usage: Dict[str, int] = {}
        for b in self._buffers.values():
            if b.tier == DEVICE and b.tenant:
                usage[b.tenant] = usage.get(b.tenant, 0) + b.size
        over = set()
        for t, used in usage.items():
            budget = int(self._tenant_budgets.get(
                t, self._tenant_default_budget))
            if budget > 0 and used > budget:
                over.add(t)
        return over

    def synchronous_spill(self, target_device_bytes: int) -> int:
        """Spill device buffers until accounted device usage <= target.
        Eviction order is ``(tenant_over_budget, priority, seq)``: an
        over-budget tenant's batches spill FIRST (tenant-aware pressure
        response, docs/serving.md), then lowest priority, oldest first
        (the ``RapidsBufferCatalog.synchronousSpill`` `:589` contract)."""
        spilled = 0
        with self._lock:
            over = self._over_budget_tenants()
            candidates = sorted(
                (b for b in self._buffers.values() if b.tier == DEVICE),
                key=lambda b: (0 if b.tenant in over else 1,
                               b.priority, b.seq))
            for buf in candidates:
                if self.device_bytes <= target_device_bytes:
                    break
                self._device_to_host(buf)
                spilled += buf.size
                self.spill_count += 1
        return spilled

    def ensure_headroom(self, request_bytes: int,
                        already_resident: bool = False) -> bool:
        """Make room for an incoming allocation; the DeviceMemoryEventHandler
        equivalent.  Returns True if the request now fits the pool.

        Pressure is judged on BOTH the accounted registered bytes and the
        backend's actual ``bytes_in_use`` (live kernel intermediates the
        bookkeeping cannot see), so a real chip near HBM exhaustion spills
        even when the catalog's own ledger looks comfortable.
        ``already_resident``: the requested bytes are ALREADY on device
        (add_batch registering a computed batch) — real usage must not
        count them twice."""
        dm = DeviceManager.get()
        limit = dm.pool_limit_bytes()

        def used_now():
            real = dm.bytes_in_use()
            if not already_resident:
                real += request_bytes
            return max(self.device_bytes + request_bytes, real)

        with self._lock:
            if used_now() <= limit:
                return True
            self.synchronous_spill(max(0, limit - request_bytes))
            return used_now() <= limit

    def spill_all_device(self) -> int:
        return self.synchronous_spill(0)

    # --- tier movement (callers hold the lock) -----------------------------
    def _device_to_host(self, buf: _Buffer):
        import jax
        # one concurrent D2H for all leaves (per-array pulls each cost a
        # full host<->device round trip)
        with _trace.span("spill", "spill.deviceToHost", bytes=buf.size):
            buf.leaves = list(jax.device_get(buf.leaves))
        _om.inc("spill_bytes_total", buf.size, dir="deviceToHost")
        buf.tier = HOST
        self.device_bytes -= buf.size
        self.host_bytes += buf.size
        if self.host_bytes > self.host_limit:
            self._overflow_host_to_disk()

    def _overflow_host_to_disk(self):
        candidates = sorted(
            (b for b in self._buffers.values() if b.tier == HOST),
            key=lambda b: (b.priority, b.seq))
        for buf in candidates:
            if self.host_bytes <= self.host_limit:
                break
            try:
                self._host_to_disk(buf)
            except SpillDiskFull:
                # disk-full fallback: keep this (and the remaining
                # lowest-priority) buffers resident at host — the tier
                # runs over its limit, loudly, rather than failing the
                # query on an unwritable spill
                break

    def _host_to_disk(self, buf: _Buffer):
        os.makedirs(self.spill_dir, exist_ok=True)
        path = os.path.join(self.spill_dir, f"buf-{uuid.uuid4().hex}.spill")

        def _write():
            # the chaos site sits inside the retried closure so every
            # attempt re-draws its own seeded decision
            _faults.maybe_inject("spill.disk_write", exc=OSError,
                                 bytes=buf.size)
            with open(path, "wb") as f:
                pickle.dump(buf.leaves, f, protocol=pickle.HIGHEST_PROTOCOL)
        with _trace.span("spill", "spill.hostToDisk", bytes=buf.size):
            try:
                _retry_disk_io(_write, "spill.disk_write")
            except SpillDiskFull:
                try:
                    os.unlink(path)   # a partial file must not leak
                except OSError:
                    pass
                raise
        _om.inc("spill_bytes_total", buf.size, dir="hostToDisk")
        buf.leaves = None
        buf.disk_path = path
        buf.tier = DISK
        self.host_bytes -= buf.size
        self.disk_bytes += buf.size

    def _disk_to_host(self, buf: _Buffer):
        def _read():
            _faults.maybe_inject("spill.disk_read", exc=OSError,
                                 bytes=buf.size)
            with open(buf.disk_path, "rb") as f:
                return pickle.load(f)
        with _trace.span("spill", "spill.diskToHost", bytes=buf.size):
            buf.leaves = _retry_disk_io(_read, "spill.disk_read")
        _om.inc("spill_bytes_total", buf.size, dir="diskToHost")
        os.unlink(buf.disk_path)
        buf.disk_path = None
        buf.tier = HOST
        self.disk_bytes -= buf.size
        self.host_bytes += buf.size
        self.unspill_count += 1

    def _host_to_device(self, buf: _Buffer):
        import jax
        # a False verdict here is deliberately tolerated (transient
        # oversubscription): the split path itself must materialize a
        # too-big parent to slice it, so raising would deadlock recovery —
        # a real allocation failure during unspill is caught by the
        # kernel-level oom_guard on the next device op instead
        self.ensure_headroom(buf.size)
        with _trace.span("spill", "spill.unspillToDevice", bytes=buf.size):
            buf.leaves = [jax.device_put(l) if isinstance(l, np.ndarray)
                          else l for l in buf.leaves]
        _om.inc("spill_bytes_total", buf.size, dir="unspillToDevice")
        buf.tier = DEVICE
        self.host_bytes -= buf.size
        self.device_bytes += buf.size
        self.unspill_count += 1


class SpillableColumnarBatch:
    """Owns a batch registered with the catalog; the working-set currency of
    out-of-core operators (``SpillableColumnarBatch.scala:29,192``).  While
    an operator isn't actively computing on a batch it holds one of these,
    so the catalog may demote it under memory pressure."""

    def __init__(self, handle: int, num_rows: Optional[int], size: int,
                 catalog: BufferCatalog,
                 priority: int = ACTIVE_BATCHING_PRIORITY):
        self._handle: Optional[int] = handle
        self._num_rows = num_rows
        self.size_bytes = size
        self.priority = priority
        self._catalog = catalog

    @property
    def num_rows(self) -> int:
        """Host row count, pulled LAZILY: registering a batch whose count
        only exists on the device must not cost a device sync unless
        someone actually needs the number."""
        if self._num_rows is None:
            self._num_rows = self.get().num_rows_int
        return self._num_rows

    @staticmethod
    def create(batch: ColumnarBatch,
               priority: int = ACTIVE_BATCHING_PRIORITY,
               catalog: Optional[BufferCatalog] = None
               ) -> "SpillableColumnarBatch":
        catalog = catalog or BufferCatalog.get()
        size = batch_device_bytes(batch)
        h = catalog.add_batch(batch, priority)
        return SpillableColumnarBatch(h, getattr(batch, "_nrows_host", None),
                                      size, catalog, priority)

    @property
    def catalog(self) -> BufferCatalog:
        return self._catalog

    def get(self) -> ColumnarBatch:
        if self._handle is None:
            raise ValueError("SpillableColumnarBatch already closed")
        batch = self._catalog.get_batch(self._handle)
        if self._num_rows is not None:
            # the catalog rebuilds the batch from its leaves: hand the
            # registrant's host-known count on, so no reader syncs for it
            batch.with_known_rows(self._num_rows)
        return batch

    def get_and_close(self) -> ColumnarBatch:
        b = self.get()
        self.close()
        return b

    def close(self):
        if self._handle is not None:
            self._catalog.remove(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
