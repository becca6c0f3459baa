"""Device manager — knows the executor's chips and sizes the buffer pool, the
analog of ``GpuDeviceManager.scala:150,275``.  Where the reference creates an RMM
pool of ``allocFraction × free-memory`` minus a reserve, the TPU runtime has
no user-managed allocator: XLA/PjRt owns HBM.  What we manage is the
*accounted* pool: every live ``ColumnarBatch`` registered with the
:class:`~spark_rapids_tpu.memory.spill.BufferCatalog` counts against the pool
limit computed here, and crossing it triggers synchronous spill — the same
contract ``DeviceMemoryEventHandler.scala:37`` provides via RMM callbacks.
"""

from __future__ import annotations

import threading
from typing import Optional, Tuple

from ..config import ALLOC_FRACTION, RESERVE_BYTES, RapidsConf

#: accounted pool size on the CPU platform, whose devices report no memory
#: stats (the test suite).  An accelerator that reports none is an error.
_CPU_POOL_BYTES = 16 << 30


class DeviceManager:
    _instance: Optional["DeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf: Optional[RapidsConf] = None,
                 pool_limit_override: Optional[int] = None):
        conf = conf or RapidsConf.get_global()
        self.alloc_fraction = float(conf.get(ALLOC_FRACTION))
        self.reserve_bytes = int(conf.get(RESERVE_BYTES))
        self._pool_limit_override = pool_limit_override
        #: the host's chips, chip 0 first (every local device)
        self._chips: Optional[Tuple] = None
        self._hbm_bytes: Optional[int] = None

    # --- singleton --------------------------------------------------------
    @classmethod
    def initialize(cls, conf: Optional[RapidsConf] = None,
                   pool_limit_override: Optional[int] = None
                   ) -> "DeviceManager":
        with cls._lock:
            cls._instance = cls(conf, pool_limit_override)
            return cls._instance

    @classmethod
    def get(cls) -> "DeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls()
            return cls._instance

    @classmethod
    def shutdown(cls):
        with cls._lock:
            cls._instance = None

    # --- device info -------------------------------------------------------
    @property
    def chips(self) -> Tuple:
        """Every chip of this executor, chip 0 first."""
        if self._chips is None:
            import jax
            self._chips = tuple(jax.local_devices())
        return self._chips

    @property
    def device(self):
        """Chip 0: where everything lies while partitions are not spread
        (``parallel/placement.py`` says when they are)."""
        return self.chips[0]

    def hbm_bytes(self) -> int:
        if self._hbm_bytes is None:
            stats = self.device.memory_stats()
            if stats and stats.get("bytes_limit"):
                self._hbm_bytes = int(stats["bytes_limit"])
            elif self.device.platform == "cpu":
                self._hbm_bytes = _CPU_POOL_BYTES
            else:
                raise RuntimeError(
                    f"{self.device} reports no memory_stats()['bytes_limit']"
                    f"; the buffer pool cannot be sized")
        return self._hbm_bytes

    def pool_limit_bytes(self) -> int:
        """The accounted pool: ONE chip's share, also where partitions are
        spread over several chips (``parallel/placement.py``).  The
        catalog keeps one ledger for all of them, so while their sum fits
        one chip's pool no chip can be over its own; several executors
        spill earlier than each would alone."""
        if self._pool_limit_override is not None:
            return self._pool_limit_override
        limit = int(self.hbm_bytes() * self.alloc_fraction) - self.reserve_bytes
        return max(limit, 1 << 20)

    def bytes_in_use(self, chip=None) -> int:
        """What the backend holds on ``chip``, or on the fullest of the
        host's chips (the pool is one chip's: it is the fullest that must
        fit)."""
        if chip is None:
            return max(self.bytes_in_use(c) for c in self.chips)
        stats = chip.memory_stats()
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"])
        return 0
