"""Device manager — selects the chip and sizes the buffer pool, the analog
of ``GpuDeviceManager.scala:150,275``.  Where the reference creates an RMM
pool of ``allocFraction × free-memory`` minus a reserve, the TPU runtime has
no user-managed allocator: XLA/PjRt owns HBM.  What we manage is the
*accounted* pool: every live ``ColumnarBatch`` registered with the
:class:`~spark_rapids_tpu.memory.spill.BufferCatalog` counts against the pool
limit computed here, and crossing it triggers synchronous spill — the same
contract ``DeviceMemoryEventHandler.scala:37`` provides via RMM callbacks.
"""

from __future__ import annotations

import threading
from typing import Optional

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
        self._device = None
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
    def device(self):
        if self._device is None:
            import jax
            self._device = jax.local_devices()[0]
        return self._device

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
        if self._pool_limit_override is not None:
            return self._pool_limit_override
        limit = int(self.hbm_bytes() * self.alloc_fraction) - self.reserve_bytes
        return max(limit, 1 << 20)

    def bytes_in_use(self) -> int:
        stats = self.device.memory_stats()
        if stats and stats.get("bytes_in_use") is not None:
            return int(stats["bytes_in_use"])
        return 0
