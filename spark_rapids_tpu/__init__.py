"""spark_rapids_tpu — TPU-native columnar SQL acceleration framework.

A ground-up TPU/XLA re-design of the capabilities of the RAPIDS Accelerator
for Apache Spark (reference at /root/reference): a columnar dataframe/SQL
engine whose physical plans are rewritten so that supported operators execute
on TPUs as columnar batches via JAX/XLA (with Pallas kernels for hot ops),
falling back to a host (Arrow/numpy) engine per-operator when anything is
unsupported, while targeting bit-identical results to the host engine.
"""

__version__ = "0.3.0"

import jax as _jax

# Spark semantics are 64-bit (bigint, double, timestamp-micros); JAX defaults
# to 32-bit, so x64 must be on before any array is created.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache.  Compiling is the dominant cold-start
# cost on the chip (minutes per sort program at SF1 buckets), so every entry
# point shares one cache across processes and runs.  Where
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and this package
# touches no cache setting.  Otherwise the cache is <checkout>/.jax_cache,
# flat: the path is part of what a later run must find again.  Processes
# started for the CPU platform (JAX_PLATFORMS=cpu: the test suite, CI rigs)
# skip it — XLA:CPU AOT entries are machine-feature specific and cheap to
# redo.
import os as _os

_CACHE_DIR_FROM_ENV = bool(_os.environ.get("JAX_COMPILATION_CACHE_DIR"))


def _default_cache_dir() -> str:
    return _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), ".jax_cache")


if (not _CACHE_DIR_FROM_ENV
        and _os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu"):
    _os.makedirs(_default_cache_dir(), exist_ok=True)
    _jax.config.update("jax_compilation_cache_dir", _default_cache_dir())
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


def compile_cache_dir():
    """The persistent compile-cache directory in effect (None = off)."""
    return _jax.config.jax_compilation_cache_dir


from .types import (  # noqa: F401
    BOOLEAN, BYTE, SHORT, INT, LONG, FLOAT, DOUBLE, STRING, BINARY, DATE,
    TIMESTAMP, NULL, ArrayType, BinaryType, BooleanType, ByteType, DataType,
    DateType, DecimalType, DoubleType, FloatType, IntegerType, LongType,
    MapType, NullType, ShortType, StringType, StructField, StructType,
    TimestampType)
from .config import RapidsConf  # noqa: F401
from .columnar import ColumnarBatch, DeviceColumn  # noqa: F401


def pin_host_platform() -> None:
    """Flip this process to the CPU platform, and drop the package's own
    persistent compile cache with it (XLA:CPU AOT entries are machine-
    feature specific).  For multi-process rigs whose executors cannot
    share one chip.  A cache directory given from outside
    (JAX_COMPILATION_CACHE_DIR) is left alone."""
    _jax.config.update("jax_platforms", "cpu")
    if not _CACHE_DIR_FROM_ENV:
        _jax.config.update("jax_compilation_cache_dir", None)


def session(conf=None, **conf_kwargs):
    """Create (or get) the TpuSession — entry point of the user API."""
    try:
        from .sql.session import TpuSession
    except ImportError as e:  # pragma: no cover
        raise NotImplementedError(
            "the sql session layer is not available in this build") from e
    return TpuSession.get_or_create(conf, **conf_kwargs)
