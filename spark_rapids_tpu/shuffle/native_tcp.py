"""Native (C++) cross-process shuffle transport — ctypes binding to
``native/srt_transport.cpp``.

The data plane runs in C++: an epoll progress thread serves block
fetches (the reference's UCX module is exactly this split — Spark-RPC
control plane on the JVM, native transport underneath; ``UCX.scala:105``
single progress thread), and fetches go through a pooled native client.
The wire protocol matches the Python :class:`~.tcp.TcpShuffleTransport`
byte-for-byte, so native and Python executors interoperate in one job.

The Python implementation remains the fallback wherever the toolchain or
the shared library is unavailable (``available()`` gates selection in the
shuffle manager).
"""

from __future__ import annotations

import ctypes
import threading
from typing import Dict, List, Optional

from ..robustness import faults as _faults
from .tcp import ShuffleFetchFailed, _conf_timeouts
from .transport import BlockId, PeerInfo, ShuffleTransport

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

_FOUND, _MISSING, _NETFAIL = 0, 1, 2


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        from ..native._loader import load
        lib = load("libsrt_transport.so", "srt_transport.cpp",
                   extra_flags=("-pthread",))
        if lib is None:
            return None
        i64, u64p, u8pp = (ctypes.c_int64, ctypes.POINTER(ctypes.c_uint64),
                           ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)))
        lib.srt_shuffle_server_start.restype = i64
        lib.srt_shuffle_server_start.argtypes = [ctypes.c_char_p,
                                                 ctypes.c_int]
        lib.srt_shuffle_server_port.restype = ctypes.c_int
        lib.srt_shuffle_server_port.argtypes = [i64]
        lib.srt_shuffle_server_publish.argtypes = [
            i64, i64, i64, i64, ctypes.c_char_p, ctypes.c_uint64]
        lib.srt_shuffle_server_get.restype = ctypes.c_int
        lib.srt_shuffle_server_get.argtypes = [i64, i64, i64, i64, u8pp,
                                               u64p]
        lib.srt_shuffle_server_block_count.restype = i64
        lib.srt_shuffle_server_block_count.argtypes = [i64, i64]
        lib.srt_shuffle_server_block_list.restype = i64
        lib.srt_shuffle_server_block_list.argtypes = [
            i64, i64, ctypes.POINTER(ctypes.c_int64), i64]
        lib.srt_shuffle_server_clear.argtypes = [i64, i64]
        lib.srt_shuffle_server_stop.argtypes = [i64]
        lib.srt_shuffle_client_new.restype = i64
        lib.srt_shuffle_client_fetch.restype = ctypes.c_int
        lib.srt_shuffle_client_fetch.argtypes = [
            i64, ctypes.c_char_p, ctypes.c_int, i64, i64, i64, u8pp, u64p]
        lib.srt_shuffle_client_close.argtypes = [i64]
        if hasattr(lib, "srt_shuffle_client_set_timeout_ms"):
            lib.srt_shuffle_client_set_timeout_ms.argtypes = [
                i64, ctypes.c_int]
        lib.srt_transport_buf_free.argtypes = [
            ctypes.POINTER(ctypes.c_uint8)]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def _take_buffer(lib, ptr, n: int) -> bytes:
    try:
        return ctypes.string_at(ptr, n)
    finally:
        lib.srt_transport_buf_free(ptr)


class NativeTcpShuffleTransport(ShuffleTransport):
    """SPI implementation backed by the C++ epoll server + pooled client.

    Semantics mirror the Python transport exactly: ``fetch`` returns the
    frame, ``None`` when the peer authoritatively reports the block
    missing, and raises :class:`ShuffleFetchFailed` on network failure.
    """

    def __init__(self, executor_id: str = "exec-0", host: str = "127.0.0.1",
                 port: int = 0, read_timeout_s: Optional[float] = None):
        lib = _load()
        if lib is None:
            raise RuntimeError("native transport library unavailable")
        self._lib = lib
        self.executor_id = executor_id
        self._host = host
        self._server = lib.srt_shuffle_server_start(host.encode(), port)
        if self._server < 0:
            raise RuntimeError(f"cannot bind native block server on "
                               f"{host}:{port}")
        self._port = lib.srt_shuffle_server_port(self._server)
        self._client = lib.srt_shuffle_client_new()
        # conf-driven socket timeout (guarded: a stale prebuilt .so from
        # before the setter existed keeps its baked-in 10s default)
        _, read_s = _conf_timeouts(None, read_timeout_s)
        if hasattr(lib, "srt_shuffle_client_set_timeout_ms"):
            lib.srt_shuffle_client_set_timeout_ms(
                self._client, int(read_s * 1000))
        self._closed = False

    @property
    def endpoint(self) -> str:
        return f"{self._host}:{self._port}"

    # --- SPI --------------------------------------------------------------
    def publish(self, executor_id: str, block: BlockId, frame: bytes) -> None:
        self._lib.srt_shuffle_server_publish(
            self._server, block.shuffle_id, block.map_id, block.reduce_id,
            frame, len(frame))

    def fetch(self, peer: PeerInfo, block: BlockId) -> Optional[bytes]:
        lib = self._lib
        _faults.maybe_inject("shuffle.fetch", exc=ShuffleFetchFailed,
                             peer=peer.executor_id, block=str(block))
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        n = ctypes.c_uint64()
        if peer.executor_id == self.executor_id or peer.endpoint in (
                "local", self.endpoint):
            rc = lib.srt_shuffle_server_get(
                self._server, block.shuffle_id, block.map_id,
                block.reduce_id, ctypes.byref(ptr), ctypes.byref(n))
            return _take_buffer(lib, ptr, n.value) if rc == _FOUND else None
        host, port = peer.endpoint.rsplit(":", 1)
        rc = lib.srt_shuffle_client_fetch(
            self._client, host.encode(), int(port), block.shuffle_id,
            block.map_id, block.reduce_id, ctypes.byref(ptr),
            ctypes.byref(n))
        if rc == _FOUND:
            return _take_buffer(lib, ptr, n.value)
        if rc == _MISSING:
            return None
        raise ShuffleFetchFailed(
            f"cannot fetch block {block} from {peer.executor_id} "
            f"({peer.endpoint})")

    def blocks_of(self, executor_id: str) -> List[BlockId]:
        lib = self._lib
        cap = lib.srt_shuffle_server_block_count(self._server, -1)
        if cap <= 0:
            return []
        out = (ctypes.c_int64 * (3 * cap))()
        got = lib.srt_shuffle_server_block_list(self._server, -1, out, cap)
        return [BlockId(out[3 * i], out[3 * i + 1], out[3 * i + 2])
                for i in range(got)]

    def clear(self, shuffle_id: Optional[int] = None):
        self._lib.srt_shuffle_server_clear(
            self._server, -1 if shuffle_id is None else shuffle_id)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._lib.srt_shuffle_client_close(self._client)
        self._lib.srt_shuffle_server_stop(self._server)
