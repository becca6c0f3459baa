"""ctypes binding to the native host-kernel library (``native/``), the
JNI-layer analog of the reference (SURVEY §2.10).  The library is built
lazily with g++ on first use and cached next to the sources; every entry
point has a pure-Python fallback so the framework still runs where no
toolchain exists (callers check ``available()``)."""

from __future__ import annotations

import ctypes
import threading
from typing import Optional

import numpy as np

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        from ._loader import load
        lib = load("libsrt_native.so", "srt_native.cpp")
        if lib is None:
            return None
        _register(lib)
        _lib = lib
        return _lib


#: (name, restype, argtypes) for every exported symbol
_SYMBOLS = [
    ("srt_pack_strings", None,
     [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
      ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]),
    ("srt_unpack_strings", ctypes.c_int64,
     [ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
      ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p]),
    ("srt_byte_array_walk", ctypes.c_int64,
     [ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
      ctypes.c_void_p, ctypes.c_void_p]),
    ("srt_murmur3_i32", None,
     [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32,
      ctypes.c_void_p]),
    ("srt_murmur3_i64", None,
     [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32,
      ctypes.c_void_p]),
    ("srt_murmur3_bytes", ctypes.c_int32,
     [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint32]),
    ("srt_xxhash64_bytes", ctypes.c_uint64,
     [ctypes.c_void_p, ctypes.c_int64, ctypes.c_uint64]),
]


def _register(lib: ctypes.CDLL) -> None:
    """Declare symbol signatures PER SYMBOL: a stale prebuilt .so missing
    only newer symbols keeps its older fast paths; wrappers for absent
    symbols degrade to pure Python via :func:`_sym`."""
    for name, restype, argtypes in _SYMBOLS:
        try:
            fn = getattr(lib, name)
        except AttributeError:
            continue
        if restype is not None:
            fn.restype = restype
        fn.argtypes = argtypes


def _sym(name: str):
    """The ctypes function for ``name``, or None when the lib or the
    symbol is unavailable (pure-Python fallback)."""
    lib = _load()
    if lib is None:
        return None
    try:
        return getattr(lib, name)
    except AttributeError:
        return None


def available() -> bool:
    return _load() is not None


def has(name: str) -> bool:
    """Whether a specific exported symbol is loadable (stale prebuilt
    libraries may lack newer symbols while keeping the rest)."""
    return _sym(name) is not None


# ---------------------------------------------------------------------------
# wrappers
# ---------------------------------------------------------------------------

def pack_strings(flat: np.ndarray, offsets: np.ndarray, width: int,
                 capacity: int):
    """(matrix uint8[capacity, width], lens int32[capacity]) from
    concatenated bytes + int64 offsets[n+1]."""
    lib = _load()
    n = len(offsets) - 1
    if lib is None or n == 0:
        return None
    matrix = np.zeros((capacity, width), dtype=np.uint8)
    lens = np.zeros(capacity, dtype=np.int32)
    flat = np.ascontiguousarray(flat, dtype=np.uint8)
    offsets = np.ascontiguousarray(offsets, dtype=np.int64)
    lib.srt_pack_strings(
        flat.ctypes.data, offsets.ctypes.data, n, width,
        matrix.ctypes.data, lens.ctypes.data)
    return matrix, lens


def byte_array_walk(data: np.ndarray, n: int):
    """(starts int64[n], lens int32[n]) for a PLAIN BYTE_ARRAY section
    (u32le length-prefixed values); None when the native lib is absent,
    raises ValueError on a truncated/overrunning section."""
    fn = _sym("srt_byte_array_walk")
    if fn is None:
        return None
    data = np.ascontiguousarray(data, dtype=np.uint8)
    starts = np.empty(n, dtype=np.int64)
    lens = np.empty(n, dtype=np.int32)
    used = fn(data.ctypes.data, len(data), n,
              starts.ctypes.data, lens.ctypes.data)
    if used < 0:
        raise ValueError("truncated BYTE_ARRAY section")
    return starts, lens


def unpack_strings(matrix: np.ndarray, lens: np.ndarray, n: int):
    """(flat uint8, offsets int64[n+1]) from a padded byte matrix."""
    lib = _load()
    if lib is None:
        return None
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    lens32 = np.ascontiguousarray(lens[:n], dtype=np.int32)
    total = int(np.minimum(lens32, matrix.shape[1]).sum())
    flat = np.empty(total, dtype=np.uint8)
    offsets = np.empty(n + 1, dtype=np.int64)
    lib.srt_unpack_strings(matrix.ctypes.data, lens32.ctypes.data, n,
                           matrix.shape[1], flat.ctypes.data,
                           offsets.ctypes.data)
    return flat, offsets


def murmur3_i64(vals: np.ndarray, seed: int) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    out = np.empty(len(vals), dtype=np.int32)
    lib.srt_murmur3_i64(vals.ctypes.data, len(vals),
                        np.uint32(seed), out.ctypes.data)
    return out


def murmur3_i32(vals: np.ndarray, seed: int) -> Optional[np.ndarray]:
    lib = _load()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.int32)
    out = np.empty(len(vals), dtype=np.int32)
    lib.srt_murmur3_i32(vals.ctypes.data, len(vals),
                        np.uint32(seed), out.ctypes.data)
    return out


def murmur3_bytes(data: bytes, seed: int) -> Optional[int]:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    return int(lib.srt_murmur3_bytes(
        buf.ctypes.data if len(buf) else None, len(buf), np.uint32(seed)))


def xxhash64_bytes(data, seed: int = 0) -> int:
    """Frame checksum; falls back to a pure-Python xxhash64 so the wire
    format is identical with or without the native library."""
    lib = _load()
    if lib is not None:
        buf = np.frombuffer(data, dtype=np.uint8)
        return int(lib.srt_xxhash64_bytes(
            buf.ctypes.data if len(buf) else None, len(buf),
            np.uint64(seed)))
    return _xxhash64_py(bytes(data), seed)


# --- pure-Python xxhash64 (fallback; identical output) ----------------------

_P1 = 0x9E3779B185EBCA87
_P2 = 0xC2B2AE3D27D4EB4F
_P3 = 0x165667B19E3779F9
_P4 = 0x85EBCA77C2B2AE63
_P5 = 0x27D4EB2F165667C5
_M64 = (1 << 64) - 1


def _rotl(x, r):
    return ((x << r) | (x >> (64 - r))) & _M64


def _round(acc, inp):
    acc = (acc + inp * _P2) & _M64
    return (_rotl(acc, 31) * _P1) & _M64


def _merge(acc, val):
    acc ^= _round(0, val)
    return (acc * _P1 + _P4) & _M64


def _xxhash64_py(data: bytes, seed: int) -> int:
    import struct
    n = len(data)
    pos = 0
    if n >= 32:
        v1 = (seed + _P1 + _P2) & _M64
        v2 = (seed + _P2) & _M64
        v3 = seed & _M64
        v4 = (seed - _P1) & _M64
        while pos + 32 <= n:
            a, b, c, d = struct.unpack_from("<QQQQ", data, pos)
            v1, v2 = _round(v1, a), _round(v2, b)
            v3, v4 = _round(v3, c), _round(v4, d)
            pos += 32
        h = (_rotl(v1, 1) + _rotl(v2, 7) + _rotl(v3, 12)
             + _rotl(v4, 18)) & _M64
        for v in (v1, v2, v3, v4):
            h = _merge(h, v)
    else:
        h = (seed + _P5) & _M64
    h = (h + n) & _M64
    while pos + 8 <= n:
        (k,) = struct.unpack_from("<Q", data, pos)
        h = ((_rotl(h ^ _round(0, k), 27) * _P1) + _P4) & _M64
        pos += 8
    if pos + 4 <= n:
        (k,) = struct.unpack_from("<I", data, pos)
        h = ((_rotl(h ^ ((k * _P1) & _M64), 23) * _P2) + _P3) & _M64
        pos += 4
    while pos < n:
        h = (_rotl(h ^ ((data[pos] * _P5) & _M64), 11) * _P1) & _M64
        pos += 1
    h ^= h >> 33
    h = (h * _P2) & _M64
    h ^= h >> 29
    h = (h * _P3) & _M64
    h ^= h >> 32
    return h
