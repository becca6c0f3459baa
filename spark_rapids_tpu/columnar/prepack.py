"""Device-side pre-pack for host fetches: shrink bytes BEFORE they cross
the wire (reference analog: the nvcomp shuffle codecs,
``NvcompLZ4CompressionCodec.scala:26`` + ``TableCompressionCodec.scala`` —
the reference compresses table buffers on device before they travel).

The TPU-native twist: general-purpose byte codecs (LZ4/zstd) don't map to
XLA's static-shape model — the compressed size is data-dependent.  What
does map are *fixed-ratio* transforms chosen per buffer from a cheap
device-side probe:

  * integer bit-width narrowing  — int64/u64 columns whose live range fits
    in 1/2/4 bytes ship narrowed (up to 8x);
  * float64 -> float32           — when every value round-trips losslessly
    (on TPU, where "f64" is a double-float pair, this is exactly "the low
    component is zero" and halves the wire pair);
  * bool bit-packing             — validity masks and bool columns ship as
    bits, not bytes (8x).

Two-phase protocol (both phases are cached compiled programs):

  phase A ("probe"):  ONE small fetch of per-buffer (min, max) + f64
                      losslessness flags for the whole batch;
  phase B ("pack"):   a program specialized to the chosen width codes
                      emits the narrowed buffers fused into ONE uint32
                      word stream (via ``pack_leaves_traced``) — one
                      transfer for the whole batch, like
                      :func:`~spark_rapids_tpu.columnar.convert.bulk_device_get`.

The host widens everything back, so callers see bit-identical buffers
(floats: value-identical; the f32 path is only taken when lossless).
``STATS`` carries the bytes-on-wire accounting the bench reports.
"""

from __future__ import annotations

import math
import threading
from typing import Dict, List, Tuple

import numpy as np

#: wire accounting — bytes_naive is what a plain bulk fetch would have
#: pulled, bytes_on_wire what the prepacked fetch actually pulled
#: (+ the probe fetch, counted honestly).
STATS = {"prepacked_fetches": 0, "bytes_on_wire": 0, "bytes_naive": 0,
         "probe_bytes": 0, "fallbacks": 0}

_LOCK = threading.Lock()
_PROBE_CACHE: Dict = {}
_PACK_CACHE: Dict = {}

#: narrowing codes: per-leaf verdicts from the probe.  "keep" = ship
#: as-is; "bits" = bool bit-pack; "f32" = lossless f64 downcast;
#: "i1/i2/i4/u1/u2/u4" = integer narrowing target.
_INT_TARGETS = (
    ("i1", np.int8), ("i2", np.int16), ("i4", np.int32),
)
_UINT_TARGETS = (
    ("u1", np.uint8), ("u2", np.uint16), ("u4", np.uint32),
)


def _leaf_kind(dt: np.dtype) -> str:
    """Classification driving the probe: which narrowing family applies."""
    if dt == np.bool_:
        return "bool"
    if dt.kind == "i" and dt.itemsize >= 2:
        return "int"
    if dt.kind == "u" and dt.itemsize >= 2:
        return "uint"
    if dt.kind == "f" and dt.itemsize == 8:
        return "f64"
    return "other"


def _probe_program(sig):
    """Phase A: per-int-leaf (min, max) as int64 pairs + per-f64-leaf
    lossless flags, all in two small output arrays (one fetch)."""
    import jax
    import jax.numpy as jnp

    def probe(*arrs):
        mins, maxs, flags = [], [], []
        for a, (_, dts) in zip(arrs, sig):
            kind = _leaf_kind(np.dtype(dts))
            if kind in ("int", "uint"):
                flat = a.reshape(-1)
                # empty leaves narrow maximally; jnp.min on empty throws
                if flat.size == 0:
                    mins.append(jnp.int64(0))
                    maxs.append(jnp.int64(0))
                else:
                    # u64 max may exceed i64 — clamp via the sign trick:
                    # values >= 2^63 report i64-max, which keeps them wide
                    if kind == "uint" and np.dtype(dts).itemsize == 8:
                        big = jnp.max(flat)
                        clamped = jnp.where(
                            big >= jnp.uint64(1) << jnp.uint64(63),
                            jnp.uint64((1 << 63) - 1), big)
                        mins.append(jnp.min(flat).astype(jnp.int64))
                        maxs.append(clamped.astype(jnp.int64))
                    else:
                        mins.append(jnp.min(flat).astype(jnp.int64))
                        maxs.append(jnp.max(flat).astype(jnp.int64))
            elif kind == "f64":
                flat = a.reshape(-1)
                if flat.size == 0:
                    flags.append(jnp.bool_(True))
                else:
                    rt = flat.astype(jnp.float32).astype(flat.dtype)
                    flags.append(jnp.all(rt == flat))
        return (jnp.stack(mins) if mins else jnp.zeros(0, jnp.int64),
                jnp.stack(maxs) if maxs else jnp.zeros(0, jnp.int64),
                jnp.stack(flags) if flags else jnp.zeros(0, jnp.bool_))

    return jax.jit(probe)


def _choose_codes(sig, mins, maxs, flags) -> Tuple[str, ...]:
    codes: List[str] = []
    im = 0
    fm = 0
    for shape, dts in sig:
        dt = np.dtype(dts)
        kind = _leaf_kind(dt)
        if kind == "bool":
            codes.append("bits")
        elif kind == "int":
            lo, hi = int(mins[im]), int(maxs[im])
            im += 1
            code = "keep"
            for c, t in _INT_TARGETS:
                ii = np.iinfo(t)
                if np.dtype(t).itemsize < dt.itemsize \
                        and ii.min <= lo and hi <= ii.max:
                    code = c
                    break
            codes.append(code)
        elif kind == "uint":
            lo, hi = int(mins[im]), int(maxs[im])
            im += 1
            code = "keep"
            for c, t in _UINT_TARGETS:
                ii = np.iinfo(t)
                if np.dtype(t).itemsize < dt.itemsize and hi <= ii.max:
                    code = c
                    break
            codes.append(code)
        elif kind == "f64":
            codes.append("f32" if bool(flags[fm]) else "keep")
            fm += 1
        else:
            codes.append("keep")
    return tuple(codes)


_CODE_DTYPE = {"i1": np.int8, "i2": np.int16, "i4": np.int32,
               "u1": np.uint8, "u2": np.uint16, "u4": np.uint32,
               "f32": np.float32}


def _narrowed_sig(sig, codes):
    """The (shape, dtype) signature of the narrowed leaves, shared by the
    traced pack body and the host decoder (must never drift)."""
    out = []
    for (shape, dts), code in zip(sig, codes):
        if code == "keep":
            out.append((shape, dts))
        elif code == "bits":
            count = int(np.prod(shape, dtype=np.int64)) if shape else 1
            out.append(((math.ceil(count / 8),), "uint8"))
        else:
            out.append((shape, str(np.dtype(_CODE_DTYPE[code]))))
    return tuple(out)


def _bitpack_traced(a):
    """Bool array -> little-endian bit-packed uint8 (traced; numpy
    ``packbits(bitorder='little')`` semantics)."""
    import jax.numpy as jnp
    flat = a.reshape(-1).astype(jnp.uint8)
    pad = (-flat.size) % 8
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, jnp.uint8)])
    weights = jnp.asarray([1, 2, 4, 8, 16, 32, 64, 128], jnp.uint8)
    return (flat.reshape(-1, 8) * weights).sum(axis=1).astype(jnp.uint8)


def _pack_program(sig, codes):
    """Phase B: narrow each leaf per its code, then fuse every narrowed
    buffer into one word stream via ``pack_leaves_traced``."""
    import jax
    import jax.numpy as jnp

    from .convert import pack_leaves_traced
    nsig = _narrowed_sig(sig, codes)

    def pack(*arrs):
        narrowed = []
        for a, (_, dts), code in zip(arrs, sig, codes):
            if code == "keep":
                narrowed.append(a)
            elif code == "bits":
                narrowed.append(_bitpack_traced(a))
            else:
                narrowed.append(a.astype(_CODE_DTYPE[code]))
        return pack_leaves_traced(narrowed, nsig)

    return jax.jit(pack), nsig


def _widen(host_leaves, sig, codes):
    out = []
    for leaf, (shape, dts), code in zip(host_leaves, sig, codes):
        if code == "keep":
            out.append(leaf)
        elif code == "bits":
            count = int(np.prod(shape, dtype=np.int64)) if shape else 1
            bits = np.unpackbits(leaf, count=count, bitorder="little")
            out.append(bits.astype(np.bool_).reshape(shape))
        else:
            out.append(leaf.astype(np.dtype(dts)).reshape(shape))
    return out


def _min_bytes() -> int:
    from ..config import D2H_PREPACK_MIN_BYTES, RapidsConf
    try:
        return int(RapidsConf.get_global().get(D2H_PREPACK_MIN_BYTES))
    except Exception:  # pragma: no cover
        return 1 << 20


def enabled() -> bool:
    """'auto' (default) = on for a non-CPU backend (narrowing trades a
    little device compute + one probe round trip for fewer bytes over the
    host link); 'true' forces on (tests/CPU-mesh measurement), 'false'
    kills."""
    from ..config import D2H_PREPACK, RapidsConf
    try:
        mode = str(RapidsConf.get_global().get(D2H_PREPACK)).lower()
    except Exception:  # pragma: no cover
        mode = "auto"
    if mode in ("true", "on"):
        return True
    if mode in ("false", "off"):
        return False
    import jax
    return jax.default_backend() != "cpu"


def _narrowed_fetch(devs, sig, naive: int, sp):
    """The probe and, unless it keeps every leaf (None), the narrowed
    fetch, widened back on the host.  ``sp`` is the caller's d2h span:
    the bytes that crossed are known only here."""
    with _LOCK:
        probe = _PROBE_CACHE.get(sig)
        if probe is None:
            probe = _PROBE_CACHE[sig] = _probe_program(sig)
            if len(_PROBE_CACHE) > 256:
                _PROBE_CACHE.clear()
                _PROBE_CACHE[sig] = probe
    mins_d, maxs_d, flags_d = probe(*devs)
    for b in (mins_d, maxs_d, flags_d):
        b.copy_to_host_async()
    mins, maxs, flags = (np.asarray(mins_d), np.asarray(maxs_d),
                         np.asarray(flags_d))
    probe_nbytes = mins.nbytes + maxs.nbytes + flags.nbytes
    with _LOCK:  # shuffle writer/reader pools fetch concurrently
        STATS["probe_bytes"] += probe_nbytes
        STATS["bytes_on_wire"] += probe_nbytes  # probe crossed too
    codes = _choose_codes(sig, mins, maxs, flags)
    if all(c == "keep" for c in codes):
        sp.set_metadata(bytes=probe_nbytes)
        return None
    # keep-f64 leaves ride pack_leaves_traced, whose word layout
    # depends on the f64 encoding mode (backend + packFloat64 conf) —
    # part of the key, like bulk_device_get's cache (convert.py)
    from .convert import _f64_as_pair, _pack_f64_enabled
    key = (sig, codes, _f64_as_pair(), _pack_f64_enabled())
    with _LOCK:
        entry = _PACK_CACHE.get(key)
        if entry is None:
            entry = _PACK_CACHE[key] = _pack_program(sig, codes)
            if len(_PACK_CACHE) > 256:
                _PACK_CACHE.clear()
                _PACK_CACHE[key] = entry
    pack, nsig = entry
    bufs = pack(*devs)
    for b in bufs:
        b.copy_to_host_async()
    host = [np.asarray(b) for b in bufs]
    from .convert import unpack_buffers
    narrowed_host = unpack_buffers(host, nsig)
    widened = _widen(narrowed_host, sig, codes)
    wire = sum(b.nbytes for b in host)
    with _LOCK:
        STATS["prepacked_fetches"] += 1
        STATS["bytes_on_wire"] += wire
        STATS["bytes_naive"] += naive
    sp.set_metadata(bytes=wire + probe_nbytes)
    return widened


def prepacked_device_get(tree):
    """Drop-in for ``bulk_device_get`` with device-side narrowing.

    Falls back to :func:`~.convert.bulk_device_get` whenever prepack is
    disabled, the batch is too small for the probe round trip to pay, or
    anything in the narrow path fails (correctness first)."""
    import jax

    from ..shims import tree_flatten, tree_unflatten
    from .convert import bulk_device_get
    if not enabled():
        return bulk_device_get(tree)
    leaves, treedef = tree_flatten(tree)
    dev_idx = [i for i, l in enumerate(leaves)
               if isinstance(l, jax.Array) and not isinstance(l, np.ndarray)]
    if not dev_idx:
        return tree
    devs = [leaves[i] for i in dev_idx]
    sig = tuple((l.shape, str(l.dtype)) for l in devs)
    naive = 0
    narrowable = 0
    for (shape, dts) in sig:
        try:
            isz = np.dtype(dts).itemsize
        except TypeError:
            return bulk_device_get(tree)  # exotic dtype: plain path
        count = int(np.prod(shape, dtype=np.int64)) if shape else 1
        naive += count * isz
        if _leaf_kind(np.dtype(dts)) != "other":
            narrowable += count * isz
    if narrowable < _min_bytes():
        return bulk_device_get(tree)
    from ..observability import tracer as _trace
    # probe + narrowed fetch: both crossings in one d2h span
    try:
        with _trace.span("d2h", "prepacked_device_get", bytes_naive=naive,
                         leaves=len(devs)) as sp:
            widened = _narrowed_fetch(devs, sig, naive, sp)
    except Exception:  # pragma: no cover - toolchain-specific lowerings
        with _LOCK:
            STATS["fallbacks"] += 1
        return bulk_device_get(tree)
    if widened is None:     # the probe kept every leaf: plain path
        return bulk_device_get(tree)
    for i, leaf in zip(dev_idx, widened):
        leaves[i] = leaf
    return tree_unflatten(treedef, leaves)
