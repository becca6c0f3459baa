"""Host(Arrow) <-> device(JAX) batch conversion.

This is the TPU analog of the reference's transition layer:
``HostColumnarToGpu`` / ``GpuColumnarToRowExec`` / ``GpuRowToColumnarExec``
(SURVEY §2.2) with Arrow as the host columnar format.  Host decode is
vectorized numpy over Arrow buffers (no per-row Python) and the device upload
is a single ``jnp.asarray`` per buffer.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import types as T
from ..observability import tracer as _trace
from .batch import ColumnarBatch
from .column import (DeviceColumn, bucket_capacity, bucket_width,
                     is_string_like, null_column)


# --------------------------------------------------------------------------
# Bulk device -> host fetch (single-pull D2H)
# --------------------------------------------------------------------------

#: compiled pack programs keyed by the leaf signature
_PACK_CACHE: dict = {}


def _word_packable(dt: str) -> bool:
    """Dtypes the pack program can turn into uint32 words on the TPU
    toolchain.  64-bit types can't use bitcast-convert (the X64-rewrite
    pass doesn't implement it) — ints split arithmetically and f64 goes
    through :func:`_f64_bits` (arithmetic IEEE-754 bit extraction)."""
    if dt == "bool":
        return True
    d = np.dtype(dt)
    if d.kind == "f":
        if d.itemsize == 4:
            return True
        if d.itemsize == 8:
            # exact bits on CPU; double-float pair on TPU unless the user
            # opted into storage-fidelity fetches
            return not _f64_as_pair() or _pack_f64_enabled()
        return False
    return d.kind in ("i", "u") and d.itemsize in (1, 2, 4, 8)


def u64_to_i64(u):
    """Two's-complement uint64 -> int64 WITHOUT 64-bit bitcast-convert
    (the TPU X64 rewrite doesn't implement it).  The one shared copy of
    this trick — device_parquet and ranks.f64_bits_i64 both route here."""
    big = u >= (jnp.uint64(1) << jnp.uint64(63))
    low = (u & jnp.uint64((1 << 63) - 1)).astype(jnp.int64)
    int64_min = jnp.int64(-(2 ** 62)) + jnp.int64(-(2 ** 62))
    return jnp.where(big, low + int64_min, low)


def _f64_bits(x):
    """IEEE-754 bit pattern of float64 as uint64, WITHOUT bitcast-convert
    (traced; exact).  The exponent is recovered by a 10-step power-of-two
    binary search — every multiply is by an exact power of two, so the
    normalized mantissa m ∈ [1,2) is the value's own 53-bit mantissa and
    ``(m-1)*2^52`` converts to uint64 exactly.  NaNs canonicalize to the
    quiet NaN (payloads are not preserved — Spark normalizes NaNs).
    Denormals encode as signed zero: XLA flushes f64 denormals to zero in
    EVERY operation on these backends (DAZ — even ``x == 0`` is true for
    them), so this matches the engine's own arithmetic semantics."""
    ax = jnp.abs(x)
    neg_zero = (x == 0.0) & (1.0 / x < 0)
    sign = jnp.where((x < 0) | neg_zero, jnp.uint64(1), jnp.uint64(0))
    m = ax
    e = jnp.zeros(x.shape, jnp.int32)
    for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        big = m >= (2.0 ** k)
        m = jnp.where(big, m * (2.0 ** -k), m)
        e = e + jnp.where(big, k, 0)
    for k in (512, 256, 128, 64, 32, 16, 8, 4, 2, 1):
        small = m < (2.0 ** (1 - k))
        m = jnp.where(small, m * (2.0 ** k), m)
        e = e - jnp.where(small, k, 0)
    normal = e >= -1022
    exp_field = jnp.where(normal, (e + 1023).astype(jnp.uint64),
                          jnp.uint64(0))
    mant = jnp.where(normal, ((m - 1.0) * (2.0 ** 52)).astype(jnp.uint64),
                     jnp.uint64(0))
    bits = (sign << jnp.uint64(63)) | (exp_field << jnp.uint64(52)) | mant
    bits = jnp.where(ax == 0.0, sign << jnp.uint64(63), bits)
    bits = jnp.where(jnp.isinf(x),
                     (sign << jnp.uint64(63)) | jnp.uint64(0x7FF0000000000000),
                     bits)
    bits = jnp.where(jnp.isnan(x), jnp.uint64(0x7FF8000000000000), bits)
    return bits


def _to_words(a):
    """Flatten one device array to little-endian uint32 words (traced).
    64-bit types are split arithmetically — the TPU toolchain's X64
    rewrite does not implement 64-bit bitcast-convert; sub-32-bit types
    pad to 4 bytes and pack 4 per word."""
    import jax
    a = a.reshape(-1)
    if a.dtype == jnp.bool_:
        a = a.astype(jnp.uint8)
    isz = a.dtype.itemsize
    if isz == 4:
        return jax.lax.bitcast_convert_type(a, jnp.uint32)
    if isz == 8:
        if jnp.issubdtype(a.dtype, jnp.floating):
            if jax.default_backend() == "cpu":
                u = _f64_bits(a)  # native f64: exact bit extraction
            else:
                # TPU "f64" is a double-float (f32 hi/lo pair — values
                # beyond f32 exponent range are already inf ON DEVICE and
                # plain device_get can't round-trip true f64 either).
                # The (hi, lo) pair IS the device's exact representation.
                # lo is rescaled by an exact power of two picked from
                # |hi|'s magnitude so it never lands in the f32-denormal
                # range (the TPU flushes those to zero); the host decoder
                # re-derives the same scale from hi.
                hi32 = a.astype(jnp.float32)
                ahi = jnp.abs(hi32)
                scale = jnp.where(ahi < 2.0 ** -30, 2.0 ** 64,
                                  jnp.where(ahi > 2.0 ** 97, 2.0 ** -64,
                                            1.0)).astype(a.dtype)
                lo32 = jnp.where(jnp.isfinite(hi32),
                                 ((a - hi32.astype(a.dtype)) * scale)
                                 .astype(jnp.float32),
                                 jnp.float32(0))
                pair = jnp.stack([hi32, lo32], axis=-1).reshape(-1)
                return jax.lax.bitcast_convert_type(pair, jnp.uint32)
        else:
            u = a.astype(jnp.uint64)
        lo = (u & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        hi = (u >> jnp.uint64(32)).astype(jnp.uint32)
        return jnp.stack([lo, hi], axis=-1).reshape(-1)
    # 1- or 2-byte: widen to u32 lanes via [m,4]-u8 -> u32 bitcast
    b = jax.lax.bitcast_convert_type(a, jnp.uint8).reshape(-1) \
        if isz > 1 else a.astype(jnp.uint8)
    pad = (-b.size) % 4
    if pad:
        b = jnp.concatenate([b, jnp.zeros(pad, jnp.uint8)])
    return jax.lax.bitcast_convert_type(b.reshape(-1, 4), jnp.uint32)


def pack_leaves_traced(arrs, sig):
    """Traced body: pack device leaves into (words, other0, other1, ...).

    Word-packable leaves (bools, ints, f32) become ONE uint32 vector with
    8-byte-aligned segments; every other dtype (f64, ...) concatenates
    into one flat vector per dtype (sorted-dtype order) — no bitcast, so
    the X64-rewrite restriction doesn't apply.  Composable inside larger
    jitted programs (the whole-query tail fusion) or jitted alone."""
    other_dts = sorted({dt for _, dt in sig if not _word_packable(dt)})
    parts = []
    groups = {dt: [] for dt in other_dts}
    for a, (_, dt) in zip(arrs, sig):
        if not _word_packable(dt):
            groups[dt].append(a.reshape(-1))
            continue
        w = _to_words(a).reshape(-1)
        if w.size % 2:  # 8-byte-align segments (2 words)
            w = jnp.concatenate([w, jnp.zeros(1, jnp.uint32)])
        parts.append(w)
    words = (jnp.concatenate(parts) if len(parts) > 1
             else parts[0] if parts else jnp.zeros(0, jnp.uint32))
    others = []
    for dt in other_dts:
        g = groups[dt]
        others.append(jnp.concatenate(g) if len(g) > 1
                      else g[0] if g else jnp.zeros(0, np.dtype(dt)))
    return (words,) + tuple(others)


def unpack_buffers(host_bufs, sig):
    """Invert :func:`pack_leaves_traced` on fetched numpy buffers; returns
    the host leaves in signature order."""
    words = host_bufs[0].view(np.uint8)
    other_dts = sorted({dt for _, dt in sig if not _word_packable(dt)})
    other_buf = dict(zip(other_dts, host_bufs[1:]))
    other_off = {dt: 0 for dt in other_dts}
    out = []
    off = 0
    for shape, dt in sig:
        count = 1
        for s in shape:
            count *= s
        if not _word_packable(dt):
            o = other_off[dt]
            out.append(other_buf[dt][o:o + count].reshape(shape))
            other_off[dt] = o + count
            continue
        want_bool = dt == "bool"
        np_dt = np.dtype("uint8") if want_bool else np.dtype(dt)
        seg = count * np_dt.itemsize
        if np_dt == np.float64 and _f64_as_pair():
            pair = np.frombuffer(words, np.float32, count=2 * count,
                                 offset=off).reshape(-1, 2)
            hi = pair[:, 0].astype(np.float64)
            ahi = np.abs(pair[:, 0])
            scale = np.where(ahi < 2.0 ** -30, 2.0 ** -64,
                             np.where(ahi > 2.0 ** 97, 2.0 ** 64, 1.0))
            a = hi + pair[:, 1] * scale
        else:
            a = np.frombuffer(words, np_dt, count=count, offset=off)
        if want_bool:
            a = a.view(np.bool_)
        out.append(a.reshape(shape))
        off += seg + ((-seg) % 8)
    return out


def _f64_as_pair() -> bool:
    """Whether f64 words were packed as (hi, lo) float32 pairs (non-CPU
    backends — see :func:`_to_words`).  The pair is bit-faithful to every
    f64 the device can COMPUTE (its arithmetic flushes f32-denormal low
    components exactly like the extraction does); only raw storage of
    uploaded tiny values (~<1e-29) differs, gated by
    ``spark.rapids.tpu.d2h.packFloat64``."""
    import jax
    return jax.default_backend() != "cpu"


def _pack_f64_enabled() -> bool:
    from ..config import D2H_PACK_F64, RapidsConf
    try:
        return bool(RapidsConf.get_global().get(D2H_PACK_F64))
    except Exception:  # pragma: no cover
        return True


def _pack_program(sig):
    """Compiled pack program for :func:`bulk_device_get` (signature-keyed)."""
    import jax
    return jax.jit(lambda *arrs: pack_leaves_traced(arrs, sig))


def bulk_device_get(tree):
    """``jax.device_get`` with one transfer for the whole pytree: device
    leaves are byte-packed by a compiled kernel and unpacked from the one
    fetched buffer on the host; non-device leaves pass through unchanged."""
    import jax
    from ..robustness import faults as _faults
    from ..shims import tree_flatten
    _faults.maybe_inject("transfer.d2h", exc=ConnectionError)
    leaves, treedef = tree_flatten(tree)
    dev_idx = [i for i, l in enumerate(leaves)
               if isinstance(l, jax.Array) and not isinstance(l, np.ndarray)]
    if not dev_idx:
        return tree
    devs = [leaves[i] for i in dev_idx]
    sig = tuple((l.shape, str(l.dtype)) for l in devs)
    for _, dt in sig:
        if dt == "bool":
            continue
        try:
            np.dtype(dt)
        except TypeError:
            # e.g. bfloat16: numpy can't view it
            with _trace.span("d2h", "device_get.fallback", leaves=len(devs)):
                return jax.device_get(tree)
    # layout depends on the f64 encoding mode (backend + packFloat64
    # config), which can change mid-session — it must be part of the key
    cache_key = (sig, _f64_as_pair(), _pack_f64_enabled())
    pack = _PACK_CACHE.get(cache_key)
    if pack is None:
        pack = _PACK_CACHE[cache_key] = _pack_program(sig)
        if len(_PACK_CACHE) > 512:
            _PACK_CACHE.clear()
            _PACK_CACHE[cache_key] = pack
    try:
        with _trace.span("d2h", "bulk_device_get", leaves=len(devs)) as sp:
            bufs = pack(*devs)
            for b in bufs:  # overlap the (few) transfers: one latency
                b.copy_to_host_async()
            host = [np.asarray(b) for b in bufs]
            sp.set_metadata(bytes=sum(b.nbytes for b in host))
    except Exception:
        # e.g. an exotic dtype the pack program can't lower on this
        # toolchain — correctness first, one pull per leaf as before
        with _trace.span("d2h", "device_get.fallback", leaves=len(devs)):
            return jax.device_get(tree)
    for i, leaf in zip(dev_idx, unpack_buffers(host, sig)):
        leaves[i] = leaf
    from ..shims import tree_unflatten
    return tree_unflatten(treedef, leaves)


# --------------------------------------------------------------------------
# Arrow -> device
# --------------------------------------------------------------------------

def split_ragged_strings(table: pa.Table,
                         threshold_bytes: int = 16 << 20,
                         min_saving: float = 4.0) -> list:
    """Split a table whose PADDED string footprint would blow up.

    The device string layout is a ``[capacity, width]`` byte matrix with
    width = the batch's max row length bucketed to a power of two — one
    10KB string makes every row pay 16KB (the
    reference avoids this with cuDF's offsets+chars layout).  The
    TPU-native answer keeps every kernel's static shapes intact: cut the
    batch into width classes, so short rows ride a narrow matrix and the
    few long rows ride a small wide one.  Row order is not preserved
    (Spark makes no ordering promise before a sort).

    Returns [table] when splitting is unnecessary or unhelpful.
    """
    from .column import bucket_capacity, bucket_width
    n = table.num_rows
    if n < 2:
        return [table]
    str_cols = [i for i, f in enumerate(table.schema)
                if pa.types.is_string(f.type) or pa.types.is_binary(f.type)
                or pa.types.is_large_string(f.type)
                or pa.types.is_large_binary(f.type)]
    if not str_cols:
        return [table]
    cap = bucket_capacity(n)
    # per-row max length across string columns decides the row's class
    row_max = np.zeros(n, dtype=np.int64)
    widths = []
    for ci in str_cols:
        col = table.column(ci)
        lens = pa.compute.binary_length(col).fill_null(0)
        lens_np = lens.to_numpy(zero_copy_only=False).astype(np.int64)
        widths.append(bucket_width(int(lens_np.max()) if n else 0))
        np.maximum(row_max, lens_np, out=row_max)
    footprint = cap * sum(widths)
    if footprint <= threshold_bytes:
        return [table]
    # short class at the 99th-percentile width; only split when it
    # actually pays
    w_short = bucket_width(int(np.percentile(row_max, 99.0)))
    long_mask = row_max > w_short
    n_long = int(long_mask.sum())
    if n_long == 0 or n_long == n:
        return [table]
    w_full = bucket_width(int(row_max.max()))
    after = (bucket_capacity(n - n_long) * len(str_cols) * w_short
             + bucket_capacity(n_long) * len(str_cols) * w_full)
    if footprint < after * min_saving:
        return [table]
    mask = pa.array(long_mask)
    return [table.filter(pa.compute.invert(mask)), table.filter(mask)]


def split_for_upload(table: pa.Table, conf=None) -> list:
    """Conf-gated :func:`split_ragged_strings` — the one place scan paths
    read the threshold, so the in-memory and file-scan gates can't
    drift."""
    from ..config import RAGGED_STRING_SPLIT_BYTES, RapidsConf
    thr = int((conf or RapidsConf.get_global())
              .get(RAGGED_STRING_SPLIT_BYTES))
    return split_ragged_strings(table, thr) if thr > 0 else [table]


def arrow_to_device(table: pa.Table, capacity: Optional[int] = None,
                    conf=None) -> ColumnarBatch:
    from ..robustness import faults as _faults
    n = table.num_rows
    cap = capacity or bucket_capacity(n)
    _faults.maybe_inject("transfer.h2d", exc=ConnectionError,
                         bytes=table.nbytes)
    with _trace.span("h2d", "arrow_to_device", bytes=table.nbytes, rows=n):
        cols = [arrow_to_device_column(table.column(i), cap, conf=conf)
                for i in range(table.num_columns)]
        return ColumnarBatch.make(table.column_names, cols, n)


def arrow_to_device_column(arr, capacity: int, conf=None) -> DeviceColumn:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    dtype = T.from_arrow(arr.type)
    if pa.types.is_dictionary(arr.type) and not is_string_like(dtype):
        arr = arr.cast(arr.type.value_type)   # only strings stay encoded
    n = len(arr)
    valid_np = np.zeros(capacity, dtype=bool)
    if n:
        valid_np[:n] = _valid_mask(arr)
    validity = jnp.asarray(valid_np)

    if isinstance(dtype, T.NullType):
        return null_column(dtype, capacity).with_validity(validity)

    if isinstance(dtype, (T.ArrayType, T.MapType)):
        return _list_to_device(arr, dtype, capacity, validity, n, conf=conf)

    if isinstance(dtype, T.StructType):
        children = tuple(arrow_to_device_column(arr.field(i), capacity,
                                                conf=conf)
                         for i in range(arr.type.num_fields))
        return DeviceColumn(dtype, None, validity, children=children)

    if is_string_like(dtype):
        # scan-side encoded retention: low-cardinality strings stay as
        # codes + dictionary (columnar/encoded.py) instead of eagerly
        # materializing the padded byte matrix — the decline path falls
        # through to the raw layout below
        from .encoded import enabled as _enc_on, encode_string_arrow
        if _enc_on(conf):
            enc = encode_string_arrow(arr, dtype, capacity, conf=conf)
            if enc is not None:
                return enc
        if pa.types.is_dictionary(arr.type):
            arr = arr.cast(arr.type.value_type)   # encoding declined
        chars, lengths = _strings_to_matrix(arr, capacity)
        return DeviceColumn(dtype, jnp.asarray(chars), validity,
                            lengths=jnp.asarray(lengths))

    if isinstance(dtype, T.DecimalType):
        lo, hi = _decimal_words(arr, capacity)
        aux = jnp.asarray(hi) if not dtype.is_long_backed else None
        return DeviceColumn(dtype, jnp.asarray(lo), validity, aux=aux)

    np_data = _fixed_to_numpy(arr, dtype)
    out = np.zeros(capacity, dtype=dtype.np_dtype)
    out[:n] = np_data
    out[:n][~valid_np[:n]] = 0  # dead data zeroed for deterministic kernels
    if np.dtype(dtype.np_dtype).kind in ("i", "u"):
        from .encoded import enabled as _enc_on, encode_rle_numpy
        if _enc_on(conf):
            rle = encode_rle_numpy(dtype, out, valid_np, n, capacity)
            if rle is not None:
                return rle
    return DeviceColumn(dtype, jnp.asarray(out), validity)


def _list_to_device(arr, dtype, capacity: int, validity, n: int, conf=None
                    ) -> DeviceColumn:
    """Arrow List/Map -> padded row-block layout: child element r*w+j is
    slot j of row r; slots past the row's length are dead."""
    from .column import make_array_column
    if isinstance(arr.type, pa.MapType):
        arr = arr.cast(pa.map_(arr.type.key_type, arr.type.item_type))
        offsets = np.asarray(arr.offsets)
        child_arrays = [arr.keys, arr.items]
    else:
        if pa.types.is_large_list(arr.type):
            arr = arr.cast(pa.list_(arr.type.value_type))
        offsets = np.asarray(arr.offsets)
        child_arrays = [arr.values]
    lengths_np = (offsets[1:] - offsets[:-1]).astype(np.int32)
    valid_np = np.asarray(validity)[:n]
    lengths_np = np.where(valid_np, lengths_np, 0)
    width = bucket_width(int(lengths_np.max()) if n else 0)
    # take-index into the flattened arrow child; None -> null (dead slot)
    take = np.full(capacity * width, -1, dtype=np.int64)
    if n:
        row = np.repeat(np.arange(n), lengths_np)
        slot = np.arange(lengths_np.sum()) - np.repeat(
            np.cumsum(lengths_np) - lengths_np, lengths_np)
        src = np.repeat(offsets[:-1].astype(np.int64), lengths_np) + slot
        take[row * width + slot] = src
    import pyarrow.compute as pc
    idx = _null_take_indices(take)
    children = []
    for ch in child_arrays:
        if isinstance(ch, pa.ChunkedArray):
            ch = ch.combine_chunks()
        children.append(arrow_to_device_column(pc.take(ch, idx),
                                               capacity * width, conf=conf))
    lengths = np.zeros(capacity, dtype=np.int32)
    lengths[:n] = lengths_np
    return make_array_column(dtype, jnp.asarray(lengths), tuple(children),
                             validity)


def _null_take_indices(take: np.ndarray) -> pa.Array:
    """int64 indices with nulls where take < 0 (pyarrow take -> null)."""
    mask = take < 0
    safe = np.where(mask, 0, take)
    return pa.Array.from_buffers(
        pa.int64(), len(take),
        [pa.py_buffer(np.packbits(~mask, bitorder="little").tobytes()),
         pa.py_buffer(safe.astype(np.int64).tobytes())])


def _valid_mask(arr: pa.Array) -> np.ndarray:
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=bool)
    return np.asarray(arr.is_valid())


def _fixed_to_numpy(arr: pa.Array, dtype: T.DataType) -> np.ndarray:
    if isinstance(dtype, T.DateType):
        arr = arr.cast(pa.int32())
    elif isinstance(dtype, T.TimestampType):
        arr = arr.cast(pa.timestamp("us")).cast(pa.int64())
    elif isinstance(dtype, T.BooleanType):
        pass
    if arr.null_count:
        zero = pa.scalar(False if pa.types.is_boolean(arr.type) else 0, type=arr.type)
        arr = arr.fill_null(zero)
    return np.asarray(arr.to_numpy(zero_copy_only=False)).astype(
        dtype.np_dtype, copy=False)


def _strings_to_matrix(arr: pa.Array, capacity: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    if pa.types.is_large_string(arr.type):
        arr = arr.cast(pa.string())
    elif pa.types.is_large_binary(arr.type):
        arr = arr.cast(pa.binary())
    n = len(arr)
    if arr.null_count:
        arr = arr.fill_null("" if pa.types.is_string(arr.type) else b"")
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], dtype=np.int32,
                            count=(arr.offset + n + 1))[arr.offset:]
    data = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None else \
        np.zeros(0, dtype=np.uint8)
    starts = offsets[:-1].astype(np.int64)  # absolute buffer positions
    lengths_np = (offsets[1:] - offsets[:-1]).astype(np.int32)
    width = bucket_width(int(lengths_np.max()) if n else 0)
    if n:
        # native single-pass pack (no O(total-bytes) index temporaries);
        # the numpy path below is the toolchain-free fallback
        from ..native import pack_strings as _native_pack
        packed = _native_pack(data, offsets.astype(np.int64), width,
                              capacity)
        if packed is not None:
            return packed
    chars = np.zeros((capacity, width), dtype=np.uint8)
    total = int(lengths_np.sum())
    if total:
        # within-row byte index is relative to each row's own start, not to
        # the raw buffer offset (which is nonzero for sliced arrays)
        local_starts = np.zeros(n, dtype=np.int64)
        np.cumsum(lengths_np[:-1], out=local_starts[1:])
        row_idx = np.repeat(np.arange(n), lengths_np)
        within = np.arange(total) - np.repeat(local_starts, lengths_np)
        chars[row_idx, within] = data[np.repeat(starts, lengths_np) + within]
    lengths = np.zeros(capacity, dtype=np.int32)
    lengths[:n] = lengths_np
    return chars, lengths


def _decimal_words(arr: pa.Array, capacity: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    n = len(arr)
    bufs = arr.buffers()
    words = (np.frombuffer(bufs[1], dtype=np.int64)
             [(arr.offset * 2):(arr.offset + n) * 2]
             if bufs[1] is not None else np.zeros(0, dtype=np.int64))
    lo = np.zeros(capacity, dtype=np.int64)
    hi = np.zeros(capacity, dtype=np.int64)
    if n:
        lo[:n] = words[0::2]
        hi[:n] = words[1::2]
        mask = ~_valid_mask(arr)
        lo[:n][mask] = 0
        hi[:n][mask] = 0
    return lo, hi


# --------------------------------------------------------------------------
# device -> Arrow
# --------------------------------------------------------------------------

def device_to_arrow(batch: ColumnarBatch) -> pa.Table:
    # ONE bulk transfer for every leaf: per-array pulls each cost a full
    # host<->device round trip; large batches
    # additionally narrow on device first (columnar/prepack.py)
    from .prepack import prepacked_device_get
    batch = prepacked_device_get(batch)
    n = batch.num_rows_int
    arrays = [device_column_to_arrow(c, n) for c in batch.columns]
    return pa.table(arrays, names=list(batch.names))


def device_column_to_arrow(col: DeviceColumn, n: int) -> pa.Array:
    dtype = col.dtype
    valid = np.asarray(col.validity)[:n] if col.validity is not None else \
        np.ones(n, dtype=bool)
    mask = ~valid  # pyarrow mask semantics: True = null

    if isinstance(dtype, T.NullType):
        return pa.nulls(n)

    if isinstance(dtype, (T.ArrayType, T.MapType)):
        w = col.array_width
        lens = np.asarray(col.lengths)[:n].astype(np.int64)
        lens = np.where(valid, lens, 0)
        total = int(lens.sum())
        # child rows live at r*w .. r*w+len-1
        starts = np.cumsum(lens) - lens
        row = np.repeat(np.arange(n), lens)
        slot = np.arange(total) - np.repeat(starts, lens)
        child_idx = row * w + slot
        offsets = np.zeros(n + 1, dtype=np.int32)
        np.cumsum(lens, out=offsets[1:])
        kids = []
        for ch in col.children:
            flat = device_column_to_arrow(ch, ch.capacity)
            kids.append(flat.take(pa.array(child_idx, type=pa.int64())))
        # null rows: nulls in the offsets array mark null lists/maps
        off = pa.array(offsets,
                       mask=np.append(mask, False) if mask.any() else None)
        if isinstance(dtype, T.MapType):
            out = pa.MapArray.from_arrays(off, kids[0], kids[1])
        else:
            out = pa.ListArray.from_arrays(off, kids[0])
        return out.cast(T.to_arrow(dtype))

    if isinstance(dtype, T.StructType):
        children = [device_column_to_arrow(c, n) for c in col.children]
        return pa.StructArray.from_arrays(
            children, names=list(dtype.names),
            mask=pa.array(mask) if mask.any() else None)

    if is_string_like(dtype):
        return _matrix_to_strings(col, n, mask,
                                  binary=isinstance(dtype, T.BinaryType))

    if isinstance(dtype, T.DecimalType):
        lo = np.asarray(col.data)[:n]
        hi = (np.asarray(col.aux)[:n] if col.aux is not None
              else np.where(lo < 0, -1, 0).astype(np.int64))
        words = np.empty(n * 2, dtype=np.int64)
        words[0::2] = lo
        words[1::2] = hi
        return pa.Array.from_buffers(
            pa.decimal128(dtype.precision, dtype.scale), n,
            [_bitmap(valid), pa.py_buffer(words.tobytes())])

    data = np.asarray(col.data)[:n]
    if isinstance(dtype, T.DateType):
        return pa.array(data.astype(np.int32), type=pa.date32(),
                        mask=mask if mask.any() else None)
    if isinstance(dtype, T.TimestampType):
        return pa.array(data.astype(np.int64),
                        type=pa.timestamp("us", tz="UTC"),
                        mask=mask if mask.any() else None)
    return pa.array(data, type=T.to_arrow(dtype),
                    mask=mask if mask.any() else None)


def _bitmap(valid: np.ndarray) -> Optional[pa.Buffer]:
    if valid.all():
        return None
    return pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())


def _matrix_to_strings(col: DeviceColumn, n: int, mask: np.ndarray,
                       binary: bool) -> pa.Array:
    chars = np.asarray(col.data)[:n]
    lengths = np.asarray(col.lengths)[:n].astype(np.int64)
    lengths = np.where(mask, 0, lengths)
    offsets = np.zeros(n + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    total = int(offsets[-1])
    flat = np.zeros(total, dtype=np.uint8)
    if total:
        row_idx = np.repeat(np.arange(n), lengths)
        col_idx = np.arange(total) - np.repeat(offsets[:-1].astype(np.int64), lengths)
        flat[:] = chars[row_idx, col_idx]
    at = pa.binary() if binary else pa.utf8()
    return pa.Array.from_buffers(
        at, n, [_bitmap(~mask), pa.py_buffer(offsets.tobytes()),
                pa.py_buffer(flat.tobytes())])


# --------------------------------------------------------------------------
# pandas convenience
# --------------------------------------------------------------------------

def pandas_to_device(df) -> ColumnarBatch:
    return arrow_to_device(pa.Table.from_pandas(df, preserve_index=False))


def device_to_pandas(batch: ColumnarBatch):
    return device_to_arrow(batch).to_pandas()
