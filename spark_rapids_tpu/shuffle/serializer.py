"""Columnar batch wire serializer — the analog of cuDF's
``JCudfSerialization`` + ``GpuColumnarBatchSerializer.scala:82,170-180``
(SURVEY §2.8 mode 1).

Frame layout (little-endian):

  magic 'TPUB' | version u16 | flags u16 | num_rows u32 | num_cols u32
  | schema blob (json: names + type strings) u32-prefixed
  | per column: validity bitmap, then layout-dependent buffers, each
    u64-length-prefixed

Buffers are written packed to live rows only (capacity padding is NOT
shipped); the reader re-pads into a fresh capacity bucket.  Optional
whole-frame compression (zstd) mirrors the reference's nvcomp codecs
(``TableCompressionCodec.scala``).

Encoded-batch wire format (frame version 2, docs/encoded_columns.md):
dictionary-encoded columns ship their codes NARROWED to the smallest
unsigned width that holds the dictionary size (u1/u2/u4) plus the
dictionary itself, written once per frame — or replaced by a content-hash
reference when the in-process dictionary registry already holds it
(``spark.rapids.tpu.sql.encoded.shuffle.dictRefs.enabled``; bypassed on
multi-slice topologies, whose frames cross process boundaries).  RLE
columns ship run values + run ends.  Version-2 readers accept version-1
frames unchanged (per-column ``enc`` metadata is simply absent); a
version-1 reader must not see version-2 frames — bump the version again
on any layout change so mixed-version deployments fail loudly on the
header instead of mis-parsing."""

from __future__ import annotations

import io
import json
import struct
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import types as T
from ..observability import metrics as _om
from ..observability import tracer as _trace
from ..columnar.batch import ColumnarBatch
from ..columnar.column import DeviceColumn, bucket_capacity, make_array_column

_MAGIC = b"TPUB"
#: v2 = encoded-batch wire format (dict codes + dictionaries / RLE runs)
_VERSION = 2

#: map-side sent-set for dictionary refs: content hashes known to be
#: resolvable from the process-global dictionary registry.  Ship each
#: dictionary once per process; repeated batches pay only code bytes.
_SENT_DICTS: set = set()

_FLAG_ZSTD = 1
_FLAG_CRC = 2   # trailing xxhash64 of the (possibly compressed) payload


class FrameCorrupt(ValueError):
    """A shuffle frame failed structural validation (bad magic, torn
    length prefix, checksum mismatch).  Subclasses ValueError for
    back-compat; the shuffle manager treats it as a retryable fetch
    failure (re-fetch / lost-block recompute), never as data."""


def _codec(conf) -> str:
    from ..config import SHUFFLE_COMPRESSION_CODEC, RapidsConf
    conf = conf or RapidsConf.get_global()
    c = str(conf.get(SHUFFLE_COMPRESSION_CODEC)).lower()
    return "zstd" if c in ("zstd", "lz4hc", "lz4") else "none"


def _write_buf(out: io.BytesIO, arr: Optional[np.ndarray]):
    if arr is None:
        out.write(struct.pack("<Q", 0xFFFFFFFFFFFFFFFF))
        return
    raw = np.ascontiguousarray(arr).tobytes()
    out.write(struct.pack("<Q", len(raw)))
    out.write(raw)


def _read_buf(buf: memoryview, pos: int, dtype, shape
              ) -> Tuple[Optional[np.ndarray], int]:
    (n,) = struct.unpack_from("<Q", buf, pos)
    pos += 8
    if n == 0xFFFFFFFFFFFFFFFF:
        return None, pos
    arr = np.frombuffer(buf, dtype=dtype, count=n // np.dtype(dtype).itemsize,
                        offset=pos).reshape(shape)
    return arr, pos + n


def _type_str(dt: T.DataType) -> str:
    return dt.json_repr() if hasattr(dt, "json_repr") else dt.simple_string()


def _code_dtype(dict_size: int):
    if dict_size <= 0xFF:
        return np.uint8
    if dict_size <= 0xFFFF:
        return np.uint16
    return np.uint32


def _dict_refs_on(conf) -> bool:
    from ..config import ENCODED_SHUFFLE_DICT_REFS, RapidsConf
    conf = conf or RapidsConf.get_global()
    if not bool(conf.get(ENCODED_SHUFFLE_DICT_REFS)):
        return False
    # multi-slice topologies fetch peer blocks across process boundaries,
    # where the reader cannot resolve this process's registry — inline
    try:
        from .manager import get_shuffle_manager
        topo = get_shuffle_manager(conf).topology
        return topo is None or not topo.multi_slice
    except Exception:  # pragma: no cover - manager not initialized
        return False


def _serialize_encoded(out: io.BytesIO, col, n: int, meta: dict,
                       conf) -> bool:
    """Encoded-column wire write (frame v2).  Returns False to decline —
    the caller then materializes and writes the raw layout."""
    from ..columnar import encoded as E
    if not (E.op_enabled("shuffle", conf)):
        return False
    if isinstance(col, E.DictEncodedColumn):
        d = col.dictionary
        validity = np.asarray(col.validity)[:n]
        _write_buf(out, np.packbits(validity, bitorder="little"))
        cdt = _code_dtype(d.size)
        codes = np.asarray(col.codes)[:n].astype(cdt)
        _write_buf(out, codes)
        meta["enc"] = "dict"
        meta["dsize"] = d.size
        meta["dsorted"] = bool(d.sorted)
        meta["dhash"] = f"{d.content_hash:x}"
        dc = d.column
        raw_matrix = (n * (dc.width or 0)) + 4 * n  # chars + lengths
        dict_bytes = 0
        if _dict_refs_on(conf) and d.content_hash in _SENT_DICTS:
            meta["dref"] = True
            E._bump("wire_dict_refs")
        else:
            dmeta: dict = {}
            pos0 = out.tell()
            _serialize_column(out, dc, d.size, dmeta, conf)
            dict_bytes = out.tell() - pos0
            meta["dmeta"] = dmeta
            E._bump("wire_dict_inline")
            if _dict_refs_on(conf) \
                    and E.registered_dictionary(d.content_hash) is not None:
                _SENT_DICTS.add(d.content_hash)
        E._bump("wire_code_bytes", codes.nbytes)
        E.add_wire_saved(max(0, raw_matrix - codes.nbytes - dict_bytes))
        return True
    if isinstance(col, E.RLEColumn):
        validity = np.asarray(col.validity)[:n]
        _write_buf(out, np.packbits(validity, bitorder="little"))
        k = col.num_runs
        meta["enc"] = "rle"
        meta["nruns"] = k
        _write_buf(out, np.asarray(col.run_ends)[:k].astype(np.int32))
        rmeta: dict = {}
        _serialize_column(out, col.run_values, k, rmeta, conf)
        meta["rmeta"] = rmeta
        rv = col.run_values
        item = np.asarray(rv.data).dtype.itemsize
        E.add_wire_saved(max(0, (n - k) * item - 4 * k))
        return True
    return False


def _serialize_column(out: io.BytesIO, col: DeviceColumn, n: int,
                      meta: dict, conf=None):
    """Packed (live rows only) column write; meta collects shape info."""
    from ..columnar.encoded import DictEncodedColumn, RLEColumn
    if isinstance(col, (DictEncodedColumn, RLEColumn)):
        if _serialize_encoded(out, col, n, meta, conf):
            return
        col = col.materialized()
    validity = np.asarray(col.validity)[:n] if col.validity is not None \
        else np.ones(n, dtype=bool)
    _write_buf(out, np.packbits(validity, bitorder="little"))
    if col.is_array_like:
        w = col.array_width
        meta["w"] = w
        _write_buf(out, np.asarray(col.lengths)[:n].astype(np.int32))
        kids = []
        for ch in col.children:
            km: dict = {}
            _serialize_column(out, ch, n * w, km, conf)
            kids.append(km)
        meta["children"] = kids
        return
    if col.data is None:  # struct
        kids = []
        for ch in col.children:
            km = {}
            _serialize_column(out, ch, n, km, conf)
            kids.append(km)
        meta["children"] = kids
        return
    data = np.asarray(col.data)[:n]
    if data.ndim == 2:
        meta["sw"] = int(data.shape[1])
    _write_buf(out, data)
    _write_buf(out, np.asarray(col.lengths)[:n].astype(np.int32)
               if col.lengths is not None else None)
    _write_buf(out, np.asarray(col.aux)[:n] if col.aux is not None else None)


def serialize_batch(batch: ColumnarBatch, conf=None) -> bytes:
    tracing = _trace.TRACING["on"]
    t0 = time.perf_counter() if tracing else 0.0
    from ..columnar import encoded as E
    # thread-local wire accounting: exact per-frame delta even when pool
    # threads serialize other frames concurrently
    tok = E.begin_wire_account()
    # the wire span id is stamped both into the frame's schema json and
    # onto this span, so the consumer's deserialize span (which surfaces
    # the frame's producer_span) can be flow-connected back to here
    tctx = _trace.current_trace_context() if tracing else None
    wire_span = _trace.next_span_id() if tctx else ""
    frame = _serialize_batch(batch, conf, wire_span=wire_span)
    saved = E.end_wire_account(tok)
    if tracing:
        extra = ({"trace_id": tctx.get("trace", ""),
                  "span_id": wire_span} if wire_span else {})
        _trace.get_tracer().complete(
            "shuffle", "serialize_batch", t0, time.perf_counter() - t0,
            bytes=len(frame), rows=batch.num_rows_int, **extra)
    # per-query wire accounting (last_query_metrics): actual frame bytes
    # plus the encoded representation's saving vs raw value buffers
    from ..sql.physical.base import TaskContext
    t = TaskContext.current()
    if t is not None:
        t.inc_metric("shuffleBytesOnWire", len(frame))
        t.inc_metric("shuffleFramesWritten")
        if saved:
            t.inc_metric("shuffleEncodedBytesSaved", saved)
    if _om.METRICS["on"]:
        reg = _om.get_registry()
        reg.observe("shuffle_frame_bytes", len(frame))
        reg.inc("shuffle_bytes_on_wire_total", len(frame))
    return frame


def _serialize_batch(batch: ColumnarBatch, conf=None,
                     wire_span: str = "") -> bytes:
    # one transfer for all buffers, with device-side narrowing when the
    # batch is big enough to pay for the probe (columnar/prepack.py —
    # bytes shrink BEFORE they cross to the host, nvcomp-codec analog)
    from ..columnar.prepack import prepacked_device_get
    batch = prepacked_device_get(batch)
    n = batch.num_rows_int
    body = io.BytesIO()
    metas = []
    for col in batch.columns:
        m: dict = {}
        _serialize_column(body, col, n, m, conf)
        metas.append(m)
    schema = {
        "names": list(batch.names),
        "metas": metas,
        "specs": [_spec_of(c.dtype) for c in batch.columns],
    }
    # versioned header extension: the producer's distributed trace
    # context rides the schema json.  Readers only consume the
    # names/metas/specs keys, so pre-extension peers ignore it without a
    # layout version bump; trace-aware readers surface it on their
    # deserialize span (producer_trace/producer_span), letting
    # tools/trace_merge.py connect frame producer and consumer across
    # processes.
    if _trace.TRACING["on"]:
        tctx = _trace.current_trace_context()
        if tctx and tctx.get("trace"):
            schema["trace"] = {"trace": tctx["trace"],
                               "span": wire_span or _trace.next_span_id(),
                               "tenant": tctx.get("tenant", "")}
    sj = json.dumps(schema).encode()
    payload = body.getvalue()
    flags = 0
    raw = sj + payload
    if _codec(conf) == "zstd":
        try:
            import zstandard
        except ImportError:
            # codec library missing: degrade to uncompressed frames (the
            # flag bit tells readers) instead of failing every shuffle
            # write — readers only need zstd for frames that USED it
            zstandard = None
        if zstandard is not None:
            raw = zstandard.ZstdCompressor(level=1).compress(raw)
            flags |= _FLAG_ZSTD
    # xxhash64 frame checksum — corruption on the wire/disk fails loudly
    # instead of deserializing garbage.  "auto" only engages the native
    # library (the pure-Python fallback would dominate the hot path).
    tail = b""
    if _checksum_on(conf):
        from ..native import xxhash64_bytes
        crc = xxhash64_bytes(raw, seed=len(raw))
        flags |= _FLAG_CRC
        tail = struct.pack("<Q", crc)
    head = struct.pack("<4sHHII", _MAGIC, _VERSION, flags, n,
                       batch.num_cols)
    return head + struct.pack("<I", len(sj)) + raw + tail


def _checksum_on(conf) -> bool:
    from ..config import SHUFFLE_CHECKSUM, RapidsConf
    conf = conf or RapidsConf.get_global()
    mode = str(conf.get(SHUFFLE_CHECKSUM)).lower()
    if mode == "true":
        return True
    if mode == "false":
        return False
    from ..native import available
    return available()


def _spec_of(dt: T.DataType):
    if isinstance(dt, T.ArrayType):
        return {"k": "array", "e": _spec_of(dt.element_type)}
    if isinstance(dt, T.MapType):
        return {"k": "map", "key": _spec_of(dt.key_type),
                "v": _spec_of(dt.value_type)}
    if isinstance(dt, T.StructType):
        return {"k": "struct",
                "fields": [[f.name, _spec_of(f.data_type)]
                           for f in dt.fields]}
    if isinstance(dt, T.DecimalType):
        return {"k": "decimal", "p": dt.precision, "s": dt.scale}
    return {"k": type(dt).__name__}


_SIMPLE = {c.__name__: c for c in (
    T.BooleanType, T.ByteType, T.ShortType, T.IntegerType, T.LongType,
    T.FloatType, T.DoubleType, T.StringType, T.BinaryType, T.DateType,
    T.TimestampType, T.NullType)}


def _spec_to_type(spec) -> T.DataType:
    k = spec["k"]
    if k == "array":
        return T.ArrayType(_spec_to_type(spec["e"]))
    if k == "map":
        return T.MapType(_spec_to_type(spec["key"]), _spec_to_type(spec["v"]))
    if k == "struct":
        return T.StructType(tuple(
            T.StructField(n, _spec_to_type(s), True)
            for n, s in spec["fields"]))
    if k == "decimal":
        return T.DecimalType(spec["p"], spec["s"])
    return _SIMPLE[k]()


def _deserialize_encoded(buf: memoryview, pos: int, dt: T.DataType, n: int,
                         cap: int, meta: dict) -> Tuple[DeviceColumn, int]:
    """Read a v2 encoded column (host numpy buffers).  With the encoded
    kill switch off the column materializes immediately on the host, so a
    disabled session never observes encoded representations."""
    from ..columnar import encoded as E
    enc = meta["enc"]
    bits, pos = _read_buf(buf, pos, np.uint8, (-1,))
    validity = np.zeros(cap, dtype=bool)
    if n:
        validity[:n] = np.unpackbits(bits, count=n, bitorder="little") \
            .astype(bool)
    if enc == "dict":
        dsize = int(meta["dsize"])
        codes_np, pos = _read_buf(buf, pos, _code_dtype(dsize), (-1,))
        codes = np.zeros(cap, dtype=np.int32)
        if n:
            codes[:n] = codes_np.astype(np.int32)
            codes[:n][~validity[:n]] = 0
        dhash = int(meta["dhash"], 16)
        if meta.get("dref"):
            d = E.registered_dictionary(dhash)
            if d is None:
                raise FrameCorrupt(
                    f"shuffle frame references unknown dictionary "
                    f"{meta['dhash']} — registry miss (cross-process "
                    f"frame?); refetch/recompute will inline it")
        else:
            dcap = bucket_capacity(dsize + 1)
            dcol, pos = _deserialize_column(buf, pos, dt, dsize, dcap,
                                            meta["dmeta"])
            d = E.dictionary_from_wire(dcol, dsize, bool(meta["dsorted"]),
                                       dhash)
        col = E.DictEncodedColumn(dt, codes, d, validity)
        if not E.enabled():
            return E.materialize_np(col), pos
        return col, pos
    if enc == "rle":
        k = int(meta["nruns"])
        ends_np, pos = _read_buf(buf, pos, np.int32, (-1,))
        run_cap = bucket_capacity(k)
        rends = np.full(run_cap, cap, dtype=np.int32)
        rends[:k] = ends_np
        rv, pos = _deserialize_column(buf, pos, dt, k, run_cap,
                                      meta["rmeta"])
        col = E.RLEColumn(dt, rv, rends, k, validity)
        if not E.enabled():
            return E.materialize_np(col), pos
        return col, pos
    raise FrameCorrupt(f"unknown encoded column kind {enc!r}")


def _deserialize_column(buf: memoryview, pos: int, dt: T.DataType, n: int,
                        cap: int, meta: dict) -> Tuple[DeviceColumn, int]:
    # host (numpy) buffers: the device upload happens naturally when a
    # jitted exec traces the batch (jnp.asarray on trace), so host-side
    # consumers never see device arrays
    if "enc" in meta:
        return _deserialize_encoded(buf, pos, dt, n, cap, meta)
    bits, pos = _read_buf(buf, pos, np.uint8, (-1,))
    validity = np.zeros(cap, dtype=bool)
    if n:
        validity[:n] = np.unpackbits(bits, count=n, bitorder="little") \
            .astype(bool)
    v = validity
    if isinstance(dt, (T.ArrayType, T.MapType)):
        w = meta["w"]
        lens_np, pos = _read_buf(buf, pos, np.int32, (-1,))
        lens = np.zeros(cap, dtype=np.int32)
        lens[:n] = lens_np
        kids = []
        child_types = [dt.element_type] if isinstance(dt, T.ArrayType) else \
            [dt.key_type, dt.value_type]
        for ct, km in zip(child_types, meta["children"]):
            ch, pos = _deserialize_column(buf, pos, ct, n * w, cap * w, km)
            kids.append(ch)
        return make_array_column(dt, lens, tuple(kids), v), pos
    if isinstance(dt, T.StructType):
        kids = []
        for f, km in zip(dt.fields, meta["children"]):
            ch, pos = _deserialize_column(buf, pos, f.data_type, n, cap, km)
            kids.append(ch)
        return DeviceColumn(dt, None, v, children=tuple(kids)), pos
    sw = meta.get("sw")
    if sw is not None:
        data_np, pos = _read_buf(buf, pos, np.uint8, (n, sw))
        data = np.zeros((cap, sw), dtype=np.uint8)
        data[:n] = data_np
    else:
        np_dtype = dt.np_dtype if dt.np_dtype is not None else np.int8
        data_np, pos = _read_buf(buf, pos, np_dtype, (-1,))
        data = np.zeros(cap, dtype=np_dtype)
        data[:n] = data_np[:n] if data_np is not None else 0
    lens_np, pos = _read_buf(buf, pos, np.int32, (-1,))
    lengths = None
    if lens_np is not None:
        lengths = np.zeros(cap, dtype=np.int32)
        lengths[:n] = lens_np
    aux_np, pos = _read_buf(buf, pos, np.int64, (-1,))
    aux = None
    if aux_np is not None:
        aux = np.zeros(cap, dtype=np.int64)
        aux[:n] = aux_np
    return DeviceColumn(dt, data, v, lengths, aux), pos


def deserialize_batch(frame: bytes, capacity: Optional[int] = None
                     ) -> ColumnarBatch:
    if not _trace.TRACING["on"]:
        return _deserialize_batch(frame, capacity)
    # surface the frame's embedded producer trace context on the
    # consumer span (producer_trace/producer_span) — the cross-process
    # edge trace_merge.py stitches for frames that moved between event
    # logs
    t0 = time.perf_counter()
    trace_out: list = []
    batch = _deserialize_batch(frame, capacity, trace_out=trace_out)
    args = {"bytes": len(frame)}
    if trace_out:
        args.update(producer_trace=str(trace_out[0].get("trace", "")),
                    producer_span=str(trace_out[0].get("span", "")))
    _trace.get_tracer().complete("shuffle", "deserialize_batch", t0,
                                 time.perf_counter() - t0, **args)
    return batch


def _deserialize_batch(frame: bytes, capacity: Optional[int] = None,
                       trace_out: Optional[list] = None
                       ) -> ColumnarBatch:
    if len(frame) < 20:
        raise FrameCorrupt(f"shuffle frame truncated ({len(frame)} bytes)")
    head = struct.unpack_from("<4sHHII", frame, 0)
    if head[0] != _MAGIC:
        raise FrameCorrupt("bad shuffle frame magic")
    flags, n, ncols = head[2], head[3], head[4]
    (sj_len,) = struct.unpack_from("<I", frame, 16)
    raw = frame[20:]
    if flags & _FLAG_CRC:
        raw, tail = raw[:-8], raw[-8:]
        from ..native import xxhash64_bytes
        (want,) = struct.unpack("<Q", tail)
        got = xxhash64_bytes(raw, seed=len(raw))
        if got != want:
            raise FrameCorrupt(
                f"shuffle frame checksum mismatch "
                f"(got {got:#x}, want {want:#x}) — corrupt frame")
    if flags & _FLAG_ZSTD:
        import zstandard
        raw = zstandard.ZstdDecompressor().decompress(raw)
    schema = json.loads(raw[:sj_len])
    if trace_out is not None and isinstance(schema.get("trace"), dict):
        trace_out.append(schema["trace"])
    buf = memoryview(raw)[sj_len:]
    cap = capacity or bucket_capacity(n)
    cols = []
    pos = 0
    for spec, meta in zip(schema["specs"], schema["metas"]):
        dt = _spec_to_type(spec)
        col, pos = _deserialize_column(buf, pos, dt, n, cap, meta)
        cols.append(col)
    return ColumnarBatch.make(tuple(schema["names"]), cols, n)


def concat_serialized(frames: Sequence[bytes]) -> Optional[ColumnarBatch]:
    """Host-side concat of serialized tables before one device upload
    (``GpuShuffleCoalesceExec.scala:36-56`` analog)."""
    batches = [deserialize_batch(f) for f in frames]
    batches = [b for b in batches if b.num_rows_int > 0]
    if not batches:
        return None
    if len(batches) == 1:
        return batches[0]
    return ColumnarBatch.concat(batches)
