"""Device-side Parquet decode — the TPU-native analog of the reference's
on-GPU parquet decode (``GpuParquetScan.scala:2649`` ``Table.readParquet``:
host parses footers and assembles raw column-chunk bytes, the device decodes
encodings).  The split here follows the same line:

* **Host** (structure only, O(pages + runs), no per-value work): pyarrow
  footer metadata, a minimal Thrift-compact ``PageHeader`` reader, per-page
  decompression (no TPU byte-codec exists — the reference offloads this leg
  to nvcomp), and a walk of the RLE/bit-packed hybrid *run headers* that
  yields a run-descriptor table (a handful of entries per page).
* **Device** (all per-value work, one shape-bucketed XLA program per
  signature): bit-unpacking of packed runs and PLAIN sections via gather +
  shift arithmetic over uint32 words (each value's run attributes come
  from a prefix sum over run-start marks, never from a search per value),
  RLE broadcast, dictionary-index
  gather, definition-level decode -> validity, non-null scatter (cumsum
  positions), and physical->carrier finishing (two's-complement bitcasts,
  IEEE-754 float64 reconstruction without 64-bit bitcast, timestamp unit
  scaling).

PLAIN value sections are degenerate bit-packed runs (width = 8*itemsize at a
byte-aligned bit offset), so ONE descriptor-driven kernel decodes a whole
column chunk — across all its pages and row groups — in a single call.

Anything outside the envelope (nested columns, mixed PLAIN/dictionary
chunks, exotic encodings/codecs, pathological run counts) falls back to the
host pyarrow decode **per column**; supported columns still decode on device
and the two merge into one batch — the same per-op fallback discipline the
reference applies at plan level.
"""

from __future__ import annotations

import math
import struct
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --------------------------------------------------------------------------
# Thrift compact-protocol reader (just enough for parquet PageHeader)
# --------------------------------------------------------------------------

_CT_STOP = 0
_CT_TRUE = 1
_CT_FALSE = 2
_CT_BYTE = 3
_CT_I16 = 4
_CT_I32 = 5
_CT_I64 = 6
_CT_DOUBLE = 7
_CT_BINARY = 8
_CT_LIST = 9
_CT_SET = 10
_CT_MAP = 11
_CT_STRUCT = 12


class _ThriftReader:
    """Minimal thrift compact-protocol cursor over a bytes object."""

    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def _byte(self) -> int:
        b = self.buf[self.pos]
        self.pos += 1
        return b

    def varint(self) -> int:
        out, self.pos = _read_uleb(self.buf, self.pos)
        return out

    def zigzag(self) -> int:
        v = self.varint()
        return (v >> 1) ^ -(v & 1)

    def skip(self, ftype: int) -> None:
        if ftype in (_CT_TRUE, _CT_FALSE):
            return
        if ftype == _CT_BYTE:
            self.pos += 1
        elif ftype in (_CT_I16, _CT_I32, _CT_I64):
            self.varint()
        elif ftype == _CT_DOUBLE:
            self.pos += 8
        elif ftype == _CT_BINARY:
            # NB: must read the varint BEFORE touching pos — augmented
            # assignment would snapshot pos before varint() advances it
            ln = self.varint()
            self.pos += ln
        elif ftype in (_CT_LIST, _CT_SET):
            h = self._byte()
            size = h >> 4
            etype = h & 0xF
            if size == 15:
                size = self.varint()
            for _ in range(size):
                self.skip(etype)
        elif ftype == _CT_MAP:
            size = self.varint()
            if size:
                h = self._byte()
                kt, vt = h >> 4, h & 0xF
                for _ in range(size):
                    self.skip(kt)
                    self.skip(vt)
        elif ftype == _CT_STRUCT:
            for _fid, ft in self.fields():
                self.skip(ft)
        else:
            raise ValueError(f"unknown thrift compact type {ftype}")

    def fields(self):
        """Yield (field_id, type) for one struct, consuming the STOP."""
        fid = 0
        while True:
            b = self._byte()
            if b == _CT_STOP:
                return
            delta = (b >> 4) & 0xF
            ftype = b & 0xF
            if delta:
                fid += delta
            else:
                fid = self.zigzag()
            yield fid, ftype


@dataclass
class _PageHeader:
    type: int = -1                 # 0 data, 2 dictionary, 3 data v2
    uncompressed_size: int = 0
    compressed_size: int = 0
    num_values: int = 0
    encoding: int = -1
    def_encoding: int = -1
    # v2 only
    num_nulls: int = -1
    def_len: int = 0
    rep_len: int = 0
    values_compressed: bool = True
    header_len: int = 0            # bytes consumed by the header itself


def _parse_page_header(buf: bytes, pos: int) -> _PageHeader:
    r = _ThriftReader(buf, pos)
    h = _PageHeader()
    for fid, ftype in r.fields():
        if fid == 1 and ftype == _CT_I32:
            h.type = r.zigzag()
        elif fid == 2 and ftype == _CT_I32:
            h.uncompressed_size = r.zigzag()
        elif fid == 3 and ftype == _CT_I32:
            h.compressed_size = r.zigzag()
        elif fid == 5 and ftype == _CT_STRUCT:      # DataPageHeader
            for sfid, sft in r.fields():
                if sfid == 1 and sft == _CT_I32:
                    h.num_values = r.zigzag()
                elif sfid == 2 and sft == _CT_I32:
                    h.encoding = r.zigzag()
                elif sfid == 3 and sft == _CT_I32:
                    h.def_encoding = r.zigzag()
                else:
                    r.skip(sft)
        elif fid == 7 and ftype == _CT_STRUCT:      # DictionaryPageHeader
            for sfid, sft in r.fields():
                if sfid == 1 and sft == _CT_I32:
                    h.num_values = r.zigzag()
                elif sfid == 2 and sft == _CT_I32:
                    h.encoding = r.zigzag()
                else:
                    r.skip(sft)
        elif fid == 8 and ftype == _CT_STRUCT:      # DataPageHeaderV2
            for sfid, sft in r.fields():
                if sfid == 1 and sft == _CT_I32:
                    h.num_values = r.zigzag()
                elif sfid == 2 and sft == _CT_I32:
                    h.num_nulls = r.zigzag()
                elif sfid == 4 and sft == _CT_I32:
                    h.encoding = r.zigzag()
                elif sfid == 5 and sft == _CT_I32:
                    h.def_len = r.zigzag()
                elif sfid == 6 and sft == _CT_I32:
                    h.rep_len = r.zigzag()
                elif sfid == 7:
                    h.values_compressed = (sft == _CT_TRUE)
                else:
                    r.skip(sft)
        else:
            r.skip(ftype)
    h.header_len = r.pos - pos
    return h


# --------------------------------------------------------------------------
# Encodings / codecs / guards
# --------------------------------------------------------------------------

_ENC_PLAIN = 0
_ENC_PLAIN_DICT = 2
_ENC_RLE = 3
_ENC_RLE_DICT = 8

_CODECS: Dict[str, Optional[str]] = {
    "UNCOMPRESSED": None,
    "SNAPPY": "snappy",
    "GZIP": "gzip",
    "ZSTD": "zstd",
}

#: per-page run-count guard: a hostile hybrid stream could make the O(runs)
#: host walk cost O(values) — beyond this the column goes to the host path
_MAX_RUNS_PER_PAGE = 4096

_PHYS_ITEMBITS = {"INT32": 32, "INT64": 64, "FLOAT": 32, "DOUBLE": 64,
                  "BOOLEAN": 1}

_PHYS_NP = {"INT32": np.int32, "INT64": np.int64,
            "FLOAT": np.float32, "DOUBLE": np.float64}


def _strings_matrix(values, lens: np.ndarray):
    """bytes sequence + lengths -> (zero-padded byte matrix with a
    power-of-two width bucket, int32 lengths) — the dictionary analog of
    the engine's string column layout."""
    from ..columnar.column import bucket_width
    width = bucket_width(int(lens.max()) if len(lens) else 0)
    mat = np.zeros((max(len(lens), 1), width), np.uint8)
    for i, v in enumerate(values):
        if v:
            mat[i, :len(v)] = np.frombuffer(v, np.uint8)
    return mat, lens.astype(np.int32)


class _Unsupported(Exception):
    """Internal: this column can't take the device path — fall back."""


class _DeclineFile(Exception):
    """Internal: the whole FILE must take the host path (per-column
    fallback would itself be unsafe — e.g. a ragged string column needs
    the host pipeline's width-class splitting, which only applies to
    whole host tables)."""


def _max_string_matrix_bytes(conf=None) -> int:
    """Cap on a device string matrix (capacity x width-bucket bytes) from
    a dictionary gather — the device-path twin of the engine's ragged-
    string upload guard (convert.split_for_upload)."""
    from ..config import RAGGED_STRING_SPLIT_BYTES, RapidsConf
    thr = int((conf or RapidsConf.get_global())
              .get(RAGGED_STRING_SPLIT_BYTES))
    return thr if thr > 0 else (1 << 62)


def _decompress(codec: Optional[str], data: bytes, out_size: int) -> bytes:
    if codec is None:
        return data
    import pyarrow as pa
    out = pa.Codec(codec).decompress(data, decompressed_size=out_size)
    return out.to_pybytes()


# --------------------------------------------------------------------------
# Hybrid (RLE / bit-packed) run-descriptor walk — host, O(runs)
# --------------------------------------------------------------------------

@dataclass
class _Runs:
    """Descriptor table for the device expansion kernel.  ``width == 0``
    marks an RLE run (broadcast ``rle_val``); otherwise the run is
    ``width``-bit packed starting at absolute bit ``src_bit`` of the
    uploaded chunk buffer."""

    out_start: List[int] = field(default_factory=list)
    src_bit: List[int] = field(default_factory=list)
    width: List[int] = field(default_factory=list)
    rle_val: List[int] = field(default_factory=list)

    def add_rle(self, out_start: int, value: int) -> None:
        self.out_start.append(out_start)
        self.src_bit.append(0)
        self.width.append(0)
        self.rle_val.append(value)

    def add_packed(self, out_start: int, src_bit: int, width: int) -> None:
        self.out_start.append(out_start)
        self.src_bit.append(src_bit)
        self.width.append(width)
        self.rle_val.append(0)

    def __len__(self) -> int:
        return len(self.out_start)


def _read_uleb(buf: bytes, pos: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        b = buf[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7


def _walk_hybrid(buf: bytes, start: int, end: int, bit_width: int,
                 num_values: int, out_base: int, base_bit: int,
                 runs: _Runs, count_eq: Optional[int] = None) -> int:
    """Walk RLE/bit-packed hybrid run headers in ``buf[start:end)`` covering
    ``num_values`` logical values, appending descriptors.  ``base_bit`` is
    the absolute bit position of ``buf[start]`` in the device buffer (chunk
    bytes upload verbatim, so source positions line up 1:1).  When
    ``count_eq`` is given, also counts values == count_eq in the SAME walk
    (vectorized popcount for packed groups) — the def-level non-null count
    the dense-stream offsets need, without a second pass."""
    pos = start
    produced = 0
    hits = 0
    vbytes = (bit_width + 7) // 8
    n0 = len(runs)
    while produced < num_values and pos < end:
        if len(runs) - n0 > _MAX_RUNS_PER_PAGE:
            raise _Unsupported("run count guard")
        header, pos = _read_uleb(buf, pos)
        if header & 1:                       # bit-packed groups of 8
            groups = header >> 1
            count = min(groups * 8, num_values - produced)
            runs.add_packed(out_base + produced,
                            base_bit + (pos - start) * 8, bit_width)
            if count_eq is not None:
                nbytes = groups * bit_width
                chunk = np.frombuffer(buf, np.uint8, nbytes, pos)
                bits = np.unpackbits(chunk, bitorder="little")
                if bit_width == 1:
                    hits += int(np.count_nonzero(bits[:count] == count_eq))
                else:
                    vals = bits[:count * bit_width].reshape(count, bit_width)
                    weights = (1 << np.arange(bit_width)).astype(np.int64)
                    hits += int(np.count_nonzero(
                        vals @ weights == count_eq))
            pos += groups * bit_width        # groups * 8 values * w bits / 8
            produced += count
        else:                                # RLE run
            count = min(header >> 1, num_values - produced)
            val = int.from_bytes(buf[pos:pos + vbytes], "little") \
                if vbytes else 0
            pos += vbytes
            runs.add_rle(out_base + produced, val)
            if count_eq is not None and val == count_eq:
                hits += count
            produced += count
    if produced < num_values:
        raise _Unsupported("short hybrid stream")
    return hits


# --------------------------------------------------------------------------
# Device kernels (shape-bucketed; jit caches one program per signature)
# --------------------------------------------------------------------------

def _pad_pow2(n: int, minimum: int = 8) -> int:
    n = max(int(n), minimum, 1)
    return 1 << (n - 1).bit_length()


def _prefix_sum(x):
    """``jnp.cumsum(x, axis=1)`` in two levels: inside blocks of 1024
    columns, then the blocks' carries.  Same sums and the same run time on
    the chip; the TPU compiler takes under a second for this form at 2^21
    columns where the flat one takes 6-14 s (PERF.md section 6, PR 32)."""
    k, n = x.shape
    block = math.gcd(n, 1024)
    inner = jnp.cumsum(x.reshape(k, n // block, block), axis=2)
    total = inner[:, :, -1]
    carry = jnp.cumsum(total, axis=1) - total
    return (inner + carry[:, :, None]).reshape(k, n)


def _spread_runs(out_start, cols, out_cap):
    """Run-constant int32 columns at every output position, with no search
    and no gather: ``q[r] - q[r-1]`` is scattered at ``out_start[r]`` (one
    update per run, never per value) and a prefix sum carries it to the
    next run's start.  The sums telescope in wrapping int32 arithmetic, so
    any 32-bit pattern comes back exactly.  Runs sharing an ``out_start``
    add up to the last one's value (a zero-length run loses to its
    successor), table padding (``out_start`` past ``out_cap``) is dropped,
    and positions before the first run read 0.  Returns one
    ``int32[out_cap]`` per column."""
    table = jnp.stack(cols)
    delta = table - jnp.pad(table, ((0, 0), (1, 0)))[:, :-1]
    marks = jnp.zeros((len(cols), out_cap), jnp.int32
                      ).at[:, out_start].add(delta, mode="drop")
    return tuple(_prefix_sum(marks))


def _run_origin(out_start, src_bit, width):
    """Per run, the bit address its value 0 would have if the run began at
    output 0: inside a run ``bitpos = origin + idx * width``.  May be
    negative and pass 2^31 (merged chunk buffers exceed 256 MB), so it is
    taken in int64 over the run table only and handed on as an int32
    (word, bit-in-word) pair: 64-bit integers are emulated on the chip."""
    origin = src_bit - out_start.astype(jnp.int64) * width
    return ((origin >> 5).astype(jnp.int32), (origin & 31).astype(jnp.int32))


def _bit_address(word0, bit0, w, extra_bits=0):
    """(word index, shift) of bit ``origin + idx * w + extra_bits`` for
    every output ``idx``.  ``idx`` is split at 32 so that no intermediate
    leaves int32 while the address itself may pass 2^31 bits."""
    idx = jnp.arange(word0.shape[0], dtype=jnp.int32)
    t = (idx & 31) * w + bit0 + extra_bits
    return word0 + (idx >> 5) * w + (t >> 5), (t & 31).astype(jnp.uint32)


def _window_u32(words, word0, bit0, w, extra_bits=0):
    """The 32 bits at :func:`_bit_address`: two word reads, shifted
    together."""
    w0, sh = _bit_address(word0, bit0, w, extra_bits)
    w0 = jnp.clip(w0, 0, words.shape[0] - 2)
    return (words[w0] >> sh) | jnp.where(
        sh == 0, jnp.uint32(0), words[w0 + 1] << (jnp.uint32(32) - sh))


@partial(jax.jit, static_argnames=("out_cap",))
def _expand_runs_u32(words, out_start, src_bit, width, rle_val, out_cap):
    """Expand a run-descriptor table into ``uint32[out_cap]`` raw values:
    bit-packed runs read+shift from the word buffer, RLE runs broadcast.
    Values before the first run and past the last are garbage — callers
    mask them."""
    word0, bit0 = _run_origin(out_start, src_bit, width)
    word0, bit0, w, rle = _spread_runs(
        out_start, (word0, bit0, width, rle_val), out_cap)
    raw = _window_u32(words, word0, bit0, w)
    wu = w.astype(jnp.uint32)
    mask = jnp.where(wu >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << wu) - jnp.uint32(1))
    return jnp.where(w == 0, rle.astype(jnp.uint32), raw & mask)


@partial(jax.jit, static_argnames=("out_cap", "width"))
def _expand_flba(words, out_start, src_bit, out_cap, width):
    """FIXED_LEN_BYTE_ARRAY expansion: each value is `width` big-endian
    two's-complement bytes (parquet decimal storage) -> sign-extended
    (lo, hi) uint64 words.  Static byte loop (width <= 16)."""
    word0, bit0 = _spread_runs(
        out_start, _run_origin(out_start, src_bit, width * 8), out_cap)
    lo = jnp.zeros(out_cap, jnp.uint64)
    hi = jnp.zeros(out_cap, jnp.uint64)
    first_byte = None
    for k in range(width):
        b = _window_u32(words, word0, bit0, width * 8, k * 8
                        ) & jnp.uint32(0xFF)
        if k == 0:
            first_byte = b
        b64 = b.astype(jnp.uint64)
        pos = (width - 1 - k) * 8
        if pos < 64:
            lo = lo | (b64 << jnp.uint64(pos))
        else:
            hi = hi | (b64 << jnp.uint64(pos - 64))
    neg = (first_byte & jnp.uint32(0x80)) != 0
    if width < 8:
        fill_lo = jnp.uint64((~((1 << (width * 8)) - 1)) & ((1 << 64) - 1))
        lo = jnp.where(neg, lo | fill_lo, lo)
        hi = jnp.where(neg, jnp.uint64((1 << 64) - 1), hi)
    elif width == 8:
        hi = jnp.where(neg, jnp.uint64((1 << 64) - 1), hi)
    elif width < 16:
        fill_hi = jnp.uint64(
            (~((1 << ((width - 8) * 8)) - 1)) & ((1 << 64) - 1))
        hi = jnp.where(neg, hi | fill_hi, hi)
    return lo, hi


def _flba_bytes_to_words(entries, width: int):
    """Host: sequence of `width`-byte big-endian values -> (lo, hi) int64
    numpy arrays (used for small dictionary pages only)."""
    n = len(entries)
    if n == 0:
        return np.zeros(1, np.int64), np.zeros(1, np.int64)
    raw = np.frombuffer(b"".join(entries), np.uint8).reshape(n, width)
    lo = np.zeros(n, np.uint64)
    hi = np.zeros(n, np.uint64)
    for k in range(width):
        b = raw[:, k].astype(np.uint64)
        pos = (width - 1 - k) * 8
        if pos < 64:
            lo |= b << np.uint64(pos)
        else:
            hi |= b << np.uint64(pos - 64)
    neg = raw[:, 0] >= 128
    if width < 8:
        lo[neg] |= np.uint64((~((1 << (width * 8)) - 1)) & ((1 << 64) - 1))
        hi[neg] = np.uint64((1 << 64) - 1)
    elif width == 8:
        hi[neg] = np.uint64((1 << 64) - 1)
    elif width < 16:
        hi[neg] |= np.uint64(
            (~((1 << ((width - 8) * 8)) - 1)) & ((1 << 64) - 1))
    return lo.view(np.int64), hi.view(np.int64)


@partial(jax.jit, static_argnames=("out_cap",))
def _expand_runs_u64(words, out_start, src_bit, out_cap):
    """64-bit PLAIN expansion: each value is assembled from two 32-bit
    window reads (sections are byte- but not word-aligned, so each window
    may itself span two words)."""
    word0, bit0 = _spread_runs(
        out_start, _run_origin(out_start, src_bit, 64), out_cap)
    lo0 = _window_u32(words, word0, bit0, 64)
    hi0 = _window_u32(words, word0, bit0, 64, 32)
    return (hi0.astype(jnp.uint64) << jnp.uint64(32)) | lo0.astype(jnp.uint64)


def _u64_to_i64(raw):
    from ..columnar.convert import u64_to_i64
    return u64_to_i64(raw)


def _f64_from_bits(bits):
    """IEEE-754 bits -> float64 arithmetically (inverse of the engine's
    ``convert._f64_bits``; denormals flush to signed zero, matching the
    engine's DAZ semantics)."""
    sign = jnp.where((bits >> jnp.uint64(63)) > 0, -1.0, 1.0)
    expf = ((bits >> jnp.uint64(52)) & jnp.uint64(0x7FF)).astype(jnp.int32)
    mant = (bits & jnp.uint64((1 << 52) - 1)).astype(jnp.float64)
    frac = 1.0 + mant * (2.0 ** -52)
    val = sign * jnp.ldexp(frac, expf - 1023)
    val = jnp.where(expf == 0, sign * 0.0, val)
    val = jnp.where(expf == 0x7FF, sign * jnp.inf, val)
    return jnp.where((expf == 0x7FF) & (mant != 0.0), jnp.nan, val)


def byte_at_words(words, k):
    """Byte ``k`` of an uploaded little-endian word buffer (traced).
    Shared by the parquet and ORC string gathers."""
    w = jnp.clip((k >> 2).astype(jnp.int32), 0, words.shape[0] - 1)
    return (words[w] >> ((k & 3).astype(jnp.uint32) * 8)) & jnp.uint32(0xFF)


@partial(jax.jit, static_argnames=("width", "cap"))
def gather_string_matrix(words, starts, lens, width, cap):
    """Variable-length byte values at ``starts`` -> [cap, width] matrix
    (row r byte j = buf[starts[r] + j], zero past the row's length)."""
    j = jnp.arange(width, dtype=jnp.int64)[None, :]
    pos = starts[:, None].astype(jnp.int64) + j
    b = byte_at_words(words, pos)
    live = j < lens[:, None]
    return jnp.where(live, b, 0).astype(jnp.uint8)


@jax.jit
def _remap_indices(idx, group_starts, remap_offsets, remap):
    """Apply per-row-group dictionary remapping: dense value j belongs to
    the last group g with group_starts[g] <= j; its unioned-dictionary
    index is remap[remap_offsets[g] + local_idx]."""
    (offset,) = _spread_runs(group_starts, (remap_offsets,), idx.shape[0])
    pos = jnp.clip(offset + idx, 0, remap.shape[0] - 1)
    return remap[pos]


@partial(jax.jit, static_argnames=("cap",))
def _scatter_nonnull(dense, valid, n, cap):
    """Place dense non-null values at their row positions; null and dead
    rows get zeroed data.  Returns (data, final_validity)."""
    rowlive = jnp.arange(cap, dtype=jnp.int32) < n
    v = valid & rowlive
    pos = jnp.cumsum(v.astype(jnp.int32)) - 1
    gathered = dense[jnp.clip(pos, 0, dense.shape[0] - 1)]
    zero = jnp.zeros((), dtype=dense.dtype)
    if dense.ndim == 2:
        return jnp.where(v[:, None], gathered, zero), v
    return jnp.where(v, gathered, zero), v


# --------------------------------------------------------------------------
# Column-chunk planning (host)
# --------------------------------------------------------------------------

@dataclass
class _ChunkPlan:
    """Host-side decode plan for one column over the selected row groups:
    the concatenated decompressed page payloads plus run descriptors."""

    buf: bytes = b""
    total_values: int = 0
    total_nonnull: int = 0
    def_runs: _Runs = field(default_factory=_Runs)
    val_runs: _Runs = field(default_factory=_Runs)
    dict_values: Optional[np.ndarray] = None
    dict_strings: Optional[Tuple[np.ndarray, np.ndarray]] = None
    is_dict: Optional[bool] = None
    nullable: bool = True
    # merged-plan only: per-row-group dictionaries usually diverge (each
    # writer chunk builds its own, in first-occurrence order), so indices
    # are remapped ON DEVICE into a unioned global dictionary:
    # value j of the dense stream belongs to the last group g with
    # group_starts[g] <= j; its global index is remap[remap_offsets[g] + idx]
    remap: Optional[np.ndarray] = None            # int32, concat per group
    remap_offsets: Optional[np.ndarray] = None    # int32[G]
    group_starts: Optional[np.ndarray] = None     # int32[G] dense offsets
    # PLAIN BYTE_ARRAY pages: per-page payload byte offsets into buf +
    # value lengths (host walk of the u32 prefixes; native helper or a
    # bounded python loop), consumed by the device gather kernel
    str_starts: List[np.ndarray] = field(default_factory=list)
    str_lens: List[np.ndarray] = field(default_factory=list)
    # what the page walk read: pages, decompressed page bytes
    pages: int = 0
    out_bytes: int = 0


def _plain_dict_values(phys: str, data: bytes, n: int) -> np.ndarray:
    np_t = _PHYS_NP.get(phys)
    if np_t is None:
        raise _Unsupported(f"dictionary of {phys}")
    return np.frombuffer(data, np_t, n)


def _plain_dict_strings(data: bytes, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Length-prefixed BYTE_ARRAY dictionary -> (byte matrix, lengths).
    Dictionaries are bounded by the writer's dict-size cap, so this host
    loop is O(dictionary), not O(rows)."""
    lens = np.empty(n, np.int32)
    vals: List[bytes] = []
    pos = 0
    for i in range(n):
        (ln,) = struct.unpack_from("<i", data, pos)
        pos += 4
        vals.append(data[pos:pos + ln])
        pos += ln
        lens[i] = ln
    return _strings_matrix(vals, lens)


#: python-loop ceiling for the PLAIN BYTE_ARRAY prefix walk when the
#: native helper is unavailable — beyond this the host loop would rival
#: the decode itself, so the column declines to pyarrow instead
_PY_WALK_MAX = 100_000


def _walk_byte_array(data: np.ndarray, n: int):
    """(payload starts int64[n], lens int32[n]) for n u32-length-prefixed
    values — native scan, or a bounded python loop."""
    from ..native import byte_array_walk
    try:
        out = byte_array_walk(data, n)
    except ValueError:
        raise _Unsupported("truncated BYTE_ARRAY section")
    if out is not None:
        return out
    if n > _PY_WALK_MAX:
        raise _Unsupported("PLAIN byte-array walk without native helper")
    starts = np.empty(n, np.int64)
    lens = np.empty(n, np.int32)
    buf = data.tobytes()
    pos = 0
    for i in range(n):
        if pos + 4 > len(buf):
            raise _Unsupported("truncated BYTE_ARRAY section")
        (ln,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        if ln > len(buf) - pos:
            raise _Unsupported("truncated BYTE_ARRAY section")
        starts[i] = pos
        lens[i] = ln
        pos += ln
    return starts, lens


def _plan_chunk(raw: bytes, cc, phys: str, nullable: bool,
                type_length: int = 0) -> _ChunkPlan:
    """Parse one column chunk's pages into a decode plan.  Raises
    ``_Unsupported`` for anything outside the device-decode envelope."""
    codec = _CODECS.get(cc.compression, "?")
    if codec == "?":
        raise _Unsupported(f"codec {cc.compression}")
    itembits = _PHYS_ITEMBITS.get(phys)
    if phys == "FIXED_LEN_BYTE_ARRAY":
        if not 0 < type_length <= 16:
            raise _Unsupported(f"FLBA width {type_length}")
        itembits = type_length * 8
    if itembits is None and phys != "BYTE_ARRAY":
        raise _Unsupported(f"physical type {phys}")
    plan = _ChunkPlan(nullable=nullable)
    max_def = 1 if nullable else 0

    pieces: List[bytes] = []
    piece_bits = 0
    pos = 0
    n_pages = 0
    while plan.total_values < cc.num_values and pos < len(raw):
        h = _parse_page_header(raw, pos)
        pos += h.header_len
        body = raw[pos:pos + h.compressed_size]
        pos += h.compressed_size
        n_pages += 1
        if n_pages > 100_000:
            raise _Unsupported("page count guard")

        plan.pages += 1
        plan.out_bytes += h.uncompressed_size
        if h.type == 2:                       # dictionary page
            if h.encoding not in (_ENC_PLAIN, _ENC_PLAIN_DICT):
                raise _Unsupported("non-PLAIN dictionary")
            data = _decompress(codec, body, h.uncompressed_size)
            if phys == "BYTE_ARRAY":
                plan.dict_strings = _plain_dict_strings(data, h.num_values)
            elif phys == "FIXED_LEN_BYTE_ARRAY":
                W = type_length
                plan.dict_values = np.asarray(
                    [data[i * W:(i + 1) * W]
                     for i in range(h.num_values)], dtype=object)
            else:
                plan.dict_values = _plain_dict_values(phys, data,
                                                      h.num_values)
            continue
        if h.type == 0:                       # data page v1
            data = _decompress(codec, body, h.uncompressed_size)
            vstart = 0
            nonnull = h.num_values
            if max_def:
                if h.def_encoding != _ENC_RLE:
                    raise _Unsupported("non-RLE def levels")
                (dlen,) = struct.unpack_from("<i", data, 0)
                nonnull = _walk_hybrid(data, 4, 4 + dlen, 1, h.num_values,
                                       plan.total_values, piece_bits + 32,
                                       plan.def_runs, count_eq=max_def)
                vstart = 4 + dlen
            enc = h.encoding
        elif h.type == 3:                     # data page v2
            if h.rep_len:
                raise _Unsupported("repetition levels")
            levels = body[:h.def_len]
            vals_raw = body[h.def_len:]
            if h.values_compressed:
                vals_raw = _decompress(
                    codec, vals_raw,
                    h.uncompressed_size - h.def_len - h.rep_len)
            data = levels + vals_raw
            nonnull = h.num_values - max(h.num_nulls, 0)
            if max_def:
                _walk_hybrid(data, 0, h.def_len, 1, h.num_values,
                             plan.total_values, piece_bits, plan.def_runs)
            enc, vstart = h.encoding, h.def_len
        else:
            raise _Unsupported(f"page type {h.type}")

        if enc in (_ENC_RLE_DICT, _ENC_PLAIN_DICT):
            if plan.is_dict is False:
                raise _Unsupported("mixed dict/plain pages")
            plan.is_dict = True
            if nonnull:
                idx_width = data[vstart]
                if idx_width > 32:
                    raise _Unsupported("index width > 32")
                _walk_hybrid(data, vstart + 1, len(data), idx_width, nonnull,
                             plan.total_nonnull,
                             piece_bits + (vstart + 1) * 8, plan.val_runs)
        elif enc == _ENC_PLAIN:
            if plan.is_dict is True:
                raise _Unsupported("mixed dict/plain pages")
            plan.is_dict = False
            if phys == "BYTE_ARRAY":
                if nonnull:
                    starts, lens = _walk_byte_array(
                        np.frombuffer(data, np.uint8, len(data) - vstart,
                                      vstart), nonnull)
                    plan.str_starts.append(starts
                                           + (piece_bits // 8 + vstart))
                    plan.str_lens.append(lens)
            elif nonnull:
                plan.val_runs.add_packed(plan.total_nonnull,
                                         piece_bits + vstart * 8, itembits)
        else:
            raise _Unsupported(f"encoding {enc}")

        plan.total_values += h.num_values
        plan.total_nonnull += nonnull
        pieces.append(data)
        piece_bits += len(data) * 8

    if plan.total_values < cc.num_values:
        raise _Unsupported("truncated chunk")
    plan.buf = b"".join(pieces)
    if plan.is_dict is None:
        plan.is_dict = False
    return plan


def _merge_plans(plans: List[_ChunkPlan], phys: str) -> _ChunkPlan:
    """Concatenate per-row-group plans into one chunk-spanning plan.  Dict
    plans union their per-group dictionaries into one global dictionary
    with per-group device-side index remapping (host cost is O(dictionary
    entries), never O(rows))."""
    out = _ChunkPlan(nullable=plans[0].nullable, is_dict=plans[0].is_dict,
                     pages=sum(p.pages for p in plans),
                     out_bytes=sum(p.out_bytes for p in plans))
    if plans[0].is_dict:
        _unify_dictionaries(plans, phys, out)
    bufs: List[bytes] = []
    bit_base = 0
    for p in plans:
        if p.is_dict != out.is_dict and p.total_nonnull:
            raise _Unsupported("dict/plain mix across row groups")
        for runs_src, runs_dst, base in (
                (p.def_runs, out.def_runs, out.total_values),
                (p.val_runs, out.val_runs, out.total_nonnull)):
            for i in range(len(runs_src)):
                runs_dst.out_start.append(base + runs_src.out_start[i])
                runs_dst.src_bit.append(bit_base + runs_src.src_bit[i])
                runs_dst.width.append(runs_src.width[i])
                runs_dst.rle_val.append(runs_src.rle_val[i])
        for s in p.str_starts:
            out.str_starts.append(s + bit_base // 8)
        out.str_lens.extend(p.str_lens)
        out.total_values += p.total_values
        out.total_nonnull += p.total_nonnull
        bufs.append(p.buf)
        bit_base += len(p.buf) * 8
    out.buf = b"".join(bufs)
    return out


def _unify_dictionaries(plans: List[_ChunkPlan], phys: str,
                        out: _ChunkPlan) -> None:
    """Union per-group dictionaries into one global dictionary and build
    per-group index remap tables (applied ON DEVICE).  When every group's
    dictionary is a prefix of the longest one — the single-writer
    fast path — the remap is the identity and is skipped entirely."""
    import pandas as pd

    per_group: List[np.ndarray] = []
    if phys == "BYTE_ARRAY":
        for p in plans:
            if p.dict_strings is None:
                if p.total_nonnull:
                    raise _Unsupported("missing dictionary")
                per_group.append(np.empty(0, object))
                continue
            mat, lens = p.dict_strings
            per_group.append(np.asarray(
                [mat[i, :lens[i]].tobytes() for i in range(len(lens))],
                dtype=object))
    else:
        np_t = (object if phys == "FIXED_LEN_BYTE_ARRAY"
                else _PHYS_NP[phys])
        for p in plans:
            if p.dict_values is None:
                if p.total_nonnull:
                    raise _Unsupported("missing dictionary")
                per_group.append(np.empty(0, np_t))
            else:
                per_group.append(p.dict_values)

    longest = max(per_group, key=len)
    prefix_ok = all(np.array_equal(g, longest[:len(g)]) for g in per_group)
    if prefix_ok:
        merged = longest
        remaps = None
    else:
        # first-occurrence-ordered union; O(total dictionary entries).
        # float dictionaries with NaN entries would break the pd.Index
        # lookup (NaN != NaN) — send those to the host path.
        nonempty = [g for g in per_group if len(g)]
        if phys in ("FLOAT", "DOUBLE") and any(
                np.isnan(g).any() for g in nonempty):
            raise _Unsupported("NaN in divergent float dictionaries")
        merged = pd.unique(np.concatenate(nonempty))
        index = pd.Index(merged)
        remaps = [index.get_indexer(g).astype(np.int32)
                  if len(g) else np.zeros(0, np.int32)
                  for g in per_group]

    if phys == "BYTE_ARRAY":
        lens = np.asarray([len(v) for v in merged], np.int32) \
            if len(merged) else np.zeros(0, np.int32)
        out.dict_strings = _strings_matrix(merged, lens)
    else:
        out.dict_values = np.asarray(merged) if len(merged) else None

    if remaps is not None:
        out.remap = np.concatenate(remaps) if any(len(r) for r in remaps) \
            else np.zeros(1, np.int32)
        offs = np.zeros(len(remaps), np.int64)
        np.cumsum([len(r) for r in remaps[:-1]], out=offs[1:])
        out.remap_offsets = offs.astype(np.int32)
        starts = np.zeros(len(plans), np.int64)
        np.cumsum([p.total_nonnull for p in plans[:-1]], out=starts[1:])
        out.group_starts = starts.astype(np.int32)


# --------------------------------------------------------------------------
# Device execution of a merged plan
# --------------------------------------------------------------------------

def _runs_to_device(runs: _Runs):
    r = max(len(runs), 1)
    rp = _pad_pow2(r, 4)
    big = np.iinfo(np.int32).max
    out_start = np.full(rp, big, np.int32)
    src_bit = np.zeros(rp, np.int64)
    width = np.zeros(rp, np.int32)
    rle_val = np.zeros(rp, np.int32)
    n = len(runs)
    if n:
        out_start[:n] = runs.out_start
        src_bit[:n] = runs.src_bit
        width[:n] = runs.width
        rle_val[:n] = runs.rle_val
    else:
        out_start[0] = 0
    return (jnp.asarray(out_start), jnp.asarray(src_bit),
            jnp.asarray(width), jnp.asarray(rle_val))


def _buf_to_words(buf: bytes):
    nwords = _pad_pow2((len(buf) + 3) // 4 + 2, 16)
    w = np.zeros(nwords, np.uint32)
    if buf:
        full = len(buf) // 4
        if full:
            w[:full] = np.frombuffer(buf, np.uint32, full)
        rem = len(buf) - full * 4
        if rem:
            tail = np.zeros(4, np.uint8)
            tail[:rem] = np.frombuffer(buf, np.uint8, rem, full * 4)
            w[full] = tail.view(np.uint32)[0]
    return jnp.asarray(w)


def _finish(v, phys: str, dtype, arrow_type):
    """Physical value -> the carrier dtype ``arrow_to_device`` would use
    (see ``convert._fixed_to_numpy``: dates int32 days, timestamps int64
    micros, decimals scaled int64)."""
    import pyarrow as pa

    from .. import types as T
    if phys == "INT64" and isinstance(dtype, T.TimestampType) and \
            pa.types.is_timestamp(arrow_type):
        # ns never reaches here: decode_file gates it to the host path,
        # whose safe arrow cast RAISES on sub-microsecond truncation —
        # silently flooring on device would diverge from that contract
        if arrow_type.unit == "ms":
            v = v * 1000
    if isinstance(dtype, T.DecimalType):
        return v.astype(jnp.int64)
    if isinstance(dtype, T.BooleanType):
        return v.astype(jnp.bool_) if v.dtype != jnp.bool_ else v
    return v.astype(dtype.np_dtype)


def _finish_decimal_words(lo, hi, valid, dtype, n_rows: int,
                          capacity: int):
    """(lo, hi) sign-extended int64 words -> the engine's decimal column
    layout: scaled int64 ``data`` for precision <= 18, else lo in ``data``
    and hi in ``aux`` (Aggregation128Utils-equivalent layout,
    columnar/column.py)."""
    from ..columnar.column import DeviceColumn
    data, v = _scatter_nonnull(lo, valid, jnp.int32(n_rows), capacity)
    if dtype.is_long_backed:
        return DeviceColumn(dtype, data, v)
    aux, _ = _scatter_nonnull(hi, valid, jnp.int32(n_rows), capacity)
    return DeviceColumn(dtype, data, v, aux=aux)


def _decode_column_device(plan: _ChunkPlan, phys: str, dtype, arrow_type,
                          capacity: int, n_rows: int,
                          max_str_bytes: int = 1 << 62,
                          type_length: int = 0, conf=None):
    """Run the device programs for one merged chunk plan -> DeviceColumn."""
    from ..columnar.column import DeviceColumn

    words = _buf_to_words(plan.buf)
    nn_cap = _pad_pow2(plan.total_nonnull)

    if plan.nullable and len(plan.def_runs):
        d_os, d_sb, d_w, d_rv = _runs_to_device(plan.def_runs)
        defs = _expand_runs_u32(words, d_os, d_sb, d_w, d_rv, capacity)
        valid = defs == 1
    else:
        valid = jnp.ones(capacity, jnp.bool_)

    v_os, v_sb, v_w, v_rv = _runs_to_device(plan.val_runs)
    if plan.is_dict:
        idx = _expand_runs_u32(words, v_os, v_sb, v_w, v_rv, nn_cap
                               ).astype(jnp.int32)
        if plan.remap is not None:
            # divergent per-group dictionaries: local -> global indices
            gp = _pad_pow2(len(plan.group_starts), 4)
            big = np.iinfo(np.int32).max
            gs = np.full(gp, big, np.int32)
            gs[:len(plan.group_starts)] = plan.group_starts
            ro = np.zeros(gp, np.int32)
            ro[:len(plan.remap_offsets)] = plan.remap_offsets
            idx = _remap_indices(idx, jnp.asarray(gs), jnp.asarray(ro),
                                 jnp.asarray(plan.remap))
        if phys == "BYTE_ARRAY":
            mat, lens = plan.dict_strings if plan.dict_strings is not None \
                else (np.zeros((1, 4), np.uint8), np.zeros(1, np.int32))
            # ragged-string guard: one long dictionary entry makes the
            # dense [capacity, width] matrix explode.  Per-column host
            # fallback would build the SAME matrix (arrow_to_device_column
            # has no width-class splitting) — so decline the whole file;
            # the scan's host pipeline then splits via split_for_upload.
            if capacity * mat.shape[1] > max_str_bytes:
                raise _DeclineFile("string matrix exceeds ragged guard")
            # encoded scan retention (docs/encoded_columns.md): keep the
            # parquet dictionary page as codes+dict instead of eagerly
            # gathering the padded byte matrix; None = decline -> gather
            from ..columnar.encoded import retain_scan_dictionary
            enc = retain_scan_dictionary(
                dtype, mat, lens, idx, valid, n_rows, capacity,
                lambda dense: _scatter_nonnull(dense, valid,
                                               jnp.int32(n_rows), capacity),
                conf)
            if enc is not None:
                return enc
            dmat = jnp.asarray(mat)
            dlen = jnp.asarray(lens if len(lens) else
                               np.zeros(1, np.int32))
            idx = jnp.clip(idx, 0, dmat.shape[0] - 1)
            data, v = _scatter_nonnull(dmat[idx], valid,
                                       jnp.int32(n_rows), capacity)
            lengths, _ = _scatter_nonnull(dlen[idx], valid,
                                          jnp.int32(n_rows), capacity)
            return DeviceColumn(dtype, data, v, lengths=lengths)
        if phys == "FIXED_LEN_BYTE_ARRAY":
            # decimal dictionary: host-decoded (lo, hi) words, two gathers
            entries = plan.dict_values if plan.dict_values is not None \
                else np.empty(0, object)
            lo_np, hi_np = _flba_bytes_to_words(list(entries), type_length)
            dlo, dhi = jnp.asarray(lo_np), jnp.asarray(hi_np)
            idx = jnp.clip(idx, 0, dlo.shape[0] - 1)
            return _finish_decimal_words(dlo[idx], dhi[idx], valid, dtype,
                                         n_rows, capacity)
        dvals = plan.dict_values
        if dvals is None or not len(dvals):
            dvals = np.zeros(1, _PHYS_NP[phys])
        darr = jnp.asarray(dvals)
        idx = jnp.clip(idx, 0, darr.shape[0] - 1)
        dense = _finish(darr[idx], phys, dtype, arrow_type)
    elif phys == "BYTE_ARRAY":
        # PLAIN strings: host-walked payload offsets, device gather
        from ..columnar.column import bucket_width
        starts = (np.concatenate(plan.str_starts) if plan.str_starts
                  else np.zeros(0, np.int64))
        lens = (np.concatenate(plan.str_lens) if plan.str_lens
                else np.zeros(0, np.int32))
        w = bucket_width(int(lens.max()) if len(lens) else 0)
        if capacity * w > max_str_bytes:
            raise _DeclineFile("string matrix exceeds ragged guard")
        pad = _pad_pow2(max(len(starts), 1))
        sp = np.zeros(pad, np.int64)
        sp[:len(starts)] = starts
        lp = np.zeros(pad, np.int32)
        lp[:len(lens)] = lens
        chars = gather_string_matrix(words, jnp.asarray(sp),
                                     jnp.asarray(lp), w, pad)
        data, v = _scatter_nonnull(chars, valid, jnp.int32(n_rows),
                                   capacity)
        lengths, _ = _scatter_nonnull(jnp.asarray(lp), valid,
                                      jnp.int32(n_rows), capacity)
        return DeviceColumn(dtype, data, v,
                            lengths=lengths.astype(jnp.int32))
    elif phys == "FIXED_LEN_BYTE_ARRAY":
        lo_u, hi_u = _expand_flba(words, v_os, v_sb, nn_cap, type_length)
        return _finish_decimal_words(_u64_to_i64(lo_u), _u64_to_i64(hi_u),
                                     valid, dtype, n_rows, capacity)
    elif phys == "INT64":
        raw = _expand_runs_u64(words, v_os, v_sb, nn_cap)
        dense = _finish(_u64_to_i64(raw), phys, dtype, arrow_type)
    elif phys == "DOUBLE":
        raw = _expand_runs_u64(words, v_os, v_sb, nn_cap)
        dense = _finish(_f64_from_bits(raw), phys, dtype, arrow_type)
    else:
        raw = _expand_runs_u32(words, v_os, v_sb, v_w, v_rv, nn_cap)
        if phys == "INT32":
            dense = _finish(jax.lax.bitcast_convert_type(raw, np.int32),
                            phys, dtype, arrow_type)
        elif phys == "FLOAT":
            dense = _finish(jax.lax.bitcast_convert_type(raw, np.float32),
                            phys, dtype, arrow_type)
        elif phys == "BOOLEAN":
            dense = _finish((raw & 1).astype(jnp.bool_), phys, dtype,
                            arrow_type)
        else:
            raise _Unsupported(f"finish {phys}")
    data, v = _scatter_nonnull(dense, valid, jnp.int32(n_rows), capacity)
    return DeviceColumn(dtype, data, v)


# --------------------------------------------------------------------------
# Public entry
# --------------------------------------------------------------------------

def _dtype_supported(dtype, arrow_type) -> bool:
    import pyarrow as pa

    from .. import types as T
    if dtype is None:
        return False
    if isinstance(dtype, (T.ArrayType, T.MapType, T.StructType, T.NullType,
                          T.BinaryType)):
        return False
    # decimals of every precision are in the envelope: INT32/INT64 backed
    # directly, FIXED_LEN_BYTE_ARRAY via the (lo, hi) word kernels
    if pa.types.is_timestamp(arrow_type) and arrow_type.unit not in (
            "us", "ms"):
        # ns -> us is lossy; the host path's safe cast raises — keep one
        # behavior by sending ns files to the host path
        return False
    return True


#: encodings we can never decode on device — seen in chunk METADATA they
#: let us skip the whole parse+decompress pass for that column
#: NB: BIT_PACKED is deliberately NOT here — parquet-mr (Spark/Hive)
#: lists it for the levels encoding of flat columns even when no value
#: data uses it; it is levels-only per spec, and the page parser already
#: rejects non-RLE def levels.  Rejecting it here would silently disable
#: device decode for every Spark-written file.
_UNSUPPORTED_ENCODINGS = {"DELTA_BINARY_PACKED", "DELTA_LENGTH_BYTE_ARRAY",
                          "DELTA_BYTE_ARRAY", "BYTE_STREAM_SPLIT"}


def _precheck_chunk_meta(cc) -> None:
    """Cheap metadata-only rejection BEFORE reading/decompressing pages:
    the column-chunk footer lists its encodings and codec, so columns that
    can't take the device path cost zero byte-level work."""
    if _CODECS.get(cc.compression, "?") == "?":
        raise _Unsupported(f"codec {cc.compression}")
    encs = set(cc.encodings)
    if encs & _UNSUPPORTED_ENCODINGS:
        raise _Unsupported(f"encodings {sorted(encs)}")
    # pure-PLAIN BYTE_ARRAY chunks decode on device (round 5): the host
    # walks only the u32 length prefixes — native scan, or a python loop
    # bounded PER CHUNK before any decompression happens
    if cc.physical_type == "BYTE_ARRAY" and not (
            encs & {"PLAIN_DICTIONARY", "RLE_DICTIONARY"}):
        from ..native import has
        if not has("srt_byte_array_walk") \
                and cc.num_values > _PY_WALK_MAX:
            raise _Unsupported(
                "PLAIN byte-array walk without native helper")


def wanted_fields(schema, columns: Optional[Sequence[str]]
                  ) -> Optional[List[int]]:
    """Field indices of ``schema`` (a file's Arrow schema) for the names
    ``columns``, in the order given (None: every field).  None, with the
    decline reason set, where the file has no such column: the host path
    then reads the file and pyarrow says which."""
    if columns is None:
        return list(range(len(schema)))
    wanted = [schema.get_field_index(name) for name in columns]
    if any(fi < 0 for fi in wanted):
        from .decode_stats import set_decline_reason
        set_decline_reason("no-such-column")
        return None
    return wanted


def chunk_bytes(md, row_groups: Sequence[int],
                columns: Optional[Sequence[str]] = None) -> int:
    """Uncompressed bytes of the column chunks a read of ``columns`` (top-
    level field names; None: every column) of ``row_groups`` decodes: what
    the scan's spans and ``decode_stats`` count for a run."""
    if columns is None:
        return sum(md.row_group(rg).total_byte_size for rg in row_groups)
    wanted = set(columns)
    rg0 = md.row_group(row_groups[0]) if row_groups else None
    leaves = [li for li in range(rg0.num_columns if rg0 else 0)
              if rg0.column(li).path_in_schema.split(".", 1)[0] in wanted]
    return sum(md.row_group(rg).column(li).total_uncompressed_size
               for rg in row_groups for li in leaves)


def decode_file(path: str, row_groups: Optional[Sequence[int]] = None,
                tctx=None, pf=None, conf=None,
                columns: Optional[Sequence[str]] = None):
    """Decode (a subset of row groups of) one parquet file into a
    :class:`ColumnarBatch`, device-decoding every column the envelope
    supports and falling back to pyarrow per column otherwise.

    ``columns`` names the fields to decode (None: all of them): no other
    column's chunk is read from the file, and the batch holds them in the
    order given.

    Returns ``None`` when no column takes the device path, or when safe
    decode requires the host pipeline's whole-table handling (ragged
    strings) — callers then use their existing host read wholesale.
    """
    import pyarrow.parquet as pq

    from .. import types as T
    from ..columnar.batch import ColumnarBatch
    from ..columnar.column import bucket_capacity
    from ..columnar.convert import arrow_to_device_column
    from ..observability import tracer as _trace
    from .decode_stats import record_chunk_read, record_pages

    if pf is None:
        pf = pq.ParquetFile(path)   # callers with an open handle pass it in
    md = pf.metadata
    schema = pf.schema_arrow
    wanted = wanted_fields(schema, columns)
    if wanted is None:
        return None
    rgs = list(range(md.num_row_groups)) if row_groups is None \
        else list(row_groups)
    if not rgs:
        return None
    n_rows = sum(md.row_group(rg).num_rows for rg in rgs)
    capacity = bucket_capacity(n_rows)

    # flat leaf index per top-level field (nested fields span >1 leaf and
    # their path contains '.'; those take the host path)
    leaf_of_field: Dict[int, int] = {}
    rg0 = md.row_group(rgs[0])
    for li in range(rg0.num_columns):
        path_in = rg0.column(li).path_in_schema
        if "." in path_in:
            continue
        fi = schema.get_field_index(path_in)
        if fi >= 0:
            leaf_of_field[fi] = li

    max_str_bytes = _max_string_matrix_bytes(conf)
    device_cols: Dict[int, object] = {}
    host_fields: List[int] = []
    with open(path, "rb") as fobj:
        for fi in wanted:
            fld = schema.field(fi)
            li = leaf_of_field.get(fi)
            try:
                dtype = T.from_arrow(fld.type)
            except Exception:
                dtype = None
            if li is None or not _dtype_supported(dtype, fld.type):
                host_fields.append(fi)
                continue
            try:
                chunks = []
                phys = None
                type_length = int(getattr(md.schema.column(li), "length",
                                          0) or 0)
                for rg in rgs:
                    cc = md.row_group(rg).column(li)
                    phys = cc.physical_type
                    if cc.file_path:
                        raise _Unsupported("external chunk file")
                    if phys == "BYTE_ARRAY" and \
                            isinstance(dtype, T.DecimalType):
                        # legacy writers annotate variable-length
                        # BYTE_ARRAY as decimal — that shape is host-only
                        # (the string-dictionary kernel would mislabel it)
                        raise _Unsupported("BYTE_ARRAY decimal")
                    _precheck_chunk_meta(cc)
                    # offset 0 can never be a real page (files start with
                    # the PAR1 magic) — some writers emit 0 for "absent"
                    offs = [o for o in (cc.dictionary_page_offset,
                                        cc.data_page_offset)
                            if o is not None and o > 0]
                    with _trace.span("scan", "chunk_read",
                                     bytes=cc.total_compressed_size):
                        fobj.seek(min(offs))
                        raw = fobj.read(cc.total_compressed_size)
                    record_chunk_read(len(raw))
                    chunks.append((raw, cc))
                with _trace.span("scan", "pages") as pages:
                    plans = [_plan_chunk(raw, cc, phys, fld.nullable,
                                         type_length) for raw, cc in chunks]
                    merged = _merge_plans(plans, phys)
                    pages.set_metadata(
                        pages=merged.pages, out_bytes=merged.out_bytes,
                        bytes=sum(len(raw) for raw, _ in chunks))
                record_pages(merged.pages, merged.out_bytes)
                with _trace.eager("parquet.decode_column",
                                  rows=n_rows):
                    device_cols[fi] = _decode_column_device(
                        merged, phys, dtype, fld.type, capacity, n_rows,
                        max_str_bytes, type_length, conf=conf)
                if tctx is not None:
                    tctx.inc_metric("parquetDeviceDecodedColumns")
            except _Unsupported:
                host_fields.append(fi)
            except _DeclineFile:
                from .decode_stats import set_decline_reason
                set_decline_reason("ragged-strings")
                return None
            except (ValueError, IndexError, KeyError, struct.error,
                    OSError):
                # malformed/truncated chunks surface as low-level errors
                # from the hand-rolled parsers; the contract is per-column
                # fallback — pyarrow reports real corruption cleanly
                if tctx is not None:
                    tctx.inc_metric("parquetDeviceDecodeErrors")
                host_fields.append(fi)

    if not device_cols:
        from .decode_stats import set_decline_reason
        set_decline_reason("no-device-columns")
        return None
    if host_fields:
        # these columns alone take the host's way, under the spans of the
        # whole-run host path: scan_host_ms and h2d_ms count them
        names = [schema.field(fi).name for fi in host_fields]
        with _trace.span("scan", "host_decode", row_groups=len(rgs),
                         bytes=chunk_bytes(md, rgs, names),
                         columns=len(names), declined="per-column"):
            tbl = pf.read_row_groups(rgs, columns=names)
        with _trace.span("h2d", "arrow_to_device", bytes=tbl.nbytes,
                         rows=n_rows):
            for k, fi in enumerate(host_fields):
                device_cols[fi] = arrow_to_device_column(tbl.column(k),
                                                         capacity)
                if tctx is not None:
                    tctx.inc_metric("parquetHostDecodedColumns")

    return ColumnarBatch.make([schema.field(fi).name for fi in wanted],
                              [device_cols[fi] for fi in wanted], n_rows)
