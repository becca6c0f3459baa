"""Encoded column representations that survive through the engine.

"GPU Acceleration of SQL Analytics on Compressed Data" (PAPERS.md) shows
operators can run directly on encoded columns.  This module generalizes the
``columnar/prepack.py`` narrow-before-the-wire trick into first-class
encoded batch citizens:

* :class:`DictEncodedColumn` — int32 codes + a shared :class:`Dictionary`
  of distinct values.  Scans keep low-cardinality string columns as
  codes+dict instead of eagerly materializing the padded byte matrix;
  joins probe on integer codes (sql/physical/join.py lowers both sides
  into the build dictionary's code space), group-bys and sorts run on
  codes via ``ops/ranks.column_sort_keys`` (the dictionary is always
  stored SORTED, so code order == value order), and the shuffle
  serializer ships narrowed codes with the dictionary sent once per
  batch (or once per exchange via the ref cache).

* :class:`RLEColumn` — run values + run ends for repetitive fixed-width
  columns; mainly a wire/scan representation (any gather materializes).

Decline-to-materialize discipline (the device-decode split, applied to
encoding): every operator that does not understand an encoded column
simply touches ``.data`` / ``.lengths`` / ``.aux`` / ``.children`` — those
are properties that transparently materialize (and memoize) the decoded
column, so unaware ops are bit-identical BY CONSTRUCTION, never wrong.
Aware ops (gather, concat, sort keys, join key lowering, the serializer)
check ``isinstance`` and stay in code space.  Materialized data for
null/dead rows is zeroed, matching the engine-wide "nulls hold zeroed
data" invariant (arrow_to_device does the same), so hashing/bloom paths
see identical bytes either way.

The kill switch is structural: ``spark.rapids.tpu.sql.encoded.enabled``
gates *creation* (scan encode + wire decode); with it off no encoded
column ever exists, every jitted program retraces on the plain treedef,
and the whole engine is back on the raw path.
"""

from __future__ import annotations

import struct
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from .column import DeviceColumn, bucket_capacity, bucket_width, \
    is_string_like

#: observability (tests + bench + last_query_metrics deltas).
#: materializations counts decode-on-access events (per traced program,
#: not per row); columns_encoded/declined track the scan-side gate.
STATS = {
    "columns_encoded": 0,          # dict columns created (scan/wire/concat)
    "rle_columns_encoded": 0,
    "columns_declined": 0,         # eligible but over cardinality budget
    "materializations": 0,         # encoded -> raw decodes (any site)
    "dict_filters": 0,             # filter predicates evaluated on the dict
    "join_code_lowerings": 0,      # join key pairs lowered to code space
    "join_code_declines": 0,
    "concat_unified": 0,           # dict-aware concats (incl. unify)
    "wire_dict_inline": 0,         # dictionaries shipped inline in a frame
    "wire_dict_refs": 0,           # dictionaries replaced by a cache ref
    "wire_code_bytes": 0,          # narrowed code bytes on the wire
    "wire_bytes_saved": 0,         # raw-matrix bytes minus encoded bytes
}

_LOCK = threading.Lock()


def _bump(key: str, n: int = 1) -> None:
    with _LOCK:
        STATS[key] += n


def stats_snapshot() -> dict:
    with _LOCK:
        return dict(STATS)


#: thread-local wire accounting: one frame serializes entirely on one
#: thread, so the per-frame bytes-saved delta is exact even when the
#: MULTITHREADED shuffle serializes frames concurrently
_WIRE_TLS = threading.local()


def begin_wire_account():
    prev = getattr(_WIRE_TLS, "saved", None)
    _WIRE_TLS.saved = 0
    return prev


def add_wire_saved(n: int) -> None:
    _bump("wire_bytes_saved", n)
    if getattr(_WIRE_TLS, "saved", None) is not None:
        _WIRE_TLS.saved += n


def end_wire_account(prev) -> int:
    cur = getattr(_WIRE_TLS, "saved", 0) or 0
    _WIRE_TLS.saved = prev
    return cur


# --------------------------------------------------------------------------
# configuration gates
# --------------------------------------------------------------------------

def enabled(conf=None) -> bool:
    from ..config import ENCODED_ENABLED, RapidsConf
    try:
        return bool((conf or RapidsConf.get_global()).get(ENCODED_ENABLED))
    except Exception:  # pragma: no cover - partial-init paths
        return False


def op_enabled(op: str, conf=None) -> bool:
    """Per-op opt-out (filter/join/aggregate/sort/shuffle).  Read at
    trace/lowering time — see docs/encoded_columns.md for the kernel-cache
    caveat on flipping these mid-session."""
    from ..config import ENCODED_OP_CONFS, RapidsConf
    entry = ENCODED_OP_CONFS.get(op)
    if entry is None:
        return True
    try:
        return bool((conf or RapidsConf.get_global()).get(entry))
    except Exception:  # pragma: no cover
        return True


def _max_cardinality(conf=None) -> int:
    from ..config import ENCODED_MAX_CARDINALITY, RapidsConf
    return int((conf or RapidsConf.get_global())
               .get(ENCODED_MAX_CARDINALITY))


def encode_params(conf=None) -> tuple:
    """The scan-side encode decision inputs — part of any cache key that
    stores encoded batches (e.g. the in-memory scan upload cache)."""
    return (enabled(conf), _max_cardinality(conf))


# --------------------------------------------------------------------------
# Dictionary — the shared distinct-value table
# --------------------------------------------------------------------------

#: process-global host-value + identity registry, keyed by content hash.
#: Entries are small (<= maxDictionaryCardinality values); the registry is
#: append-only up to a generous cap, after which new dictionaries simply
#: stop registering (wire frames then inline, join lowering declines) —
#: no eviction means a wire ref can never dangle in-process.
_REGISTRY_CAP = 4096
_HOST_VALUES: Dict[int, np.ndarray] = {}
_DICT_OBJECTS: Dict[int, "Dictionary"] = {}


def _register_host_values(content_hash: int, values: np.ndarray) -> None:
    with _LOCK:
        if content_hash not in _HOST_VALUES \
                and len(_HOST_VALUES) < _REGISTRY_CAP:
            _HOST_VALUES[content_hash] = values


def host_values_for(content_hash: int) -> Optional[np.ndarray]:
    with _LOCK:
        return _HOST_VALUES.get(content_hash)


def registered_dictionary(content_hash: int) -> Optional["Dictionary"]:
    with _LOCK:
        return _DICT_OBJECTS.get(content_hash)


def _register_dictionary(d: "Dictionary") -> "Dictionary":
    """Canonicalize by content hash so every frame/batch carrying the same
    dictionary shares ONE object (identity short-circuits concat/join)."""
    with _LOCK:
        got = _DICT_OBJECTS.get(d.content_hash)
        if got is not None:
            return got
        if len(_DICT_OBJECTS) < _REGISTRY_CAP:
            _DICT_OBJECTS[d.content_hash] = d
        return d


def _hash_values(values: Sequence[bytes]) -> int:
    """Stable content hash of the sorted distinct values (xxhash64 when the
    native lib is present, else a seeded 64-bit FNV fold)."""
    payload = struct.pack("<I", len(values)) + b"\x00".join(values)
    try:
        from ..native import xxhash64_bytes
        h = xxhash64_bytes(payload, seed=len(payload))
        if h is not None:
            return int(h)
    except Exception:  # pragma: no cover - native lib optional
        pass
    h = 0xcbf29ce484222325
    for b in payload:
        h = ((h ^ b) * 0x100000001b3) & 0xFFFFFFFFFFFFFFFF
    return h


class Dictionary:
    """Distinct values of a dict-encoded column, device-resident as a
    regular :class:`DeviceColumn` over ``size`` entries, plus static
    metadata.  Always SORTED ascending in engine byte order (lexicographic
    over the value bytes) and UNIQUE — code order therefore equals value
    order, which is what lets sorts/comparisons run on codes.

    The entry table's capacity is always > ``size``: index ``size`` is a
    guaranteed all-null spare row, used by the filter fast path to
    evaluate a predicate's null-input verdict in the same pass.
    """

    __slots__ = ("column", "size", "sorted", "content_hash")

    def __init__(self, column: DeviceColumn, size: int,
                 sorted_: bool, content_hash: int):
        self.column = column
        self.size = int(size)
        self.sorted = bool(sorted_)
        self.content_hash = int(content_hash)

    # --- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        return ((self.column,), (self.size, self.sorted, self.content_hash))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        size, sorted_, content_hash = aux
        return cls(leaves[0], size, sorted_, content_hash)

    def host_values(self) -> Optional[np.ndarray]:
        """The sorted distinct values as a host object array of bytes, from
        the registry (populated at creation/deserialization; dictionaries
        are never built on-device)."""
        return host_values_for(self.content_hash)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"Dictionary(size={self.size}, sorted={self.sorted}, "
                f"hash={self.content_hash:#x})")


def _register_pytrees():
    jax.tree_util.register_pytree_node_class(Dictionary)


def dictionary_from_values(dtype: T.DataType,
                           values: Sequence[bytes]) -> Dictionary:
    """Build a (sorted, unique) dictionary from host byte values.  Callers
    must pass values already sorted ascending and deduplicated."""
    k = len(values)
    cap = bucket_capacity(k + 1)  # always leave the spare null slot
    width = bucket_width(max((len(v) for v in values), default=0))
    chars = np.zeros((cap, width), dtype=np.uint8)
    lengths = np.zeros(cap, dtype=np.int32)
    for i, v in enumerate(values):
        lengths[i] = len(v)
        if v:
            chars[i, :len(v)] = np.frombuffer(v, dtype=np.uint8)
    validity = np.zeros(cap, dtype=bool)
    validity[:k] = True
    col = DeviceColumn(dtype, jnp.asarray(chars), jnp.asarray(validity),
                       lengths=jnp.asarray(lengths))
    h = _hash_values(list(values))
    vals = np.empty(k, dtype=object)
    vals[:k] = list(values)
    _register_host_values(h, vals)
    return _register_dictionary(Dictionary(col, k, True, h))


# --------------------------------------------------------------------------
# DictEncodedColumn
# --------------------------------------------------------------------------

def _trace_encode_span(name: str, **args):
    """Host-side encode/materialize span (cat ``encode``); silently skipped
    when the tracer is off."""
    from ..observability import tracer as _trace
    if not _trace.TRACING["on"]:
        return None
    return _trace.span("encode", name, **args)


class DictEncodedColumn(DeviceColumn):
    """codes + dictionary, masquerading as its logical :class:`DeviceColumn`.

    ``dtype`` is the LOGICAL type (StringType/BinaryType); ``codes`` is
    int32[capacity] with code 0 for null/dead rows; ``validity`` is the
    usual row-validity array.  ``join_codes`` (optional) carries this
    column's codes remapped into a join partner's dictionary space — set
    only by the join lowering immediately before the jitted join programs,
    cleared by any structural operation (gather/slice), and consumed by
    ``ops/join.join_search_keys``.

    Accessing ``.data`` / ``.lengths`` / ``.aux`` / ``.children``
    materializes (and memoizes) the decoded column — the decline path for
    every op that does not understand encoding.
    """

    def __init__(self, dtype: T.DataType, codes, dictionary: Dictionary,
                 validity, join_codes=None):
        # deliberately NOT calling the dataclass __init__: data/lengths/aux
        # are properties on this class
        self.dtype = dtype
        self.codes = codes
        self.dictionary = dictionary
        self.validity = validity
        self.join_codes = join_codes
        self._mat: Optional[DeviceColumn] = None

    # --- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        return ((self.codes, self.validity, self.dictionary,
                 self.join_codes), self.dtype)

    @classmethod
    def tree_unflatten(cls, dtype, leaves):
        codes, validity, dictionary, join_codes = leaves
        return cls(dtype, codes, dictionary, validity, join_codes)

    # --- shape ------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.codes.shape[0])

    @property
    def width(self) -> Optional[int]:
        return self.materialized().width

    # --- decline-to-materialize safety net --------------------------------
    def materialized(self) -> DeviceColumn:
        """The decoded column: one gather of the dictionary by codes, with
        null/dead rows zeroed (engine invariant — hash/bloom/serializer
        paths must see the same bytes as the raw pipeline)."""
        m = self._mat
        if m is not None:
            return m
        from ..observability import tracer as _trace
        d = self.dictionary.column
        with _trace.eager("encoded.dict_materialize"):
            safe = jnp.clip(self.codes, 0, d.capacity - 1)
            data = jnp.where(self.validity[:, None], d.data[safe], 0)
            lengths = jnp.where(self.validity, d.lengths[safe], 0)
        m = DeviceColumn(self.dtype, data, self.validity, lengths=lengths)
        self._mat = m
        _bump("materializations")
        span = _trace_encode_span("dict.materialize", rows=self.capacity,
                                  dict_size=self.dictionary.size)
        if span is not None:
            with span:
                pass
        return m

    @property
    def data(self):
        return self.materialized().data

    @property
    def lengths(self):
        return self.materialized().lengths

    @property
    def aux(self):
        return None

    @property
    def children(self):
        return ()

    # --- structural ops (stay encoded) ------------------------------------
    def with_validity(self, validity) -> "DictEncodedColumn":
        return DictEncodedColumn(self.dtype, self.codes, self.dictionary,
                                 validity, self.join_codes)

    def mask_dead_rows(self, row_mask) -> "DictEncodedColumn":
        v = self.validity & row_mask if self.validity is not None else row_mask
        return self.with_validity(v)

    def with_join_codes(self, join_codes) -> "DictEncodedColumn":
        return DictEncodedColumn(self.dtype, self.codes, self.dictionary,
                                 self.validity, join_codes)

    def slice_capacity(self, new_capacity: int) -> "DictEncodedColumn":
        from .column import _fix_1d
        return DictEncodedColumn(
            self.dtype, _fix_1d(self.codes, new_capacity, 0),
            self.dictionary, _fix_1d(self.validity, new_capacity, False))

    def window(self, start, capacity: int, live) -> "DictEncodedColumn":
        from .column import _window
        validity = _window(self.validity, start, capacity) & live
        codes = jnp.where(validity, _window(self.codes, start, capacity), 0)
        return DictEncodedColumn(self.dtype, codes, self.dictionary,
                                 validity)

    def gather(self, idx, idx_valid=None) -> "DictEncodedColumn":
        """Row selection gathers CODES, not values — the encoding survives
        filters, join output assembly, group-by key emission, and sorts.
        ``join_codes`` does not survive (it is only valid for the exact
        batch pair the join lowering prepared)."""
        safe = jnp.clip(idx, 0, self.capacity - 1)
        validity = self.validity[safe]
        if idx_valid is not None:
            validity = validity & idx_valid
        codes = jnp.where(validity, self.codes[safe], 0)
        return DictEncodedColumn(self.dtype, codes, self.dictionary,
                                 validity)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"DictEncodedColumn(rows={self.capacity}, "
                f"dict={self.dictionary.size}, dtype={self.dtype})")


# --------------------------------------------------------------------------
# RLEColumn
# --------------------------------------------------------------------------

@jax.jit
def _expand_rle(values, aux, run_ends, validity):
    """Run values at every row, with null rows zeroed.  A row's run is
    the number of ``run_ends`` at or before it — what
    ``searchsorted(run_ends, row, side="right")`` returns for the sorted
    ends — counted by a scatter of one mark a run end (ends at capacity,
    the padding, are dropped; a zero-length run marks twice) and a
    two-level prefix sum: a search compiles to a ``while`` of
    whole-array gathers, one a round.  Every shape is the padded one, so
    one program serves every ``num_runs`` of a capacity."""
    from ..ops.ranks import prefix_sum
    marks = jnp.zeros(validity.shape[0], jnp.int32
                      ).at[run_ends].add(1, mode="drop")
    run_idx = jnp.clip(prefix_sum(jnp, marks), 0, values.shape[0] - 1)
    data = jnp.where(validity, values[run_idx], 0)
    if aux is not None:
        aux = jnp.where(validity, aux[run_idx], 0)
    return data, aux


class RLEColumn(DeviceColumn):
    """Run-length encoded fixed-width column: ``run_values`` (a plain
    DeviceColumn over ``num_runs`` entries, bucket-padded) + ``run_ends``
    (int32 exclusive end offsets, padded with capacity).  Row validity is
    stored explicitly (bool[capacity] — 1 byte/row; the win is the data
    words).  Primarily a scan/wire representation: any structural
    operation (gather/slice) materializes, by design.
    """

    def __init__(self, dtype: T.DataType, run_values: DeviceColumn,
                 run_ends, num_runs: int, validity):
        self.dtype = dtype
        self.run_values = run_values
        self.run_ends = run_ends
        self.num_runs = int(num_runs)
        self.validity = validity
        self._mat: Optional[DeviceColumn] = None

    def tree_flatten(self):
        return ((self.run_values, self.run_ends, self.validity),
                (self.dtype, self.num_runs))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        dtype, num_runs = aux
        run_values, run_ends, validity = leaves
        return cls(dtype, run_values, run_ends, num_runs, validity)

    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def width(self) -> Optional[int]:
        return None

    def materialized(self) -> DeviceColumn:
        m = self._mat
        if m is not None:
            return m
        from ..observability import tracer as _trace
        with _trace.eager("encoded.rle_materialize"):
            data, aux = _expand_rle(self.run_values.data,
                                    self.run_values.aux, self.run_ends,
                                    self.validity)
        m = DeviceColumn(self.dtype, data, self.validity, aux=aux)
        self._mat = m
        _bump("materializations")
        return m

    @property
    def data(self):
        return self.materialized().data

    @property
    def lengths(self):
        return None

    @property
    def aux(self):
        return self.materialized().aux

    @property
    def children(self):
        return ()

    def with_validity(self, validity) -> "RLEColumn":
        return RLEColumn(self.dtype, self.run_values, self.run_ends,
                         self.num_runs, validity)

    def mask_dead_rows(self, row_mask) -> "RLEColumn":
        v = self.validity & row_mask if self.validity is not None else row_mask
        return self.with_validity(v)

    def slice_capacity(self, new_capacity: int) -> DeviceColumn:
        return self.materialized().slice_capacity(new_capacity)

    def gather(self, idx, idx_valid=None) -> DeviceColumn:
        return self.materialized().gather(idx, idx_valid)

    def window(self, start, capacity: int, live) -> DeviceColumn:
        return self.materialized().window(start, capacity, live)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"RLEColumn(rows={self.capacity}, runs={self.num_runs}, "
                f"dtype={self.dtype})")


def _register_encoded_pytrees():
    jax.tree_util.register_pytree_node_class(Dictionary)
    jax.tree_util.register_pytree_node_class(DictEncodedColumn)
    jax.tree_util.register_pytree_node_class(RLEColumn)


_register_encoded_pytrees()


# --------------------------------------------------------------------------
# encoding (host side — scans and the wire)
# --------------------------------------------------------------------------

def _cardinality_ok(k: int, n: int, max_cardinality: int) -> bool:
    """Encode when the dictionary is within budget.  The distinct/rows
    ratio rule only applies to LARGE columns: a tiny dim table with all-
    unique keys still encodes (its dictionary is trivially small and the
    join's code-space lowering needs both sides encoded)."""
    if k > max_cardinality:
        return False
    return n <= 1024 or k <= max(1, n // 2)


def encode_string_column_np(dtype: T.DataType, values: List[Optional[bytes]],
                            capacity: int,
                            max_cardinality: int) -> Optional[DictEncodedColumn]:
    """Dict-encode a host string/binary column (None = null).  Returns
    None (decline) when the cardinality exceeds the budget or encoding
    cannot shrink the representation."""
    n = len(values)
    present = [v for v in values if v is not None]
    distinct = sorted(set(present))
    k = len(distinct)
    if not _cardinality_ok(k, n, max_cardinality):
        _bump("columns_declined")
        return None
    d = dictionary_from_values(dtype, distinct)
    index = {v: i for i, v in enumerate(distinct)}
    codes_np = np.zeros(capacity, dtype=np.int32)
    valid_np = np.zeros(capacity, dtype=bool)
    for i, v in enumerate(values):
        if v is not None:
            codes_np[i] = index[v]
            valid_np[i] = True
    _bump("columns_encoded")
    span = _trace_encode_span("dict.encode", rows=n, dict_size=k)
    if span is not None:
        with span:
            pass
    return DictEncodedColumn(dtype, jnp.asarray(codes_np), d,
                             jnp.asarray(valid_np))


def encode_string_arrow(arr, dtype: T.DataType, capacity: int,
                        conf=None) -> Optional[DictEncodedColumn]:
    """Scan-side retention: keep a low-cardinality arrow string/binary
    column as codes+dict.  Uses arrow's dictionary_encode (this ALSO
    covers parquet/ORC dictionary pages arriving pre-encoded from
    pyarrow) and re-sorts the dictionary into engine byte order."""
    import pyarrow as pa
    import pyarrow.compute as pc
    n = len(arr)
    if n == 0 or not is_string_like(dtype):
        return None
    max_card = _max_cardinality(conf)
    try:
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if pa.types.is_dictionary(arr.type):
            denc = arr
        else:
            denc = pc.dictionary_encode(arr)
        dict_vals = denc.dictionary
        k = len(dict_vals)
        if not _cardinality_ok(k, n, max_card):
            _bump("columns_declined")
            return None
        raw = [v.as_py() for v in dict_vals]
        as_bytes = [v.encode("utf-8") if isinstance(v, str) else bytes(v)
                    for v in raw]
        order = sorted(range(k), key=lambda i: as_bytes[i])
        sorted_vals = [as_bytes[i] for i in order]
        if len(set(sorted_vals)) != k:
            # distinct logical values with equal byte forms — be safe
            _bump("columns_declined")
            return None
        remap = np.zeros(k, dtype=np.int32)
        for new, old in enumerate(order):
            remap[old] = new
        d = dictionary_from_values(dtype, sorted_vals)
        idx = denc.indices
        valid_np = np.zeros(capacity, dtype=bool)
        valid_np[:n] = np.asarray(arr.is_valid()) if arr.null_count else True
        idx_np = np.asarray(idx.fill_null(0)) if idx.null_count \
            else np.asarray(idx)
        codes_np = np.zeros(capacity, dtype=np.int32)
        codes_np[:n] = remap[idx_np.astype(np.int64)]
        codes_np[:n][~valid_np[:n]] = 0
        _bump("columns_encoded")
        span = _trace_encode_span("dict.encode", rows=n, dict_size=k)
        if span is not None:
            with span:
                pass
        return DictEncodedColumn(dtype, jnp.asarray(codes_np), d,
                                 jnp.asarray(valid_np))
    except Exception:  # pragma: no cover - arrow corner cases: decline
        _bump("columns_declined")
        return None


def retain_scan_dictionary(dtype: T.DataType, mat: np.ndarray,
                           lens_np: np.ndarray, dense_idx, valid,
                           n_rows: int, capacity: int, scatter,
                           conf=None) -> Optional[DictEncodedColumn]:
    """Device-decoder retention: keep an already-decoded dictionary page
    (parquet PLAIN/RLE_DICTIONARY, ORC DICTIONARY_V2) as codes + dict
    instead of eagerly gathering the padded byte matrix.  ``mat``/
    ``lens_np`` are the HOST dictionary entries, ``dense_idx`` the device
    array of per-nonnull-value dictionary indices, ``scatter`` the
    decoder's dense->row scatter (``_scatter_nonnull`` partial).  Returns
    None to decline (cardinality over budget, duplicate entries — e.g.
    repeated values across ORC stripe dictionaries — or encoding off);
    the caller then gathers exactly as before."""
    k = int(len(lens_np))
    if not enabled(conf) or not is_string_like(dtype) \
            or not _cardinality_ok(k, n_rows, _max_cardinality(conf)):
        return None
    vals = [mat[i, :int(lens_np[i])].tobytes() for i in range(k)]
    if len(set(vals)) != k:
        return None
    order = sorted(range(k), key=vals.__getitem__)
    d = dictionary_from_values(dtype, [vals[i] for i in order])
    remap = np.zeros(max(k, 1), dtype=np.int32)
    for new, old in enumerate(order):
        remap[old] = new
    dense_codes = jnp.asarray(remap)[
        jnp.clip(dense_idx, 0, max(k - 1, 0)).astype(jnp.int32)]
    codes, v = scatter(dense_codes)
    _bump("columns_encoded")
    span = _trace_encode_span("dict.retain", rows=n_rows, dict_size=k)
    if span is not None:
        with span:
            pass
    return DictEncodedColumn(dtype, codes.astype(jnp.int32), d, v)


#: minimum compression ratio (rows per run) for RLE retention to engage
_RLE_MIN_RATIO = 4


def encode_rle_numpy(dtype: T.DataType, data_np: np.ndarray,
                     valid_np: np.ndarray, n: int,
                     capacity: int) -> Optional[RLEColumn]:
    """RLE-encode a fixed-width host column when its live prefix is
    run-compressible (>= _RLE_MIN_RATIO rows per run).  Validity changes
    break runs so each run is uniformly valued AND uniformly valid."""
    if n < 64 or data_np.ndim != 1:
        return None
    live = data_np[:n]
    live_valid = valid_np[:n]
    breaks = np.flatnonzero((live[1:] != live[:-1])
                            | (live_valid[1:] != live_valid[:-1]))
    num_runs = len(breaks) + 1
    if num_runs * _RLE_MIN_RATIO > n:
        return None
    ends = np.empty(num_runs, dtype=np.int32)
    ends[:-1] = breaks + 1
    ends[-1] = n
    starts = np.concatenate([[0], ends[:-1]])
    run_cap = bucket_capacity(num_runs)
    rv = np.zeros(run_cap, dtype=data_np.dtype)
    rvalid = np.zeros(run_cap, dtype=bool)
    rv[:num_runs] = live[starts]
    rvalid[:num_runs] = live_valid[starts]
    rends = np.full(run_cap, capacity, dtype=np.int32)
    rends[:num_runs] = ends
    run_col = DeviceColumn(dtype, jnp.asarray(rv), jnp.asarray(rvalid))
    _bump("rle_columns_encoded")
    return RLEColumn(dtype, run_col, jnp.asarray(rends), num_runs,
                     jnp.asarray(valid_np))


def materialize_column(col: DeviceColumn) -> DeviceColumn:
    if isinstance(col, (DictEncodedColumn, RLEColumn)):
        return col.materialized()
    return col


def materialize_batch(batch):
    """Decode every encoded column (the op-level decline path)."""
    from .batch import ColumnarBatch
    if not any(isinstance(c, (DictEncodedColumn, RLEColumn))
               for c in batch.columns):
        return batch
    cols = tuple(materialize_column(c) for c in batch.columns)
    out = ColumnarBatch(batch.names, cols, batch.num_rows)
    cached = getattr(batch, "_nrows_host", None)
    if cached is not None:
        out._nrows_host = cached
    return out


def has_encoded_columns(batch) -> bool:
    return any(isinstance(c, (DictEncodedColumn, RLEColumn))
               for c in batch.columns)


def dictionary_from_wire(column: DeviceColumn, size: int, sorted_: bool,
                         content_hash: int) -> Dictionary:
    """Rebuild a dictionary from deserialized (host numpy) buffers,
    registering its host values and canonicalizing by content hash so
    every frame of one exchange shares a single object."""
    got = registered_dictionary(content_hash)
    if got is not None:
        return got
    if host_values_for(content_hash) is None:
        data = np.asarray(column.data)
        lengths = np.asarray(column.lengths)
        vals = np.empty(size, dtype=object)
        for i in range(size):
            vals[i] = bytes(data[i, :int(lengths[i])])
        _register_host_values(content_hash, vals)
    return _register_dictionary(
        Dictionary(column, size, sorted_, content_hash))


def materialize_np(col: DeviceColumn) -> DeviceColumn:
    """Host-side (numpy) materialization for deserialized encoded columns
    when the encoded kill switch is off — keeps the wire reader's
    host-buffers-only contract."""
    if isinstance(col, DictEncodedColumn):
        d = col.dictionary
        data = np.asarray(d.column.data)
        lengths = np.asarray(d.column.lengths)
        codes = np.asarray(col.codes)
        valid = np.asarray(col.validity)
        safe = np.clip(codes, 0, data.shape[0] - 1)
        out = np.where(valid[:, None], data[safe], 0).astype(np.uint8)
        out_len = np.where(valid, lengths[safe], 0).astype(np.int32)
        return DeviceColumn(col.dtype, out, valid, lengths=out_len)
    if isinstance(col, RLEColumn):
        valid = np.asarray(col.validity)
        cap = valid.shape[0]
        rends = np.asarray(col.run_ends)
        idx = np.searchsorted(rends, np.arange(cap), side="right")
        idx = np.clip(idx, 0, np.asarray(col.run_values.data).shape[0] - 1)
        data = np.where(valid, np.asarray(col.run_values.data)[idx], 0)
        aux = None
        if col.run_values.aux is not None:
            aux = np.where(valid, np.asarray(col.run_values.aux)[idx], 0)
        return DeviceColumn(col.dtype, data, valid, aux=aux)
    return col


# --------------------------------------------------------------------------
# dict-aware concat (exchange reduce, broadcast, join build sides)
# --------------------------------------------------------------------------

def same_dictionary(cols: Sequence[DeviceColumn]) -> bool:
    """All of ``cols`` are dict-encoded over one dictionary (the same
    object, or equal by content): their codes concatenate as they are."""
    first = cols[0]
    return all(isinstance(c, DictEncodedColumn)
               and (c.dictionary is first.dictionary
                    or c.dictionary.content_hash
                    == first.dictionary.content_hash)
               for c in cols)


def try_concat_dict_columns(cols: Sequence[DeviceColumn],
                            counts: Sequence[int],
                            out_capacity: int) -> Optional[DictEncodedColumn]:
    """Concatenate dict-encoded pieces WITHOUT materializing: same
    dictionary -> concat codes; different dictionaries -> unify on the
    host (dictionaries are small, values live in the registry) and remap
    each piece's codes.  Returns None to decline (caller materializes)."""
    if not all(isinstance(c, DictEncodedColumn) for c in cols):
        return None
    dtype = cols[0].dtype
    first = cols[0].dictionary
    if same_dictionary(cols):
        codes = _concat_padded([c.codes for c in cols], counts,
                               out_capacity, 0)
        validity = _concat_padded([c.validity for c in cols], counts,
                                  out_capacity, False)
        _bump("concat_unified")
        return DictEncodedColumn(dtype, codes, first, validity)
    value_lists = []
    for c in cols:
        hv = c.dictionary.host_values()
        if hv is None:
            return None
        value_lists.append(hv)
    union = sorted(set(v for hv in value_lists for v in hv))
    if len(union) > _max_cardinality():
        return None
    d = dictionary_from_values(dtype, union)
    index = {v: i for i, v in enumerate(union)}
    remapped = []
    for c, hv in zip(cols, value_lists):
        mapping = np.zeros(bucket_capacity(len(hv) + 1), dtype=np.int32)
        for old, v in enumerate(hv):
            mapping[old] = index[v]
        m = jnp.asarray(mapping)
        safe = jnp.clip(c.codes, 0, mapping.shape[0] - 1)
        remapped.append(jnp.where(c.validity, m[safe], 0))
    codes = _concat_padded(remapped, counts, out_capacity, 0)
    validity = _concat_padded([c.validity for c in cols], counts,
                              out_capacity, False)
    _bump("concat_unified")
    return DictEncodedColumn(dtype, codes, d, validity)


def _concat_padded(arrs, counts, out_capacity, fill):
    live = [a[:c] for a, c in zip(arrs, counts)]
    cat = jnp.concatenate(live) if live else arrs[0][:0]
    return jnp.pad(cat, (0, out_capacity - cat.shape[0]),
                   constant_values=fill)


# --------------------------------------------------------------------------
# join key lowering (probe on integer codes, not raw strings)
# --------------------------------------------------------------------------

#: remap tables are pure functions of the two dictionaries' contents —
#: cache per (probe hash, build hash) so B probe batches over one scan's
#: shared dictionary compute the table once
_MAP_CACHE: Dict[Tuple[int, int], np.ndarray] = {}


def map_codes_between(probe_dict: Dictionary,
                      build_dict: Dictionary) -> Optional[np.ndarray]:
    """Host remap table: probe dictionary code -> build dictionary code,
    -1 for values absent from the build side (the miss sentinel — never
    equal to any build code, so a missing value simply finds no match).
    O(|probe dict| log |build dict|) host work on the registry values."""
    ck = (probe_dict.content_hash, build_dict.content_hash)
    with _LOCK:
        got = _MAP_CACHE.get(ck)
    if got is not None:
        return got
    pv = probe_dict.host_values()
    bv = build_dict.host_values()
    if pv is None or bv is None:
        return None
    table = np.full(bucket_capacity(len(pv) + 1), -1, dtype=np.int32)
    bl = list(bv)
    pos = np.searchsorted(np.asarray(bv, dtype=object), pv)
    for i, v in enumerate(pv):
        p = int(pos[i])
        if p < len(bl) and bl[p] == v:
            table[i] = p
    with _LOCK:
        if len(_MAP_CACHE) > 1024:
            _MAP_CACHE.clear()
        _MAP_CACHE[ck] = table
    return table


def lower_join_codes(probe_col: DictEncodedColumn,
                     build_col: DictEncodedColumn
                     ) -> Optional[Tuple[DictEncodedColumn,
                                         DictEncodedColumn]]:
    """Prepare one key-column pair for code-space joining: the build side
    keeps its own (sorted) codes as join codes; the probe side's codes are
    remapped into the build dictionary (misses -> -1).  Equality of join
    codes is then exactly equality of values, and code ORDER on the build
    side equals value order (sorted dict), so the fast-path binary search
    is sound.  Null rows get join code 0 with validity False — excluded by
    the join's bad-row handling exactly like raw keys."""
    if probe_col.dictionary is build_col.dictionary or \
            probe_col.dictionary.content_hash == \
            build_col.dictionary.content_hash:
        return (probe_col.with_join_codes(probe_col.codes),
                build_col.with_join_codes(build_col.codes))
    if not build_col.dictionary.sorted:
        return None
    mapping = map_codes_between(probe_col.dictionary, build_col.dictionary)
    if mapping is None:
        return None
    m = jnp.asarray(mapping)
    safe = jnp.clip(probe_col.codes, 0, mapping.shape[0] - 1)
    jc = jnp.where(probe_col.validity, m[safe], 0)
    return (probe_col.with_join_codes(jc),
            build_col.with_join_codes(build_col.codes))
