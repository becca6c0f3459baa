"""Device-side CSV decode (reference ``GpuCSVScan.scala:355`` —
``Table.readCSV`` takes a host buffer and parses on the GPU).  Same
architecture as the parquet/ORC decoders: the host does O(structure)
work ONLY — vectorized numpy scans for newline and delimiter positions —
and the device does the per-value work: field-byte gathers into matrices
(:func:`.device_parquet.gather_string_matrix`) and Spark-exact parsing
via the ``ops/cast_strings`` kernels (the CastStrings analog the cast
matrix already uses, so CSV-parsed and CAST-parsed values can never
disagree).

Decline-to-host discipline (pyarrow keeps serving what's outside the
envelope): quoted fields, custom null markers, multi-char separators,
CR/LF line endings, BOMs, blank interior lines, ragged rows — and any
file where a non-empty field fails to parse as the plan schema's type
(sample-based inference may have guessed a narrower type than the full
file supports; correctness beats the fast path).
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar.batch import ColumnarBatch
from ..columnar.column import (DeviceColumn, bucket_capacity, bucket_width,
                               null_column)
from .device_parquet import (_buf_to_words, _max_string_matrix_bytes,
                             _pad_pow2, gather_string_matrix)


def decode_file(path: str, options: Dict, out_fields, tctx=None,
                conf=None, raw: Optional[bytes] = None,
                columns: Optional[Sequence[int]] = None,
                width: Optional[int] = None) -> Optional[ColumnarBatch]:
    """Decode one CSV file into a :class:`ColumnarBatch` typed by the
    plan's output fields, or ``None`` to decline to the host reader.
    Callers that already read the file pass ``raw`` so a decline does
    not re-read it from disk.  A narrowed scan's ``out_fields`` are some
    of a line's ``width`` fields: those at positions ``columns``."""
    sep = str(options.get("sep", options.get("delimiter", ",")))
    if len(sep) != 1:
        return None
    if str(options.get("nullValue", "")) != "":
        return None  # custom null markers: host
    has_header = str(options.get("header", "true")).lower() == "true"

    if raw is None:
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except OSError:
            return None
    if not raw or raw.startswith(b"\xef\xbb\xbf"):
        return None
    buf = np.frombuffer(raw, np.uint8)
    if (buf == ord('"')).any() or (buf == 13).any():
        return None  # quoting / CRLF: host

    nl = np.flatnonzero(buf == 10)
    if raw[-1:] == b"\n":
        ends = nl.astype(np.int64)
    else:
        ends = np.append(nl, len(raw)).astype(np.int64)
    starts = np.concatenate([[0], nl + 1]).astype(np.int64)[:len(ends)]
    if len(starts) == 0 or (starts == ends).any():
        return None  # blank lines (Spark skips them): host
    if has_header:
        starts, ends = starts[1:], ends[1:]
    n = len(starts)
    if n == 0:
        return None

    ncols = width or len(out_fields)
    dp = np.flatnonzero(buf == ord(sep)).astype(np.int64)
    dp = dp[dp >= starts[0]]
    if ncols > 1:
        line_of = np.searchsorted(starts, dp, side="right") - 1
        counts = np.bincount(line_of, minlength=n)
        if not (counts == ncols - 1).all():
            return None  # ragged rows / stray delimiters: host
        dmat = dp.reshape(n, ncols - 1)
    else:
        if len(dp):
            return None  # separators in a single-column file
        dmat = np.zeros((n, 0), np.int64)
    col_starts = np.concatenate([starts[:, None], dmat + 1], axis=1)
    col_ends = np.concatenate([dmat, ends[:, None]], axis=1)
    col_lens = (col_ends - col_starts).astype(np.int32)

    capacity = bucket_capacity(n)
    max_bytes = _max_string_matrix_bytes(conf)
    words = _buf_to_words(raw)
    from ..ops import cast_strings as CS
    cols = []
    fail_counts = []
    for ci, fld in zip(range(ncols) if columns is None else columns,
                       out_fields):
        dt = fld.dtype if hasattr(fld, "dtype") else fld.data_type
        if isinstance(dt, T.NullType):
            cols.append(null_column(dt, capacity))
            continue
        lens_np = col_lens[:, ci]
        w = bucket_width(int(lens_np.max()))
        if capacity * w > max_bytes:
            return None  # ragged guard: the host path width-splits
        sp = np.zeros(capacity, np.int64)
        sp[:n] = col_starts[:, ci]
        lp = np.zeros(capacity, np.int32)
        lp[:n] = lens_np
        starts_d = jnp.asarray(sp)
        lens_d = jnp.asarray(lp)
        chars = gather_string_matrix(words, starts_d, lens_d, w, capacity)
        live = jnp.arange(capacity) < n
        present = (lens_d > 0) & live  # empty field = null (nullValue "")
        if isinstance(dt, (T.StringType, T.BinaryType)):
            cols.append(DeviceColumn(
                dt, chars, present,
                lengths=jnp.where(present, lens_d, 0)))
            continue
        if T.is_integral(dt):
            v, ok = CS.parse_long(jnp, chars, lens_d, present)
            if dt.np_dtype.itemsize < 8:
                info = np.iinfo(dt.np_dtype)
                ok = ok & (v >= int(info.min)) & (v <= int(info.max))
            data = v.astype(dt.np_dtype)
        elif isinstance(dt, (T.FloatType, T.DoubleType)):
            v, ok = CS.parse_double(jnp, chars, lens_d, present)
            data = v.astype(dt.np_dtype)
        elif isinstance(dt, T.BooleanType):
            data, ok = CS.parse_bool(jnp, chars, lens_d, present)
        elif isinstance(dt, T.DateType):
            data, ok = CS.parse_date(jnp, chars, lens_d, present)
        elif isinstance(dt, T.TimestampType):
            data, ok = CS.parse_timestamp(jnp, chars, lens_d, present)
        elif isinstance(dt, T.DecimalType) and dt.is_long_backed:
            data, ok = CS.parse_decimal(jnp, chars, lens_d, present,
                                        dt.precision, dt.scale)
        elif isinstance(dt, T.DecimalType):
            lo, hi, ok = CS.parse_decimal128(jnp, chars, lens_d, present,
                                             dt.precision, dt.scale)
            fail_counts.append(jnp.sum(present & ~ok))
            cols.append(DeviceColumn(dt, lo, ok & present, aux=hi))
            continue
        else:
            return None  # nested/unsupported plan type
        # a NON-EMPTY field the parser rejected means the plan's
        # (sample-inferred) type doesn't fit the full file — decline
        fail_counts.append(jnp.sum(present & ~ok))
        valid = ok & present
        cols.append(DeviceColumn(dt, jnp.where(valid, data, 0), valid))

    if fail_counts:
        total = int(jnp.stack(fail_counts).sum())
        if total:
            if tctx is not None:
                tctx.inc_metric("csvDeviceParseDeclines")
            return None
    if tctx is not None:
        tctx.inc_metric("csvDeviceDecodedFiles")
    names = [f.name for f in out_fields]
    return ColumnarBatch.make(tuple(names), cols, n)
