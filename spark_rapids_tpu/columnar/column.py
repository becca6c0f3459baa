"""Device column layout — the TPU-native analog of cuDF's ``ColumnVector``
(reference consumes it as ``ai.rapids.cudf.ColumnVector`` via
``GpuColumnVector.java``; see SURVEY §2.10).

Layout rules (XLA-first):

* Every column is padded to a power-of-two row **capacity** so that XLA
  compiles one program per (schema, capacity-bucket) instead of one per row
  count.  Rows at index >= ``num_rows`` (tracked on the batch) are dead:
  their validity is False and their data is zero.
* Fixed-width types: ``data[capacity]`` with the type's numpy carrier dtype,
  ``validity[capacity]`` bool (True = valid; nulls hold zeroed data).
* STRING/BINARY: ``data[capacity, width]`` uint8 byte matrix (width is a
  power-of-two bucket) + ``lengths[capacity]`` int32.  This trades memory for
  static shapes and vectorizable string kernels on the VPU — the TPU answer
  to cuDF's offset+chars layout, which would force dynamic shapes under XLA.
* STRUCT: no own data, only ``children`` columns + own validity.
* ARRAY: ``data[capacity, width]`` is replaced by a child column holding
  ``capacity * width`` flattened elements plus ``lengths``; width buckets the
  max list length (same padding trick one level down).
* DECIMAL(p<=18): scaled int64 in ``data``. DECIMAL(p>18): ``data`` is the
  low 64 bits, ``aux`` the high 64 bits (Aggregation128Utils equivalent).

Columns are registered as JAX pytrees, so whole batches flow through ``jit``.
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..types import (ArrayType, BinaryType, DataType, DecimalType, MapType,
                     NullType, StringType, StructType)

_MIN_CAPACITY = 8
_MIN_WIDTH = 4


def bucket_capacity(num_rows: int, minimum: int = _MIN_CAPACITY) -> int:
    """Smallest power-of-two >= max(num_rows, minimum)."""
    n = max(int(num_rows), minimum, 1)
    return 1 << (n - 1).bit_length()


def bucket_width(max_len: int, minimum: int = _MIN_WIDTH) -> int:
    n = max(int(max_len), minimum, 1)
    return 1 << (n - 1).bit_length()


def is_string_like(dt: DataType) -> bool:
    return isinstance(dt, (StringType, BinaryType))


@jax.tree_util.register_pytree_node_class
@dataclass
class DeviceColumn:
    """One logical column resident in device memory."""

    dtype: DataType
    data: Optional[jnp.ndarray] = None          # None for STRUCT
    validity: Optional[jnp.ndarray] = None      # bool[capacity]
    lengths: Optional[jnp.ndarray] = None       # int32[capacity] strings/lists
    aux: Optional[jnp.ndarray] = None           # decimal128 high words
    children: Tuple["DeviceColumn", ...] = ()

    # --- pytree protocol --------------------------------------------------
    def tree_flatten(self):
        return ((self.data, self.validity, self.lengths, self.aux,
                 self.children), self.dtype)

    @classmethod
    def tree_unflatten(cls, dtype, leaves):
        data, validity, lengths, aux, children = leaves
        return cls(dtype, data, validity, lengths, aux, children)

    # --- shape info -------------------------------------------------------
    @property
    def capacity(self) -> int:
        if self.data is not None:
            return int(self.data.shape[0])
        if self.validity is not None:
            return int(self.validity.shape[0])
        return self.children[0].capacity

    @property
    def width(self) -> Optional[int]:
        if self.data is not None and self.data.ndim == 2:
            return int(self.data.shape[1])
        return None

    @property
    def is_array_like(self) -> bool:
        return isinstance(self.dtype, (ArrayType, MapType))

    @property
    def array_width(self) -> int:
        """Max-list-length bucket: the element child holds
        ``capacity * array_width`` flattened rows (row r's slots at
        ``r*w .. r*w+w-1``)."""
        assert self.is_array_like
        return self.children[0].capacity // max(self.capacity, 1)

    def with_validity(self, validity: jnp.ndarray) -> "DeviceColumn":
        return replace(self, validity=validity)

    def mask_dead_rows(self, row_mask: jnp.ndarray) -> "DeviceColumn":
        """Clear validity (and zero data) for rows beyond num_rows."""
        v = self.validity & row_mask if self.validity is not None else row_mask
        return replace(self, validity=v)

    # --- constructors for padding changes ---------------------------------
    def slice_capacity(self, new_capacity: int) -> "DeviceColumn":
        """Narrow or grow the capacity padding (device-side)."""
        if self.is_array_like:
            w = self.array_width
            return DeviceColumn(
                self.dtype, None,
                _fix_1d(self.validity, new_capacity, False),
                _fix_1d(self.lengths, new_capacity, 0),
                None,
                tuple(c.slice_capacity(new_capacity * w)
                      for c in self.children))

        def fix(arr, fill=0):
            if arr is None:
                return None
            cap = arr.shape[0]
            if cap == new_capacity:
                return arr
            if cap > new_capacity:
                return arr[:new_capacity]
            if getattr(arr, "dtype", None) == object:  # host nested column
                out = np.empty(new_capacity, dtype=object)
                out[:cap] = np.asarray(arr)
                return out
            pad = [(0, new_capacity - cap)] + [(0, 0)] * (arr.ndim - 1)
            return jnp.pad(arr, pad, constant_values=fill)

        return DeviceColumn(
            self.dtype, fix(self.data),
            fix(self.validity, False),
            fix(self.lengths),
            fix(self.aux),
            tuple(c.slice_capacity(new_capacity) for c in self.children))

    def window(self, start, capacity: int, live) -> "DeviceColumn":
        """Rows ``[start, start + capacity)`` as a column of that capacity
        — a contiguous copy, no gather.  ``start`` may be traced; rows
        past this column's end read as padding; ``live`` (bool[capacity])
        says which rows of the window exist, the rest come out invalid."""
        validity = _window(self.validity, start, capacity) & live
        lengths = _window(self.lengths, start, capacity)
        if self.is_array_like:
            w = self.array_width
            child_live = live[:, None].repeat(w, axis=1).reshape(-1)
            return DeviceColumn(
                self.dtype, None, validity, lengths, None,
                tuple(c.window(start * w, capacity * w, child_live)
                      for c in self.children))
        return DeviceColumn(
            self.dtype, _window(self.data, start, capacity), validity,
            lengths, _window(self.aux, start, capacity),
            tuple(c.window(start, capacity, live) for c in self.children))

    def gather(self, idx: jnp.ndarray, idx_valid: Optional[jnp.ndarray] = None
               ) -> "DeviceColumn":
        """Select rows by index (the JoinGatherer primitive).  ``idx`` may
        contain out-of-range sentinels; ``idx_valid`` marks which produce a
        valid row (False -> null output row, e.g. outer-join misses)."""
        safe = jnp.clip(idx, 0, self.capacity - 1)
        lengths = self.lengths[safe] if self.lengths is not None else None
        validity = (self.validity[safe] if self.validity is not None
                    else jnp.ones(idx.shape[0], dtype=bool))
        if idx_valid is not None:
            validity = validity & idx_valid
        if self.is_array_like:
            # row blocks: child row r*w+j follows its parent row
            w = self.array_width
            j = jnp.arange(w, dtype=safe.dtype)[None, :]
            child_idx = (safe[:, None] * w + j).reshape(-1)
            child_valid = (jnp.broadcast_to(
                validity[:, None], (idx.shape[0], w)).reshape(-1)
                if idx_valid is not None else None)
            children = tuple(c.gather(child_idx, child_valid)
                             for c in self.children)
            return DeviceColumn(self.dtype, None, validity, lengths, None,
                                children)
        data = self.data[safe] if self.data is not None else None
        aux = self.aux[safe] if self.aux is not None else None
        children = tuple(c.gather(idx, idx_valid) for c in self.children)
        return DeviceColumn(self.dtype, data, validity, lengths, aux, children)

    def with_array_width(self, new_width: int) -> "DeviceColumn":
        """Re-bucket an array column's slot width (grow or shrink)."""
        assert self.is_array_like
        w = self.array_width
        if new_width == w:
            return self
        cap = self.capacity
        r = jnp.arange(cap, dtype=jnp.int32)[:, None]
        j = jnp.arange(new_width, dtype=jnp.int32)[None, :]
        in_range = j < w
        child_idx = jnp.where(in_range, r * w + jnp.minimum(j, w - 1),
                              0).reshape(-1)
        child_valid = (in_range & (j < self.lengths[:, None])).reshape(-1)
        children = tuple(c.gather(child_idx, child_valid)
                         for c in self.children)
        lengths = jnp.minimum(self.lengths, new_width)
        return DeviceColumn(self.dtype, None, self.validity, lengths, None,
                            children)


def _window(arr, start, capacity: int):
    """``arr[start : start + capacity]`` with a possibly traced ``start``,
    zero-padded where the window runs past the array's end."""
    if arr is None:
        return None
    if isinstance(arr, np.ndarray):     # host backend: ``start`` is concrete
        out = np.zeros((capacity,) + arr.shape[1:], dtype=arr.dtype)
        rows = arr[int(start):int(start) + capacity]
        out[:rows.shape[0]] = rows
        return out
    pad = [(0, capacity)] + [(0, 0)] * (arr.ndim - 1)
    return jax.lax.dynamic_slice_in_dim(jnp.pad(arr, pad), start, capacity)


def _fix_1d(arr, new_capacity: int, fill):
    if arr is None:
        return None
    cap = arr.shape[0]
    if cap == new_capacity:
        return arr
    if cap > new_capacity:
        return arr[:new_capacity]
    return jnp.pad(arr, (0, new_capacity - cap), constant_values=fill)


def make_array_column(dtype: DataType, lengths: jnp.ndarray,
                      children: Tuple["DeviceColumn", ...],
                      validity: Optional[jnp.ndarray] = None) -> DeviceColumn:
    """ARRAY/MAP column: ``children`` hold capacity*width flattened rows
    (one child for arrays; (keys, values) for maps)."""
    if validity is None:
        validity = jnp.ones(lengths.shape[0], dtype=bool)
    return DeviceColumn(dtype, None, validity, lengths=lengths,
                        children=tuple(children))


def make_fixed_column(dtype: DataType, data: jnp.ndarray,
                      validity: Optional[jnp.ndarray] = None) -> DeviceColumn:
    if validity is None:
        validity = jnp.ones(data.shape[0], dtype=bool)
    return DeviceColumn(dtype, data, validity)


def make_string_column(dtype: DataType, chars: jnp.ndarray,
                       lengths: jnp.ndarray,
                       validity: Optional[jnp.ndarray] = None) -> DeviceColumn:
    if validity is None:
        validity = jnp.ones(chars.shape[0], dtype=bool)
    return DeviceColumn(dtype, chars, validity, lengths=lengths)


def null_column(dtype: DataType, capacity: int) -> DeviceColumn:
    """All-null column of the given type."""
    validity = jnp.zeros(capacity, dtype=bool)
    if isinstance(dtype, ArrayType):
        child = null_column(dtype.element_type, capacity * _MIN_WIDTH)
        return make_array_column(dtype, jnp.zeros(capacity, dtype=jnp.int32),
                                 (child,), validity)
    if isinstance(dtype, MapType):
        keys = null_column(dtype.key_type, capacity * _MIN_WIDTH)
        vals = null_column(dtype.value_type, capacity * _MIN_WIDTH)
        return make_array_column(dtype, jnp.zeros(capacity, dtype=jnp.int32),
                                 (keys, vals), validity)
    if isinstance(dtype, StructType):
        children = tuple(null_column(f.data_type, capacity) for f in dtype.fields)
        return DeviceColumn(dtype, None, validity, children=children)
    if is_string_like(dtype):
        chars = jnp.zeros((capacity, _MIN_WIDTH), dtype=jnp.uint8)
        lengths = jnp.zeros(capacity, dtype=jnp.int32)
        return DeviceColumn(dtype, chars, validity, lengths=lengths)
    np_dtype = dtype.np_dtype if dtype.np_dtype is not None else np.dtype(np.int8)
    data = jnp.zeros(capacity, dtype=np_dtype)
    aux = jnp.zeros(capacity, dtype=jnp.int64) if (
        isinstance(dtype, DecimalType) and not dtype.is_long_backed) else None
    return DeviceColumn(dtype, data, validity, aux=aux)


def scalar_column(dtype: DataType, value: Any, capacity: int) -> DeviceColumn:
    """Broadcast a host scalar to a device column (cudf ``Scalar`` analog)."""
    if value is None:
        return null_column(dtype, capacity)
    validity = jnp.ones(capacity, dtype=bool)
    if is_string_like(dtype):
        raw = value.encode("utf-8") if isinstance(value, str) else bytes(value)
        width = bucket_width(len(raw))
        row = np.zeros(width, dtype=np.uint8)
        row[:len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        chars = jnp.broadcast_to(jnp.asarray(row), (capacity, width))
        lengths = jnp.full(capacity, len(raw), dtype=jnp.int32)
        return DeviceColumn(dtype, chars, validity, lengths=lengths)
    if isinstance(dtype, DecimalType):
        import decimal
        unscaled = int(decimal.Decimal(value).scaleb(dtype.scale).to_integral_value())
        if dtype.is_long_backed:
            data = jnp.full(capacity, unscaled, dtype=jnp.int64)
            return DeviceColumn(dtype, data, validity)
        lo = unscaled & ((1 << 64) - 1)
        lo = lo - (1 << 64) if lo >= (1 << 63) else lo
        hi = unscaled >> 64
        return DeviceColumn(dtype, jnp.full(capacity, lo, dtype=jnp.int64),
                            validity, aux=jnp.full(capacity, hi, dtype=jnp.int64))
    import datetime as _dt
    from ..types import DateType, TimestampType
    if isinstance(dtype, DateType) and isinstance(value, _dt.date):
        value = (value - _dt.date(1970, 1, 1)).days
    elif isinstance(dtype, TimestampType) and isinstance(value, _dt.datetime):
        if value.tzinfo is None:
            value = value.replace(tzinfo=_dt.timezone.utc)
        value = int(value.timestamp() * 1_000_000)
    data = jnp.full(capacity, value, dtype=dtype.np_dtype)
    return DeviceColumn(dtype, data, validity)
