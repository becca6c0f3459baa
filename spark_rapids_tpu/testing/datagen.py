"""Composable, seeded random data generators — an original implementation
of the reference's integration-test datagen design (``data_gen.py:38-751``:
per-type generators with nullability, special values, and nesting) used by
the independent-oracle test harness (engine vs pandas, not engine-vs-own-
numpy-backend, which shares bugs by construction).

Every generator is deterministic under a seed and produces a pyarrow array;
``gen_table`` assembles a full table.  Special values (extreme ints, NaN,
±inf, ±0.0, empty strings, epoch boundaries) are mixed in at a fixed rate
so boundary behavior is exercised at every scale.
"""

from __future__ import annotations

import datetime as _dt
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa


class DataGen:
    """Base: generates a pyarrow array of length n; subclasses implement
    ``_values(rng, n)`` returning a python list or numpy array."""

    arrow_type: pa.DataType = None  # type: ignore

    def __init__(self, nullable: bool = True, null_rate: float = 0.08,
                 special_rate: float = 0.05):
        self.nullable = nullable
        self.null_rate = null_rate if nullable else 0.0
        self.special_rate = special_rate

    # --- interface --------------------------------------------------------
    def _values(self, rng: np.random.Generator, n: int) -> List:
        raise NotImplementedError

    def _specials(self) -> List:
        return []

    def gen(self, rng: np.random.Generator, n: int) -> pa.Array:
        vals = list(self._values(rng, n))
        specials = self._specials()
        if specials and self.special_rate > 0:
            hits = rng.random(n) < self.special_rate
            picks = rng.integers(0, len(specials), n)
            for i in np.nonzero(hits)[0]:
                vals[i] = specials[int(picks[i])]
        if self.null_rate > 0:
            nulls = rng.random(n) < self.null_rate
            for i in np.nonzero(nulls)[0]:
                vals[i] = None
        return pa.array(vals, type=self.arrow_type)


class BooleanGen(DataGen):
    arrow_type = pa.bool_()

    def _values(self, rng, n):
        return rng.integers(0, 2, n).astype(bool).tolist()


class _IntGen(DataGen):
    _lo = _hi = 0

    def __init__(self, min_val: Optional[int] = None,
                 max_val: Optional[int] = None, **kw):
        super().__init__(**kw)
        self.min_val = self._lo if min_val is None else min_val
        self.max_val = self._hi if max_val is None else max_val

    def _values(self, rng, n):
        return rng.integers(self.min_val, self.max_val + 1, n,
                            dtype=np.int64).tolist()

    def _specials(self):
        return [self.min_val, self.max_val, 0, 1, -1]


class ByteGen(_IntGen):
    arrow_type = pa.int8()
    _lo, _hi = -128, 127


class ShortGen(_IntGen):
    arrow_type = pa.int16()
    _lo, _hi = -(1 << 15), (1 << 15) - 1


class IntegerGen(_IntGen):
    arrow_type = pa.int32()
    _lo, _hi = -(1 << 31), (1 << 31) - 1


class LongGen(_IntGen):
    arrow_type = pa.int64()
    _lo, _hi = -(1 << 63), (1 << 63) - 1


class FloatGen(DataGen):
    arrow_type = pa.float32()

    def __init__(self, no_nans: bool = False, no_extremes: bool = False,
                 **kw):
        super().__init__(**kw)
        self.no_nans = no_nans
        self.no_extremes = no_extremes  # drop ±max (sums overflow to ±inf
        # in an order-dependent way, poisoning aggregation oracles)

    def _values(self, rng, n):
        return ((rng.random(n) - 0.5) * 2e6).astype(np.float32).tolist()

    def _specials(self):
        base = [0.0, -0.0, 1.0, -1.0, 1.17549435e-38]
        if not self.no_extremes:
            base += [3.4028235e38, -3.4028235e38]
        if not self.no_nans:
            base += [float("nan"), float("inf"), float("-inf")]
        return base


class DoubleGen(DataGen):
    arrow_type = pa.float64()

    def __init__(self, no_nans: bool = False, no_extremes: bool = False,
                 **kw):
        super().__init__(**kw)
        self.no_nans = no_nans
        self.no_extremes = no_extremes

    def _values(self, rng, n):
        return ((rng.random(n) - 0.5) * 2e12).tolist()

    def _specials(self):
        base = [0.0, -0.0, 1.0, -1.0, 2.2250738585072014e-308]
        if not self.no_extremes:
            base += [1.7976931348623157e308, -1.7976931348623157e308]
        if not self.no_nans:
            base += [float("nan"), float("inf"), float("-inf")]
        return base


class DecimalGen(DataGen):
    def __init__(self, precision: int = 18, scale: int = 2, **kw):
        super().__init__(**kw)
        self.precision, self.scale = precision, scale
        self.arrow_type = pa.decimal128(precision, scale)
        self._m = 10 ** (precision - scale) - 1

    def _values(self, rng, n):
        from decimal import Decimal
        unscaled = rng.integers(-self._m, self._m, n)
        q = Decimal(1).scaleb(-self.scale)
        return [(Decimal(int(u)) * q) for u in unscaled]

    def _specials(self):
        from decimal import Decimal
        q = Decimal(1).scaleb(-self.scale)
        return [Decimal(0) * q, Decimal(self._m) * q, Decimal(-self._m) * q]


_DEFAULT_CHARS = ("abcdefghijklmnopqrstuvwxyz"
                  "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789 _-.,:/@")


class StringGen(DataGen):
    arrow_type = pa.string()

    def __init__(self, charset: str = _DEFAULT_CHARS, min_len: int = 0,
                 max_len: int = 24, **kw):
        super().__init__(**kw)
        self.charset = charset
        self.min_len, self.max_len = min_len, max_len

    def _values(self, rng, n):
        lens = rng.integers(self.min_len, self.max_len + 1, n)
        chars = rng.integers(0, len(self.charset), int(lens.sum()))
        out, pos = [], 0
        for ln in lens:
            out.append("".join(self.charset[c]
                               for c in chars[pos:pos + ln]))
            pos += ln
        return out

    def _specials(self):
        return ["", " ", "NULL", "null", "0", "-1", "a" * self.max_len,
                "é中ñ"[:max(self.max_len, 1)]]


class DateGen(DataGen):
    arrow_type = pa.date32()

    def _values(self, rng, n):
        days = rng.integers(-25000, 25000, n)  # ~1901..2106
        epoch = _dt.date(1970, 1, 1)
        return [epoch + _dt.timedelta(days=int(d)) for d in days]

    def _specials(self):
        return [_dt.date(1970, 1, 1), _dt.date(2000, 2, 29),
                _dt.date(1969, 12, 31), _dt.date(2038, 1, 19)]


class TimestampGen(DataGen):
    arrow_type = pa.timestamp("us", tz="UTC")

    def _values(self, rng, n):
        micros = rng.integers(-2_000_000_000_000_000, 4_000_000_000_000_000,
                              n)
        return micros.tolist()

    def gen(self, rng, n):  # micros -> arrow timestamps directly
        vals = list(self._values(rng, n))
        if self.null_rate > 0:
            nulls = rng.random(n) < self.null_rate
            for i in np.nonzero(nulls)[0]:
                vals[i] = None
        return pa.array(vals, type=self.arrow_type)


class ArrayGen(DataGen):
    def __init__(self, child: DataGen, min_len: int = 0, max_len: int = 6,
                 **kw):
        super().__init__(**kw)
        self.child = child
        self.min_len, self.max_len = min_len, max_len
        self.arrow_type = pa.list_(child.arrow_type)

    def _values(self, rng, n):
        lens = rng.integers(self.min_len, self.max_len + 1, n)
        flat = self.child.gen(rng, int(lens.sum())).to_pylist()
        out, pos = [], 0
        for ln in lens:
            out.append(flat[pos:pos + ln])
            pos += ln
        return out


class MapGen(DataGen):
    def __init__(self, key: Optional[DataGen] = None,
                 value: Optional[DataGen] = None, max_len: int = 4, **kw):
        super().__init__(**kw)
        self.key = key or StringGen(min_len=1, max_len=6, nullable=False)
        self.value = value or LongGen(min_val=-1000, max_val=1000)
        self.max_len = max_len
        self.arrow_type = pa.map_(self.key.arrow_type, self.value.arrow_type)

    def _values(self, rng, n):
        lens = rng.integers(0, self.max_len + 1, n)
        total = int(lens.sum())
        keys = self.key.gen(rng, total).to_pylist()
        vals = self.value.gen(rng, total).to_pylist()
        out, pos = [], 0
        for ln in lens:
            # map keys must be unique per row
            seen, items = set(), []
            for k, v in zip(keys[pos:pos + ln], vals[pos:pos + ln]):
                if k not in seen:
                    seen.add(k)
                    items.append((k, v))
            out.append(items)
            pos += ln
        return out


class StructGen(DataGen):
    def __init__(self, fields: Sequence[Tuple[str, DataGen]], **kw):
        super().__init__(**kw)
        self.fields = list(fields)
        self.arrow_type = pa.struct(
            [pa.field(n, g.arrow_type) for n, g in self.fields])

    def _values(self, rng, n):
        cols = {name: g.gen(rng, n).to_pylist() for name, g in self.fields}
        return [{name: cols[name][i] for name, _ in self.fields}
                for i in range(n)]


def gen_table(gens: Dict[str, DataGen], n: int, seed: int = 0) -> pa.Table:
    """Deterministic table: one independent rng stream per column so adding
    a column never perturbs the others (reference datagen invariant)."""
    arrays, names = [], []
    for i, (name, g) in enumerate(gens.items()):
        rng = np.random.default_rng([seed, i])
        arrays.append(g.gen(rng, n))
        names.append(name)
    return pa.table(dict(zip(names, arrays)))
