"""Spark-compatible logical data types for the TPU columnar engine.

Mirrors the type surface the reference plugin supports (see reference
``sql-plugin/.../TypeChecks.scala`` TypeSig enumeration): BOOLEAN, BYTE, SHORT,
INT, LONG, FLOAT, DOUBLE, DATE, TIMESTAMP, STRING, BINARY, DECIMAL, NULL,
ARRAY, STRUCT, MAP.  On TPU the physical carrier for each type is a JAX dtype
(column layout documented in ``columnar/column.py``).
"""

from __future__ import annotations

import datetime as _dt
from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import numpy as np

__all__ = [
    "DataType", "BooleanType", "ByteType", "ShortType", "IntegerType",
    "LongType", "FloatType", "DoubleType", "StringType", "BinaryType",
    "DateType", "TimestampType", "DecimalType", "NullType", "ArrayType",
    "StructField", "StructType", "MapType", "from_arrow", "to_arrow",
    "BOOLEAN", "BYTE", "SHORT", "INT", "LONG", "FLOAT", "DOUBLE", "STRING",
    "BINARY", "DATE", "TIMESTAMP", "NULL",
    "is_numeric", "is_integral", "is_floating", "common_type",
    "numeric_promote",
]


class DataType:
    """Base class for all logical types."""

    #: numpy dtype used as the physical device carrier (None = layout-special)
    np_dtype: Optional[np.dtype] = None

    def simple_string(self) -> str:
        return type(self).__name__.replace("Type", "").lower()

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return self.simple_string()

    def __eq__(self, other: Any) -> bool:
        return type(self) is type(other)

    def __hash__(self) -> int:
        return hash(type(self).__name__)

    @property
    def is_nested(self) -> bool:
        return isinstance(self, (ArrayType, StructType, MapType))


class NumericType(DataType):
    pass


class IntegralType(NumericType):
    pass


class FractionalType(NumericType):
    pass


class BooleanType(DataType):
    np_dtype = np.dtype(np.bool_)


class ByteType(IntegralType):
    np_dtype = np.dtype(np.int8)
    min_value, max_value = -(2 ** 7), 2 ** 7 - 1

    def simple_string(self) -> str:
        return "tinyint"


class ShortType(IntegralType):
    np_dtype = np.dtype(np.int16)
    min_value, max_value = -(2 ** 15), 2 ** 15 - 1

    def simple_string(self) -> str:
        return "smallint"


class IntegerType(IntegralType):
    np_dtype = np.dtype(np.int32)
    min_value, max_value = -(2 ** 31), 2 ** 31 - 1

    def simple_string(self) -> str:
        return "int"


class LongType(IntegralType):
    np_dtype = np.dtype(np.int64)
    min_value, max_value = -(2 ** 63), 2 ** 63 - 1

    def simple_string(self) -> str:
        return "bigint"


class FloatType(FractionalType):
    np_dtype = np.dtype(np.float32)


class DoubleType(FractionalType):
    np_dtype = np.dtype(np.float64)


class StringType(DataType):
    # physical layout: uint8 byte matrix + int32 lengths (see column.py)
    np_dtype = np.dtype(np.uint8)


class BinaryType(DataType):
    np_dtype = np.dtype(np.uint8)


class DateType(DataType):
    """Days since epoch, int32 carrier (Spark DateType semantics)."""
    np_dtype = np.dtype(np.int32)


class TimestampType(DataType):
    """Microseconds since epoch UTC, int64 carrier (Spark TimestampType)."""
    np_dtype = np.dtype(np.int64)


class NullType(DataType):
    np_dtype = np.dtype(np.int8)

    def simple_string(self) -> str:
        return "void"


@dataclass(frozen=True)
class DecimalType(FractionalType):
    """Spark decimal(p, s).  Carrier is a scaled int64 for precision <= 18
    (DECIMAL_64); precision 19-38 uses a (hi, lo) int64 pair column
    (DECIMAL_128), mirroring the reference's decimal-128 support
    (reference ``Aggregation128Utils``/``DecimalUtils`` JNI kernels)."""
    precision: int = 10
    scale: int = 0

    MAX_PRECISION = 38
    MAX_LONG_DIGITS = 18

    def __post_init__(self):
        if not (0 < self.precision <= self.MAX_PRECISION):
            raise ValueError(f"decimal precision out of range: {self.precision}")
        if not (0 <= self.scale <= self.precision):
            raise ValueError(
                f"decimal scale {self.scale} out of range for precision {self.precision}")

    @property
    def np_dtype(self):  # type: ignore[override]
        return np.dtype(np.int64)

    @property
    def is_long_backed(self) -> bool:
        return self.precision <= self.MAX_LONG_DIGITS

    def simple_string(self) -> str:
        return f"decimal({self.precision},{self.scale})"

    def __hash__(self) -> int:
        return hash(("decimal", self.precision, self.scale))

    def __eq__(self, other: Any) -> bool:
        return (isinstance(other, DecimalType)
                and other.precision == self.precision and other.scale == self.scale)

    @staticmethod
    def bounded(precision: int, scale: int) -> "DecimalType":
        return DecimalType(min(precision, DecimalType.MAX_PRECISION),
                           min(scale, DecimalType.MAX_PRECISION))


@dataclass(frozen=True)
class ArrayType(DataType):
    element_type: DataType = field(default_factory=lambda: NullType())
    contains_null: bool = True

    def simple_string(self) -> str:
        return f"array<{self.element_type.simple_string()}>"

    def __hash__(self) -> int:
        return hash(("array", self.element_type))

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, ArrayType) and other.element_type == self.element_type


@dataclass(frozen=True)
class StructField:
    name: str
    data_type: DataType
    nullable: bool = True


@dataclass(frozen=True)
class StructType(DataType):
    fields: Tuple[StructField, ...] = ()

    def __init__(self, fields=()):
        object.__setattr__(self, "fields", tuple(fields))

    @property
    def names(self) -> List[str]:
        return [f.name for f in self.fields]

    def field_index(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def add(self, name: str, dt: DataType, nullable: bool = True) -> "StructType":
        return StructType(self.fields + (StructField(name, dt, nullable),))

    def simple_string(self) -> str:
        inner = ",".join(f"{f.name}:{f.data_type.simple_string()}" for f in self.fields)
        return f"struct<{inner}>"

    def __hash__(self) -> int:
        return hash(("struct", self.fields))

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, StructType) and other.fields == self.fields

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)


@dataclass(frozen=True)
class MapType(DataType):
    key_type: DataType = field(default_factory=lambda: NullType())
    value_type: DataType = field(default_factory=lambda: NullType())
    value_contains_null: bool = True

    def simple_string(self) -> str:
        return (f"map<{self.key_type.simple_string()},"
                f"{self.value_type.simple_string()}>")

    def __hash__(self) -> int:
        return hash(("map", self.key_type, self.value_type))

    def __eq__(self, other: Any) -> bool:
        return (isinstance(other, MapType) and other.key_type == self.key_type
                and other.value_type == self.value_type)


# Singletons for the common scalar types
BOOLEAN = BooleanType()
BYTE = ByteType()
SHORT = ShortType()
INT = IntegerType()
LONG = LongType()
FLOAT = FloatType()
DOUBLE = DoubleType()
STRING = StringType()
BINARY = BinaryType()
DATE = DateType()
TIMESTAMP = TimestampType()
NULL = NullType()

_INTEGRAL_ORDER = [ByteType(), ShortType(), IntegerType(), LongType()]


def is_numeric(dt: DataType) -> bool:
    return isinstance(dt, NumericType)


def is_integral(dt: DataType) -> bool:
    return isinstance(dt, IntegralType)


def is_floating(dt: DataType) -> bool:
    return isinstance(dt, (FloatType, DoubleType))


def numeric_promote(a: DataType, b: DataType) -> DataType:
    """Binary arithmetic result type following Spark's numeric precedence
    byte < short < int < long < float < double (decimal handled separately)."""
    if isinstance(a, DecimalType) or isinstance(b, DecimalType):
        da = a if isinstance(a, DecimalType) else _decimal_for_integral(a)
        db = b if isinstance(b, DecimalType) else _decimal_for_integral(b)
        if da is None or db is None:  # decimal with float → double
            return DOUBLE
        p = max(da.precision - da.scale, db.precision - db.scale) + max(da.scale, db.scale)
        s = max(da.scale, db.scale)
        return DecimalType.bounded(p, s)
    if isinstance(a, DoubleType) or isinstance(b, DoubleType):
        return DOUBLE
    if isinstance(a, FloatType) or isinstance(b, FloatType):
        return FLOAT
    ia = _INTEGRAL_ORDER.index(a) if a in _INTEGRAL_ORDER else None
    ib = _INTEGRAL_ORDER.index(b) if b in _INTEGRAL_ORDER else None
    if ia is None or ib is None:
        raise TypeError(f"cannot promote {a} and {b}")
    return _INTEGRAL_ORDER[max(ia, ib)]


def _decimal_for_integral(dt: DataType) -> Optional[DecimalType]:
    if isinstance(dt, ByteType):
        return DecimalType(3, 0)
    if isinstance(dt, ShortType):
        return DecimalType(5, 0)
    if isinstance(dt, IntegerType):
        return DecimalType(10, 0)
    if isinstance(dt, LongType):
        return DecimalType(20, 0)
    return None


def common_type(a: DataType, b: DataType) -> Optional[DataType]:
    """Least common type for comparisons/conditionals (subset of Spark's
    TypeCoercion.findTightestCommonType)."""
    if a == b:
        return a
    if isinstance(a, NullType):
        return b
    if isinstance(b, NullType):
        return a
    if is_numeric(a) and is_numeric(b):
        return numeric_promote(a, b)
    if isinstance(a, StringType) or isinstance(b, StringType):
        return STRING
    if isinstance(a, DateType) and isinstance(b, TimestampType):
        return TIMESTAMP
    if isinstance(a, TimestampType) and isinstance(b, DateType):
        return TIMESTAMP
    return None


def from_arrow(at) -> DataType:
    """Map a pyarrow type to the engine's logical type."""
    import pyarrow as pa
    if pa.types.is_boolean(at):
        return BOOLEAN
    if pa.types.is_int8(at):
        return BYTE
    if pa.types.is_int16(at):
        return SHORT
    if pa.types.is_int32(at):
        return INT
    if pa.types.is_int64(at):
        return LONG
    if pa.types.is_uint8(at):
        return SHORT
    if pa.types.is_uint16(at):
        return INT
    if pa.types.is_uint32(at) or pa.types.is_uint64(at):
        return LONG
    if pa.types.is_float32(at):
        return FLOAT
    if pa.types.is_float64(at):
        return DOUBLE
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return STRING
    if pa.types.is_binary(at) or pa.types.is_large_binary(at):
        return BINARY
    if pa.types.is_date32(at):
        return DATE
    if pa.types.is_timestamp(at):
        return TIMESTAMP
    if pa.types.is_decimal(at):
        return DecimalType(at.precision, at.scale)
    if pa.types.is_null(at):
        return NULL
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return ArrayType(from_arrow(at.value_type))
    if pa.types.is_struct(at):
        return StructType(tuple(StructField(f.name, from_arrow(f.type), f.nullable)
                                for f in at))
    if pa.types.is_map(at):
        return MapType(from_arrow(at.key_type), from_arrow(at.item_type))
    if pa.types.is_dictionary(at):
        return from_arrow(at.value_type)    # an encoding, not a type
    raise TypeError(f"unsupported arrow type {at}")


def to_arrow(dt: DataType):
    import pyarrow as pa
    if isinstance(dt, BooleanType):
        return pa.bool_()
    if isinstance(dt, ByteType):
        return pa.int8()
    if isinstance(dt, ShortType):
        return pa.int16()
    if isinstance(dt, IntegerType):
        return pa.int32()
    if isinstance(dt, LongType):
        return pa.int64()
    if isinstance(dt, FloatType):
        return pa.float32()
    if isinstance(dt, DoubleType):
        return pa.float64()
    if isinstance(dt, StringType):
        return pa.string()
    if isinstance(dt, BinaryType):
        return pa.binary()
    if isinstance(dt, DateType):
        return pa.date32()
    if isinstance(dt, TimestampType):
        return pa.timestamp("us", tz="UTC")
    if isinstance(dt, DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, NullType):
        return pa.null()
    if isinstance(dt, ArrayType):
        return pa.list_(to_arrow(dt.element_type))
    if isinstance(dt, StructType):
        return pa.struct([pa.field(f.name, to_arrow(f.data_type), f.nullable)
                          for f in dt.fields])
    if isinstance(dt, MapType):
        return pa.map_(to_arrow(dt.key_type), to_arrow(dt.value_type))
    raise TypeError(f"unsupported type {dt}")


def python_value_type(v: Any) -> DataType:
    """Infer the logical type of a Python literal (Spark Literal inference)."""
    if v is None:
        return NULL
    if isinstance(v, bool):
        return BOOLEAN
    if isinstance(v, int):
        return INT if IntegerType.min_value <= v <= IntegerType.max_value else LONG
    if isinstance(v, float):
        return DOUBLE
    if isinstance(v, str):
        return STRING
    if isinstance(v, (bytes, bytearray)):
        return BINARY
    if isinstance(v, _dt.datetime):
        return TIMESTAMP
    if isinstance(v, _dt.date):
        return DATE
    import decimal
    if isinstance(v, decimal.Decimal):
        sign, digits, exp = v.as_tuple()
        if exp >= 0:
            return DecimalType(len(digits) + exp, 0)
        scale = -exp
        precision = max(len(digits), scale + 1)
        return DecimalType(precision, scale)
    if isinstance(v, (list, tuple)):
        et = python_value_type(v[0]) if v else NULL
        return ArrayType(et)
    raise TypeError(f"cannot infer literal type for {type(v)}")
