"""Hive UDF bridge — the analog of the reference's
``org.apache.spark.sql.hive.rapids.hiveUDFs.scala`` /
``rowBasedHiveUDFs.scala`` (SURVEY §2.9).

The reference runs Hive UDFs two ways: a columnar device call when the
UDF implements the ``RapidsUDF`` SPI, and a row-based JVM fallback
otherwise.  This engine is JVM-free, so the registered implementation is
a Python class resolved from a ``CREATE TEMPORARY FUNCTION name AS
'module.Class'`` statement (the exact DDL shape Spark uses for Hive
UDFs) or from :meth:`TpuSession.register_hive_function`:

* ``evaluate(*row_values)``            — row-based (GenericUDF analog);
  the expression is host-tagged like the other Python UDFs.
* ``evaluate_columnar(ctx, *cols)``    — device columnar (RapidsUDF SPI
  analog); receives the EvalContext + DeviceColumns and returns a
  DeviceColumn, running inside the jitted kernel like DeviceUDF.
* ``return_type``                      — engine DataType (attribute or
  zero-arg method), the ObjectInspector analog.
"""

from __future__ import annotations

from typing import Any

from ... import types as T
from .core import Expression, resolve_expression
from .udf import _col_from_pylist, _col_to_pylist


def resolve_hive_class(class_path: str) -> Any:
    """'module.sub.Class' -> instance (the Hive FunctionRegistry's
    class-loading analog, importing Python instead of JVM classes)."""
    import importlib
    mod_name, _, cls_name = class_path.rpartition(".")
    if not mod_name:
        raise ValueError(
            f"hive function class {class_path!r} must be a fully "
            f"qualified 'module.Class' path")
    try:
        mod = importlib.import_module(mod_name)
        cls = getattr(mod, cls_name)
    except (ImportError, AttributeError) as e:
        raise ValueError(
            f"cannot load hive function class {class_path!r}: {e}") from e
    return cls() if isinstance(cls, type) else cls


def _impl_return_type(impl) -> T.DataType:
    rt = getattr(impl, "return_type", None)
    if callable(rt):
        rt = rt()
    if not isinstance(rt, T.DataType):
        raise ValueError(
            f"hive function {type(impl).__name__} must declare "
            f"`return_type` as an engine DataType (the ObjectInspector "
            f"analog); got {rt!r}")
    return rt


class HiveSimpleUDF(Expression):
    """A registered Hive-style function call."""

    def __init__(self, name: str, impl: Any, *args):
        self.name = name
        self.impl = impl
        self.children = tuple(resolve_expression(a) for a in args)
        self._rt = _impl_return_type(impl)
        self._columnar = callable(getattr(impl, "evaluate_columnar", None))
        if not self._columnar and not callable(
                getattr(impl, "evaluate", None)):
            raise ValueError(
                f"hive function {name!r} must define evaluate() "
                f"(row-based) or evaluate_columnar() (device SPI)")

    def with_children(self, children):
        return HiveSimpleUDF(self.name, self.impl, *children)

    @property
    def data_type(self):
        return self._rt

    def pretty_name(self):
        return self.name

    def semantic_key(self):
        return ("HiveSimpleUDF", self.name, id(self.impl), str(self._rt))

    def tag_for_device(self, conf=None):
        if self._columnar:
            return None  # RapidsUDF-analog: runs in the device kernel
        return (f"hive UDF {self.name!r} is row-based (no "
                f"evaluate_columnar); runs on the host engine "
                f"(rowBasedHiveUDFs analog)")

    def kernel(self, ctx, *cols):
        if self._columnar:
            return self.impl.evaluate_columnar(ctx, *cols)
        n = int(ctx.batch.num_rows)
        lists = [_col_to_pylist(ctx, c, n) for c in cols]
        out = [self.impl.evaluate(*row) for row in zip(*lists)] if lists \
            else [self.impl.evaluate() for _ in range(n)]
        cap = cols[0].capacity if cols else ctx.capacity
        return _col_from_pylist(ctx, out + [None] * (cap - n),
                                self._rt, cap)
