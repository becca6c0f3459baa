"""Logical plan nodes.

The reference plugs into Spark Catalyst and rewrites *physical* plans
(SURVEY §2.2); standalone, we own the whole stack, so this module is the
Catalyst-equivalent logical algebra the DataFrame API builds, the analyzer
resolves, and the planner lowers to physical execs.  Node set mirrors the
exec coverage in ``GpuOverrides.scala:3805-4184``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple

from .. import types as T
from .expressions.core import (Alias, AttributeReference, Expression, Literal)


@dataclass(eq=False)
class SortOrder:
    child: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # default: nulls first iff ascending

    def __post_init__(self):
        if self.nulls_first is None:
            self.nulls_first = self.ascending

    def sql(self):
        d = "ASC" if self.ascending else "DESC"
        n = "NULLS FIRST" if self.nulls_first else "NULLS LAST"
        return f"{self.child.sql()} {d} {n}"


class LogicalPlan:
    children: Tuple["LogicalPlan", ...] = ()

    @property
    def output(self) -> List[AttributeReference]:
        raise NotImplementedError(type(self).__name__)

    @property
    def schema(self) -> T.StructType:
        return T.StructType(tuple(
            T.StructField(a.name, a.dtype, a.nullable) for a in self.output))

    def node_name(self) -> str:
        return type(self).__name__

    def simple_string(self) -> str:
        return self.node_name()

    def tree_string(self, level: int = 0) -> str:
        s = "  " * level + ("+- " if level else "") + self.simple_string()
        return "\n".join([s] + [c.tree_string(level + 1) for c in self.children])


@dataclass(eq=False)
class Relation(LogicalPlan):
    """In-memory relation over a pyarrow Table (optionally pre-partitioned)."""
    table: Any = None  # pa.Table
    partitions: Optional[List[Any]] = None  # list of pa.Table

    @property
    def output(self):
        if not hasattr(self, "_output"):
            self._output = [
                AttributeReference(f.name, T.from_arrow(f.type), f.nullable)
                for f in self.table.schema]
        return self._output

    def narrowed(self, attrs) -> "Relation":
        """This relation handing on only ``attrs`` (some of ``output``, in
        its order): the same table and partition objects, which the scan's
        upload cache is keyed by (``sql/column_pruning.py``)."""
        new = Relation(self.table, self.partitions)
        new._output = list(attrs)
        return new

    def simple_string(self):
        return f"Relation [{', '.join(a.name for a in self.output)}]"


@dataclass(eq=False)
class CachedRelation(LogicalPlan):
    """df.persist() backing store: the collected result held as COMPRESSED
    parquet bytes, decoded lazily on first scan (the
    ParquetCachedBatchSerializer analog — cached data costs parquet bytes,
    not live arrow/device memory, until it is read again)."""
    blob: bytes = b""
    schema_fields: Tuple = ()

    @property
    def table(self):
        whole = getattr(self, "_whole", None)
        if whole is not None:
            return whole.table
        if not hasattr(self, "_table"):
            import io as _io
            import pyarrow.parquet as _pq
            self._table = _pq.read_table(_io.BytesIO(self.blob))
            self._blob_len = len(self.blob)
            self.blob = b""  # decoded form replaces the bytes — never both
        return self._table

    @property
    def output(self):
        if hasattr(self, "_output"):
            return self._output
        return [AttributeReference(f.name, f.data_type, True)
                for f in self.schema_fields]

    def narrowed(self, attrs) -> "CachedRelation":
        """This relation handing on only ``attrs``.  The copy holds no bytes
        of its own: it reads the table this one decodes, once for both."""
        names = {a.name for a in attrs}
        new = CachedRelation(b"", tuple(f for f in self.schema_fields
                                        if f.name in names))
        new._whole = getattr(self, "_whole", None) or self
        new._output = list(attrs)
        return new

    def simple_string(self):
        whole = getattr(self, "_whole", None) or self
        nbytes = len(whole.blob) or getattr(whole, "_blob_len", 0)
        return (f"CachedRelation [{', '.join(a.name for a in self.output)}] "
                f"({nbytes} parquet bytes)")


@dataclass(eq=False)
class ScanRelation(LogicalPlan):
    """File-source relation (Parquet/ORC/CSV/JSON/Avro)."""
    fmt: str = "parquet"
    paths: Tuple[str, ...] = ()
    read_schema: Optional[T.StructType] = None
    options: dict = field(default_factory=dict)
    #: a narrowed copy's positions in the file's whole schema (None: this
    #: node reads every column), and how many columns that schema has
    columns: Optional[Tuple[int, ...]] = field(default=None, init=False)
    file_width: Optional[int] = field(default=None, init=False)

    @property
    def output(self):
        if not hasattr(self, "_output"):
            if self.read_schema is None:
                from ..io_.registry import infer_schema
                self.read_schema = infer_schema(self.fmt, self.paths,
                                                self.options)
            self._output = [AttributeReference(f.name, f.data_type, f.nullable)
                            for f in self.read_schema.fields]
        return self._output

    def narrowed(self, attrs) -> "ScanRelation":
        """This scan handing on only ``attrs`` (some of ``output``, in the
        file's order): the same paths and options, a ``read_schema`` of those
        fields and, in ``columns``, their positions in the whole schema.
        ``FileScanExec`` finds them in a file by those positions, as the
        whole scan binds a file's columns (``sql/column_pruning.py``)."""
        out = self.output
        keep = [i for i, a in enumerate(out) if any(a is x for x in attrs)]
        new = ScanRelation(
            self.fmt, self.paths,
            T.StructType(tuple(self.read_schema.fields[i] for i in keep)),
            self.options)
        new._output = [out[i] for i in keep]
        new.columns = tuple(keep) if self.columns is None \
            else tuple(self.columns[i] for i in keep)
        new.file_width = self.file_width or len(out)
        return new

    def simple_string(self):
        cols = "" if self.columns is None else \
            f" [{', '.join(a.name for a in self.output)}]"
        return f"Scan {self.fmt} {list(self.paths)[:1]}{cols}"


@dataclass(eq=False)
class Range(LogicalPlan):
    start: int = 0
    end: int = 0
    step: int = 1
    num_slices: int = 1

    @property
    def output(self):
        if not hasattr(self, "_output"):
            self._output = [AttributeReference("id", T.LONG, False)]
        return self._output

    def simple_string(self):
        return f"Range ({self.start}, {self.end}, step={self.step})"


@dataclass(eq=False)
class Project(LogicalPlan):
    exprs: Tuple[Expression, ...] = ()
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        out = []
        for e in self.exprs:
            if isinstance(e, Alias):
                out.append(e.to_attribute())
            elif isinstance(e, AttributeReference):
                out.append(e)
            else:
                out.append(AttributeReference(e.sql(), e.data_type, e.nullable))
        return out

    def simple_string(self):
        return f"Project [{', '.join(e.sql() for e in self.exprs)}]"


@dataclass(eq=False)
class Filter(LogicalPlan):
    condition: Expression = None  # type: ignore
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return self.child.output

    def simple_string(self):
        return f"Filter ({self.condition.sql()})"


@dataclass(eq=False)
class Aggregate(LogicalPlan):
    grouping: Tuple[Expression, ...] = ()
    aggregates: Tuple[Expression, ...] = ()  # output exprs incl. group refs
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        out = []
        for e in self.aggregates:
            if isinstance(e, Alias):
                out.append(e.to_attribute())
            elif isinstance(e, AttributeReference):
                out.append(e)
            else:
                out.append(AttributeReference(e.sql(), e.data_type, e.nullable))
        return out

    def simple_string(self):
        g = ", ".join(e.sql() for e in self.grouping)
        a = ", ".join(e.sql() for e in self.aggregates)
        return f"Aggregate [{g}] [{a}]"


@dataclass(eq=False)
class Sort(LogicalPlan):
    orders: Tuple[SortOrder, ...] = ()
    is_global: bool = True
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return self.child.output

    def simple_string(self):
        return f"Sort [{', '.join(o.sql() for o in self.orders)}] global={self.is_global}"


@dataclass(eq=False)
class Limit(LogicalPlan):
    n: int = 0
    offset: int = 0
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return self.child.output

    def simple_string(self):
        return f"Limit {self.n}"


@dataclass(eq=False)
class Union(LogicalPlan):
    inputs: Tuple[LogicalPlan, ...] = ()

    def __post_init__(self):
        self.children = tuple(self.inputs)

    @property
    def output(self):
        first = self.children[0].output
        return [AttributeReference(a.name, a.dtype,
                                   any(c.output[i].nullable for c in self.children))
                for i, a in enumerate(first)]


@dataclass(eq=False)
class Join(LogicalPlan):
    left: LogicalPlan = None  # type: ignore
    right: LogicalPlan = None  # type: ignore
    how: str = "inner"  # inner|left|right|full|left_semi|left_anti|cross
    left_keys: Tuple[Expression, ...] = ()
    right_keys: Tuple[Expression, ...] = ()
    condition: Optional[Expression] = None  # non-equi residual
    #: the BUILD (right) side carried a broadcast hint
    #: (F.broadcast(df) / df.hint("broadcast")): the join planner skips
    #: the size threshold, like Spark's ResolveHints + JoinSelection
    broadcast_hint: bool = False
    #: the share of a star's fact rows this join's dimension is estimated
    #: to keep, where the join-order rule estimated it (``join_order.py``)
    kept_share: Optional[float] = None

    def __post_init__(self):
        self.children = (self.left, self.right)

    @property
    def output(self):
        how = self.how
        lo = list(self.left.output)
        ro = list(self.right.output)
        if how in ("left_semi", "left_anti"):
            return lo
        if how == "left":
            ro = [AttributeReference(a.name, a.dtype, True, a.expr_id) for a in ro]
        if how == "right":
            lo = [AttributeReference(a.name, a.dtype, True, a.expr_id) for a in lo]
        if how == "full":
            lo = [AttributeReference(a.name, a.dtype, True, a.expr_id) for a in lo]
            ro = [AttributeReference(a.name, a.dtype, True, a.expr_id) for a in ro]
        return lo + ro

    def simple_string(self):
        keys = ", ".join(f"{l.sql()}={r.sql()}" for l, r in
                         zip(self.left_keys, self.right_keys))
        from .join_order import share_note
        return f"Join {self.how} [{keys}]{share_note(self.kept_share)}"


@dataclass(eq=False)
class Expand(LogicalPlan):
    projections: Tuple[Tuple[Expression, ...], ...] = ()
    out_attrs: Tuple[AttributeReference, ...] = ()
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return list(self.out_attrs)


@dataclass(eq=False)
class Sample(LogicalPlan):
    lower: float = 0.0
    upper: float = 0.1
    with_replacement: bool = False
    seed: int = 0
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return self.child.output


@dataclass(eq=False)
class Repartition(LogicalPlan):
    num_partitions: int = 0
    exprs: Tuple[Expression, ...] = ()  # empty -> round robin
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return self.child.output


@dataclass(eq=False)
class Generate(LogicalPlan):
    """explode/posexplode over array columns."""
    generator: Expression = None  # type: ignore
    outer: bool = False
    gen_output: Tuple[AttributeReference, ...] = ()
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return list(self.child.output) + list(self.gen_output)


@dataclass(eq=False)
class Window(LogicalPlan):
    """Window operator: child columns plus one output column per window
    expression (Catalyst Window; reference GpuWindowExec SURVEY §2.3).
    ``window_exprs`` are Alias(WindowExpression) sharing one (partition,
    order) spec."""
    window_exprs: Tuple[Alias, ...] = ()
    partition_spec: Tuple[Expression, ...] = ()
    order_spec: Tuple[SortOrder, ...] = ()
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return list(self.child.output) + [
            a.to_attribute() for a in self.window_exprs]

    def simple_string(self):
        return (f"Window [{', '.join(a.child.sql() for a in self.window_exprs)}]")


@dataclass(eq=False)
class MapInPandas(LogicalPlan):
    """mapInPandas: user fn over an iterator of pandas DataFrames
    (reference GpuMapInPandasExec, SURVEY §2.9 Python execs)."""
    func: object = None
    out_schema: "T.StructType" = None  # type: ignore
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return [AttributeReference(f.name, f.data_type, True)
                for f in self.out_schema.fields]


@dataclass(eq=False)
class FlatMapGroupsInPandas(LogicalPlan):
    """groupBy(...).applyInPandas (reference GpuFlatMapGroupsInPandasExec)."""
    grouping: Tuple[Expression, ...] = ()
    func: object = None
    out_schema: "T.StructType" = None  # type: ignore
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        return [AttributeReference(f.name, f.data_type, True)
                for f in self.out_schema.fields]


@dataclass(eq=False)
class AggregateInPandas(LogicalPlan):
    """groupBy(...).agg(grouped-agg pandas UDFs) — one scalar per UDF per
    key group (reference GpuAggregateInPandasExec)."""
    grouping: Tuple[Expression, ...] = ()
    # (output name, GroupedAggPandasUDF) in output order after the keys
    agg_udfs: Tuple = ()
    child: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.child,)

    @property
    def output(self):
        keys = [AttributeReference(getattr(g, "name", g.sql()),
                                   g.data_type, True)
                for g in self.grouping]
        aggs = [AttributeReference(name, u.return_type, True)
                for name, u in self.agg_udfs]
        return keys + aggs


@dataclass(eq=False)
class FlatMapCoGroupsInPandas(LogicalPlan):
    """a.groupBy(k).cogroup(b.groupBy(k)).applyInPandas (reference
    GpuFlatMapCoGroupsInPandasExec)."""
    left_grouping: Tuple[Expression, ...] = ()
    right_grouping: Tuple[Expression, ...] = ()
    func: object = None
    out_schema: "T.StructType" = None  # type: ignore
    left: LogicalPlan = None  # type: ignore
    right: LogicalPlan = None  # type: ignore

    def __post_init__(self):
        self.children = (self.left, self.right)

    @property
    def output(self):
        return [AttributeReference(f.name, f.data_type, True)
                for f in self.out_schema.fields]
