"""TpuOverrides — the plan-rewrite/placement engine, the analog of the
reference's ``GpuOverrides``/``RapidsMeta`` (SURVEY §2.2, §3.2).

Every logical node and expression is wrapped in a Meta carrying tag state
("will not work on TPU because ...").  Tagging consults the expression
registry, per-op TypeSigs, and config kill-switches; the planner then places
each operator on the device or the host engine accordingly, and explain()
reports placements exactly like ``spark.rapids.sql.explain=ALL``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Type

from .. import types as T
from ..config import RapidsConf
from . import plan as P
from . import typesig as TS
from .expressions import aggregates as AGG
from .expressions.cast import Cast
from .expressions.core import (Alias, AttributeReference, BoundReference,
                               Expression, Literal)
from .expressions.registry import EXPRESSION_REGISTRY

# ---------------------------------------------------------------------------
# per-expression input/output type matrices (TypeChecks.scala analog).
# Family defaults keyed by the defining module; EXPR_SIGS carries the
# resolved per-class entry (specific overrides win).  Anything absent
# defaults to ALL_DEVICE for both sides.  Tagging, explain() reasons,
# docs/supported_ops.md and tools/generated_files/supportedExprs.csv all
# read THIS data — the point is that type decisions live in a table, not
# in ad-hoc code.
# ---------------------------------------------------------------------------

_STR_ARR = TS.TypeSig((T.ArrayType,), nested=TS.STRING + TS.NULL)
_MATH_SIG = TS.ExprSig(TS.NUMERIC + TS.NULL)
_STRINGS_SIG = TS.ExprSig(
    # FormatNumber/Conv take numerics; ConcatWs takes array<string>
    TS.BASIC + _STR_ARR,
    TS.STRING + TS.INTEGRAL + TS.BOOLEAN + TS.NULL)
_REGEXP_SIG = TS.ExprSig(
    TS.STRING + TS.INTEGRAL + TS.NULL,
    TS.STRING + TS.BOOLEAN + TS.NULL + _STR_ARR
    + TS.TypeSig((T.MapType,), nested=TS.STRING + TS.NULL))
_DATETIME_SIG = TS.ExprSig(TS.BASIC, TS.BASIC)
_HASH_SIG = TS.ExprSig(TS.BASIC + TS.STRUCT, TS.INTEGRAL)

_FAMILY_SIGS: Dict[str, TS.ExprSig] = {
    "math_fns": _MATH_SIG,
    "strings": _STRINGS_SIG,
    "regexp": _REGEXP_SIG,
    "datetime": _DATETIME_SIG,
    "hashing": _HASH_SIG,
}

_SPECIFIC_SIGS: Dict[str, TS.ExprSig] = {
    # predicates: maps are not comparable in Spark at all; output boolean
    **{n: TS.ExprSig(TS.BASIC + TS.STRUCT
                     + TS.TypeSig((T.ArrayType,), nested=TS.BASIC),
                     TS.BOOLEAN + TS.NULL)
       for n in ("EqualTo", "EqualNullSafe", "LessThan", "LessThanOrEqual",
                 "GreaterThan", "GreaterThanOrEqual", "In", "InSet")},
    "And": TS.ExprSig(TS.BOOLEAN + TS.NULL),
    "Or": TS.ExprSig(TS.BOOLEAN + TS.NULL),
    "Not": TS.ExprSig(TS.BOOLEAN + TS.NULL),
    "IsNaN": TS.ExprSig(TS.FP + TS.NULL, TS.BOOLEAN),
    # arithmetic: numeric except the orderable n-ary pickers
    **{n: TS.ExprSig(TS.NUMERIC + TS.NULL)
       for n in ("Add", "Subtract", "Multiply", "Divide", "Remainder",
                 "Pmod", "IntegralDivide", "Abs", "UnaryMinus",
                 "UnaryPositive")},
    **{n: TS.ExprSig(TS.INTEGRAL + TS.BOOLEAN + TS.NULL)
       for n in ("BitwiseAnd", "BitwiseOr", "BitwiseXor", "BitwiseNot",
                 "ShiftLeft", "ShiftRight", "ShiftRightUnsigned")},
    "Greatest": TS.ExprSig(TS.ORDERABLE),
    "Least": TS.ExprSig(TS.ORDERABLE),
    # aggregates (function inputs; outputs per Spark result types)
    "Sum": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.NUMERIC),
    "Average": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.FP + TS.DECIMAL),
    "StddevPop": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.FP),
    "StddevSamp": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.FP),
    "VariancePop": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.FP),
    "VarianceSamp": TS.ExprSig(TS.NUMERIC + TS.NULL, TS.FP),
    "Min": TS.ExprSig(TS.ORDERABLE),
    "Max": TS.ExprSig(TS.ORDERABLE),
    "ApproximatePercentile": TS.ExprSig(
        TS.NUMERIC + TS.NULL,
        TS.NUMERIC + TS.TypeSig((T.ArrayType,), nested=TS.NUMERIC)),
    # flat/string values only: evaluate() interleaves value buffers into
    # an array column, which nested/binary children cannot ride
    "PivotFirst": TS.ExprSig(
        TS.BASIC,
        TS.TypeSig((T.ArrayType,), nested=TS.BASIC)),
}


def _resolve_expr_sigs() -> Dict[str, TS.ExprSig]:
    out: Dict[str, TS.ExprSig] = {}
    for name, cls in EXPRESSION_REGISTRY.items():
        fam = _FAMILY_SIGS.get(cls.__module__.rsplit(".", 1)[-1])
        if fam is not None:
            out[name] = fam
    out.update(_SPECIFIC_SIGS)
    return out


EXPR_SIGS: Dict[str, TS.ExprSig] = _resolve_expr_sigs()

# expressions that are registered but must run on the host in some forms
_HOST_ONLY_EXPRS = {"RaiseError"}

#: registry names whose tagging path never consults a per-rule enable
#: flag: structural pass-throughs (the isinstance fast path in
#: ExprMeta.tag) and the AggregateExpression wrapper (its FUNCTION's
#: flag is honored).  docgen imports this so the documented flag list
#: stays in lockstep with what tagging consults.
UNFLAGGED_EXPRS = {"Alias", "AttributeReference", "BoundReference",
                   "Literal", "AggregateExpression"} | _HOST_ONLY_EXPRS

# config kill-switches per exec family (subset of the reference's
# spark.rapids.sql.exec.* flags)
#: per-exec enable flags keyed by logical node, named after the Spark
#: exec class the reference's rule covers (GpuOverrides auto-generates
#: one ``spark.rapids.sql.exec.*`` conf per exec rule)
_EXEC_ENABLE_KEYS = {
    "Project": "spark.rapids.sql.exec.ProjectExec",
    "Filter": "spark.rapids.sql.exec.FilterExec",
    "Aggregate": "spark.rapids.sql.exec.HashAggregateExec",
    "Sort": "spark.rapids.sql.exec.SortExec",
    "Join": "spark.rapids.sql.exec.ShuffledHashJoinExec",
    "Range": "spark.rapids.sql.exec.RangeExec",
    "Union": "spark.rapids.sql.exec.UnionExec",
    "Expand": "spark.rapids.sql.exec.ExpandExec",
    "Sample": "spark.rapids.sql.exec.SampleExec",
    "Limit": "spark.rapids.sql.exec.GlobalLimitExec",
    "Window": "spark.rapids.sql.exec.WindowExec",
    "Generate": "spark.rapids.sql.exec.GenerateExec",
    "Repartition": "spark.rapids.sql.exec.ShuffleExchangeExec",
    "ScanRelation": "spark.rapids.sql.exec.FileSourceScanExec",
    "MapInPandas": "spark.rapids.sql.exec.MapInPandasExec",
    "FlatMapGroupsInPandas": "spark.rapids.sql.exec.FlatMapGroupsInPandasExec",
    "FlatMapCoGroupsInPandas":
        "spark.rapids.sql.exec.FlatMapCoGroupsInPandasExec",
    "AggregateInPandas": "spark.rapids.sql.exec.AggregateInPandasExec",
}

_SUPPORTED_AGGS = (AGG.Sum, AGG.Count, AGG.Min, AGG.Max, AGG.Average,
                   AGG.First, AGG.Last, AGG.StddevPop, AGG.StddevSamp,
                   AGG.VariancePop, AGG.VarianceSamp, AGG.CollectList,
                   AGG.CollectSet, AGG.ApproximatePercentile,
                   AGG.PivotFirst)


class ExprMeta:
    def __init__(self, expr: Expression, conf: RapidsConf):
        self.expr = expr
        self.conf = conf
        self.reasons: List[str] = []
        self.children = [ExprMeta(c, conf) for c in expr.children]

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    def tag(self):
        e = self.expr
        cls_name = type(e).__name__
        if isinstance(e, (AttributeReference, BoundReference, Literal, Alias)):
            pass
        elif isinstance(e, AGG.AggregateExpression):
            fname = type(e.func).__name__
            if not isinstance(e.func, _SUPPORTED_AGGS):
                self.will_not_work(
                    f"aggregate {fname} is not supported on TPU")
            elif not self.conf.get_bool(
                    f"spark.rapids.sql.expression.{fname}", True):
                self.will_not_work(
                    f"aggregate {fname} disabled by "
                    f"spark.rapids.sql.expression.{fname}")
            elif hasattr(e.func, "tag_for_device"):
                reason = e.func.tag_for_device(self.conf)
                if reason:
                    self.will_not_work(
                        f"{type(e.func).__name__}: {reason}")
            # DISTINCT support is a PLAN-shape property: the planner's
            # dedup-then-aggregate rewrite handles the uniform shape and
            # raises (never silently de-DISTINCTs) on the rest
        elif isinstance(e, AGG.AggregateFunction):
            if not isinstance(e, _SUPPORTED_AGGS):
                self.will_not_work(
                    f"aggregate {cls_name} is not supported on TPU")
            elif not self.conf.get_bool(
                    f"spark.rapids.sql.expression.{cls_name}", True):
                self.will_not_work(
                    f"aggregate {cls_name} disabled by "
                    f"spark.rapids.sql.expression.{cls_name}")
            elif hasattr(e, "tag_for_device"):
                reason = e.tag_for_device(self.conf)
                if reason:
                    self.will_not_work(f"{cls_name}: {reason}")
        elif cls_name not in EXPRESSION_REGISTRY:
            self.will_not_work(f"expression {cls_name} is not supported on TPU")
        elif cls_name in _HOST_ONLY_EXPRS:
            self.will_not_work(f"expression {cls_name} runs on the host only")
        elif not self.conf.get_bool(
                f"spark.rapids.sql.expression.{cls_name}", True):
            # per-expression enable flag (reference: one auto-generated
            # conf per expr rule, honored by GpuOverrides tagging)
            self.will_not_work(
                f"expression {cls_name} disabled by "
                f"spark.rapids.sql.expression.{cls_name}")
        elif hasattr(e, "tag_for_device"):
            # per-expression device-capability hook (literal-only args,
            # ASCII-only patterns, timezone checks, host-exact long-tail
            # ops, ...); uniform signature tag_for_device(conf)
            reason = e.tag_for_device(self.conf)
            if reason:
                self.will_not_work(f"{cls_name}: {reason}")
        # type checks: the node's result against its OUTPUT sig, the
        # children against its INPUT sig (per-matrix data, EXPR_SIGS)
        es = EXPR_SIGS.get(cls_name, TS.DEFAULT_EXPR_SIG)
        for node, s, side in [(e, es.output, "produces")] + [
                (c, es.input, "input") for c in e.children]:
            try:
                dt = node.data_type
            except NotImplementedError:
                continue
            r = s.supports(dt)
            if r:
                self.will_not_work(f"{cls_name} {side}: {r}")
                break
        if isinstance(e, Cast):
            from .expressions.cast import device_string_cast_supported
            ft = e.children[0].data_type
            if isinstance(ft, T.StringType) or isinstance(e.to, T.StringType):
                string_string = isinstance(ft, T.StringType) and isinstance(
                    e.to, T.StringType)
                if not string_string and not device_string_cast_supported(
                        ft, e.to):
                    self.will_not_work(
                        f"cast {ft.simple_string()} -> "
                        f"{e.to.simple_string()} runs on the host "
                        "(outside the device CastStrings-analog matrix)")
                elif isinstance(e.to, T.TimestampType) or isinstance(
                        ft, T.TimestampType):
                    # zoneless strings parse in the SESSION timezone;
                    # the device kernel is UTC-only (same gate as the
                    # timezone-aware datetime ops)
                    from .expressions.datetime import _tz_reason
                    from ..config import SESSION_TIMEZONE
                    reason = _tz_reason(self.conf.get(SESSION_TIMEZONE))
                    if reason:
                        self.will_not_work(f"cast: {reason}")
        for c in self.children:
            c.tag()

    def all_reasons(self) -> List[str]:
        out = list(self.reasons)
        for c in self.children:
            out.extend(c.all_reasons())
        return out


class PlanMeta:
    def __init__(self, node: P.LogicalPlan, conf: RapidsConf):
        self.node = node
        self.conf = conf
        self.reasons: List[str] = []
        self.children = [PlanMeta(c, conf) for c in node.children]
        self.backend = "tpu"

    def will_not_work(self, reason: str):
        self.reasons.append(reason)

    def _expressions(self) -> List[Expression]:
        n = self.node
        if isinstance(n, P.Project):
            return list(n.exprs)
        if isinstance(n, P.Filter):
            return [n.condition]
        if isinstance(n, P.Aggregate):
            return list(n.grouping) + list(n.aggregates)
        if isinstance(n, P.Sort):
            return [o.child for o in n.orders]
        if isinstance(n, P.Join):
            out = list(n.left_keys) + list(n.right_keys)
            if n.condition is not None:
                out.append(n.condition)
            return out
        if isinstance(n, P.Expand):
            return [e for proj in n.projections for e in proj]
        if isinstance(n, P.Generate):
            return [n.generator]
        if isinstance(n, P.Window):
            out = list(n.partition_spec) + [o.child for o in n.order_spec]
            for a in n.window_exprs:
                out.extend(a.child.function.children)
            return out
        return []

    def tag(self):
        if not self.conf.is_sql_enabled:
            self.will_not_work("spark.rapids.sql.enabled is false")
        key = _EXEC_ENABLE_KEYS.get(type(self.node).__name__)
        if key and not self.conf.get_bool(key, True):
            self.will_not_work(f"{key} is disabled")
        # output AND input schema types must have a device layout (the
        # reference's ExecChecks covers input attributes the same way)
        for a in self.node.output:
            r = TS.ALL_DEVICE.supports(a.dtype)
            if r:
                self.will_not_work(f"output column '{a.name}': {r}")
                break
        for child in self.node.children:
            for a in child.output:
                r = TS.ALL_DEVICE.supports(a.dtype)
                if r:
                    self.will_not_work(f"input column '{a.name}': {r}")
                    break
        if isinstance(self.node, P.Window):
            self._tag_window()
        for e in self._expressions():
            em = ExprMeta(e, self.conf)
            em.tag()
            for reason in em.all_reasons():
                self.will_not_work(reason)
        for c in self.children:
            c.tag()
        self.backend = "cpu" if self.reasons else "tpu"

    def _tag_window(self):
        """Window capability checks (reference GpuWindowExpression tagging
        in GpuOverrides: supported functions, frames, types)."""
        from .expressions import windows as WX
        n = self.node
        supported = (WX.RankLike, WX.Lead, WX.Lag, WX.NthValue, AGG.Sum,
                     AGG.Count, AGG.Min, AGG.Max, AGG.Average, AGG.First,
                     AGG.Last)
        for a in n.window_exprs:
            fn = a.child.function
            if not isinstance(fn, supported):
                self.will_not_work(
                    f"window function {type(fn).__name__} is not supported")
                continue
            if isinstance(fn, (AGG.Sum, AGG.Average, AGG.Min, AGG.Max)):
                dt = fn.children[0].data_type
                if not (T.is_numeric(dt) and not isinstance(dt, T.DecimalType)):
                    self.will_not_work(
                        f"window {type(fn).__name__} over "
                        f"{dt.simple_string()} is not supported on the device")
            frame = a.child.spec.effective_frame(fn)
            if frame.frame_type == "range" and (
                    frame.lower not in (WX.UNBOUNDED_PRECEDING, WX.CURRENT_ROW)
                    or frame.upper not in (WX.UNBOUNDED_FOLLOWING,
                                           WX.CURRENT_ROW)):
                if len(n.order_spec) != 1:
                    self.will_not_work(
                        "RANGE frame with offsets needs exactly one "
                        "order column")
                else:
                    odt = n.order_spec[0].child.data_type
                    if not (T.is_numeric(odt)
                            and not isinstance(odt, T.DecimalType)):
                        self.will_not_work(
                            "RANGE frame offsets need a numeric order "
                            f"column, got {odt.simple_string()}")

    def explain(self, all_ops: bool = False, level: int = 0) -> str:
        mark = "*" if self.backend == "tpu" else "!"
        pad = "  " * level
        lines = []
        if all_ops or self.backend != "tpu":
            lines.append(f"{pad}{mark}{type(self.node).__name__} "
                         f"{'will run on TPU' if self.backend == 'tpu' else 'cannot run on TPU because ' + '; '.join(dict.fromkeys(self.reasons))}")
        for c in self.children:
            sub = c.explain(all_ops, level + 1)
            if sub:
                lines.append(sub)
        return "\n".join([l for l in lines if l])


class TpuOverrides:
    """Entry point: wrap + tag a logical plan, yielding placement info the
    planner consumes (GpuOverrides.apply analog)."""

    @staticmethod
    def apply(plan: P.LogicalPlan, conf: Optional[RapidsConf] = None) -> PlanMeta:
        conf = conf or RapidsConf.get_global()
        meta = PlanMeta(plan, conf)
        meta.tag()
        return meta


def explain_potential_plan(df, all_ops: bool = True) -> str:
    """Public explain API (reference ``ExplainPlan.explainPotentialGpuPlan``)."""
    from .column_pruning import prune_columns
    from .join_order import order_joins
    meta = TpuOverrides.apply(prune_columns(order_joins(df._plan)[0]),
                              df._session.conf)
    return meta.explain(all_ops)
