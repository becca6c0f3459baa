"""SQL front-end: text -> logical plans over the existing algebra.

The reference accelerates Spark SQL transparently — every query surface
(``spark.sql(...)``, ``df.filter("a > 1")``, ``selectExpr``) is SQL text
compiled by Catalyst before the plugin ever sees a physical plan
(SURVEY §1 user-visible API; ``Plugin.scala:46-53`` hooks run *after* SQL
parsing).  Standalone, we own that parsing step too: this module is the
Catalyst-parser equivalent, a recursive-descent SQL parser producing the
same ``Column``/``LogicalPlan`` objects the DataFrame API builds, so SQL
text and DataFrame calls share one planning/execution path.

Scope: SELECT [DISTINCT] with expressions/functions/CASE/CAST/window
functions, FROM with joins (INNER/LEFT/RIGHT/FULL/SEMI/ANTI/CROSS, ON and
USING), WHERE, GROUP BY (exprs/ordinals/aliases), HAVING, ORDER BY
(exprs/ordinals/aliases, ASC/DESC, NULLS FIRST/LAST), LIMIT/OFFSET,
UNION [ALL]/EXCEPT/INTERSECT, WITH ctes, subqueries in FROM, temp views,
and direct file relations (``parquet.`/path/to/file```).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from .. import types as T
from .expressions.aggregates import AggregateExpression, AggregateFunction
from .expressions.core import Alias, AttributeReference, Expression, Literal
from .expressions.windows import (CURRENT_ROW, UNBOUNDED_FOLLOWING,
                                  UNBOUNDED_PRECEDING, WindowFrame,
                                  WindowSpecDefinition, WindowExpression,
                                  WindowFunction)
from .plan import SortOrder


# --------------------------------------------------------------------------
# Lexer
# --------------------------------------------------------------------------

_TOKEN_RE = re.compile(r"""
    (?P<ws>\s+|--[^\n]*)
  | (?P<num>(?:\d+\.\d*|\.\d+|\d+)(?:[eE][+-]?\d+)?[dDlLfF]?)
  | (?P<str>'(?:[^'\\]|\\[\s\S]|'')*')
  | (?P<qident>`[^`]*`|"[^"]*")
  | (?P<ident>[A-Za-z_][A-Za-z_0-9]*)
  | (?P<op><=>|==|!=|<>|<=|>=|\|\||<<|>>>|>>|[-+*/%(),.<>=&|^~])
""", re.VERBOSE)


@dataclass
class Tok:
    kind: str   # num|str|ident|qident|op|eof
    text: str
    pos: int

    @property
    def upper(self) -> str:
        return self.text.upper()


def tokenize(sql: str) -> List[Tok]:
    out: List[Tok] = []
    i = 0
    while i < len(sql):
        m = _TOKEN_RE.match(sql, i)
        if m is None:
            raise SqlParseError(f"unexpected character {sql[i]!r} at {i} in {sql!r}")
        i = m.end()
        kind = m.lastgroup
        if kind == "ws":
            continue
        out.append(Tok(kind, m.group(), m.start()))
    out.append(Tok("eof", "", len(sql)))
    return out


class SqlParseError(ValueError):
    pass


import itertools as _it

#: distinct seeds for non-REPEATABLE TABLESAMPLEs
_SAMPLE_SEEDS = _it.count(0x5EED)


def unescape_sql_string(body: str) -> str:
    """Spark's default string-literal semantics (``unescapeSQLString``,
    ``spark.sql.parser.escapedStringLiterals=false``): backslash escapes
    are processed ('\\\\d' is a 2-char regex escape, '\\n' a newline),
    '' is a quote, \\% and \\_ KEEP their backslash (LIKE escapes), an
    unknown escaped char is the char itself, plus \\uXXXX and 3-digit
    octal forms."""
    out = []
    i = 0
    n = len(body)
    mapped = {"0": "\0", "b": "\b", "n": "\n", "r": "\r", "t": "\t",
              "Z": "\x1a", "\\": "\\", "'": "'", '"': '"'}
    while i < n:
        c = body[i]
        if c == "'" and i + 1 < n and body[i + 1] == "'":
            out.append("'")
            i += 2
            continue
        if c == "\\" and i + 1 < n:
            nx = body[i + 1]
            # 3-digit octal BEFORE the single-char map: '\012' is a
            # newline, not NUL + "12" (Spark checks octal first too)
            oct3 = body[i + 1:i + 4]
            if (len(oct3) == 3 and nx in "0123"
                    and all(ch in "01234567" for ch in oct3)):
                out.append(chr(int(oct3, 8)))
                i += 4
                continue
            if nx in mapped:
                out.append(mapped[nx])
                i += 2
                continue
            if nx in "%_":
                out.append("\\" + nx)
                i += 2
                continue
            hex4 = body[i + 2:i + 6]
            if (nx == "u" and len(hex4) == 4
                    and all(ch in "0123456789abcdefABCDEF"
                            for ch in hex4)):
                out.append(chr(int(hex4, 16)))
                i += 6
                continue
            out.append(nx)
            i += 2
            continue
        out.append(c)
        i += 1
    return "".join(out)


# --------------------------------------------------------------------------
# AST for statements (expressions become live Expression trees immediately)
# --------------------------------------------------------------------------

@dataclass
class Star:
    qualifier: Optional[str] = None


@dataclass
class SelectItem:
    expr: Any           # Expression | Star
    alias: Optional[str] = None


@dataclass
class TableRef:
    name: str                       # view/table name, or format for files
    alias: Optional[str] = None
    path: Optional[str] = None      # direct file relation
    sample: Optional[tuple] = None  # ("percent"|"rows", value, seed)


@dataclass
class SubqueryRef:
    stmt: "Any"
    alias: Optional[str] = None
    sample: Optional[tuple] = None  # ("percent"|"rows", value, seed)


@dataclass
class JoinStep:
    how: str
    right: Any                      # TableRef | SubqueryRef
    on: Optional[Expression] = None
    using: Optional[List[str]] = None


@dataclass
class OrderItem:
    expr: Any                       # Expression | int (ordinal)
    ascending: bool = True
    nulls_first: Optional[bool] = None


@dataclass
class LateralView:
    outer: bool
    func: str
    arg: "Any"
    table_alias: str
    col_aliases: List[str]


@dataclass
class SelectStmt:
    items: List[SelectItem] = field(default_factory=list)
    distinct: bool = False
    from_: Optional[Any] = None     # TableRef | SubqueryRef
    joins: List[JoinStep] = field(default_factory=list)
    where: Optional[Expression] = None
    group_by: List[Any] = field(default_factory=list)   # Expression | int
    group_by_mode: Optional[str] = None           # None|rollup|cube|sets
    grouping_sets_raw: List[List[Any]] = field(default_factory=list)
    having: Optional[Expression] = None
    order_by: List[OrderItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: Optional[int] = None
    ctes: Dict[str, "Any"] = field(default_factory=dict)
    #: Hive-style LATERAL VIEW [OUTER] explode(...) alias AS cols —
    #: applied after the FROM/JOIN chain (the common placement)
    lateral_views: List[LateralView] = field(default_factory=list)


@dataclass
class SetOpStmt:
    op: str                         # union|except|intersect
    all: bool
    left: Any
    right: Any
    order_by: List[OrderItem] = field(default_factory=list)
    limit: Optional[int] = None
    offset: Optional[int] = None
    ctes: Dict[str, "Any"] = field(default_factory=dict)


class ExistsSubquery(Expression):
    """WHERE EXISTS (SELECT ...) marker — rewritten by the builder into a
    LEFT SEMI join (NOT EXISTS -> LEFT ANTI), the same lowering Spark's
    RewritePredicateSubquery performs before the reference plugin sees the
    plan (semi/anti joins then run on GpuHashJoin)."""

    children: Tuple[Expression, ...] = ()
    _unresolved = True  # must never reach resolution/execution

    def __init__(self, stmt):
        self.stmt = stmt

    @property
    def data_type(self):
        return T.BOOLEAN

    def sql(self) -> str:
        return "EXISTS(<subquery>)"

    def with_children(self, children):
        return self

    def _key_extras(self):
        return (id(self.stmt),)


class _InnerUnit(Expression):
    """Placeholder for a maximal inner-only subexpression lifted out of a
    mixed correlated EXISTS conjunct (projected as __nq{idx} from the
    subquery and substituted back into the join's residual condition)."""

    children: Tuple[Expression, ...] = ()
    _unresolved = True

    def __init__(self, idx: int):
        self.idx = idx

    @property
    def data_type(self):
        raise TypeError("_InnerUnit must be substituted before typing")

    def sql(self) -> str:
        return f"<inner:{self.idx}>"

    def with_children(self, children):
        return self

    def _key_extras(self):
        return (self.idx,)


class InSubquery(Expression):
    """``expr IN (SELECT ...)`` marker — LEFT SEMI join on equality;
    NOT IN is the null-aware LEFT ANTI form (SQL 3-valued logic: a null
    needle or any null in the subquery result filters the row)."""

    _unresolved = True

    def __init__(self, needle: Expression, stmt):
        self.children = (needle,)
        self.stmt = stmt

    @property
    def data_type(self):
        return T.BOOLEAN

    def sql(self) -> str:
        return f"{self.children[0].sql()} IN (<subquery>)"

    def with_children(self, children):
        return InSubquery(children[0], self.stmt)

    def _key_extras(self):
        return (id(self.stmt),)


class ScalarSubquery(Expression):
    """Uncorrelated ``(SELECT <one value>)`` in an expression position —
    evaluated once at plan-build time into a Literal (the subquery result
    is a single value by definition; Spark's ReuseSubquery evaluates it
    once per query too, just lazily)."""

    children: Tuple[Expression, ...] = ()
    _unresolved = True

    def __init__(self, stmt):
        self.stmt = stmt

    @property
    def data_type(self):
        raise SqlParseError(
            "scalar subquery leaked past build-time evaluation")

    def sql(self) -> str:
        return "(<scalar subquery>)"

    def with_children(self, children):
        return self

    def _key_extras(self):
        return (id(self.stmt),)


class IntervalLiteral(Expression):
    """Parse-time ``INTERVAL 'n' unit`` value — only valid next to +/-
    with a date/timestamp, where _additive folds it into DateAddInterval/
    TimeAdd (the reference's GpuDateAddInterval/GpuTimeAdd literal
    restriction)."""

    children: Tuple[Expression, ...] = ()
    _unresolved = True

    def __init__(self, months: int, days: int, micros: int):
        self.months, self.days, self.micros = months, days, micros

    @property
    def data_type(self):
        raise SqlParseError(
            "INTERVAL literals are only valid in date/timestamp +/- "
            "arithmetic")

    def sql(self) -> str:
        return f"INTERVAL({self.months}mo {self.days}d {self.micros}us)"

    def with_children(self, children):
        return self

    def _key_extras(self):
        return (self.months, self.days, self.micros)


_INTERVAL_UNITS = {
    "year": (12, 0, 0), "years": (12, 0, 0),
    "month": (1, 0, 0), "months": (1, 0, 0),
    "week": (0, 7, 0), "weeks": (0, 7, 0),
    "day": (0, 1, 0), "days": (0, 1, 0),
    "hour": (0, 0, 3_600_000_000), "hours": (0, 0, 3_600_000_000),
    "minute": (0, 0, 60_000_000), "minutes": (0, 0, 60_000_000),
    "second": (0, 0, 1_000_000), "seconds": (0, 0, 1_000_000),
}


class UnresolvedQualified(Expression):
    """``t.a`` — bound to the aliased relation's attribute by the builder.
    Never reaches execution; data_type raises to catch leaks.  Marked
    ``_unresolved`` so the analyzer-lite coercion defers until binding
    (outside session.sql, ``_resolve_expr`` falls back to by-name
    resolution, pyspark ``expr("t.a")`` style)."""

    children: Tuple[Expression, ...] = ()
    _unresolved = True

    def __init__(self, qualifier: str, name: str):
        self.qualifier = qualifier
        self.name = name

    @property
    def data_type(self):
        raise SqlParseError(
            f"unresolved qualified reference {self.qualifier}.{self.name} "
            "(qualified names are only valid inside session.sql queries)")

    def sql(self) -> str:
        return f"{self.qualifier}.{self.name}"

    def with_children(self, children):
        return self

    def _key_extras(self):
        return (self.qualifier, self.name)


# --------------------------------------------------------------------------
# Function registry: SQL name -> callable over Columns
# --------------------------------------------------------------------------

#: public helpers in functions.py that are NOT SQL functions (constructors,
#: decorators, sort helpers) — calling them with SQL args would crash with
#: confusing internal errors instead of "unknown SQL function"
_NON_SQL_FUNCTIONS = {
    "col", "column", "lit", "expr", "expr_fn", "when", "udf", "pandas_udf",
    "device_udf", "broadcast", "asc", "desc", "window",
}


def _function_table():
    from . import functions as F
    tbl: Dict[str, Any] = {}
    for name in dir(F):
        if name.startswith("_") or name in _NON_SQL_FUNCTIONS:
            continue
        fn = getattr(F, name)
        # only functions DEFINED in functions.py — dir() also surfaces its
        # imports (e.g. typing.Optional), which are not SQL functions
        if callable(fn) and not isinstance(fn, type) and \
                getattr(fn, "__module__", None) == F.__name__:
            tbl[name.lower()] = fn
    # SQL spellings that differ from the pyspark function names
    alias = {
        "power": "pow", "ceiling": "ceil", "ln": "log", "ucase": "upper",
        "lcase": "lower", "char_length": "length",
        "character_length": "length", "sign": "signum",
        "day": "dayofmonth", "position": "locate", "ifnull": "nvl",
        "regexp_like": "rlike", "std": "stddev",
        "approx_percentile": "percentile_approx",
        "array_agg": "collect_list",
    }
    for sql_name, py_name in alias.items():
        fn = tbl.get(py_name.lower())
        if fn is not None:
            tbl[sql_name] = fn
    return tbl


#: argument positions that are plain python values in the pyspark function
#: signatures (format strings, pad chars, counts...) — a parsed Literal in
#: one of these positions is unwrapped to its raw value before the call.
_LITERAL_POS: Dict[str, set] = {
    "substring_index": {1, 2}, "instr": {1}, "translate": {1, 2},
    "repeat": {1}, "lpad": {1, 2}, "rpad": {1, 2}, "trim": {1},
    "ltrim": {1}, "rtrim": {1}, "format_number": {1}, "conv": {1, 2},
    "round": {1}, "bround": {1}, "shiftleft": {1}, "shiftright": {1},
    "shiftrightunsigned": {1}, "rlike": {1}, "regexp_like": {1},
    "regexp_replace": {1, 2}, "regexp_extract": {1, 2},
    "regexp_extract_all": {1, 2}, "split": {1, 2}, "str_to_map": {1, 2},
    "get_json_object": {1}, "json_tuple": {1, 2, 3, 4, 5, 6, 7, 8},
    "date_format": {1}, "trunc": {1}, "from_unixtime": {1},
    "unix_timestamp": {1}, "to_unix_timestamp": {1}, "to_timestamp": {1},
    "months_between": {2}, "from_utc_timestamp": {1}, "lead": {1, 2},
    "lag": {1, 2}, "nth_value": {1, 2}, "ntile": {0}, "first": {1},
    "last": {1}, "sort_array": {1}, "like": {1, 2},
    "locate": {0, 2}, "position": {0, 2}, "concat_ws": {0},
    "slice": {1, 2}, "percentile_approx": {1, 2},
    "approx_count_distinct": {1},
}


_FN_TABLE = None


def _functions():
    global _FN_TABLE
    if _FN_TABLE is None:
        _FN_TABLE = _function_table()
    return _FN_TABLE


def _parse_type_tokens(p: "Parser") -> T.DataType:
    name = p.expect_ident().lower()
    if name in ("decimal", "dec", "numeric"):
        prec, scale = 10, 0
        if p.accept_op("("):
            prec = p.expect_int()
            if p.accept_op(","):
                scale = p.expect_int()
            p.expect_op(")")
        return T.DecimalType(prec, scale)
    from .dataframe import _parse_type
    return _parse_type(name)


# --------------------------------------------------------------------------
# Parser
# --------------------------------------------------------------------------

_RESERVED_STOP = {
    "FROM", "WHERE", "GROUP", "HAVING", "ORDER", "LIMIT", "OFFSET", "UNION",
    "EXCEPT", "INTERSECT", "MINUS", "JOIN", "INNER", "LEFT", "RIGHT", "FULL",
    "CROSS", "ON", "USING", "AS", "WHEN", "THEN", "ELSE", "END", "AND", "OR",
    "NOT", "IS", "IN", "BETWEEN", "LIKE", "RLIKE", "ASC", "DESC", "NULLS",
    "BY", "SELECT", "DISTINCT", "ALL", "WITH", "OVER", "PARTITION", "ROWS",
    "RANGE", "PRECEDING", "FOLLOWING", "CURRENT", "UNBOUNDED", "SEMI", "ANTI",
    "LATERAL", "TABLESAMPLE",
}


class Parser:
    def __init__(self, sql: str, udfs: Optional[Dict[str, Any]] = None):
        self.sql = sql
        self.toks = tokenize(sql)
        self.i = 0
        #: session-registered Hive UDFs (name -> impl); consulted before
        #: the builtin function table in _call
        self.udfs = udfs or {}

    # --- token helpers ----------------------------------------------------
    def peek(self, ahead: int = 0) -> Tok:
        return self.toks[min(self.i + ahead, len(self.toks) - 1)]

    def next(self) -> Tok:
        t = self.toks[self.i]
        if t.kind != "eof":
            self.i += 1
        return t

    def at_kw(self, *kws: str) -> bool:
        t = self.peek()
        return t.kind == "ident" and t.upper in kws

    def accept_kw(self, *kws: str) -> bool:
        if self.at_kw(*kws):
            self.next()
            return True
        return False

    def expect_kw(self, kw: str) -> None:
        if not self.accept_kw(kw):
            raise SqlParseError(
                f"expected {kw} at {self.peek().pos} in {self.sql!r}, "
                f"got {self.peek().text!r}")

    def at_op(self, *ops: str) -> bool:
        t = self.peek()
        return t.kind == "op" and t.text in ops

    def accept_op(self, *ops: str) -> bool:
        if self.at_op(*ops):
            self.next()
            return True
        return False

    def expect_op(self, op: str) -> None:
        if not self.accept_op(op):
            raise SqlParseError(
                f"expected {op!r} at {self.peek().pos} in {self.sql!r}, "
                f"got {self.peek().text!r}")

    def expect_kind(self, kind: str) -> Tok:
        t = self.peek()
        if t.kind != kind:
            raise SqlParseError(
                f"expected {kind} at {t.pos} in {self.sql!r}, got {t.text!r}")
        return self.next()

    def expect_ident(self) -> str:
        t = self.peek()
        if t.kind == "ident":
            return self.next().text
        if t.kind == "qident":
            return self.next().text[1:-1]
        raise SqlParseError(
            f"expected identifier at {t.pos} in {self.sql!r}, got {t.text!r}")

    def expect_int(self) -> int:
        t = self.expect_kind("num")
        if not t.text.isdigit():
            raise SqlParseError(
                f"expected an integer at {t.pos} in {self.sql!r}, "
                f"got {t.text!r}")
        return int(t.text)

    # --- expressions ------------------------------------------------------
    def parse_expression(self) -> Expression:
        return self._or()

    def _or(self) -> Expression:
        from .expressions.predicates import Or
        e = self._and()
        while self.accept_kw("OR"):
            e = Or(e, self._and())
        return e

    def _and(self) -> Expression:
        from .expressions.predicates import And
        e = self._not()
        while self.accept_kw("AND"):
            e = And(e, self._not())
        return e

    def _not(self) -> Expression:
        from .expressions.predicates import Not
        if self.accept_kw("NOT"):
            return Not(self._not())
        return self._predicate()

    def _predicate(self) -> Expression:
        from .expressions import predicates as PR
        from .expressions import strings as STR
        from .expressions import regexp as RXE
        e = self._comparison()
        while True:
            negate = False
            save = self.i
            if self.accept_kw("NOT"):
                negate = True
            if self.accept_kw("BETWEEN"):
                lo = self._comparison()
                self.expect_kw("AND")
                hi = self._comparison()
                e2 = PR.And(self._cmp(PR.GreaterThanOrEqual, e, lo),
                            self._cmp(PR.LessThanOrEqual, e, hi))
            elif self.accept_kw("IN"):
                self.expect_op("(")
                if self.at_kw("SELECT"):
                    q = self._query_term({})
                    self.expect_op(")")
                    e2 = InSubquery(e, q)
                else:
                    vals = [self.parse_expression()]
                    while self.accept_op(","):
                        vals.append(self.parse_expression())
                    self.expect_op(")")
                    e2 = PR.In(e, tuple(vals))
            elif self.accept_kw("LIKE"):
                pat = self._comparison()
                if not isinstance(pat, Literal):
                    raise SqlParseError("LIKE pattern must be a literal")
                e2 = STR.Like(e, pat)
            elif self.accept_kw("RLIKE", "REGEXP"):
                pat = self._comparison()
                if not isinstance(pat, Literal):
                    raise SqlParseError("RLIKE pattern must be a literal")
                e2 = RXE.RLike(e, pat.value)
            elif self.accept_kw("IS"):
                neg2 = self.accept_kw("NOT")
                if self.accept_kw("NULL"):
                    e2 = PR.IsNull(e)
                elif self.accept_kw("DISTINCT"):
                    self.expect_kw("FROM")
                    rhs = self._comparison()
                    e2 = PR.Not(PR.EqualNullSafe(e, rhs))
                elif self.accept_kw("TRUE"):
                    e2 = PR.EqualNullSafe(e, Literal(True))
                elif self.accept_kw("FALSE"):
                    e2 = PR.EqualNullSafe(e, Literal(False))
                else:
                    raise SqlParseError(
                        f"expected NULL/TRUE/FALSE/DISTINCT after IS at "
                        f"{self.peek().pos}")
                if neg2:
                    e2 = PR.Not(e2)
                if negate:
                    raise SqlParseError("NOT IS is not valid SQL")
                e = e2
                continue
            else:
                self.i = save
                return e
            e = PR.Not(e2) if negate else e2

    @staticmethod
    def _cmp(cls, a: Expression, b: Expression) -> Expression:
        from .dataframe import _coerce_pair
        a, b = _coerce_pair(a, b)
        return cls(a, b)

    def _comparison(self) -> Expression:
        from .expressions import predicates as PR
        e = self._bitor()
        ops = {"=": PR.EqualTo, "==": PR.EqualTo, "<": PR.LessThan,
               "<=": PR.LessThanOrEqual, ">": PR.GreaterThan,
               ">=": PR.GreaterThanOrEqual, "<=>": PR.EqualNullSafe}
        t = self.peek()
        if t.kind == "op" and t.text in ops:
            self.next()
            rhs = self._bitor()
            return self._cmp(ops[t.text], e, rhs)
        if t.kind == "op" and t.text in ("!=", "<>"):
            self.next()
            rhs = self._bitor()
            return PR.Not(self._cmp(PR.EqualTo, e, rhs))
        return e

    # value-operator precedence, tightest to loosest (Spark SqlBase.g4):
    #   *,/,%,DIV > +,- > || > <<,>>,>>> > & > ^ > |
    def _bitor(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._bitxor()
        while self.accept_op("|"):
            e = self._arith(A.BitwiseOr, e, self._bitxor())
        return e

    def _bitxor(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._bitand()
        while self.accept_op("^"):
            e = self._arith(A.BitwiseXor, e, self._bitand())
        return e

    def _bitand(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._shift()
        while self.accept_op("&"):
            e = self._arith(A.BitwiseAnd, e, self._shift())
        return e

    def _shift(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._concat()
        while True:
            if self.accept_op("<<"):
                e = A.ShiftLeft(e, self._concat())
            elif self.accept_op(">>>"):
                e = A.ShiftRightUnsigned(e, self._concat())
            elif self.accept_op(">>"):
                e = A.ShiftRight(e, self._concat())
            else:
                return e

    def _concat(self) -> Expression:
        from .expressions import strings as STR
        e = self._additive()
        while self.accept_op("||"):
            e = STR.Concat(_as_string(e), _as_string(self._additive()))
        return e

    def _additive(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._multiplicative()
        while True:
            if self.accept_op("+"):
                e = self._fold_interval(A.Add, e, self._addend())
            elif self.accept_op("-"):
                e = self._fold_interval(A.Subtract, e, self._addend())
            else:
                return e

    def _addend(self) -> Expression:
        """The operand after ``+``/``-``.  ``<n> <unit>`` right before a
        closing parenthesis is a labeled duration, the form the TPC-DS
        templates write their date ranges in (``(cast('1999-02-22' as
        date) + 30 days)``, query98.tpl): the same value as ``INTERVAL
        <n> <unit>``.  Anywhere else a name after a number stays what it
        was, the implicit alias of a select item."""
        v, u = self.peek(), self.peek(1)
        if v.kind == "num" and v.text.isdigit() and u.kind == "ident" \
                and u.text.lower() in _INTERVAL_UNITS \
                and self.peek(2).kind == "op" and self.peek(2).text == ")":
            self.next()
            self.next()
            months, days, micros = (
                int(v.text) * k for k in _INTERVAL_UNITS[u.text.lower()])
            return IntervalLiteral(months, days, micros)
        return self._multiplicative()

    def _fold_interval(self, cls, a: Expression, b: Expression
                       ) -> Expression:
        """date/timestamp +/- INTERVAL folds to DateAddInterval/TimeAdd;
        interval + date commutes; everything else is plain arithmetic."""
        from .expressions import arithmetic as A
        from .expressions.datetime import AddCalendarInterval
        if isinstance(a, IntervalLiteral) and \
                not isinstance(b, IntervalLiteral) and cls is A.Add:
            a, b = b, a
        if isinstance(b, IntervalLiteral):
            if isinstance(a, IntervalLiteral):
                raise SqlParseError("interval +/- interval is not supported")
            sign = 1 if cls is A.Add else -1
            # operand-type dispatch (date vs timestamp, sub-day promotion)
            # happens inside AddCalendarInterval at resolution time
            return AddCalendarInterval(a, months=sign * b.months,
                                       days=sign * b.days,
                                       micros=sign * b.micros)
        if isinstance(a, IntervalLiteral):
            raise SqlParseError(
                "INTERVAL literals are only valid in +/- date arithmetic")
        return self._arith(cls, a, b)

    @staticmethod
    def _arith(cls, a: Expression, b: Expression) -> Expression:
        from .dataframe import _coerce_pair
        a, b = _coerce_pair(a, b)
        return cls(a, b)

    def _multiplicative(self) -> Expression:
        from .expressions import arithmetic as A
        e = self._unary()
        while True:
            if self.accept_op("*"):
                e = self._arith(A.Multiply, e, self._unary())
            elif self.accept_op("/"):
                e = self._arith(A.Divide, e, self._unary())
            elif self.accept_op("%"):
                e = self._arith(A.Remainder, e, self._unary())
            elif self.at_kw("DIV"):
                self.next()
                e = self._arith(A.IntegralDivide, e, self._unary())
            else:
                return e

    def _unary(self) -> Expression:
        from .expressions import arithmetic as A
        if self.accept_op("-"):
            child = self._unary()
            if isinstance(child, Literal) and isinstance(
                    child.value, (int, float)) and not isinstance(
                    child.value, bool):
                return Literal(-child.value, child.dtype)
            return A.UnaryMinus(child)
        if self.accept_op("+"):
            return self._unary()
        if self.accept_op("~"):
            return A.BitwiseNot(self._unary())
        return self._primary()

    def _primary(self) -> Expression:
        from . import functions as F
        t = self.peek()
        if t.kind == "ident" and t.upper == "EXISTS" \
                and self.peek(1).kind == "op" and self.peek(1).text == "(":
            self.next()
            self.expect_op("(")
            q = self._query_term({})
            self.expect_op(")")
            return ExistsSubquery(q)
        if t.kind == "num":
            return self._number(self.next().text)
        if t.kind == "str":
            self.next()
            return Literal(unescape_sql_string(t.text[1:-1]))
        if t.kind == "op" and t.text == "(" and self.peek(1).kind == "ident" \
                and self.peek(1).upper == "SELECT":
            self.next()
            q = self._query_term({})
            self.expect_op(")")
            return ScalarSubquery(q)
        if self.accept_op("("):
            e = self.parse_expression()
            self.expect_op(")")
            return e
        if t.kind == "op" and t.text == "*":
            self.next()
            return Star()           # only valid in select-list / count(*)
        if t.kind in ("ident", "qident"):
            up = t.upper
            if up == "NULL" and t.kind == "ident":
                self.next()
                return Literal(None)
            if up in ("TRUE", "FALSE") and t.kind == "ident":
                self.next()
                return Literal(up == "TRUE")
            if up == "CAST" and t.kind == "ident" and \
                    self.peek(1).kind == "op" and self.peek(1).text == "(":
                return self._cast()
            if up == "CASE" and t.kind == "ident":
                return self._case()
            if up in ("DATE", "TIMESTAMP") and t.kind == "ident" \
                    and self.peek(1).kind == "str":
                # typed literal: DATE '1995-01-01' / TIMESTAMP '...' —
                # the form the TPC-H query texts use everywhere.  Only
                # when a string literal follows: bare `date` stays a
                # valid column name.
                import datetime as _dt
                self.next()
                s = unescape_sql_string(self.next().text[1:-1])
                try:
                    if up == "DATE":
                        return Literal(_dt.date.fromisoformat(s))
                    return Literal(_dt.datetime.fromisoformat(s))
                except ValueError:
                    raise SqlParseError(
                        f"bad {up} literal {s!r}") from None
            if up == "INTERVAL" and t.kind == "ident":
                self.next()
                months = days = micros = 0
                saw = False
                def unit_at(k: int) -> bool:
                    u = self.peek(k)
                    return (u.kind == "ident"
                            and u.text.lower() in _INTERVAL_UNITS)

                while True:
                    # commit to a component only when a UNIT follows the
                    # value — a trailing +/- or number belongs to the
                    # enclosing arithmetic (INTERVAL '1' DAY - x)
                    v = self.peek()
                    if v.kind in ("str", "num") and unit_at(1):
                        self.next()
                        txt = v.text[1:-1] if v.kind == "str" else v.text
                        try:
                            n = int(txt)
                        except ValueError:
                            raise SqlParseError(
                                f"bad INTERVAL value {v.text}") from None
                    elif v.kind == "op" and v.text == "-" \
                            and self.peek(1).kind in ("num", "str") \
                            and unit_at(2):
                        self.next()
                        v2 = self.next()
                        txt = v2.text[1:-1] if v2.kind == "str" else v2.text
                        try:
                            n = -int(txt)
                        except ValueError:
                            raise SqlParseError(
                                f"bad INTERVAL value {v2.text}") from None
                    else:
                        break
                    u = self.next()
                    mo, d, us = _INTERVAL_UNITS[u.text.lower()]
                    months += n * mo
                    days += n * d
                    micros += n * us
                    saw = True
                if not saw:
                    raise SqlParseError("empty INTERVAL literal")
                return IntervalLiteral(months, days, micros)
            name = self.expect_ident()
            # function call?
            if self.at_op("(") and t.kind == "ident":
                return self._call(name)
            # qualified: t.a, t.*
            if self.accept_op("."):
                if self.accept_op("*"):
                    return Star(qualifier=name)
                sub = self.expect_ident()
                return UnresolvedQualified(name, sub)
            return F.col(name).expr
        raise SqlParseError(
            f"unexpected token {t.text!r} at {t.pos} in {self.sql!r}")

    @staticmethod
    def _number(text: str) -> Literal:
        suffix = text[-1] if text[-1] in "dDlLfF" else ""
        if suffix:
            text = text[:-1]
        if (suffix and suffix in "dDfF") or "." in text \
                or "e" in text or "E" in text:
            return Literal(float(text))
        if suffix:                      # 42L — explicit bigint
            return Literal(int(text), T.LONG)
        return Literal(int(text))

    def _cast(self) -> Expression:
        from .expressions.cast import Cast
        self.next()             # CAST
        self.expect_op("(")
        e = self.parse_expression()
        self.expect_kw("AS")
        dt = _parse_type_tokens(self)
        self.expect_op(")")
        return Cast(e, dt)

    def _case(self) -> Expression:
        from .expressions.conditional import CaseWhen
        from .expressions import predicates as PR
        self.next()             # CASE
        subject = None
        if not self.at_kw("WHEN"):
            subject = self.parse_expression()
        branches = []
        while self.accept_kw("WHEN"):
            cond = self.parse_expression()
            if subject is not None:
                cond = self._cmp(PR.EqualTo, subject, cond)
            self.expect_kw("THEN")
            branches.append((cond, self.parse_expression()))
        else_v = None
        if self.accept_kw("ELSE"):
            else_v = self.parse_expression()
        self.expect_kw("END")
        return CaseWhen(branches, else_v)

    def _call(self, name: str) -> Expression:
        from . import functions as F
        from .dataframe import Column
        from .expressions.aggregates import (AggregateExpression, Average,
                                             Count, Max, Min, Sum)
        self.expect_op("(")
        lname = name.lower()
        if lname == "extract":
            # EXTRACT(unit FROM expr) — special syntactic form (SQL
            # standard; TPC-H q7/q8/q9 use extract(year from ...)).
            # Lowered onto the plain field-extraction functions.
            unit_tok = self.next()
            unit = unit_tok.text.lower()
            fn = {"year": "year", "month": "month", "day": "day",
                  "dayofmonth": "day", "hour": "hour", "minute": "minute",
                  "second": "second", "quarter": "quarter",
                  "week": "weekofyear", "dow": "dayofweek",
                  "doy": "dayofyear"}.get(unit)
            if fn is None or unit_tok.kind != "ident":
                raise SqlParseError(
                    f"unsupported EXTRACT unit {unit_tok.text!r}")
            self.expect_kw("FROM")
            arg = self.parse_expression()
            self.expect_op(")")
            from .dataframe import Column as _Col
            res = _functions()[fn](_Col(arg))
            e = res.expr if isinstance(res, _Col) else res
            if unit == "dow":
                # Spark's EXTRACT(DOW) is 0=Sunday..6; dayofweek() is
                # 1=Sunday..7
                from .expressions import arithmetic as A
                e = A.Subtract(e, Literal(1))
            return e
        distinct = False
        if self.accept_kw("DISTINCT"):
            distinct = True
        args: List[Expression] = []
        if not self.at_op(")"):
            args.append(self.parse_expression())
            while self.accept_op(","):
                args.append(self.parse_expression())
        self.expect_op(")")

        if lname == "count" and len(args) == 1 and isinstance(args[0], Star):
            if distinct:
                raise SqlParseError("count(DISTINCT *) is not supported")
            e: Expression = Count()
        elif lname == "count" and distinct:
            e = AggregateExpression(Count(*args), is_distinct=True)
        elif distinct and lname in ("sum", "avg", "mean", "min", "max"):
            base = {"sum": Sum, "avg": Average, "mean": Average,
                    "min": Min, "max": Max}[lname](args[0])
            e = AggregateExpression(base, is_distinct=True)
        elif lname in ("if", "iff"):
            from .expressions.conditional import If
            if len(args) != 3:
                raise SqlParseError("if() takes exactly 3 arguments")
            e = If(args[0], args[1], args[2])
        elif lname == "nullif":
            from .expressions.conditional import CaseWhen
            from .expressions import predicates as PR
            e = CaseWhen([(self._cmp(PR.EqualTo, args[0], args[1]),
                           Literal(None))], args[0])
        elif lname in self.udfs:
            from .expressions.hive_udf import HiveSimpleUDF
            if distinct:
                raise SqlParseError(
                    f"DISTINCT is not supported inside {name}()")
            e = HiveSimpleUDF(lname, self.udfs[lname], *args)
        else:
            fn = _functions().get(lname)
            if fn is None:
                raise SqlParseError(f"unknown SQL function {name!r}")
            if distinct:
                raise SqlParseError(
                    f"DISTINCT is not supported inside {name}()")
            unwrap = _LITERAL_POS.get(lname, ())
            call_args: List[Any] = []
            for idx, a in enumerate(args):
                if idx in unwrap and isinstance(a, Literal):
                    call_args.append(a.value)
                else:
                    call_args.append(Column(a))
            res = fn(*call_args)
            e = res.expr if isinstance(res, Column) else res
        if self.at_kw("OVER"):
            e = self._over(e)
        return e

    def _over(self, fn_expr: Expression) -> Expression:
        self.expect_kw("OVER")
        self.expect_op("(")
        partition: List[Expression] = []
        orders: List[SortOrder] = []
        frame = None
        if self.accept_kw("PARTITION"):
            self.expect_kw("BY")
            partition.append(self.parse_expression())
            while self.accept_op(","):
                partition.append(self.parse_expression())
        if self.accept_kw("ORDER"):
            self.expect_kw("BY")
            orders.append(self._sort_order())
            while self.accept_op(","):
                orders.append(self._sort_order())
        if self.at_kw("ROWS", "RANGE"):
            mode = self.next().text.lower()
            self.expect_kw("BETWEEN")
            lo = self._frame_bound()
            self.expect_kw("AND")
            hi = self._frame_bound()
            frame = WindowFrame(mode, lo, hi)
        self.expect_op(")")
        spec = WindowSpecDefinition(tuple(partition), tuple(orders), frame)
        if isinstance(fn_expr, Alias):
            return Alias(WindowExpression(fn_expr.child, spec), fn_expr.name)
        return WindowExpression(fn_expr, spec)

    def _frame_bound(self) -> int:
        if self.accept_kw("UNBOUNDED"):
            if self.accept_kw("PRECEDING"):
                return UNBOUNDED_PRECEDING
            self.expect_kw("FOLLOWING")
            return UNBOUNDED_FOLLOWING
        if self.accept_kw("CURRENT"):
            self.expect_kw("ROW")
            return CURRENT_ROW
        sign = -1 if self.accept_op("-") else 1
        n = self.expect_int() * sign
        if self.accept_kw("PRECEDING"):
            return -n
        self.expect_kw("FOLLOWING")
        return n

    def _sort_order(self) -> SortOrder:
        e = self.parse_expression()
        asc = True
        if self.accept_kw("ASC"):
            asc = True
        elif self.accept_kw("DESC"):
            asc = False
        nulls_first = None
        if self.accept_kw("NULLS"):
            if self.accept_kw("FIRST"):
                nulls_first = True
            else:
                self.expect_kw("LAST")
                nulls_first = False
        return SortOrder(e, asc, nulls_first)

    # --- statements -------------------------------------------------------
    def _maybe_function_ddl(self):
        if self.accept_kw("CREATE"):
            replace = False
            if self.accept_kw("OR"):
                self.expect_kw("REPLACE")
                replace = True
            if not self.accept_kw("TEMPORARY", "TEMP"):
                return None
            if self.accept_kw("VIEW"):
                name = self.expect_ident()
                self.expect_kw("AS")
                ctes = self._parse_ctes()
                sub = self._query_term(ctes)
                sub.ctes = ctes
                return CreateTempViewStmt(name, sub, replace)
            if not self.accept_kw("FUNCTION"):
                return None
            name = self.expect_ident()
            self.expect_kw("AS")
            t = self.peek()
            if t.kind != "str":
                raise SqlParseError(
                    f"expected a quoted class path after AS at {t.pos}")
            self.next()
            path = unescape_sql_string(t.text[1:-1])
            return CreateFunctionStmt(name, path, replace)
        if self.accept_kw("DROP"):
            self.accept_kw("TEMPORARY", "TEMP")
            if self.accept_kw("VIEW"):
                if_exists = False
                if self.accept_kw("IF"):
                    self.expect_kw("EXISTS")
                    if_exists = True
                return DropViewStmt(self.expect_ident(), if_exists)
            if not self.accept_kw("FUNCTION"):
                return None
            if_exists = False
            if self.accept_kw("IF"):
                self.expect_kw("EXISTS")
                if_exists = True
            return DropFunctionStmt(self.expect_ident(), if_exists)
        return None

    def _parse_ctes(self):
        ctes: Dict[str, Any] = {}
        if self.accept_kw("WITH"):
            while True:
                name = self.expect_ident()
                self.expect_kw("AS")
                self.expect_op("(")
                sub = self._query_term(ctes)
                self.expect_op(")")
                ctes[name.lower()] = sub
                if not self.accept_op(","):
                    break
        return ctes

    def parse_statement(self):
        # DDL: CREATE [OR REPLACE] TEMPORARY FUNCTION f AS 'module.Class'
        # (the exact shape Spark uses to register Hive UDFs) / DROP
        # TEMPORARY FUNCTION [IF EXISTS] f / SHOW TABLES /
        # DESCRIBE [TABLE] name
        if self.at_kw("SHOW"):
            save = self.i
            self.next()
            if self.accept_kw("TABLES") and self.peek().kind == "eof":
                return ShowTablesStmt()
            self.i = save
        if self.at_kw("DESCRIBE", "DESC"):
            save = self.i
            self.next()
            self.accept_kw("TABLE")
            t = self.peek()
            if t.kind in ("ident", "qident"):
                name = self.expect_ident()
                if self.peek().kind == "eof":
                    return DescribeTableStmt(name)
            self.i = save
        if self.at_kw("CREATE") or self.at_kw("DROP"):
            save = self.i
            stmt = self._maybe_function_ddl()
            if stmt is not None:
                tail = self.peek()
                if tail.kind != "eof":
                    raise SqlParseError(
                        f"unexpected trailing input {tail.text!r} at "
                        f"{tail.pos} in {self.sql!r}")
                return stmt
            self.i = save
        ctes = self._parse_ctes()
        stmt = self._query_term(ctes)
        stmt.ctes = ctes
        tail = self.peek()
        if tail.kind != "eof":
            raise SqlParseError(
                f"unexpected trailing input {tail.text!r} at {tail.pos} "
                f"in {self.sql!r}")
        return stmt

    def _set_op_modifier(self) -> bool:
        is_all = self.accept_kw("ALL")
        if self.accept_kw("DISTINCT") and is_all:
            raise SqlParseError("cannot combine ALL and DISTINCT in a "
                                "set operation")
        return is_all

    def _query_term(self, ctes) -> Any:
        # INTERSECT binds tighter than UNION/EXCEPT (SQL standard)
        left = self._intersect_term(ctes)
        while self.at_kw("UNION", "EXCEPT", "MINUS"):
            op = self.next().upper
            if op == "MINUS":
                op = "EXCEPT"
            is_all = self._set_op_modifier()
            right = self._intersect_term(ctes)
            left = SetOpStmt(op.lower(), is_all, left, right)
        # ORDER BY / LIMIT terminate the whole query term (a set-op branch
        # cannot carry its own trailing clauses without parentheses)
        ob = self._order_by_clause()
        lim, off = self._limit_clause()
        if ob:
            if left.order_by:
                raise SqlParseError("multiple ORDER BY clauses")
            left.order_by = ob
        if lim is not None or off is not None:
            if left.limit is not None or left.offset is not None:
                raise SqlParseError("multiple LIMIT/OFFSET clauses")
            left.limit, left.offset = lim, off
        return left

    def _intersect_term(self, ctes) -> Any:
        left = self._query_primary(ctes)
        while self.at_kw("INTERSECT"):
            self.next()
            is_all = self._set_op_modifier()
            right = self._query_primary(ctes)
            left = SetOpStmt("intersect", is_all, left, right)
        return left

    def _query_primary(self, ctes) -> Any:
        if self.accept_op("("):
            q = self._query_term(ctes)
            self.expect_op(")")
            return q
        return self._select(ctes)

    def _select(self, ctes) -> SelectStmt:
        self.expect_kw("SELECT")
        stmt = SelectStmt()
        if self.accept_kw("DISTINCT"):
            stmt.distinct = True
        else:
            self.accept_kw("ALL")
        stmt.items.append(self._select_item())
        while self.accept_op(","):
            stmt.items.append(self._select_item())
        if self.accept_kw("FROM"):
            stmt.from_ = self._table_ref(ctes)
            while True:
                if self.at_kw("LATERAL"):
                    self.next()
                    self.expect_kw("VIEW")
                    outer = self.accept_kw("OUTER")
                    fname = self.expect_ident().lower()
                    self.expect_op("(")
                    arg = self.parse_expression()
                    self.expect_op(")")
                    talias = self.expect_ident()
                    cols: List[str] = []
                    if self.accept_kw("AS"):
                        cols.append(self.expect_ident())
                        while self.accept_op(","):
                            cols.append(self.expect_ident())
                    stmt.lateral_views.append(
                        LateralView(outer, fname, arg, talias, cols))
                    continue
                step = self._join_step(ctes)
                if step is None:
                    break
                if stmt.lateral_views:
                    # Spark's grammar puts LATERAL VIEW after all joins;
                    # silently joining-then-exploding would reorder the
                    # user's written evaluation, so reject like Spark
                    raise SqlParseError(
                        "JOIN after LATERAL VIEW is not supported — "
                        "put all JOINs before the LATERAL VIEW clauses")
                stmt.joins.append(step)
        if self.accept_kw("WHERE"):
            stmt.where = self.parse_expression()
        if self.accept_kw("GROUP"):
            self.expect_kw("BY")
            self._group_element(stmt)
            while self.accept_op(","):
                self._group_element(stmt)
        if self.accept_kw("HAVING"):
            stmt.having = self.parse_expression()
        # ORDER BY / LIMIT are parsed at the query-term level so they bind
        # to a whole set-operation result, never to its last branch
        return stmt

    def _group_item(self):
        t = self.peek()
        if t.kind == "num" and t.text.isdigit():
            self.next()
            return int(t.text)
        return self.parse_expression()

    def _group_element(self, stmt: "SelectStmt") -> None:
        """One GROUP BY element: a plain item (always-grouped base key)
        or ONE ROLLUP/CUBE/GROUPING SETS construct, mixable with base
        keys (Spark 3 partial grouping: GROUP BY a, ROLLUP(b) =
        {a} x rollup sets)."""
        def one_construct(mode: str):
            if stmt.group_by_mode is not None:
                raise SqlParseError(
                    "only one ROLLUP/CUBE/GROUPING SETS construct is "
                    "supported per GROUP BY")
            stmt.group_by_mode = mode
        if self.at_kw("ROLLUP", "CUBE") and \
                self.peek(1).kind == "op" and self.peek(1).text == "(":
            one_construct(self.peek().upper.lower())
            self.next()
            self.expect_op("(")
            exprs = [self._group_item()]
            while self.accept_op(","):
                exprs.append(self._group_item())
            self.expect_op(")")
            stmt.grouping_sets_raw = [exprs]
            return
        if self.at_kw("GROUPING") and self.peek(1).upper == "SETS" \
                and self.peek(2).kind == "op" and self.peek(2).text == "(":
            one_construct("sets")
            self.next()
            self.next()
            self.expect_op("(")
            while True:
                one: List[Any] = []
                if self.accept_op("("):
                    # parenthesized (possibly empty) key list
                    if not self.accept_op(")"):
                        one.append(self._group_item())
                        while self.accept_op(","):
                            one.append(self._group_item())
                        self.expect_op(")")
                else:
                    one.append(self._group_item())  # bare single key
                stmt.grouping_sets_raw.append(one)
                if not self.accept_op(","):
                    break
            self.expect_op(")")
            return
        stmt.group_by.append(self._group_item())

    def _order_by_clause(self) -> List[OrderItem]:
        out: List[OrderItem] = []
        if self.accept_kw("ORDER"):
            self.expect_kw("BY")
            while True:
                t = self.peek()
                if t.kind == "num" and t.text.isdigit():
                    self.next()
                    e: Any = int(t.text)
                else:
                    e = self.parse_expression()
                asc = True
                if self.accept_kw("DESC"):
                    asc = False
                else:
                    self.accept_kw("ASC")
                nf = None
                if self.accept_kw("NULLS"):
                    if self.accept_kw("FIRST"):
                        nf = True
                    else:
                        self.expect_kw("LAST")
                        nf = False
                out.append(OrderItem(e, asc, nf))
                if not self.accept_op(","):
                    break
        return out

    def _limit_clause(self) -> Tuple[Optional[int], Optional[int]]:
        limit = offset = None
        if self.accept_kw("LIMIT"):
            if self.accept_kw("ALL"):
                limit = None
            else:
                limit = self.expect_int()
        if self.accept_kw("OFFSET"):
            offset = self.expect_int()
        return limit, offset

    def _select_item(self) -> SelectItem:
        e = self.parse_expression()
        alias = None
        if self.accept_kw("AS"):
            alias = self.expect_ident()
        elif (self.peek().kind == "qident"
              or (self.peek().kind == "ident"
                  and self.peek().upper not in _RESERVED_STOP)):
            alias = self.expect_ident()
        return SelectItem(e, alias)

    def _table_ref(self, ctes) -> Any:
        if self.accept_op("("):
            q = self._query_term(ctes)
            self.expect_op(")")
            alias, sample = self._ref_suffix()
            return SubqueryRef(q, alias, sample=sample)
        name = self.expect_ident()
        # direct file relation: parquet.`/path`
        if name.lower() in ("parquet", "orc", "csv", "json", "avro") and \
                self.at_op(".") and self.peek(1).kind == "qident":
            self.next()
            path = self.expect_ident()
            alias, sample = self._ref_suffix()
            return TableRef(name.lower(), alias, path=path, sample=sample)
        alias, sample = self._ref_suffix()
        return TableRef(name, alias, sample=sample)

    def _ref_suffix(self):
        """[alias] [TABLESAMPLE ...] [alias] after a relation — one place
        for all three _table_ref branches."""
        alias = self._table_alias()
        sample = self._maybe_tablesample()
        return alias or self._table_alias(), sample

    def _maybe_tablesample(self):
        """TABLESAMPLE (n PERCENT | n ROWS) [REPEATABLE (seed)] after a
        relation (Spark's sample clause; PERCENT maps to the Sample
        operator, ROWS to a limit)."""
        if not self.accept_kw("TABLESAMPLE"):
            return None
        self.expect_op("(")
        t = self.expect_kind("num")
        try:
            val = float(t.text)
        except ValueError:
            raise SqlParseError(
                f"bad TABLESAMPLE value {t.text!r} at {t.pos} in "
                f"{self.sql!r}") from None
        if self.accept_kw("PERCENT"):
            kind = "percent"
        elif self.accept_kw("ROWS"):
            kind = "rows"
            if not t.text.isdigit():
                raise SqlParseError(
                    f"TABLESAMPLE ROWS expects an integer at {t.pos} in "
                    f"{self.sql!r}, got {t.text!r}")
        else:
            raise SqlParseError(
                "TABLESAMPLE supports 'n PERCENT' and 'n ROWS'")
        self.expect_op(")")
        seed = None  # None = fresh per sample (Spark's non-REPEATABLE)
        if self.accept_kw("REPEATABLE"):
            self.expect_op("(")
            seed = self.expect_int()
            self.expect_op(")")
        return (kind, val, seed)

    def _table_alias(self) -> Optional[str]:
        if self.accept_kw("AS"):
            return self.expect_ident()
        t = self.peek()
        if t.kind == "qident" or (t.kind == "ident"
                                  and t.upper not in _RESERVED_STOP):
            return self.expect_ident()
        return None

    def _join_step(self, ctes) -> Optional[JoinStep]:
        how = None
        if self.accept_op(","):
            how = "cross"
        elif self.at_kw("JOIN"):
            self.next()
            how = "inner"
        elif self.at_kw("INNER") and self.peek(1).upper == "JOIN":
            self.next(); self.next()
            how = "inner"
        elif self.at_kw("CROSS") and self.peek(1).upper == "JOIN":
            self.next(); self.next()
            how = "cross"
        elif self.at_kw("LEFT", "RIGHT", "FULL"):
            side = self.next().upper.lower()
            if self.accept_kw("OUTER"):
                pass
            elif side == "left" and self.accept_kw("SEMI"):
                side = "left_semi"
            elif side == "left" and self.accept_kw("ANTI"):
                side = "left_anti"
            self.expect_kw("JOIN")
            how = {"full": "full"}.get(side, side)
        if how is None:
            return None
        right = self._table_ref(ctes)
        on = None
        using = None
        if self.accept_kw("ON"):
            on = self.parse_expression()
        elif self.accept_kw("USING"):
            self.expect_op("(")
            using = [self.expect_ident()]
            while self.accept_op(","):
                using.append(self.expect_ident())
            self.expect_op(")")
        return JoinStep(how, right, on, using)


# --------------------------------------------------------------------------
# Public expression-string entry points
# --------------------------------------------------------------------------

def _active_udfs():
    """Hive UDFs of the active session — the fallback for surfaces with
    no session in reach (bare F.expr)."""
    from .session import TpuSession
    s = TpuSession._active
    return getattr(s, "_hive_udfs", None) if s is not None else None


def parse_expr(sql: str, udfs=None):
    """``F.expr("...")`` — expression string to a Column (plain column
    names stay unresolved, resolved later against the target frame).
    ``udfs``: the owning session's Hive UDF registry (DataFrame surfaces
    pass their own session's; bare F.expr falls back to the active
    session)."""
    from .dataframe import Column
    p = Parser(sql, udfs=udfs if udfs is not None else _active_udfs())
    e = p.parse_expression()
    alias = None
    if p.accept_kw("AS"):
        alias = p.expect_ident()
    tail = p.peek()
    if tail.kind != "eof":
        raise SqlParseError(
            f"unexpected trailing input {tail.text!r} in expression "
            f"{sql!r}")
    if isinstance(e, Star):
        raise SqlParseError("'*' is only valid in a select list")
    if alias:
        e = Alias(e, alias)
    return Column(e)


def parse_select_item(sql: str, udfs=None):
    """One selectExpr entry: expression with optional alias, or '*'."""
    p = Parser(sql, udfs=udfs if udfs is not None else _active_udfs())
    item = p._select_item()
    tail = p.peek()
    if tail.kind != "eof":
        raise SqlParseError(
            f"unexpected trailing input {tail.text!r} in {sql!r}")
    return item


# --------------------------------------------------------------------------
# Query builder: statement AST -> DataFrame
# --------------------------------------------------------------------------

@dataclass
class CreateFunctionStmt:
    name: str
    class_path: str
    replace: bool = False


@dataclass
class DropFunctionStmt:
    name: str
    if_exists: bool = False


@dataclass
class ShowTablesStmt:
    pass


@dataclass
class CreateTempViewStmt:
    name: str
    stmt: "Any"
    replace: bool = False


@dataclass
class DropViewStmt:
    name: str
    if_exists: bool = False


@dataclass
class DescribeTableStmt:
    name: str


class QueryBuilder:
    """Builds DataFrames from parsed statements against a session's
    temp-view catalog (the Catalyst analyzer+planner front half)."""

    def __init__(self, session):
        self.session = session
        self._subq = 0

    # --- entry ------------------------------------------------------------
    def build(self, stmt, outer_ctes: Optional[Dict[str, Any]] = None):
        ctes = dict(outer_ctes or {})
        ctes.update({k: ("stmt", v) for k, v in stmt.ctes.items()})
        if isinstance(stmt, SetOpStmt):
            return self._build_setop(stmt, ctes)
        return self._build_select(stmt, ctes)

    def _build_setop(self, stmt: SetOpStmt, ctes):
        left = self._build_sub(stmt.left, ctes)
        right = self._build_sub(stmt.right, ctes)
        if stmt.op == "union":
            df = left.union(right)
            if not stmt.all:
                df = df.distinct()
        elif stmt.op == "intersect":
            df = left.intersectAll(right) if stmt.all else \
                left.intersect(right)
        else:
            df = left.exceptAll(right) if stmt.all else left.subtract(right)
        df = self._apply_order_limit(df, stmt.order_by, stmt.limit,
                                     stmt.offset, items=None)
        return df

    def _build_sub(self, stmt, ctes):
        if isinstance(stmt, SetOpStmt):
            return self._build_setop(stmt, ctes)
        return self._build_select(stmt, ctes)

    # --- FROM -------------------------------------------------------------
    def _resolve_relation(self, ref, ctes):
        from .dataframe import DataFrame
        if isinstance(ref, SubqueryRef):
            df = self._build_sub(ref.stmt, ctes)
            self._subq += 1
            alias = ref.alias or f"__subquery{self._subq}"
            return self._apply_sample(self._fresh(df), ref.sample), alias
        assert isinstance(ref, TableRef)
        if ref.path is not None:
            reader = self.session.read
            df = getattr(reader, ref.name)(ref.path)
            return (self._apply_sample(self._fresh(df), ref.sample),
                    ref.alias or ref.name)
        key = ref.name.lower()
        if key in ctes:
            kind, payload = ctes[key]
            df = self._build_sub(payload, ctes) if kind == "stmt" else payload
            return (self._apply_sample(self._fresh(df), ref.sample),
                    ref.alias or ref.name)
        view = self.session._temp_views.get(key)
        if view is None:
            raise SqlParseError(f"table or view not found: {ref.name}")
        df = self._fresh(DataFrame(view._plan, self.session))
        return self._apply_sample(df, ref.sample), ref.alias or ref.name

    @staticmethod
    def _apply_sample(df, sample):
        if sample is None:
            return df
        kind, val, seed = sample
        if kind == "rows":
            return df.limit(int(val))
        if not (0.0 <= val <= 100.0):
            raise SqlParseError(
                f"TABLESAMPLE percentage {val} not in [0, 100]")
        if seed is None:
            # non-REPEATABLE: each sample gets a distinct seed so two
            # samples of the same table in one query are independent
            # (deterministic across reruns — engine-wide determinism)
            seed = next(_SAMPLE_SEEDS)
        return df.sample(val / 100.0, seed=seed)

    def _fresh(self, df):
        """Re-alias every output column under fresh expression ids, so two
        references to the same relation (self-join ``t a JOIN t b``) have
        distinct attributes (Catalyst's deduplicateRelations)."""
        from . import plan as P
        from .dataframe import DataFrame
        exprs = tuple(Alias(a, a.name) for a in df._plan.output)
        return DataFrame(P.Project(exprs, df._plan), self.session)

    # --- scalar subqueries ------------------------------------------------
    def _eval_scalar_expr(self, e: Expression, ctes) -> Expression:
        def repl(x):
            if not isinstance(x, ScalarSubquery):
                return None
            if isinstance(x.stmt, SelectStmt):
                inner_aliases = self._relation_aliases(x.stmt)
                exprs = ([it.expr for it in x.stmt.items
                          if isinstance(it.expr, Expression)]
                         + [e2 for e2 in (x.stmt.where, x.stmt.having)
                            if e2 is not None])
                for e2 in exprs:
                    if e2.collect(lambda n: isinstance(n, UnresolvedQualified)
                                  and n.qualifier.lower()
                                  not in inner_aliases):
                        # correlated: leave the node for the decorrelation
                        # pass in _build_select (grouped-agg LEFT JOIN)
                        return None
            inner = self._build_sub(x.stmt, ctes)
            if len(inner._plan.output) != 1:
                raise SqlParseError(
                    "scalar subquery must return exactly one column")
            attr = inner._plan.output[0]
            rows = inner.limit(2).collect().to_pylist()
            if len(rows) > 1:
                raise SqlParseError(
                    "scalar subquery returned more than one row")
            val = rows[0][attr.name] if rows else None
            return Literal(val, attr.dtype)
        return e.transform(repl)

    def _eval_scalar_subqueries_stmt(self, stmt: SelectStmt, ctes):
        """Replace uncorrelated scalar subqueries in every expression slot
        with their (build-time evaluated) literal value."""
        has = any(
            isinstance(e, Expression)
            and e.collect(lambda x: isinstance(x, ScalarSubquery))
            for e in ([it.expr for it in stmt.items]
                      + [stmt.where, stmt.having]
                      + list(stmt.group_by)
                      + [g for s in stmt.grouping_sets_raw for g in s]
                      + [j.on for j in stmt.joins]
                      + [oi.expr for oi in stmt.order_by])
            if e is not None)
        if not has:
            return stmt
        import dataclasses

        def ev(e):
            if e is None or not isinstance(e, Expression):
                return e
            return self._eval_scalar_expr(e, ctes)

        return dataclasses.replace(
            stmt,
            items=[SelectItem(it.expr if isinstance(it.expr, Star)
                              else ev(it.expr), it.alias)
                   for it in stmt.items],
            where=ev(stmt.where), having=ev(stmt.having),
            group_by=[ev(g) for g in stmt.group_by],
            grouping_sets_raw=[[ev(g) for g in s]
                               for s in stmt.grouping_sets_raw],
            joins=[dataclasses.replace(j, on=ev(j.on))
                   for j in stmt.joins],
            order_by=[dataclasses.replace(oi, expr=ev(oi.expr))
                      for oi in stmt.order_by])

    # --- subquery predicates (EXISTS / IN) --------------------------------
    @staticmethod
    def _relation_aliases(stmt) -> set:
        """Lower-cased relation aliases visible inside a SelectStmt's own
        FROM clause (for telling correlated references apart)."""
        out = set()
        if not isinstance(stmt, SelectStmt):
            return out
        refs = ([stmt.from_] if stmt.from_ is not None else []) \
            + [j.right for j in stmt.joins]
        for r in refs:
            if isinstance(r, TableRef):
                # an alias HIDES the base table name (SQL scoping): outer
                # references to the unaliased name stay outer
                out.add((r.alias or r.name).lower())
            elif isinstance(r, SubqueryRef) and r.alias:
                out.add(r.alias.lower())
        return out

    def _split_correlation(self, q, what: str, allow_mixed: bool = False):
        """Split a subquery's WHERE into ([(outer_expr, inner_expr)],
        [inner-only conjuncts], [mixed conjuncts]) — the decorrelation
        shared by correlated EXISTS and correlated scalar subqueries
        (Spark's RewriteCorrelatedScalarSubquery /
        RewritePredicateSubquery).

        ``allow_mixed`` (EXISTS only): correlated conjuncts that are NOT
        outer=inner equalities (TPC-H q21's ``l2.l_suppkey <>
        l1.l_suppkey``) are returned in the third slot for the caller to
        fold into the semi/anti join's residual condition; without it
        they raise, since the scalar-subquery rewrite needs equality
        keys to group on."""
        from .expressions import predicates as PR
        inner_aliases = self._relation_aliases(q)

        def outer_quals(e):
            return e.collect(
                lambda x: isinstance(x, UnresolvedQualified)
                and x.qualifier.lower() not in inner_aliases)

        corr_pairs = []
        inner_conj = []
        mixed = []
        if isinstance(q, SelectStmt) and q.where is not None:
            for c in _split_and(q.where):
                oq = outer_quals(c)
                if not oq:
                    inner_conj.append(c)
                    continue
                if isinstance(c, PR.EqualTo):
                    a, b = c.children
                    if outer_quals(a) and not outer_quals(b):
                        corr_pairs.append((a, b))
                        continue
                    if outer_quals(b) and not outer_quals(a):
                        corr_pairs.append((b, a))
                        continue
                if allow_mixed:
                    mixed.append(c)
                    continue
                raise SqlParseError(
                    f"{what} supports only AND-connected "
                    f"equality predicates, got {c.sql()!r}")
        return corr_pairs, inner_conj, mixed

    def _rewrite_mixed_conjunct(self, c, q, units):
        """Replace each maximal inner-only subexpression of a mixed
        correlated conjunct with an _InnerUnit placeholder (appending the
        subexpression to ``units`` for the caller to project out of the
        subquery); outer references stay in place for binding against the
        outer frame."""
        inner_aliases = self._relation_aliases(q)

        def has_outer(e):
            return bool(e.collect(
                lambda x: isinstance(x, UnresolvedQualified)
                and x.qualifier.lower() not in inner_aliases))

        def walk(e):
            if not has_outer(e):
                if isinstance(e, Literal):
                    return e
                units.append(e)
                return _InnerUnit(len(units) - 1)
            kids = tuple(walk(ch) for ch in e.children)
            return e.with_children(kids) if kids != e.children else e

        return walk(c)

    def _apply_lateral_view(self, df, lv: "LateralView", scope):
        """One LATERAL VIEW [OUTER] generator step -> a Generate node
        over the running frame (Hive/Spark semantics: generated columns
        join every source row; OUTER keeps rows whose array is
        empty/null).  The view alias resolves qualified references to
        the generated columns."""
        from . import plan as P
        from .dataframe import DataFrame
        from .expressions.collections import Explode, PosExplode
        cls = {"explode": Explode, "explode_outer": Explode,
               "posexplode": PosExplode,
               "posexplode_outer": PosExplode}.get(lv.func)
        if cls is None:
            raise SqlParseError(
                f"unsupported LATERAL VIEW generator {lv.func!r} "
                "(explode/posexplode[_outer])")
        outer = lv.outer or lv.func.endswith("_outer")
        arg = _resolve_or_err(self._bind_quals(lv.arg, scope), df._plan)
        gen = cls(arg)
        attrs = gen.gen_output_attrs()
        if lv.col_aliases:
            if len(lv.col_aliases) != len(attrs):
                raise SqlParseError(
                    f"LATERAL VIEW {lv.func} produces {len(attrs)} "
                    f"column(s); {len(lv.col_aliases)} alias(es) given")
            attrs = [a.renamed(n)
                     for a, n in zip(attrs, lv.col_aliases)]
        plan2 = P.Generate(gen, outer, tuple(attrs), df._plan)
        out = DataFrame(plan2, self.session)
        if lv.table_alias.lower() in scope:
            raise SqlParseError(
                f"duplicate relation alias {lv.table_alias!r}")
        scope[lv.table_alias.lower()] = DataFrame(
            P.Project(tuple(attrs), plan2), self.session)
        return out

    def _decorrelate_scalar_subqueries(self, df, stmt: "SelectStmt",
                                       scope, ctes):
        """Rewrite correlated scalar subqueries in the WHERE clause and
        SELECT list into a grouped-aggregate LEFT JOIN (TPC-H q2/q17
        shape: ``v < (SELECT avg(x) FROM t2 WHERE t2.k = outer.k)``).
        The aggregate-without-GROUP-BY requirement guarantees at most one
        row per correlation key, so the join cannot duplicate outer rows.
        Returns (joined df, stmt with the subquery nodes substituted)."""
        import dataclasses

        from .dataframe import Column
        from .expressions import predicates as PR
        from .expressions.conditional import Coalesce

        visible = list(df._plan.output)  # pre-join schema for SELECT *
        subs = []
        for e in ([it.expr for it in stmt.items
                   if isinstance(it.expr, Expression)]
                  + ([stmt.where] if stmt.where is not None else [])):
            subs.extend(e.collect(
                lambda x: isinstance(x, ScalarSubquery)))
        replacements = {}
        by_semantic = {}  # ReuseSubquery: identical subqueries share a join
        for sq in subs:
            if id(sq) in replacements:
                continue
            q = sq.stmt
            if not isinstance(q, SelectStmt):
                raise SqlParseError(
                    "correlated scalar subquery must be a simple SELECT")
            corr_pairs, inner_conj, _ = self._split_correlation(
                q, "correlated scalar subquery")
            if not corr_pairs:
                # the evaluation pass only leaves a node here when it saw
                # outer references SOMEWHERE (items/where/having); with no
                # WHERE equality to decorrelate on, reject cleanly
                raise SqlParseError(
                    "correlated scalar subquery must correlate through "
                    "AND-connected equality predicates in its WHERE "
                    "clause (correlation in the SELECT list or HAVING "
                    "has no join rewrite)")
            if len(q.items) != 1 or isinstance(q.items[0].expr, Star):
                raise SqlParseError(
                    "scalar subquery must select exactly one expression")
            item = q.items[0].expr
            if not _has_agg(item):
                raise SqlParseError(
                    "correlated scalar subquery must be an aggregate "
                    "(that is what guarantees one value per outer row); "
                    "rewrite other shapes as a join")
            if q.group_by or q.group_by_mode or q.having is not None \
                    or q.limit is not None or q.offset:
                raise SqlParseError(
                    "correlated scalar subquery supports a single "
                    "aggregate over AND-connected equality correlation "
                    "only (no GROUP BY/HAVING/LIMIT)")
            sem = _subquery_semantic_key(q)
            if sem is not None and sem in by_semantic:
                replacements[id(sq)] = by_semantic[sem]
                continue
            is_count = _count_only_agg(item)
            if _has_count(item) and not is_count:
                raise SqlParseError(
                    "COUNT inside a compound correlated scalar subquery "
                    "is not supported (empty groups would need per-outer-"
                    "row evaluation); use a plain count(...) subquery")
            key_items = [SelectItem(ie, f"__ck{i}")
                         for i, (_, ie) in enumerate(corr_pairs)]
            q2 = dataclasses.replace(
                q, where=_and_all(inner_conj),
                items=key_items + [SelectItem(item, "__sval")],
                group_by=[ie for _, ie in corr_pairs],
                order_by=[], distinct=False, limit=None, offset=None)
            inner = self._fresh(self._build_sub(q2, ctes))
            out = inner._plan.output
            keys, val = out[:len(corr_pairs)], out[len(corr_pairs)]
            cond = None
            for (oe, _), k in zip(corr_pairs, keys):
                o = _resolve_or_err(self._bind_quals(oe, scope), df._plan)
                term = PR.EqualTo(o, k)
                cond = term if cond is None else PR.And(cond, term)
            df = df.join(inner, on=Column(cond), how="left")
            rep: Expression = val
            if is_count:
                # the COUNT bug: an empty correlation group has no row in
                # the grouped subquery, but count() over it must be 0
                rep = Coalesce(val, Literal(0))
            replacements[id(sq)] = rep
            if sem is not None:
                by_semantic[sem] = rep
        if not replacements:
            return df, stmt, None

        def repl(x):
            return replacements.get(id(x))

        def item_sub(it):
            if isinstance(it.expr, Star):
                return it
            new = it.expr.transform(repl)
            if it.alias is None and isinstance(it.expr, ScalarSubquery) \
                    and new is not it.expr:
                # Spark names an unaliased scalar subquery column
                # scalarsubquery(); never leak the internal __sval name
                new = Alias(new, "scalarsubquery()")
            return SelectItem(new, it.alias)

        stmt = dataclasses.replace(
            stmt,
            items=[item_sub(it) for it in stmt.items],
            where=(stmt.where.transform(repl)
                   if stmt.where is not None else None))
        return df, stmt, visible

    def _apply_subquery_predicate(self, df, pred, negated: bool,
                                  scope, ctes):
        """Rewrite one EXISTS/IN subquery predicate into a semi/anti join
        (Spark's RewritePredicateSubquery)."""
        from . import functions as F
        from .dataframe import Column
        from .expressions import predicates as PR

        if isinstance(pred, InSubquery):
            inner = self._fresh(self._build_sub(pred.stmt, ctes))
            if len(inner._plan.output) != 1:
                raise SqlParseError(
                    "IN subquery must return exactly one column")
            key = Column(inner._plan.output[0])
            needle = Column(_resolve_or_err(pred.children[0], df._plan))
            if not negated:
                return df.join(inner, on=needle == key, how="left_semi")
            # null-aware NOT IN (3-valued logic): a null needle is
            # disqualified only when the subquery has rows (empty set:
            # NOT IN is TRUE even for null); ANY null in the subquery
            # result disqualifies every row
            df = df.join(inner.limit(1), on=needle.isNull(),
                         how="left_anti")
            nonnull = inner.filter(key.isNotNull())
            df = df.join(nonnull,
                         on=needle == Column(nonnull._plan.output[0]),
                         how="left_anti")
            nulls = inner.filter(key.isNull()).limit(1)
            return df.join(nulls, on=F.lit(True), how="left_anti")

        # EXISTS: extract equality correlation (inner.col = outer.col via
        # outer-alias-qualified references) into join keys
        q = pred.stmt
        corr_pairs, inner_conj, mixed = self._split_correlation(
            q, "correlated EXISTS", allow_mixed=True)
        if corr_pairs or mixed:
            import dataclasses
            if q.group_by or q.having is not None or q.group_by_mode:
                raise SqlParseError(
                    "correlated EXISTS with GROUP BY/HAVING is not "
                    "supported — aggregate in a FROM subquery instead")
            # LIMIT/OFFSET in a correlated EXISTS are per-OUTER-row in SQL
            # semantics; after decorrelation they would apply globally and
            # drop join keys.  LIMIT n>0 is a no-op for EXISTS; LIMIT 0
            # means the subquery is always empty.
            if q.offset:
                raise SqlParseError(
                    "correlated EXISTS with OFFSET is not supported (it "
                    "is per-outer-row and has no join rewrite)")
            limit = q.limit
            # mixed conjuncts (non-equality correlation, TPC-H q21): lift
            # each maximal inner-only subexpression into the projection
            # and fold the rewritten predicate into the join's residual
            # condition — the same plan Spark builds (semi/anti hash join
            # with an extra non-equi condition)
            units: list = []
            mixed_rw = [self._rewrite_mixed_conjunct(c, q, units)
                        for c in mixed]
            q2 = dataclasses.replace(
                q,
                where=_and_all(inner_conj),
                items=[SelectItem(ie, f"__corr{i}")
                       for i, (_, ie) in enumerate(corr_pairs)]
                + [SelectItem(u, f"__nq{i}")
                   for i, u in enumerate(units)],
                order_by=[], distinct=False, limit=None, offset=None)
            if limit is not None and limit <= 0:
                return df.filter(F.lit(negated))
            inner = self._fresh(self._build_sub(q2, ctes))
            unit_outs = inner._plan.output[len(corr_pairs):
                                           len(corr_pairs) + len(units)]
            cond = None
            for i, (oe, _) in enumerate(corr_pairs):
                outer_col = Column(_resolve_or_err(
                    self._bind_quals(oe, scope), df._plan))
                term = outer_col == Column(inner._plan.output[i])
                cond = term if cond is None else cond & term
            for c in mixed_rw:
                bound = c.transform(
                    lambda x: unit_outs[x.idx]
                    if isinstance(x, _InnerUnit) else None)
                term = Column(_resolve_or_err(
                    self._bind_quals(bound, scope), df._plan))
                cond = term if cond is None else cond & term
        else:
            # existence is decided by ONE surviving row
            inner = self._fresh(self._build_sub(q, ctes).limit(1))
            cond = F.lit(True)
        return df.join(inner, on=cond,
                       how="left_anti" if negated else "left_semi")

    def _apply_embedded_subqueries(self, df, conjuncts, scope, ctes):
        """[NOT] IN / EXISTS predicates nested under OR/CASE: the
        existence-join rewrite (Spark's RewritePredicateSubquery
        ExistenceJoin form, reference ``ExistenceJoin.scala``).  Each
        subquery contributes marker columns — a LEFT OUTER join against
        its DISTINCT keys plus, for null-aware IN, a one-row aggregate of
        (count(*), count(key)) cross-joined in — and the predicate node
        is replaced by a boolean expression over the markers with exact
        three-valued semantics:

            IN  =  TRUE   when a key matched
                   FALSE  when the subquery is empty
                   NULL   when the needle is null, or no match and the
                          subquery result contains a null
                   FALSE  otherwise

        so ``NOT (x IN (...))`` filters correctly too.  The helper
        columns are projected away after the filter, restoring the
        pre-rewrite schema."""
        from . import functions as F
        from . import plan as P
        from .dataframe import Column, DataFrame

        visible = tuple(df._plan.output)
        k_counter = [0]

        def attr_by_name(frame, name):
            for a in frame._plan.output:
                if a.name == name:
                    return Column(a)
            raise AssertionError(name)

        def rewrite(e: Expression) -> Expression:
            nonlocal df
            if isinstance(e, InSubquery):
                k = k_counter[0]
                k_counter[0] += 1
                inner = self._fresh(self._build_sub(e.stmt, ctes))
                if len(inner._plan.output) != 1:
                    raise SqlParseError(
                        "IN subquery must return exactly one column")
                key = Column(inner._plan.output[0])
                needle = Column(_resolve_or_err(
                    self._bind_quals(e.children[0], scope), df._plan))
                keys = inner.select(key.alias(f"__exk{k}"),
                                    F.lit(True).alias(f"__exm{k}")
                                    ).distinct()
                flags = inner.agg(
                    F.count(F.lit(1)).alias(f"__exc{k}"),
                    F.count(key).alias(f"__exn{k}"))
                df = df.join(
                    keys, on=needle == Column(keys._plan.output[0]),
                    how="left")
                df = df.crossJoin(flags)
                m = attr_by_name(df, f"__exm{k}")
                cnt = attr_by_name(df, f"__exc{k}")
                cntk = attr_by_name(df, f"__exn{k}")
                null_b = F.lit(None).cast("boolean")
                val = (F.when(m.isNotNull(), F.lit(True))
                       .when(cnt == 0, F.lit(False))
                       .when(needle.isNull(), null_b)
                       .when(cnt > cntk, null_b)
                       .otherwise(F.lit(False)))
                return val.expr
            if isinstance(e, ExistsSubquery):
                k = k_counter[0]
                k_counter[0] += 1
                q = e.stmt
                corr_pairs, inner_conj, mixed = self._split_correlation(
                    q, "correlated EXISTS", allow_mixed=True)
                if mixed:
                    raise SqlParseError(
                        "non-equality-correlated EXISTS is only supported "
                        "as an AND-connected top-level WHERE predicate")
                if corr_pairs:
                    import dataclasses
                    if q.group_by or q.having is not None \
                            or q.group_by_mode:
                        raise SqlParseError(
                            "correlated EXISTS with GROUP BY/HAVING is "
                            "not supported — aggregate in a FROM "
                            "subquery instead")
                    # LIMIT/OFFSET are per-OUTER-row in a correlated
                    # EXISTS; after decorrelation they would apply
                    # globally and drop join keys (same guard as the
                    # top-level rewrite above).  LIMIT n>0 is a no-op for
                    # EXISTS; LIMIT <=0 makes the subquery always empty,
                    # so the marker is constant FALSE.
                    if q.offset:
                        raise SqlParseError(
                            "correlated EXISTS with OFFSET is not "
                            "supported (it is per-outer-row and has no "
                            "join rewrite)")
                    if q.limit is not None and q.limit <= 0:
                        return F.lit(False).expr
                    q2 = dataclasses.replace(
                        q, where=_and_all(inner_conj),
                        items=[SelectItem(ie, f"__exq{k}_{i}")
                               for i, (_, ie) in enumerate(corr_pairs)],
                        order_by=[], distinct=False, limit=None,
                        offset=None)
                    inner = self._fresh(self._build_sub(q2, ctes))
                    keys = inner.select(
                        *[Column(a).alias(f"__exk{k}_{i}")
                          for i, a in enumerate(inner._plan.output)],
                        F.lit(True).alias(f"__exm{k}")).distinct()
                    cond = None
                    for i, (oe, _) in enumerate(corr_pairs):
                        outer_col = Column(_resolve_or_err(
                            self._bind_quals(oe, scope), df._plan))
                        term = outer_col == Column(keys._plan.output[i])
                        cond = term if cond is None else cond & term
                    df = df.join(keys, on=cond, how="left")
                    return attr_by_name(df, f"__exm{k}").isNotNull().expr
                flags = self._fresh(self._build_sub(q, ctes)).limit(1).agg(
                    F.count(F.lit(1)).alias(f"__exc{k}"))
                df = df.crossJoin(flags)
                return (attr_by_name(df, f"__exc{k}") > 0).expr
            if not e.children:
                return e
            return e.with_children(tuple(rewrite(c) for c in e.children))

        new_cond = _and_all([rewrite(c) for c in conjuncts])
        df = DataFrame(P.Filter(_resolve_or_err(new_cond, df._plan),
                                df._plan), self.session)
        return DataFrame(P.Project(visible, df._plan), self.session)

    def _plan_comma_joins(self, stmt: "SelectStmt", ctes, scope):
        """Join planning for a pure comma/CROSS FROM list — the analog of
        Spark's PushPredicateThroughJoin + ReorderJoin, which run before
        the reference plugin sees the plan (its GpuShuffledHashJoinExec
        receives already-planned equi joins).

        Splits the WHERE into conjuncts; pushes single-relation ones
        beneath the joins; uses multi-relation conjuncts as inner-join
        conditions, joining relations in connected order (greedy, driven
        by equality conjuncts) so no unfiltered cross product ever
        materializes; anything unplaceable (subquery predicates,
        ambiguous references) stays in the residual WHERE.  The order is
        connectivity alone; the planner reorders a star's dimensions by
        the share of the fact each keeps (``sql/join_order.py``).  Returns
        (joined df, stmt with the consumed conjuncts removed)."""
        import dataclasses

        from . import plan as P
        from .dataframe import Column, DataFrame
        from .expressions import predicates as PR
        from .functions import _UnresolvedAttribute

        rels: List[str] = []

        def add(ref):
            rdf, ralias = self._resolve_relation(ref, ctes)
            key = ralias.lower()
            if key in scope:
                raise SqlParseError(f"duplicate relation alias {ralias!r}")
            scope[key] = rdf
            rels.append(key)

        add(stmt.from_)
        for step in stmt.joins:
            add(step.right)

        col_owners: Dict[str, set] = {}
        for a in rels:
            for attr in scope[a]._plan.output:
                col_owners.setdefault(attr.name.lower(), set()).add(a)

        def conj_aliases(c):
            """Relations a conjunct references, or None when a reference
            cannot be attributed to exactly one relation (unknown alias,
            ambiguous or missing bare name) — those conjuncts stay in
            the residual WHERE where normal resolution reports errors."""
            out = set()
            for n in c.collect(lambda x: isinstance(
                    x, (UnresolvedQualified, _UnresolvedAttribute))):
                if isinstance(n, UnresolvedQualified):
                    if n.qualifier.lower() not in scope:
                        return None
                    out.add(n.qualifier.lower())
                else:
                    owners = col_owners.get(n.name.lower(), set())
                    if len(owners) != 1:
                        return None
                    out.add(next(iter(owners)))
            return out

        # the _build_select WHERE guards run only on the residual; pushed
        # conjuncts must fail just as cleanly here
        if stmt.where is not None:
            if _has_agg(stmt.where):
                raise SqlParseError(
                    "aggregate functions are not allowed in WHERE")
            if _has_window(stmt.where):
                raise SqlParseError(
                    "window functions are not allowed in WHERE")

        residual: List[Expression] = []
        singles: Dict[str, List[Expression]] = {a: [] for a in rels}
        multis: List[Tuple[Expression, set]] = []
        conjs = _split_and(stmt.where) if stmt.where is not None else []
        for c in conjs:
            if c.collect(lambda x: isinstance(
                    x, (ExistsSubquery, InSubquery, ScalarSubquery))):
                residual.append(c)
                continue
            al = conj_aliases(c)
            if not al:
                residual.append(c)
            elif len(al) == 1:
                singles[next(iter(al))].append(c)
            else:
                multis.append((c, al))

        for a in rels:
            if singles[a]:
                rel = scope[a]
                pred = None
                for c in singles[a]:
                    b = _resolve_or_err(self._bind_quals(c, scope),
                                        rel._plan)
                    pred = b if pred is None else PR.And(pred, b)
                # Filter preserves the child's output attributes, so
                # join conditions bound against the unfiltered plan stay
                # valid
                scope[a] = DataFrame(P.Filter(pred, rel._plan),
                                     self.session)

        joined = {rels[0]}
        df = scope[rels[0]]
        remaining = rels[1:]
        used = [False] * len(multis)
        while remaining:
            pick = None
            for want_eq in (True, False):
                for a in remaining:
                    if any(not used[i] and a in al
                           and al <= joined | {a}
                           and (isinstance(c, PR.EqualTo) or not want_eq)
                           for i, (c, al) in enumerate(multis)):
                        pick = a
                        break
                if pick is not None:
                    break
            connected = pick is not None
            if pick is None:
                pick = remaining[0]
            conds = []
            for i, (c, al) in enumerate(multis):
                if not used[i] and al <= joined | {pick}:
                    used[i] = True
                    conds.append(self._bind_quals(c, scope))
            rdf = scope[pick]
            if connected and conds:
                cond = conds[0]
                for c in conds[1:]:
                    cond = PR.And(cond, c)
                df = df.join(rdf, on=Column(cond), how="inner")
            else:
                df = df.crossJoin(rdf)
                for c in conds:  # subset-covered but disconnected
                    df = df.filter(Column(c))
            joined.add(pick)
            remaining = [a for a in remaining if a != pick]

        residual.extend(c for i, (c, _) in enumerate(multis)
                        if not used[i])
        # SELECT * must see columns in FROM-list order (SQL), not the
        # greedy join order — restore it with a (free) projection
        ordered = tuple(a for r in rels for a in scope[r]._plan.output)
        if ordered != tuple(df._plan.output):
            df = DataFrame(P.Project(ordered, df._plan), self.session)
        return df, dataclasses.replace(stmt, where=_and_all(residual)
                                       if residual else None)

    # --- SELECT -----------------------------------------------------------
    def _build_select(self, stmt: SelectStmt, ctes):
        from . import plan as P
        from .dataframe import Column, DataFrame

        stmt = self._eval_scalar_subqueries_stmt(stmt, ctes)
        for slot, e in ([("SELECT list", it.expr) for it in stmt.items]
                        + [("HAVING", stmt.having)]
                        + [("GROUP BY", g) for g in stmt.group_by]
                        + [("join condition", j.on) for j in stmt.joins]
                        + [("GROUPING SETS", g)
                           for s in stmt.grouping_sets_raw for g in s]
                        + [("ORDER BY", oi.expr) for oi in stmt.order_by]):
            if isinstance(e, Expression) and e.collect(
                    lambda x: isinstance(x, (ExistsSubquery, InSubquery))):
                raise SqlParseError(
                    f"EXISTS/IN subqueries are not supported in the {slot}"
                    " — only as AND-connected WHERE predicates")
        for j in stmt.joins:
            if isinstance(j.on, Expression) and j.on.collect(
                    lambda x: isinstance(x, ScalarSubquery)):
                raise SqlParseError(
                    "correlated scalar subqueries are only supported in "
                    "the WHERE clause and SELECT list (found in join "
                    "condition)")
        scope: Dict[str, Any] = {}      # alias -> DataFrame
        if stmt.from_ is None:
            df = self.session.range(1)
        elif stmt.joins and all(s.how == "cross" and s.on is None
                                and not s.using for s in stmt.joins):
            # comma-FROM (`FROM a, b, c WHERE ...`) — the TPC-H query
            # texts' surface.  Naive left-to-right cross joins explode
            # (part x supplier x partsupp x nation x region before any
            # filter); plan them instead (see _plan_comma_joins).
            df, stmt = self._plan_comma_joins(stmt, ctes, scope)
        else:
            df, alias = self._resolve_relation(stmt.from_, ctes)
            scope[alias.lower()] = df
            for step in stmt.joins:
                rdf, ralias = self._resolve_relation(step.right, ctes)
                if ralias.lower() in scope:
                    raise SqlParseError(
                        f"duplicate relation alias {ralias!r}")
                scope[ralias.lower()] = rdf
                if step.using:
                    df = df.join(rdf, on=list(step.using), how=step.how)
                elif step.on is not None:
                    cond = self._bind_quals(step.on, scope)
                    df = df.join(rdf, on=Column(cond), how=step.how)
                else:
                    if step.how not in ("cross", "inner"):
                        raise SqlParseError(
                            f"{step.how} join requires ON or USING")
                    df = df.crossJoin(rdf)

        for lv in stmt.lateral_views:
            df = self._apply_lateral_view(df, lv, scope)
        df, stmt, star_visible = self._decorrelate_scalar_subqueries(
            df, stmt, scope, ctes)
        for slot, e in ([("HAVING", stmt.having)]
                        + [("GROUP BY", g) for g in stmt.group_by]
                        + [("join condition", j.on) for j in stmt.joins]
                        + [("GROUPING SETS", g)
                           for sset in stmt.grouping_sets_raw for g in sset]
                        + [("ORDER BY", oi.expr) for oi in stmt.order_by]):
            if isinstance(e, Expression) and e.collect(
                    lambda x: isinstance(x, ScalarSubquery)):
                raise SqlParseError(
                    "correlated scalar subqueries are only supported in "
                    f"the WHERE clause and SELECT list (found in {slot})")

        if stmt.where is not None:
            cond = self._bind_quals(stmt.where, scope)
            if _has_agg(cond):
                raise SqlParseError(
                    "aggregate functions are not allowed in WHERE")
            if _has_window(cond):
                raise SqlParseError(
                    "window functions are not allowed in WHERE")
            plain, sub_preds, embedded = _split_subquery_predicates(cond)
            if plain is not None:
                df = DataFrame(P.Filter(_resolve_or_err(plain, df._plan),
                                        df._plan), self.session)
            for pred, negated in sub_preds:
                df = self._apply_subquery_predicate(df, pred, negated,
                                                    scope, ctes)
            if embedded:
                df = self._apply_embedded_subqueries(df, embedded, scope,
                                                     ctes)

        # resolve select list against the (joined, filtered) frame
        items: List[Tuple[str, Expression]] = []
        for it in stmt.items:
            if isinstance(it.expr, Star):
                if it.expr.qualifier is not None:
                    src = scope.get(it.expr.qualifier.lower())
                    if src is None:
                        raise SqlParseError(
                            f"unknown relation {it.expr.qualifier!r} "
                            "for qualified star")
                    for a in src._plan.output:
                        items.append((a.name, a))
                else:
                    # a decorrelation join widened df with internal
                    # __ck*/__sval columns; * sees the pre-join schema
                    for a in (star_visible if star_visible is not None
                              else df._plan.output):
                        items.append((a.name, a))
                continue
            e = self._bind_quals(it.expr, scope)
            e = _resolve_or_err(e, df._plan)
            items.append((it.alias or _auto_name(it.expr, e), e))

        having = None
        if stmt.having is not None:
            having = _resolve_or_err(self._bind_quals(stmt.having, scope),
                                     df._plan)

        aggregating = bool(stmt.group_by) or having is not None or \
            any(_has_agg(e) for _, e in items)

        pre_orders = None
        if aggregating:
            df, items, pre_orders = self._build_aggregate(
                df, stmt, items, having, scope)
        return self._finish(df, items, stmt, scope, pre_orders)

    # --- aggregation ------------------------------------------------------
    def _build_aggregate(self, df, stmt, items, having, scope):
        from . import plan as P
        from .dataframe import DataFrame, _resolve_expr

        # group expressions: ordinals, select aliases, or raw expressions
        def resolve_group(g) -> Expression:
            if isinstance(g, int):
                if not (1 <= g <= len(items)):
                    raise SqlParseError(
                        f"GROUP BY position {g} is out of range")
                ge = items[g - 1][1]
            else:
                ge = self._bind_quals(g, scope)
                try:
                    ge = _resolve_expr(ge, df._plan)
                except KeyError:
                    # select-list alias (GROUP BY alias) — Spark resolves
                    # the child column first, the alias second
                    name = ge.sql().lower() if not isinstance(
                        ge, AttributeReference) else ge.name.lower()
                    match = [e for n, e in items if n.lower() == name]
                    if not match:
                        raise SqlParseError(
                            f"cannot resolve GROUP BY expression "
                            f"{g.sql()!r}") from None
                    ge = match[0]
            if _has_agg(ge):
                raise SqlParseError(
                    "aggregate functions are not allowed in GROUP BY")
            return ge

        # base keys (GROUP BY a, ... before/around any construct) are
        # included in EVERY grouping set (Spark 3 partial grouping sets)
        groups: List[Expression] = [resolve_group(g) for g in stmt.group_by]
        base_idx = frozenset(range(len(groups)))
        explicit_sets = None
        if stmt.group_by_mode:
            from .dataframe import cube_sets, rollup_sets
            keys_seen: Dict[Tuple, int] = {
                g.semantic_key(): i for i, g in enumerate(groups)}

            def key_index(ge: Expression) -> int:
                k = ge.semantic_key()
                if k not in keys_seen:
                    keys_seen[k] = len(groups)
                    groups.append(ge)
                return keys_seen[k]

            if stmt.group_by_mode == "sets":
                # GROUPING SETS ((a,b),(a),()) — keys = union of the sets
                # in first-appearance order; each set selects positions
                explicit_sets = [
                    base_idx | frozenset(key_index(resolve_group(g))
                                         for g in raw)
                    for raw in stmt.grouping_sets_raw]
            else:
                cidx = [key_index(resolve_group(g))
                        for g in stmt.grouping_sets_raw[0]]
                subs = rollup_sets(len(cidx)) \
                    if stmt.group_by_mode == "rollup" else cube_sets(len(cidx))
                explicit_sets = [
                    base_idx | frozenset(cidx[i] for i in s) for s in subs]

        group_keys = [g.semantic_key() for g in groups]
        group_outs: List[Expression] = []
        group_attrs: List[AttributeReference] = []
        gid_out = None
        resolve_marks = None
        if stmt.group_by_mode:
            # shared Expand lowering + grouping()/grouping_id() marker
            # resolution (dataframe.grouping_sets_expand)
            from .dataframe import grouping_mark_resolver, grouping_sets_expand
            expanded, gkeys, (pos_attr, gid_attr) = grouping_sets_expand(
                df._plan, tuple(groups), explicit_sets)
            df = DataFrame(expanded, self.session)
            resolve_marks = grouping_mark_resolver(tuple(groups), gid_attr)
            items = [(n, e.transform(resolve_marks)) for n, e in items]
            if having is not None:
                having = having.transform(resolve_marks)
            for i, g in enumerate(groups):
                name = g.name if isinstance(g, AttributeReference) \
                    else f"__group_{i}"
                a = Alias(gkeys[i], name)
                group_outs.append(a)
                group_attrs.append(a.to_attribute())
            groups = list(gkeys) + [pos_attr, gid_attr]
            gid_out = gid_attr
        else:
            for i, g in enumerate(groups):
                if isinstance(g, AttributeReference):
                    group_outs.append(g)
                    group_attrs.append(g)
                else:
                    a = Alias(g, f"__group_{i}")
                    group_outs.append(a)
                    group_attrs.append(a.to_attribute())

        agg_aliases: Dict[Tuple, Alias] = {}

        def strip(e: Expression) -> Expression:
            for key, attr in zip(group_keys, group_attrs):
                if e.semantic_key() == key:
                    return attr
            if isinstance(e, WindowExpression):
                # windows evaluate AFTER aggregation (Spark's
                # ExtractWindowExpressions over an Aggregate): the window
                # node stays in the post-agg projection; its function's
                # OWN aggregate is the window computation, while nested
                # aggregates and group keys inside it resolve against the
                # Aggregate output (avg(sum(x)) OVER (PARTITION BY
                # grouping(k), ...) — the spec-TPC-DS idiom)
                def strip_fn(fn: Expression) -> Expression:
                    if isinstance(fn, AggregateExpression):
                        return fn.with_children(
                            tuple(strip_fn(c) for c in fn.children))
                    if isinstance(fn, AggregateFunction):
                        return fn.with_children(
                            tuple(strip(c) for c in fn.children))
                    return strip(fn)
                rest = tuple(strip(c) for c in e.children[1:])
                return e.with_children((strip_fn(e.children[0]),) + rest)
            if isinstance(e, (AggregateFunction, AggregateExpression)):
                key = e.semantic_key()
                if key not in agg_aliases:
                    agg_aliases[key] = Alias(e, f"__agg_{len(agg_aliases)}")
                return agg_aliases[key].to_attribute()
            if not e.children:
                return e
            return e.with_children(tuple(strip(c) for c in e.children))

        new_items = [(name, strip(e)) for name, e in items]
        if having is not None and _has_window(having):
            raise SqlParseError(
                "window functions are not allowed in HAVING")
        new_having = strip(having) if having is not None else None

        # ORDER BY must be stripped BEFORE the Aggregate plan is frozen so
        # aggregates that appear only in the sort (ORDER BY sum(x)) get
        # buffer slots too
        pre_orders: List[SortOrder] = []
        out_by_name = {n.lower(): e for n, e in reversed(new_items)}
        for oi in stmt.order_by:
            if isinstance(oi.expr, int):
                if not (1 <= oi.expr <= len(new_items)):
                    raise SqlParseError(
                        f"ORDER BY position {oi.expr} is out of range")
                target = new_items[oi.expr - 1][1]
            else:
                e = oi.expr
                if isinstance(e, AttributeReference) and getattr(
                        e, "_unresolved", False) and \
                        e.name.lower() in out_by_name:
                    target = out_by_name[e.name.lower()]
                else:
                    target = _resolve_or_err(
                        self._bind_quals(e, scope), df._plan)
                    if resolve_marks is not None:
                        # ORDER BY grouping_id()/grouping() in rollup/cube
                        target = target.transform(resolve_marks)
                    target = strip(target)
                    ok_ids = {a.expr_id for a in group_attrs}
                    ok_ids.update(al.expr_id for al in agg_aliases.values())
                    if gid_out is not None:
                        ok_ids.add(gid_out.expr_id)
                    for r in target.references():
                        if r.expr_id not in ok_ids:
                            raise SqlParseError(
                                f"ORDER BY column {r.name!r} must appear in "
                                "GROUP BY or be inside an aggregate "
                                "function")
            pre_orders.append(SortOrder(target, oi.ascending,
                                        oi.nulls_first))

        # every remaining column reference must be a group key or an
        # aggregate result
        allowed = {a.expr_id for a in group_attrs}
        allowed.update(al.expr_id for al in agg_aliases.values())
        if gid_out is not None:
            allowed.add(gid_out.expr_id)
        for name, e in new_items:
            for r in e.references():
                if r.expr_id not in allowed:
                    raise SqlParseError(
                        f"column {r.name!r} must appear in GROUP BY or be "
                        "inside an aggregate function")
        if new_having is not None:
            for r in new_having.references():
                if r.expr_id not in allowed:
                    raise SqlParseError(
                        f"HAVING column {r.name!r} must appear in GROUP BY "
                        "or be inside an aggregate function")

        extra = (gid_out,) if gid_out is not None else ()
        plan = P.Aggregate(tuple(groups),
                           tuple(group_outs) + extra
                           + tuple(agg_aliases.values()),
                           df._plan)
        adf = DataFrame(plan, self.session)
        if new_having is not None:
            adf = DataFrame(P.Filter(new_having, adf._plan), self.session)
        return adf, new_items, pre_orders

    # --- ORDER BY / DISTINCT / LIMIT tail ---------------------------------
    def _finish(self, df, items, stmt: SelectStmt, scope,
                pre_orders: Optional[List[SortOrder]] = None):
        from . import plan as P
        from .dataframe import DataFrame, _resolve_expr

        if pre_orders is not None:
            orders = pre_orders
        else:
            orders = []
            out_by_name = {}
            for n, e in items:
                out_by_name.setdefault(n.lower(), e)
            for oi in stmt.order_by:
                if isinstance(oi.expr, int):
                    if not (1 <= oi.expr <= len(items)):
                        raise SqlParseError(
                            f"ORDER BY position {oi.expr} is out of range")
                    target = items[oi.expr - 1][1]
                else:
                    e = oi.expr
                    name = e.name.lower() if isinstance(
                        e, AttributeReference) and getattr(
                        e, "_unresolved", False) else None
                    if name is not None and name in out_by_name:
                        target = out_by_name[name]
                    else:
                        target = _resolve_or_err(self._bind_quals(e, scope),
                                                 df._plan)
                orders.append(SortOrder(target, oi.ascending,
                                        oi.nulls_first))

        project_exprs = tuple(
            e if (isinstance(e, AttributeReference) and e.name == n)
            else Alias(e, n)
            for n, e in items)
        out_attrs = [pe if isinstance(pe, AttributeReference)
                     else pe.to_attribute() for pe in project_exprs]

        def make_project(exprs, plan):
            # same window/generator extraction hook as DataFrame.select
            from .dataframe import _extract_generators, _extract_windows
            exprs, plan = _extract_generators(tuple(exprs), plan)
            exprs, plan = _extract_windows(tuple(exprs), plan)
            return P.Project(tuple(exprs), plan)

        # rewrite order targets that exactly match a projected expression
        # to reference the projected output (post-projection sort)
        def to_output(e: Expression) -> Optional[Expression]:
            for pe, attr in zip(project_exprs, out_attrs):
                src = pe.child if isinstance(pe, Alias) else pe
                if e.semantic_key() == src.semantic_key():
                    return attr
            return None

        sortable_post = []
        needs_hidden = False
        for so in orders:
            mapped = to_output(so.child)
            if mapped is not None:
                sortable_post.append(SortOrder(mapped, so.ascending,
                                               so.nulls_first))
            else:
                needs_hidden = True
                sortable_post.append(so)

        if stmt.distinct and needs_hidden:
            raise SqlParseError(
                "ORDER BY with SELECT DISTINCT must reference select-list "
                "expressions")

        if not needs_hidden:
            result = DataFrame(make_project(project_exprs, df._plan),
                               self.session)
            if stmt.distinct:
                result = result.distinct()
            if sortable_post:
                result = DataFrame(
                    P.Sort(tuple(sortable_post), True, result._plan),
                    self.session)
        else:
            # project select list + hidden sort keys, sort, project away
            hidden = []
            full_orders = []
            for so in sortable_post:
                if any(so.child.semantic_key() == a.semantic_key()
                       for a in out_attrs):
                    full_orders.append(so)
                    continue
                h = Alias(so.child, f"__sort_{len(hidden)}")
                hidden.append(h)
                full_orders.append(SortOrder(h.to_attribute(), so.ascending,
                                             so.nulls_first))
            wide = DataFrame(
                make_project(project_exprs + tuple(hidden), df._plan),
                self.session)
            sorted_df = DataFrame(P.Sort(tuple(full_orders), True,
                                         wide._plan), self.session)
            result = DataFrame(P.Project(tuple(out_attrs), sorted_df._plan),
                               self.session)

        if stmt.offset:
            lim = stmt.limit if stmt.limit is not None else (1 << 30)
            result = DataFrame(P.Limit(lim, stmt.offset, result._plan),
                               self.session)
        elif stmt.limit is not None:
            result = result.limit(stmt.limit)
        return result

    def _apply_order_limit(self, df, order_by, limit, offset, items):
        from . import plan as P
        from .dataframe import DataFrame
        if order_by:
            orders = []
            attrs = df._plan.output
            for oi in order_by:
                if isinstance(oi.expr, int):
                    if not (1 <= oi.expr <= len(attrs)):
                        raise SqlParseError(
                            f"ORDER BY position {oi.expr} is out of range")
                    target: Expression = attrs[oi.expr - 1]
                else:
                    target = _resolve_or_err(oi.expr, df._plan)
                orders.append(SortOrder(target, oi.ascending,
                                        oi.nulls_first))
            df = DataFrame(P.Sort(tuple(orders), True, df._plan),
                           self.session)
        if offset:
            lim = limit if limit is not None else (1 << 30)
            df = DataFrame(P.Limit(lim, offset, df._plan), self.session)
        elif limit is not None:
            df = df.limit(limit)
        return df

    # --- qualified-name binding ------------------------------------------
    def _bind_quals(self, e: Expression, scope) -> Expression:
        if isinstance(e, Star):
            raise SqlParseError("'*' is only valid in a select list")

        def walk(node: Expression) -> Expression:
            if isinstance(node, UnresolvedQualified):
                src = scope.get(node.qualifier.lower())
                if src is None:
                    raise SqlParseError(
                        f"unknown relation alias {node.qualifier!r} "
                        f"(known: {sorted(scope)})")
                for a in src._plan.output:
                    if a.name.lower() == node.name.lower():
                        return a
                raise SqlParseError(
                    f"column {node.name!r} not found in relation "
                    f"{node.qualifier!r}")
            if not node.children:
                return node
            return node.with_children(tuple(walk(c) for c in node.children))
        return walk(e)


def _as_string(e: Expression) -> Expression:
    """Implicit cast for the ``||`` operator (Spark casts both concat
    operands to string).  Unresolved refs keep the cast — string->string
    casting is the identity."""
    from .expressions.cast import Cast
    try:
        if e.data_type == T.STRING:
            return e
    except (NotImplementedError, SqlParseError):
        pass
    return Cast(e, T.STRING)


def _resolve_or_err(e: Expression, plan) -> Expression:
    """Name resolution with the module's error contract (SqlParseError,
    never a bare KeyError)."""
    from .dataframe import _resolve_expr
    try:
        return _resolve_expr(e, plan)
    except KeyError as exc:
        raise SqlParseError(str(exc.args[0]) if exc.args else str(exc)) \
            from None


def _split_and(e: Expression) -> List[Expression]:
    """Flatten a conjunction tree into its AND-connected conjuncts."""
    from .expressions.predicates import And
    if isinstance(e, And):
        return _split_and(e.children[0]) + _split_and(e.children[1])
    return [e]


def _and_all(conjuncts: Sequence[Expression]) -> Optional[Expression]:
    from .expressions.predicates import And
    out = None
    for c in conjuncts:
        out = c if out is None else And(out, c)
    return out


def _split_subquery_predicates(cond: Expression):
    """(plain_condition_or_None, [(marker, negated)], [embedded]) from a
    WHERE tree.  Top-level AND-connected markers get the efficient
    semi/anti join rewrite; conjuncts with subqueries embedded deeper
    (under OR, inside CASE/NOT) go to ``embedded`` for the existence-join
    rewrite (reference ``ExistenceJoin.scala``)."""
    from .expressions.predicates import Not
    plain: List[Expression] = []
    subs = []
    embedded: List[Expression] = []
    for c in _split_and(cond):
        inner = c.children[0] if isinstance(c, Not) else c
        if isinstance(inner, (ExistsSubquery, InSubquery)):
            subs.append((inner, isinstance(c, Not)))
            continue
        if c.collect(lambda x: isinstance(x, (ExistsSubquery, InSubquery))):
            embedded.append(c)
            continue
        plain.append(c)
    return _and_all(plain), subs, embedded


def _has_window(e: Expression) -> bool:
    return bool(e.collect(lambda n: isinstance(n, WindowExpression)))


def _has_agg(e: Expression) -> bool:
    """True if e contains a grouping aggregate (sum() OVER (...) is a
    window computation, not an aggregation — don't descend into specs)."""
    if isinstance(e, WindowExpression):
        return False
    if isinstance(e, (AggregateFunction, AggregateExpression)):
        return True
    return any(_has_agg(c) for c in e.children)


def _subquery_semantic_key(q):
    """Hashable identity for a correlated scalar subquery over simple
    table FROMs (ReuseSubquery analog); None = don't dedupe."""
    rels = []
    refs = ([q.from_] if q.from_ is not None else []) \
        + [j.right for j in q.joins]
    for r in refs:
        if not isinstance(r, TableRef) or r.path is not None:
            return None
        rels.append((r.name.lower(), (r.alias or "").lower()))
    try:
        return (tuple(rels),
                tuple((j.how,
                       j.on.sql() if isinstance(j.on, Expression) else "",
                       tuple(j.using or ()))
                      for j in q.joins),
                tuple(it.alias or "" for it in q.items),
                tuple(it.expr.sql() for it in q.items
                      if isinstance(it.expr, Expression)),
                q.where.sql() if q.where is not None else "")
    except Exception:
        return None


def _has_count(e: Expression) -> bool:
    from .expressions.aggregates import Count
    return bool(e.collect(lambda n: isinstance(n, Count)))


def _count_only_agg(e: Expression) -> bool:
    """e IS a bare count aggregate (possibly wrapped in the
    AggregateExpression distinct marker) — the shape whose empty-group
    result must be 0, not NULL, after decorrelation."""
    from .expressions.aggregates import AggregateExpression, Count
    if isinstance(e, Count):
        return True
    return isinstance(e, AggregateExpression) and isinstance(e.func, Count)


def _auto_name(raw: Expression, resolved: Expression) -> str:
    if isinstance(resolved, AttributeReference):
        return resolved.name
    if isinstance(resolved, Alias):
        return resolved.name
    return raw.sql()


def parse_query(session, sql: str):
    """``session.sql(...)`` entry point."""
    stmt = Parser(sql, udfs=getattr(session, "_hive_udfs", None)
                  ).parse_statement()
    if isinstance(stmt, CreateFunctionStmt):
        if not stmt.replace and stmt.name.lower() in session._hive_udfs:
            raise ValueError(
                f"function {stmt.name!r} already exists (use CREATE OR "
                f"REPLACE TEMPORARY FUNCTION)")
        session.register_hive_function(stmt.name, stmt.class_path)
        return session.create_dataframe(_empty_ddl_result())
    if isinstance(stmt, DropFunctionStmt):
        if session._hive_udfs.pop(stmt.name.lower(), None) is None \
                and not stmt.if_exists:
            raise ValueError(f"function not found: {stmt.name}")
        return session.create_dataframe(_empty_ddl_result())
    if isinstance(stmt, CreateTempViewStmt):
        if not stmt.replace and stmt.name.lower() in session._temp_views:
            raise ValueError(
                f"temp view {stmt.name!r} already exists (use CREATE OR "
                f"REPLACE TEMP VIEW)")
        df = QueryBuilder(session).build(stmt.stmt)
        df.createOrReplaceTempView(stmt.name)
        return session.create_dataframe(_empty_ddl_result())
    if isinstance(stmt, DropViewStmt):
        if session._temp_views.pop(stmt.name.lower(), None) is None \
                and not stmt.if_exists:
            raise ValueError(f"view not found: {stmt.name}")
        return session.create_dataframe(_empty_ddl_result())
    if isinstance(stmt, ShowTablesStmt):
        import pyarrow as pa
        names = sorted(session._temp_views)
        return session.create_dataframe(pa.table({
            "namespace": pa.array([""] * len(names), pa.string()),
            "tableName": pa.array(names, pa.string()),
            "isTemporary": pa.array([True] * len(names), pa.bool_()),
        }))
    if isinstance(stmt, DescribeTableStmt):
        import pyarrow as pa
        # session.table() is THE catalog resolution (same lookup, same
        # error) — don't fork it here
        attrs = session.table(stmt.name)._plan.output
        return session.create_dataframe(pa.table({
            "col_name": pa.array([a.name for a in attrs], pa.string()),
            "data_type": pa.array([a.dtype.simple_string() for a in attrs],
                                  pa.string()),
            "comment": pa.array([None] * len(attrs), pa.string()),
        }))
    return QueryBuilder(session).build(stmt)


def _empty_ddl_result():
    import pyarrow as pa
    return pa.schema([]).empty_table()
