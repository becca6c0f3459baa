"""Logical column pruning — the analog of Catalyst's ``ColumnPruning``, which
runs before the reference plugin ever sees a plan, so the reference never
carries a column nobody reads.  This engine owns its front end and does it
itself, at the head of ``Planner.plan``.

One top-down pass.  Each node is handed the positions of its output that its
parent reads and asks its children for those plus what its own expressions
reference.  Two kinds of node shrink: a leaf becomes a narrowed copy — an
in-memory one (``Relation``, ``CachedRelation``) over the SAME table and
partition objects (the scan's upload cache is keyed by them), a file scan
(``ScanRelation``) over the same paths and options with a ``read_schema`` of
the columns read, which ``FileScanExec`` then reads, decodes and uploads and
no other — and a ``Project`` or an ``Expand`` drops the expressions nobody
reads (the SQL front end puts a ``select *`` project over every relation and
every join).  Every other node keeps its own output and only passes the
requirement down; a node the rule does not know requires every column of its
children.

A reference is resolved the way ``bind_references`` binds it: by ``expr_id``
first, then by name — and by name every match is kept, so what binds first
after pruning is what bound first before.  References are collected from
every attribute of an expression, not only ``children`` (an aggregate's
FILTER clause is not among its children).

The rule returns a new tree and never mutates the one it is given: a
DataFrame collected twice plans twice, and a relation shared by the two
branches of a self-join gets two narrowings.  There is no conf key: the
decision is read from the plan.
"""

from __future__ import annotations

import copy
from typing import Dict, FrozenSet, Iterable, List, Sequence, Tuple

from .. import types as T
from . import plan as P
from .expressions.core import AttributeReference, Expression

Positions = FrozenSet[int]
#: (the pruned node, the positions of the original output it still has, in
#: order); the positions asked for are always among them
Pruned = Tuple[P.LogicalPlan, Tuple[int, ...]]


def prune_columns(plan: P.LogicalPlan) -> P.LogicalPlan:
    """``plan`` with every scan, of a table in memory or of files, narrowed
    to the columns the query reads.  The root keeps its whole output."""
    return _Pruner().prune(plan, _all(plan.output))[0]


def _all(attrs: Sequence) -> Positions:
    return frozenset(range(len(attrs)))


#: helper objects that hold expressions (a window spec, a frame) live here
_EXPRESSIONS = __package__ + ".expressions."


def _references(obj, out: List[AttributeReference], seen: set) -> None:
    """Every AttributeReference reachable from ``obj``: through children and
    through any other attribute that holds expressions (a FILTER clause, a
    window spec, a sort order).  Logical plans inside an expression are not
    entered: a subquery is planned, and pruned, on its own."""
    if isinstance(obj, (str, bytes, int, float, bool, type(None),
                        P.LogicalPlan)) or id(obj) in seen:
        return
    seen.add(id(obj))
    if isinstance(obj, AttributeReference):
        out.append(obj)
    elif isinstance(obj, (tuple, list, set, frozenset)):
        for x in obj:
            _references(x, out, seen)
    elif isinstance(obj, dict):
        for x in obj.values():
            _references(x, out, seen)
    elif isinstance(obj, (Expression, P.SortOrder)) or (
            type(obj).__module__.startswith(_EXPRESSIONS)
            and hasattr(obj, "__dict__")):
        _references(getattr(obj, "children", ()), out, seen)
        _references(vars(obj), out, seen)


def _needed(exprs: Iterable, attrs: Sequence[AttributeReference]
            ) -> Positions:
    """Positions of ``attrs`` that ``exprs`` would bind to."""
    refs: List[AttributeReference] = []
    _references(list(exprs), refs, set())
    ids: Dict[int, int] = {}
    names: Dict[str, List[int]] = {}
    for i, a in enumerate(attrs):
        ids.setdefault(a.expr_id, i)
        names.setdefault(a.name.lower(), []).append(i)
    need = set()
    for r in refs:
        if r.expr_id in ids:
            need.add(ids[r.expr_id])
        else:
            need.update(names.get(r.name.lower(), ()))
    return frozenset(need)


def _width(dtype) -> int:
    try:
        return T.to_arrow(dtype).bit_width
    except (ValueError, TypeError, NotImplementedError):
        return 1 << 20          # strings, binary, nested: never narrow


def _narrowest(candidates: Sequence) -> int:
    """Where nothing is read (``count(*)``) a batch still needs a column to
    carry its rows: the first of the narrowest type."""
    return min(range(len(candidates)),
               key=lambda i: _width(candidates[i].data_type))


class _Pruner:
    def __init__(self):
        #: a subtree shared by two parents that ask the same of it stays
        #: shared (the planner counts parents: planner._count_parents)
        self._memo: Dict[Tuple[int, Positions], Pruned] = {}

    def prune(self, node: P.LogicalPlan, required: Positions) -> Pruned:
        key = (id(node), required)
        got = self._memo.get(key)
        if got is None:
            rule = getattr(self, "_" + type(node).__name__, self._unknown)
            got = self._memo[key] = rule(node, required)
        return got

    # --- leaves -------------------------------------------------------------
    def _Relation(self, node, required: Positions) -> Pruned:
        out = node.output
        names = [a.name for a in out]
        if len(required) == len(out) or len(set(names)) < len(names):
            return node, tuple(range(len(out)))
        keep = sorted(required) or [_narrowest(out)]
        return node.narrowed([out[i] for i in keep]), tuple(keep)

    _CachedRelation = _ScanRelation = _Relation

    def _unknown(self, node, required: Positions) -> Pruned:
        """A leaf that is no table and no file (``Range``), a pandas node,
        anything added later: all of its own output, all of its children's."""
        kids = [self.prune(c, _all(c.output))[0] for c in node.children]
        return (_with_children(node, kids),
                tuple(range(len(node.output))))

    # --- nodes that shrink --------------------------------------------------
    def _Project(self, node, required: Positions) -> Pruned:
        keep = sorted(required) or [_narrowest(node.exprs)]
        exprs = tuple(node.exprs[i] for i in keep)
        child, _ = self.prune(node.child,
                              _needed(exprs, node.child.output))
        if child is node.child and len(exprs) == len(node.exprs):
            return node, tuple(keep)
        new = copy.copy(node)
        new.exprs, new.child, new.children = exprs, child, (child,)
        return new, tuple(keep)

    def _Expand(self, node, required: Positions) -> Pruned:
        """A project with several rows of expressions: the columns nobody
        reads go from every one of them."""
        keep = sorted(required) or [_narrowest(node.out_attrs)]
        rows = tuple(tuple(row[i] for i in keep) for row in node.projections)
        child, _ = self.prune(node.child, _needed(rows, node.child.output))
        if child is node.child and len(keep) == len(node.out_attrs):
            return node, tuple(keep)
        new = copy.copy(node)
        new.projections, new.child, new.children = rows, child, (child,)
        new.out_attrs = tuple(node.out_attrs[i] for i in keep)
        return new, tuple(keep)

    # --- nodes that hand their child's columns on ---------------------------
    def _passing(self, node, required: Positions, own: Iterable,
                 added: int = 0) -> Pruned:
        """``node``'s output is its child's, then ``added`` columns of its
        own making; ``own`` are the expressions it evaluates."""
        attrs = node.child.output
        below = frozenset(i for i in required if i < len(attrs))
        child, kept = self.prune(node.child, below | _needed(own, attrs))
        return (_with_children(node, [child]),
                kept + tuple(range(len(attrs), len(attrs) + added)))

    def _Filter(self, node, required):
        return self._passing(node, required, [node.condition])

    def _Sort(self, node, required):
        return self._passing(node, required, node.orders)

    def _Repartition(self, node, required):
        return self._passing(node, required, node.exprs)

    def _Limit(self, node, required):
        return self._passing(node, required, ())

    _Sample = _Limit

    def _Window(self, node, required):
        return self._passing(
            node, required,
            [node.window_exprs, node.partition_spec, node.order_spec],
            added=len(node.window_exprs))

    def _Generate(self, node, required):
        return self._passing(node, required, [node.generator],
                             added=len(node.gen_output))

    def _Aggregate(self, node, required):
        """All of its output is its own making, and all of it is kept."""
        child, _ = self.prune(
            node.child,
            _needed([node.grouping, node.aggregates], node.child.output))
        return (_with_children(node, [child]),
                tuple(range(len(node.output))))

    # --- two children and more ------------------------------------------------
    def _Join(self, node, required: Positions) -> Pruned:
        if node.how not in ("inner", "cross", "left", "right", "full",
                            "left_semi", "left_anti"):
            return self._unknown(node, required)
        lo, ro = node.left.output, node.right.output
        # a semi or anti join hands on its left child's columns alone
        both = node.how not in ("left_semi", "left_anti")
        cond = [node.condition] if node.condition is not None else []
        need_l = (frozenset(i for i in required if i < len(lo))
                  | _needed([node.left_keys] + cond, lo))
        need_r = _needed([node.right_keys] + cond, ro)
        if both:
            need_r |= frozenset(i - len(lo) for i in required
                                if i >= len(lo))
        left, kept_l = self.prune(node.left, need_l)
        right, kept_r = self.prune(node.right, need_r)
        kept = kept_l
        if both:
            kept += tuple(len(lo) + i for i in kept_r)
        if left is node.left and right is node.right:
            return node, kept
        new = copy.copy(node)
        new.left, new.right, new.children = left, right, (left, right)
        return new, kept

    def _Union(self, node, required: Positions) -> Pruned:
        """Children line up by position, so all of them hand on exactly the
        same positions, or everything."""
        keep = tuple(sorted(required) or [_narrowest(node.output)])
        got = [self.prune(c, frozenset(keep)) for c in node.children]
        if any(kept != keep for _c, kept in got):
            keep = tuple(range(len(node.output)))
            got = [self.prune(c, frozenset(keep)) for c in node.children]
        return _with_children(node, [c for c, _kept in got]), keep


def _with_children(node: P.LogicalPlan, kids: Sequence[P.LogicalPlan]
                   ) -> P.LogicalPlan:
    """``node`` over ``kids``: itself where none changed, else a shallow copy
    whose every attribute that held an old child holds the new one (two
    attributes holding the same child are asked the same, so get the same)."""
    if all(n is o for n, o in zip(kids, node.children)):
        return node
    swap = {id(o): n for o, n in zip(node.children, kids)}
    new = copy.copy(node)
    for name, value in vars(node).items():
        if isinstance(value, P.LogicalPlan):
            setattr(new, name, swap.get(id(value), value))
        elif isinstance(value, tuple) and value and all(
                isinstance(x, P.LogicalPlan) for x in value):
            setattr(new, name, tuple(swap.get(id(x), x) for x in value))
    return new
