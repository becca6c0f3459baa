"""Star-join ordering: the dimensions of a star are joined in the order of
the share of the fact table's rows each one keeps, the most selective first
— the part of Catalyst's ``ReorderJoin`` / cost-based join reordering that a
star needs, applied at the head of ``Planner.plan`` beside column pruning.

A *run* is a left-deep chain of inner equi-joins.  Its left-most child is the
*fact*; each join's right child is a *dimension*.  The rule reorders the
longest bottom part of a run in which every join references, in its keys and
its residual condition, the fact's columns and its own dimension's alone: any
order of such joins gives the same rows.  It leaves alone outer, semi and
anti joins, a join that references an earlier dimension (a chain such as
TPC-H Q3's ``customer -> orders -> lineitem``) and every run with a share it
cannot estimate.  A reordered run hands on its columns in the order it had,
through a ``Project``.

The estimate, for a dimension ``d`` joined by ``fact.fk = d.k``, is the usual
uniform-foreign-key assumption limited to the key range the fact uses:

    rows of d that pass d's filters with k in [min fk, max fk]
    ----------------------------------------------------------
               rows of d with k in [min fk, max fk]

Both counts come from the dimension's own filters, evaluated with the
engine's expressions on numpy over the relation's host table; ``min fk`` and
``max fk`` from the fact's host column.  An unfiltered dimension keeps 1.
Only a dimension that is an in-memory ``Relation`` under filters and
column-renaming projects, joined on one column of it, and a fact column that
traces to an in-memory ``Relation`` the same way, can be estimated; anything
else (a file scan, a cached relation, a subquery, an aggregate, a join)
leaves its run in the order it was given.  Ties keep that order too.

Estimates are memoised per table, predicate and column (``_ESTIMATES``,
cleared with the kernel cache), so a warm collect re-plans without reading a
table.  There is no conf key: the decision is read from the plan and the
data.
"""

from __future__ import annotations

import contextlib
import copy
import threading
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow.compute as pc

from . import plan as P
from .column_pruning import _with_children
from .expressions.core import (Alias, AttributeReference, EvalContext,
                               Expression, bind_references)
from .expressions.predicates import And

#: (tag, id(table), ...) -> (weakref to the table, value); bounded like the
#: join selectivities (``physical/join.py``) and cleared with them
_ESTIMATES: Dict[tuple, tuple] = {}
_LOCK = threading.Lock()
_MAX_ESTIMATES = 1024


def order_joins(plan: P.LogicalPlan) -> Tuple[P.LogicalPlan, int]:
    """``plan`` with the dimensions of every star run in ascending order of
    their estimated kept share, and how many runs changed order.  The plan
    handed in is never mutated."""
    orderer = _Orderer()
    return orderer.visit(plan), orderer.reordered


def clear_estimates() -> None:
    """Called by ``kernel_cache.clear_cache``."""
    with _LOCK:
        _ESTIMATES.clear()


def share_note(share: Optional[float]) -> str:
    """The explain suffix of a join whose dimension's share was estimated."""
    return "" if share is None else f" [keeps ~{share:.2%} of the fact]"


class _Orderer:
    def __init__(self):
        #: a subtree shared by two parents stays shared
        self._memo: Dict[int, P.LogicalPlan] = {}
        self.reordered = 0

    def visit(self, node: P.LogicalPlan) -> P.LogicalPlan:
        got = self._memo.get(id(node))
        if got is None:
            got = self._memo[id(node)] = (
                self._run(node) if _is_link(node) else _with_children(
                    node, [self.visit(c) for c in node.children]))
        return got

    def _run(self, top: P.Join) -> P.LogicalPlan:
        links: List[P.Join] = []
        node: P.LogicalPlan = top
        while _is_link(node):
            links.append(node)
            node = node.left
        links.reverse()                     # bottom-up
        fact = node
        star = 0
        while star < len(links) and _references_fact_and_own(links[star],
                                                              fact):
            star += 1
        cur = self.visit(fact)
        shares = [_kept_share(fact, j) for j in links[:star]] \
            if star >= 2 else []
        if shares and None not in shares:
            order = sorted(range(star), key=lambda i: shares[i])
            for i in order:
                cur = self._relinked(links[i], cur, shares[i])
            if order != list(range(star)):
                self.reordered += 1
                cur = P.Project(tuple(links[star - 1].output), cur)
            rest = links[star:]
        else:
            rest = links
        for j in rest:
            cur = self._relinked(j, cur, None)
        return cur

    def _relinked(self, link: P.Join, left: P.LogicalPlan,
                  share: Optional[float]) -> P.Join:
        right = self.visit(link.right)
        if left is link.left and right is link.right and share is None:
            return link
        new = copy.copy(link)
        new.left, new.right, new.children = left, right, (left, right)
        new.kept_share = share
        return new


def _is_link(node) -> bool:
    return (isinstance(node, P.Join) and node.how == "inner"
            and bool(node.left_keys))


def _ids(attrs) -> set:
    return {a.expr_id for a in attrs}


def _references_fact_and_own(link: P.Join, fact: P.LogicalPlan) -> bool:
    fact_ids = _ids(fact.output)
    own = fact_ids | _ids(link.right.output)
    if any(not _ids(k.references()) <= fact_ids for k in link.left_keys):
        return False
    return link.condition is None or _ids(link.condition.references()) <= own


# --- the estimate ---------------------------------------------------------------

def _kept_share(fact: P.LogicalPlan, link: P.Join) -> Optional[float]:
    if len(link.left_keys) != 1:
        return None
    fk, k = link.left_keys[0], link.right_keys[0]
    if not (isinstance(fk, AttributeReference)
            and isinstance(k, AttributeReference)):
        return None
    fact_leaf = _rebased(fact)
    dim_leaf = _rebased(link.right)
    if fact_leaf is None or dim_leaf is None:
        return None
    fact_rel, _, fact_cols = fact_leaf
    dim_rel, preds, dim_cols = dim_leaf
    if fk.expr_id not in fact_cols or k.expr_id not in dim_cols:
        return None
    if not preds:
        return 1.0
    bounds = _key_range(fact_rel.table, fact_cols[fk.expr_id])
    if bounds is None:
        return None
    return _share(dim_rel, preds, dim_cols[k.expr_id], bounds)


def _rebased(node: P.LogicalPlan):
    """(the in-memory relation under ``node``, ``node``'s filters rewritten
    over the relation's attributes, ``node``'s output expr_id -> the
    relation column's position), or None where ``node`` is anything but
    filters and column-renaming projects over a ``Relation``."""
    if isinstance(node, P.Relation):
        return node, [], {a.expr_id: i for i, a in enumerate(node.output)}
    if not isinstance(node, (P.Filter, P.Project)):
        return None
    below = _rebased(node.child)
    if below is None:
        return None
    rel, preds, cols = below
    if isinstance(node, P.Filter):
        attrs = rel.output

        def rebase(e):
            if isinstance(e, AttributeReference):
                return attrs[cols[e.expr_id]] if e.expr_id in cols else e
            return None
        cond = node.condition.transform(rebase)
        if not _ids(cond.references()) <= _ids(attrs):
            return None
        return rel, preds + [cond], cols
    out = {}
    for e, a in zip(node.exprs, node.output):
        src = e.child if isinstance(e, Alias) else e
        if isinstance(src, AttributeReference) and src.expr_id in cols:
            out[a.expr_id] = cols[src.expr_id]
    return rel, preds, out


def _memo(key: tuple, table, compute):
    with _LOCK:
        got = _ESTIMATES.get(key)
    if got is not None and got[0]() is table:
        return got[1]
    try:
        value = compute()
    except Exception:
        # a column pyarrow cannot compare, an expression the host cannot
        # evaluate: the estimate is unknown and the run keeps its order
        value = None
    with _LOCK:
        if len(_ESTIMATES) >= _MAX_ESTIMATES:
            _ESTIMATES.clear()
        _ESTIMATES[key] = (weakref.ref(table), value)
    return value


def _key_range(table, col: int):
    """(min, max) of a fact's key column, None where it has no value."""
    def compute():
        got = pc.min_max(table.column(col))
        lo, hi = got["min"].as_py(), got["max"].as_py()
        return None if lo is None else (lo, hi)
    return _memo(("range", id(table), col), table, compute)


def _share(rel: P.Relation, preds: Sequence[Expression], key: int, bounds):
    """The share of ``rel``'s rows with their key in ``bounds`` that pass
    ``preds``; None where they cannot be evaluated on the host."""
    table = rel.table
    cond = preds[0]
    for p in preds[1:]:
        cond = And(cond, p)
    refs = _ids(cond.references())
    used = [i for i, a in enumerate(rel.output) if a.expr_id in refs]
    bound = bind_references(cond, [rel.output[i] for i in used])

    def compute():
        k = table.column(key)
        lo, hi = bounds
        in_range = pc.fill_null(pc.and_(pc.greater_equal(k, lo),
                                        pc.less_equal(k, hi)), False)
        in_range = in_range.to_numpy(zero_copy_only=False)
        n = int(in_range.sum())
        if n == 0:
            return 0.0
        keep = _evaluate(bound, table.select(used))
        return float(np.count_nonzero(keep & in_range)) / n
    return _memo(("share", id(table), tuple(used), bound.semantic_key(), key,
                  bounds), table, compute)


def _evaluate(bound: Expression, table) -> np.ndarray:
    """The rows of ``table`` for which ``bound`` is true, evaluated with
    numpy over a host copy of the columns (on the CPU device where jax has
    one, so nothing is uploaded to an accelerator)."""
    import jax
    from ..columnar.convert import arrow_to_device
    try:
        where = jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        where = contextlib.nullcontext()
    with where:
        batch = jax.device_get(arrow_to_device(table))
    got = bound.eval(EvalContext(batch, xp=np))
    n = table.num_rows
    return (np.asarray(got.data)[:n].astype(bool)
            & np.asarray(got.validity)[:n].astype(bool))
