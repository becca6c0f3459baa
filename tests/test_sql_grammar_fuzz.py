"""Grammar fuzz for the SQL front end (sqlparser.py — the repo's largest
file had example-based tests only).  A type-directed
random generator emits queries over a dialect-common subset and runs the
SAME text through the engine and through stdlib sqlite3 — a genuinely
independent SQL implementation — comparing row sets.

The grammar stays inside semantics both dialects share exactly: integer
(no division, bounded ranges), float64 (no NaN/inf), ASCII strings,
three-valued NULL logic, CASE/COALESCE/NULLIF/IN/BETWEEN/LIKE-free
predicates, COUNT/SUM/MIN/MAX/AVG (+DISTINCT), GROUP BY/HAVING, inner and
left equi-joins, uncorrelated scalar/IN subqueries, UNION ALL, and
ORDER BY with a unique tiebreaker + LIMIT (NULLS FIRST asc / NULLS LAST
desc — both engines' default).
"""

import math
import random
import sqlite3

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt

N1, N2 = 2000, 300


def _make_data(seed=7):
    rng = np.random.default_rng(seed)

    def nullable(arr, frac=0.12):
        mask = rng.random(len(arr)) < frac
        return [None if m else v for m, v in zip(mask, arr.tolist())]

    words = ["alpha", "Beta", "GAMMA", "delta", "Ep", "zeta_9", "", "x"]
    t1 = pa.table({
        "id": pa.array(list(range(N1)), pa.int64()),
        "i": pa.array(nullable(rng.integers(-1000, 1000, N1)), pa.int64()),
        "j": pa.array(rng.integers(0, 20, N1), pa.int64()),
        "f": pa.array(nullable(np.round(rng.standard_normal(N1) * 100, 4)),
                      pa.float64()),
        "s": pa.array(nullable(rng.choice(words, N1), 0.15)),
    })
    t2 = pa.table({
        "k": pa.array(rng.integers(0, 20, N2), pa.int64()),
        "v": pa.array(nullable(np.round(rng.random(N2) * 50, 4)),
                      pa.float64()),
        "s2": pa.array(nullable(rng.choice(words, N2), 0.2)),
    })
    return t1, t2


@pytest.fixture(scope="module")
def engines():
    t1, t2 = _make_data()
    sess = srt.session()
    sess.create_dataframe(t1, num_partitions=3).createOrReplaceTempView("t1")
    sess.create_dataframe(t2).createOrReplaceTempView("t2")
    con = sqlite3.connect(":memory:")
    for name, tbl in (("t1", t1), ("t2", t2)):
        cols = ", ".join(tbl.column_names)
        con.execute(f"CREATE TABLE {name} ({cols})")
        rows = list(zip(*[tbl.column(c).to_pylist()
                          for c in tbl.column_names]))
        ph = ", ".join("?" * tbl.num_columns)
        con.executemany(f"INSERT INTO {name} VALUES ({ph})", rows)
    yield sess, con
    con.close()


# --------------------------------------------------------------------------
# Type-directed expression generator
# --------------------------------------------------------------------------

class Gen:
    """Random expressions with SQL text shared by both dialects.  Types:
    'int', 'float', 'str'; predicates are separate."""

    def __init__(self, rng: random.Random, int_cols, float_cols, str_cols):
        self.rng = rng
        self.cols = {"int": int_cols, "float": float_cols, "str": str_cols}

    def expr(self, t: str, depth: int) -> str:
        r = self.rng
        if depth <= 0 or r.random() < 0.35:
            if self.cols[t] and r.random() < 0.75:
                return r.choice(self.cols[t])
            if t == "int":
                return str(r.randint(-50, 50))
            if t == "float":
                return f"{r.uniform(-20, 20):.3f}"
            return "'" + r.choice(["ab", "Qx", "", "zz9", "Ep"]) + "'"
        d = depth - 1
        if t in ("int", "float"):
            pick = r.random()
            if pick < 0.35:
                op = r.choice(["+", "-"] + (["*"] if t == "float" else []))
                return f"({self.expr(t, d)} {op} {self.expr(t, d)})"
            if pick < 0.45 and t == "int":
                return f"({self.expr(t, d)} * {r.randint(-4, 4)})"
            if pick < 0.60:
                return f"abs({self.expr(t, d)})"
            if pick < 0.72:
                return f"coalesce({self.expr(t, d)}, {self.expr(t, 0)})"
            if pick < 0.82:
                return f"nullif({self.expr(t, d)}, {self.expr(t, 0)})"
            if pick < 0.92:
                return (f"(CASE WHEN {self.pred(d)} THEN {self.expr(t, d)} "
                        f"ELSE {self.expr(t, d)} END)")
            if t == "int":
                return f"length({self.expr('str', d)})"
            return f"({self.expr('float', d)} * 0.5)"
        # strings
        pick = r.random()
        if pick < 0.25:
            return f"upper({self.expr('str', d)})"
        if pick < 0.50:
            return f"lower({self.expr('str', d)})"
        if pick < 0.68:
            return (f"substr({self.expr('str', d)}, "
                    f"{r.randint(1, 3)}, {r.randint(1, 4)})")
        if pick < 0.84:
            return f"({self.expr('str', d)} || {self.expr('str', d)})"
        return (f"(CASE WHEN {self.pred(d)} THEN {self.expr('str', d)} "
                f"ELSE {self.expr('str', d)} END)")

    def pred(self, depth: int) -> str:
        r = self.rng
        if depth <= 0 or r.random() < 0.4:
            t = r.choice(["int", "float", "str"])
            a = self.expr(t, max(depth - 1, 0))
            pick = r.random()
            if pick < 0.15:
                return f"({a} IS {'NOT ' if r.random() < 0.5 else ''}NULL)"
            if pick < 0.35 and t != "str":
                lo = r.randint(-100, 0)
                return f"({a} BETWEEN {lo} AND {lo + r.randint(1, 150)})"
            if pick < 0.5 and t == "int":
                lits = ", ".join(str(r.randint(-20, 20))
                                 for _ in range(r.randint(1, 5)))
                return f"({a} {'NOT ' if r.random() < 0.3 else ''}IN ({lits}))"
            op = r.choice(["<", "<=", ">", ">=", "=", "<>"])
            return f"({a} {op} {self.expr(t, max(depth - 1, 0))})"
        d = depth - 1
        pick = r.random()
        if pick < 0.45:
            return f"({self.pred(d)} AND {self.pred(d)})"
        if pick < 0.85:
            return f"({self.pred(d)} OR {self.pred(d)})"
        return f"(NOT {self.pred(d)})"

    def agg(self, t: str, depth: int) -> str:
        """Includes DISTINCT mixed with plain aggregates and across
        different child sets — the engine's Expand-distinct path
        (planner._plan_expand_distinct) covers those."""
        r = self.rng
        pick = r.random()
        e = self.expr(t, depth)
        if pick < 0.15:
            return "count(*)"
        if pick < 0.3:
            return f"count({e})"
        if pick < 0.42:
            d = r.choice(self.cols[t]) if (self.cols[t]
                                           and r.random() < 0.6) else e
            return f"count(DISTINCT {d})"
        if pick < 0.58 and t != "str":
            return f"sum({e})"
        if pick < 0.74:
            return f"min({e})"
        if pick < 0.9:
            return f"max({e})"
        if t != "str":
            return f"avg({e})"
        return f"count({e})"


# --------------------------------------------------------------------------
# Comparison
# --------------------------------------------------------------------------

def _norm(v):
    if v is None:
        return (1, "")
    if isinstance(v, bool):
        return (0, int(v))
    if isinstance(v, float):
        if math.isnan(v):
            return (1, "")
        return (0, round(v, 5))
    return (0, v)


def _rows(cols):
    return [tuple(_norm(v) for v in row) for row in zip(*cols)]


def _run_both(engines, sql, ordered=False):
    sess, con = engines
    got_tbl = sess.sql(sql).collect()
    got = _rows([got_tbl.column(i).to_pylist()
                 for i in range(got_tbl.num_columns)])
    want = [tuple(_norm(v) for v in row) for row in con.execute(sql)]
    if not ordered:
        got, want = sorted(got), sorted(want)
    assert len(got) == len(want), f"{len(got)} != {len(want)} rows\n{sql}"
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a[1], float) or isinstance(b[1], float):
                assert a[0] == b[0] and math.isclose(
                    a[1] or 0.0, b[1] or 0.0,
                    rel_tol=1e-6, abs_tol=1e-6), f"{g} != {w}\n{sql}"
            else:
                assert a == b, f"{g} != {w}\n{sql}"


# --------------------------------------------------------------------------
# Fuzz tiers
# --------------------------------------------------------------------------

def test_project_filter_fuzz(engines):
    rng = random.Random(101)
    g = Gen(rng, ["i", "j", "id"], ["f"], ["s"])
    for q in range(30):
        nsel = rng.randint(1, 4)
        sels = ", ".join(
            f"{g.expr(rng.choice(['int', 'float', 'str']), 3)} AS c{k}"
            for k in range(nsel))
        sql = f"SELECT {sels} FROM t1 WHERE {g.pred(3)}"
        _run_both(engines, sql)


def test_group_agg_having_fuzz(engines):
    rng = random.Random(202)
    g = Gen(rng, ["i", "j"], ["f"], ["s"])
    for q in range(25):
        key = rng.choice(["j", "s", "(i * 2)", "substr(s, 1, 1)",
                          "(j + 1)"])
        if rng.random() < 0.2:
            # distinct-only shape (the engine's supported DISTINCT form)
            col = rng.choice(["i", "j", "s"])
            aggs = f"count(DISTINCT {col}) AS a0"
            key = rng.choice(["j", "s"])
        else:
            aggs = ", ".join(
                f"{g.agg(rng.choice(['int', 'float', 'str']), 2)} AS a{k}"
                for k in range(rng.randint(1, 3)))
        sql = f"SELECT {key} AS k0, {aggs} FROM t1"
        if rng.random() < 0.6:
            sql += f" WHERE {g.pred(2)}"
        sql += f" GROUP BY {key}"
        if rng.random() < 0.4:
            sql += f" HAVING count(*) > {rng.randint(0, 30)}"
        _run_both(engines, sql)


def test_join_fuzz(engines):
    rng = random.Random(303)
    ga = Gen(rng, ["a.i", "a.j"], ["a.f"], ["a.s"])
    gb = Gen(rng, ["b.k"], ["b.v"], ["b.s2"])
    gboth = Gen(rng, ["a.i", "a.j", "b.k"], ["a.f", "b.v"], ["a.s", "b.s2"])
    for q in range(20):
        jt = rng.choice(["JOIN", "LEFT JOIN"])
        on = "a.j = b.k"
        if rng.random() < 0.4:
            on += f" AND {gb.pred(1)}"
        sels = ", ".join(
            f"{gboth.expr(rng.choice(['int', 'float', 'str']), 2)} AS c{k}"
            for k in range(rng.randint(1, 3)))
        sql = f"SELECT {sels} FROM t1 a {jt} t2 b ON {on}"
        if rng.random() < 0.5:
            sql += f" WHERE {ga.pred(2)}"
        _run_both(engines, sql)


def test_subquery_union_fuzz(engines):
    rng = random.Random(404)
    g = Gen(rng, ["i", "j"], ["f"], ["s"])
    for q in range(15):
        shape = rng.random()
        if shape < 0.4:
            inner = rng.choice(["(SELECT max(j) FROM t1)",
                                "(SELECT min(k) FROM t2)",
                                "(SELECT count(*) FROM t2)",
                                "(SELECT avg(k) FROM t2)"])
            sql = (f"SELECT i, j FROM t1 WHERE j > {inner} "
                   f"AND {g.pred(2)}")
        elif shape < 0.7:
            sql = (f"SELECT i FROM t1 WHERE j IN "
                   f"(SELECT k FROM t2 WHERE {Gen(rng, ['k'], ['v'], ['s2']).pred(1)})")
        else:
            e1 = g.expr("int", 2)
            e2 = g.expr("int", 2)
            sql = (f"SELECT {e1} AS c FROM t1 WHERE {g.pred(1)} "
                   f"UNION ALL SELECT {e2} AS c FROM t1 WHERE {g.pred(1)}")
        _run_both(engines, sql)


def test_order_limit_fuzz(engines):
    rng = random.Random(505)
    g = Gen(rng, ["i", "j"], ["f"], ["s"])
    for q in range(15):
        e = g.expr(rng.choice(["int", "str"]), 2)
        direction = rng.choice(["ASC", "DESC"])
        sql = (f"SELECT id, {e} AS c FROM t1 WHERE {g.pred(2)} "
               f"ORDER BY c {direction}, id LIMIT {rng.randint(1, 40)}")
        _run_both(engines, sql, ordered=True)
