"""Grammar fuzz for the Spark-ONLY SQL surface: the
sqlite-oracle fuzz (test_sql_grammar_fuzz.py) is constrained to the
dialect intersection — no datetime functions, no DECIMAL, no LATERAL
VIEW.  This harness reuses its type-directed-generator idea with DUAL
EMISSION: every random node produces both SQL text and an independent
pandas evaluation lambda, so the oracle needs no SQL engine at all.

Covered grammar: date arithmetic (date_add/date_sub/last_day), date
extraction (year/month/dayofmonth/quarter/dayofweek/datediff), exact
DECIMAL literals/arithmetic/aggregation, LATERAL VIEW explode, CASE with
three-valued predicates, and GROUP BY over extracted date parts.
"""

import datetime
import random
from decimal import Decimal

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt

N = 3000


@pytest.fixture(scope="module")
def env():
    rng = np.random.default_rng(11)
    base = datetime.date(2019, 1, 1)
    def dates(frac_null):
        days = rng.integers(0, 1500, N)
        mask = rng.random(N) < frac_null
        return [None if m else base + datetime.timedelta(days=int(d))
                for m, d in zip(mask, days)]
    def decs(frac_null):
        cents = rng.integers(-10_000_00, 10_000_00, N)
        mask = rng.random(N) < frac_null
        return pa.array(
            [None if m else Decimal(int(c)).scaleb(-2)
             for m, c in zip(mask, cents)], pa.decimal128(12, 2))
    arrs = []
    for k in range(N):
        r = rng.random()
        if r < 0.08:
            arrs.append(None)
        elif r < 0.16:
            arrs.append([])
        else:
            arrs.append([int(x) for x in
                         rng.integers(-50, 50, rng.integers(1, 5))])
    t = pa.table({
        "dt": pa.array(dates(0.1), pa.date32()),
        "dt2": pa.array(dates(0.15), pa.date32()),
        "j": pa.array(rng.integers(0, 20, N), pa.int64()),
        "dec": decs(0.12),
        "dec2": decs(0.2),
        "arr": pa.array(arrs, pa.list_(pa.int64())),
    })
    sess = srt.session()
    sess.create_dataframe(t, num_partitions=3).createOrReplaceTempView(
        "pg")
    pdf = pd.DataFrame({
        "dt": pd.to_datetime(pd.Series(dates_col(t, "dt"))),
        "dt2": pd.to_datetime(pd.Series(dates_col(t, "dt2"))),
        "j": t.column("j").to_pandas(),
        "dec": pd.Series(t.column("dec").to_pylist(), dtype=object),
        "dec2": pd.Series(t.column("dec2").to_pylist(), dtype=object),
        "arr": pd.Series(t.column("arr").to_pylist(), dtype=object),
    })
    return sess, pdf


def dates_col(t, name):
    return t.column(name).to_pylist()


# --------------------------------------------------------------------------
# Dual-emission generator: node = (sql, fn(pdf) -> Series)
# --------------------------------------------------------------------------


class DualGen:
    def __init__(self, rng: random.Random):
        self.rng = rng

    # ---- dates -----------------------------------------------------------
    def date(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.45:
            col = r.choice(["dt", "dt2"])
            return col, lambda df: df[col]
        d = depth - 1
        pick = r.random()
        if pick < 0.35:
            s, f = self.date(d)
            k = r.randint(0, 200)
            return (f"date_add({s}, {k})",
                    lambda df: f(df) + pd.Timedelta(days=k))
        if pick < 0.6:
            s, f = self.date(d)
            k = r.randint(0, 200)
            return (f"date_sub({s}, {k})",
                    lambda df: f(df) - pd.Timedelta(days=k))
        if pick < 0.8:
            s, f = self.date(d)
            return (f"last_day({s})",
                    lambda df: f(df) + pd.offsets.MonthEnd(0))
        ps, pf = self.pred(d)
        asql, af = self.date(d)
        bsql, bf = self.date(d)
        return (f"(CASE WHEN {ps} THEN {asql} ELSE {bsql} END)",
                lambda df: af(df).where(
                    pf(df).fillna(False).astype(bool), bf(df)))

    # ---- ints (incl. date extraction) ------------------------------------
    def intx(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.3:
            if r.random() < 0.5:
                return "j", lambda df: df["j"].astype("Int64")
            k = r.randint(-30, 30)
            return str(k), lambda df: pd.Series([k] * len(df),
                                                dtype="Int64")
        d = depth - 1
        pick = r.random()
        ds, dfn = self.date(d)
        if pick < 0.12:
            return (f"year({ds})",
                    lambda df: dfn(df).dt.year.astype("Int64"))
        if pick < 0.24:
            return (f"month({ds})",
                    lambda df: dfn(df).dt.month.astype("Int64"))
        if pick < 0.36:
            return (f"dayofmonth({ds})",
                    lambda df: dfn(df).dt.day.astype("Int64"))
        if pick < 0.46:
            return (f"quarter({ds})",
                    lambda df: dfn(df).dt.quarter.astype("Int64"))
        if pick < 0.56:
            # Spark dayofweek: 1 = Sunday .. 7 = Saturday;
            # pandas dayofweek: 0 = Monday .. 6 = Sunday
            return (f"dayofweek({ds})",
                    lambda df: ((dfn(df).dt.dayofweek + 1) % 7 + 1)
                    .astype("Int64"))
        if pick < 0.7:
            bs, bfn = self.date(d)
            return (f"datediff({ds}, {bs})",
                    lambda df: (dfn(df) - bfn(df)).dt.days.astype("Int64"))
        asql, af = self.intx(d)
        bsql, bf = self.intx(d)
        op = r.choice(["+", "-"])
        if op == "+":
            return f"({asql} + {bsql})", lambda df: af(df) + bf(df)
        return f"({asql} - {bsql})", lambda df: af(df) - bf(df)

    # ---- decimals --------------------------------------------------------
    def dec(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.4:
            if r.random() < 0.65:
                col = r.choice(["dec", "dec2"])
                return col, lambda df: df[col]
            lit = Decimal(r.randint(-9999, 9999)).scaleb(-2)
            return (f"CAST('{lit}' AS DECIMAL(10,2))",
                    lambda df: pd.Series([lit] * len(df), dtype=object))
        d = depth - 1
        pick = r.random()
        if pick < 0.3:
            asql, af = self.dec(d)
            bsql, bf = self.dec(d)
            return (f"({asql} + {bsql})",
                    lambda df: _dec_binop(af(df), bf(df),
                                          lambda a, b: a + b))
        if pick < 0.55:
            asql, af = self.dec(d)
            bsql, bf = self.dec(d)
            return (f"({asql} - {bsql})",
                    lambda df: _dec_binop(af(df), bf(df),
                                          lambda a, b: a - b))
        if pick < 0.7:
            # one multiply level only: nested products outgrow DECIMAL(38)
            asql, af = self.dec(0)
            lit = Decimal(r.randint(-300, 300)).scaleb(-2)
            return (f"({asql} * CAST('{lit}' AS DECIMAL(5,2)))",
                    lambda df: _dec_binop(
                        af(df), pd.Series([lit] * len(df), dtype=object),
                        lambda a, b: a * b))
        if pick < 0.82:
            asql, af = self.dec(d)
            return (f"(- {asql})",
                    lambda df: af(df).map(
                        lambda v: None if v is None else -v))
        ps, pf = self.pred(d)
        asql, af = self.dec(d)
        bsql, bf = self.dec(d)
        return (f"(CASE WHEN {ps} THEN {asql} ELSE {bsql} END)",
                lambda df: af(df).where(
                    pf(df).fillna(False).astype(bool), bf(df)))

    # ---- predicates ------------------------------------------------------
    def pred(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.45:
            pick = r.random()
            if pick < 0.3:
                asql, af = self.date(max(depth - 1, 0))
                bsql, bf = self.date(max(depth - 1, 0))
                op = r.choice(["<", "<=", ">", ">=", "="])
                return (f"({asql} {op} {bsql})",
                        lambda df: _cmp(af(df), bf(df), op))
            if pick < 0.6:
                asql, af = self.dec(max(depth - 1, 0))
                bsql, bf = self.dec(max(depth - 1, 0))
                op = r.choice(["<", "<=", ">", ">=", "="])
                return (f"({asql} {op} {bsql})",
                        lambda df: _cmp_obj(af(df), bf(df), op))
            if pick < 0.75:
                asql, af = self.date(max(depth - 1, 0))
                neg = r.random() < 0.5
                sql = f"({asql} IS {'NOT ' if neg else ''}NULL)"
                if neg:
                    return sql, lambda df: af(df).notna()
                return sql, lambda df: af(df).isna()
            asql, af = self.intx(max(depth - 1, 0))
            bsql, bf = self.intx(max(depth - 1, 0))
            op = r.choice(["<", "<=", ">", ">=", "="])
            return (f"({asql} {op} {bsql})",
                    lambda df: _cmp(af(df), bf(df), op))
        d = depth - 1
        asql, af = self.pred(d)
        bsql, bf = self.pred(d)
        pick = r.random()
        if pick < 0.45:
            # Kleene AND over nullable booleans
            return (f"({asql} AND {bsql})",
                    lambda df: _and3(af(df), bf(df)))
        if pick < 0.9:
            return (f"({asql} OR {bsql})",
                    lambda df: _or3(af(df), bf(df)))
        return f"(NOT {asql})", lambda df: ~af(df).astype("boolean")


def _dec_binop(a, b, op):
    return pd.Series(
        [None if (x is None or y is None or
                  (isinstance(x, float)) or (isinstance(y, float)))
         else op(x, y)
         for x, y in zip(a.tolist(), b.tolist())], dtype=object)


def _cmp(a, b, op):
    m = {"<": "lt", "<=": "le", ">": "gt", ">=": "ge", "=": "eq"}[op]
    out = getattr(a, m)(b)
    # comparisons with NaT/NA are UNKNOWN (masked), not False
    na = a.isna() | b.isna()
    return out.astype("boolean").mask(na)


def _cmp_obj(a, b, op):
    import operator
    f = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
         ">=": operator.ge, "=": operator.eq}[op]
    vals = [None if (x is None or y is None) else f(x, y)
            for x, y in zip(a.tolist(), b.tolist())]
    return pd.Series(vals, dtype="boolean")


def _and3(a, b):
    a = a.astype("boolean")
    b = b.astype("boolean")
    return a & b


def _or3(a, b):
    return a.astype("boolean") | b.astype("boolean")


# --------------------------------------------------------------------------
# comparison plumbing
# --------------------------------------------------------------------------


def _norm(v):
    if v is None or v is pd.NaT or (isinstance(v, float) and np.isnan(v)):
        return (1, "")
    if isinstance(v, Decimal):
        if v == 0:
            v = abs(v)  # Decimal('-0') normalizes to '-0'; engine says '0'
        return (0, str(v.normalize()))
    if isinstance(v, (pd.Timestamp, np.datetime64)):
        return (0, pd.Timestamp(v).date().isoformat())
    if isinstance(v, datetime.date):
        return (0, v.isoformat())
    if isinstance(v, (np.integer, int)) or v is pd.NA:
        return (1, "") if v is pd.NA else (0, int(v))
    if isinstance(v, np.bool_):
        return (0, bool(v))
    return (0, v)


def _check(sess, pdf, sql, exp_cols):
    got_tbl = sess.sql(sql).collect()
    got = sorted(tuple(_norm(v) for v in row)
                 for row in zip(*[got_tbl.column(i).to_pylist()
                                  for i in range(got_tbl.num_columns)]))
    want = sorted(tuple(_norm(v) for v in row)
                  for row in zip(*[c.tolist() for c in exp_cols]))
    assert len(got) == len(want), f"{len(got)} != {len(want)}\n{sql}"
    for g, w in zip(got, want):
        assert g == w, f"{g} != {w}\n{sql}"


# --------------------------------------------------------------------------
# fuzz tiers
# --------------------------------------------------------------------------


def test_datetime_project_filter_fuzz(env):
    sess, pdf = env
    rng = random.Random(606)
    g = DualGen(rng)
    for q in range(18):
        nodes = [g.date(2) if rng.random() < 0.5 else g.intx(2)
                 for _ in range(rng.randint(1, 3))]
        psql, pfn = g.pred(2)
        sels = ", ".join(f"{s} AS c{k}" for k, (s, _) in enumerate(nodes))
        sql = f"SELECT {sels} FROM pg WHERE {psql}"
        mask = pfn(pdf).fillna(False).astype(bool).to_numpy()
        _check(sess, pdf, sql, [f(pdf)[mask] for _, f in nodes])


def test_decimal_project_filter_fuzz(env):
    sess, pdf = env
    rng = random.Random(707)
    g = DualGen(rng)
    for q in range(15):
        nodes = [g.dec(2) for _ in range(rng.randint(1, 3))]
        psql, pfn = g.pred(2)
        sels = ", ".join(f"{s} AS c{k}" for k, (s, _) in enumerate(nodes))
        sql = f"SELECT {sels} FROM pg WHERE {psql}"
        mask = pfn(pdf).fillna(False).astype(bool).to_numpy()
        _check(sess, pdf, sql, [f(pdf)[mask] for _, f in nodes])


def test_decimal_group_agg_fuzz(env):
    sess, pdf = env
    rng = random.Random(808)
    g = DualGen(rng)
    for q in range(12):
        keysql, keyfn = rng.choice([
            ("year(dt)", lambda df: df["dt"].dt.year.astype("Int64")),
            ("month(dt)", lambda df: df["dt"].dt.month.astype("Int64")),
            ("j", lambda df: df["j"].astype("Int64")),
        ])
        psql, pfn = g.pred(1)
        sql = (f"SELECT {keysql} AS k0, sum(dec) AS a0, "
               f"count(dec) AS a1, min(dt) AS a2, max(dt2) AS a3, "
               f"count(*) AS a4 "
               f"FROM pg WHERE {psql} GROUP BY {keysql}")
        mask = pfn(pdf).fillna(False).astype(bool).to_numpy()
        sub = pdf[mask].copy()
        sub["__k"] = keyfn(pdf)[mask]
        groups = []
        for k, grp in sub.groupby("__k", dropna=False):
            decs = [v for v in grp["dec"].tolist() if v is not None]
            groups.append((
                None if k is pd.NA else k,
                sum(decs) if decs else None,
                len(decs),
                grp["dt"].min(),
                grp["dt2"].max(),
                len(grp)))
        cols = [pd.Series([r[i] for r in groups], dtype=object)
                for i in range(6)]
        _check(sess, pdf, sql, cols)


# --------------------------------------------------------------------------
# encoded columnar execution parity (ISSUE 6): the same grammar idea over
# LOW-CARDINALITY strings and REPETITIVE ints — the columns the scan keeps
# dictionary/RLE-encoded — with every generated query run encoded-ON vs
# encoded-OFF and the two engines compared bit-identically.  The oracle
# here is the RAW engine itself: the kill switch is structural, so any
# divergence is an encoding bug by definition.
# --------------------------------------------------------------------------

ENC_N = 4000
_ENC_CATS = [f"c{i:02d}" for i in range(12)]


class EncodedGen(DualGen):
    """String/repetitive-int extension used only by the encoded-parity
    fuzz (SQL emission only — the raw engine is the oracle)."""

    def strx(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.5:
            return r.choice(["s", "s2"])
        d = depth - 1
        p = self.epred(d)
        a = self.strx(d)
        b = self.strx(d)
        return f"(CASE WHEN {p} THEN {a} ELSE {b} END)"

    def epred(self, depth: int):
        r = self.rng
        if depth <= 0 or r.random() < 0.5:
            pick = r.random()
            if pick < 0.3:
                a = self.strx(0)
                op = r.choice(["<", "<=", ">", ">=", "=", "<>"])
                lit = r.choice(_ENC_CATS)
                return f"({a} {op} '{lit}')"
            if pick < 0.5:
                a = self.strx(0)
                items = ", ".join(
                    f"'{c}'" for c in r.sample(_ENC_CATS, r.randint(1, 4)))
                return f"({a} IN ({items}))"
            if pick < 0.65:
                a = self.strx(0)
                neg = "NOT " if r.random() < 0.5 else ""
                return f"({a} IS {neg}NULL)"
            if pick < 0.85:
                col = r.choice(["r", "j"])
                op = r.choice(["<", "<=", ">", ">=", "="])
                return f"({col} {op} {r.randint(0, 30)})"
            a, b = self.strx(0), self.strx(0)
            op = r.choice(["<", "=", ">="])
            return f"({a} {op} {b})"
        d = depth - 1
        a, b = self.epred(d), self.epred(d)
        pick = r.random()
        if pick < 0.45:
            return f"({a} AND {b})"
        if pick < 0.9:
            return f"({a} OR {b})"
        return f"(NOT {a})"


def _enc_table():
    rng = np.random.default_rng(23)

    def strs(frac_null):
        idx = rng.integers(0, len(_ENC_CATS), ENC_N)
        mask = rng.random(ENC_N) < frac_null
        return [None if m else _ENC_CATS[i] for m, i in zip(mask, idx)]
    return pa.table({
        "s": pa.array(strs(0.08)),
        "s2": pa.array(strs(0.15)),
        "r": pa.array(np.repeat(
            np.arange(ENC_N // 100, dtype=np.int64), 100)),
        "j": pa.array(rng.integers(0, 20, ENC_N), pa.int64()),
        "v": pa.array(rng.random(ENC_N)),
    })


def _enc_run(sess, sql):
    tbl = sess.sql(sql).collect()
    return sorted(tuple(_norm(v) for v in row)
                  for row in zip(*[tbl.column(i).to_pylist()
                                   for i in range(tbl.num_columns)]))


def test_encoded_vs_raw_parity_fuzz():
    rng = random.Random(404)
    g = EncodedGen(rng)
    queries = []
    for _ in range(16):
        p = g.epred(2)
        if rng.random() < 0.5:
            sels = ", ".join(f"{g.strx(2)} AS c{k}"
                             for k in range(rng.randint(1, 2)))
            queries.append(f"SELECT {sels}, r, v FROM eg WHERE {p}")
        else:
            queries.append(
                f"SELECT s, count(*) AS n, sum(v) AS sv, min(s2) AS m, "
                f"max(r) AS mr FROM eg WHERE {p} GROUP BY s")
    t = _enc_table()
    results = {}
    for on in (True, False):
        sess = srt.session(**{
            "spark.rapids.tpu.sql.encoded.enabled": on,
            "spark.rapids.sql.autoBroadcastJoinThreshold": 1})
        sess.create_dataframe(t, num_partitions=3) \
            .createOrReplaceTempView("eg")
        results[on] = [_enc_run(sess, sql) for sql in queries]
    for sql, enc, raw in zip(queries, results[True], results[False]):
        assert enc == raw, sql


def test_lateral_view_fuzz(env):
    sess, pdf = env
    rng = random.Random(909)
    for q in range(10):
        lo = rng.randint(-50, 20)
        with_where = rng.random() < 0.6
        sql = "SELECT j, x, (x + j) AS y FROM pg " \
              "LATERAL VIEW explode(arr) e AS x"
        if with_where:
            sql += f" WHERE x > {lo}"
        rows = []
        for j, arr in zip(pdf["j"], pdf["arr"]):
            if arr is None:
                continue
            for x in arr:
                if with_where and not (x > lo):
                    continue
                rows.append((j, x, x + j))
        cols = [pd.Series([r[i] for r in rows], dtype=object)
                for i in range(3)]
        _check(sess, pdf, sql, cols)
