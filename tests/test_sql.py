"""SQL front-end tests — session.sql / selectExpr / expr / string filters.

The reference accelerates SQL text transparently (every Spark query is SQL
compiled by Catalyst before the plugin runs; SURVEY §1).  These tests drive
the same engine through SQL strings and check against pandas oracles or the
equivalent DataFrame-API query.
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.sqlparser import SqlParseError


@pytest.fixture()
def spark(session):
    return session


@pytest.fixture()
def t(spark):
    df = spark.createDataFrame(
        [(1, "a", 10.0), (2, "b", 20.0), (1, "c", 30.0),
         (3, None, 40.0), (2, "b", 5.5), (1, "a", None)],
        "k int, s string, v double")
    df.createOrReplaceTempView("t")
    return df


def rows(df):
    return df.collect().to_pylist()


# --- expression strings ----------------------------------------------------

def test_expr_arithmetic_precedence(spark, t):
    out = rows(t.select(F.expr("k + 2 * 3").alias("x")))
    assert [r["x"] for r in out] == [7, 8, 7, 9, 8, 7]


def test_expr_string_functions(spark, t):
    out = rows(t.select(F.expr("upper(concat(s, '!'))").alias("x")))
    assert [r["x"] for r in out] == ["A!", "B!", "C!", None, "B!", "A!"]


def test_expr_concat_pipes(spark, t):
    out = rows(t.select(F.expr("s || '_' || s").alias("x")))
    assert out[0]["x"] == "a_a"


def test_filter_string_predicates(spark, t):
    got = rows(t.filter("v > 10 AND s IS NOT NULL"))
    assert [(r["k"], r["s"]) for r in got] == [(2, "b"), (1, "c")]


def test_filter_string_in_between_like(spark, t):
    assert len(rows(t.filter("k IN (1, 3)"))) == 4
    assert len(rows(t.filter("v BETWEEN 10 AND 30"))) == 3
    assert len(rows(t.filter("s LIKE 'a%'"))) == 2
    assert len(rows(t.filter("s NOT LIKE 'a%'"))) == 3  # null drops
    assert len(rows(t.filter("s RLIKE '^[ab]$'"))) == 4


def test_selectExpr(spark, t):
    out = rows(t.selectExpr("k", "v * 2 AS w", "upper(s) u"))
    assert set(out[0]) == {"k", "w", "u"}
    assert out[1]["w"] == 40.0 and out[1]["u"] == "B"


def test_selectExpr_star(spark, t):
    out = t.selectExpr("*", "k + 1 AS k2")
    assert out.columns == ["k", "s", "v", "k2"]


def test_number_literal_types(spark, t):
    tab = t.selectExpr("1 AS a", "1.5 AS b", "1e2 AS c", "2L AS d",
                       "3d AS e").collect()
    import pyarrow as pa
    assert tab.schema.field("a").type == pa.int32()
    assert tab.schema.field("b").type == pa.float64()
    assert tab.schema.field("c").type == pa.float64()
    assert tab.schema.field("d").type == pa.int64()
    assert tab.schema.field("e").type == pa.float64()


def test_case_when(spark, t):
    out = rows(t.selectExpr(
        "CASE WHEN v > 15 THEN 'hi' WHEN v > 8 THEN 'mid' ELSE 'lo' END c"))
    assert [r["c"] for r in out] == ["mid", "hi", "hi", "hi", "lo", "lo"]
    # simple-subject form
    out = rows(t.selectExpr("CASE k WHEN 1 THEN 'one' ELSE 'many' END c"))
    assert [r["c"] for r in out] == ["one", "many", "one", "many", "many",
                                     "one"]


def test_cast_and_types(spark, t):
    out = rows(t.selectExpr("CAST(v AS int) i", "CAST(k AS string) s2",
                            "CAST(v AS decimal(5,1)) d"))
    assert out[0]["i"] == 10
    assert out[0]["s2"] == "1"


def test_is_null_not(spark, t):
    assert len(rows(t.filter("s IS NULL"))) == 1
    assert len(rows(t.filter("v IS NOT NULL AND NOT (k = 1)"))) == 3


# --- session.sql -----------------------------------------------------------

def test_sql_basic_projection(spark, t):
    got = rows(spark.sql("SELECT k, v FROM t WHERE v >= 10 ORDER BY v"))
    assert got == [{"k": 1, "v": 10.0}, {"k": 2, "v": 20.0},
                   {"k": 1, "v": 30.0}, {"k": 3, "v": 40.0}]


def test_sql_select_star(spark, t):
    assert spark.sql("SELECT * FROM t").columns == ["k", "s", "v"]


def test_sql_no_from(spark):
    got = rows(spark.sql("SELECT 1 + 1 AS two, upper('x') AS u"))
    assert got == [{"two": 2, "u": "X"}]


def test_sql_group_by(spark, t):
    got = rows(spark.sql(
        "SELECT k, sum(v) AS total, count(*) AS n, count(v) AS nv "
        "FROM t GROUP BY k ORDER BY k"))
    assert got == [
        {"k": 1, "total": 40.0, "n": 3, "nv": 2},
        {"k": 2, "total": 25.5, "n": 2, "nv": 2},
        {"k": 3, "total": 40.0, "n": 1, "nv": 1}]


def test_sql_group_by_ordinal_and_alias(spark, t):
    a = rows(spark.sql("SELECT k AS kk, avg(v) a FROM t GROUP BY 1 ORDER BY 1"))
    b = rows(spark.sql("SELECT k AS kk, avg(v) a FROM t GROUP BY kk ORDER BY kk"))
    assert a == b
    assert a[0]["kk"] == 1 and a[0]["a"] == 20.0


def test_sql_group_by_expression(spark, t):
    got = rows(spark.sql(
        "SELECT k % 2 AS odd, count(*) n FROM t GROUP BY k % 2 ORDER BY odd"))
    assert got == [{"odd": 0, "n": 2}, {"odd": 1, "n": 4}]


def test_sql_select_list_order_differs_from_groups(spark, t):
    # aggregate first in the select list — plan must not force key-first
    got = rows(spark.sql(
        "SELECT sum(v) AS total, k FROM t GROUP BY k ORDER BY k"))
    assert got[0] == {"total": 40.0, "k": 1}


def test_sql_having(spark, t):
    got = rows(spark.sql(
        "SELECT k, sum(v) s FROM t GROUP BY k HAVING sum(v) > 30 ORDER BY k"))
    assert [r["k"] for r in got] == [1, 3]
    # HAVING over an aggregate that is NOT in the select list
    got = rows(spark.sql(
        "SELECT k FROM t GROUP BY k HAVING count(*) >= 2 ORDER BY k"))
    assert [r["k"] for r in got] == [1, 2]


def test_sql_global_aggregate(spark, t):
    got = rows(spark.sql("SELECT sum(v) s, max(k) m FROM t"))
    assert got == [{"s": 105.5, "m": 3}]


def test_sql_order_by_hidden_column(spark, t):
    # ORDER BY a column that is not in the select list
    got = rows(spark.sql("SELECT s FROM t WHERE v IS NOT NULL ORDER BY v DESC"))
    assert [r["s"] for r in got] == [None, "c", "b", "a", "b"]


def test_sql_order_by_agg_not_in_select(spark, t):
    got = rows(spark.sql(
        "SELECT k FROM t GROUP BY k ORDER BY sum(v) DESC, k"))
    assert [r["k"] for r in got] == [1, 3, 2]


def test_sql_distinct(spark, t):
    got = rows(spark.sql("SELECT DISTINCT k FROM t ORDER BY k"))
    assert [r["k"] for r in got] == [1, 2, 3]


def test_sql_count_distinct(spark, t):
    got = rows(spark.sql("SELECT count(DISTINCT k) ck FROM t"))
    assert got[0]["ck"] == 3
    got = rows(spark.sql("SELECT sum(DISTINCT v) sv FROM t"))
    assert got[0]["sv"] == 105.5


def test_sql_limit_offset(spark, t):
    got = rows(spark.sql("SELECT v FROM t WHERE v IS NOT NULL "
                         "ORDER BY v LIMIT 2 OFFSET 1"))
    assert [r["v"] for r in got] == [10.0, 20.0]


def test_sql_join(spark, t):
    d = spark.createDataFrame([(1, "x"), (2, "y"), (9, "z")],
                              "k int, name string")
    d.createOrReplaceTempView("d")
    got = rows(spark.sql(
        "SELECT t.k, d.name, t.v FROM t JOIN d ON t.k = d.k "
        "WHERE t.v IS NOT NULL ORDER BY t.v"))
    assert [(r["k"], r["name"]) for r in got] == [
        (2, "y"), (1, "x"), (2, "y"), (1, "x")]
    # left join keeps unmatched
    got = rows(spark.sql(
        "SELECT t.k, d.name FROM t LEFT JOIN d ON t.k = d.k ORDER BY t.k"))
    assert {(r["k"], r["name"]) for r in got} == {
        (1, "x"), (2, "y"), (3, None)}


def test_sql_join_using(spark, t):
    d = spark.createDataFrame([(1, "x"), (2, "y")], "k int, name string")
    d.createOrReplaceTempView("d2")
    df = spark.sql("SELECT * FROM t JOIN d2 USING (k)")
    assert df.columns == ["k", "s", "v", "name"]


def test_sql_join_aliases(spark, t):
    got = rows(spark.sql(
        "SELECT a.k, b.v AS bv FROM t a JOIN t b ON a.k = b.k "
        "WHERE a.v = 10.0 AND b.v = 30.0"))
    assert got == [{"k": 1, "bv": 30.0}]


def test_sql_subquery(spark, t):
    got = rows(spark.sql(
        "SELECT k, total FROM (SELECT k, sum(v) AS total FROM t GROUP BY k) "
        "WHERE total > 30 ORDER BY k"))
    assert [r["k"] for r in got] == [1, 3]


def test_sql_cte(spark, t):
    got = rows(spark.sql(
        "WITH agg AS (SELECT k, sum(v) AS total FROM t GROUP BY k), "
        "big AS (SELECT * FROM agg WHERE total > 30) "
        "SELECT k FROM big ORDER BY k"))
    assert [r["k"] for r in got] == [1, 3]


def test_sql_union(spark, t):
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k = 1 UNION SELECT k FROM t WHERE k <= 2 "
        "ORDER BY k"))
    assert [r["k"] for r in got] == [1, 2]
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k = 3 UNION ALL SELECT k FROM t WHERE k = 3"))
    assert [r["k"] for r in got] == [3, 3]


def test_sql_setop_trailing_clauses_bind_to_result(spark, t):
    # LIMIT/ORDER BY after a UNION applies to the whole result, not the
    # last branch
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k = 1 UNION ALL SELECT k FROM t LIMIT 2"))
    assert len(got) == 2
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k = 3 UNION ALL SELECT k FROM t WHERE k = 2 "
        "ORDER BY k DESC"))
    assert [r["k"] for r in got] == [3, 2, 2]


def test_sql_intersect_binds_tighter_than_union(spark, t):
    # a UNION (b INTERSECT c), not (a UNION b) INTERSECT c
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k = 3 "
        "UNION SELECT k + 10 AS k FROM t "
        "INTERSECT SELECT k + 10 AS k FROM t WHERE k = 1 ORDER BY k"))
    assert [r["k"] for r in got] == [3, 11]


def test_sql_operator_precedence(spark):
    got = rows(spark.sql(
        "SELECT 2 | 1 + 1 AS a, 2 ^ 3 & 1 AS b, 1 << 2 + 1 AS c, "
        "'a' || 1 + 1 AS d, -2L AS e"))
    # Spark: | loosest, then ^, then &, then shifts, then ||, then +/-
    assert got == [{"a": 2, "b": 3, "c": 8, "d": "a2", "e": -2}]
    tab = spark.sql("SELECT -2L AS e").collect()
    import pyarrow as pa
    assert tab.schema.field("e").type == pa.int64()


def test_sql_count_distinct_star_rejected(spark, t):
    with pytest.raises(SqlParseError):
        spark.sql("SELECT count(DISTINCT *) FROM t")


def test_sql_bad_ordinals_are_parse_errors(spark, t):
    for bad in ("SELECT k FROM t GROUP BY 1e1",
                "SELECT k FROM t ORDER BY k LIMIT 1e1"):
        with pytest.raises(SqlParseError):
            spark.sql(bad)


def test_sql_union_all_distinct_rejected(spark, t):
    with pytest.raises(SqlParseError):
        spark.sql("SELECT k FROM t UNION ALL DISTINCT SELECT k FROM t")


def test_sql_window_in_where_rejected(spark, t):
    with pytest.raises(SqlParseError):
        spark.sql("SELECT k FROM t "
                  "WHERE sum(v) OVER (PARTITION BY k) > 20")


def test_sql_non_sql_helpers_not_functions(spark, t):
    for bad in ("lit(1)", "col('k')", "expr_fn(k)"):
        with pytest.raises(SqlParseError, match="unknown SQL function"):
            spark.sql(f"SELECT {bad} FROM t")


def test_sql_unknown_column_is_parse_error(spark, t):
    with pytest.raises(SqlParseError):
        spark.sql("SELECT nope FROM t")
    with pytest.raises(SqlParseError):
        spark.sql("SELECT k FROM t ORDER BY nope")


def test_sql_except_intersect(spark, t):
    got = rows(spark.sql(
        "SELECT k FROM t EXCEPT SELECT k FROM t WHERE k = 1 ORDER BY k"))
    assert [r["k"] for r in got] == [2, 3]
    got = rows(spark.sql(
        "SELECT k FROM t WHERE k <= 2 INTERSECT SELECT k FROM t WHERE k >= 2"))
    assert [r["k"] for r in got] == [2]


def test_sql_window_function(spark, t):
    got = rows(spark.sql(
        "SELECT k, v, row_number() OVER (PARTITION BY k ORDER BY v) rn "
        "FROM t WHERE v IS NOT NULL ORDER BY k, v"))
    assert [(r["k"], r["rn"]) for r in got] == [
        (1, 1), (1, 2), (2, 1), (2, 2), (3, 1)]


def test_sql_window_running_sum(spark, t):
    got = rows(spark.sql(
        "SELECT k, v, sum(v) OVER (PARTITION BY k ORDER BY v "
        "ROWS BETWEEN UNBOUNDED PRECEDING AND CURRENT ROW) rs "
        "FROM t WHERE v IS NOT NULL ORDER BY k, v"))
    assert [r["rs"] for r in got] == [10.0, 40.0, 5.5, 25.5, 40.0]


def test_sql_oracle_tpch_q1_shape(spark):
    rng = np.random.default_rng(0)
    n = 5000
    pdf = pd.DataFrame({
        "rf": rng.choice(["A", "N", "R"], n),
        "ls": rng.choice(["O", "F"], n),
        "qty": rng.integers(1, 51, n).astype("float64"),
        "price": rng.random(n) * 1000,
        "disc": rng.random(n) * 0.1,
    })
    spark.createDataFrame(pdf).createOrReplaceTempView("lineitem")
    got = spark.sql(
        "SELECT rf, ls, sum(qty) AS sum_qty, "
        "sum(price * (1 - disc)) AS sum_disc_price, "
        "avg(price) AS avg_price, count(*) AS n "
        "FROM lineitem WHERE qty < 24 "
        "GROUP BY rf, ls ORDER BY rf, ls").collect().to_pandas()
    exp = (pdf[pdf.qty < 24]
           .assign(sum_disc_price=lambda d: d.price * (1 - d.disc))
           .groupby(["rf", "ls"], as_index=False)
           .agg(sum_qty=("qty", "sum"), sum_disc_price=("sum_disc_price", "sum"),
                avg_price=("price", "mean"), n=("rf", "size"))
           .sort_values(["rf", "ls"]).reset_index(drop=True))
    pd.testing.assert_frame_equal(
        got, exp[got.columns.tolist()], check_dtype=False, atol=1e-6)


def test_sql_errors(spark, t):
    with pytest.raises(SqlParseError):
        spark.sql("SELECT nope(")
    with pytest.raises(SqlParseError):
        spark.sql("SELECT v FROM t GROUP BY k")   # v not grouped
    with pytest.raises(SqlParseError):
        spark.sql("SELECT * FROM t WHERE sum(v) > 1")  # agg in WHERE
    with pytest.raises(SqlParseError):
        spark.sql("SELECT unknown_fn(v) FROM t")
    with pytest.raises(SqlParseError):
        spark.sql("SELECT t2.v FROM t")           # unknown alias
    with pytest.raises(ValueError):
        spark.sql("SELECT * FROM no_such_view")


def test_catalog(spark, t):
    assert spark.catalog.tableExists("t")
    assert "t" in spark.catalog.listTables()
    assert rows(spark.table("t")) == rows(t)
    spark.sql("SELECT 1").collect()               # catalog untouched
    assert spark.catalog.dropTempView("t")
    assert not spark.catalog.tableExists("t")


def test_interval_arithmetic(spark):
    """INTERVAL 'n' unit in date/timestamp +/- arithmetic (TPC-H spec
    cutoffs: DATE '1998-12-01' - INTERVAL '90' DAY)."""
    import datetime
    import pyarrow as pa
    rng = np.random.default_rng(3)
    n = 2000
    base = np.datetime64("1996-01-01")
    d = (base + rng.integers(0, 1000, n).astype("timedelta64[D]")
         ).astype("datetime64[D]")
    t = pa.table({"d": pa.array(d)})
    pdf = t.to_pandas()
    spark.create_dataframe(t).createOrReplaceTempView("t_iv")
    got = spark.sql(
        "SELECT count(*) AS c FROM t_iv WHERE d <= "
        "CAST('1998-12-01' AS date) - INTERVAL '90' DAY"
    ).collect().to_pylist()[0]["c"]
    cutoff = datetime.date(1998, 12, 1) - datetime.timedelta(days=90)
    assert got == int((pdf.d <= cutoff).sum())
    # month arithmetic clamps to month end; interval commutes over +
    got = spark.sql("SELECT (INTERVAL '1' YEAR + CAST('2000-02-29' AS "
                   "date)) AS d2").collect().to_pylist()[0]["d2"]
    assert got == datetime.date(2001, 2, 28)
    got = spark.sql("SELECT CAST('2000-01-01' AS date) + "
                   "INTERVAL '1' MONTH 10 DAYS AS d2"
                   ).collect().to_pylist()[0]["d2"]
    assert got == datetime.date(2000, 2, 11)
    with pytest.raises(ValueError):
        spark.sql("SELECT INTERVAL '1' DAY + INTERVAL '2' DAY AS x"
                  ).collect()
    # operand-type dispatch: timestamp keeps sub-day precision, a date
    # with a sub-day interval promotes to timestamp, month arithmetic is
    # calendar-aware, and subtraction may CHAIN after an interval
    ts = pa.table({"ts": pa.array(
        [datetime.datetime(2020, 1, 31, 10)], type=pa.timestamp("us")),
        "d2": pa.array([datetime.date(2020, 1, 31)], type=pa.date32())})
    spark.create_dataframe(ts).createOrReplaceTempView("t_iv2")
    r = spark.sql(
        "SELECT ts + INTERVAL '1' MONTH AS b, d2 + INTERVAL '2' HOUR AS c,"
        " ts + INTERVAL '1' DAY - INTERVAL '1' DAY AS f FROM t_iv2"
    ).collect().to_pylist()[0]

    def naive(x):
        return x.replace(tzinfo=None) if getattr(x, "tzinfo", None) else x
    assert naive(r["b"]) == datetime.datetime(2020, 2, 29, 10)
    assert naive(r["c"]) == datetime.datetime(2020, 1, 31, 2)
    assert naive(r["f"]) == datetime.datetime(2020, 1, 31, 10)


def test_labeled_durations_of_the_tpcds_templates(spark):
    """``(date + 30 days)``: the form query98.tpl and its like write their
    date ranges in, the same value as ``INTERVAL 30 DAYS``; a name after a
    number that no parenthesis closes is still a select item's alias."""
    import datetime
    one = spark.sql(
        "SELECT (CAST('1999-02-22' AS date) + 30 days) AS hi, "
        "(CAST('2000-03-11' AS date) - 30 days) AS lo, "
        "(CAST('2000-01-31' AS date) + 1 month) AS m, "
        "1 + 30 days").collect()
    row = one.to_pylist()[0]
    assert row["hi"] == datetime.date(1999, 3, 24)
    assert row["lo"] == datetime.date(2000, 2, 10)
    assert row["m"] == datetime.date(2000, 2, 29)
    assert row["days"] == 31
    t = pa.table({"d": pa.array([datetime.date(1999, 2, 21),
                                 datetime.date(1999, 2, 22),
                                 datetime.date(1999, 3, 24),
                                 datetime.date(1999, 3, 25)])})
    spark.create_dataframe(t).createOrReplaceTempView("t_days")
    got = spark.sql(
        "SELECT count(*) AS c FROM t_days WHERE d BETWEEN "
        "cast('1999-02-22' as date) AND (cast('1999-02-22' as date) "
        "+ 30 days)").collect().to_pylist()[0]["c"]
    assert got == 2


def test_string_literal_backslash_escapes(spark):
    """Spark default (escapedStringLiterals=false): '\\\\d' is the 2-char
    regex escape, '\\n' a newline, '' a quote, \\% keeps its backslash."""
    tt = pa.table({"s": ["alpha1", "x", "a\nb"]})
    spark.create_dataframe(tt).createOrReplaceTempView("esc_t")
    out = spark.sql(
        r"SELECT s RLIKE '[a-z]+\\d+' AS m, 'a\nb' = s AS nl, "
        r"length('it''s') AS q, 'p\\%q' AS pct FROM esc_t"
    ).collect().to_pylist()
    assert [r["m"] for r in out] == [True, False, False]
    assert [r["nl"] for r in out] == [False, False, True]
    assert out[0]["q"] == 4
    assert out[0]["pct"] == "p\\%q"


def test_show_tables_and_describe(spark, t):
    out = spark.sql("SHOW TABLES").collect().to_pylist()
    assert any(r["tableName"] == "t" and r["isTemporary"] for r in out)
    d = spark.sql("DESCRIBE TABLE t").collect().to_pylist()
    assert [r["col_name"] for r in d] == ["k", "s", "v"]
    assert [r["data_type"] for r in d] == ["int", "string", "double"]
    assert spark.sql("DESC t").collect().num_rows == 3
    with pytest.raises(ValueError, match="not found"):
        spark.sql("DESCRIBE no_such_view").collect()


def test_create_and_drop_temp_view(spark, t):
    spark.sql("CREATE TEMP VIEW tv_agg AS "
              "SELECT k, sum(v) AS s FROM t GROUP BY k")
    out = spark.sql("SELECT * FROM tv_agg ORDER BY k").collect().to_pylist()
    assert [r["k"] for r in out] == [1, 2, 3]
    with pytest.raises(ValueError, match="already exists"):
        spark.sql("CREATE TEMP VIEW tv_agg AS SELECT 1 AS x")
    spark.sql("CREATE OR REPLACE TEMPORARY VIEW tv_agg AS "
              "SELECT k FROM t WHERE k = 1")
    assert spark.sql("SELECT count(*) AS c FROM tv_agg"
                     ).collect().to_pylist()[0]["c"] == 3
    spark.sql("DROP VIEW tv_agg")
    with pytest.raises(Exception):
        spark.sql("SELECT * FROM tv_agg").collect()
    spark.sql("DROP VIEW IF EXISTS tv_agg")
    with pytest.raises(ValueError, match="view not found"):
        spark.sql("DROP VIEW tv_agg")


def test_temp_view_with_cte_body(spark, t):
    spark.sql("CREATE OR REPLACE TEMP VIEW tv_cte AS "
              "WITH c AS (SELECT k FROM t WHERE k > 1) "
              "SELECT count(*) AS c FROM c")
    assert spark.sql("SELECT * FROM tv_cte").collect().to_pylist() == \
        [{"c": 3}]
    spark.sql("DROP VIEW tv_cte")


def test_lateral_view_explode(spark):
    t = pa.table({"k": [1, 2, 3], "arr": [[10, 20], [30], []]})
    spark.create_dataframe(t).createOrReplaceTempView("lv_t")
    out = spark.sql("SELECT k, c FROM lv_t LATERAL VIEW explode(arr) x "
                    "AS c ORDER BY k, c").collect().to_pylist()
    assert out == [{"k": 1, "c": 10}, {"k": 1, "c": 20},
                   {"k": 2, "c": 30}]
    out2 = spark.sql("SELECT k, x.c FROM lv_t LATERAL VIEW OUTER "
                     "explode(arr) x AS c ORDER BY k, c"
                     ).collect().to_pylist()
    assert out2[-1] == {"k": 3, "c": None}
    out3 = spark.sql("SELECT k, p, c FROM lv_t LATERAL VIEW "
                     "posexplode(arr) x AS p, c ORDER BY k, p"
                     ).collect().to_pylist()
    assert out3[:2] == [{"k": 1, "p": 0, "c": 10},
                        {"k": 1, "p": 1, "c": 20}]
    with pytest.raises(ValueError, match="unsupported LATERAL"):
        spark.sql("SELECT 1 FROM lv_t LATERAL VIEW json_tuple(arr) x "
                  "AS a").collect()


def test_lateral_view_then_join_rejected(spark):
    t = pa.table({"k": [1], "arr": [[1]]})
    spark.create_dataframe(t).createOrReplaceTempView("lvj_t")
    spark.create_dataframe(pa.table({"k": [1]})
                           ).createOrReplaceTempView("lvj_u")
    with pytest.raises(ValueError, match="JOIN after LATERAL VIEW"):
        spark.sql("SELECT * FROM lvj_t LATERAL VIEW explode(arr) x AS c "
                  "JOIN lvj_u ON lvj_t.k = lvj_u.k").collect()


def test_tablesample(spark):
    t = pa.table({"k": list(range(10_000))})
    spark.create_dataframe(t).createOrReplaceTempView("ts_t")
    n = spark.sql("SELECT count(*) AS c FROM ts_t TABLESAMPLE (10 PERCENT)"
                  " REPEATABLE (7)").collect().to_pylist()[0]["c"]
    assert 500 < n < 1_500
    n2 = spark.sql("SELECT count(*) AS c FROM ts_t TABLESAMPLE "
                   "(10 PERCENT) REPEATABLE (7)"
                   ).collect().to_pylist()[0]["c"]
    assert n == n2  # deterministic under REPEATABLE
    assert spark.sql("SELECT count(*) AS c FROM ts_t TABLESAMPLE (25 ROWS)"
                     ).collect().to_pylist()[0]["c"] == 25
    # both alias positions
    assert len(spark.sql("SELECT x.k FROM ts_t TABLESAMPLE (5 ROWS) x"
                         ).collect()) == 5
    assert len(spark.sql("SELECT x.k FROM ts_t x TABLESAMPLE (5 ROWS)"
                         ).collect()) == 5
    with pytest.raises(ValueError, match="PERCENT"):
        spark.sql("SELECT 1 FROM ts_t TABLESAMPLE (10 BUCKETS)").collect()
