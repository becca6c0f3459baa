"""Scale-test harness — the analog of the reference's
``integration_tests/.../scaletest/QuerySpecs.scala`` + ``datagen/``
(SURVEY §4 tier 4): a deterministic query suite over generated join/agg/
window-shaped data with controllable scale, each query checked against a
pandas oracle and timed.

Run standalone:  python -m spark_rapids_tpu.testing.scaletest [rows]
(CI runs it small through tests/test_scale.py; crank ``rows`` for a rig.)
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from .datagen import (DoubleGen, IntegerGen, LongGen, StringGen, gen_table)
from . import tpcds_queries as _TDS
from . import tpch_queries as _TQ


def build_tables(rows: int, seed: int = 17) -> Dict[str, pa.Table]:
    """fact + two dimensions with skewed keys (the reference's datagen
    controls cardinality/skew the same way)."""
    rng = np.random.default_rng(seed)
    # skew: 20% of fact rows land on 1% of keys
    n_keys = max(rows // 100, 10)
    hot = rng.integers(0, max(n_keys // 100, 1), rows // 5)
    cold = rng.integers(0, n_keys, rows - rows // 5)
    keys = np.concatenate([hot, cold])
    rng.shuffle(keys)
    fact = gen_table({
        "v": DoubleGen(no_nans=True, no_extremes=True),
        "q": IntegerGen(0, 100, nullable=False),
        "s": StringGen(max_len=12),
    }, rows, seed=seed)
    fact = fact.append_column("k", pa.array(keys, type=pa.int64()))
    dim = gen_table({
        "w": DoubleGen(no_nans=True, no_extremes=True, nullable=False),
        "cat": IntegerGen(0, 8, nullable=False),
    }, n_keys, seed=seed + 1)
    dim = dim.append_column("k", pa.array(np.arange(n_keys),
                                          type=pa.int64()))
    return {"fact": fact, "dim": dim}


def _q1(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    got = (fact.filter(fact.q < 50)
           .groupBy("q").agg(F.sum(fact.v).alias("sv"),
                             F.count("*").alias("c"))
           .orderBy("q").collect().to_pandas())
    pdf = t["fact"].to_pandas()
    pdf = pdf[pdf.q < 50]
    exp = pdf.groupby("q").agg(sv=("v", "sum"), c=("q", "size")).reset_index()
    assert np.array_equal(got["q"], exp["q"])
    assert np.allclose(got["sv"].fillna(0), exp["sv"].fillna(0))
    assert np.array_equal(got["c"], exp["c"])


def _q2(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    dim = sess.create_dataframe(t["dim"], num_partitions=2)
    got = (fact.join(dim, on="k", how="inner")
           .groupBy("cat").agg(F.count("*").alias("n"),
                               F.sum(fact.v).alias("sv"))
           .orderBy("cat").collect().to_pandas())
    exp = (t["fact"].to_pandas().merge(t["dim"].to_pandas(), on="k")
           .groupby("cat").agg(n=("k", "size"), sv=("v", "sum"))
           .reset_index())
    assert np.array_equal(got["cat"], exp["cat"])
    assert np.array_equal(got["n"], exp["n"])
    assert np.allclose(got["sv"].fillna(0), exp["sv"].fillna(0))


def _q3(sess, t, F):
    """skewed join: the hot keys stress partition balance."""
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    dim = sess.create_dataframe(t["dim"], num_partitions=2)
    got = (fact.join(dim, on="k", how="left")
           .filter(fact.q >= 90).select(fact.k, fact.v, dim.w)
           .orderBy("k", "v").collect().to_pandas())
    pdf = t["fact"].to_pandas()
    exp = (pdf[pdf.q >= 90].merge(t["dim"].to_pandas(), on="k", how="left")
           .sort_values(["k", "v"]).reset_index(drop=True))
    assert len(got) == len(exp)
    assert np.array_equal(got["k"], exp["k"])
    gw, ew = got["w"].to_numpy(), exp["w"].to_numpy()
    m = ~np.isnan(ew)
    assert np.allclose(gw[m], ew[m]) and np.isnan(gw[~m]).all()


def _q4(sess, t, F):
    from ..sql.window_api import Window
    fact = sess.create_dataframe(t["fact"], num_partitions=2)
    w = Window.partitionBy("q").orderBy("v")
    got = (fact.select(fact.q, fact.v,
                       F.row_number().over(w).alias("rn"))
           .filter(F.col("rn") <= 3)
           .collect().to_pandas())
    pdf = t["fact"].to_pandas().dropna(subset=["v"])
    exp = (pdf.sort_values(["q", "v"]).groupby("q").head(3))
    # row_number over possibly-null v: compare counts per q
    got_counts = got.groupby("q").size()
    exp_counts = exp.groupby("q").size()
    assert got_counts.max() <= 3  # the rn<=3 filter actually filtered
    for q in exp_counts.index:
        assert got_counts.get(q, 0) >= min(3, exp_counts[q]) - 1


def _q5(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    got = (fact.orderBy(fact.v.desc_nulls_first(), "k")
           .select(fact.k, fact.v).collect().to_pandas())
    assert len(got) == t["fact"].num_rows
    vals = got["v"].to_numpy()
    nn = vals[~np.isnan(vals)]
    assert np.all(np.diff(nn) <= 1e-12)  # descending


def _q6(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    got = (fact.select(F.upper(fact.s).alias("u"),
                       F.length(fact.s).alias("ln"))
           .filter(F.col("ln") > 4).count())
    pdf = t["fact"].to_pandas()
    exp = int((pdf.s.str.len() > 4).sum())
    assert got == exp


def build_tpch_tables(rows: int, seed: int = 23) -> Dict[str, pa.Table]:
    """Full 8-table TPC-H set (round 4: the 22-query suite needs
    supplier/partsupp/nation/region and the full column complement —
    ``tpch_queries.build_tables`` owns the schema now)."""
    from .tpch_queries import build_tables
    return build_tables(rows, seed)


def _q1_oracle_check(got, lineitem_table):
    """Shared pandas oracle for TPC-H q1 (DataFrame-API and SQL forms)."""
    import datetime
    pdf = lineitem_table.to_pandas()
    pdf = pdf[pdf.l_shipdate <= datetime.date(1998, 9, 2)]
    dp = pdf.l_extendedprice * (1.0 - pdf.l_discount)
    ch = dp * (1.0 + pdf.l_tax)
    exp = (pd.DataFrame({
        "rf": pdf.l_returnflag, "ls": pdf.l_linestatus,
        "q": pdf.l_quantity, "p": pdf.l_extendedprice, "dp": dp,
        "ch": ch, "d": pdf.l_discount})
        .groupby(["rf", "ls"])
        .agg(sum_qty=("q", "sum"), sum_base_price=("p", "sum"),
             sum_disc_price=("dp", "sum"), sum_charge=("ch", "sum"),
             avg_qty=("q", "mean"), avg_price=("p", "mean"),
             avg_disc=("d", "mean"), count_order=("q", "size"))
        .sort_index().reset_index())
    assert list(got["l_returnflag"]) == list(exp["rf"])
    assert list(got["l_linestatus"]) == list(exp["ls"])
    for col in ("sum_qty", "sum_base_price", "sum_disc_price",
                "sum_charge", "avg_qty", "avg_price", "avg_disc"):
        assert np.allclose(got[col], exp[col]), col
    assert np.array_equal(got["count_order"], exp["count_order"])


def _q6_oracle_check(got, lineitem_table):
    """Shared pandas oracle for TPC-H q6 (DataFrame-API and SQL forms)."""
    import datetime
    pdf = lineitem_table.to_pandas()
    lo, hi = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
    m = ((pdf.l_shipdate >= lo) & (pdf.l_shipdate < hi)
         & (pdf.l_discount >= 0.05) & (pdf.l_discount <= 0.07)
         & (pdf.l_quantity < 24.0))
    exp = float((pdf.l_extendedprice[m] * pdf.l_discount[m]).sum())
    assert np.allclose(got["revenue"].fillna(0.0), exp)


def _tpch_q1(sess, t, F):
    """TPC-H q1: pricing summary report (BASELINE milestone 2)."""
    import datetime
    li = sess.create_dataframe(t["lineitem"], num_partitions=4)
    cutoff = datetime.date(1998, 9, 2)
    got = (li.filter(li.l_shipdate <= F.lit(cutoff))
           .withColumn("disc_price",
                       li.l_extendedprice * (1.0 - li.l_discount))
           .withColumn("charge", li.l_extendedprice
                       * (1.0 - li.l_discount) * (1.0 + li.l_tax))
           .groupBy("l_returnflag", "l_linestatus")
           .agg(F.sum(F.col("l_quantity")).alias("sum_qty"),
                F.sum(F.col("l_extendedprice")).alias("sum_base_price"),
                F.sum(F.col("disc_price")).alias("sum_disc_price"),
                F.sum(F.col("charge")).alias("sum_charge"),
                F.avg(F.col("l_quantity")).alias("avg_qty"),
                F.avg(F.col("l_extendedprice")).alias("avg_price"),
                F.avg(F.col("l_discount")).alias("avg_disc"),
                F.count("*").alias("count_order"))
           .orderBy("l_returnflag", "l_linestatus")
           .collect().to_pandas())
    _q1_oracle_check(got, t["lineitem"])


def _tpch_q6(sess, t, F):
    """TPC-H q6: forecast revenue change (BASELINE milestone 2)."""
    import datetime
    li = sess.create_dataframe(t["lineitem"], num_partitions=4)
    lo, hi = datetime.date(1994, 1, 1), datetime.date(1995, 1, 1)
    got = (li.filter((li.l_shipdate >= F.lit(lo))
                     & (li.l_shipdate < F.lit(hi))
                     & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
                     & (li.l_quantity < 24.0))
           .agg(F.sum(F.col("l_extendedprice") * F.col("l_discount"))
                .alias("revenue"))
           .collect().to_pandas())
    _q6_oracle_check(got, t["lineitem"])


def _tpch_q4(sess, t, F):
    """TPC-H q4 shape: EXISTS subquery as a LEFT SEMI join (late lineitems
    per order), priority counts — exercises the semi-join planning path on
    a benchmark query (reference: semi joins via GpuHashJoin)."""
    import datetime
    lo, hi = datetime.date(1993, 7, 1), datetime.date(1993, 10, 1)
    o = sess.create_dataframe(t["orders"], num_partitions=4)
    li = sess.create_dataframe(t["lineitem"], num_partitions=4)
    late = li.filter(li.l_commitdate < li.l_receiptdate)
    got = (o.filter((o.o_orderdate >= F.lit(lo)) & (o.o_orderdate < F.lit(hi)))
           .join(late, o.o_orderkey == late.l_orderkey, how="left_semi")
           .groupBy("o_orderpriority")
           .agg(F.count("*").alias("order_count"))
           .orderBy("o_orderpriority")
           .collect().to_pandas())
    op = t["orders"].to_pandas()
    lp = t["lineitem"].to_pandas()
    late_keys = set(lp.l_orderkey[lp.l_commitdate < lp.l_receiptdate])
    op = op[(op.o_orderdate >= lo) & (op.o_orderdate < hi)
            & op.o_orderkey.isin(late_keys)]
    exp = (op.groupby("o_orderpriority").size()
           .sort_index().reset_index(name="order_count"))
    assert list(got["o_orderpriority"]) == list(exp["o_orderpriority"])
    assert np.array_equal(got["order_count"], exp["order_count"])


def _tpch_q14(sess, t, F):
    """TPC-H q14 shape: join + conditional aggregation (CASE WHEN p_type
    LIKE 'PROMO%') — promo revenue percentage."""
    import datetime
    lo, hi = datetime.date(1995, 9, 1), datetime.date(1995, 10, 1)
    li = sess.create_dataframe(t["lineitem"], num_partitions=4)
    p = sess.create_dataframe(t["part"], num_partitions=2)
    j = (li.filter((li.l_shipdate >= F.lit(lo)) & (li.l_shipdate < F.lit(hi)))
         .join(p, li.l_partkey == p.p_partkey))
    rev = j.l_extendedprice * (1.0 - j.l_discount)
    got = (j.agg((F.sum(F.when(j.p_type.startswith("PROMO"), rev)
                        .otherwise(0.0)) * 100.0
                  / F.sum(rev)).alias("promo_revenue"))
           .collect().to_pandas())
    lp = t["lineitem"].to_pandas()
    pp = t["part"].to_pandas()
    m = (lp.l_shipdate >= lo) & (lp.l_shipdate < hi)
    jp = lp[m].merge(pp, left_on="l_partkey", right_on="p_partkey")
    r = jp.l_extendedprice * (1.0 - jp.l_discount)
    promo = r[jp.p_type.str.startswith("PROMO")].sum()
    exp = 100.0 * promo / r.sum()
    assert np.allclose(got["promo_revenue"].fillna(0.0), exp)


#: TPC-H q1 as SQL text, exactly the spec's form (the cutoff is interval
#: arithmetic: DATE '1998-12-01' - INTERVAL '90' DAY = 1998-09-02)
_TPCH_Q1_SQL = """
SELECT l_returnflag, l_linestatus,
       sum(l_quantity) AS sum_qty,
       sum(l_extendedprice) AS sum_base_price,
       sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
       sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
       avg(l_quantity) AS avg_qty,
       avg(l_extendedprice) AS avg_price,
       avg(l_discount) AS avg_disc,
       count(*) AS count_order
FROM lineitem
WHERE l_shipdate <= CAST('1998-12-01' AS date) - INTERVAL '90' DAY
GROUP BY l_returnflag, l_linestatus
ORDER BY l_returnflag, l_linestatus
"""

_TPCH_Q6_SQL = """
SELECT sum(l_extendedprice * l_discount) AS revenue
FROM lineitem
WHERE l_shipdate >= CAST('1994-01-01' AS date)
  AND l_shipdate < CAST('1995-01-01' AS date)
  AND l_discount BETWEEN 0.05 AND 0.07
  AND l_quantity < 24
"""


_TPCH_Q4_SQL = """
SELECT o_orderpriority, count(*) AS order_count
FROM orders
WHERE o_orderdate >= CAST('1993-07-01' AS date)
  AND o_orderdate < CAST('1993-10-01' AS date)
  AND EXISTS (
    SELECT 1 FROM lineitem
    WHERE lineitem.l_orderkey = orders.o_orderkey
      AND lineitem.l_commitdate < lineitem.l_receiptdate)
GROUP BY o_orderpriority
ORDER BY o_orderpriority
"""


def _tpch_q4_sql(sess, t, F):
    """TPC-H q4 in its REAL spec form — correlated EXISTS rewritten to a
    left-semi join (Spark RewritePredicateSubquery)."""
    import datetime
    sess.create_dataframe(t["orders"], num_partitions=4) \
        .createOrReplaceTempView("orders")
    sess.create_dataframe(t["lineitem"], num_partitions=4) \
        .createOrReplaceTempView("lineitem")
    got = sess.sql(_TPCH_Q4_SQL).collect().to_pandas()
    op = t["orders"].to_pandas()
    lp = t["lineitem"].to_pandas()
    lo, hi = datetime.date(1993, 7, 1), datetime.date(1993, 10, 1)
    late = set(lp.l_orderkey[lp.l_commitdate < lp.l_receiptdate])
    op = op[(op.o_orderdate >= lo) & (op.o_orderdate < hi)
            & op.o_orderkey.isin(late)]
    exp = (op.groupby("o_orderpriority").size()
           .sort_index().reset_index(name="order_count"))
    assert list(got["o_orderpriority"]) == list(exp["o_orderpriority"])
    assert np.array_equal(got["order_count"], exp["order_count"])


_TPCH_Q22_SQL = """
SELECT cntrycode, count(*) AS numcust, sum(c_acctbal) AS totacctbal
FROM (SELECT substring(c_phone, 1, 2) AS cntrycode, c_acctbal, c_custkey
      FROM customer
      WHERE substring(c_phone, 1, 2) IN ('13', '31', '23', '29', '30')
        AND c_acctbal > (SELECT avg(c_acctbal) FROM customer
                         WHERE c_acctbal > 0.00
                           AND substring(c_phone, 1, 2)
                               IN ('13', '31', '23', '29', '30'))) custsale
WHERE NOT EXISTS (SELECT 1 FROM orders
                  WHERE orders.o_custkey = custsale.c_custkey)
GROUP BY cntrycode
ORDER BY cntrycode
"""


def _tpch_q22_sql(sess, t, F):
    """TPC-H q22 shape (global sales opportunity): IN-list + scalar
    subquery + correlated NOT EXISTS + FROM subquery + group/sort, all
    from SQL text — the full new-subquery machinery on one benchmark
    query."""
    sess.create_dataframe(t["customer"], num_partitions=4) \
        .createOrReplaceTempView("customer")
    sess.create_dataframe(t["orders"], num_partitions=4) \
        .createOrReplaceTempView("orders")
    got = sess.sql(_TPCH_Q22_SQL).collect().to_pandas()
    cp = t["customer"].to_pandas()
    op = t["orders"].to_pandas()
    codes = {"13", "31", "23", "29", "30"}
    cc = cp.c_phone.str[:2]
    sel = cp[cc.isin(codes)]
    avg_bal = cp.c_acctbal[(cp.c_acctbal > 0.0) & cc.isin(codes)].mean()
    sel = sel[sel.c_acctbal > avg_bal]
    sel = sel[~sel.c_custkey.isin(set(op.o_custkey))]
    exp = (sel.assign(cntrycode=sel.c_phone.str[:2])
           .groupby("cntrycode")
           .agg(numcust=("c_acctbal", "size"),
                totacctbal=("c_acctbal", "sum"))
           .sort_index().reset_index())
    assert list(got["cntrycode"]) == list(exp["cntrycode"])
    assert np.array_equal(got["numcust"], exp["numcust"])
    assert np.allclose(got["totacctbal"], exp["totacctbal"])


def _tpch_q1_sql(sess, t, F):
    """TPC-H q1 executed from SQL text — the reference's actual query
    surface (Spark SQL in; SURVEY §1) — checked against a pandas oracle."""
    sess.create_dataframe(t["lineitem"], num_partitions=4) \
        .createOrReplaceTempView("lineitem")
    got = sess.sql(_TPCH_Q1_SQL).collect().to_pandas()
    _q1_oracle_check(got, t["lineitem"])


def _tpch_q6_sql(sess, t, F):
    """TPC-H q6 from SQL text, pandas-oracle checked."""
    sess.create_dataframe(t["lineitem"], num_partitions=4) \
        .createOrReplaceTempView("lineitem")
    got = sess.sql(_TPCH_Q6_SQL).collect().to_pandas()
    _q6_oracle_check(got, t["lineitem"])


def _tpch_q17_sql(sess, t, F):
    """TPC-H q17 shape: correlated scalar subquery (avg quantity per
    part) decorrelated into a grouped-agg LEFT JOIN, pandas-checked."""
    li = t["lineitem"]
    sess.create_dataframe(li, num_partitions=4) \
        .createOrReplaceTempView("lineitem")
    got = sess.sql(
        "SELECT sum(l.l_extendedprice) / 7.0 AS avg_yearly "
        "FROM lineitem l "
        "WHERE l.l_quantity < (SELECT 0.2 * avg(l2.l_quantity) "
        "FROM lineitem l2 WHERE l2.l_partkey = l.l_partkey)"
    ).collect().to_pylist()[0]["avg_yearly"]
    pdf = li.to_pandas()
    th = pdf.groupby("l_partkey").l_quantity.mean() * 0.2
    exp = pdf[pdf.l_quantity < pdf.l_partkey.map(th)] \
        .l_extendedprice.sum() / 7.0
    assert abs(got - exp) <= 1e-9 * max(abs(exp), 1.0), (got, exp)


def build_tpcds_tables(rows: int, seed: int = 31):
    """Delegates to the full star schema (``tpcds_queries.build_tables``
    owns it now — a column-superset of the round-3 5-table subset, so
    existing callers keep working)."""
    return _TDS.build_tables(rows, seed)


def _tpcds_q3(sess, t, F):
    """TPC-DS q3 shape: star join store_sales x date_dim x item with a
    manufacturer + month filter, grouped revenue by (year, brand)."""
    ss = sess.create_dataframe(t["store_sales"], num_partitions=4)
    dd = sess.create_dataframe(t["date_dim"], num_partitions=2)
    it = sess.create_dataframe(t["item"], num_partitions=2)
    got = (ss.join(dd, ss.ss_sold_date_sk == dd.d_date_sk)
           .join(it, ss.ss_item_sk == it.i_item_sk)
           .filter((it.i_manufact_id == 7) & (dd.d_moy == 11))
           .groupBy("d_year", "i_brand_id")
           .agg(F.sum(F.col("ss_ext_sales_price")).alias("sum_agg"))
           .orderBy("d_year", "i_brand_id")
           .collect().to_pandas())
    pdf = (t["store_sales"].to_pandas()
           .merge(t["date_dim"].to_pandas(), left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
           .merge(t["item"].to_pandas(), left_on="ss_item_sk",
                  right_on="i_item_sk"))
    pdf = pdf[(pdf.i_manufact_id == 7) & (pdf.d_moy == 11)]
    exp = (pdf.groupby(["d_year", "i_brand_id"])
           .agg(sum_agg=("ss_ext_sales_price", "sum"))
           .sort_index().reset_index())
    assert np.array_equal(got["d_year"], exp["d_year"])
    assert np.array_equal(got["i_brand_id"], exp["i_brand_id"])
    assert np.allclose(got["sum_agg"], exp["sum_agg"])


def _tpcds_q7(sess, t, F):
    """TPC-DS q7 shape: 4-way star join (store_sales x cdemo x date x
    item x promotion) with demographic + promo-channel filters, four AVGs
    by item (BASELINE config 3)."""
    ss = sess.create_dataframe(t["store_sales"], num_partitions=4)
    cd = sess.create_dataframe(t["customer_demographics"], num_partitions=2)
    dd = sess.create_dataframe(t["date_dim"], num_partitions=2)
    it = sess.create_dataframe(t["item"], num_partitions=2)
    pr = sess.create_dataframe(t["promotion"], num_partitions=2)
    got = (ss.join(cd, ss.ss_cdemo_sk == cd.cd_demo_sk)
           .join(dd, ss.ss_sold_date_sk == dd.d_date_sk)
           .join(it, ss.ss_item_sk == it.i_item_sk)
           .join(pr, ss.ss_promo_sk == pr.p_promo_sk)
           .filter((cd.cd_gender == "M")
                   & (cd.cd_marital_status == "S")
                   & (cd.cd_education_status == "College")
                   & ((pr.p_channel_email == "N")
                      | (pr.p_channel_event == "N"))
                   & (dd.d_year == 2000))
           .groupBy("i_item_sk")
           .agg(F.avg(F.col("ss_quantity")).alias("agg1"),
                F.avg(F.col("ss_list_price")).alias("agg2"),
                F.avg(F.col("ss_coupon_amt")).alias("agg3"),
                F.avg(F.col("ss_ext_sales_price")).alias("agg4"))
           .orderBy("i_item_sk")
           .collect().to_pandas())
    pdf = (t["store_sales"].to_pandas()
           .merge(t["customer_demographics"].to_pandas(),
                  left_on="ss_cdemo_sk", right_on="cd_demo_sk")
           .merge(t["date_dim"].to_pandas(), left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
           .merge(t["item"].to_pandas(), left_on="ss_item_sk",
                  right_on="i_item_sk")
           .merge(t["promotion"].to_pandas(), left_on="ss_promo_sk",
                  right_on="p_promo_sk"))
    pdf = pdf[(pdf.cd_gender == "M") & (pdf.cd_marital_status == "S")
              & (pdf.cd_education_status == "College")
              & ((pdf.p_channel_email == "N") | (pdf.p_channel_event == "N"))
              & (pdf.d_year == 2000)]
    exp = (pdf.groupby("i_item_sk")
           .agg(agg1=("ss_quantity", "mean"),
                agg2=("ss_list_price", "mean"),
                agg3=("ss_coupon_amt", "mean"),
                agg4=("ss_ext_sales_price", "mean"))
           .sort_index().reset_index())
    assert np.array_equal(got["i_item_sk"], exp["i_item_sk"])
    for c in ("agg1", "agg2", "agg3", "agg4"):
        assert np.allclose(got[c], exp[c]), c


def _tpcds_q19(sess, t, F):
    """TPC-DS q19 shape: brand revenue for a (year, month) window with a
    manager filter — join order stresses the broadcast-vs-shuffle
    decision (BASELINE config 3)."""
    ss = sess.create_dataframe(t["store_sales"], num_partitions=4)
    dd = sess.create_dataframe(t["date_dim"], num_partitions=2)
    it = sess.create_dataframe(t["item"], num_partitions=2)
    got = (dd.join(ss, ss.ss_sold_date_sk == dd.d_date_sk)
           .join(it, ss.ss_item_sk == it.i_item_sk)
           .filter((it.i_manager_id == 8) & (dd.d_moy == 11)
                   & (dd.d_year == 1999))
           .groupBy("i_brand_id")
           .agg(F.sum(F.col("ss_ext_sales_price")).alias("ext_price"))
           .orderBy(F.col("ext_price").desc(), "i_brand_id")
           .collect().to_pandas())
    pdf = (t["store_sales"].to_pandas()
           .merge(t["date_dim"].to_pandas(), left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
           .merge(t["item"].to_pandas(), left_on="ss_item_sk",
                  right_on="i_item_sk"))
    pdf = pdf[(pdf.i_manager_id == 8) & (pdf.d_moy == 11)
              & (pdf.d_year == 1999)]
    exp = (pdf.groupby("i_brand_id")
           .agg(ext_price=("ss_ext_sales_price", "sum")).reset_index()
           .sort_values(["ext_price", "i_brand_id"],
                        ascending=[False, True]).reset_index(drop=True))
    assert np.array_equal(got["i_brand_id"], exp["i_brand_id"])
    assert np.allclose(got["ext_price"], exp["ext_price"])


def _tpcds_q42(sess, t, F):
    """TPC-DS q42 shape: (year, category) revenue for one month
    (BASELINE config 3)."""
    ss = sess.create_dataframe(t["store_sales"], num_partitions=4)
    dd = sess.create_dataframe(t["date_dim"], num_partitions=2)
    it = sess.create_dataframe(t["item"], num_partitions=2)
    got = (dd.join(ss, ss.ss_sold_date_sk == dd.d_date_sk)
           .join(it, ss.ss_item_sk == it.i_item_sk)
           .filter((dd.d_moy == 12) & (dd.d_year == 2000))
           .groupBy("d_year", "i_category_id")
           .agg(F.sum(F.col("ss_ext_sales_price")).alias("total"))
           .orderBy(F.col("total").desc(), "d_year", "i_category_id")
           .collect().to_pandas())
    pdf = (t["store_sales"].to_pandas()
           .merge(t["date_dim"].to_pandas(), left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
           .merge(t["item"].to_pandas(), left_on="ss_item_sk",
                  right_on="i_item_sk"))
    pdf = pdf[(pdf.d_moy == 12) & (pdf.d_year == 2000)]
    exp = (pdf.groupby(["d_year", "i_category_id"])
           .agg(total=("ss_ext_sales_price", "sum")).reset_index()
           .sort_values(["total", "d_year", "i_category_id"],
                        ascending=[False, True, True])
           .reset_index(drop=True))
    assert np.array_equal(got["i_category_id"], exp["i_category_id"])
    assert np.allclose(got["total"], exp["total"])


def _tpcds_q89_window(sess, t, F):
    """TPC-DS q89 shape: monthly category revenue ranked by a window over
    the star join (avg over the category partition; rows where the month
    deviates most from the category average) — the window-over-join shape
    the per-table micro queries don't cover."""
    from ..sql.window_api import Window
    ss = sess.create_dataframe(t["store_sales"], num_partitions=4)
    dd = sess.create_dataframe(t["date_dim"], num_partitions=2)
    it = sess.create_dataframe(t["item"], num_partitions=2)
    monthly = (dd.join(ss, ss.ss_sold_date_sk == dd.d_date_sk)
               .join(it, ss.ss_item_sk == it.i_item_sk)
               .filter(dd.d_year == 2000)
               .groupBy("i_category_id", "d_moy")
               .agg(F.sum(F.col("ss_ext_sales_price")).alias("rev")))
    w = Window.partitionBy("i_category_id")
    got = (monthly
           .withColumn("avg_rev", F.avg(F.col("rev")).over(w))
           .filter(F.col("rev") > F.col("avg_rev"))
           .orderBy("i_category_id", "d_moy")
           .collect().to_pandas())
    pdf = (t["store_sales"].to_pandas()
           .merge(t["date_dim"].to_pandas(), left_on="ss_sold_date_sk",
                  right_on="d_date_sk")
           .merge(t["item"].to_pandas(), left_on="ss_item_sk",
                  right_on="i_item_sk"))
    pdf = pdf[pdf.d_year == 2000]
    m = (pdf.groupby(["i_category_id", "d_moy"])
         .agg(rev=("ss_ext_sales_price", "sum")).reset_index())
    m["avg_rev"] = m.groupby("i_category_id").rev.transform("mean")
    exp = (m[m.rev > m.avg_rev]
           .sort_values(["i_category_id", "d_moy"])
           .reset_index(drop=True))
    assert len(got) == len(exp)
    assert np.array_equal(got["i_category_id"], exp["i_category_id"])
    assert np.array_equal(got["d_moy"], exp["d_moy"])
    assert np.allclose(got["rev"], exp["rev"])
    assert np.allclose(got["avg_rev"], exp["avg_rev"])



QUERIES: List[Tuple[str, Callable]] = [
    ("q1_filter_agg", _q1),
    ("q2_join_agg", _q2),
    ("q3_skewed_left_join", _q3),
    ("q4_window_topn", _q4),
    ("q5_global_sort", _q5),
    ("q6_strings", _q6),
    ("tpch_q1", _tpch_q1),
    ("tpch_q4_semi_join", _tpch_q4),
    ("tpch_q6", _tpch_q6),
    ("tpch_q14_promo_case", _tpch_q14),
    ("tpch_q1_sql", _tpch_q1_sql),
    ("tpch_q4_sql_exists", _tpch_q4_sql),
    ("tpch_q22_sql_subqueries", _tpch_q22_sql),
    ("tpch_q6_sql", _tpch_q6_sql),
    ("tpch_q17_corr_scalar", _tpch_q17_sql),
    # round 4: the 16 queries completing TPC-H 22 (tpch_queries.py)
    *[(f"tpch_{name}_full", _TQ.make_runner(sql, oracle))
      for name, sql, oracle in _TQ.QUERY_SET],
    ("tpcds_q3_star_join", _tpcds_q3),
    ("tpcds_q7_star4_avgs", _tpcds_q7),
    ("tpcds_q19_brand_rev", _tpcds_q19),
    ("tpcds_q42_cat_rev", _tpcds_q42),
    ("tpcds_q89_window_join", _tpcds_q89_window),
    # round 4: 12 more TPC-DS spec-SQL shapes (tpcds_queries.py)
    *[(f"tpcds_{name}", _TDS.make_runner(sql, oracle))
      for name, sql, oracle in _TDS.QUERY_SET],
]

#: table-set builders per query prefix (run_suite routes each query to
#: the tables it expects)
_TABLE_SETS = {"tpch": build_tpch_tables, "tpcds": _TDS.build_tables}


def iter_suite(rows: int, queries=None, tables=None, sess=None,
               extra_tables=None):
    """Per-query streaming driver over :data:`QUERIES` with amortized
    tables/session: yields each report record as its query completes, or
    an ``{"query", "error"}`` record for a failing query.  The one
    iteration loop `main()` consumes."""
    import spark_rapids_tpu as srt
    tables = tables if tables is not None else build_tables(rows)
    extra = extra_tables if extra_tables is not None else {}
    sess = sess or srt.session()
    for name, _fn in QUERIES:
        if queries and name not in queries:
            continue
        try:
            rep = run_suite(rows, queries=[name], tables=tables,
                            sess=sess, extra_tables=extra)
        except Exception as e:
            yield {"query": name,
                   "error": f"{type(e).__name__}: {e}"[:200]}
            continue
        for entry in rep:
            yield entry


#: re-export — the recipe lives at engine level (kernel_cache) so the
#: test conftest does not have to import the whole 60-query rig module
#: just to clear two caches
from ..sql.physical.kernel_cache import (  # noqa: E402
    release_compiled_programs)


class _RecordingTables(dict):
    """Table dict that records which tables a query touches, so the rig
    can report bytes-scanned per query instead of the whole set."""

    def __init__(self, base):
        super().__init__(base)
        self.accessed: set = set()

    def __getitem__(self, key):
        self.accessed.add(key)
        return super().__getitem__(key)

    def get(self, key, default=None):
        if key in self:
            self.accessed.add(key)
        return super().get(key, default)


def run_suite(rows: int = 50_000, queries=None, tables=None,
              sess=None, extra_tables=None) -> List[dict]:
    """Runs the selected queries; pass ``tables``/``sess``/
    ``extra_tables`` (a mutable dict, filled with the per-prefix TPC
    table sets on first use) to amortize datagen and session setup
    across calls.  ``seconds`` includes compile plus the pandas oracle
    check; ``warm_seconds`` is the second run with compiles amortized —
    the number to compare across rigs."""
    import spark_rapids_tpu as srt
    from ..sql import functions as F
    base_tables = tables if tables is not None else build_tables(rows)
    extra: Dict[str, Dict[str, pa.Table]] = (
        extra_tables if extra_tables is not None else {})
    sess = sess or srt.session()
    report = []
    for name, fn in QUERIES:
        if queries and name not in queries:
            continue
        prefix = name.split("_", 1)[0]
        if prefix in _TABLE_SETS:
            if prefix not in extra:
                extra[prefix] = _TABLE_SETS[prefix](rows)
            t = extra[prefix]
        else:
            t = base_tables
        rec = _RecordingTables(t)
        try:
            t0 = time.perf_counter()
            fn(sess, rec, F)
            total = time.perf_counter() - t0
            t0 = time.perf_counter()
            fn(sess, rec, F)  # warm again; compile amortized
            warm = time.perf_counter() - t0
        finally:
            # ALSO on failure: a raising query must not leak its
            # compiled programs toward the JIT-region crash
            release_compiled_programs()
        report.append({"query": name,
                       "seconds": round(total, 3),
                       "warm_seconds": round(warm, 3),
                       "rows": rows,
                       # bytes of the tables the query actually touched
                       # (warm_seconds also includes the pandas oracle
                       # re-check, so derived GB/s stays conservative)
                       "tables_bytes": sum(t[k].nbytes
                                           for k in rec.accessed)})
    return report


def scan_engagement_report(rows: int = 20_000, tmpdir=None) -> dict:
    """File-scan leg of the rig: write the
    fact table to parquet AND ORC (ORC with dictionary encoding on, the
    encoded-retention shape), scan each back with a filter+agg, and
    return the device-decode engagement scoreboard per format from the
    queries' ``last_query_metrics``.  A regression that silently declines
    every file to the host pyarrow path still returns bit-correct
    results — this record is what makes it VISIBLE (test_encoded asserts
    ``files_engaged >= 1`` for both formats)."""
    import os
    import shutil
    import tempfile

    import pyarrow.orc as pa_orc
    import pyarrow.parquet as pq

    import spark_rapids_tpu as srt
    from ..io_ import decode_stats as DS
    from ..sql import functions as F
    own = tmpdir is None
    tmpdir = tmpdir or tempfile.mkdtemp(prefix="srt_scan_rig_")
    try:
        fact = build_tables(max(rows, 1000))["fact"]
        sess = srt.session()
        out: Dict[str, dict] = {}
        for fmt in ("parquet", "orc"):
            path = os.path.join(tmpdir, f"fact.{fmt}")
            if fmt == "parquet":
                pq.write_table(fact, path)
            else:
                pa_orc.write_table(fact, path,
                                   dictionary_key_size_threshold=1.0)
            q = (getattr(sess.read, fmt)(path)
                 .filter(F.col("q") < 50).groupBy("q")
                 .agg(F.count("*").alias("c"),
                      F.sum(F.col("v")).alias("sv")))
            q.collect()
            m = sess.last_query_metrics
            out[fmt] = {
                "files_engaged": int(m.get(f"{fmt}DecodeFilesEngaged", 0)),
                "files_declined": int(
                    m.get(f"{fmt}DecodeFilesDeclined", 0)),
                "bytes_engaged": int(m.get(f"{fmt}DecodeBytesEngaged", 0)),
                "columns_encoded": int(m.get("encodedColumnsEncoded", 0)),
            }
        out["decode_stats"] = DS.report()
        return out
    finally:
        if own:
            shutil.rmtree(tmpdir, ignore_errors=True)


def main() -> None:
    import json
    import sys

    # runs on the platform JAX finds (JAX_PLATFORMS=cpu for a rehearsal);
    # the first line of output names it
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "device_count": len(jax.devices())}), flush=True)
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 50_000
    # stream per query (amortized tables/session) so a timeout or crash
    # still leaves the completed queries' evidence on stdout
    failed = 0
    for entry in iter_suite(rows):
        if "error" in entry:
            failed += 1
        print(json.dumps(entry), flush=True)
    # device-decode engagement leg: the rig report must show the
    # parquet/ORC scans actually ENGAGING the device decoders
    scan = scan_engagement_report(min(rows, 20_000))
    print(json.dumps({"scan_engagement": scan}), flush=True)
    for fmt in ("parquet", "orc"):
        if scan[fmt]["files_engaged"] < 1:
            print(json.dumps({"error": f"{fmt} scan did not engage the "
                              f"device decoder", "scan": scan[fmt]}),
                  flush=True)
            failed += 1
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
