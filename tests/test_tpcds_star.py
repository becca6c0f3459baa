"""TPC-DS store-sales star joins (ISSUE 33) on the CPU at small scale: the
engine against the benchmark's own pandas references over the benchmark's
own generator, the side-choice rule (``store_sales`` never built on, never
exchanged, whatever order the text names the tables in), NULL keys and
measures, and the test that ties one chip's share to the whole deployment:
the eight shares' per-group sums and counts add up to the whole table's
answer."""

import importlib.util
import itertools
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql.physical.exchange import (BroadcastExchangeExec,
                                                    ShuffleExchangeExec)
from spark_rapids_tpu.sql.physical.join import (AdaptiveJoinExec,
                                                BaseJoinExec,
                                                BroadcastHashJoinExec)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare as C  # noqa: E402  (benchmarks/compare.py)

SEEDS = (1, 2, 3)
#: under scale factor 0.137 the generator keeps 12,288 rows a ticket class
SMALL = {"scale_factor": 0.01, "share_of": 8}       # 49,152 fact rows
#: the fact table has to be the largest relation for the rule to call it
#: the probe side: date_dim is 11 MB and customer_demographics 106 MB
#: whatever the scale factor
Q3_SCALE = {"scale_factor": 0.01, "share_of": 4}     # 98,304 rows, 14 MB
Q7_SCALE = {"scale_factor": 0.3, "share_of": 1}      # 863,968 rows, 127 MB
Q3_TABLES = ("store_sales", "date_dim", "item")
Q3_FROM = " from  date_dim dt\n      ,store_sales\n      ,item\n"


def _module(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(
        "star_" + "_".join(parts).replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


GEN = _module("generators", "tpcds.py")
_BUILT: dict = {}


def tables_of(scale, seed, names=GEN.TABLES):
    key = (json.dumps(scale, sort_keys=True), seed, tuple(names))
    if key not in _BUILT:
        _BUILT[key] = GEN.build_tables(scale, seed, names)
    return _BUILT[key]


def query(q):
    with open(os.path.join(BENCH, "queries", q + ".sql")) as f:
        sql = f.read()
    with open(os.path.join(BENCH, "queries", q + ".json")) as f:
        spec = json.load(f)
    return sql, spec, _module("reference", q + ".py").reference


@pytest.fixture(scope="module")
def sess():
    """A session of its own at the default threshold: a bare
    ``srt.session()`` hands back whatever the module before left active in
    this worker, with that module's conf (the driver's run of PR 33 read a
    shuffled demographics join here and nowhere else)."""
    return srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * 1024 * 1024})


def register(sess, tables, partitions=2):
    for name, table in tables.items():
        sess.create_dataframe(
            table, num_partitions=partitions).createOrReplaceTempView(name)


def numbers(sess, q, tables, sql=None):
    """compare.py's three numbers for one collect, and the answer."""
    text, spec, reference = query(q)
    got = sess.sql(sql or text).collect().to_pandas(date_as_object=False)
    want = reference(C.tables_for_reference(tables, spec["tables"]))
    return C.compare(got, want, spec), spec["limits"], got, want


# --- what executed ----------------------------------------------------------

def executed(node):
    return node._chosen if isinstance(node, AdaptiveJoinExec) else node


def leaves(node):
    node = executed(node)
    if not node.children:
        return {a.name.split("_")[0] for a in node.output}
    return set().union(*(leaves(c) for c in node.children))


def joins(node, below_join=False, out=None):
    """Every executed join as (strategy, build side's tables, the fact
    table met an exchange below a join)."""
    out = [] if out is None else out
    node = executed(node)
    if isinstance(node, BaseJoinExec):
        out.append({
            "broadcast": isinstance(node, BroadcastHashJoinExec),
            "build": leaves(node._build),
            "probe": leaves(node._probe)})
        below_join = True
    if isinstance(node, ShuffleExchangeExec) and below_join \
            and "ss" in leaves(node):
        out.append({"fact_exchanged": True})
    for child in node.children:
        if isinstance(child, BroadcastExchangeExec):
            assert "ss" not in leaves(child), "store_sales was broadcast"
        joins(child, below_join, out)
    return out


def assert_star(sess, dimensions):
    """Every join of the last collect executed as a broadcast, built on a
    dimension, and ``store_sales`` crossed no exchange on its way up."""
    found = joins(sess._last_phys)
    assert not any(j.get("fact_exchanged") for j in found), found
    assert len(found) == len(dimensions)
    assert all(j["broadcast"] for j in found), found
    assert {frozenset(j["build"]) for j in found} == {
        frozenset([d]) for d in dimensions}
    assert all("ss" in j["probe"] for j in found)
    m = sess.last_query_metrics
    assert m.get("joinStrategyBroadcast") == len(dimensions)
    assert not m.get("joinStrategyShuffle")
    return found


# --- the engine against the references -------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("q,scale", [("tpcds_q7", SMALL),
                                     ("tpcds_q3", Q3_SCALE)])
def test_engine_returns_what_the_reference_returns(sess, q, scale, seed):
    tables = tables_of(scale, seed)
    register(sess, tables)
    got, limits, answer, want = numbers(sess, q, tables)
    assert len(want) > 0
    assert C.within(got, limits), got
    report = sess.explain(sess.sql(query(q)[0]), all_ops=False)
    assert "cannot run on TPU" not in report


def test_q7_probes_store_sales_and_broadcasts_every_dimension(sess):
    tables = tables_of(Q7_SCALE, 1)
    register(sess, tables, partitions=4)
    got, limits, _, want = numbers(sess, "tpcds_q7", tables)
    assert C.within(got, limits) and len(want) == 100
    assert_star(sess, ["cd", "d", "i", "p"])
    m = sess.last_query_metrics
    assert m["joinProbeRows"] >= tables["store_sales"].num_rows
    assert 0 < m["joinOutputRows"] < m["joinProbeRows"]
    nulls = tables["store_sales"].column("ss_cdemo_sk").null_count
    assert m["joinNullKeyRows"] >= nulls > 0
    assert m["broadcastBuildRows"] >= 27440 + 366
    assert m["aggGroupRows"] >= 100


def test_counters_are_per_collect(sess):
    """One DataFrame collected twice: each collect counts its own joins,
    build rows and group rows (none is a once-ever flag on the plan)."""
    tables = tables_of(Q3_SCALE, 2, Q3_TABLES)
    register(sess, tables)
    df = sess.sql(query("tpcds_q3")[0])
    seen = []
    for _ in range(2):
        df.collect()
        m = sess.last_query_metrics
        seen.append({k: m.get(k) for k in (
            "joinStrategyBroadcast", "broadcastBuildRows", "aggGroupRows",
            "joinProbeRows", "joinOutputRows")})
    assert seen[0] == seen[1]
    assert seen[0]["joinStrategyBroadcast"] == 2
    assert seen[0]["broadcastBuildRows"] > 0 and seen[0]["aggGroupRows"] > 0


@pytest.mark.parametrize("order", list(itertools.permutations(
    ["date_dim dt", "store_sales", "item"])),
    ids=lambda o: "-".join(t.split()[0] for t in o))
def test_q3_builds_on_the_dimensions_in_any_from_order(sess, order):
    tables = tables_of(Q3_SCALE, 2, Q3_TABLES)
    register(sess, tables)
    text = query("tpcds_q3")[0]
    assert Q3_FROM in text
    sql = text.replace(Q3_FROM, " from " + ", ".join(order) + "\n")
    got, limits, _, want = numbers(sess, "tpcds_q3", tables, sql=sql)
    assert C.within(got, limits) and len(want) > 0, got
    assert_star(sess, ["d", "i"])


# --- NULLs --------------------------------------------------------------------

def _surviving_rows(tables):
    """Row numbers of ``store_sales`` that q7's four joins let through."""
    import join_bytes as JB
    *_, (_, _, alive) = JB.survivors("tpcds_q7", tables)
    return np.flatnonzero(alive.to_numpy(zero_copy_only=False))


def _with_nulls(table, column, rows):
    values = table.column(column).to_pandas().astype("float64")
    mask = values.isna().to_numpy().copy()
    mask[rows] = True
    field = table.schema.field(column)
    planted = pa.array(np.nan_to_num(values.to_numpy()).astype(
        field.type.to_pandas_dtype()), type=field.type, mask=mask)
    return table.set_column(table.schema.get_field_index(column), field,
                            planted)


@pytest.mark.parametrize("left_rows,right_rows,threshold,want", [
    (4000, 18000, -1, "right"),        # like size, both shuffled: the text's
    (1000, 18000, -1, "left"),         # dwarfed (18x): the smaller, shuffled
    (4000, 18000, 10 << 20, "left"),   # may be broadcast, whichever side
], ids=["like-size", "dwarfed", "broadcastable"])
def test_the_build_side_of_an_inner_join(left_rows, right_rows, threshold,
                                         want):
    """``plan_join``'s side choice on its own: the left child is built on
    where it may be broadcast or the right child is at least ``_LIKE_SIZE``
    times its size; two sides of like size that are both shuffled keep the
    text's order."""
    from spark_rapids_tpu.sql.session import TpuSession
    before = TpuSession._active
    try:
        s = srt.session(**{
            "spark.rapids.sql.autoBroadcastJoinThreshold": threshold})
        for name, n in (("l", left_rows), ("r", right_rows)):
            s.create_dataframe(pa.table({
                name + "_k": pa.array(np.arange(n) % 977, pa.int32()),
                name + "_v": pa.array(np.arange(n), pa.int64())}),
                num_partitions=2).createOrReplaceTempView(name)
        got = s.sql("select count(*) c, sum(l_v + r_v) t from l, r "
                    "where l_k = r_k").collect().to_pandas()
        lk, rk = np.arange(left_rows) % 977, np.arange(right_rows) % 977
        pairs = np.bincount(lk, minlength=977) * np.bincount(rk, minlength=977)
        assert int(got.c[0]) == int(pairs.sum())
        found = joins(s._last_phys)
        assert len(found) == 1 and found[0]["build"] == {want[0]}
        assert found[0]["broadcast"] == (threshold > 0)
    finally:
        TpuSession._active = before


@pytest.mark.parametrize("column", ["ss_item_sk", "ss_cdemo_sk",
                                    "ss_quantity"])
def test_a_null_key_joins_nothing_and_a_null_measure_is_skipped(sess,
                                                                column):
    tables = dict(tables_of(SMALL, 1))
    rows = _surviving_rows(tables)
    assert len(rows) >= 8
    _, spec, reference = query("tpcds_q7")
    before = reference(C.tables_for_reference(tables, spec["tables"]))
    tables["store_sales"] = _with_nulls(tables["store_sales"], column,
                                        rows[::2])
    register(sess, tables)
    got, limits, answer, want = numbers(sess, "tpcds_q7", tables)
    assert C.within(got, limits), got
    # the planted NULLs changed the answer: rows left (a key) or an
    # average moved or became NULL (the measure)
    assert not before.equals(want)
    if column == "ss_quantity":
        assert len(want) == len(before)
        assert want.agg1.isna().sum() > before.agg1.isna().sum()
        assert np.array_equal(answer.agg1.isna(), want.agg1.isna())
    else:
        # (at this size store_sales is smaller than customer_demographics
        # and is built on there: only a probe's NULL keys are counted)
        assert len(want) < len(before) or not np.array_equal(
            want.agg2.to_numpy(), before.agg2.to_numpy())
        assert sess.last_query_metrics["joinNullKeyRows"] > 0


# --- the share ------------------------------------------------------------------

SHARE_SQL = {
    "tpcds_q7": """
select i_item_id, sum(ss_quantity) s1, count(ss_quantity) c1,
       sum(ss_list_price) s2, count(ss_list_price) c2,
       sum(ss_coupon_amt) s3, count(ss_coupon_amt) c3,
       sum(ss_sales_price) s4, count(ss_sales_price) c4
 from store_sales, customer_demographics, date_dim, item, promotion
 where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk and
       ss_cdemo_sk = cd_demo_sk and ss_promo_sk = p_promo_sk and
       cd_gender = 'M' and cd_marital_status = 'S' and
       cd_education_status = 'College' and
       (p_channel_email = 'N' or p_channel_event = 'N') and d_year = 2000
 group by i_item_id""",
    "tpcds_q3": """
select dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
       sum(ss_ext_sales_price) s, count(ss_ext_sales_price) c
 from date_dim dt, store_sales, item
 where dt.d_date_sk = store_sales.ss_sold_date_sk
   and store_sales.ss_item_sk = item.i_item_sk
   and item.i_manufact_id = 128 and dt.d_moy = 11
 group by dt.d_year, item.i_brand, item.i_brand_id""",
}


@pytest.mark.parametrize("q", ["tpcds_q7", "tpcds_q3"])
def test_the_eight_shares_add_up_to_the_whole_deployment(sess, q):
    """What one chip computes over its eighth of ``store_sales`` is its
    part of the SF's answer: per group, the shares' sums and counts add
    up to the uncut reference's averages (q7) and sums (q3)."""
    import pandas as pd
    whole_scale = {"scale_factor": 0.01, "share_of": 1}
    whole = tables_of(whole_scale, 3)
    _, spec, reference = query(q)
    want = reference(C.tables_for_reference(whole, spec["tables"]))
    assert len(want) > 10
    parts, rows = [], 0
    for share in range(8):
        tables = dict(whole)
        tables["store_sales"] = GEN.build_tables(
            {"scale_factor": 0.01, "share_of": 8, "share": share}, 3,
            ["store_sales"])["store_sales"]
        rows += tables["store_sales"].num_rows
        assert tables["store_sales"].num_rows == GEN.sizes(
            {"scale_factor": 0.01, "share_of": 8})["store_sales"]
        assert pc.all(pc.equal(pc.bit_wise_and(
            tables["store_sales"].column("ss_ticket_number"), 7),
            share)).as_py()
        register(sess, tables)
        parts.append(sess.sql(SHARE_SQL[q]).collect().to_pandas())
    assert rows == whole["store_sales"].num_rows
    both = pd.concat(parts)
    if q == "tpcds_q7":
        total = both.groupby("i_item_id").sum().sort_index().head(100)
        assert list(total.index) == list(want.i_item_id)
        for k in "1234":
            got = (total["s" + k] / total["c" + k].where(
                total["c" + k] > 0)).to_numpy(dtype=np.float64)
            np.testing.assert_allclose(got, want["agg" + k].to_numpy(),
                                       rtol=1e-12, equal_nan=True)
    else:
        total = both.groupby(["d_year", "brand_id", "brand"]).sum()
        total["sum_agg"] = total.s.where(total.c > 0)
        total = (total.reset_index().sort_values(
            ["d_year", "sum_agg", "brand_id"], ascending=[True, False, True],
            kind="stable").head(100).reset_index(drop=True))
        assert list(total.brand_id) == list(want.brand_id)
        assert list(total.d_year) == list(want.d_year)
        np.testing.assert_allclose(total.sum_agg.to_numpy(),
                                   want.sum_agg.to_numpy(), rtol=1e-12)
