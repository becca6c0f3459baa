"""The planner's column pruning (ISSUE 34, ``sql/column_pruning.py``): the
rule by node type, that planning never mutates a DataFrame's plan, that SQL
and the DataFrame API narrow the same scans, that answers are the same with
the rule and without it (TPC-H Q3, TPC-DS q7 and q3 with NULL keys planted),
that the upload cache still holds a table once, the two counters, and the
plans the benchmark's join cells get at their own sizes."""

import contextlib
import importlib.util
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import Window
from spark_rapids_tpu.sql import column_pruning as CP
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql import plan as P
from spark_rapids_tpu.sql.physical import basic as B
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

MIB = 1 << 20


def _module(*parts):
    spec = importlib.util.spec_from_file_location(
        "prune_" + "_".join(parts).replace(".", "_"),
        os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TPCH = _module("generators", "tpch.py")
TPCDS = _module("generators", "tpcds.py")


def query(q):
    with open(os.path.join(BENCH, "queries", q + ".sql")) as f:
        sql = f.read()
    with open(os.path.join(BENCH, "queries", q + ".json")) as f:
        spec = json.load(f)
    return sql, spec, _module("reference", q + ".py").reference


@contextlib.contextmanager
def session_of(**conf):
    """A session of this test's own, and the one before put back (a bare
    ``srt.session()`` of a later module must not inherit the conf)."""
    from spark_rapids_tpu.sql.session import TpuSession
    before = TpuSession._active
    try:
        yield srt.session(**conf)
    finally:
        TpuSession._active = before


@pytest.fixture(scope="module")
def sess():
    with session_of(**{
            "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * MIB}) as s:
        yield s


def table(prefix, n=64, seed=0):
    """k (a key), then four measures and a string, all named by prefix."""
    rng = np.random.default_rng(seed)
    return pa.table({
        prefix + "k": pa.array(np.arange(n) % 7, pa.int64()),
        prefix + "a": pa.array(rng.integers(0, 100, n), pa.int32()),
        prefix + "b": pa.array(rng.normal(size=n)),
        prefix + "c": pa.array(rng.integers(0, 9, n), pa.int64()),
        prefix + "s": pa.array(["s%d" % (i % 5) for i in range(n)]),
        prefix + "flag": pa.array(np.arange(n) % 2 == 0)})


@pytest.fixture(scope="module")
def frames(sess):
    l = sess.create_dataframe(table("l_"), num_partitions=2)
    r = sess.create_dataframe(table("r_", seed=1), num_partitions=2)
    l.createOrReplaceTempView("l")
    r.createOrReplaceTempView("r")
    return l, r


def scans(plan):
    """The column names of every in-memory leaf, left to right."""
    if isinstance(plan, (P.Relation, P.CachedRelation)):
        return [[a.name for a in plan.output]]
    return [s for c in plan.children for s in scans(c)]


def pruned(df):
    return scans(CP.prune_columns(df._plan))


@contextlib.contextmanager
def rule_off():
    """Plans the tree as it stands: what the engine did before the rule."""
    rule = CP.prune_columns
    CP.prune_columns = lambda plan: plan
    try:
        yield
    finally:
        CP.prune_columns = rule


def same_answer(df, sort_by=None):
    """Collects with the rule and without it; both answers, equal."""
    with_rule = df.collect()
    with rule_off():
        without = df.collect()
    if sort_by:
        order = [(c, "ascending") for c in sort_by]
        with_rule, without = with_rule.sort_by(order), without.sort_by(order)
    assert with_rule.schema == without.schema
    assert with_rule.equals(without), (with_rule.to_pydict(),
                                       without.to_pydict())
    return with_rule


# --- the rule, node by node ---------------------------------------------------

def _project(l, r):
    return l.select("l_a"), [["l_a"]]


def _computed(l, r):
    return l.select((F.col("l_a") + F.col("l_c")).alias("t")), [
        ["l_a", "l_c"]]


def _filter(l, r):
    return l.filter(F.col("l_b") > 0).select("l_a"), [["l_a", "l_b"]]


def _aggregate(l, r):
    return l.groupBy("l_k").agg(F.sum("l_c").alias("t")), [["l_k", "l_c"]]


def _sort(l, r):
    return l.orderBy("l_c").select("l_a"), [["l_a", "l_c"]]


def _limit(l, r):
    return l.limit(5).select("l_s"), [["l_s"]]


def _sample(l, r):
    return l.sample(0.5, seed=3).select("l_a"), [["l_a"]]


def _repartition(l, r):
    return l.repartition(3, "l_k").select("l_a"), [["l_k", "l_a"]]


def _union(l, r):
    both = l.union(r.select("r_k", "r_a", "r_b", "r_c", "r_s", "r_flag"))
    return both.select("l_a"), [["l_a"], ["r_a"]]


def _union_of_filters(l, r):
    """A child that cannot hand on exactly what is asked (its filter reads
    another column) keeps the union whole: children line up by position."""
    both = l.union(r.filter(F.col("r_b") > 0))
    return both.select("l_a"), [None, None]


def _window(l, r):
    w = Window.partitionBy("l_k").orderBy("l_b")
    return (l.withColumn("n", F.row_number().over(w)).select("l_s", "n"),
            [["l_k", "l_b", "l_s"]])


def _expand(l, r):
    return l.rollup("l_k", "l_a").agg(F.sum("l_c").alias("t")), [
        ["l_k", "l_a", "l_c"]]


def _pandas(l, r):
    """A node the rule does not know requires every column below it."""
    def double(frames):
        for frame in frames:
            yield frame[["l_a"]] * 2
    return l.mapInPandas(double, "l_a int").select("l_a"), [None]


def _count_star(l, r):
    """Nothing is read: the narrowest column carries the rows."""
    from spark_rapids_tpu.sql.expressions.aggregates import Count
    from spark_rapids_tpu.sql.expressions.core import Alias
    from spark_rapids_tpu.sql.dataframe import DataFrame
    return DataFrame(P.Aggregate((), (Alias(Count(), "n"),), l._plan),
                     l._session), [["l_flag"]]


def _select_star(l, r):
    return l._session.sql("select * from l where l_b > 0"), [None]


def _cross(l, r):
    return l.crossJoin(r).select("l_a"), [["l_a"], ["r_flag"]]


CASES = [_project, _computed, _filter, _aggregate, _sort, _limit, _sample,
         _repartition, _union, _union_of_filters, _window, _expand, _pandas,
         _count_star, _select_star, _cross]
WHOLE = {"l": list(table("l_").column_names),
         "r": list(table("r_").column_names)}


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.__name__.strip("_"))
def test_the_rule_by_node_type(frames, case):
    df, want = case(*frames)
    got = pruned(df)
    assert len(got) == len(want)
    for names, wanted in zip(got, want):
        assert names == (wanted if wanted is not None
                         else WHOLE[names[0][0]]), got
    if case in (_limit, _sample):       # which rows come is not an order
        assert df.collect().num_rows <= 64
    else:
        same_answer(df, sort_by=df.columns)


def test_select_star_is_the_same_tree(frames):
    l, r = frames
    df = l.join(r, l.l_k == r.r_k, "inner")
    assert CP.prune_columns(df._plan) is df._plan
    assert CP.prune_columns(l._plan) is l._plan


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "left_semi", "left_anti"])
def test_a_join_keeps_its_keys_and_its_condition(frames, how):
    """Every join type with a non-equi residual: each side keeps its keys,
    what the condition reads of it and what is selected above."""
    l, r = frames
    on = (l.l_k == r.r_k) & (l.l_a < r.r_c * 20)
    joined = l.join(r, on, how)
    df = joined.select("l_s") if how.startswith("left_") \
        else joined.select("l_s", "r_b")
    right = ["r_k", "r_c"] if how.startswith("left_") \
        else ["r_k", "r_b", "r_c"]
    assert pruned(df) == [["l_k", "l_a", "l_s"], right]
    same_answer(df, sort_by=df.columns)


def test_a_self_join_narrows_one_table_two_ways(sess, frames):
    l, _ = frames
    df = sess.sql("select x.l_a, y.l_s from l x, l y "
                  "where x.l_k = y.l_k and x.l_c < y.l_c")
    narrowed = CP.prune_columns(df._plan)
    assert scans(narrowed) == [["l_k", "l_a", "l_c"], ["l_k", "l_c", "l_s"]]
    leaves = []

    def walk(node):
        if isinstance(node, P.Relation):
            leaves.append(node)
        for c in node.children:
            walk(c)
    walk(narrowed)
    # two narrowings of one table: the same pa.Table and partition objects
    assert leaves[0] is not leaves[1]
    assert leaves[0].table is leaves[1].table is l._plan.table
    assert leaves[0].partitions is leaves[1].partitions is l._plan.partitions
    same_answer(df, sort_by=["l_a", "l_s"])


def test_a_reference_bound_by_name_keeps_its_column(sess, frames):
    """A cached relation mints its attributes anew on every ``output``: what
    was resolved against an earlier list binds by name, and so prunes."""
    l, _ = frames
    cached = l.select("l_k", "l_a", "l_b").cache()
    df = cached.filter(F.col("l_b") > 0).groupBy("l_k").agg(
        F.count("l_k").alias("n"))
    assert pruned(df) == [["l_k", "l_b"]]
    same_answer(df, sort_by=["l_k"])
    # the narrowed copy reads the table its original decodes, once
    narrowed = CP.prune_columns(df._plan)
    while narrowed.children:
        narrowed = narrowed.children[0]
    assert narrowed.table is cached._plan.table


def test_an_unknown_join_type_keeps_everything(frames):
    l, r = frames
    j = P.Join(l._plan, r._plan, "existence",
               (l._plan.output[0],), (r._plan.output[0],))
    top = P.Project((l._plan.output[1],), j)
    assert scans(CP.prune_columns(top)) == [WHOLE["l"], WHOLE["r"]]


def test_a_table_with_two_columns_of_one_name_is_left_whole(sess):
    t = pa.Table.from_arrays([pa.array([1, 2]), pa.array([3, 4]),
                              pa.array([5, 6])], names=["a", "a", "b"])
    rel = P.Relation(t)
    top = P.Project((rel.output[2],), rel)
    assert scans(CP.prune_columns(top)) == [["a", "a", "b"]]


# --- planning leaves the DataFrame's plan alone ----------------------------------

def test_the_logical_plan_is_unchanged_after_a_collect(sess, frames):
    l, r = frames
    df = (l.join(r, l.l_k == r.r_k, "inner").filter(F.col("l_b") > 0)
          .groupBy("l_s").agg(F.sum("r_c").alias("t")))
    before = df._plan.tree_string()
    nodes = []

    def walk(node):
        nodes.append(node)
        for c in node.children:
            walk(c)
    walk(df._plan)
    first = df.collect()
    second = df.collect()           # a DataFrame collected twice plans twice
    assert first.equals(second)
    after = []
    nodes, seen = after, nodes
    walk(df._plan)
    assert df._plan.tree_string() == before
    assert len(after) == len(seen) and all(
        a is b for a, b in zip(after, seen))
    assert scans(df._plan) == [WHOLE["l"], WHOLE["r"]]
    assert pruned(df) == [["l_k", "l_b", "l_s"], ["r_k", "r_c"]]


def test_explain_describes_the_executed_plan(sess, frames):
    l, _ = frames
    df = l.filter(F.col("l_b") > 0).select("l_a")
    report = sess.explain(df)
    assert "TpuInMemoryScan [l_a, l_b]" in report
    assert "l_flag" not in report
    df.collect()
    assert "TpuInMemoryScan [l_a, l_b]" in sess.explain()


# --- one rule for every front end -----------------------------------------------

Q3_LIKE = {
    "comma": "select l_s, sum(r_c) t from l, r "
             "where l_k = r_k and l_b > 0 group by l_s",
    "join_on": "select l_s, sum(r_c) t from l join r on l_k = r_k "
               "where l_b > 0 group by l_s",
}


def test_sql_and_the_dataframe_api_narrow_the_same_scans(sess, frames):
    l, r = frames
    api = (l.join(r, l.l_k == r.r_k, "inner").filter(F.col("l_b") > 0)
           .groupBy("l_s").agg(F.sum("r_c").alias("t")))
    want = [["l_k", "l_b", "l_s"], ["r_k", "r_c"]]
    answers = [same_answer(api, sort_by=["l_s"])]
    assert pruned(api) == want
    for text in Q3_LIKE.values():
        df = sess.sql(text)
        assert pruned(df) == want, text
        answers.append(same_answer(df, sort_by=["l_s"]))
    assert all(a.equals(answers[0]) for a in answers)


# --- the same answers -------------------------------------------------------------

def _with_null_keys(t, column, every=5):
    values = t.column(column).to_numpy(zero_copy_only=False)
    mask = np.zeros(len(values), dtype=bool)
    mask[::every] = True
    field = t.schema.field(column)
    planted = pa.array(np.nan_to_num(values.astype("float64")).astype(
        field.type.to_pandas_dtype()), type=field.type, mask=mask)
    return t.set_column(t.schema.get_field_index(column),
                        pa.field(column, field.type, True), planted)


def _benchmark_tables(q, seed):
    if q == "tpch_q3":
        tables = TPCH.build_tables({"scale_factor": 0.002}, seed,
                                   ("lineitem", "orders", "customer"))
        tables["lineitem"] = _with_null_keys(tables["lineitem"],
                                             "l_orderkey")
        tables["orders"] = _with_null_keys(tables["orders"], "o_custkey", 7)
        return tables
    tables = dict(TPCDS.build_tables(
        {"scale_factor": 0.01, "share_of": 8}, seed, TPCDS.TABLES))
    tables["store_sales"] = _with_null_keys(
        _with_null_keys(tables["store_sales"], "ss_item_sk", 11),
        "ss_sold_date_sk", 13)
    return tables


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("q", ["tpch_q3", "tpcds_q7", "tpcds_q3"])
def test_the_answer_is_the_same_with_the_rule_and_without(sess, q, seed):
    tables = _benchmark_tables(q, seed)
    for name, t in tables.items():
        sess.create_dataframe(t, num_partitions=2
                              ).createOrReplaceTempView(name)
    text, spec, reference = query(q)
    df = sess.sql(text)
    got = same_answer(df)
    assert got.num_rows > 0
    want = reference(C.tables_for_reference(tables, spec["tables"]))
    numbers = C.compare(got.to_pandas(date_as_object=False), want, spec)
    assert C.within(numbers, spec["limits"]), numbers
    # what the query file says the query reads is what the scans hand on
    read = {t: sorted(cols) for t, cols in spec["tables"].items()}
    narrowed = {s[0].split("_")[0]: sorted(s) for s in pruned(df)}
    assert sorted(narrowed.values()) == sorted(read.values())


# --- the upload cache and the counters ----------------------------------------------

def test_the_upload_cache_holds_a_table_once(sess):
    t = table("u_", n=200, seed=5)
    df = sess.create_dataframe(t, num_partitions=1)
    df.select("u_a").collect()
    df.filter(F.col("u_s") == "s1").select("u_b", "u_c").collect()
    df.collect()
    part = df._plan.partitions[0] if df._plan.partitions else df._plan.table
    with B._UPLOAD_LOCK:
        entry = B._UPLOAD_CACHE[id(part)]
    assert entry[0]() is part
    assert len(entry[1]) == 1                  # one backend, one upload
    (batches,) = entry[1].values()
    assert all(b.names == tuple(t.column_names) for b in batches)


def test_the_scan_selects_on_the_host(sess):
    """What the narrowed scan yields are the cached batch's own arrays."""
    t = table("h_", n=100, seed=6)
    df = sess.create_dataframe(t, num_partitions=1).select("h_c", "h_a")
    scan = sess.physical_plan(df)
    while scan.children:
        scan = scan.children[0]
    assert isinstance(scan, B.InMemoryScanExec)
    assert [a.name for a in scan.output] == ["h_a", "h_c"]
    from spark_rapids_tpu.sql.physical.base import TaskContext
    (got,) = list(scan.execute(0, TaskContext(0, sess._conf)))
    (whole,) = B._cached_upload(scan._parts[0], scan.backend, sess._conf)
    assert got.names == ("h_a", "h_c") and got.num_rows_int == 100
    assert got.columns[0] is whole.column("h_a")
    assert got.columns[1] is whole.column("h_c")
    assert scan.estimate_bytes() == (t.column("h_a").nbytes
                                     + t.column("h_c").nbytes)


def test_the_counters_count_a_scan_once_a_collect(sess, frames):
    l, r = frames
    df = (l.join(r, l.l_k == r.r_k, "inner")
          .groupBy("l_s").agg(F.sum("r_c").alias("t")))
    seen = []
    for _ in range(2):
        df.collect()
        m = sess.last_query_metrics
        seen.append((m.get("scanColumnsRead"), m.get("scanColumnsPruned")))
    assert seen == [(4, 8), (4, 8)]
    l.collect()
    m = sess.last_query_metrics
    assert (m.get("scanColumnsRead"), m.get("scanColumnsPruned", 0)) == (6, 0)


# --- the benchmark's join cells, planned at their own sizes -------------------------

def executed(node):
    return node._chosen if isinstance(node, AdaptiveJoinExec) and \
        node._chosen is not None else node


def walk_physical(node):
    node = executed(node)
    yield node
    for c in node.children:
        yield from walk_physical(c)


def leaf_tables(node):
    return {a.name.split("_")[0] for n in walk_physical(node)
            if not n.children for a in n.output}


@pytest.fixture(scope="module")
def tpch_cell():
    """LINEITEM, ORDERS and CUSTOMER at ``tpch-1m-8tables``' scale."""
    with open(os.path.join(BENCH, "configs", "tpch-1m-8tables.json")) as f:
        config = json.load(f)
    scale = {"scale_factor": config["scale"]["scale_factor"]}
    tables = TPCH.build_tables(scale, 3, ("lineitem", "orders", "customer"))
    assert tables["lineitem"].num_rows == 999995
    return tables, int(config["storage"]["memory"]["partitions"])


def _plan_q3(conf, tables, partitions):
    with session_of(**conf) as s:
        for name, t in tables.items():
            s.create_dataframe(t, num_partitions=partitions
                               ).createOrReplaceTempView(name)
        df = s.sql(query("tpch_q3")[0])
        return s.physical_plan(df), pruned(df)


def test_q3_on_one_chip_plans_no_hash_exchange_below_the_aggregate(
        tpch_cell):
    tables, partitions = tpch_cell
    phys, narrowed = _plan_q3({}, tables, partitions)
    assert narrowed == [["c_custkey", "c_mktsegment"],
                        ["o_orderkey", "o_custkey", "o_orderdate",
                         "o_shippriority"],
                        ["l_orderkey", "l_extendedprice", "l_discount",
                         "l_shipdate"]]
    nodes = list(walk_physical(phys))
    sizes = {n.output[0].name[0]: n.estimate_bytes() for n in nodes
             if isinstance(n, B.InMemoryScanExec)}
    assert sizes["l"] == 999995 * 28 and sizes["o"] == 250000 * 24
    joins = [n for n in nodes if isinstance(n, BaseJoinExec)]
    assert len(joins) == 2 and all(
        isinstance(j, BroadcastHashJoinExec) for j in joins)
    # customer under orders, their join under lineitem, the probe
    assert leaf_tables(joins[0]._build) == {"c", "o"}
    assert leaf_tables(joins[0]._probe) == {"l"}
    assert leaf_tables(joins[1]._build) == {"c"}
    exchanges = [n for n in nodes if isinstance(n, ShuffleExchangeExec)]
    assert len(exchanges) == 1          # the aggregate's own, above the joins
    assert not any(isinstance(n, ShuffleExchangeExec)
                   for j in joins for n in walk_physical(j))


def test_q3_on_four_chips_keeps_its_exchanges_and_lineitem_its_side(
        tpch_cell):
    """``tpch-1m-mesh4``'s conf (nothing may be broadcast).  The join under
    ``lineitem`` reads 6.0 against 28.0 MB, inside ``_LIKE_SIZE``: it builds
    on the side it built on (PR 33 read +54.9 % on four chips when that one
    flipped), and every join shuffles both sides as it did.  What the pruned
    sizes do change: ``customer`` (2 columns, 0.55 MB) is now 11 times
    smaller than ``orders`` (4 columns, 6.0 MB) where the whole tables were
    6.6 times apart, so ``customer`` \u22c8 ``orders`` builds on ``customer``
    (PERF.md section 6, PR 34)."""
    tables, partitions = tpch_cell
    with open(os.path.join(BENCH, "configs", "tpch-1m-mesh4.json")) as f:
        conf = dict(json.load(f)["session_conf"])
    conf.pop("spark.executor.instances")    # the plan's shape, on one device

    def shape(phys):
        out = []
        for n in walk_physical(phys):
            if isinstance(n, AdaptiveJoinExec):
                build = n.children[0 if n._build_left else 1]
                out.append(("join", tuple(sorted(leaf_tables(build))),
                            tuple(sorted(leaf_tables(n)))))
            elif isinstance(n, (ShuffleExchangeExec, BroadcastExchangeExec)):
                out.append((type(n).__name__,
                            tuple(sorted(leaf_tables(n)))))
        return out

    now, narrowed = _plan_q3(conf, tables, partitions)
    with rule_off():
        before, whole = _plan_q3(conf, tables, partitions)
    assert [len(s) for s in narrowed] == [2, 4, 4]
    assert [len(s) for s in whole] == [8, 9, 16]
    all_three = ("c", "l", "o")
    assert shape(before) == [
        ("ShuffleExchangeExec", all_three), ("join", ("l",), all_three),
        ("join", ("o",), ("c", "o"))]
    assert shape(now) == [
        ("ShuffleExchangeExec", all_three), ("join", ("l",), all_three),
        ("join", ("c",), ("c", "o"))]
    sizes = {n.output[0].name[0]: n.estimate_bytes()
             for n in walk_physical(now)
             if isinstance(n, B.InMemoryScanExec)}
    from spark_rapids_tpu.sql.physical.join import _LIKE_SIZE
    assert sizes["o"] * _LIKE_SIZE > sizes["l"] > sizes["o"]
    assert sizes["c"] * _LIKE_SIZE < sizes["o"]


def test_q7_at_the_cells_size_broadcasts_all_four_dimensions():
    """The dimensions at SF100's sizes (``item`` 204,000 rows: 2 of its 22
    columns are under the threshold, so its join is planned broadcast and
    both exchanges of it go), ``store_sales`` the probe of all four and
    never exchanged, 19 columns read of the five tables' 101."""
    with open(os.path.join(
            BENCH, "configs", "tpcds-sf100-store-share.json")) as f:
        config = json.load(f)
    tables = dict(TPCDS.build_tables(
        config["scale"], 1, [t for t in TPCDS.TABLES if t != "store_sales"]))
    assert tables["item"].num_rows == 204000
    tables.update(TPCDS.build_tables(
        {"scale_factor": 0.3, "share_of": 1}, 1, ["store_sales"]))
    assert sum(t.num_columns for t in tables.values()) == 101
    with session_of(**dict(config["session_conf"])) as s:
        for name, t in tables.items():
            s.create_dataframe(t, num_partitions=4
                               ).createOrReplaceTempView(name)
        text, spec, reference = query("tpcds_q7")
        df = s.sql(text)
        planned = s.physical_plan(df)
        static = [n for n in walk_physical(planned)
                  if isinstance(n, BroadcastHashJoinExec)]
        # item, dates and promotions by the static sizes; demographics (1.9 M
        # rows before its filter) by the adaptive join's measure
        assert {frozenset(leaf_tables(j._build)) for j in static} >= {
            frozenset(["i"]), frozenset(["d"]), frozenset(["p"])}
        got = df.collect()
        m = s.last_query_metrics
        assert m.get("scanColumnsRead") == 19
        assert m.get("scanColumnsPruned") == 82
        assert m.get("joinStrategyBroadcast") == 4
        assert not m.get("joinStrategyShuffle")
        joins = [n for n in walk_physical(s._last_phys)
                 if isinstance(n, BaseJoinExec)]
        assert len(joins) == 4
        assert all(isinstance(j, BroadcastHashJoinExec) for j in joins)
        assert all("ss" in leaf_tables(j._probe) for j in joins)
        assert {frozenset(leaf_tables(j._build)) for j in joins} == {
            frozenset([d]) for d in ("cd", "d", "i", "p")}
        assert not any(isinstance(n, ShuffleExchangeExec)
                       for j in joins for n in walk_physical(j))
        want = reference(C.tables_for_reference(tables, spec["tables"]))
        numbers = C.compare(got.to_pandas(date_as_object=False), want, spec)
        assert C.within(numbers, spec["limits"]) and len(want) == 100


# --- a scan of files (ISSUE 36): the rule narrows ``ScanRelation`` too ---------------

def file_scans(plan):
    """Every ``ScanRelation`` of a logical plan, left to right."""
    if isinstance(plan, P.ScanRelation):
        return [plan]
    return [s for c in plan.children for s in file_scans(c)]


def file_columns(plan):
    return [[a.name for a in s.output] for s in file_scans(plan)]


@contextlib.contextmanager
def scan_rule_off():
    """The rule as the parent had it: a ``ScanRelation`` is left whole."""
    rule = CP._Pruner._ScanRelation
    CP._Pruner._ScanRelation = CP._Pruner._unknown
    try:
        yield
    finally:
        CP._Pruner._ScanRelation = rule


@pytest.fixture(scope="module")
def lineitem_file(tmp_path_factory):
    """LINEITEM (16 columns) as the parquet cell stores it, at a 1000th of
    its size; the session that reads it and the table."""
    import pyarrow.parquet as pq
    t = TPCH.build_tables({"scale_factor": 0.001}, 5, ("lineitem",)
                          )["lineitem"]
    assert t.num_columns == 16
    path = str(tmp_path_factory.mktemp("pruned_scan") / "lineitem.parquet")
    pq.write_table(t, path, row_group_size=2048)
    with session_of() as s:
        s.read.parquet(path).createOrReplaceTempView("lineitem")
        yield s, path, t


@pytest.mark.parametrize("q, wanted", [
    ("tpch_q6", ["l_quantity", "l_extendedprice", "l_discount",
                 "l_shipdate"]),
    ("tpch_q1", ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                 "l_returnflag", "l_linestatus", "l_shipdate"])])
def test_the_parquet_cells_queries_read_4_and_7_of_16_columns(
        lineitem_file, q, wanted):
    s, path, t = lineitem_file
    text, spec, reference = query(q)
    df = s.sql(text)
    (scan,) = file_scans(CP.prune_columns(df._plan))
    assert [a.name for a in scan.output] == wanted      # the file's order
    assert [f.name for f in scan.read_schema.fields] == wanted
    assert scan.columns == tuple(t.column_names.index(n) for n in wanted)
    assert scan.file_width == 16 and scan.paths == (path,)
    (whole,) = file_scans(df._plan)
    assert len(whole.output) == 16 and whole.columns is None
    # the same AttributeReference objects: what is above still binds
    assert all(any(a is b for b in whole.output) for a in scan.output)
    got = df.collect()
    m = s.last_query_metrics
    assert m.get("scanColumnsRead") == len(wanted)
    assert m.get("scanColumnsPruned") == 16 - len(wanted)
    want = reference(C.tables_for_reference({"lineitem": t}, spec["tables"]))
    numbers = C.compare(got.to_pandas(date_as_object=False), want, spec)
    assert C.within(numbers, spec["limits"]), numbers


def test_count_star_over_a_file_keeps_its_narrowest_column(lineitem_file):
    s, path, t = lineitem_file
    df = s.sql("select count(*) as n from lineitem")
    # int32 and date32 are the narrowest; l_linenumber is the first of them
    assert file_columns(CP.prune_columns(df._plan)) == [["l_linenumber"]]
    assert df.collect()["n"].to_pylist() == [t.num_rows]
    assert s.last_query_metrics.get("scanColumnsRead") == 1


def test_a_file_read_whole_or_with_two_columns_of_one_name_keeps_its_node(
        lineitem_file, tmp_path):
    import pyarrow.parquet as pq
    s, path, _ = lineitem_file
    df = s.read.parquet(path)
    assert CP.prune_columns(df._plan) is df._plan
    star = s.sql("select * from lineitem where l_quantity < 3")
    assert file_scans(CP.prune_columns(star._plan)) == file_scans(star._plan)
    twice = str(tmp_path / "twice.parquet")
    pq.write_table(pa.Table.from_arrays(
        [pa.array([1, 2]), pa.array([3, 4]), pa.array([5, 6])],
        names=["a", "a", "b"]), twice)
    rel = s.read.parquet(twice)._plan
    top = P.Project((rel.output[2],), rel)
    assert file_scans(CP.prune_columns(top)) == [rel]


def test_a_file_joined_with_itself_gets_two_narrowings(lineitem_file):
    s, path, _ = lineitem_file
    df = s.sql("select x.l_tax, y.l_comment from lineitem x, lineitem y "
               "where x.l_orderkey = y.l_orderkey "
               "and x.l_linenumber = 1 and y.l_linenumber = 2")
    (whole,) = set(map(id, file_scans(df._plan)))       # one node, twice
    narrowed = CP.prune_columns(df._plan)
    left, right = file_scans(narrowed)
    assert left is not right and id(left) != whole != id(right)
    assert file_columns(narrowed) == [
        ["l_orderkey", "l_linenumber", "l_tax"],
        ["l_orderkey", "l_linenumber", "l_comment"]]
    assert left.paths == right.paths == (path,)
    assert left.options is right.options
    got = df.collect()
    with scan_rule_off():
        want = df.collect()
    order = [("l_tax", "ascending"), ("l_comment", "ascending")]
    assert got.num_rows > 0 and got.sort_by(order).equals(want.sort_by(order))


def test_a_file_scan_is_not_mutated_by_the_rule(lineitem_file):
    s, path, _ = lineitem_file
    df = s.sql(query("tpch_q6")[0])
    (scan,) = file_scans(df._plan)
    before = (df._plan.tree_string(), list(scan.output), scan.read_schema,
              scan.columns, scan.file_width)
    first = df.collect()
    second = df.collect()               # plans twice, narrows twice
    assert first.equals(second)
    (after,) = file_scans(df._plan)
    assert after is scan
    assert (df._plan.tree_string(), list(scan.output), scan.read_schema,
            scan.columns, scan.file_width) == before
    assert all(a is b for a, b in zip(scan.output, before[1]))
    assert len(scan.read_schema.fields) == 16


def test_a_narrowed_scan_of_a_narrowed_scan_keeps_the_files_positions(
        lineitem_file):
    s, path, t = lineitem_file
    (scan,) = file_scans(s.read.parquet(path)._plan)
    once = scan.narrowed([scan.output[i] for i in (2, 5, 9)])
    again = once.narrowed([once.output[2], once.output[0]])
    assert again.columns == (2, 9) and again.file_width == 16
    assert [a.name for a in again.output] == ["l_suppkey", "l_linestatus"]


def test_the_scan_adjacent_filter_still_pushes_its_conjuncts(lineitem_file):
    from spark_rapids_tpu.io_.exec import FileScanExec
    s, path, t = lineitem_file
    df = s.read.parquet(path)
    q = (df.filter((df.l_orderkey > 10**9) & (df.l_quantity < 24))
         .select("l_tax"))
    (scan,) = [n for n in walk_physical(s.physical_plan(q))
               if isinstance(n, FileScanExec)]
    assert [a.name for a in scan.output] == ["l_orderkey", "l_quantity",
                                             "l_tax"]
    assert sorted(scan.pushed_filters) == [("l_orderkey", ">", 10**9),
                                           ("l_quantity", "<", 24)]
    assert q.collect().num_rows == 0
    m = s.last_query_metrics
    groups = -(-t.num_rows // 2048)
    assert m.get("rowGroupsTotal") == m.get("rowGroupsPruned") == groups


def _cell_frames(s, cell):
    """A cell's tables at a small size, registered as the cell registers
    them; its queries' texts."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(BENCH, "configs",
                           workload["config"] + ".json")) as f:
        config = json.load(f)
    assert workload["view"] == "memory"
    gen, scale = (TPCH, {"scale_factor": 0.002}) \
        if config["generator"] == "tpch" \
        else (TPCDS, {"scale_factor": 0.01, "share_of": 8})
    tables = gen.build_tables(scale, 2, config["tables"])
    for name, t in tables.items():
        s.create_dataframe(
            t, num_partitions=int(config["storage"]["memory"]["partitions"])
        ).createOrReplaceTempView(name)
    return config, [query(q)[0] for q in workload["queries"]]


@pytest.mark.parametrize("cell", [
    "tpch-sf2.75-resident-q6q1", "tpch-1m-join-q3", "tpch-1m-join-q3-mesh4",
    "tpcds-sf100-star-q7", "tpcds-sf100-report-q98"])
def test_a_cell_that_scans_no_file_plans_as_on_the_parent(cell):
    """The new rule matches ``ScanRelation`` alone: where a plan holds none,
    the tree the rule returns and the physical plan are the parent's."""
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        config_name = json.load(f)["config"]
    with open(os.path.join(BENCH, "configs", config_name + ".json")) as f:
        conf = dict(json.load(f)["session_conf"])
    conf.pop("spark.executor.instances", None)  # the plan's shape, one device
    with session_of(**conf) as s:
        _config, texts = _cell_frames(s, cell)
        for text in texts:
            df = s.sql(text)
            assert not file_scans(df._plan)
            now = CP.prune_columns(df._plan)
            planned = s.physical_plan(df).tree_string()
            with scan_rule_off():
                before = CP.prune_columns(df._plan)
                assert s.physical_plan(df).tree_string() == planned
            assert now.tree_string() == before.tree_string()
            assert scans(now) == scans(before)
