"""The planner's star-join ordering (``sql/join_order.py``): the benchmark's
join queries planned with the rule and without it — q98 probes ``date_dim``
before ``item``, q7 keeps ``customer_demographics`` then ``date_dim`` first,
TPC-H Q3 (a chain, not a star) plans the same tree at its own conf and at the
four-executor cell's — the estimated shares against the shares counted from
the generated tables, the same answers under both orders, the counter and
the explain line, and the runs the rule must leave alone: a dimension that
joins another dimension, an outer join, a dimension it cannot estimate, and
``SELECT *`` over a reordered run."""

import contextlib
import datetime
import importlib.util
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import join_order as JO
from spark_rapids_tpu.sql import plan as P
from spark_rapids_tpu.sql.physical.join import AdaptiveJoinExec, BaseJoinExec

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
MIB = 1 << 20

#: the TPC-DS generator's smallest cut: 49,152 fact rows
SMALL = {"scale_factor": 0.01, "share_of": 8}
#: the shares are compared with counts over the fact's rows, and the smallest
#: cut holds ~5 k tickets: its q7 date share reads 1.3 points off at seed 2
#: by sampling alone.  At 864 k rows every share is within 0.3 of a point
SHARES = {"scale_factor": 0.3, "share_of": 1}


def _module(*parts):
    spec = importlib.util.spec_from_file_location(
        "order_" + "_".join(parts).replace(".", "_"),
        os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


TPCH = _module("generators", "tpch.py")
TPCDS = _module("generators", "tpcds.py")


def text(q):
    with open(os.path.join(BENCH, "queries", q + ".sql")) as f:
        return f.read()


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@contextlib.contextmanager
def session_of(**conf):
    """A session of this test's own, and the one before put back."""
    from spark_rapids_tpu.sql.session import TpuSession
    before = TpuSession._active
    try:
        yield srt.session(**conf)
    finally:
        TpuSession._active = before


@contextlib.contextmanager
def rule_off():
    """Plans every run in the order it was given: the engine before the
    rule (the planner looks the rule up on every call)."""
    rule = JO.order_joins
    JO.order_joins = lambda plan: (plan, 0)
    try:
        yield
    finally:
        JO.order_joins = rule


@pytest.fixture(scope="module")
def sess():
    with session_of(**{
            "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * MIB}) as s:
        yield s


_BUILT = {}


def tpcds(scale, seed=1):
    key = (json.dumps(scale, sort_keys=True), seed)
    if key not in _BUILT:
        _BUILT[key] = TPCDS.build_tables(scale, seed, TPCDS.TABLES)
    return _BUILT[key]


def register(sess, tables, partitions=2):
    for name, t in tables.items():
        sess.create_dataframe(t, num_partitions=partitions
                              ).createOrReplaceTempView(name)


def prefix(node):
    """The table a subtree reads, by its columns' prefix (``ss``, ``d``)."""
    return {a.name.split("_")[0] for a in node.output}


def leaves(node):
    if not node.children:
        return prefix(node)
    return set().union(*(leaves(c) for c in node.children))


def probe_order(phys, fact="ss"):
    """The dimension of every join of a physical plan, the deepest first."""
    out = []

    def walk(node):
        for c in node.children:
            walk(c)
        if isinstance(node, (BaseJoinExec, AdaptiveJoinExec)):
            dims = [leaves(c) for c in node.children
                    if fact not in leaves(c)]
            out.append("+".join(sorted(dims[0])))
    walk(phys)
    return out


def shares(plan):
    """{dimension: the share the rule estimated} of a reordered plan."""
    out = {}

    def walk(node):
        if isinstance(node, P.Join) and node.kept_share is not None:
            out["+".join(sorted(prefix(node.right)))] = node.kept_share
        for c in node.children:
            walk(c)
    walk(plan)
    return out


def collect_both(df):
    """(answer with the rule, its joinsReordered, answer without it)."""
    with_rule = df.collect()
    reordered = df._session.last_query_metrics["joinsReordered"]
    with rule_off():
        without = df.collect()
        assert df._session.last_query_metrics["joinsReordered"] == 0
    return with_rule, reordered, without


# --- the benchmark's queries ---------------------------------------------------

def test_q98_probes_the_days_before_item(sess):
    register(sess, tpcds(SMALL))
    df = sess.sql(text("tpcds_q98"))
    assert probe_order(sess.physical_plan(df)) == ["d", "i"]
    with rule_off():
        assert probe_order(sess.physical_plan(df)) == ["i", "d"]


def test_q7_keeps_demographics_then_dates_first(sess):
    register(sess, tpcds(SMALL))
    df = sess.sql(text("tpcds_q7"))
    assert probe_order(sess.physical_plan(df)) == ["cd", "d", "p", "i"]
    with rule_off():
        assert probe_order(sess.physical_plan(df)) == ["cd", "d", "i", "p"]


@pytest.mark.parametrize("cell", ["tpch-1m-8tables", "tpch-1m-mesh4"])
def test_q3_is_a_chain_and_plans_the_same_tree(cell):
    """``lineitem`` joins ``orders``, not the run's root ``customer``: the
    rule leaves the run as it is, at either cell's conf."""
    conf = dict(config(cell)["session_conf"])
    conf.pop("spark.executor.instances", None)  # the plan's shape, one device
    tables = TPCH.build_tables({"scale_factor": 0.01}, 3,
                               ("lineitem", "orders", "customer"))
    with session_of(**conf) as s:
        register(s, tables, partitions=4)
        df = s.sql(text("tpch_q3"))
        now = s.physical_plan(df).tree_string()
        with rule_off():
            before = s.physical_plan(df).tree_string()
        assert now == before
        assert "keeps ~" not in now
        with_rule, reordered, without = collect_both(df)
        assert reordered == 0
        assert with_rule.equals(without)


@pytest.mark.parametrize("q,want", [
    ("tpcds_q98", {"d", "i"}), ("tpcds_q7", {"cd", "d", "p", "i"})])
def test_estimated_shares_match_the_counted_ones(sess, q, want):
    """The estimate against the share of the fact's joinable rows that each
    filtered dimension keeps, counted from the generated tables."""
    tables = tpcds(SHARES)
    register(sess, tables)
    got = shares(JO.order_joins(sess.sql(text(q))._plan)[0])
    assert set(got) == want
    sales = tables["store_sales"]
    lo = datetime.date(1999, 2, 22)
    filters = {
        "d": ("ss_sold_date_sk", "date_dim", "d_date_sk",
              (lambda d: (d.d_date >= lo)
               & (d.d_date <= lo + datetime.timedelta(days=30)))
              if q == "tpcds_q98" else (lambda d: d.d_year == 2000)),
        "i": ("ss_item_sk", "item", "i_item_sk",
              (lambda d: d.i_category.isin(["Sports", "Books", "Home"]))
              if q == "tpcds_q98" else (lambda d: d.i_item_sk == d.i_item_sk)),
        "cd": ("ss_cdemo_sk", "customer_demographics", "cd_demo_sk",
               lambda d: (d.cd_gender == "M") & (d.cd_marital_status == "S")
               & (d.cd_education_status == "College")),
        "p": ("ss_promo_sk", "promotion", "p_promo_sk",
              lambda d: (d.p_channel_email == "N")
              | (d.p_channel_event == "N"))}
    for dim, share in got.items():
        fk, name, key, passes = filters[dim]
        d = tables[name].to_pandas()
        f = sales.column(fk).to_pandas().dropna()
        counted = f.isin(d[key][passes(d)]).sum() / f.isin(d[key]).sum()
        assert abs(share - counted) < 0.01, (dim, share, counted)
    if q == "tpcds_q7":
        assert got["d"] > 10 * (366 / 73049)    # the fact's years, not all
        assert got["i"] == 1.0                  # no filter


@pytest.mark.parametrize("q,reordered", [("tpcds_q98", 1), ("tpcds_q7", 1)])
def test_both_orders_collect_the_same_answer(sess, q, reordered):
    register(sess, tpcds(SMALL))
    df = sess.sql(text(q))
    with_rule, counted, without = collect_both(df)
    assert counted == reordered
    assert with_rule.num_rows > 0
    assert with_rule.equals(without)


def test_explain_prints_each_dimensions_share(sess):
    register(sess, tpcds(SMALL))
    df = sess.sql(text("tpcds_q98"))
    report = sess.explain(df)
    notes = [line for line in report.splitlines() if "keeps ~" in line]
    assert len(notes) == 2
    assert "d_date_sk" in notes[-1] and "1.70%" in notes[-1]
    assert "i_item_sk" in notes[0]


# --- planted runs ---------------------------------------------------------------

def star_tables(n=4000):
    """A fact with three foreign keys; ``wide`` keeps half its rows by its
    filter, ``narrow`` a twentieth, ``leaf`` hangs off ``wide``."""
    rng = np.random.default_rng(7)
    keys = np.arange(100, dtype=np.int64)
    return {
        "fact": pa.table({
            "f_wide": pa.array(rng.integers(0, 100, n)),
            "f_narrow": pa.array(rng.integers(0, 100, n)),
            "f_v": pa.array(rng.normal(size=n))}),
        "wide": pa.table({"w_k": keys, "w_x": keys % 2,
                          "w_leaf": keys % 10}),
        "narrow": pa.table({"n_k": keys, "n_x": keys % 20}),
        "leaf": pa.table({"l_k": np.arange(10, dtype=np.int64),
                          "l_x": np.arange(10) % 5})}


@pytest.fixture(scope="module")
def star(sess):
    tables = star_tables()
    register(sess, tables)
    return tables


STAR = ("SELECT {cols} FROM fact, wide, narrow WHERE f_wide = w_k "
        "AND f_narrow = n_k AND w_x = 0 AND n_x = 3")


def test_a_star_in_the_text_order_is_reordered(sess, star):
    df = sess.sql(STAR.format(cols="sum(f_v) AS s, count(*) AS c"))
    assert probe_order(sess.physical_plan(df), "f") == ["n", "w"]
    got = shares(JO.order_joins(df._plan)[0])
    assert got == {"n": pytest.approx(0.05), "w": pytest.approx(0.5)}
    with_rule, reordered, without = collect_both(df)
    assert reordered == 1
    assert with_rule["c"].equals(without["c"])
    assert with_rule["s"][0].as_py() == pytest.approx(without["s"][0].as_py())


def test_select_star_keeps_the_from_lists_column_order(sess, star):
    df = sess.sql(STAR.format(cols="*"))
    with_rule, reordered, without = collect_both(df)
    assert reordered == 1
    names = [f.name for t in ("fact", "wide", "narrow")
             for f in star[t].schema]
    assert with_rule.column_names == names
    order = [("f_v", "ascending")]
    assert with_rule.sort_by(order).equals(without.sort_by(order))


def test_the_dataframe_api_is_reordered_too(sess, star):
    fact = sess.table("fact")
    wide = sess.table("wide").filter("w_x = 0")
    narrow = sess.table("narrow").filter("n_x = 3")
    df = fact.join(wide, fact["f_wide"] == wide["w_k"]).join(
        narrow, fact["f_narrow"] == narrow["n_k"])
    with_rule, reordered, without = collect_both(df)
    assert reordered == 1
    assert with_rule.column_names == without.column_names
    order = [("f_v", "ascending")]
    assert with_rule.sort_by(order).equals(without.sort_by(order))


def _left_alone(sess, sql):
    df = sess.sql(sql)
    now = sess.physical_plan(df).tree_string()
    with rule_off():
        assert sess.physical_plan(df).tree_string() == now
    with_rule, reordered, without = collect_both(df)
    assert reordered == 0
    order = [(with_rule.column_names[0], "ascending"),
             (with_rule.column_names[-1], "ascending")]
    assert with_rule.sort_by(order).equals(without.sort_by(order))
    return now


def test_a_dimension_joining_another_dimension_is_left_alone(sess, star):
    """``leaf`` joins ``wide``, not the fact: the run is no star (the shape
    of TPC-H Q3's ``customer -> orders -> lineitem``)."""
    _left_alone(sess, "SELECT f_v, l_x FROM fact, wide, leaf "
                      "WHERE f_wide = w_k AND w_leaf = l_k AND l_x = 1")


def test_an_outer_join_is_left_alone(sess, star):
    plan = _left_alone(
        sess, "SELECT f_v, n_x FROM fact JOIN wide ON f_wide = w_k "
              "LEFT JOIN (SELECT * FROM narrow WHERE n_x = 3) n "
              "ON f_narrow = n_k WHERE w_x = 0")
    assert "keeps ~" not in plan


def test_a_dimension_it_cannot_estimate_keeps_the_order(sess, star,
                                                       tmp_path):
    """``narrow`` read from a file: no host table to count, so the run
    keeps the order it was given."""
    path = str(tmp_path / "narrow.parquet")
    pq.write_table(star["narrow"], path)
    sess.read.parquet(path).createOrReplaceTempView("narrow_file")
    try:
        plan = _left_alone(
            sess, "SELECT f_v, n_x FROM fact, wide, narrow_file "
                  "WHERE f_wide = w_k AND f_narrow = n_k "
                  "AND w_x = 0 AND n_x = 3")
    finally:
        sess.create_dataframe(star["narrow"], num_partitions=2
                              ).createOrReplaceTempView("narrow")
    assert "keeps ~" not in plan


def test_the_estimates_are_memoised_and_cleared_with_the_kernel_cache(
        sess, star):
    from spark_rapids_tpu.sql.physical import kernel_cache
    df = sess.sql(STAR.format(cols="count(*) AS c"))
    JO.order_joins(df._plan)
    held = dict(JO._ESTIMATES)
    assert any(k[0] == "share" for k in held)
    assert any(k[0] == "range" for k in held)
    JO.order_joins(df._plan)
    assert JO._ESTIMATES == held          # a second plan reads no table
    kernel_cache.clear_cache()
    assert not JO._ESTIMATES
