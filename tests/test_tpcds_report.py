"""TPC-DS q98 on the store channel (ISSUE 35) on the CPU at small scale: the
official text (``benchmarks/queries/tpcds_q98.sql``) through ``sess.sql``
against the benchmark's own pandas reference over the benchmark's own
generator, with what the generated tables rarely hold planted into them: a
one-row group whose only price is NULL (a NULL revenue and ratio), a class
whose revenue is 0 (every ratio of it NULL), an item without a class (a NULL
key: first in the order, a window partition of its own), groups that tie on
the first four ORDER BY keys (a NULL ratio before a number, the smaller
ratio first) - and every row of the answer returned.  Then what executed: a
window, a sort above a range-partitioning exchange, nothing off the TPU
backend, and the counters and spans the window and the sort bring."""

import importlib.util
import json
import os
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.observability import tracer
from spark_rapids_tpu.parallel.partitioning import RangePartitioning
from spark_rapids_tpu.sql.physical.exchange import ShuffleExchangeExec
from spark_rapids_tpu.sql.physical.sortlimit import SortExec
from spark_rapids_tpu.sql.physical.window import WindowExec

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import compare as C  # noqa: E402  (benchmarks/compare.py)

Q = "tpcds_q98"
TABLES = ("store_sales", "item", "date_dim")
SCALE = {"scale_factor": 0.01, "share_of": 8}       # 49,152 fact rows
#: 1999-02-22 + 10 days as d_date_sk (2415022 is 1900-01-02)
IN_WINDOW = 2415022 + (np.datetime64("1999-03-04")
                       - np.datetime64("1900-01-02")).astype(int)


def _module(*parts):
    spec = importlib.util.spec_from_file_location(
        "report_" + "_".join(parts).replace(".", "_"),
        os.path.join(BENCH, *parts))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


GEN = _module("generators", "tpcds.py")
REFERENCE = _module("reference", Q + ".py").reference
with open(os.path.join(BENCH, "queries", Q + ".sql")) as _f:
    SQL = _f.read()
with open(os.path.join(BENCH, "queries", Q + ".json")) as _f:
    SPEC = json.load(_f)


def planted(seed):
    """The generated tables with the cases q98's semantics turn on planted
    into them; (tables, what was planted)."""
    tables = GEN.build_tables(SCALE, seed, TABLES)
    item = tables["item"].to_pandas().astype(
        {"i_category": object, "i_class": object})
    sales = tables["store_sales"].to_pandas()
    wanted = item[item.i_category.isin(["Sports", "Books", "Home"])]
    # i_item_id is shared by the revisions of an item: one revision each
    picked = wanted.drop_duplicates("i_item_id").i_item_sk.to_numpy()[:6]
    revisions = item[item.i_item_id.isin(
        item[item.i_item_sk.isin(picked)].i_item_id)].i_item_sk
    null_price, zero_a, zero_b, classless, tie_a, tie_b = picked
    # a class of its own whose two groups sell for nothing
    item.loc[item.i_item_sk.isin([zero_a, zero_b]), "i_class"] = "zeroes"
    # an item without a class
    item.loc[item.i_item_sk == classless, "i_class"] = None
    # three more revisions of tie_a's item that differ in price alone: four
    # groups that tie on category, class, id and description
    twins = item[item.i_item_sk == tie_a]
    top = int(item.i_item_sk.max())
    more = pd.concat([twins] * 3, ignore_index=True)
    more["i_item_sk"] = np.arange(top + 1, top + 4, dtype=np.int32)
    more["i_current_price"] = twins.i_current_price.iloc[0] + [1.0, 2.0, 3.0]
    item = pd.concat([item, more], ignore_index=True)
    # fact rows of the window for each planted item; the generated rows of
    # these items leave the window so that the planted ones are alone
    sales.loc[sales.ss_item_sk.isin(revisions), "ss_sold_date_sk"] = 1
    rows = [(null_price, None), (zero_a, 0.0), (zero_a, 0.0), (zero_b, 0.0),
            (classless, 12.5), (tie_a, 40.0), (top + 1, None),
            (top + 2, 10.0), (top + 2, 5.0), (top + 3, 15.0),
            (tie_b, 7.25)]
    extra = sales.iloc[:len(rows)].copy()
    extra["ss_sold_date_sk"] = IN_WINDOW
    extra["ss_item_sk"] = [r[0] for r in rows]
    extra["ss_ext_sales_price"] = [r[1] for r in rows]
    sales = pd.concat([sales, extra], ignore_index=True)
    out = {"store_sales": pa.Table.from_pandas(
               sales, schema=tables["store_sales"].schema,
               preserve_index=False),
           "item": pa.Table.from_pandas(item, preserve_index=False),
           "date_dim": tables["date_dim"]}
    ids = item.set_index("i_item_sk").i_item_id
    return out, {"null_price": ids[null_price], "classless": ids[classless],
                 "tie": ids[tie_a]}


@pytest.fixture(scope="module")
def sess():
    """A session of its own: a bare ``srt.session()`` hands back whatever
    the module before left active in this worker."""
    return srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * 1024 * 1024})


def register(sess, tables, partitions=2):
    for name, table in tables.items():
        sess.create_dataframe(
            table, num_partitions=partitions).createOrReplaceTempView(name)


def nodes(plan, kind):
    found = [plan] if isinstance(plan, kind) else []
    chosen = getattr(plan, "_chosen", None)
    for child in ([chosen] if chosen is not None else plan.children):
        found.extend(nodes(child, kind))
    return found


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_q98_agrees_with_the_reference(sess, seed):
    tables, what = planted(seed)
    register(sess, tables)
    got = sess.sql(SQL).collect().to_pandas(date_as_object=False)
    want = REFERENCE(C.tables_for_reference(tables, SPEC["tables"]))
    numbers = C.compare(got, want, SPEC)
    assert C.within(numbers, SPEC["limits"]), numbers
    assert list(got.columns) == list(want.columns)
    # every row returned: no LIMIT, and more rows than any cell fetched
    assert len(got) == len(want) > 100
    # the one-row group whose price is NULL: NULL revenue, NULL ratio
    lone = got[got.i_item_id == what["null_price"]]
    assert len(lone) == 1 and lone.itemrevenue.isna().all() \
        and lone.revenueratio.isna().all()
    # a class that sold for nothing: revenue 0, every ratio NULL
    zeroes = got[got.i_class == "zeroes"]
    assert len(zeroes) == 2 and (zeroes.itemrevenue == 0).all() \
        and zeroes.revenueratio.isna().all()
    # the item without a class is a partition of its own, first in its
    # category (NULLs first)
    classless = got[got.i_item_id == what["classless"]]
    assert len(classless) == 1 and classless.i_class.isna().all()
    assert classless.revenueratio.iloc[0] == pytest.approx(100.0)
    category = got[got.i_category == classless.i_category.iloc[0]]
    assert category.index[0] == classless.index[0]
    # four groups tie on the first four keys: the NULL ratio first, then
    # by the ratio (revenues 15, 15, 40 of a class: the tie of the two 15s
    # is broken by nothing the query names, so only their ratios are told)
    tie = got[got.i_item_id == what["tie"]]
    assert len(tie) == 4 and tie.index.max() - tie.index.min() == 3
    assert np.isnan(tie.revenueratio.iloc[0])
    assert list(tie.itemrevenue.iloc[1:]) == [15.0, 15.0, 40.0]
    assert tie.revenueratio.iloc[1:].is_monotonic_increasing
    # the whole answer is in the stated order
    order = ["i_category", "i_class", "i_item_id", "i_item_desc",
             "revenueratio"]
    again = got.sort_values(order, kind="stable", na_position="first")
    assert (again.index == got.index).all()


@pytest.mark.parametrize("coalesce_rows", (1 << 16, 0),
                         ids=("coalesced", "ranged"))
def test_what_executed(coalesce_rows):
    """The plan holds a window, a global sort and a range-partitioning
    exchange below it, nothing of it off the TPU backend.  At the default
    the small answer goes to one partition unsampled (AQE coalescing, as
    at the cell's own 31 k rows); with coalescing off the exchange samples
    its bounds and the answer is the same."""
    tables, _ = planted(4)
    mine = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * 1024 * 1024,
        "spark.sql.adaptive.coalescePartitions.minRows": coalesce_rows})
    register(mine, tables)
    df = mine.sql(SQL)
    report = mine.explain(df, all_ops=False)
    assert "cannot run on TPU" not in report, report
    got = df.collect().to_pandas(date_as_object=False)
    want = REFERENCE(C.tables_for_reference(tables, SPEC["tables"]))
    numbers = C.compare(got, want, SPEC)
    assert C.within(numbers, SPEC["limits"]), numbers
    plan = mine._last_phys
    assert len(nodes(plan, WindowExec)) == 1
    sorts = nodes(plan, SortExec)
    assert len(sorts) == 1 and sorts[0].is_global and len(
        sorts[0].orders) == 5
    ranged = [e for e in nodes(plan, ShuffleExchangeExec)
              if isinstance(e.partitioning, RangePartitioning)]
    assert len(ranged) == 1 and sorts[0].children[0] is ranged[0]
    m = dict(mine.last_query_metrics)
    groups = len(want)
    assert m["aggGroupRows"] == groups
    assert m["windowRows"] == groups and m["sortRows"] == groups
    assert m["windowPartitions"] == want.i_class.nunique(dropna=False)
    if coalesce_rows:
        assert m["rangeBoundSamples"] == 0
    else:
        assert 0 < m["rangeBoundSamples"] <= groups


def test_spans_of_the_window_and_the_sort():
    """``srt:window:compute`` once a window batch, ``srt:sort:compute`` once
    a sort launch, ``srt:sort:range_bounds`` where bounds are sampled."""
    tables, _ = planted(4)
    mine = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 10 * 1024 * 1024,
        "spark.sql.adaptive.coalescePartitions.minRows": 0,
        "spark.rapids.tpu.trace.sink": "memory"})
    register(mine, tables)
    mine.sql(SQL).collect()
    names = [f"{e['cat']}:{e['name']}" for e in tracer.get_tracer().snapshot()
             if e.get("cat") in ("sort", "window")]
    assert names.count("sort:range_bounds") == 1
    assert names.count("window:compute") >= 1
    assert names.count("sort:compute") >= 1
