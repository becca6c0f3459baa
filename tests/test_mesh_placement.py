"""Four executors on four chips of one host (``spark.executor.instances=4``):
partitions live on their own chip, stages run where their data lies,
exchanges ride the ICI all_to_all — because of where the partitions lie,
not because a shuffle mode says so.

Four of the eight virtual CPU devices stand in for the chips.  The oracle
is pandas, over seeded
tables of TPC-H Q3's shape (three-table shuffled join, group by three
keys, sort, limit) that carry string and nullable columns through every
exchange into the answer.  Placement is asserted, not assumed: partition
``p`` of a scan on device ``p % 4``, reduce partition ``t`` of every mesh
exchange on device ``t % 4``, no fallback, no cross-chip copy."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.parallel import placement
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.physical import kernel_cache as KC

CHIPS = 4
MAPS = 8
#: small shapes must still shuffle both join sides and ride the mesh (AQE
#: would rightly broadcast and coalesce them)
SMALL = {"spark.rapids.sql.autoBroadcastJoinThreshold": 1,
         "spark.sql.adaptive.coalescePartitions.minRows": 0}
LOCAL = {"spark.rapids.shuffle.mode": "MULTITHREADED"}

Q3_SHAPE = """
SELECT l.l_orderkey, o.o_orderdate, o.o_clerk,
       sum(l.l_extendedprice * (1 - l.l_discount)) AS revenue,
       count(l.l_tax) AS taxed, count(*) AS lines, max(c.c_name) AS who
FROM customer c, orders o, lineitem l
WHERE c.c_mktsegment = 'BUILDING' AND c.c_custkey = o.o_custkey
  AND l.l_orderkey = o.o_orderkey
  AND o.o_orderdate < date '1995-03-15' AND l.l_shipdate > date '1995-03-15'
GROUP BY l.l_orderkey, o.o_orderdate, o.o_clerk
ORDER BY revenue DESC, o.o_orderdate, l.l_orderkey
LIMIT 25
"""


def ici(targets: int) -> dict:
    return {"spark.executor.instances": CHIPS,
            "spark.sql.shuffle.partitions": targets, **SMALL}


@pytest.fixture(scope="module", autouse=True)
def four_chips(tmp_path_factory):
    """The executors' chips are the first four virtual devices, and jax's
    persistent compile cache is on for the module: a stage program then
    compiles once and the other three devices load it
    (``kernel_cache.share_executables``), which is what keeps
    the module's time and the XLA:CPU JIT's load down.  What the module
    changed of the process is put back (other modules of this worker run
    on one device, with no cache)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from spark_rapids_tpu.memory.device import DeviceManager
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    cc.reset_cache()
    yield tuple(jax.devices()[:CHIPS])
    DeviceManager.shutdown()
    KC.share_executables(())     # jax's own cache keys and floor again
    assert jax.config.jax_persistent_cache_min_compile_time_secs == floor
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    srt.session(**LOCAL, **{"spark.sql.shuffle.partitions": 8,
                            "spark.rapids.sql.autoBroadcastJoinThreshold":
                            10 << 20})


def _release_programs():
    """The XLA:CPU JIT crashes past a few hundred live programs
    (``conftest.release_compiled_caches``)."""
    from conftest import release_compiled_caches
    release_compiled_caches()


@pytest.fixture(scope="module")
def q3_case(four_chips):
    """The tables and pandas' answer, made once."""
    tables = q3_tables(seed=28)
    return tables, q3_pandas(tables)


def q3_tables(seed: int) -> dict:
    rng = np.random.default_rng(seed)
    nc, no = 300, 3000
    day0 = np.datetime64("1995-01-01")
    customer = pa.table({
        "c_custkey": np.arange(nc, dtype=np.int64),
        "c_mktsegment": rng.choice(["BUILDING", "MACHINERY", "AUTOMOBILE"],
                                   nc),
        "c_name": [None if i % 11 == 0 else f"Customer#{i:05d}"
                   for i in range(nc)]})
    orders = pa.table({
        "o_orderkey": np.arange(no, dtype=np.int64) * 4,
        "o_custkey": rng.integers(0, nc, no),
        "o_orderdate": pa.array(day0 + rng.integers(0, 150, no)),
        "o_clerk": [None if i % 13 == 0 else f"Clerk#{i % 37:03d}"
                    for i in range(no)]})
    lines = rng.integers(1, 8, no)
    key = np.repeat(np.asarray(orders["o_orderkey"]), lines)
    nl = len(key)
    tax = rng.random(nl)
    lineitem = pa.table({
        "l_orderkey": key,
        "l_extendedprice": rng.random(nl) * 1e5,
        "l_discount": rng.integers(0, 11, nl) / 100.0,
        "l_tax": pa.array([None if i % 5 == 0 else float(t)
                           for i, t in enumerate(tax)], type=pa.float64()),
        "l_shipdate": pa.array(day0 + rng.integers(30, 200, nl)),
        "l_comment": [f"line {i % 97} of {'x' * (i % 9)}"
                      for i in range(nl)]})
    return {"customer": customer, "orders": orders, "lineitem": lineitem}


def q3_pandas(tables: dict) -> pd.DataFrame:
    c, o, l = (tables[k].to_pandas() for k in ("customer", "orders",
                                               "lineitem"))
    cut = pd.Timestamp("1995-03-15")
    j = (c[c.c_mktsegment == "BUILDING"]
         .merge(o[pd.to_datetime(o.o_orderdate) < cut],
                left_on="c_custkey", right_on="o_custkey")
         .merge(l[pd.to_datetime(l.l_shipdate) > cut],
                left_on="o_orderkey", right_on="l_orderkey"))
    j["rev"] = j.l_extendedprice * (1 - j.l_discount)
    g = (j.groupby(["l_orderkey", "o_orderdate", "o_clerk"], dropna=False)
         .agg(revenue=("rev", "sum"), taxed=("l_tax", "count"),
              lines=("rev", "size"), who=("c_name", "max")).reset_index())
    g = g.sort_values(["revenue", "o_orderdate", "l_orderkey"],
                      ascending=[False, True, True]).head(25)
    return g.reset_index(drop=True)


def run_q3(conf: dict, tables: dict):
    sess = srt.session(**conf)
    for name, t in tables.items():
        sess.create_dataframe(t, num_partitions=MAPS) \
            .createOrReplaceTempView(name)
    return sess, sess.sql(Q3_SHAPE).collect().to_pandas()


def plan_nodes(plan):
    """Every exec of an executed plan, the join an adaptive join chose
    included."""
    stack, seen = [plan], set()
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        yield node
        stack.extend(node.children)
        chosen = getattr(node, "_chosen", None)
        if chosen is not None:
            stack.append(chosen)


def leaves_live_on(batch) -> set:
    import jax
    return {d for leaf in jax.tree_util.tree_leaves(batch.columns)
            if isinstance(leaf, jax.Array) for d in leaf.devices()}


def assert_placed(sess, chips) -> int:
    """Scan partition p on chip p % n, reduce partition t of every mesh
    exchange on chip t % n.  Returns the exchanges seen."""
    from spark_rapids_tpu.sql.physical.basic import (InMemoryScanExec,
                                                     _cached_upload)
    from spark_rapids_tpu.sql.physical.exchange import ShuffleExchangeExec
    n, scans, exchanges = len(chips), 0, 0
    for node in plan_nodes(sess._last_phys):
        if isinstance(node, InMemoryScanExec):
            for p, part in enumerate(node._parts):
                for b in _cached_upload(part, node.backend, sess._conf,
                                        chip=chips[p % n]):
                    assert leaves_live_on(b) == {chips[p % n]}, (p, b)
            scans += 1
        elif (isinstance(node, ShuffleExchangeExec)
              and node._materialized is not None
              and node.num_partitions() > 1):
            for t, part in enumerate(node._materialized):
                for b in part:
                    assert leaves_live_on(b) == {chips[t % n]}, (t, b)
            exchanges += 1
    assert scans
    return exchanges


def assert_same(got: pd.DataFrame, want: pd.DataFrame, rtol: float):
    assert len(got) == len(want)
    for col in want.columns:
        g, w = got[col], want[col]
        if w.dtype.kind == "f":
            assert np.allclose(g.astype(float), w, rtol=rtol, atol=0), col
        elif w.dtype.kind == "M" or g.dtype.kind == "M":
            assert list(pd.to_datetime(g)) == list(pd.to_datetime(w)), col
        else:
            assert [None if pd.isna(x) else x for x in g] \
                == [None if pd.isna(x) else x for x in w], col


def test_q3_shape_on_four_chips(four_chips, q3_case, targets=CHIPS):
    tables, want = q3_case
    before = dict(M.STATS)
    copies = placement.STATS["cross_chip_copies"]
    sess, got = run_q3(ici(targets), tables)
    m = sess.last_query_metrics
    assert KC.share_executables(four_chips) is True
    assert m["meshExchanges"] == 5, m
    assert m["meshFallbacks"] == 0, m
    assert m["meshExchangeBytes"] > 0
    assert M.STATS["fallbacks"] == before["fallbacks"]
    assert placement.STATS["cross_chip_copies"] == copies
    assert assert_placed(sess, four_chips) == 5
    record = M.RECENT_EXCHANGES[-1]
    assert len(record["program_outputs_live_on"]) == CHIPS
    assert record["sent_bytes"] > 0 and record["sent_rows"] > 0
    # keys, counts, strings and nulls exactly
    assert_same(got, want, rtol=1e-12)
    _release_programs()


def grouped(conf, table, keys, maps=MAPS, where=None):
    sess = srt.session(**conf)
    df = sess.create_dataframe(table, num_partitions=maps)
    if where is not None:
        df = df.filter(where(df))
    got = (df.groupBy(*keys)
           .agg(F.sum(df.v).alias("sv"), F.count(df.v).alias("c"))
           .orderBy(*keys).collect().to_pandas())
    return sess, got


def keyed_table(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return pa.table({
        "p": np.arange(n) * MAPS // n,          # the partition of the row
        "k": rng.integers(0, 60, n),
        "s": [f"x{'y' * (i % 13)}{i % 5}" for i in range(n)],
        "v": pa.array([None if i % 7 == 0 else float(x)
                       for i, x in enumerate(rng.random(n))],
                      type=pa.float64())})


def expected(table, keys, keep=None):
    pdf = table.to_pandas()
    if keep is not None:
        pdf = pdf[keep(pdf)]
    return (pdf.groupby(keys)       # SQL's sum of no value is NULL
            .agg(sv=("v", lambda v: v.sum(min_count=1)), c=("v", "count"))
            .reset_index())


@pytest.mark.parametrize("targets", [CHIPS, 2 * CHIPS])
def test_group_by_string_key_returns_the_keys(four_chips, targets):
    t = keyed_table()
    sess, got = grouped(ici(targets), t, ["s"])
    want = expected(t, ["s"])
    assert list(got["s"]) == list(want["s"]) and "" not in set(got["s"])
    assert np.array_equal(got["c"], want["c"])
    assert np.allclose(got["sv"], want["sv"], rtol=1e-12, atol=0)
    m = sess.last_query_metrics
    assert m["meshExchanges"] >= 1 and m["meshFallbacks"] == 0, m
    assert assert_placed(sess, four_chips) >= 1


@pytest.mark.parametrize("case,where,keep", [
    # chip 1's maps (partitions 1 and 5) produce nothing: an empty shard
    ("empty_shard", lambda df: (df.p % CHIPS) != 1,
     lambda pdf: (pdf.p % CHIPS) != 1),
    # one map output with no live row, its chip's other map has some
    ("empty_map", lambda df: df.p != 6, lambda pdf: pdf.p != 6),
])
def test_shards_and_maps_without_rows(four_chips, case, where, keep):
    t = keyed_table(seed=5)
    sess, got = grouped(ici(2 * CHIPS), t, ["k", "s"], where=where)
    want = expected(t, ["k", "s"], keep)
    assert list(got["k"]) == list(want["k"])
    assert list(got["s"]) == list(want["s"])
    assert np.array_equal(got["c"], want["c"])
    assert np.allclose(got["sv"], want["sv"], rtol=1e-12, atol=0,
                       equal_nan=True)
    m = sess.last_query_metrics
    assert m["meshExchanges"] >= 1 and m["meshFallbacks"] == 0, m
    assert_placed(sess, four_chips)


@pytest.mark.parametrize("targets", [CHIPS, 32])
def test_all_rows_to_one_target(four_chips, targets):
    t = keyed_table(seed=9)
    t = t.set_column(t.schema.get_field_index("k"), "k",
                     pa.array(np.full(t.num_rows, 17, dtype=np.int64)))
    before = M.STATS["mesh_exchanges"]
    sess = srt.session(**ici(targets))
    df = sess.create_dataframe(t, num_partitions=MAPS)
    got = df.repartition(targets, "k").collect()
    assert M.STATS["mesh_exchanges"] > before
    assert got.num_rows == t.num_rows
    assert sorted(zip(got["p"].to_pylist(), got["s"].to_pylist())) \
        == sorted(zip(t["p"].to_pylist(), t["s"].to_pylist()))
    from spark_rapids_tpu.sql.physical.exchange import ShuffleExchangeExec
    sizes = [[sum(b.num_rows_int for b in part)
              for part in node._materialized]
             for node in plan_nodes(sess._last_phys)
             if isinstance(node, ShuffleExchangeExec)
             and node._materialized is not None]
    assert sizes and all(sorted(s)[-1] == t.num_rows and sum(s) == t.num_rows
                         for s in sizes)      # one target holds every row
    assert sess.last_query_metrics["meshFallbacks"] == 0
    assert_placed(sess, four_chips)


def test_one_executor_or_too_few_chips_changes_nothing():
    """One executor (the default), or more executors than the host shows
    chips: nothing is spread, whatever the shuffle mode says."""
    import jax
    from spark_rapids_tpu.config import RapidsConf
    one = (jax.devices()[0],)
    for conf in (LOCAL, {"spark.rapids.shuffle.mode": "ICI"},
                 {"spark.executor.instances": 1},
                 {"spark.executor.instances": len(jax.devices()) + 1},
                 {"spark.executor.instances": CHIPS,
                  "spark.rapids.shuffle.topology.numSlices": 2}):
        c = RapidsConf().copy(conf)
        assert placement.chips(c) == one, conf
        assert placement.home_chip(3, c) is None, conf
    four = RapidsConf().copy({"spark.executor.instances": CHIPS})
    assert placement.chips(four) == tuple(jax.devices()[:CHIPS])


def test_sharing_executables_ends_with_the_layout(four_chips):
    """One executable for every chip is a setting of the layout, not of
    the process: the first call under one executor puts jax's own cache
    key function and its floor back."""
    import jax
    from jax._src import compiler
    from spark_rapids_tpu.config import RapidsConf
    four = RapidsConf().copy({"spark.executor.instances": CHIPS})
    placement.chips(RapidsConf())
    keyed = compiler._get_cache_key
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    assert placement.chips(four) == four_chips
    assert compiler._get_cache_key is not keyed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert placement.chips(RapidsConf()) == four_chips[:1]
    assert compiler._get_cache_key is keyed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == floor


def test_a_jax_without_the_key_function_compiles_per_chip(
        four_chips, monkeypatch):
    """Where this jax has no ``_get_cache_key`` to wrap, asking for one
    executable warns and changes nothing: every chip compiles its own."""
    import jax
    from jax._src import compiler
    KC.share_executables(())
    monkeypatch.setitem(KC._SHARED, "works", None)
    monkeypatch.delattr(compiler, "_get_cache_key")
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    with pytest.warns(UserWarning, match="every chip compiles its own"):
        assert KC.share_executables(four_chips) is False
    assert KC._SHARED["first"] is None
    assert jax.config.jax_persistent_cache_min_compile_time_secs == floor
    assert KC.share_executables(four_chips) is False    # asked once


def test_a_decline_of_the_mesh_plane_raises(four_chips, monkeypatch):
    """Between executors on their own chips there is no other plane: a
    decline is counted and fails the collect, it never falls to the wire
    plane in silence."""
    def declined(*a, **kw):
        raise M.MeshShuffleUnsupported("forced by the test")
    import spark_rapids_tpu.parallel.mesh as mesh_mod
    monkeypatch.setattr(mesh_mod, "mesh_shuffle_batches", declined)
    before = M.STATS["fallbacks"]
    sess = srt.session(**ici(CHIPS))
    df = sess.create_dataframe(keyed_table(), num_partitions=MAPS)
    with pytest.raises(RuntimeError, match="mesh plane declined"):
        df.groupBy("k").agg(F.sum(df.v).alias("sv")).collect()
    assert M.STATS["fallbacks"] == before + 1


def test_tpch_q3_and_q1_on_four_chips(four_chips):
    """The cell's query, and Q1 with its string group keys, over the
    repo's TPC-H tables at a small scale against the repo's pandas
    oracles (rows and keys exact, floats to 1e-10 and tighter)."""
    from spark_rapids_tpu.testing import scaletest as ST
    from spark_rapids_tpu.testing import tpch_queries as TQ
    sess = srt.session(**{"spark.executor.instances": CHIPS,
                          "spark.sql.shuffle.partitions": CHIPS,
                          "spark.rapids.sql.autoBroadcastJoinThreshold": -1})
    t = TQ.build_tables(2000, seed=23)
    TQ.register_views(sess, t, parts=CHIPS)
    copies = placement.STATS["cross_chip_copies"]
    got = sess.sql(TQ.Q3).collect().to_pandas()
    m = dict(sess.last_query_metrics)
    TQ.q3_oracle(got, TQ._pandas(t))
    assert m["meshExchanges"] >= 4 and m["meshFallbacks"] == 0, m
    assert m["meshExchangeBytes"] >= m["meshCrossChipBytes"] > 0, m
    assert assert_placed(sess, four_chips) >= 4
    _release_programs()
    ST._tpch_q1_sql(sess, t, F)         # asserts against pandas itself
    m = dict(sess.last_query_metrics)
    assert not m.get("meshFallbacks"), m
    assert placement.STATS["cross_chip_copies"] == copies
    _release_programs()


def test_one_executor_runs_the_parents_q3(four_chips):
    """With ``spark.executor.instances`` unset or 1 nothing of the
    placement is on the path: Q3's physical plan and the names of the
    programs it launches (a digest of each program's key, the same in
    every process) are those recorded with the key unset
    (``tests/data/q3_one_executor_programs.json``: first from the commit
    before the key existed, again at PR 33: the same plan, the probe
    programs named ``_probe_``, and one ``concat`` program a schema now
    that a table's partitions share their dictionaries; again at PR 34:
    the scans hand on the 2 + 4 + 4 columns Q3 reads, so the same 46
    programs run over fewer columns and 28 of them have new digests)."""
    import json
    import os
    from spark_rapids_tpu.testing import tpch_queries as TQ
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "q3_one_executor_programs.json")) as f:
        want = json.load(f)
    t = TQ.build_tables(2000, seed=23)
    for extra in ({}, {"spark.executor.instances": 1}):
        sess = srt.session(**{
            "spark.sql.shuffle.partitions": 4,
            "spark.rapids.sql.autoBroadcastJoinThreshold": -1, **extra})
        TQ.register_views(sess, t, parts=4)
        KC.clear_cache()
        got = sess.sql(TQ.Q3).collect()
        TQ.q3_oracle(got.to_pandas(), TQ._pandas(t))
        assert sess._last_phys.tree_string() == want["plan"]
        assert sorted(KC.dispatch_stats_by_key()) == want["programs"]
        assert "meshExchanges" not in sess.last_query_metrics
        _release_programs()
