"""Planned queries over the 8-device virtual mesh: ShuffleExchangeExec
routes through the compiled all_to_all data plane (parallel/mesh.py), the
engine-level analog of the reference's UCX device-direct shuffle
(RapidsShuffleClient.scala / GpuShuffleExchangeExecBase.scala:266-277).

Oracle: the same query on the default (local) shuffle plane + pandas."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.parallel import mesh as M
from spark_rapids_tpu.sql import functions as F

#: eight executors on the eight virtual devices: the layout, not a shuffle
#: mode, puts every exchange on the mesh plane (parallel/placement.py)
ICI_CONF = {"spark.executor.instances": 8,
            "spark.sql.shuffle.partitions": 8,
            # small test shapes must still exercise the mesh data plane
            # (AQE would rightly coalesce them to one partition)
            "spark.sql.adaptive.coalescePartitions.minRows": 0}


@pytest.fixture(scope="module", autouse=True)
def _one_executor_afterwards():
    """The next module's bare ``srt.session()`` must not inherit eight
    executors, nor the process a spread layout."""
    yield
    from spark_rapids_tpu.memory.device import DeviceManager
    from spark_rapids_tpu.sql.physical import kernel_cache
    from spark_rapids_tpu.sql.session import TpuSession
    TpuSession._active = None
    DeviceManager.shutdown()
    kernel_cache.share_executables(())


@pytest.fixture()
def ici_sess():
    return srt.session(**ICI_CONF)


def make_tables(rng, n=4000):
    left = pa.table({
        "k": rng.integers(0, 200, n),
        "v": rng.random(n),
        "s": [f"name{i % 101}" for i in range(n)],
    })
    right = pa.table({
        "k": pa.array(np.arange(150), type=pa.int64()),
        "w": pa.array(np.arange(150) * 10.0),
    })
    return left, right


def test_mesh_groupby_agg_matches_local(ici_sess, rng):
    left, _ = make_tables(rng)
    before = M.STATS["mesh_exchanges"]
    df = ici_sess.create_dataframe(left, num_partitions=8)
    got = (df.groupBy("k")
           .agg(F.sum(df.v).alias("sv"), F.count("*").alias("c"),
                F.max(df.v).alias("mx"))
           .orderBy("k").collect().to_pandas())
    assert M.STATS["mesh_exchanges"] > before, "exchange did not ride mesh"
    exp = (left.to_pandas().groupby("k")
           .agg(sv=("v", "sum"), c=("v", "size"), mx=("v", "max"))
           .reset_index())
    assert np.array_equal(got["k"], exp["k"])
    assert np.array_equal(got["c"], exp["c"])
    assert np.allclose(got["sv"], exp["sv"])
    assert np.allclose(got["mx"], exp["mx"])


def test_mesh_shuffled_join_matches_pandas(ici_sess, rng):
    left, right = make_tables(rng)
    before = M.STATS["mesh_exchanges"]
    # force a shuffled hash join (defeat broadcast with a tiny threshold)
    sess = srt.session(**ICI_CONF,
                       **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    l = sess.create_dataframe(left, num_partitions=8)
    r = sess.create_dataframe(right, num_partitions=4)
    got = (l.join(r, on="k", how="inner")
           .select(l.k, l.v, r.w)
           .orderBy("k", "v").collect().to_pandas())
    assert M.STATS["mesh_exchanges"] > before
    exp = (left.to_pandas().merge(right.to_pandas(), on="k", how="inner")
           .sort_values(["k", "v"]).reset_index(drop=True))
    assert len(got) == len(exp)
    assert np.array_equal(got["k"], exp["k"])
    assert np.allclose(got["v"], exp["v"])
    assert np.allclose(got["w"], exp["w"])


def test_mesh_sort_range_partitioned(ici_sess, rng):
    """orderBy over the mesh: RangePartitioning pids + all_to_all."""
    left, _ = make_tables(rng)
    before = M.STATS["mesh_exchanges"]
    df = ici_sess.create_dataframe(left, num_partitions=8)
    got = df.orderBy("k", "v").select(df.k, df.v).collect().to_pandas()
    exp = (left.to_pandas()[["k", "v"]]
           .sort_values(["k", "v"]).reset_index(drop=True))
    assert np.array_equal(got["k"], exp["k"])
    assert np.allclose(got["v"], exp["v"])
    # global sort may use range exchange or a single-partition merge —
    # only assert mesh usage when a multi-partition exchange happened
    assert M.STATS["mesh_exchanges"] >= before


def test_mesh_string_and_null_columns_roundtrip(ici_sess, rng):
    n = 1000
    ks = rng.integers(0, 40, n)
    vs = rng.random(n)
    vs_null = [None if i % 7 == 0 else float(v) for i, v in enumerate(vs)]
    t = pa.table({"k": ks, "v": pa.array(vs_null, type=pa.float64()),
                  "s": [f"x{'y' * (i % 13)}{i % 5}" for i in range(n)]})
    before = M.STATS["mesh_exchanges"]
    df = ici_sess.create_dataframe(t, num_partitions=8)
    got = (df.groupBy("s").agg(F.count(df.v).alias("c"),
                               F.sum(df.v).alias("sv"))
           .orderBy("s").collect().to_pandas())
    assert M.STATS["mesh_exchanges"] > before
    exp = (t.to_pandas().groupby("s")
           .agg(c=("v", "count"), sv=("v", "sum")).reset_index())
    assert list(got["s"]) == list(exp["s"])
    assert np.array_equal(got["c"], exp["c"])
    assert np.allclose(got["sv"], exp["sv"])


def test_mesh_repartition_preserves_rows(ici_sess, rng):
    n = 3000
    t = pa.table({"k": rng.integers(0, 1000, n), "v": rng.random(n)})
    before = M.STATS["mesh_exchanges"]
    df = ici_sess.create_dataframe(t, num_partitions=8)
    got = df.repartition(8, "k").collect()
    assert M.STATS["mesh_exchanges"] > before
    assert got.num_rows == n
    a = sorted(zip(got["k"].to_pylist(), got["v"].to_pylist()))
    b = sorted(zip(t["k"].to_pylist(), t["v"].to_pylist()))
    assert a == b


@pytest.fixture(scope="module")
def tpcds_rig():
    """TPC-DS tables + ICI session amortized across the star-join cases
    (same pattern as scaletest.run_suite's table cache)."""
    from spark_rapids_tpu.testing import scaletest as ST
    t = ST.build_tpcds_tables(6000)
    sess = srt.session(**ICI_CONF,
                       **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    return ST, t, sess


@pytest.mark.slow    # 16-57 s each on the CPU: the TPC-DS rig and its joins
@pytest.mark.parametrize("qname", ["tpcds_q3_star_join",
                                   "tpcds_q19_brand_rev",
                                   "tpcds_q42_cat_rev"])
def test_mesh_tpcds_star_joins(qname, tpcds_rig):
    """BASELINE milestone-3 analog: TPC-DS star-join query shapes executed
    over the 8-device mesh — every shuffle exchange rides the compiled
    all_to_all ICI plane, results checked against the rig's pandas oracle
    (reference target: TPC-DS join subset on 8 chips, BASELINE.md)."""
    ST, t, sess = tpcds_rig
    fn = dict(ST.QUERIES)[qname]
    before = M.STATS["mesh_exchanges"]
    fn(sess, t, F)  # oracle asserts inside
    assert M.STATS["mesh_exchanges"] > before, \
        "star join did not ride the mesh data plane"


def test_mesh_rollup(ici_sess, rng):
    """Grouping sets over the mesh: Expand feeds a mesh-exchanged
    aggregate; every level must match pandas."""
    left, _ = make_tables(rng)
    before = M.STATS["mesh_exchanges"]
    df = ici_sess.create_dataframe(left, num_partitions=8)
    got = (df.rollup("k")
           .agg(F.sum(df.v).alias("sv"), F.grouping_id().alias("gid"))
           .collect().to_pandas())
    assert M.STATS["mesh_exchanges"] > before
    pdf = left.to_pandas()
    l1 = pdf.groupby("k").agg(sv=("v", "sum")).reset_index()
    assert len(got) == len(l1) + 1
    g0 = got[got.gid == 0].sort_values("k").reset_index(drop=True)
    assert np.array_equal(g0["k"], l1["k"])
    assert np.allclose(g0["sv"], l1["sv"])
    assert np.isclose(float(got[got.gid == 1]["sv"].iloc[0]), pdf.v.sum())


def test_mesh_subquery_semi_join(ici_sess, rng):
    """EXISTS/IN rewrites produce semi/anti joins that ride the mesh."""
    left, right = make_tables(rng)
    sess = srt.session(**ICI_CONF,
                       **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    sess.create_dataframe(left, num_partitions=8) \
        .createOrReplaceTempView("mesh_l")
    sess.create_dataframe(right, num_partitions=4) \
        .createOrReplaceTempView("mesh_r")
    before = M.STATS["mesh_exchanges"]
    got = sess.sql(
        "SELECT k, count(*) AS c FROM mesh_l WHERE k IN "
        "(SELECT k FROM mesh_r WHERE w > 500) GROUP BY k ORDER BY k"
    ).collect().to_pandas()
    assert M.STATS["mesh_exchanges"] > before
    lp, rp = left.to_pandas(), right.to_pandas()
    keys = set(rp.k[rp.w > 500])
    exp = (lp[lp.k.isin(keys)].groupby("k").size()
           .sort_index().reset_index(name="c"))
    assert np.array_equal(got["k"], exp["k"])
    assert np.array_equal(got["c"], exp["c"])


def test_mesh_rides_when_partitions_exceed_devices(session):
    """nt=16 partitions on an 8-device mesh: rows route to their owner
    device over ICI, then split locally — the exchange must still ride
    the mesh plane with exact results."""
    from spark_rapids_tpu.parallel import mesh as MESH
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    sess = srt.session(**{
        "spark.executor.instances": 8,
        "spark.sql.shuffle.partitions": 16,
        "spark.sql.adaptive.enabled": False})
    try:
        rng = np.random.default_rng(0)
        n, G = 120_000, 3_000
        t = pa.table({"k": rng.integers(0, G, n), "v": rng.random(n)})
        df = sess.create_dataframe(t, num_partitions=8)
        before = MESH.STATS["mesh_exchanges"]
        got = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"))
               .collect().to_pandas().sort_values("k").reset_index(drop=True))
        assert MESH.STATS["mesh_exchanges"] > before, \
            "exchange did not ride the mesh plane at nt=16 on 8 devices"
        m = sess.last_query_metrics
        assert m.get("meshExchanges", 0) >= 1
        exp = (t.to_pandas().groupby("k").agg(s=("v", "sum"))
               .reset_index().sort_values("k").reset_index(drop=True))
        assert np.array_equal(got["k"].values, exp["k"].values)
        assert np.allclose(got["s"].values, exp["s"].values)
    finally:
        srt.session(**{"spark.executor.instances": 1,
                       "spark.sql.shuffle.partitions": 8,
                       "spark.sql.adaptive.enabled": True})
