"""AQE (runtime broadcast-vs-shuffle re-decision) + cost-based optimizer
(reference GpuOverrides.scala:4392-4452 AQE integration,
CostBasedOptimizer.scala:54)."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.physical.join import AdaptiveJoinExec
from spark_rapids_tpu.sql.planner import Planner


def _find(p, cls):
    if isinstance(p, cls):
        return p
    for c in p.children:
        f = _find(c, cls)
        if f is not None:
            return f
    return None


def _tables(rng, n=20000):
    left = pa.table({"k": rng.integers(0, 1000, n), "v": rng.random(n)})
    right = pa.table({"k": pa.array(np.arange(n) % 1000, type=pa.int64()),
                      "w": pa.array(rng.random(n))})
    return left, right


def test_aqe_switches_misestimated_join_to_broadcast(rng):
    """Static estimate (~320KB relation) refuses broadcast under a 50KB
    threshold, but the filtered build side is ~8 rows at runtime — AQE
    provably picks a different plan than the static planner."""
    left, right = _tables(rng)
    sess = srt.session(
        **{"spark.rapids.sql.autoBroadcastJoinThreshold": 50_000})
    l = sess.create_dataframe(left, num_partitions=4)
    r = sess.create_dataframe(right, num_partitions=4)
    rf = r.filter(r.k < 8).groupBy("k").agg(F.max(r.w).alias("w"))
    q = l.join(rf, on="k", how="inner").select(l.k, l.v, rf.w)

    phys = Planner(sess._conf).plan_for_collect(q._plan)
    aqe = _find(phys, AdaptiveJoinExec)
    assert aqe is not None and aqe.chosen_strategy is None
    out = phys.execute_all(sess._conf)
    assert aqe.chosen_strategy == "broadcast"
    exp = (left.to_pandas().merge(
        right.to_pandas().query("k < 8").groupby("k")
        .agg(w=("w", "max")).reset_index(), on="k"))
    assert sum(b.num_rows_int for b in out) == len(exp)


def test_aqe_keeps_shuffle_for_big_build(rng):
    left, right = _tables(rng)
    sess = srt.session(
        **{"spark.rapids.sql.autoBroadcastJoinThreshold": 50_000})
    l = sess.create_dataframe(left, num_partitions=4)
    r = sess.create_dataframe(right, num_partitions=4)
    q = l.join(r, on="k", how="inner").select(l.k, l.v, r.w)
    phys = Planner(sess._conf).plan_for_collect(q._plan)
    aqe = _find(phys, AdaptiveJoinExec)
    assert aqe is not None
    out = phys.execute_all(sess._conf)
    assert aqe.chosen_strategy == "shuffle"
    exp = left.to_pandas().merge(right.to_pandas(), on="k")
    assert sum(b.num_rows_int for b in out) == len(exp)


def test_aqe_disabled_plans_statically(rng):
    left, right = _tables(rng)
    sess = srt.session(**{
        "spark.sql.adaptive.enabled": False,
        "spark.rapids.sql.autoBroadcastJoinThreshold": 50_000})
    l = sess.create_dataframe(left, num_partitions=4)
    r = sess.create_dataframe(right, num_partitions=4)
    q = l.join(r, on="k", how="inner")
    phys = Planner(sess._conf).plan_for_collect(q._plan)
    assert _find(phys, AdaptiveJoinExec) is None


def test_aqe_result_equivalence(rng):
    """Same query, AQE on vs off — identical results."""
    left, right = _tables(rng, n=5000)
    res = {}
    for flag in (True, False):
        sess = srt.session(**{
            "spark.sql.adaptive.enabled": flag,
            "spark.rapids.sql.autoBroadcastJoinThreshold": 10_000})
        l = sess.create_dataframe(left, num_partitions=4)
        r = sess.create_dataframe(right, num_partitions=4)
        rf = r.filter(r.k < 50)
        got = (l.join(rf, on="k", how="left_semi")
               .orderBy("k", "v").collect().to_pandas())
        res[flag] = got
    assert np.array_equal(res[True]["k"], res[False]["k"])
    assert np.allclose(res[True]["v"], res[False]["v"])


def test_cost_optimizer_demotes_when_device_expensive():
    t = pa.table({"a": list(range(100)), "b": [float(i) for i in range(100)]})
    sess = srt.session(**{
        "spark.rapids.sql.optimizer.enabled": True,
        "spark.rapids.sql.optimizer.gpu.exec.default": 100.0})
    try:
        df = sess.create_dataframe(t)
        q = df.select((df.a + 1).alias("a1"))
        rep = sess.explain(q)
        assert "CpuProject" in rep and "cost-based optimizer" in rep
        out = q.collect().to_pylist()
        assert out[5]["a1"] == 6
    finally:
        srt.session(**{"spark.rapids.sql.optimizer.enabled": False,
                       "spark.rapids.sql.optimizer.gpu.exec.default": 0.0001})


def test_cost_optimizer_keeps_device_when_cheap():
    t = pa.table({"a": list(range(100))})
    sess = srt.session(**{"spark.rapids.sql.optimizer.enabled": True})
    try:
        df = sess.create_dataframe(t)
        rep = sess.explain(df.select((df.a + 1).alias("a1")))
        assert "TpuProject" in rep
    finally:
        srt.session(**{"spark.rapids.sql.optimizer.enabled": False})


def test_cost_optimizer_off_by_default():
    t = pa.table({"a": list(range(10))})
    sess = srt.session(**{
        "spark.rapids.sql.optimizer.gpu.exec.default": 100.0})
    try:
        df = sess.create_dataframe(t)
        rep = sess.explain(df.select((df.a + 1).alias("a1")))
        assert "TpuProject" in rep  # optimizer disabled -> no demotion
    finally:
        srt.session(**{"spark.rapids.sql.optimizer.gpu.exec.default": 0.0001})


def test_cost_optimizer_unknown_stats_keep_device(tmp_path):
    """File scans have no row statistics; unknown stats must not demote
    (0 >= 0 would flip every file-based query to the host)."""
    import pyarrow.parquet as pq
    p = str(tmp_path / "t.parquet")
    pq.write_table(pa.table({"a": list(range(50))}), p)
    sess = srt.session(**{"spark.rapids.sql.optimizer.enabled": True})
    try:
        df = sess.read.parquet(p)
        rep = sess.explain(df.select((df.a + 1).alias("a1")))
        assert "CpuProject" not in rep
    finally:
        srt.session(**{"spark.rapids.sql.optimizer.enabled": False})


def test_skew_split_at_exchange(rng):
    """AQE skew handling (GpuCustomShuffleReaderExec skewed-partition
    specs): a hot-key reduce partition is re-sliced into median-sized
    chunks at materialization, the shuffled hash join probes chunk by
    chunk, results still match pandas, and the OOM-retry path never
    fires."""
    from spark_rapids_tpu.memory import oom_guard
    from spark_rapids_tpu.sql.physical import exchange as EX

    n, n_keys = 120_000, 400
    # 50% of probe rows land on ONE key -> one reduce partition ~50x the
    # median
    hot = np.zeros(n // 2, dtype=np.int64)
    cold = rng.integers(1, n_keys, n - n // 2)
    keys = np.concatenate([hot, cold])
    rng.shuffle(keys)
    fact = pa.table({"k": pa.array(keys), "v": rng.random(n)})
    dim = pa.table({"k": pa.array(np.arange(n_keys, dtype=np.int64)),
                    "w": rng.random(n_keys)})
    sess = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
        "spark.sql.adaptive.skewJoin.skewedPartitionRowsThreshold": 2000,
    })
    try:
        f = sess.create_dataframe(fact, num_partitions=4)
        d = sess.create_dataframe(dim, num_partitions=2)
        splits0 = EX.STATS["skew_splits"]
        oom0 = oom_guard.STATS["oom_caught"]
        got = (f.join(d, on="k", how="inner")
               .groupBy("k").agg(F.sum(F.col("v")).alias("sv"),
                                 F.count("*").alias("c"))
               .orderBy("k").collect().to_pandas())
        assert EX.STATS["skew_splits"] > splits0, "skew split did not fire"
        assert EX.STATS["skew_chunks"] > 0
        assert oom_guard.STATS["oom_caught"] == oom0
        m = fact.to_pandas().merge(dim.to_pandas(), on="k")
        exp = (m.groupby("k").agg(sv=("v", "sum"), c=("v", "size"))
               .sort_index().reset_index())
        assert np.array_equal(got["k"], exp["k"])
        assert np.array_equal(got["c"], exp["c"])
        assert np.allclose(got["sv"], exp["sv"])
    finally:
        sess.conf.set(
            "spark.sql.adaptive.skewJoin.skewedPartitionRowsThreshold",
            1 << 17)
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      10 * 1024 * 1024)


def test_skew_split_kill_switch(rng):
    from spark_rapids_tpu.sql.physical import exchange as EX
    n = 60_000
    keys = np.concatenate([np.zeros(n // 2, dtype=np.int64),
                           rng.integers(1, 200, n - n // 2)])
    fact = pa.table({"k": pa.array(keys), "v": rng.random(n)})
    dim = pa.table({"k": pa.array(np.arange(200, dtype=np.int64)),
                    "w": rng.random(200)})
    sess = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
        "spark.sql.adaptive.skewJoin.enabled": False,
        "spark.sql.adaptive.skewJoin.skewedPartitionRowsThreshold": 2000,
    })
    try:
        f = sess.create_dataframe(fact, num_partitions=4)
        d = sess.create_dataframe(dim, num_partitions=2)
        splits0 = EX.STATS["skew_splits"]
        n_got = f.join(d, on="k", how="inner").count()
        assert EX.STATS["skew_splits"] == splits0
        assert n_got == n
    finally:
        sess.conf.set("spark.sql.adaptive.skewJoin.enabled", True)
        sess.conf.set(
            "spark.sql.adaptive.skewJoin.skewedPartitionRowsThreshold",
            1 << 17)
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      10 * 1024 * 1024)
