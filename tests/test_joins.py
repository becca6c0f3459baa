"""Join tests — every join type on TPU vs the host engine, plus pandas
merge as an independent oracle (the reference's integration suite joins the
same frames on CPU Spark)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F

from test_dataframe import assert_tpu_and_cpu_equal


@pytest.fixture()
def sess():
    return srt.session()


def _left_table():
    return pa.table({
        "k": pa.array([1, 2, 2, 3, None, 5], type=pa.int64()),
        "lv": pa.array([10, 20, 21, 30, 40, 50], type=pa.int64()),
    })


def _right_table():
    return pa.table({
        "k": pa.array([2, 2, 3, 4, None], type=pa.int64()),
        "rv": pa.array([200, 201, 300, 400, 500], type=pa.int64()),
    })


def _none_key(rows):
    return sorted(rows, key=lambda t: tuple((v is None, v) for v in t))


def _pandas_oracle(how):
    """SQL-correct oracle (pandas merge matches NaN keys, SQL does not)."""
    l = _left_table().to_pandas()
    r = _right_table().to_pandas()
    ln, rn = l[l.k.notna()], r[r.k.notna()]
    m = ln.merge(rn, on="k", how="inner")
    rows = [(int(k), int(lv), int(rv))
            for k, lv, rv in m[["k", "lv", "rv"]].itertuples(index=False)]
    if how in ("left", "full"):
        matched = set(rn.k.dropna())
        for k, lv in l[["k", "lv"]].itertuples(index=False):
            if pd.isna(k) or k not in matched:
                rows.append((None if pd.isna(k) else int(k), int(lv), None))
    if how in ("right", "full"):
        matched = set(ln.k.dropna())
        for k, rv in r[["k", "rv"]].itertuples(index=False):
            if pd.isna(k) or k not in matched:
                rows.append((None if pd.isna(k) else int(k), None, int(rv)))
    return _none_key(rows)


@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
@pytest.mark.parametrize("nparts", [1, 3])
def test_equi_join_vs_pandas(sess, how, nparts):
    l = sess.create_dataframe(_left_table(), num_partitions=nparts)
    r = sess.create_dataframe(_right_table(), num_partitions=nparts)
    out = assert_tpu_and_cpu_equal(l.join(r, "k", how), sort_by=["k", "lv", "rv"])
    got = _none_key([
        tuple(None if v is None else int(v) for v in (row["k"], row["lv"],
                                                      row["rv"]))
        for row in out.to_pylist()])
    assert got == _pandas_oracle(how)


@pytest.mark.parametrize("how", ["left_semi", "left_anti"])
def test_semi_anti_join(sess, how):
    l = sess.create_dataframe(_left_table())
    r = sess.create_dataframe(_right_table())
    out = assert_tpu_and_cpu_equal(l.join(r, "k", how), sort_by=["lv"])
    lvs = sorted(row["lv"] for row in out.to_pylist())
    if how == "left_semi":
        assert lvs == [20, 21, 30]  # k in {2, 3}; nulls never match
    else:
        assert lvs == [10, 40, 50]  # k=1, k=None, k=5


def test_cross_join(sess):
    l = sess.create_dataframe(pa.table({"a": [1, 2, 3]}))
    r = sess.create_dataframe(pa.table({"b": [10, 20]}))
    out = assert_tpu_and_cpu_equal(l.crossJoin(r), sort_by=["a", "b"])
    assert len(out) == 6


def test_join_with_condition(sess):
    l = sess.create_dataframe(pa.table({
        "k": [1, 1, 2, 2], "x": [1, 5, 1, 5]}))
    r = sess.create_dataframe(pa.table({
        "k2": [1, 2], "y": [3, 3]}))
    cond = (F.col("k") == F.col("k2")) & (F.col("x") < F.col("y"))
    out = assert_tpu_and_cpu_equal(l.join(r, cond, "inner"),
                                   sort_by=["k", "x"])
    rows = [(row["k"], row["x"]) for row in out.to_pylist()]
    assert sorted(rows) == [(1, 1), (2, 1)]


def test_left_join_with_condition(sess):
    l = sess.create_dataframe(pa.table({"k": [1, 2, 3], "x": [0, 9, 0]}))
    r = sess.create_dataframe(pa.table({"k2": [1, 2, 3], "y": [5, 5, 5]}))
    cond = (F.col("k") == F.col("k2")) & (F.col("x") < F.col("y"))
    out = assert_tpu_and_cpu_equal(l.join(r, cond, "left"),
                                   sort_by=["k", "x"])
    rows = sorted((row["k"], row["y"]) for row in out.to_pylist())
    # k=2 fails the residual (9 < 5 false) -> null right side
    assert rows == [(1, 5), (2, None), (3, 5)]


def test_string_key_join(sess):
    l = sess.create_dataframe(pa.table({
        "name": ["alice", "bob", "carol", None],
        "v": [1, 2, 3, 4]}))
    r = sess.create_dataframe(pa.table({
        "name": ["bob", "carol", "dave", None],
        "w": [20, 30, 40, 50]}))
    out = assert_tpu_and_cpu_equal(l.join(r, "name", "inner"),
                                   sort_by=["name"])
    rows = sorted((row["name"], row["v"], row["w"])
                  for row in out.to_pylist())
    assert rows == [("bob", 2, 20), ("carol", 3, 30)]


def test_many_to_many_join(sess):
    rng = np.random.default_rng(7)
    lk = rng.integers(0, 20, 300)
    rk = rng.integers(0, 20, 200)
    l = sess.create_dataframe(pa.table({
        "k": lk, "lv": np.arange(300)}), num_partitions=4)
    r = sess.create_dataframe(pa.table({
        "k": rk, "rv": np.arange(200)}), num_partitions=2)
    out = assert_tpu_and_cpu_equal(l.join(r, "k", "inner"),
                                   sort_by=["k", "lv", "rv"])
    expected = pd.DataFrame({"k": lk, "lv": np.arange(300)}).merge(
        pd.DataFrame({"k": rk, "rv": np.arange(200)}), on="k")
    assert len(out) == len(expected)
    got = sorted(map(tuple, out.to_pydict().values().__iter__().__next__()
                 .__class__ and [
        (row["k"], row["lv"], row["rv"]) for row in out.to_pylist()]))
    exp = sorted(map(tuple, expected[["k", "lv", "rv"]].itertuples(
        index=False)))
    assert got == exp


def test_broadcast_join_path(sess):
    """Small build side + partitioned probe -> broadcast hash join."""
    l = sess.create_dataframe(pa.table({
        "k": np.arange(100) % 10, "lv": np.arange(100)}), num_partitions=4)
    r = sess.create_dataframe(pa.table({
        "k": np.arange(5), "rv": np.arange(5) * 100}))
    df = l.join(r, "k", "inner")
    from spark_rapids_tpu.sql.planner import Planner
    plan = Planner(sess._conf).plan(df._plan).tree_string()
    assert "BroadcastHashJoin" in plan
    out = assert_tpu_and_cpu_equal(df, sort_by=["k", "lv"])
    assert len(out) == 50


def test_join_then_aggregate(sess):
    """TPC-H-style join + groupby pipeline."""
    l = sess.create_dataframe(pa.table({
        "k": [1, 1, 2, 2, 3], "v": [1.0, 2.0, 3.0, 4.0, 5.0]}),
        num_partitions=2)
    r = sess.create_dataframe(pa.table({
        "k": [1, 2, 3], "grp": ["a", "b", "a"]}))
    df = (l.join(r, "k", "inner")
          .groupBy("grp").agg(F.sum("v").alias("s")))
    out = assert_tpu_and_cpu_equal(df, sort_by=["grp"])
    rows = {row["grp"]: row["s"] for row in out.to_pylist()}
    assert rows == {"a": 8.0, "b": 7.0}


def test_outer_nested_loop_empty_build(sess):
    """Left no-key join against an empty build side must keep every probe
    row (regression: out_cap was sized without unmatched slack)."""
    l = sess.create_dataframe(pa.table({"a": list(range(20))}))
    r = sess.create_dataframe(pa.table({"b": pa.array([], type=pa.int64())}))
    out = assert_tpu_and_cpu_equal(l.join(r, None, "left"), sort_by=["a"])
    assert len(out) == 20
    assert all(row["b"] is None for row in out.to_pylist())


def test_right_join_column_order(sess):
    """USING-column right join keeps pyspark's column order."""
    l = sess.create_dataframe(pa.table({"k": [1, 2], "lv": [10, 20]}))
    r = sess.create_dataframe(pa.table({"k": [2, 3], "rv": [200, 300]}))
    out = assert_tpu_and_cpu_equal(l.join(r, "k", "right"), sort_by=["k"])
    assert out.column_names == ["k", "lv", "rv"]
    rows = _none_key([(row["k"], row["lv"], row["rv"])
                      for row in out.to_pylist()])
    assert rows == [(2, 20, 200), (3, None, 300)]


def test_when_otherwise_string_literals(sess):
    """F.when value-position strings are literals, not column names."""
    df = sess.create_dataframe(pa.table({"a": [5, 15]}))
    out = df.select(F.when(F.col("a") > 10, "big")
                    .otherwise("small").alias("sz")).collect()
    assert out.column("sz").to_pylist() == ["small", "big"]


def test_full_join_nulls_both_sides(sess):
    l = sess.create_dataframe(pa.table({
        "k": pa.array([None, None, 1], type=pa.int64()),
        "lv": [1, 2, 3]}))
    r = sess.create_dataframe(pa.table({
        "k": pa.array([None, 2], type=pa.int64()),
        "rv": [10, 20]}))
    out = assert_tpu_and_cpu_equal(l.join(r, "k", "full"),
                                   sort_by=["lv", "rv"])
    # nulls never match: 3 unmatched left + 2 unmatched right + 0 matches
    assert len(out) == 5


# ---------------------------------------------------------------------------
# bloom-filter join runtime filters (GpuBloomFilterMightContain analog)
# ---------------------------------------------------------------------------

def _star_shapes(rng, n_fact=300_000, n_dim=400, key_space=80_000):
    fact = pa.table({"fk": rng.integers(0, key_space, n_fact),
                     "x": rng.random(n_fact)})
    pks = rng.choice(key_space, size=n_dim, replace=False)
    dim = pa.table({"pk": pks.astype(np.int64),
                    "name": [f"d{i}" for i in range(n_dim)]})
    return fact, dim


def test_bloom_star_join_reduces_probe_rows():
    """TPC-DS-shaped star join: a selective dimension must shrink the
    fact-side shuffle via the map-side bloom filter, with results exactly
    matching pandas."""
    from spark_rapids_tpu.ops import bloom as B
    rng = np.random.default_rng(11)
    fact, dim = _star_shapes(rng)
    sess = srt.session(**{"spark.rapids.sql.autoBroadcastJoinThreshold": -1})
    f = sess.create_dataframe(fact, num_partitions=4)
    d = sess.create_dataframe(dim, num_partitions=2)
    built0 = B.STATS["blooms_built"]
    in0, kept0 = B.STATS["probe_rows_in"], B.STATS["probe_rows_kept"]
    got = f.join(d, f.fk == d.pk, "inner").collect().to_pandas()
    exp = fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                 right_on="pk", how="inner")
    assert len(got) == len(exp)
    assert abs(got["x"].sum() - exp["x"].sum()) < 1e-6
    assert B.STATS["blooms_built"] > built0
    rows_in = B.STATS["probe_rows_in"] - in0
    rows_kept = B.STATS["probe_rows_kept"] - kept0
    assert rows_in >= 300_000
    assert rows_kept < rows_in * 0.1, \
        f"bloom kept {rows_kept}/{rows_in} — no real reduction"


def test_bloom_left_semi_correct():
    from spark_rapids_tpu.ops import bloom as B
    rng = np.random.default_rng(12)
    fact, dim = _star_shapes(rng, n_fact=100_000, n_dim=200)
    sess = srt.session(**{"spark.rapids.sql.autoBroadcastJoinThreshold": -1})
    f = sess.create_dataframe(fact, num_partitions=3)
    d = sess.create_dataframe(dim, num_partitions=2)
    built0 = B.STATS["blooms_built"]
    got = f.join(d, f.fk == d.pk, "left_semi").collect().to_pandas()
    exp = fact.to_pandas()[fact.to_pandas().fk.isin(dim.to_pandas().pk)]
    assert len(got) == len(exp)
    assert abs(got["x"].sum() - exp["x"].sum()) < 1e-6
    assert B.STATS["blooms_built"] > built0


def test_bloom_not_used_for_outer_joins():
    """Left outer joins must emit unmatched probe rows — exactly the rows
    the bloom filter would drop; it must not engage."""
    from spark_rapids_tpu.ops import bloom as B
    rng = np.random.default_rng(13)
    fact, dim = _star_shapes(rng, n_fact=50_000, n_dim=100)
    sess = srt.session(**{"spark.rapids.sql.autoBroadcastJoinThreshold": -1})
    f = sess.create_dataframe(fact, num_partitions=3)
    d = sess.create_dataframe(dim, num_partitions=2)
    built0 = B.STATS["blooms_built"]
    got = f.join(d, f.fk == d.pk, "left").collect().to_pandas()
    assert B.STATS["blooms_built"] == built0
    exp = fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                 right_on="pk", how="left")
    assert len(got) == len(exp)


def test_bloom_kill_switch():
    from spark_rapids_tpu.ops import bloom as B
    rng = np.random.default_rng(14)
    fact, dim = _star_shapes(rng, n_fact=50_000, n_dim=100)
    sess = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.sql.join.bloomFilter.enabled": False})
    f = sess.create_dataframe(fact, num_partitions=3)
    d = sess.create_dataframe(dim, num_partitions=2)
    built0 = B.STATS["blooms_built"]
    got = f.join(d, f.fk == d.pk, "inner").collect()
    assert B.STATS["blooms_built"] == built0
    exp = fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                 right_on="pk", how="inner")
    assert len(got) == len(exp)


def test_bloom_not_used_multi_slice():
    """In a multi-slice topology the build exchange materializes only the
    slice-LOCAL reduce partitions, so a bloom built from it would cover a
    subset of build rows and its map-side probe filter would drop rows
    whose matches live in peer-owned partitions (false negatives — the
    one thing a bloom join must never do).  The bloom must not engage
    (advisor r3 high finding)."""
    from spark_rapids_tpu.ops import bloom as B
    rng = np.random.default_rng(15)
    fact, dim = _star_shapes(rng, n_fact=50_000, n_dim=100)
    sess = srt.session(**{
        "spark.rapids.sql.autoBroadcastJoinThreshold": -1,
        "spark.rapids.shuffle.topology.numSlices": 2,
        "spark.rapids.shuffle.topology.sliceId": 0,
        "spark.sql.adaptive.enabled": False})
    try:
        f = sess.create_dataframe(fact, num_partitions=4)
        d = sess.create_dataframe(dim, num_partitions=2)
        built0 = B.STATS["blooms_built"]
        got = f.join(d, f.fk == d.pk, "inner").collect().to_pandas()
        assert B.STATS["blooms_built"] == built0
        # this slice returns its local partitions only — a strict subset,
        # every row of which must match the oracle
        exp = fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                     right_on="pk", how="inner")
        assert 0 < len(got) < len(exp)
        exp_keys = exp.groupby("fk").size()
        for fk, cnt in got.groupby("fk").size().items():
            assert exp_keys[fk] == cnt
    finally:
        srt.session(**{"spark.rapids.shuffle.topology.numSlices": 1,
                       "spark.sql.adaptive.enabled": True,
                       "spark.rapids.sql.autoBroadcastJoinThreshold":
                           10 * 1024 * 1024})


def test_broadcast_hint_forces_broadcast(sess):
    """F.broadcast(dim) / dim.hint('broadcast') skip the size threshold
    (Spark's ResolveHints + JoinSelection)."""
    rng = np.random.default_rng(21)
    fact = pa.table({"fk": rng.integers(0, 500, 20_000),
                     "x": rng.random(20_000)})
    dim = pa.table({"pk": np.arange(500, dtype=np.int64),
                    "n": [f"d{i}" for i in range(500)]})
    sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold", 1)
    try:
        f = sess.create_dataframe(fact, num_partitions=3)
        d = sess.create_dataframe(dim, num_partitions=2)
        q = f.join(F.broadcast(d), f.fk == d.pk, "inner")
        rep = str(sess.physical_plan(q).tree_string())
        assert "BroadcastHashJoin" in rep
        got = q.count()
        exp = fact.to_pandas().merge(dim.to_pandas(), left_on="fk",
                                     right_on="pk").shape[0]
        assert got == exp
        # unhinted stays off the broadcast path under the tiny threshold
        rep2 = str(sess.physical_plan(
            f.join(d, f.fk == d.pk, "inner")).tree_string())
        assert "BroadcastHashJoin" not in rep2
        # hint() surface, and unknown hints are ignored like Spark
        assert "BroadcastHashJoin" in str(sess.physical_plan(
            f.join(d.hint("broadcast"), f.fk == d.pk, "left")).tree_string())
        assert d.hint("nosuchhint") is d
    finally:
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      10 * 1024 * 1024)


def test_broadcast_hint_survives_transformations(sess):
    """select/filter/rename after the hint keep it (Spark's ResolvedHint
    survives transformations)."""
    rng = np.random.default_rng(22)
    fact = pa.table({"fk": rng.integers(0, 100, 5_000)})
    dim = pa.table({"pk": np.arange(100, dtype=np.int64),
                    "n": [f"d{i}" for i in range(100)]})
    sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold", 1)
    try:
        f = sess.create_dataframe(fact, num_partitions=3)
        d = F.broadcast(sess.create_dataframe(dim, num_partitions=2))
        d2 = d.filter(d.pk >= 0).withColumnRenamed("n", "name")
        q = f.join(d2, f.fk == d2.pk, "inner")
        assert "BroadcastHashJoin" in str(sess.physical_plan(q).tree_string())
        assert q.count() == 5_000
    finally:
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      10 * 1024 * 1024)


def test_broadcast_hint_scoping(sess):
    """A hint consumed by an inner join must not escape and broadcast the
    whole join result; a LEFT-side hint is honored for inner
    expression joins with output order preserved."""
    rng = np.random.default_rng(23)
    fact = sess.create_dataframe(
        pa.table({"fk": rng.integers(0, 50, 5_000)}), num_partitions=3)
    fact2 = sess.create_dataframe(
        pa.table({"gk": rng.integers(0, 50, 5_000)}), num_partitions=3)
    dim = sess.create_dataframe(
        pa.table({"pk": np.arange(50, dtype=np.int64),
                  "n": [f"d{i}" for i in range(50)]}))
    sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold", 1)
    try:
        mid = fact2.join(F.broadcast(dim), fact2.gk == dim.pk, "inner")
        rep = str(sess.physical_plan(
            fact.join(mid, fact.fk == mid.gk, "inner")).tree_string())
        assert rep.count("BroadcastExchange") <= 1, rep
        q = F.broadcast(dim).join(fact, dim.pk == fact.fk, "inner")
        assert "BroadcastHashJoin" in str(sess.physical_plan(q)
                                          .tree_string())
        out = q.collect()
        assert out.column_names == ["pk", "n", "fk"]
        assert out.num_rows == 5_000
    finally:
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      10 * 1024 * 1024)
