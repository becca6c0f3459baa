"""Compile-cache + whole-stage-fusion behavior:
repeated collect() of the same query must reuse compiled kernels instead of
re-tracing, and fused plans must match unfused results exactly."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.config import FUSION_ENABLED
from spark_rapids_tpu.sql.physical.kernel_cache import (cache_stats,
                                                        clear_cache)


def _q1_like(sess, rows=50_000):
    rng = np.random.default_rng(7)
    df = sess.create_dataframe(pa.table({
        "k": rng.integers(0, 5, rows).astype(np.int64),
        "v": rng.random(rows).astype(np.float32),
        "w": rng.random(rows).astype(np.float32),
    }))
    return (df.filter(df.v < 0.8)
            .withColumn("x", df.v * (1.0 - df.w))
            .groupBy("k")
            .agg(F.sum(F.col("x")).alias("sx"),
                 F.avg(F.col("v")).alias("av"),
                 F.count("*").alias("c"))
            .orderBy("k"))


def test_repeat_collect_hits_cache(session):
    clear_cache()  # order-independent: force a genuinely cold first run
    q = _q1_like(session)
    first = q.collect()
    misses_after_first = cache_stats()["misses"]

    second = q.collect()
    stats = cache_stats()
    # the second run may compile exactly ONE new program: the speculative
    # fused group+reduce sized to the group count the first run observed
    assert stats["misses"] - misses_after_first <= 1, \
        "second collect() compiled new kernels instead of reusing cached ones"
    misses_after_second = stats["misses"]
    dispatches_after_second = stats["dispatches"]

    third = q.collect()
    stats = cache_stats()
    assert stats["misses"] == misses_after_second, \
        "steady-state collect() must be fully cached"
    # the steady state launches programs of the cache and looks none up:
    # the fused collect tail keeps its program (collect_fusion.
    # _TAIL_PROGRAMS) and execs keep their wrappers, so "hits" stays 0
    # here; a lookup that hits is test_cached_jit_key_is_the_callers
    assert stats["dispatches"] > dispatches_after_second
    assert first.to_pylist() == second.to_pylist() == third.to_pylist()


def test_cached_jit_key_is_the_callers():
    """The cache's key is what the caller passed — no salt, nothing
    measured in this process — so every process of a commit builds the
    same programs under the same names; a second lookup is a hit."""
    from spark_rapids_tpu.sql.physical import kernel_cache as kc
    key = ("KeyTestExec", "probe", 17)
    before = cache_stats()
    one = kc.cached_jit(key, lambda x: x + 1)
    assert key in kc._CACHE
    two = kc.cached_jit(key, lambda x: x + 2)   # dropped: the key decides
    after = cache_stats()
    assert two is one
    assert after["misses"] - before["misses"] == 1
    assert after["hits"] - before["hits"] == 1
    assert int(one(np.int32(1))) == 2


def test_fresh_plan_same_query_reuses_kernels(session):
    """A *newly built* identical query (new expression objects) must reuse
    the same compiled kernels — keys are structural, not object-identity."""
    _q1_like(session).collect()
    misses = cache_stats()["misses"]
    _q1_like(session).collect()
    assert cache_stats()["misses"] == misses


def test_fused_kernel_not_leaked_to_unfused_query(session):
    """Regression: a fused partial kernel (filter absorbed) must not be
    served to a later UNFUSED aggregate with the same grouping/slots —
    the pre-step chain is part of the cache key and baked into the
    closure, never read from mutable exec state."""
    df = session.create_dataframe(pa.table({
        "k": [0, 0, 1, 1, 2, 2], "v": [1.0, 2.0, 3.0, 4.0, 5.0, 6.0]}))
    fused = (df.filter(df.v < 4.0).groupBy("k")
             .agg(F.sum(F.col("v")).alias("sv")).orderBy("k"))
    assert [(r["k"], r["sv"]) for r in fused.collect().to_pylist()] == \
        [(0, 3.0), (1, 3.0)]
    unfused = (df.groupBy("k").agg(F.sum(F.col("v")).alias("sv"))
               .orderBy("k"))
    assert [(r["k"], r["sv"]) for r in unfused.collect().to_pylist()] == \
        [(0, 3.0), (1, 7.0), (2, 11.0)]


def test_batched_2d_reduce_matches_per_slot(session, monkeypatch):
    """The TPU-only batched segmented-reduce path must agree with the
    per-slot path (it has no CPU coverage otherwise)."""
    import spark_rapids_tpu.sql.physical.aggregate as agg_mod
    rng = np.random.default_rng(11)
    df = session.create_dataframe(pa.table({
        "k": rng.integers(0, 7, 5000).astype(np.int64),
        "v": rng.random(5000).astype(np.float32),
        "i": rng.integers(-50, 50, 5000).astype(np.int64),
    }))
    q = (df.groupBy("k")
         .agg(F.sum(F.col("v")).alias("sv"), F.min(F.col("i")).alias("mi"),
              F.max(F.col("i")).alias("ma"), F.count("*").alias("c"),
              F.avg(F.col("v")).alias("av"))
         .orderBy("k"))
    base = q.collect().to_pylist()
    monkeypatch.setattr(agg_mod, "_use_batched_reduce",
                        lambda xp: xp.__name__ != "numpy")
    clear_cache()  # drop kernels traced through the per-slot path
    try:
        batched = q.collect().to_pylist()
    finally:
        clear_cache()  # don't leak batched-trace kernels to other tests
    assert batched == base


def test_fusion_matches_unfused(session):
    q = _q1_like(session)
    fused = q.collect()
    session.conf.set(FUSION_ENABLED.key, False)
    try:
        unfused = q.collect()
    finally:
        session.conf.set(FUSION_ENABLED.key, True)
    assert fused.to_pylist() == unfused.to_pylist()


def test_fused_stage_in_plan(session):
    rng = np.random.default_rng(3)
    df = session.create_dataframe(pa.table({
        "a": rng.integers(0, 9, 100).astype(np.int64),
        "b": rng.random(100),
    }))
    q = (df.filter(df.a > 2)
         .withColumn("c", df.b * 2.0)
         .filter(df.b < 0.9)
         .select("a", "c"))
    plan = session.physical_plan(q)
    assert "FusedStage" in plan.tree_string()
    out = q.collect()
    expect = [(int(a), float(b) * 2.0)
              for a, b in zip(np.asarray(df._plan.table["a"]),
                              np.asarray(df._plan.table["b"]))
              if a > 2 and b < 0.9]
    got = [(r["a"], r["c"]) for r in out.to_pylist()]
    assert got == pytest.approx(expect)
