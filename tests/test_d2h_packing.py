"""Tests for the host-pull-minimizing performance layer: packed
single-transfer D2H, deferred speculation validation, whole-query tail fusion, and the
adaptive OOM-guard sync policy.

Reference context: the reference's per-op kernel-launch model (SURVEY
§3.3) assumes launches are ~free; here each host pull is a full
host<->device round trip, so these subsystems exist to get a warm query down
to one program launch + one fetch.
"""

import numpy as np
import pyarrow as pa
import pytest


# ---------------------------------------------------------------------------
# packed D2H
# ---------------------------------------------------------------------------

class TestBulkDeviceGet:
    def test_round_trip_all_dtypes(self):
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.columnar.convert import bulk_device_get
        rng = np.random.default_rng(7)
        tree = {
            "i64": jnp.asarray(rng.integers(-2**62, 2**62, 100)),
            "i32": jnp.asarray(rng.integers(-2**31, 2**31, 101, dtype=np.int32)),
            "i16": jnp.asarray(np.array([-5, 300, 32767], np.int16)),
            "u8": jnp.asarray(np.array([0, 255, 17], np.uint8)),
            "f32": jnp.asarray(rng.random(103).astype(np.float32)),
            "f64": jnp.asarray(rng.random(97) * rng.choice(
                [1e-30, 1.0, 1e30], 97)),
            "bool": jnp.asarray(rng.random(111) < 0.5),
            "scalar": jnp.asarray(42, jnp.int32),
            "empty": jnp.zeros(0, jnp.float64),
            "host": np.arange(5),
            "passthrough": "not-an-array",
        }
        out = bulk_device_get(tree)
        ref = jax.device_get(tree)
        for k in ref:
            if k == "passthrough":
                assert out[k] == "not-an-array"
                continue
            a, b = np.asarray(out[k]), np.asarray(ref[k])
            assert a.dtype == b.dtype, k
            assert np.array_equal(a, b), k

    def test_f64_bit_exact_on_cpu(self):
        """CPU backend: the arithmetic IEEE-754 extraction is bit-exact
        for normals/zeros/infs; NaNs canonicalize; denormals flush (DAZ,
        matching XLA's own arithmetic)."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.columnar.convert import _f64_bits
        rng = np.random.default_rng(3)
        raw = rng.integers(0, 2**64, 50_000, dtype=np.uint64)
        vals = np.concatenate([raw.view(np.float64), np.array(
            [0.0, -0.0, np.inf, -np.inf, np.nan, 1.0, -1.5, 0.1, 1e300],
            np.float64)])
        got = np.asarray(jax.jit(_f64_bits)(jnp.asarray(vals)))
        exp = vals.view(np.uint64)
        nan = np.isnan(vals)
        denorm = (np.abs(vals) < 2.2250738585072014e-308) & (vals != 0) & ~nan
        exp = exp.copy()
        exp[denorm] &= np.uint64(0x8000000000000000)
        ok = (got == exp) | (nan & (got == np.uint64(0x7FF8000000000000)))
        assert ok.all()


# ---------------------------------------------------------------------------
# deferred speculation + whole-query tail fusion
# ---------------------------------------------------------------------------

def _q1ish(sess, table):
    from spark_rapids_tpu.sql import functions as F
    df = sess.create_dataframe(table)
    return (df.filter(df.v < 0.8)
            .groupBy("k")
            .agg(F.sum(F.col("v")).alias("s"),
                 F.avg(F.col("v")).alias("a"),
                 F.count("*").alias("c"))
            .orderBy("k"))


class TestFusedCollect:
    def _expected(self, table):
        pdf = table.to_pandas()
        f = pdf[pdf.v < 0.8]
        g = f.groupby("k").agg(s=("v", "sum"), a=("v", "mean"),
                               c=("v", "count")).reset_index().sort_values("k")
        return g

    def test_engages_and_matches_oracle(self, session):
        import spark_rapids_tpu.sql.physical.collect_fusion as CF
        rng = np.random.default_rng(0)
        t = pa.table({"k": rng.integers(0, 8, 5000), "v": rng.random(5000)})
        q = _q1ish(session, t)
        q.collect()  # first run: exact path, records the group-table size
        before = CF.STATS["fused_collects"]
        got = q.collect().to_pandas()
        assert CF.STATS["fused_collects"] > before, \
            "warm collect did not take the fused tail"
        exp = self._expected(t)
        assert np.array_equal(np.asarray(got["k"]), np.asarray(exp["k"]))
        assert np.array_equal(np.asarray(got["c"]), np.asarray(exp["c"]))
        assert np.allclose(np.asarray(got["s"]), np.asarray(exp["s"]))
        assert np.allclose(np.asarray(got["a"]), np.asarray(exp["a"]))

    def test_mis_speculation_reruns_correctly(self, session):
        """Same query shape with exploding group cardinality: the recorded
        size under-estimates, the deferred check fails post-fetch, and the
        session re-runs to a correct result."""
        from spark_rapids_tpu.sql.physical import speculation as SPEC
        rng = np.random.default_rng(1)
        small = pa.table({"k": rng.integers(0, 4, 2000),
                          "v": rng.random(2000)})
        q = _q1ish(session, small)
        q.collect()
        q.collect()  # records/uses spec sized for ~4 groups
        big = pa.table({"k": rng.integers(0, 3000, 20_000),
                        "v": rng.random(20_000)})
        qb = _q1ish(session, big)
        before = SPEC.STATS["reruns"]
        got = qb.collect().to_pandas()
        exp = self._expected(big)
        assert len(got) == len(exp)
        assert np.array_equal(np.asarray(got["k"]), np.asarray(exp["k"]))
        assert np.allclose(np.asarray(got["s"]), np.asarray(exp["s"]))
        # the under-speculated first attempt must have been detected
        assert SPEC.STATS["reruns"] > before or len(exp) <= 64

    def test_oom_injection_still_exercises_retry(self, session):
        """The fused tail runs under the OOM guard; injected RetryOOM on
        the exact path (first run) must not corrupt results."""
        from spark_rapids_tpu.memory.retry import arm_oom_injection
        rng = np.random.default_rng(2)
        t = pa.table({"k": rng.integers(0, 5, 3000), "v": rng.random(3000)})
        q = _q1ish(session, t)
        arm_oom_injection(retry=1)
        got = q.collect().to_pandas()
        exp = self._expected(t)
        assert np.allclose(np.asarray(got["s"]), np.asarray(exp["s"]))


class TestDeferredChecks:
    def test_registry_lifecycle(self):
        from spark_rapids_tpu.sql.physical import speculation as SPEC
        SPEC.clear()
        seen = []
        c = SPEC.register(64, None, seen.append)
        assert SPEC.unresolved() == [c]
        c.resolve(100)
        assert seen == [100]
        assert c.failed
        c.resolve(3)  # second resolve is a no-op
        assert seen == [100]
        drained = SPEC.drain()
        assert drained == [c]
        assert SPEC.unresolved() == []

    def test_deferral_flag_is_thread_local_and_off_by_default(self):
        from spark_rapids_tpu.sql.physical import speculation as SPEC
        assert not SPEC.deferral_enabled()
        SPEC.set_deferral(True)
        try:
            assert SPEC.deferral_enabled()
        finally:
            SPEC.set_deferral(False)


# ---------------------------------------------------------------------------
# adaptive OOM-guard sync
# ---------------------------------------------------------------------------

class TestOomSyncPolicy:
    def test_auto_skips_sync_when_idle(self):
        import spark_rapids_tpu.memory.oom_guard as G
        from spark_rapids_tpu.config import RapidsConf
        RapidsConf.get_global()
        # an OOM-injecting test earlier in the session may have armed the
        # defensive eager-sync window; this test asserts the IDLE policy
        G._defensive_until = 0.0
        before = dict(G.STATS)
        wrapped = G.guard_device_oom(lambda: np.float32(1.0))
        wrapped()
        assert G.STATS["lazy_dispatches"] > before["lazy_dispatches"]

    def test_injection_arms_eager_sync(self):
        import spark_rapids_tpu.memory.oom_guard as G
        from spark_rapids_tpu.memory.retry import arm_oom_injection, \
            injection_state
        arm_oom_injection(retry=1)
        try:
            assert G._should_sync()
        finally:
            injection_state().arm(0, 0)

    def test_always_mode_syncs(self):
        import spark_rapids_tpu.memory.oom_guard as G
        from spark_rapids_tpu.config import OOM_SYNC_MODE, RapidsConf
        conf = RapidsConf.get_global()
        old = conf.get(OOM_SYNC_MODE)
        conf.set(OOM_SYNC_MODE.key, "always")
        try:
            assert G._should_sync()
        finally:
            conf.set(OOM_SYNC_MODE.key, old)

    def test_real_oom_enters_defensive_window(self):
        import spark_rapids_tpu.memory.oom_guard as G

        class FakeXlaRuntimeError(Exception):
            pass
        FakeXlaRuntimeError.__name__ = "XlaRuntimeError"
        calls = [0]

        def flaky():
            calls[0] += 1
            if calls[0] == 1:
                raise FakeXlaRuntimeError("RESOURCE_EXHAUSTED: oom")
            return 7

        old = G._defensive_until
        try:
            assert G.guard_device_oom(flaky)() == 7
            import time
            assert G._defensive_until > time.monotonic()
            assert G._should_sync()
        finally:
            G._defensive_until = old


# ---------------------------------------------------------------------------
# speculative small-table grouping
# ---------------------------------------------------------------------------

class TestGroupIdsSmall:
    def _cols(self, keys):
        import jax.numpy as jnp

        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar.column import DeviceColumn
        return [DeviceColumn(T.LONG, jnp.asarray(keys),
                             jnp.ones(len(keys), bool))]

    def test_matches_exact_kernel_when_table_fits(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.ops.hash_group import group_ids, \
            group_ids_small
        rng = np.random.default_rng(11)
        keys = rng.integers(0, 37, 4096)
        mask = jnp.asarray(rng.random(4096) < 0.8)
        cols = self._cols(keys)
        exact = np.asarray(group_ids(jnp, cols, mask))
        small = np.asarray(group_ids_small(jnp, cols, mask, 64))
        assert np.array_equal(exact, small)

    def test_overflow_inflates_group_count(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.ops.hash_group import group_ids_small
        rng = np.random.default_rng(12)
        keys = rng.permutation(4096)  # 4096 distinct keys
        mask = jnp.ones(4096, bool)
        expected = 4
        ids = np.asarray(group_ids_small(jnp, self._cols(keys), mask,
                                         expected))
        ng = int(ids.max()) + 1
        assert ng > expected, "overflow must be visible in the count"


class TestSegmentedReductionBackends:
    def test_seg2_column_split_matches_batched(self):
        """The XLA-CPU per-column scatter split must be value-identical
        to the batched 2-D scatter form."""
        import jax.numpy as jnp

        from spark_rapids_tpu.ops import segmented as S
        rng = np.random.default_rng(9)
        n, s, out = 50_000, 6, 64
        data = jnp.asarray(rng.random((n, s)))
        ids = jnp.asarray(rng.integers(0, out + 3, n).astype(np.int64))
        a = np.asarray(S.seg_sum2(jnp, data, ids, out))
        exp = np.zeros((out, s))
        live = np.asarray(ids) < out
        np.add.at(exp, np.asarray(ids)[live], np.asarray(data)[live])
        assert np.allclose(a, exp)
        mn = np.asarray(S.seg_min2(jnp, data, ids, out, np.inf))
        mx = np.asarray(S.seg_max2(jnp, data, ids, out, -np.inf))
        for g in range(out):
            sel = np.asarray(ids) == g
            if sel.any():
                assert np.allclose(mn[g], np.asarray(data)[sel].min(axis=0))
                assert np.allclose(mx[g], np.asarray(data)[sel].max(axis=0))


class TestSyncModeNever:
    def test_never_mode_skips_all_syncs(self, session):
        import spark_rapids_tpu.memory.oom_guard as G
        from spark_rapids_tpu.config import OOM_SYNC_MODE, RapidsConf
        conf = RapidsConf.get_global()
        old = conf.get(OOM_SYNC_MODE)
        conf.set(OOM_SYNC_MODE.key, "never")
        try:
            before = G.STATS["eager_syncs"]
            wrapped = G.guard_device_oom(lambda: np.float32(2.0))
            assert wrapped() == np.float32(2.0)
            assert G.STATS["eager_syncs"] == before
        finally:
            conf.set(OOM_SYNC_MODE.key, old)


class TestTopNTailFusion:
    def test_orderby_limit_fuses_and_matches(self, session):
        import spark_rapids_tpu.sql.physical.collect_fusion as CF
        from spark_rapids_tpu.sql import functions as F
        rng = np.random.default_rng(13)
        t = pa.table({"k": rng.integers(0, 40, 20_000),
                      "v": rng.random(20_000)})
        df = session.create_dataframe(t)
        q = (df.groupBy("k").agg(F.sum(df.v).alias("s"))
             .orderBy(F.col("s").desc()).limit(6))
        plan = session.physical_plan(q).tree_string()
        assert "FusedCollect" in plan and "TakeOrdered" in plan
        q.collect()
        before = CF.STATS["fused_collects"]
        got = q.collect().to_pandas()
        assert CF.STATS["fused_collects"] > before
        exp = (t.to_pandas().groupby("k").agg(s=("v", "sum")).reset_index()
               .sort_values("s", ascending=False).head(6)
               .reset_index(drop=True))
        assert np.array_equal(np.asarray(got["k"]), np.asarray(exp["k"]))
        assert np.allclose(np.asarray(got["s"]), np.asarray(exp["s"]))

    def test_limit_with_offset_keeps_generic_path(self, session):
        from spark_rapids_tpu.sql import functions as F
        t = pa.table({"a": list(range(20))})
        df = session.create_dataframe(t)
        q = df.orderBy(F.col("a").desc()).offset(3).limit(4)
        got = sorted(q.collect().to_pandas()["a"])
        # offset paths can't take the TakeOrdered composition; results
        # must still be exact
        assert got == [13, 14, 15, 16]

    def test_sort_within_partitions_limit_not_globalized(self, session):
        """sortWithinPartitions + limit must NOT compose into a global
        TopN (the limit takes rows from the locally-sorted stream)."""
        import pyarrow as pa
        if not hasattr(session.create_dataframe(
                pa.table({"a": [1]})), "sortWithinPartitions"):
            pytest.skip("sortWithinPartitions not exposed")
        t = pa.table({"a": [5, 1, 9, 3, 7, 2]})
        df = session.create_dataframe(t, num_partitions=2)
        q = df.sortWithinPartitions("a").limit(2)
        plan = session.physical_plan(q).tree_string()
        assert "TakeOrdered" not in plan


# ---------------------------------------------------------------------------
# multi-partition tail fusion (final-mode agg, look-through range exchange)
# ---------------------------------------------------------------------------

class TestFusedCollectMultiPartition:
    def test_final_mode_fuses_first_collect(self, session):
        """Partial/exchange/final plans need NO speculation warm-up: the
        merge's group count is exact, so even a cold collect fuses."""
        import spark_rapids_tpu.sql.physical.collect_fusion as CF
        from spark_rapids_tpu.sql import functions as F
        rng = np.random.default_rng(2)
        t = pa.table({"k": rng.integers(0, 40, 30_000),
                      "v": rng.random(30_000)})
        df = session.create_dataframe(t, num_partitions=4)
        q = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"),
                                 F.count("*").alias("c"))
             .orderBy("k"))
        before = CF.STATS["fused_collects"]
        got = q.collect().to_pandas()
        assert CF.STATS["fused_collects"] > before, \
            "multi-partition cold collect did not take the fused tail"
        pdf = t.to_pandas().groupby("k").agg(
            s=("v", "sum"), c=("v", "count")).reset_index().sort_values("k")
        assert np.array_equal(np.asarray(got["k"]), np.asarray(pdf["k"]))
        assert np.array_equal(np.asarray(got["c"]), np.asarray(pdf["c"]))
        assert np.allclose(np.asarray(got["s"]), np.asarray(pdf["s"]))

    def test_high_cardinality_falls_back_with_global_order(self, session):
        """When AQE cannot coalesce to one reduce partition, the skipped
        range exchange is NOT sound — the runtime must detect live sibling
        partitions and run the original tree, preserving global order."""
        import spark_rapids_tpu.sql.physical.collect_fusion as CF
        from spark_rapids_tpu.sql import functions as F
        rng = np.random.default_rng(3)
        n = 250_000
        t = pa.table({"k": rng.integers(0, 150_000, n), "v": rng.random(n)})
        df = session.create_dataframe(t, num_partitions=4)
        q = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"))
             .orderBy("k"))
        before = CF.STATS["fallbacks"]
        got = q.collect().to_pandas()
        assert CF.STATS["fallbacks"] > before
        ks = np.asarray(got["k"])
        assert np.all(ks[1:] >= ks[:-1]), "global order broken by fusion"
        exp = t.to_pandas().groupby("k").agg(s=("v", "sum")).reset_index()
        assert len(got) == len(exp)
        assert np.allclose(np.sort(np.asarray(got["s"])),
                           np.sort(np.asarray(exp["s"])))


class TestMeasuredTransitionCost:
    def test_fixed_cost_demotes_small_query(self):
        """The measured cost model: a high fixed cost per boundary makes a
        100-row device query a loss even though per-row rates favor the
        device (reference CostBasedOptimizer.scala:54)."""
        import spark_rapids_tpu as srt
        t = pa.table({"a": list(range(100)),
                      "b": [float(i) for i in range(100)]})
        sess = srt.session(**{
            "spark.rapids.sql.optimizer.enabled": True,
            "spark.rapids.sql.optimizer.transition.fixedSeconds": 0.065})
        try:
            df = sess.create_dataframe(t)
            q = df.select((df.a + 1).alias("a1"))
            rep = sess.explain(q)
            assert "CpuProject" in rep and "cost-based optimizer" in rep
            assert q.collect().to_pylist()[5]["a1"] == 6
        finally:
            srt.session(**{
                "spark.rapids.sql.optimizer.enabled": False,
                "spark.rapids.sql.optimizer.transition.fixedSeconds": -1.0})

    def test_fixed_cost_keeps_large_query(self):
        """Same 65ms boundary cost: at 8M rows the fixed latency is noise
        and the device placement must survive."""
        import spark_rapids_tpu as srt
        sess = srt.session(**{
            "spark.rapids.sql.optimizer.enabled": True,
            "spark.rapids.sql.optimizer.transition.fixedSeconds": 0.065})
        try:
            df = sess.range(8_000_000)
            rep = sess.explain(df.select((df.id * 2).alias("x")))
            assert "TpuProject" in rep
        finally:
            srt.session(**{
                "spark.rapids.sql.optimizer.enabled": False,
                "spark.rapids.sql.optimizer.transition.fixedSeconds": -1.0})

    def test_auto_measurement_is_cached(self):
        from spark_rapids_tpu.sql import optimizer as O
        O._MEASURED["rtt_s"] = None
        from spark_rapids_tpu.config import RapidsConf
        conf = RapidsConf()
        v1 = O.transition_fixed_seconds(conf)
        assert O._MEASURED["rtt_s"] is not None
        assert O.transition_fixed_seconds(conf) == v1

    def test_topn_final_mode_not_fused(self, session):
        """groupBy().agg().orderBy().limit(n) on multi-partition input:
        TakeOrderedAndProject merges all partitions itself, so final-mode
        fusion must be rejected — result is exactly n globally-first keys."""
        from spark_rapids_tpu.sql import functions as F
        rng = np.random.default_rng(4)
        n = 200_000
        t = pa.table({"k": rng.integers(0, 120_000, n), "v": rng.random(n)})
        df = session.create_dataframe(t, num_partitions=4)
        got = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"))
               .orderBy("k").limit(5).collect().to_pandas())
        exp = (t.to_pandas().groupby("k").agg(s=("v", "sum")).reset_index()
               .sort_values("k").head(5).reset_index(drop=True))
        assert len(got) == 5
        assert np.array_equal(np.asarray(got["k"]), np.asarray(exp["k"]))
        assert np.allclose(np.asarray(got["s"]), np.asarray(exp["s"]))
