"""t-digest approx_percentile — kernel accuracy, strategy selection, and
the digest-per-batch merge path that keeps percentile memory bounded at
O(groups x delta/2) regardless of group size (reference
``GpuApproximatePercentile.scala:1-222``)."""

import os
import tempfile

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F


def _rank_err(sorted_vals, est, p):
    return abs(np.searchsorted(sorted_vals, est) / len(sorted_vals) - p)


@pytest.fixture(autouse=True)
def _restore_conf():
    """session(**conf) mutates the process-global conf — restore the keys
    these tests touch so later modules see the defaults."""
    yield
    srt.session(**{
        "spark.rapids.sql.approxPercentile.strategy": "auto",
        "spark.rapids.sql.reader.chunked": True,
        "spark.rapids.sql.reader.chunked.targetRows": 1 << 21})


class TestKernel:
    @pytest.mark.parametrize("G,per", [(50, 300), (64, 2000), (200, 37)])
    def test_accuracy_vs_oracle(self, G, per):
        from spark_rapids_tpu.ops import tdigest as TD
        rng = np.random.default_rng(0)
        vals = rng.normal(100, 20, G * per)
        grp = np.repeat(np.arange(G), per)
        ones = np.ones(G * per)
        means, wts, vmin, vmax, total = TD.build_grouped(
            np, vals, ones, ones.astype(bool), grp, ones.astype(bool),
            G, 100)
        outs = TD.percentiles_grouped(np, means, wts, vmin, vmax, total,
                                      [0.01, 0.5, 0.99])
        worst = 0.0
        for gi in range(G):
            gv = np.sort(vals[grp == gi])
            for pi, p in enumerate([0.01, 0.5, 0.99]):
                worst = max(worst, _rank_err(gv, outs[pi][gi], p))
        assert worst < 0.03 + 1.0 / per

    def test_jnp_matches_numpy(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.ops import tdigest as TD
        rng = np.random.default_rng(1)
        n, G = 30_000, 32
        vals, grp = rng.random(n) * 100, rng.integers(0, G, n)
        ones = np.ones(n)
        a = TD.build_grouped(np, vals, ones, ones.astype(bool), grp,
                             ones.astype(bool), G, 100)
        b = TD.build_grouped(jnp, jnp.asarray(vals), jnp.asarray(ones),
                             jnp.asarray(ones.astype(bool)),
                             jnp.asarray(grp),
                             jnp.asarray(ones.astype(bool)), G, 100)
        pa_ = TD.percentiles_grouped(np, *a, [0.5])[0]
        pb = np.asarray(TD.percentiles_grouped(jnp, *b, [0.5])[0])
        assert np.allclose(pa_, pb, rtol=1e-9)

    def test_weighted_merge_matches_single_pass(self):
        from spark_rapids_tpu.ops import tdigest as TD
        rng = np.random.default_rng(2)
        n, G, delta = 80_000, 16, 100
        vals, grp = rng.normal(0, 1, n), rng.integers(0, G, n)
        C = TD.n_centroids(delta)
        ev, ew, eg, los, his = [], [], [], [], []
        for ch in np.array_split(np.arange(n), 3):
            ones = np.ones(len(ch))
            m, w, lo, hi, _t = TD.build_grouped(
                np, vals[ch], ones, ones.astype(bool), grp[ch],
                ones.astype(bool), G, delta)
            gg = np.repeat(np.arange(G), C)
            sel = w.ravel() > 0
            ev.append(m.ravel()[sel]); ew.append(w.ravel()[sel])
            eg.append(gg[sel]); los.append(lo); his.append(hi)
        ev, ew, eg = map(np.concatenate, (ev, ew, eg))
        ones = np.ones(len(ev), bool)
        m, w, _lo, _hi, total = TD.build_grouped(np, ev, ew, ones, eg,
                                                 ones, G, delta)
        vmin = np.min(np.stack(los), axis=0)
        vmax = np.max(np.stack(his), axis=0)
        est = TD.percentiles_grouped(np, m, w, vmin, vmax, total, [0.5])[0]
        worst = 0.0
        for gi in range(G):
            gv = np.sort(vals[grp == gi])
            worst = max(worst, _rank_err(gv, est[gi], 0.5))
        assert worst < 0.01
        assert np.allclose(total, np.bincount(grp, minlength=G))


class TestEngine:
    def test_tdigest_strategy_grouped(self):
        rng = np.random.default_rng(3)
        n, G = 300_000, 500
        t = pa.table({"k": rng.integers(0, G, n),
                      "v": rng.normal(100, 20, n)})
        sess = srt.session(**{
            "spark.rapids.sql.approxPercentile.strategy": "tdigest"})
        df = sess.create_dataframe(t, num_partitions=4)
        got = (df.groupBy("k")
               .agg(F.percentile_approx(df.v, [0.1, 0.9]).alias("pq"),
                    F.percentile_approx(df.v, 0.5).alias("p50"))
               .collect().to_pandas())
        assert len(got) == G
        pdf = t.to_pandas()
        for gi in rng.choice(G, 20, replace=False):
            gv = np.sort(pdf[pdf.k == gi].v.values)
            row = got[got.k == gi].iloc[0]
            assert _rank_err(gv, row["p50"], 0.5) < 0.03
            for est, p in zip(row["pq"], [0.1, 0.9]):
                assert _rank_err(gv, est, p) < 0.03

    def test_exact_strategy_unchanged(self):
        """strategy=exact keeps the ordinal rule bit-for-bit."""
        t = pa.table({"k": [1, 1, 1, 1, 2, 2], "v": [1., 2., 3., 4., 7., 9.]})
        sess = srt.session(**{
            "spark.rapids.sql.approxPercentile.strategy": "exact"})
        df = sess.create_dataframe(t)
        got = (df.groupBy("k").agg(F.percentile_approx(df.v, 0.5).alias("p"))
               .collect().to_pandas().sort_values("k"))
        assert list(got["p"]) == [2.0, 7.0]

    def test_integral_input_returns_integral(self):
        rng = np.random.default_rng(4)
        t = pa.table({"k": rng.integers(0, 10, 50_000),
                      "v": rng.integers(0, 1000, 50_000).astype(np.int64)})
        sess = srt.session(**{
            "spark.rapids.sql.approxPercentile.strategy": "tdigest"})
        df = sess.create_dataframe(t)
        got = (df.groupBy("k").agg(F.percentile_approx(df.v, 0.5).alias("p"))
               .collect())
        assert got.schema.field("p").type in (pa.int64(),)

    def test_chunked_scan_merges_digests(self):
        """Chunked parquet scan: each chunk digests separately; the merge
        path must engage (no raw-row concat) and stay accurate."""
        rng = np.random.default_rng(5)
        n, G = 300_000, 30
        t = pa.table({"k": rng.integers(0, G, n).astype(np.int64),
                      "v": rng.normal(0, 1, n)})
        d = tempfile.mkdtemp()
        path = os.path.join(d, "t.parquet")
        pq.write_table(t, path, row_group_size=30_000)
        sess = srt.session(**{
            "spark.rapids.sql.approxPercentile.strategy": "tdigest",
            "spark.rapids.sql.reader.chunked": True,
            "spark.rapids.sql.reader.chunked.targetRows": 40_000})
        got = (sess.read.parquet(path).groupBy("k")
               .agg(F.percentile_approx(F.col("v"), 0.5).alias("p"))
               .collect().to_pandas())
        m = sess.last_query_metrics
        assert m.get("aggTdigestMergedBatches", 0) > 1, m
        assert len(got) == G
        pdf = t.to_pandas()
        for gi in range(G):
            gv = np.sort(pdf[pdf.k == gi].v.values)
            assert _rank_err(gv, got[got.k == gi].p.iloc[0], 0.5) < 0.02

    def test_auto_uses_exact_for_small(self):
        """auto keeps small batches on the exact ordinal rule."""
        t = pa.table({"k": [1] * 5, "v": [5., 1., 3., 2., 4.]})
        sess = srt.session()
        df = sess.create_dataframe(t)
        got = (df.groupBy("k").agg(F.percentile_approx(df.v, 0.5).alias("p"))
               .collect().to_pylist())
        assert got[0]["p"] == 3.0

    def test_all_null_group_emits_null_row(self):
        """A group whose percentile input is entirely NULL must still
        appear in the output with a NULL percentile — including on the
        multi-batch digest-merge path (anchor rows)."""
        rng = np.random.default_rng(6)
        n, G = 120_000, 20
        ks = rng.integers(0, G, n).astype(np.int64)
        vs = rng.normal(0, 1, n)
        null_mask = ks == 7          # group 7: all values NULL
        t = pa.table({"k": ks,
                      "v": pa.array(np.where(null_mask, np.nan, vs),
                                    mask=null_mask)})
        d = tempfile.mkdtemp()
        path = os.path.join(d, "t.parquet")
        pq.write_table(t, path, row_group_size=20_000)
        sess = srt.session(**{
            "spark.rapids.sql.approxPercentile.strategy": "tdigest",
            "spark.rapids.sql.reader.chunked": True,
            "spark.rapids.sql.reader.chunked.targetRows": 25_000})
        got = (sess.read.parquet(path).groupBy("k")
               .agg(F.percentile_approx(F.col("v"), 0.5).alias("p"))
               .collect().to_pandas())
        m = sess.last_query_metrics
        assert m.get("aggTdigestMergedBatches", 0) > 1, m
        assert len(got) == G, f"missing groups: {sorted(set(range(G)) - set(got.k))}"
        assert got[got.k == 7].p.isna().all()
        assert got[got.k != 7].p.notna().all()
