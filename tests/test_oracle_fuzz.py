"""Independent-oracle harness: generated data at 100k rows, the ENGINE's
device path vs PANDAS (a genuinely independent engine — the reference's
tier-1 model where CPU Spark is the oracle, asserts.py:560).  The engine's
own numpy backend shares kernels with the device path and cannot catch
shared bugs; pandas can.

OOM injection is armed for every query so the retry/spill machinery is
exercised at scale (reference conftest inject_oom)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.testing import (ArrayGen, BooleanGen, DateGen,
                                      DoubleGen, IntegerGen, LongGen,
                                      StringGen, StructGen, gen_table)

N = 100_000

OOM_CONF = {
    "spark.rapids.sql.test.injectRetryOOM": 3,
    "spark.rapids.sql.test.injectSplitAndRetryOOM": 5,
}


@pytest.fixture(scope="module")
def data():
    return gen_table({
        "i": IntegerGen(min_val=-10_000, max_val=10_000),
        "l": LongGen(min_val=-(1 << 40), max_val=1 << 40),
        "d": DoubleGen(no_nans=True, no_extremes=True),
        "g": IntegerGen(min_val=0, max_val=500, nullable=False),
        "s": StringGen(max_len=16),
        "b": BooleanGen(),
        "dt": DateGen(),
    }, N, seed=42)


@pytest.fixture(scope="module")
def sess():
    yield srt.session(**OOM_CONF)
    # drop the injection-armed session so later modules' srt.session()
    # doesn't inherit synthetic OOMs (they can land on unsplittable
    # 1-row batches and fail unrelated tests)
    srt.session(**{k: 0 for k in OOM_CONF})


def _df(sess, data):
    return sess.create_dataframe(data, num_partitions=4)


def test_arithmetic_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.select(df.i, (df.i + df.l).alias("add"),
                     (df.d * 2.0 - 1.0).alias("mul"),
                     (-df.i).alias("neg"))
           .collect().to_pandas())
    pdf = data.to_pandas()
    exp_add = pdf["i"] + pdf["l"]
    assert np.allclose(got["add"].to_numpy(np.float64),
                       exp_add.to_numpy(np.float64), equal_nan=True)
    exp_mul = pdf["d"] * 2.0 - 1.0
    assert np.allclose(got["mul"].to_numpy(np.float64),
                       exp_mul.to_numpy(np.float64), equal_nan=True)


def test_filter_and_predicates_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.filter((df.i > 0) & df.b & df.d.isNotNull())
           .select(df.i, df.d).collect().to_pandas())
    pdf = data.to_pandas()
    exp = pdf[(pdf.i > 0) & (pdf.b == True) & pdf.d.notna()  # noqa: E712
              & pdf.i.notna() & pdf.b.notna()]
    assert len(got) == len(exp)
    assert sorted(got["i"].tolist()) == sorted(exp["i"].tolist())


def test_groupby_agg_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.groupBy("g")
           .agg(F.count("*").alias("c"), F.sum(df.d).alias("sd"),
                F.min(df.i).alias("mn"), F.max(df.i).alias("mx"),
                F.avg(df.d).alias("av"))
           .orderBy("g").collect().to_pandas())
    pdf = data.to_pandas()
    exp = (pdf.groupby("g")
           .agg(c=("g", "size"), sd=("d", "sum"), mn=("i", "min"),
                mx=("i", "max"), av=("d", "mean")).reset_index())
    assert np.array_equal(got["g"], exp["g"])
    assert np.array_equal(got["c"], exp["c"])
    assert np.allclose(got["sd"], exp["sd"], rtol=1e-9)
    # pandas min/max skip nulls like Spark
    assert np.array_equal(got["mn"].to_numpy(np.float64),
                          exp["mn"].to_numpy(np.float64), equal_nan=True)
    assert np.allclose(got["av"].to_numpy(np.float64),
                       exp["av"].to_numpy(np.float64), equal_nan=True)


def test_strings_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.select(df.s, F.upper(df.s).alias("up"),
                     F.length(df.s).alias("ln"),
                     F.substring(df.s, 2, 3).alias("sub"))
           .collect().to_pandas())
    pdf = data.to_pandas()
    s = pdf["s"]
    exp_up = s.str.upper()
    exp_ln = s.str.len()
    exp_sub = s.str.slice(1, 4)
    for i in range(0, N, 997):  # sampled row-wise compare
        if pd.isna(s.iloc[i]):
            assert pd.isna(got["up"].iloc[i])
            continue
        assert got["up"].iloc[i] == exp_up.iloc[i], i
        assert got["ln"].iloc[i] == exp_ln.iloc[i], i
        assert got["sub"].iloc[i] == exp_sub.iloc[i], i


def test_sort_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.orderBy(df.i.asc(), df.l.desc()).select(df.i, df.l)
           .collect().to_pandas())
    pdf = data.to_pandas()
    # Spark: nulls first for asc; pandas can't express per-key null order
    # with mixed directions, so compare the non-null block
    exp = (pdf[["i", "l"]].dropna(subset=["i"])
           .sort_values(["i", "l"], ascending=[True, False],
                        na_position="first"))
    n_null_i = int(pdf["i"].isna().sum())
    gi = got["i"].to_numpy(np.float64)
    assert np.isnan(gi[:n_null_i]).all()
    assert np.array_equal(gi[n_null_i:],
                          exp["i"].to_numpy(np.float64))


def test_join_vs_pandas(sess, data):
    df = _df(sess, data)
    dim = gen_table({"g": IntegerGen(0, 400, nullable=False),
                     "w": DoubleGen(no_nans=True, no_extremes=True,
                                    nullable=False)},
                    300, seed=7)
    # unique join keys on the build side
    dim = dim.group_by("g").aggregate([("w", "max")]).rename_columns(
        ["g", "w"])
    r = sess.create_dataframe(dim)
    got = (df.join(r, on="g", how="inner").select(df.g, df.i, r.w)
           .collect().to_pandas())
    exp = data.to_pandas().merge(dim.to_pandas(), on="g", how="inner")
    assert len(got) == len(exp)
    assert sorted(got["g"].tolist()) == sorted(exp["g"].tolist())
    assert abs(got["w"].sum() - exp["w"].sum()) < 1e-6 * max(
        1.0, abs(exp["w"].sum()))


def test_datetime_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.select(df.dt, F.year(df.dt).alias("y"),
                     F.month(df.dt).alias("m"),
                     F.dayofmonth(df.dt).alias("dom"))
           .collect().to_pandas())
    pdf = data.to_pandas()
    dt = pd.to_datetime(pdf["dt"])
    for i in range(0, N, 991):
        if pdf["dt"].iloc[i] is None:
            continue
        assert got["y"].iloc[i] == dt.dt.year.iloc[i], i
        assert got["m"].iloc[i] == dt.dt.month.iloc[i], i
        assert got["dom"].iloc[i] == dt.dt.day.iloc[i], i


def test_conditional_vs_pandas(sess, data):
    df = _df(sess, data)
    got = (df.select(
        F.when(df.i > 0, F.lit("pos")).when(df.i < 0, F.lit("neg"))
        .otherwise(F.lit("zero")).alias("sign"))
        .collect().to_pandas())
    pdf = data.to_pandas()
    exp = np.where(pdf["i"] > 0, "pos",
                   np.where(pdf["i"] < 0, "neg", "zero"))
    # null i -> no branch matches -> otherwise("zero")? Spark: null > 0 is
    # null (false-y), so nulls fall through to the otherwise value
    assert (got["sign"].to_numpy() == exp).all()


def test_nested_arrays_roundtrip(sess):
    t = gen_table({
        "u": LongGen(0, 1 << 30, nullable=False),
        "a": ArrayGen(IntegerGen(-100, 100), max_len=5),
        "st": StructGen([("x", IntegerGen(-5, 5)),
                         ("y", StringGen(max_len=6))]),
    }, 20_000, seed=3)
    sess2 = srt.session(**OOM_CONF)
    df = sess2.create_dataframe(t, num_partitions=3)
    got = df.select(df.u, df.a, df.st, F.size(df.a).alias("sz")) \
        .collect().to_pylist()
    exp = t.to_pylist()
    for g, e in zip(got, exp):
        assert g["u"] == e["u"]
        assert g["a"] == e["a"]
        assert g["st"] == e["st"]
        assert g["sz"] == (len(e["a"]) if e["a"] is not None else -1)


def test_rollup_vs_pandas(sess, data):
    """Grouping sets at 100k rows with OOM injection armed: every level's
    sums/counts must match pandas exactly."""
    df = _df(sess, data)
    got = (df.rollup("g", "b")
           .agg(F.sum(df.d).alias("sv"), F.count("*").alias("c"),
                F.grouping_id().alias("gid"))
           .collect().to_pandas())
    pdf = data.to_pandas()
    l0 = (pdf.groupby(["g", "b"], dropna=False)
          .agg(sv=("d", "sum"), c=("d", "size")).reset_index())
    l1 = (pdf.groupby(["g"], dropna=False)
          .agg(sv=("d", "sum"), c=("d", "size")).reset_index())
    assert len(got) == len(l0) + len(l1) + 1
    # key-wise comparison at every level (b is nullable: merge on both
    # keys with NaN-safe equality via fillna sentinels)
    g0 = (got[got.gid == 0].assign(bk=lambda x: x.b.fillna(-1))
          .sort_values(["g", "bk"]).reset_index(drop=True))
    e0 = (l0.assign(bk=lambda x: x.b.fillna(-1))
          .sort_values(["g", "bk"]).reset_index(drop=True))
    assert np.array_equal(g0["g"], e0["g"])
    assert np.array_equal(g0["bk"], e0["bk"])
    assert np.array_equal(g0["c"], e0["c"])
    assert np.allclose(np.asarray(g0["sv"].fillna(0.0)),
                       np.asarray(e0["sv"].fillna(0.0)))
    g1 = got[got.gid == 1].sort_values("g").reset_index(drop=True)
    e1 = l1.sort_values("g").reset_index(drop=True)
    assert np.array_equal(g1["g"], e1["g"])
    assert np.array_equal(g1["c"], e1["c"])
    assert np.allclose(np.asarray(g1["sv"].fillna(0.0)),
                       np.asarray(e1["sv"].fillna(0.0)))
    tot = got[got.gid == 3]
    assert int(tot["c"].iloc[0]) == len(pdf)
    assert np.isclose(float(tot["sv"].iloc[0]), pdf.d.sum())


def test_subquery_predicates_vs_pandas(sess, data):
    """IN / NOT EXISTS subqueries at 100k rows against pandas."""
    df = _df(sess, data)
    df.createOrReplaceTempView("fz_t")
    pdf = data.to_pandas()
    got = sess.sql(
        "SELECT g, count(*) AS c FROM fz_t WHERE g IN "
        "(SELECT g FROM fz_t WHERE d > 0.98) GROUP BY g ORDER BY g"
    ).collect().to_pandas()
    keys = set(pdf.g[pdf.d > 0.98])
    exp = (pdf[pdf.g.isin(keys)].groupby("g").size()
           .sort_index().reset_index(name="c"))
    assert np.array_equal(got["g"], exp["g"])
    assert np.array_equal(got["c"], exp["c"])
    got = sess.sql(
        "SELECT count(*) AS c FROM fz_t a WHERE NOT EXISTS "
        "(SELECT 1 FROM fz_t b WHERE b.g = a.g AND b.d > 0.98)"
    ).collect().to_pylist()[0]["c"]
    assert got == int((~pdf.g.isin(keys)).sum())


def test_scalar_subquery_and_interval_vs_pandas(sess, data):
    df = _df(sess, data)
    df.createOrReplaceTempView("fz_t2")
    pdf = data.to_pandas()
    got = sess.sql(
        "SELECT count(*) AS c FROM fz_t2 WHERE d > "
        "(SELECT avg(d) FROM fz_t2)").collect().to_pylist()[0]["c"]
    assert got == int((pdf.d > pdf.d.mean()).sum())
    got = sess.sql(
        "SELECT count(*) AS c FROM fz_t2 WHERE dt + INTERVAL '1' YEAR "
        "<= CAST('2015-06-01' AS date)").collect().to_pylist()[0]["c"]
    import datetime
    shifted = pd.Series(pdf.dt.dropna()).map(
        lambda x: datetime.date(x.year + 1, x.month,
                                28 if (x.month == 2 and x.day == 29)
                                else x.day))
    assert got == int((shifted <= datetime.date(2015, 6, 1)).sum())


def test_bloom_filtered_star_join_vs_pandas(sess, data):
    """Shuffle join with the bloom runtime filter engaged (small dim,
    broadcast disabled) under OOM injection — results must equal pandas
    exactly; the filter may only DROP non-matching probe rows early."""
    from spark_rapids_tpu.ops import bloom as B
    dim = gen_table({
        "g": IntegerGen(min_val=0, max_val=500, nullable=False),
        "name": StringGen(max_len=8),
    }, 60, seed=7)
    # dedupe dim keys (dim tables are unique-keyed; keeps the oracle 1:1)
    dim = dim.group_by("g").aggregate([("name", "max")]).rename_columns(
        ["g", "name"])
    prev_thr = sess.conf.get("spark.rapids.sql.autoBroadcastJoinThreshold",
                             10 * 1024 * 1024)
    sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold", -1)
    try:
        df = _df(sess, data)
        ddf = sess.create_dataframe(dim, num_partitions=2)
        built0 = B.STATS["blooms_built"]
        got = (df.join(ddf, df.g == ddf.g, "inner")
               .select(df.i, df.g, F.col("name"))
               .collect().to_pandas())
        assert B.STATS["blooms_built"] > built0, "bloom did not engage"
        exp = (data.to_pandas().merge(dim.to_pandas(), on="g",
                                      how="inner")[["i", "g", "name"]])
        assert len(got) == len(exp)
        a = got.sort_values(["i", "g", "name"]).reset_index(drop=True)
        b = exp.sort_values(["i", "g", "name"]).reset_index(drop=True)
        assert a.equals(b.astype(a.dtypes.to_dict()))
    finally:
        sess.conf.set("spark.rapids.sql.autoBroadcastJoinThreshold",
                      prev_thr)


def test_tdigest_percentile_vs_pandas_quantiles(sess, data):
    """Grouped approx_percentile on the t-digest path under OOM
    injection: each estimate must sit within 3.5% rank error of the
    group's true distribution (pandas as the independent oracle; the
    delta-200 sketch merged across OOM-split batches lands ~2.5%
    worst-case on 200-row groups)."""
    sess.conf.set("spark.rapids.sql.approxPercentile.strategy", "tdigest")
    try:
        df = _df(sess, data)
        got = (df.filter(df.d.isNotNull()).groupBy("g")
               .agg(F.percentile_approx(df.d, [0.25, 0.5, 0.75])
                    .alias("pq"))
               .collect().to_pandas())
        pdf = data.to_pandas()
        pdf = pdf[pdf.d.notna()]
        checked = 0
        for gi in got["g"].head(40):
            gv = np.sort(pdf[pdf.g == gi].d.values)
            if len(gv) < 50:
                continue
            row = got[got.g == gi].pq.iloc[0]
            for est, p in zip(row, [0.25, 0.5, 0.75]):
                rank = np.searchsorted(gv, est) / len(gv)
                assert abs(rank - p) < 0.035, (gi, p, rank)
            checked += 1
        assert checked > 10
    finally:
        sess.conf.set("spark.rapids.sql.approxPercentile.strategy", "auto")


def test_window_functions_vs_pandas(sess, data):
    """Window functions over generated data under OOM injection:
    row_number / whole-partition avg / lag, vs pandas oracles."""
    from spark_rapids_tpu.sql.window_api import Window
    df = _df(sess, data)
    w = Window.partitionBy("g").orderBy("i", "l")
    wp = Window.partitionBy("g")
    got = (df.filter(df.i.isNotNull() & df.l.isNotNull())
           .select(df.g, df.i, df.l, df.d,
                   F.row_number().over(w).alias("rn"),
                   F.avg(df.d).over(wp).alias("ga"),
                   F.lag(df.i, 1).over(w).alias("pi"))
           .collect().to_pandas()
           .sort_values(["g", "i", "l"]).reset_index(drop=True))
    pdf = data.to_pandas()
    pdf = pdf[pdf.i.notna() & pdf.l.notna()].copy()
    pdf = pdf.sort_values(["g", "i", "l"], kind="stable")
    pdf["rn"] = pdf.groupby("g").cumcount() + 1
    pdf["ga"] = pdf.groupby("g").d.transform("mean")
    pdf["pi"] = pdf.groupby("g").i.shift(1)
    exp = pdf.reset_index(drop=True)
    assert len(got) == len(exp)
    assert np.array_equal(got["g"].values, exp["g"].values)
    # ties on (i, l) make rn order-dependent; per group the rank SET must
    # still be exactly 1..n
    for gi in got["g"].unique()[:30]:
        rn = np.sort(got[got.g == gi].rn.values)
        assert np.array_equal(rn, np.arange(1, len(rn) + 1)), gi
    assert np.allclose(got["ga"].values, exp["ga"].values)
    # lag: compare the multiset per group (tie order may differ)
    for gi in got["g"].unique()[:25]:
        a = sorted(got[got.g == gi].pi.dropna().values.tolist())
        b = sorted(exp[exp.g == gi].pi.dropna().values.tolist())
        assert a == b, gi


def test_lateral_view_explode_fuzz_vs_pandas(sess):
    """Randomized LATERAL VIEW [OUTER] explode/posexplode over generated
    nested rows vs pandas explode (round-3 surfaces
    had example-based tests only)."""
    rng = np.random.default_rng(61)
    n = 4000
    lens = rng.integers(0, 5, n)
    arrs = [None if i % 37 == 0 else
            [int(v) for v in rng.integers(-50, 50, lens[i])]
            for i in range(n)]
    t = pa.table({
        "k": pa.array(rng.integers(0, 30, n), pa.int64()),
        "arr": pa.array(arrs, pa.list_(pa.int64())),
    })
    sess.create_dataframe(t, num_partitions=3).createOrReplaceTempView(
        "lvf_t")
    pdf = t.to_pandas()

    for outer in (False, True):
        kw = "LATERAL VIEW OUTER" if outer else "LATERAL VIEW"
        got = sess.sql(
            f"SELECT k, c FROM lvf_t {kw} explode(arr) x AS c"
        ).collect().to_pandas()
        exp = pdf[["k", "arr"]].explode("arr").rename(columns={"arr": "c"})
        if not outer:
            exp = exp.dropna(subset=["c"])
        else:
            # OUTER keeps null/empty rows with c = NULL — pandas explode
            # already yields NaN for both empty lists and None
            pass
        g = sorted(map(tuple, got.fillna(-10**9).values.tolist()))
        e = sorted((int(k), int(c) if c == c and c is not None else -10**9)
                   for k, c in exp.values.tolist())
        assert g == e, (outer, g[:5], e[:5])

    got = sess.sql(
        "SELECT k, p, c FROM lvf_t LATERAL VIEW posexplode(arr) x AS p, c"
    ).collect().to_pandas()
    rows = []
    for k, arr in pdf[["k", "arr"]].values.tolist():
        if arr is None or (hasattr(arr, "__len__") and len(arr) == 0):
            continue
        for p, c in enumerate(arr):
            rows.append((int(k), p, int(c)))
    assert sorted(map(tuple, got.values.tolist())) == sorted(rows)


def test_tablesample_fuzz_properties(sess):
    """TABLESAMPLE (n PERCENT | n ROWS) REPEATABLE: determinism, subset
    property, and row-count bounds over random fractions."""
    rng = np.random.default_rng(62)
    n = 20_000
    t = pa.table({
        "id": pa.array(list(range(n)), pa.int64()),
        "v": pa.array(rng.random(n)),
    })
    sess.create_dataframe(t, num_partitions=4).createOrReplaceTempView(
        "tsf_t")
    all_ids = set(range(n))
    for trial in range(5):
        pct = int(rng.integers(5, 60))
        seed = int(rng.integers(0, 10_000))
        q = (f"SELECT id FROM tsf_t TABLESAMPLE ({pct} PERCENT) "
             f"REPEATABLE ({seed})")
        a = sess.sql(q).collect().column("id").to_pylist()
        b = sess.sql(q).collect().column("id").to_pylist()
        assert a == b, "REPEATABLE sample must be deterministic"
        assert set(a) <= all_ids and len(set(a)) == len(a)
        # Bernoulli sampling: expect pct% +- 5 sigma
        import math
        sigma = math.sqrt(n * (pct / 100) * (1 - pct / 100))
        assert abs(len(a) - n * pct / 100) < 5 * sigma + 10, (pct, len(a))
    for rows in (17, 1003):
        got = sess.sql(
            f"SELECT id FROM tsf_t TABLESAMPLE ({rows} ROWS)"
        ).collect().num_rows
        assert got == rows


def test_interval_arithmetic_fuzz_vs_pandas(sess):
    """Randomized INTERVAL +/- over date/timestamp columns vs pandas
    DateOffset/timedelta semantics (month arithmetic clamps to month end
    the way Spark does)."""
    rng = np.random.default_rng(63)
    n = 3000
    days = rng.integers(0, 20000, n)
    micros = rng.integers(0, 2**44, n)
    t = pa.table({
        "d": pa.array(days.astype("int32"), pa.date32()),
        "ts": pa.array(micros, pa.timestamp("us")),
    })
    sess.create_dataframe(t, num_partitions=2).createOrReplaceTempView(
        "ivf_t")
    pdf = t.to_pandas()
    for trial in range(4):
        nd = int(rng.integers(1, 400))
        nm = int(rng.integers(1, 30))
        nh = int(rng.integers(1, 100))
        got = sess.sql(
            f"SELECT d + INTERVAL '{nd}' DAY AS d1, "
            f"d - INTERVAL '{nm}' MONTH AS d2, "
            f"ts + INTERVAL '{nh}' HOUR AS t1 "
            f"FROM ivf_t").collect().to_pandas()
        exp_d1 = pdf.d + pd.Timedelta(days=nd)
        exp_d2 = (pd.to_datetime(pdf.d) - pd.DateOffset(months=nm)).dt.date
        exp_t1 = pdf.ts + pd.Timedelta(hours=nh)
        assert (pd.to_datetime(got.d1) ==
                pd.to_datetime(exp_d1)).all(), (trial, nd)
        assert (got.d2 == exp_d2).all(), (trial, nm)
        got_t1 = pd.to_datetime(got.t1)
        if got_t1.dt.tz is not None:      # engine returns tz-aware UTC
            got_t1 = got_t1.dt.tz_localize(None)
        assert (got_t1 == exp_t1).all(), (trial, nh)
