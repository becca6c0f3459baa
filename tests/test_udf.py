"""UDF stack: compiled lambdas (udf-compiler analog), row Python UDFs,
pandas UDFs, device columnar UDFs (RapidsUDF SPI analog), mapInPandas and
applyInPandas (reference SURVEY §2.9 Python exec family)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.sql import functions as F


@pytest.fixture()
def sess():
    return srt.session()


def make_df(sess):
    t = pa.table({"a": [1., 2., 3., 4.], "b": [10., 20., 30., 40.],
                  "g": [1, 1, 2, 2]})
    return sess.create_dataframe(t), t


def test_compilable_lambda_runs_on_device(sess):
    df, t = make_df(sess)
    f1 = F.udf(lambda a, b: a * 2.0 + b if a > 2.0 else b - a,
               returnType=T.DOUBLE)
    q = df.select(f1(df.a, df.b).alias("r"))
    rep = sess.explain(q)
    assert "PythonUDF" not in rep, rep  # compiled into native expressions
    assert "cannot run" not in rep, rep
    out = [r["r"] for r in q.collect().to_pylist()]
    assert out == [9.0, 18.0, 36.0, 48.0]


def test_compiled_function_with_math(sess):
    df, t = make_df(sess)

    def my_fn(a):
        return abs(a - 3.0) + sqrt_stub(a)

    # a plain def with an unknown call must NOT compile -> host UDF
    def sqrt_stub(a):  # pragma: no cover - never called on device
        return 0.0
    f = F.udf(my_fn, returnType=T.DOUBLE)
    q = df.select(f(df.a).alias("r"))
    assert "host engine" in sess.explain(q)


def test_row_udf_on_host(sess):
    df, t = make_df(sess)
    f2 = F.udf(lambda a: float(str(a).count("1")), returnType=T.DOUBLE)
    q = df.select(f2(df.a).alias("c"))
    assert "host engine" in sess.explain(q)
    out = [r["c"] for r in q.collect().to_pylist()]
    assert out == [1.0, 0.0, 0.0, 0.0]


def test_row_udf_null_handling(sess):
    t = pa.table({"x": pa.array([1.0, None, 3.0], type=pa.float64())})
    df = sess.create_dataframe(t)
    f = F.udf(lambda x: -1.0 if x is None else x + 1, returnType=T.DOUBLE)
    out = [r["r"] for r in df.select(f(df.x).alias("r"))
           .collect().to_pylist()]
    assert out == [2.0, -1.0, 4.0]


def test_pandas_udf(sess):
    df, t = make_df(sess)
    p1 = F.pandas_udf(lambda s: s * 10, returnType=T.DOUBLE)
    out = [r["p"] for r in df.select(p1(df.a).alias("p"))
           .collect().to_pylist()]
    assert out == [10., 20., 30., 40.]


def test_pandas_udf_two_args(sess):
    df, t = make_df(sess)
    p = F.pandas_udf(lambda a, b: a + b.cumsum() * 0, returnType=T.DOUBLE)
    out = [r["p"] for r in df.select(p(df.a, df.b).alias("p"))
           .collect().to_pylist()]
    assert out == [1., 2., 3., 4.]


def test_device_udf_traceable(sess):
    df, t = make_df(sess)

    def saxpy(xp, a, b):
        (ad, av), (bd, bv) = a, b
        return ad * 2.0 + bd, av & bv
    d1 = F.device_udf(saxpy, returnType=T.DOUBLE)
    q = df.select(d1(df.a, df.b).alias("s"))
    assert "cannot run" not in sess.explain(q)
    out = [r["s"] for r in q.collect().to_pylist()]
    assert out == [12., 24., 36., 48.]


def test_map_in_pandas(sess):
    df, t = make_df(sess)

    def mapper(it):
        for pdf in it:
            pdf = pdf.copy()
            pdf["a2"] = pdf["a"] * 100
            yield pdf[["a2"]]
    out = df.mapInPandas(mapper, "a2 double").collect().to_pylist()
    assert sorted(r["a2"] for r in out) == [100., 200., 300., 400.]


def test_apply_in_pandas_groups(sess):
    df, t = make_df(sess)

    def norm(pdf):
        pdf = pdf.copy()
        pdf["z"] = pdf["a"] - pdf["a"].mean()
        return pdf[["g", "z"]]
    out = (df.groupBy("g").applyInPandas(norm, "g long, z double")
           .orderBy("g", "z").collect().to_pylist())
    assert [r["z"] for r in out] == [-0.5, 0.5, -0.5, 0.5]


def test_apply_in_pandas_multi_partition(sess):
    rng = np.random.default_rng(5)
    n = 3000
    t = pa.table({"g": rng.integers(0, 20, n), "v": rng.random(n)})
    df = sess.create_dataframe(t, num_partitions=4)

    def stats(pdf):
        return pd.DataFrame({"g": [pdf["g"].iloc[0]],
                             "s": [pdf["v"].sum()],
                             "c": [float(len(pdf))]})
    got = (df.groupBy("g").applyInPandas(stats, "g long, s double, c double")
           .orderBy("g").collect().to_pandas())
    exp = (t.to_pandas().groupby("g")
           .agg(s=("v", "sum"), c=("v", "size")).reset_index())
    assert np.array_equal(got["g"], exp["g"])
    assert np.allclose(got["s"], exp["s"])
    assert np.array_equal(got["c"], exp["c"].astype(float))


def test_two_lambdas_one_line_not_miscompiled(sess):
    df, t = make_df(sess)
    fs = [F.udf(lambda x: x + 1.0, returnType=T.DOUBLE), F.udf(lambda x: x * 2.0, returnType=T.DOUBLE)]  # noqa: E501
    out = df.select(fs[0](df.a).alias("p"), fs[1](df.a).alias("q")) \
        .collect().to_pylist()
    assert [r["p"] for r in out] == [2.0, 3.0, 4.0, 5.0]
    assert [r["q"] for r in out] == [2.0, 4.0, 6.0, 8.0]


def test_truthy_and_or_not_compiled(sess):
    """Python and/or over non-boolean operands returns operands — must
    fall back to the host UDF, not compile to SQL booleans."""
    df, t = make_df(sess)
    f = F.udf(lambda a, b: a and b, returnType=T.DOUBLE)
    q = df.select(f(df.a, df.b).alias("r"))
    assert "host engine" in sess.explain(q)
    out = [r["r"] for r in q.collect().to_pylist()]
    assert out == [10., 20., 30., 40.]  # a is truthy -> b


def test_compiled_udf_respects_return_type(sess):
    df, t = make_df(sess)
    f = F.udf(lambda a: a > 2.0, returnType=T.DOUBLE)
    out = df.select(f(df.a).alias("r")).collect()
    import pyarrow as pa
    assert out.schema.field("r").type == pa.float64()
    assert [r["r"] for r in out.to_pylist()] == [0.0, 0.0, 1.0, 1.0]


def test_row_udf_exception_propagates(sess):
    df, t = make_df(sess)
    f = F.udf(lambda a: {}[a], returnType=T.DOUBLE)  # KeyError per row
    with pytest.raises(KeyError):
        df.select(f(df.a).alias("r")).collect()


def test_pandas_udf_wrong_length_raises(sess):
    df, t = make_df(sess)
    p = F.pandas_udf(lambda s: pd.Series([s.sum()]), returnType=T.DOUBLE)
    with pytest.raises(ValueError, match="length"):
        df.select(p(df.a).alias("r")).collect()


def test_apply_in_pandas_rejects_expression_keys(sess):
    df, t = make_df(sess)
    with pytest.raises(ValueError, match="plain columns"):
        df.groupBy(df.g + 1).applyInPandas(lambda p: p, "g long")


def test_cogroup_apply_in_pandas(sess):
    left = sess.create_dataframe(pa.table({
        "k": [1, 1, 2, 3], "v": [1.0, 2.0, 3.0, 4.0]}))
    right = sess.create_dataframe(pa.table({
        "k": [1, 2, 2, 4], "w": [10.0, 20.0, 30.0, 40.0]}))

    def summarize(l, r):
        k = l["k"].iloc[0] if len(l) else r["k"].iloc[0]
        return pd.DataFrame({"k": [k], "lv": [l["v"].sum() if len(l) else 0.0],
                             "rw": [r["w"].sum() if len(r) else 0.0]})
    got = (left.groupBy("k").cogroup(right.groupBy("k"))
           .applyInPandas(summarize, "k long, lv double, rw double")
           .orderBy("k").collect().to_pylist())
    assert got == [
        {"k": 1, "lv": 3.0, "rw": 10.0},
        {"k": 2, "lv": 3.0, "rw": 50.0},
        {"k": 3, "lv": 4.0, "rw": 0.0},
        {"k": 4, "lv": 0.0, "rw": 40.0},
    ]


def test_cogroup_multi_partition(sess):
    rng = np.random.default_rng(8)
    n = 2000
    left = sess.create_dataframe(pa.table({
        "k": rng.integers(0, 30, n), "v": rng.random(n)}),
        num_partitions=4)
    right = sess.create_dataframe(pa.table({
        "k": rng.integers(0, 30, n), "w": rng.random(n)}),
        num_partitions=3)

    def stats(l, r):
        k = l["k"].iloc[0] if len(l) else r["k"].iloc[0]
        return pd.DataFrame({"k": [k], "c": [float(len(l) + len(r))]})
    got = (left.groupBy("k").cogroup(right.groupBy("k"))
           .applyInPandas(stats, "k long, c double")
           .orderBy("k").collect().to_pandas())
    import collections
    cnt = collections.Counter(
        list(left.collect()["k"].to_pylist())
        + list(right.collect()["k"].to_pylist()))
    assert dict(zip(got["k"], got["c"])) == {
        k: float(v) for k, v in cnt.items()}


def test_cogroup_different_key_names(sess):
    left = sess.create_dataframe(pa.table({
        "a": [1, 2], "v": [1.0, 2.0]}))
    right = sess.create_dataframe(pa.table({
        "b": [2, 3], "w": [20.0, 30.0]}))

    def f(l, r):
        k = l["a"].iloc[0] if len(l) else r["b"].iloc[0]
        return pd.DataFrame({"k": [k],
                             "lv": [l["v"].sum() if len(l) else 0.0],
                             "rw": [r["w"].sum() if len(r) else 0.0]})
    got = (left.groupBy("a").cogroup(right.groupBy("b"))
           .applyInPandas(f, "k long, lv double, rw double")
           .orderBy("k").collect().to_pylist())
    assert got == [{"k": 1, "lv": 1.0, "rw": 0.0},
                   {"k": 2, "lv": 2.0, "rw": 20.0},
                   {"k": 3, "lv": 0.0, "rw": 30.0}]


def test_cogroup_empty_side_has_full_schema(sess):
    left = sess.create_dataframe(pa.table({
        "k": [1, 2, 3, 4], "v": [1.0, 2.0, 3.0, 4.0]}),
        num_partitions=2)
    right = sess.create_dataframe(pa.table({
        "k": [1], "w": [10.0]}))

    def f(l, r):
        # touching the non-key column of a possibly-empty side must work
        return pd.DataFrame({"k": [l["k"].iloc[0] if len(l)
                                   else r["k"].iloc[0]],
                             "rw": [float(r["w"].sum())]})
    got = (left.groupBy("k").cogroup(right.groupBy("k"))
           .applyInPandas(f, "k long, rw double")
           .orderBy("k").collect().to_pylist())
    assert got == [{"k": 1, "rw": 10.0}, {"k": 2, "rw": 0.0},
                   {"k": 3, "rw": 0.0}, {"k": 4, "rw": 0.0}]


# --- grouped-agg pandas UDFs (GpuAggregateInPandasExec analog) -------------

def test_grouped_agg_pandas_udf(sess):
    import pyarrow as pa
    from spark_rapids_tpu import types as T
    df = sess.create_dataframe(pa.table({
        "k": ["a", "a", "b", "b", "b"],
        "v": [1.0, 2.0, 3.0, 4.0, 5.0]}), num_partitions=2)
    wmean = F.pandas_udf(lambda s: float(s.mean()), T.DOUBLE,
                         functionType="grouped_agg")
    out = df.groupBy("k").agg(wmean(df.v).alias("m")).orderBy("k").collect()
    assert out.to_pylist() == [{"k": "a", "m": 1.5}, {"k": "b", "m": 4.0}]


def test_grouped_agg_pandas_udf_multi_arg_multi_udf(sess):
    import pyarrow as pa
    from spark_rapids_tpu import types as T
    df = sess.create_dataframe(pa.table({
        "k": [1, 1, 2, 2],
        "x": [1.0, 3.0, 10.0, 30.0],
        "w": [1.0, 3.0, 1.0, 1.0]}), num_partitions=3)
    wavg = F.pandas_udf(lambda v, w: float((v * w).sum() / w.sum()),
                        T.DOUBLE, functionType="grouped_agg")
    mx = F.pandas_udf(lambda v: float(v.max()), T.DOUBLE,
                      functionType="grouped_agg")
    out = (df.groupBy("k")
           .agg(wavg(df.x, df.w).alias("wa"), mx(df.x).alias("mx"))
           .orderBy("k").collect())
    assert out.to_pylist() == [
        {"k": 1, "wa": 2.5, "mx": 3.0}, {"k": 2, "wa": 20.0, "mx": 30.0}]


def test_grouped_agg_udf_rejects_mixing_with_builtin(sess):
    import pyarrow as pa
    import pytest as _pytest
    from spark_rapids_tpu import types as T
    df = sess.create_dataframe(pa.table({"k": [1], "v": [1.0]}))
    g = F.pandas_udf(lambda s: float(s.sum()), T.DOUBLE,
                     functionType="grouped_agg")
    with _pytest.raises(ValueError, match="mixed"):
        df.groupBy("k").agg(g(df.v).alias("a"),
                            F.sum(F.col("v")).alias("b"))


def test_grouped_agg_udf_expression_args(sess):
    """UDF arguments may be full expressions (pre-projected by the
    planner), not just plain columns."""
    import pyarrow as pa
    from spark_rapids_tpu import types as T
    df = sess.create_dataframe(pa.table({
        "k": [1, 1, 2], "v": [1.0, 2.0, 10.0]}), num_partitions=2)
    s = F.pandas_udf(lambda x: float(x.sum()), T.DOUBLE,
                     functionType="grouped_agg")
    out = (df.groupBy("k").agg(s(df.v * 2.0 + 1.0).alias("t"))
           .orderBy("k").collect())
    assert out.to_pylist() == [{"k": 1, "t": 8.0}, {"k": 2, "t": 21.0}]


def test_python_worker_semaphore_bounds_concurrency(sess):
    """Parallel user-Python sections never exceed the configured cap."""
    import pyarrow as pa
    import threading
    from spark_rapids_tpu.memory import python_worker as PW
    from spark_rapids_tpu import types as T
    PW.PythonWorkerSemaphore.shutdown()
    s = srt.session(**{"spark.rapids.python.concurrentPythonWorkers": 2})
    PW.STATS.update(acquires=0, peak=0, current=0)
    df = s.create_dataframe(pa.table({
        "k": list(range(8)), "v": [float(i) for i in range(8)]}),
        num_partitions=8)

    import time as _t
    def slow(pdf):
        _t.sleep(0.05)
        return pdf

    out = df.groupBy("k").applyInPandas(
        slow, T.StructType((T.StructField("k", T.LONG, True),
                            T.StructField("v", T.DOUBLE, True))))
    # run partitions on threads to create real concurrency
    results = []
    threads = [threading.Thread(target=lambda: results.append(
        out.collect().num_rows)) for _ in range(2)]
    for t in threads: t.start()
    for t in threads: t.join()
    assert results == [8, 8]
    # one acquire per python section (AQE may coalesce partitions, so the
    # count is per-exec-invocation, not per input partition)
    assert PW.STATS["acquires"] >= 2
    assert PW.STATS["peak"] <= 2
    PW.PythonWorkerSemaphore.shutdown()


def test_grouped_agg_udf_global_and_aliased_key(sess):
    import pyarrow as pa
    from spark_rapids_tpu import types as T
    df = sess.create_dataframe(pa.table({
        "k": [1, 1, 2], "v": [1.0, 2.0, 9.0]}), num_partitions=2)
    s = F.pandas_udf(lambda x: float(x.sum()), T.DOUBLE,
                     functionType="grouped_agg")
    # global aggregation (no keys)
    out = df.agg(s(df.v).alias("t")).collect()
    assert out.to_pylist() == [{"t": 12.0}]
    # aliased grouping key
    out2 = (df.groupBy(df.k.alias("kk")).agg(s(df.v).alias("t"))
            .orderBy("kk").collect())
    assert out2.to_pylist() == [{"kk": 1, "t": 3.0}, {"kk": 2, "t": 9.0}]


# ---------------------------------------------------------------------------
# out-of-process worker pool (python/rapids/daemon.py analog)
# ---------------------------------------------------------------------------

def test_udf_worker_crash_fails_task_not_session(sess):
    """A UDF that kills its interpreter takes down its WORKER process;
    the task fails with WorkerCrashed, and the session keeps serving
    queries afterwards."""
    import pytest as _pytest
    from spark_rapids_tpu.pyworker import STATS, WorkerCrashed
    t = pa.table({"x": [1.0, 2.0, 3.0]})
    df = sess.create_dataframe(t)

    def killer(it):
        import os
        os._exit(42)
        yield  # pragma: no cover

    crashes0 = STATS["crashes"]
    with _pytest.raises(Exception) as ei:
        df.mapInPandas(killer, T.StructType((
            T.StructField("x", T.DOUBLE, True),))).collect()
    assert isinstance(ei.value, WorkerCrashed) or \
        "worker died" in str(ei.value)
    assert STATS["crashes"] == crashes0 + 1
    # session is alive: both a plain query and a fresh UDF still work
    assert df.count() == 3
    out = df.mapInPandas(
        lambda it: (p.assign(x=p.x * 2) for p in it),
        T.StructType((T.StructField("x", T.DOUBLE, True),))
    ).collect().to_pandas()
    assert sorted(out["x"]) == [2.0, 4.0, 6.0]


def test_udf_worker_error_carries_traceback(sess):
    import pytest as _pytest
    t = pa.table({"x": [1.0]})
    df = sess.create_dataframe(t)

    def boom(it):
        raise RuntimeError("sentinel-broke-here")
        yield  # pragma: no cover

    with _pytest.raises(Exception, match="sentinel-broke-here"):
        df.mapInPandas(boom, T.StructType((
            T.StructField("x", T.DOUBLE, True),))).collect()


def test_udf_worker_print_does_not_corrupt_protocol(sess):
    t = pa.table({"x": [1.0, 2.0]})
    df = sess.create_dataframe(t)

    def chatty(it):
        for p in it:
            print("user print must go to stderr, not the frame pipe")
            yield p

    out = df.mapInPandas(chatty, T.StructType((
        T.StructField("x", T.DOUBLE, True),))).collect()
    assert out.num_rows == 2


def test_udf_worker_pool_reuse_and_gating(sess):
    """Workers are reused across jobs, and the pool never holds more
    live workers than the concurrentPythonWorkers cap."""
    from spark_rapids_tpu.pyworker import STATS, PythonWorkerPool
    t = pa.table({"x": [1.0, 2.0]})
    df = sess.create_dataframe(t)
    schema = T.StructType((T.StructField("x", T.DOUBLE, True),))
    spawned0 = STATS["spawned"]
    for _ in range(3):
        df.mapInPandas(lambda it: it, schema).collect()
    assert STATS["spawned"] - spawned0 <= 1, "workers were not reused"
    pool = PythonWorkerPool.get(sess._conf)
    assert STATS["peak_workers"] <= pool.capacity


def test_udf_in_process_kill_switch(sess):
    """worker.isolated=false restores the in-process path (object
    identity survives, no Arrow round-trip)."""
    sess.conf.set("spark.rapids.python.worker.isolated", False)
    try:
        from spark_rapids_tpu.pyworker import STATS
        jobs0 = STATS["jobs"]
        t = pa.table({"x": [1.0]})
        df = sess.create_dataframe(t)
        out = df.mapInPandas(
            lambda it: (p for p in it),
            T.StructType((T.StructField("x", T.DOUBLE, True),))
        ).collect()
        assert out.num_rows == 1
        assert STATS["jobs"] == jobs0  # pool untouched
    finally:
        sess.conf.set("spark.rapids.python.worker.isolated", True)


def test_udf_worker_reraises_original_exception_type(sess):
    """User exceptions cross the worker boundary with their ORIGINAL
    type (picklable case), so `except ValueError:` written against the
    in-process path keeps working — and the worker survives user errors
    (no respawn per exception)."""
    import pytest as _pytest
    from spark_rapids_tpu.pyworker import STATS
    t = pa.table({"x": [1.0]})
    df = sess.create_dataframe(t)
    schema = T.StructType((T.StructField("x", T.DOUBLE, True),))

    def raiser(it):
        raise ValueError("typed-error-sentinel")
        yield  # pragma: no cover

    df.mapInPandas(lambda it: it, schema).collect()  # warm a worker
    spawned0 = STATS["spawned"]
    with _pytest.raises(ValueError, match="typed-error-sentinel"):
        df.mapInPandas(raiser, schema).collect()
    df.mapInPandas(lambda it: it, schema).collect()
    assert STATS["spawned"] == spawned0, "user error must not kill worker"


def test_apply_in_pandas_group_gets_range_index(sess):
    """PySpark contract: each applyInPandas group arrives with a fresh
    RangeIndex (g.loc[0] works for every group) — review r4 finding."""
    t = pa.table({"k": [1, 1, 2, 2, 2], "v": [1.0, 2.0, 3.0, 4.0, 5.0]})
    df = sess.create_dataframe(t)

    def first_row(g):
        return g.loc[[0]]  # KeyError unless the index was reset

    out = (df.groupBy("k").applyInPandas(first_row, T.StructType((
        T.StructField("k", T.LONG, True),
        T.StructField("v", T.DOUBLE, True))))
        .collect().to_pandas().sort_values("k"))
    assert len(out) == 2
