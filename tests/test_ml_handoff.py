"""ML handoff (ColumnarRdd / InternalColumnarRddConverter analog +
BASELINE milestone 5's ml-integration path): a query's device output
flows zero-copy into jax training."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import ml
from spark_rapids_tpu.sql import functions as F


@pytest.fixture()
def sess():
    return srt.session()


def test_columnar_rdd_returns_device_batches(sess):
    import jax
    df = sess.create_dataframe(pa.table({
        "a": [1.0, 2.0, 3.0], "b": [4.0, 5.0, 6.0]}), num_partitions=2)
    batches = ml.columnar_rdd(df.select((df.a * 2).alias("a2"), df.b))
    assert sum(b.num_rows_int for b in batches) == 3
    for b in batches:
        for c in b.columns:
            assert isinstance(c.data, jax.Array)  # device-resident
    vals = sorted(v for b in batches
                  for v in np.asarray(b.columns[0].data[:b.num_rows_int])
                  .tolist())
    assert vals == [2.0, 4.0, 6.0]


def test_columnar_rdd_rejects_host_plans(sess):
    s = srt.session(**{"spark.rapids.sql.enabled": False})
    try:
        df = s.create_dataframe(pa.table({"a": [1.0]}))
        with pytest.raises(ValueError, match="device"):
            ml.columnar_rdd(df.select((df.a + 1).alias("b")))
    finally:
        srt.session(**{"spark.rapids.sql.enabled": True})


def test_to_features_shapes_and_values(sess):
    df = sess.create_dataframe(pa.table({
        "x1": [1.0, 2.0, 3.0, 4.0], "x2": [0.5, 1.5, 2.5, 3.5],
        "y": [1.0, 0.0, 1.0, 0.0]}), num_partitions=2)
    X, y = ml.to_features(df, ["x1", "x2"], "y")
    assert X.shape == (4, 2) and y.shape == (4,)
    assert sorted(np.asarray(X[:, 0]).tolist()) == [1.0, 2.0, 3.0, 4.0]


def test_end_to_end_training_on_engine_output(sess):
    """Engine query -> zero-copy features -> jax gradient descent learns
    the planted linear relationship."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    n = 4000
    x1 = rng.random(n); x2 = rng.random(n)
    noise = rng.normal(0, 0.01, n)
    t = pa.table({"x1": x1, "x2": x2,
                  "target": 3.0 * x1 - 2.0 * x2 + 0.5 + noise,
                  "grp": rng.integers(0, 4, n)})
    df = sess.create_dataframe(t, num_partitions=4)
    # feature engineering THROUGH the engine, then handoff
    feats = df.filter(df.grp >= 0).select(
        df.x1, df.x2, (df.x1 * df.x2).alias("x1x2"), df.target)
    X, y = ml.to_features(feats, ["x1", "x2", "x1x2"], "target")
    Xb = jnp.concatenate([X, jnp.ones((X.shape[0], 1), X.dtype)], axis=1)

    def loss(w):
        return jnp.mean((Xb @ w - y) ** 2)

    g = jax.jit(jax.grad(loss))
    w = jnp.zeros(4, X.dtype)
    for _ in range(800):
        w = w - 0.5 * g(w)
    w = np.asarray(w)
    assert abs(w[0] - 3.0) < 0.1, w
    assert abs(w[1] + 2.0) < 0.1, w
    assert abs(w[2]) < 0.2, w
    assert abs(w[3] - 0.5) < 0.15, w


def test_to_features_rejects_nulls(sess):
    df = sess.create_dataframe(pa.table({
        "x": pa.array([1.0, None, 3.0], type=pa.float64()),
        "y": [1.0, 2.0, 3.0]}))
    with pytest.raises(ValueError, match="NULL"):
        ml.to_features(df, ["x"], "y")
    # filtering the nulls in the query makes it fine
    X, y = ml.to_features(df.filter(df.x.isNotNull()), ["x"], "y")
    assert X.shape == (2, 1)


def test_to_features_rejects_string_label(sess):
    df = sess.create_dataframe(pa.table({"x": [1.0], "s": ["a"]}))
    with pytest.raises(ValueError, match="not numeric"):
        ml.to_features(df, ["x"], "s")


def test_to_torch_handoff(sess):
    import torch
    rng = np.random.default_rng(4)
    t = pa.table({"a": rng.random(200), "b": rng.random(200),
                  "y": rng.random(200)})
    df = sess.create_dataframe(t)
    X, y = ml.to_torch(df, ["a", "b"], "y")
    assert isinstance(X, torch.Tensor) and X.shape == (200, 2)
    assert isinstance(y, torch.Tensor) and y.shape == (200,)
    assert np.allclose(X[:, 0].numpy(), t["a"].to_numpy().astype(np.float32))


def test_minibatch_iterator_shuffles_per_epoch(sess):
    rng = np.random.default_rng(5)
    t = pa.table({"a": rng.random(64), "y": rng.random(64)})
    df = sess.create_dataframe(t)
    batches = list(ml.minibatches(df, ["a"], "y", batch_size=16, epochs=2))
    assert len(batches) == 8  # 4 per epoch x 2 epochs
    assert all(x.shape == (16, 1) and yy.shape == (16,)
               for x, yy in batches)
    e1 = np.concatenate([np.asarray(yy) for _, yy in batches[:4]])
    e2 = np.concatenate([np.asarray(yy) for _, yy in batches[4:]])
    assert sorted(e1.tolist()) == sorted(e2.tolist())  # same data...
    assert not np.array_equal(e1, e2)  # ...different order per epoch


def test_fit_linear_regression_recovers_weights(sess):
    rng = np.random.default_rng(6)
    n = 2000
    a = rng.random(n).astype(np.float32)
    b = rng.random(n).astype(np.float32)
    y = 3.0 * a - 2.0 * b + 0.5
    t = pa.table({"a": a, "b": b, "y": y})
    # ETL in the engine (filter keeps it a real query), training on device
    df = sess.create_dataframe(t).filter(F.col("a") >= 0.0)
    w, bias, mse = ml.fit_linear_regression(df, ["a", "b"], "y",
                                            steps=400, lr=0.3)
    assert mse < 1e-3
    assert abs(float(w[0]) - 3.0) < 0.05
    assert abs(float(w[1]) + 2.0) < 0.05
    assert abs(float(bias) - 0.5) < 0.05


def test_gradient_boosting_multi_batch_device_resident(sess):
    """BASELINE config 5 depth: a GBT-shaped model
    trains on MULTI-BATCH engine output with the training data resident
    on device throughout, and actually fits a nonlinear target a linear
    model cannot."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu import ml
    rng = np.random.default_rng(5)
    n = 6000
    x1, x2 = rng.random(n) * 4 - 2, rng.random(n) * 4 - 2
    # nonlinear, axis-aligned target: ideal for trees, hopeless for OLS
    y = np.where((x1 > 0) ^ (x2 > 0.5), 3.0, -1.0) + rng.normal(0, .05, n)
    t = pa.table({"x1": x1, "x2": x2, "y": y})
    df = sess.create_dataframe(t, num_partitions=4)  # multi-batch input
    q = df.filter(df.x1 > -10)  # through the engine, stays on device
    from spark_rapids_tpu.ml import columnar_rdd
    assert len(columnar_rdd(q.select("x1", "x2", "y"))) > 1, \
        "input must arrive as multiple device batches"
    X, yv = ml.to_features(q, ["x1", "x2"], "y")
    assert isinstance(X, jax.Array)  # device residency of training data
    predict, model, mse = ml.fit_gradient_boosting(
        q, ["x1", "x2"], "y", n_trees=25, max_depth=3)
    var = float(jnp.var(yv))
    assert mse < 0.15 * var, (mse, var)   # fits the XOR-ish structure
    _w, _b, lin_mse = ml.fit_linear_regression(q, ["x1", "x2"], "y")
    assert mse < 0.25 * lin_mse, (mse, lin_mse)  # beats linear soundly
    # jitted inference on fresh device data
    Xq = jnp.stack([jnp.asarray([1.0, -1.0]),
                    jnp.asarray([-1.5, 1.0])], axis=1).T
    preds = np.asarray(predict(jnp.asarray(Xq)))
    assert preds.shape == (2,)


def test_to_features_sharded_multichip(sess):
    """Partitioned handoff: (X, y) come back row-sharded over the
    virtual 8-device mesh, ready for pjit training with no resharding."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu import ml
    from spark_rapids_tpu.parallel.mesh import device_mesh
    if len(jax.devices()) < 2:
        import pytest as _p
        _p.skip("needs the multi-device CPU mesh")
    rng = np.random.default_rng(6)
    n = 1001  # deliberately NOT divisible by the device count
    t = pa.table({"a": rng.random(n), "b": rng.random(n),
                  "y": rng.random(n)})
    df = sess.create_dataframe(t, num_partitions=3)
    X, y, live = ml.to_features_sharded(df, ["a", "b"], "y")
    mesh = device_mesh()
    n_dev = mesh.devices.size
    assert live == n and X.shape[0] % n_dev == 0
    assert len(X.sharding.device_set) == n_dev  # genuinely row-sharded
    assert len(y.sharding.device_set) == n_dev
    # a sharded reduction consumes it without host gather
    mask = jnp.arange(X.shape[0]) < live
    tot = float(jnp.sum(jnp.where(mask, y, 0.0)))
    exp = float(np.sum(t["y"].to_numpy()))
    # float32 feature dtype: tolerance scales with the magnitude
    assert abs(tot - exp) < 1e-4 * max(abs(exp), 1.0)
