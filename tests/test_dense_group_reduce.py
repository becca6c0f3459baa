"""The dense form of the group-table reductions (``ops/segmented.py``,
``dense=True``) against the scatter form, on the CPU backend.

On the chip ``groupby_reduce`` reduces into a table of at most
``_DENSE_MAX_GROUPS`` rows densely — ``reduce(where(rank == g, x,
identity))`` — and scatters into a larger one; XLA CPU always scatters, so no
other CPU test executes the dense branch.  Here the platform gate
(``_use_batched_reduce``) is steered on from the test, exactly as
``tests/test_kernel_cache.py`` steers the batched reduce, and every case runs
the same slots through both forms: integers, counts and validity bit-equal,
floats within 1e-12 relative.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.sql.expressions import aggregates as AG
from spark_rapids_tpu.sql.expressions.core import BoundReference, EvalContext
from spark_rapids_tpu.sql.physical import aggregate as agg_mod
from spark_rapids_tpu.sql.physical.kernel_cache import clear_cache

CROSSOVER = agg_mod._DENSE_MAX_GROUPS
CAP = 640
FUNCS = {"SUM": AG.Sum, "COUNT": AG.Count, "MIN": AG.Min, "MAX": AG.Max,
         "AVG": AG.Average}
DTYPES = {"float64": T.DOUBLE, "float32": T.FLOAT, "int64": T.LONG,
          "int32": T.INT}


def _as_on_the_chip(xp):
    return xp.__name__ != "numpy"


def _inputs(dtype_name: str, out: int, seed: int, empty: bool = False):
    """A batch of CAP rows: live rows spread over min(out, 37) groups with a
    fifth of the values null, group 1 all null, non-finite floats in groups
    2-4, dead rows parked at ``CAP - 1`` and at -1, and a few live rows past
    the table (a mis-speculated batch: both forms must drop them)."""
    rng = np.random.default_rng(seed)
    n_groups = min(out, 37)
    np_dt = DTYPES[dtype_name].np_dtype
    if np.dtype(np_dt).kind == "f":
        data = ((rng.random(CAP) - 0.3) * 1e4).astype(np_dt)
    else:
        lim = 1 << (40 if np_dt == np.int64 else 20)
        data = rng.integers(-lim, lim, CAP).astype(np_dt)
    rank = rng.integers(0, n_groups, CAP).astype(np.int64)
    live = rng.random(CAP) < 0.85
    valid = rng.random(CAP) >= 0.2
    if n_groups > 1:
        valid[rank == 1] = False
    if np.dtype(np_dt).kind == "f":
        for g, bad in ((2, np.inf), (3, -np.inf), (4, np.nan)):
            rows = np.flatnonzero((rank == g) & live & valid)
            if n_groups > g and rows.size:
                data[rows[0]] = bad
        if n_groups == 1:
            data[np.flatnonzero(live & valid)[0]] = np.inf
    if empty:
        live[:] = False
    dead = np.flatnonzero(~live)
    rank[dead[::2]] = CAP - 1
    rank[dead[1::2]] = -1
    if not empty:
        rank[np.flatnonzero(live)[-3:]] = out + 3
    ng = np.int32(max(int(rank[live].max()) + 1 if live.any() else 0, 1))
    return data, valid, rank, live, ng


def _reduce(func_name, dtype_name, out, data, valid, rank, live, ng):
    dt = DTYPES[dtype_name]
    col = DeviceColumn(dt, jnp.asarray(data), jnp.asarray(valid))
    key = DeviceColumn(T.LONG, jnp.asarray(rank), jnp.ones(CAP, dtype=bool))
    batch = ColumnarBatch(("k", "v"), (key, col),
                          jnp.asarray(CAP, dtype=jnp.int32))
    ctx = EvalContext(batch, xp=jnp)
    func = FUNCS[func_name](BoundReference(1, dt, True))
    pairs = func.update_values(ctx, [col])
    ops = [s.op for s in func.slots()]

    def reduce(r):
        return agg_mod.groupby_reduce(
            jnp, [key], pairs, ops, jnp.asarray(live), rank64=r,
            n_groups=ng, out_size=out)

    traced = jax.make_jaxpr(
        lambda r: [s.data for s in reduce(r)[1]])(jnp.asarray(rank))
    keys, slots, _ = reduce(jnp.asarray(rank))
    res = func.evaluate(ctx, slots)
    return ("scatter" in str(traced),
            [(np.asarray(s.data), np.asarray(s.validity)) for s in slots],
            (np.asarray(res.data), np.asarray(res.validity)),
            (np.asarray(keys[0].data), np.asarray(keys[0].validity)))


def _same(a, b, what):
    (da, va), (db, vb) = a, b
    assert np.array_equal(va, vb), f"{what}: validity differs"
    da, db = da[va], db[va]
    if da.dtype.kind == "f":
        np.testing.assert_allclose(da, db, rtol=1e-12, atol=0,
                                   equal_nan=True, err_msg=what)
    else:
        assert np.array_equal(da, db), f"{what}: values differ"


CASES = [(f, d, o, False) for f in FUNCS for d in DTYPES
         for o in (1, 8, 64, CROSSOVER, CROSSOVER + 1)]
# zero live rows under a global aggregate: one row, null (count 0)
CASES += [(f, d, 1, True) for f in FUNCS for d in ("float64", "int64")]


@pytest.mark.parametrize("func,dtype,out,empty", CASES)
def test_dense_matches_scatter(monkeypatch, func, dtype, out, empty):
    seed = 1000 * out + 10 * list(FUNCS).index(func) + \
        list(DTYPES).index(dtype)
    inputs = _inputs(dtype, out, seed, empty)
    with_scatter, slots0, res0, keys0 = _reduce(func, dtype, out, *inputs)
    assert with_scatter, "XLA CPU keeps its scatters"
    monkeypatch.setattr(agg_mod, "_use_batched_reduce", _as_on_the_chip)
    with_scatter, slots1, res1, keys1 = _reduce(func, dtype, out, *inputs)
    # at or under the crossover no reduction of the program is a scatter;
    # one row above it every one still is
    assert with_scatter == (out > CROSSOVER)
    for i, (a, b) in enumerate(zip(slots0, slots1)):
        _same(a, b, f"slot {i}")
    _same(res0, res1, "result")
    _same(keys0, keys1, "group keys (first_idx)")
    if empty:
        data, validity = res1
        assert validity.shape == (1,)
        assert (int(data[0]) == 0 and validity[0]) if func == "COUNT" \
            else not validity[0]


def test_session_counts_batches_by_form_and_sizes_small_tables(monkeypatch):
    """Through the exec: the counters name the form each input batch was
    reduced in, the dense form sizes a grouped table from 8 rows and a
    global one to 1, and the answers do not move."""
    import pyarrow as pa

    from spark_rapids_tpu.sql import functions as F
    rng = np.random.default_rng(30)
    n = 6000
    sess = srt.session()
    df = sess.create_dataframe(pa.table({
        "k": rng.integers(0, 5, n).astype(np.int64),
        "v": rng.random(n) * 1e5,
        "i": rng.integers(-1 << 40, 1 << 40, n).astype(np.int64)}),
        num_partitions=2)
    grouped = (df.groupBy("k")
               .agg(F.sum(F.col("v")).alias("sv"), F.avg(F.col("v")).alias("av"),
                    F.sum(F.col("i")).alias("si"), F.min(F.col("i")).alias("mi"),
                    F.max(F.col("v")).alias("mv"), F.count("*").alias("c"))
               .orderBy("k"))
    whole = df.filter(df.v < 0).agg(F.sum(F.col("v")).alias("sv"),
                                    F.count("*").alias("c"))

    def run(q):
        clear_cache()
        try:
            rows = q.collect().to_pylist()
        finally:
            clear_cache()
        m = dict(sess.last_query_metrics)
        return rows, (m.get("aggDenseReduceBatches", 0),
                      m.get("aggScatterReduceBatches", 0))

    base, forms = run(grouped)
    assert forms == (0, 2)
    base_whole, forms = run(whole)
    assert forms == (0, 2) and base_whole == [{"sv": None, "c": 0}]
    assert agg_mod.group_table_floor(jnp, True) == 64

    monkeypatch.setattr(agg_mod, "_use_batched_reduce", _as_on_the_chip)
    assert agg_mod.group_table_floor(jnp, True) == 8
    assert agg_mod.group_table_floor(jnp, False) == 1
    dense, forms = run(grouped)
    assert forms == (2, 0)
    for a, b in zip(base, dense):
        assert (a["k"], a["si"], a["mi"], a["mv"], a["c"]) == \
            (b["k"], b["si"], b["mi"], b["mv"], b["c"])
        assert a["sv"] == pytest.approx(b["sv"], rel=1e-12)
        assert a["av"] == pytest.approx(b["av"], rel=1e-12)
    dense_whole, forms = run(whole)
    assert forms == (2, 0) and dense_whole == base_whole
