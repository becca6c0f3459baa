"""Decimal128 end-to-end aggregation.

The reference aggregates decimal(19-38) on device via
``Aggregation128Utils`` chunked-int32 extraction
(``AggregateFunctions.scala:902``); this engine's analog lives in
``ops/decimal128.py`` (chunked int64 XLA programs) and is wired into
Sum/Average, string casts, and MakeDecimal.  Every test here checks
against exact Python ``decimal`` arithmetic — an independent oracle."""

import decimal
from decimal import Decimal as D

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.sql import functions as F

decimal.getcontext().prec = 80


@pytest.fixture(scope="module")
def sess():
    return srt.session()


def _rand_decimals(rng, n, precision, scale, null_rate=0.1):
    digits = precision
    vals = []
    for _ in range(n):
        if rng.random() < null_rate:
            vals.append(None)
            continue
        ndig = int(rng.integers(1, digits + 1))
        mag = int("".join(rng.choice(list("0123456789"), ndig)) or "0")
        if mag > 10 ** precision - 1:
            mag = mag % (10 ** precision)
        sign = -1 if rng.random() < 0.5 else 1
        vals.append(D(sign * mag).scaleb(-scale))
    return vals


@pytest.mark.parametrize("precision,scale", [(20, 2), (30, 6), (38, 10)])
def test_sum_avg_vs_python_decimal(sess, precision, scale):
    rng = np.random.default_rng(precision)
    n, n_keys = 4000, 37
    vals = _rand_decimals(rng, n, precision - 2, scale)
    keys = rng.integers(0, n_keys, n)
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "d": pa.array(vals, type=pa.decimal128(precision, scale))})
    df = sess.create_dataframe(t, num_partitions=4)
    got = (df.groupBy("k")
           .agg(F.sum(F.col("d")).alias("s"), F.avg(F.col("d")).alias("a"),
                F.count(F.col("d")).alias("c"))
           .orderBy("k").collect().to_pylist())
    by_key = {}
    for k, v in zip(keys, vals):
        if v is not None:
            by_key.setdefault(int(k), []).append(v)
    sum_prec = min(precision + 10, 38)
    for row in got:
        grp = by_key.get(row["k"], [])
        if not grp:
            assert row["s"] is None and row["a"] is None
            continue
        exp_sum = sum(grp)
        if abs(int(exp_sum.scaleb(scale))) > 10 ** sum_prec - 1:
            exp_sum = None  # overflows the sum's decimal type -> null
        assert row["s"] == exp_sum, (row["k"], row["s"], exp_sum)
        q = D(1).scaleb(-(scale + 4))
        exp_avg = (sum(grp) / len(grp)).quantize(
            q, rounding=decimal.ROUND_HALF_UP)
        if abs(int(exp_avg.scaleb(scale + 4))) > 10 ** 38 - 1:
            exp_avg = None  # result precision capped at 38 -> null
        assert row["a"] == exp_avg, (row["k"], row["a"], exp_avg)
        assert row["c"] == len(grp)


def test_two_phase_shuffled_aggregation(sess):
    """Partial buffers (the four chunk sums) must merge exactly across a
    real shuffle — the distributed two-phase path, not the fused
    complete-mode one."""
    rng = np.random.default_rng(7)
    n = 6000
    vals = _rand_decimals(rng, n, 24, 3, null_rate=0.05)
    keys = rng.integers(0, 500, n)
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "d": pa.array(vals, type=pa.decimal128(26, 3))})
    df = sess.create_dataframe(t, num_partitions=5)
    got = (df.repartition(5, "k").groupBy("k")
           .agg(F.sum(F.col("d")).alias("s"))
           .orderBy("k").collect().to_pandas())
    by_key = {}
    for k, v in zip(keys, vals):
        if v is not None:
            by_key.setdefault(int(k), D(0))
            by_key[int(k)] += v
    for _, row in got.iterrows():
        exp = by_key.get(int(row["k"]))
        if exp is None:
            assert row["s"] is None
        else:
            assert row["s"] == exp, (row["k"], row["s"], exp)


def test_sum_overflow_nulls_group(sess):
    vals = [D("9" * 37).scaleb(-2)] * 50   # 50 * ~1e35 > 10^38-1? no:
    # 50 * (10^37-1) ~ 5e38 > 10^38-1 -> overflow
    t = pa.table({"k": pa.array([1] * 50, type=pa.int64()),
                  "d": pa.array(vals, type=pa.decimal128(38, 2))})
    got = (sess.create_dataframe(t).groupBy("k")
           .agg(F.sum(F.col("d")).alias("s")).collect().to_pylist())
    assert got[0]["s"] is None


def test_long_backed_input_dec128_result(sess):
    """sum(decimal(12,2)) -> decimal(22,2): long-backed input must
    sign-extend into the high word before chunking."""
    rng = np.random.default_rng(3)
    vals = [D(int(rng.integers(-10**11, 10**11))).scaleb(-2)
            for _ in range(3000)]
    keys = rng.integers(0, 11, 3000)
    t = pa.table({"k": pa.array(keys, type=pa.int64()),
                  "d": pa.array(vals, type=pa.decimal128(12, 2))})
    got = (sess.create_dataframe(t, num_partitions=3).groupBy("k")
           .agg(F.sum(F.col("d")).alias("s")).orderBy("k")
           .collect().to_pylist())
    for row in got:
        exp = sum(v for k, v in zip(keys, vals) if int(k) == row["k"])
        assert row["s"] == exp


def test_cast_string_to_decimal128_fuzz(sess):
    rng = np.random.default_rng(9)
    strs = []
    for _ in range(2000):
        ndig = int(rng.integers(1, 40))
        mag = "".join(rng.choice(list("0123456789"), ndig))
        dot = int(rng.integers(0, len(mag) + 1))
        s = (mag[:dot] + "." + mag[dot:]) if dot < len(mag) else mag
        if rng.random() < 0.5:
            s = "-" + s
        if rng.random() < 0.2:
            s = s + f"e{int(rng.integers(-10, 10))}"
        strs.append(s)
    strs += ["", " ", ".", "1..2", "++1", "1e", None, "0", "-0.0"]
    t = pa.table({"s": pa.array(strs, type=pa.string())})
    df = sess.create_dataframe(t, num_partitions=2)
    q = df.select(F.col("s").cast(T.DecimalType(38, 6)).alias("d"))
    assert "cannot run" not in sess.explain(q)
    got = [r["d"] for r in q.collect().to_pylist()]
    for s, g in zip(strs, got):
        if s is None:
            assert g is None
            continue
        try:
            v = D(s.strip())
        except decimal.InvalidOperation:
            assert g is None, (s, g)
            continue
        u = int(v.scaleb(6).quantize(0, rounding=decimal.ROUND_HALF_UP))
        exp = D(u).scaleb(-6) if abs(u) <= 10 ** 38 - 1 else None
        assert g == exp, (s, g, exp)


def test_unscaled_value_still_rejects_dec128(sess):
    """UnscaledValue returns LONG by contract; decimal128 cannot fit —
    the device must keep rejecting it (it would truncate), like the
    reference where only long-backed decimals reach GpuUnscaledValue."""
    from spark_rapids_tpu.sql.expressions.arithmetic import UnscaledValue
    from spark_rapids_tpu.sql import functions as F2
    t = pa.table({"d": pa.array([D("1.23")], type=pa.decimal128(25, 2))})
    df = sess.create_dataframe(t)
    col = df._col("d")
    expr = UnscaledValue(col.expr)
    assert expr.tag_for_device() is not None


def test_make_decimal_128(sess):
    from spark_rapids_tpu.sql.expressions.arithmetic import MakeDecimal
    from spark_rapids_tpu.sql.dataframe import Column
    rng = np.random.default_rng(4)
    raw = [int(x) for x in rng.integers(-10**18, 10**18, 500)]
    t = pa.table({"v": pa.array(raw, type=pa.int64())})
    df = sess.create_dataframe(t)
    out = df.select(Column(MakeDecimal(df._col("v").expr, 28, 4))
                    .alias("d")).collect().to_pylist()
    for r, row in zip(raw, out):
        assert row["d"] == D(r).scaleb(-4)


def test_arithmetic_dec128_vs_python(sess):
    """+/-/* run on device with chunked 128-bit kernels; / falls to the
    host's exact Python-int path — all checked against decimal."""
    rng = np.random.default_rng(11)
    n = 1500
    a_vals = [D(int(rng.integers(-10**15, 10**15))
               * int(rng.integers(1, 10**7))).scaleb(-2) for _ in range(n)]
    b_vals = [D(int(rng.integers(-10**15, 10**15))
               * int(rng.integers(1, 10**7)) + 1).scaleb(-2)
              for _ in range(n)]
    t = pa.table({"a": pa.array(a_vals, type=pa.decimal128(25, 2)),
                  "b": pa.array(b_vals, type=pa.decimal128(25, 2))})
    df = sess.create_dataframe(t, num_partitions=2)
    got = df.select((df.a + df.b).alias("s"), (df.a - df.b).alias("d"),
                    (df.a * df.b).alias("m")).collect().to_pylist()
    for row, x, y in zip(got, a_vals, b_vals):
        assert row["s"] == x + y
        assert row["d"] == x - y
        p = x * y  # result decimal(38, 4): overflow -> null
        exp = p if abs(int(p.scaleb(4))) <= 10 ** 38 - 1 else None
        assert row["m"] == exp, (x, y, row["m"], exp)


def test_divide_dec128_host_exact(sess):
    a, b = D("12345678901234567890123.45"), D("98765432109876543210987.65")
    t = pa.table({"a": pa.array([a], type=pa.decimal128(25, 2)),
                  "b": pa.array([b], type=pa.decimal128(25, 2))})
    df = sess.create_dataframe(t)
    q = df.select((df.a / df.b).alias("r"))
    assert "cannot run" in sess.explain(q)  # tagged to the host path
    got = q.collect().to_pylist()[0]["r"]
    scale = got.as_tuple().exponent * -1
    exp = (a / b).quantize(D(1).scaleb(-scale),
                           rounding=decimal.ROUND_HALF_UP)
    assert got == exp


def test_shuffled_group_by_dec128_key(sess):
    """Hash partitioning over a decimal128 key (murmur3/xxhash64 over the
    minimal two's-complement bytes, like Spark's BigInteger.toByteArray
    path) — previously raised NotImplementedError."""
    k1, k2 = D("1" + "0" * 20 + ".00"), D("-2.00")
    t = pa.table({"k": pa.array([k1] * 300 + [k2] * 200,
                                type=pa.decimal128(25, 2)),
                  "v": np.arange(500, dtype=np.float64)})
    df = sess.create_dataframe(t, num_partitions=4).repartition(4, "k")
    got = df.groupBy("k").agg(F.count("*").alias("c")).collect().to_pylist()
    assert sorted((str(r["k"]), r["c"]) for r in got) == \
        [("-2.00", 200), (str(k1), 300)]


def test_dec128_hash_byte_matrix_minimal():
    """The device byte-matrix equals Python's minimal signed to_bytes
    (== Java BigInteger.toByteArray) for 500+ random + edge values."""
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.sql.expressions.hashing import _dec128_byte_matrix
    rng = np.random.default_rng(0)
    vals = [0, -1, 1, 127, 128, -128, -129, 255, 10**20, -10**20,
            10**37, -(10**37), 2**64, -(2**64), 2**95 + 12345]
    vals += [int(rng.integers(-2**62, 2**62)) * int(rng.integers(1, 2**60))
             for _ in range(500)]

    def words(v):
        u = v & ((1 << 128) - 1)
        lo, hi = u & ((1 << 64) - 1), u >> 64
        return (lo - (1 << 64) if lo >= (1 << 63) else lo,
                hi - (1 << 64) if hi >= (1 << 63) else hi)

    lo = np.array([words(v)[0] for v in vals], dtype=np.int64)
    hi = np.array([words(v)[1] for v in vals], dtype=np.int64)
    col = DeviceColumn(T.DecimalType(38, 0), lo,
                       np.ones(len(vals), bool), aux=hi)
    chars, lengths = _dec128_byte_matrix(np, col)
    for i, v in enumerate(vals):
        n = max((v.bit_length() // 8) + 1, 1) if v >= 0 \
            else ((v + 1).bit_length() // 8) + 1
        assert bytes(chars[i, :lengths[i]]) == v.to_bytes(n, "big",
                                                          signed=True), v
