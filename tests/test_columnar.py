"""Round-trip and layout tests for the columnar batch model."""

import datetime
import decimal

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar import (ColumnarBatch, arrow_to_device,
                                       bucket_capacity, bucket_width,
                                       device_to_arrow, scalar_column)


def roundtrip(table: pa.Table) -> pa.Table:
    return device_to_arrow(arrow_to_device(table))


def assert_tables_equal(a: pa.Table, b: pa.Table):
    assert a.schema.names == b.schema.names
    for name in a.schema.names:
        ca, cb = a.column(name), b.column(name)
        assert ca.to_pylist() == cb.to_pylist(), name


def test_bucketing():
    assert bucket_capacity(0) == 8
    assert bucket_capacity(8) == 8
    assert bucket_capacity(9) == 16
    assert bucket_capacity(1000) == 1024
    assert bucket_width(0) == 4
    assert bucket_width(5) == 8


def test_fixed_width_roundtrip():
    t = pa.table({
        "i32": pa.array([1, 2, None, -4], type=pa.int32()),
        "i64": pa.array([10, None, 30, 40], type=pa.int64()),
        "f64": pa.array([1.5, None, float("nan"), -0.0]),
        "b": pa.array([True, False, None, True]),
        "i8": pa.array([1, -1, None, 127], type=pa.int8()),
    })
    out = roundtrip(t)
    assert out.column("i32").to_pylist() == [1, 2, None, -4]
    assert out.column("i64").to_pylist() == [10, None, 30, 40]
    assert out.column("b").to_pylist() == [True, False, None, True]
    f = out.column("f64").to_pylist()
    assert f[0] == 1.5 and f[1] is None and np.isnan(f[2]) and f[3] == -0.0


def test_string_roundtrip():
    vals = ["hello", "", None, "日本語テキスト", "x" * 100]
    t = pa.table({"s": pa.array(vals)})
    out = roundtrip(t)
    assert out.column("s").to_pylist() == vals


def test_binary_roundtrip():
    vals = [b"\x00\x01", b"", None, b"abcdef"]
    t = pa.table({"b": pa.array(vals, type=pa.binary())})
    out = roundtrip(t)
    assert out.column("b").to_pylist() == vals


def test_date_timestamp_roundtrip():
    d = [datetime.date(2020, 1, 1), None, datetime.date(1969, 12, 31)]
    ts = [datetime.datetime(2021, 6, 1, 12, 30, 15, 123456,
                            tzinfo=datetime.timezone.utc), None,
          datetime.datetime(1960, 1, 1, tzinfo=datetime.timezone.utc)]
    t = pa.table({"d": pa.array(d, type=pa.date32()),
                  "ts": pa.array(ts, type=pa.timestamp("us", tz="UTC"))})
    out = roundtrip(t)
    assert out.column("d").to_pylist() == d
    assert out.column("ts").to_pylist() == ts


def test_decimal_roundtrip():
    vals = [decimal.Decimal("123.45"), None, decimal.Decimal("-0.01"),
            decimal.Decimal("99999999.99")]
    t = pa.table({"dec": pa.array(vals, type=pa.decimal128(10, 2))})
    out = roundtrip(t)
    assert out.column("dec").to_pylist() == vals


def test_decimal128_roundtrip():
    vals = [decimal.Decimal("12345678901234567890123.456"), None,
            decimal.Decimal("-98765432109876543210.999")]
    t = pa.table({"dec": pa.array(vals, type=pa.decimal128(30, 3))})
    out = roundtrip(t)
    assert out.column("dec").to_pylist() == vals


def test_struct_roundtrip():
    vals = [{"a": 1, "b": "x"}, None, {"a": None, "b": "z"}]
    t = pa.table({"st": pa.array(vals, type=pa.struct(
        [("a", pa.int64()), ("b", pa.string())]))})
    out = roundtrip(t)
    assert out.column("st").to_pylist() == vals


def test_slice_and_concat():
    t = pa.table({"x": pa.array(range(100), type=pa.int64()),
                  "s": pa.array([f"v{i}" for i in range(100)])})
    b = arrow_to_device(t)
    s1 = b.sliced(0, 40)
    s2 = b.sliced(40, 60)
    assert s1.num_rows_int == 40 and s2.num_rows_int == 60
    cat = ColumnarBatch.concat([s1, s2])
    assert_tables_equal(device_to_arrow(cat), t)


def test_scalar_column():
    c = scalar_column(__import__("spark_rapids_tpu").STRING, "abc", 16)
    assert c.capacity == 16
    import spark_rapids_tpu.columnar.convert as cv
    arr = cv.device_column_to_arrow(c, 3)
    assert arr.to_pylist() == ["abc", "abc", "abc"]


def test_empty_table():
    t = pa.table({"x": pa.array([], type=pa.int64()),
                  "s": pa.array([], type=pa.string())})
    out = roundtrip(t)
    assert out.num_rows == 0


def test_sliced_arrow_string_input():
    # regression: offsets buffer not starting at 0 (sliced arrays)
    import spark_rapids_tpu.columnar.convert as cv
    arr = pa.array(["aa", "bbb", "cccc", "dd"]).slice(1)
    col = cv.arrow_to_device_column(arr, 8)
    assert cv.device_column_to_arrow(col, 3).to_pylist() == ["bbb", "cccc", "dd"]


def test_list_column_host_object_roundtrip():
    # nested arrays ride as host object columns (CPU fallback path)
    vals = [[1, 2], None, [3]]
    t = pa.table({"l": pa.array(vals)})
    assert roundtrip(t).column("l").to_pylist() == vals


def test_object_column_concat_and_repad():
    # host nested columns must survive concat/slice/repad (code-review regression)
    vals = [[1, 2], None, [3], [4, 5, 6]]
    b = arrow_to_device(pa.table({"l": pa.array(vals)}))
    cat = ColumnarBatch.concat([b.sliced(0, 2), b.sliced(2, 2)])
    assert device_to_arrow(cat).column("l").to_pylist() == vals
    assert device_to_arrow(b.repadded(16)).column("l").to_pylist() == vals
    with pytest.raises(ValueError):
        ColumnarBatch.concat([])


# ---------------------------------------------------------------------------
# ragged-string width-class splitting
# ---------------------------------------------------------------------------

class TestRaggedStringSplit:
    def test_split_keeps_footprint_near_data_size(self):
        """20k 1-byte strings + 3 10KB strings: unsplit the padded matrix
        is cap(32768) x width(16384) = 512MB; split it must stay within a
        few MB."""
        import spark_rapids_tpu as srt
        from spark_rapids_tpu.columnar.convert import split_ragged_strings
        from spark_rapids_tpu.sql.physical.transitions import batch_nbytes
        n = 20_000
        vals = ["a"] * n + ["x" * 10_240] * 3
        t = pa.table({"s": vals, "v": list(range(n + 3))})
        pieces = split_ragged_strings(t, 16 << 20)
        assert len(pieces) == 2
        assert pieces[0].num_rows == n and pieces[1].num_rows == 3
        # end-to-end through the scan: batches stay small
        from spark_rapids_tpu.sql.physical.basic import _cached_upload
        batches = _cached_upload(t, "tpu")
        assert len(batches) == 2
        total = sum(batch_nbytes(b) for b in batches)
        assert total < 8 << 20, f"padded footprint {total} bytes"

    def test_split_results_identical(self):
        """Query results match the host oracle after splitting (order-
        insensitive)."""
        import spark_rapids_tpu as srt
        from spark_rapids_tpu.sql import functions as F
        rng = np.random.default_rng(0)
        n = 20_000
        vals = ["k" + str(int(i)) for i in rng.integers(0, 50, n)]
        vals += ["L" * 9_000, "L" * 8_000]
        t = pa.table({"s": vals, "v": list(range(len(vals)))})
        sess = srt.session()
        df = sess.create_dataframe(t)
        got = (df.withColumn("ln", F.length(df.s))
               .groupBy("ln").count().orderBy("ln")
               .collect().to_pandas())
        pdf = t.to_pandas()
        exp = (pdf.assign(ln=pdf.s.str.len()).groupby("ln").size()
               .reset_index(name="count").sort_values("ln"))
        assert np.array_equal(got["ln"].values, exp["ln"].values)
        assert np.array_equal(got["count"].values, exp["count"].values)

    def test_uniform_strings_not_split(self):
        from spark_rapids_tpu.columnar.convert import split_ragged_strings
        t = pa.table({"s": ["abc"] * 10_000})
        assert len(split_ragged_strings(t, 16 << 20)) == 1


class TestLexSort64Split:
    """lex_sort splits 64-bit keys into (hi int32, lo uint32) comparator
    pairs on the jnp path (TPU x64-rewrite perf); order and stability
    must exactly match the numpy oracle."""

    def test_matches_numpy_incl_extremes(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.ops.ranks import lex_sort
        rng = np.random.default_rng(1)
        n = 20_000
        cases = [
            [rng.integers(-2**62, 2**62, n)],
            [rng.integers(-5, 5, n), rng.integers(-2**62, 2**62, n)],
            [rng.integers(0, 2**63, n).astype(np.uint64)],
            [np.array([np.iinfo(np.int64).min, -1, 0, 1,
                       np.iinfo(np.int64).max, 2**32, -2**32,
                       2**32 - 1, -(2**32) - 1] * 9)],
        ]
        for keys in cases:
            _, s_np = lex_sort(np, [np.asarray(k) for k in keys])
            _, s_j = lex_sort(jnp, [jnp.asarray(k) for k in keys])
            for a, b in zip(s_np, s_j):
                assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_stability_on_ties(self):
        import jax.numpy as jnp

        from spark_rapids_tpu.ops.ranks import lex_sort
        k = jnp.asarray(np.array([3, 1, 3, 1, 3, 1] * 100,
                                 dtype=np.int64))
        perm, _ = lex_sort(jnp, [k])
        p = np.asarray(perm)
        ones = p[:300]   # rows with key 1, in original order
        assert np.all(np.diff(ones) > 0)
