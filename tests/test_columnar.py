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
# concat as one cached program
# ---------------------------------------------------------------------------

def _per_array_concat(batches):
    """The per-array path (the fallback), whatever the columns are."""
    from spark_rapids_tpu.columnar import batch as B
    live = [b for b in batches if b.num_rows_int > 0]
    counts = [b.num_rows_int for b in live]
    rows = B._EagerRows(counts, bucket_capacity(sum(counts)))
    cols = [B._concat_columns([b.columns[ci] for b in live], rows)
            for ci in range(live[0].num_cols)]
    return ColumnarBatch.make(live[0].names, cols, sum(counts))


def _same_arrays(a: ColumnarBatch, b: ColumnarBatch):
    """Leaf for leaf the same bits: live rows, fills and padding."""
    import jax
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.shape == y.shape and x.dtype == y.dtype
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _plain(table, capacity=None):
    """On the device with no column dict-encoded (short tables encode
    every string otherwise, each upload over a dictionary of its own)."""
    from spark_rapids_tpu.config import RapidsConf
    return arrow_to_device(table, capacity, conf=RapidsConf(
        {"spark.rapids.tpu.sql.encoded.enabled": False}))


def _mixed_table(n, wide=False):
    return pa.table({
        "i": pa.array([None if k % 5 == 0 else k for k in range(n)],
                      type=pa.int64()),
        "f": pa.array([k / 7 for k in range(n)], type=pa.float64()),
        "s": pa.array([None if k % 3 == 0 else
                       ("w" * 40 if wide else "s") + str(k)
                       for k in range(n)], type=pa.string()),
        "d": pa.array([decimal.Decimal(k) * 10**20 for k in range(n)],
                      type=pa.decimal128(38, 2)),
        "st": pa.array([{"a": k, "b": f"x{k}"} for k in range(n)],
                       type=pa.struct([("a", pa.int64()),
                                       ("b", pa.string())])),
    })


@pytest.mark.parametrize("case", [
    "zero_row_pieces", "one_live_piece", "fills_the_bucket",
    "string_widths_differ", "piece_capacities_differ"])
def test_concat_program_matches_the_per_array_path(case):
    from spark_rapids_tpu.columnar import batch as B
    if case == "zero_row_pieces":
        whole = _plain(_mixed_table(300))
        pieces = [whole.sliced(0, 0), whole.sliced(0, 120),
                  whole.sliced(120, 0), whole.sliced(120, 180)]
    elif case == "one_live_piece":
        whole = _plain(_mixed_table(50))
        pieces = [whole.sliced(0, 0), whole, whole.sliced(50, 0)]
    elif case == "fills_the_bucket":
        whole = _plain(_mixed_table(256))
        pieces = [whole.sliced(0, 100), whole.sliced(100, 28),
                  whole.sliced(128, 128)]
    elif case == "string_widths_differ":
        pieces = [_plain(_mixed_table(40)),
                  _plain(_mixed_table(30, wide=True)),
                  _plain(_mixed_table(20))]
        assert len({p.column("s").width for p in pieces}) == 2
    else:
        pieces = [_plain(_mixed_table(10), capacity=64),
                  _plain(_mixed_table(33)),
                  _plain(_mixed_table(7), capacity=8)]
    assert B.concat_declined(pieces) == ""
    before = dict(B.CONCAT_STATS)
    out = ColumnarBatch.concat(pieces)
    live = [p for p in pieces if p.num_rows_int]
    if len(live) == 1:
        assert out is live[0]                   # no launch, no copy
        assert B.CONCAT_STATS == before
        return
    assert B.CONCAT_STATS["programs"] == before["programs"] + 1
    assert B.CONCAT_STATS["eager"] == before["eager"]
    expected = _per_array_concat(pieces)
    assert out._nrows_host == expected.num_rows_int == int(out.num_rows)
    if case == "fills_the_bucket":
        assert out.capacity == out.num_rows_int == 256
    if case == "string_widths_differ":    # re-aligned, never truncated
        assert out.column("s").width == max(p.column("s").width
                                            for p in pieces)
    _same_arrays(out, expected)
    assert device_to_arrow(out).equals(
        pa.concat_tables([device_to_arrow(p) for p in live]))


def test_concat_program_aligns_array_slot_widths():
    """Array columns of different slot widths: the children are re-laid
    to the widest inside the program, as the per-array path does."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import batch as B
    from spark_rapids_tpu.columnar.column import (make_array_column,
                                                  make_fixed_column)

    def arrays(rows, width, cap=8):
        flat = np.zeros(cap * width, dtype=np.int64)
        lengths = np.zeros(cap, dtype=np.int32)
        for r, vals in enumerate(rows):
            flat[r * width:r * width + len(vals)] = vals
            lengths[r] = len(vals)
        valid = (np.arange(cap * width) % width) < np.repeat(lengths, width)
        col = make_array_column(
            T.ArrayType(T.LONG), jnp.asarray(lengths),
            (make_fixed_column(T.LONG, jnp.asarray(flat),
                               jnp.asarray(valid)),),
            jnp.asarray(np.arange(cap) < len(rows)))
        return ColumnarBatch.make(["a"], [col], len(rows))

    pieces = [arrays([[1], [2, 3]], 4), arrays([[4, 5, 6, 7, 8, 9]], 8),
              arrays([[], [10]], 4)]
    assert B.concat_declined(pieces) == ""
    out = ColumnarBatch.concat(pieces)
    assert out.column("a").array_width == 8
    _same_arrays(out, _per_array_concat(pieces))


@pytest.mark.parametrize("kind", ["object", "numpy", "encoded"])
def test_concat_declines_what_the_program_cannot_express(kind):
    """Chosen from the columns themselves; the answer is still right."""
    from spark_rapids_tpu.columnar import batch as B
    if kind == "object":
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar.column import DeviceColumn

        def host_nested(vals):
            data = np.empty(8, dtype=object)
            data[:len(vals)] = vals
            col = DeviceColumn(T.BINARY, data, np.arange(8) < len(vals))
            return ColumnarBatch.make(["l"], [col], len(vals))
        pieces = [host_nested([[1, 2], None]), host_nested([[3], [4, 5]])]
        out = ColumnarBatch.concat(pieces)
        assert B.concat_declined(pieces) == kind
        assert list(out.column("l").data[:4]) == [[1, 2], None, [3], [4, 5]]
        assert np.asarray(out.column("l").validity).tolist() == \
            [True] * 4 + [False] * 4
        return
    elif kind == "numpy":
        import jax
        b = arrow_to_device(pa.table({"x": pa.array(range(10),
                                                    type=pa.int64())}))
        host = jax.tree_util.tree_map(np.asarray, b).with_known_rows(10)
        pieces, want = [host, host], {"x": list(range(10)) * 2}
    else:
        pieces = [arrow_to_device(pa.table({"s": [f"{tag}{k % 2}"
                                                  for k in range(40)]}))
                  for tag in ("a", "b")]
        want = {"s": [f"{tag}{k % 2}" for tag in ("a", "b")
                      for k in range(40)]}
    assert B.concat_declined(pieces) == kind
    before = dict(B.CONCAT_STATS)
    out = ColumnarBatch.concat(pieces)
    assert B.CONCAT_STATS["eager"] == before["eager"] + 1
    assert B.CONCAT_STATS["programs"] == before["programs"]
    assert device_to_arrow(out).to_pydict() == want


def test_concat_program_is_keyed_by_shapes_not_by_counts():
    """The row counts are an operand: other counts in the same buckets
    launch the same compiled program and trace nothing."""
    from spark_rapids_tpu.sql.physical import kernel_cache as KC
    whole = _plain(_mixed_table(120))
    ColumnarBatch.concat([whole.sliced(0, 50), whole.sliced(50, 60)])
    stats = KC.cache_stats()
    out = ColumnarBatch.concat([whole.sliced(0, 40), whole.sliced(70, 50)])
    after = KC.cache_stats()
    assert after["retraces"] == stats["retraces"]
    assert after["misses"] == stats["misses"]
    assert after["dispatches"] == stats["dispatches"] + 1
    assert any(name.startswith("srt_ColumnarBatch_concat_")
               for name in KC.dispatch_stats_by_key())
    assert device_to_arrow(out).equals(pa.concat_tables(
        [_mixed_table(120).slice(0, 40), _mixed_table(120).slice(70, 50)]))


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


class TestLexSortNetwork:
    """Between 2^14 and 2^16 rows the chip's ``lex_sort`` is a rolled
    bitonic network, not ``lax.sort`` (whose program takes the chip's
    compiler 8-100 s at those sizes): same permutation and sorted keys as
    the numpy oracle, ties in row order, 64-bit keys split as before."""

    @pytest.fixture()
    def as_on_the_chip(self, monkeypatch):
        import jax
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    @pytest.mark.parametrize("log2", [14, 15, 16])
    def test_matches_numpy(self, as_on_the_chip, log2):
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.ops import ranks
        n = 1 << log2
        rng = np.random.default_rng(log2)
        keys = [rng.integers(0, 2, n).astype(bool),
                rng.integers(-3, 3, n).astype(np.int32),
                rng.integers(-2**62, 2**62, n) // (1 << 61) * (1 << 40),
                rng.integers(0, 4, n).astype(np.uint32)]
        assert ranks._network_sorts(n, [jnp.asarray(k) for k in keys])
        lowered = jax.jit(lambda *k: ranks.lex_sort(jnp, list(k))).lower(
            *keys)
        assert "stablehlo.sort" not in lowered.as_text()
        perm, got = lowered.compile()(*keys)
        want_perm, want = ranks.lex_sort(np, keys)
        assert np.array_equal(np.asarray(perm), want_perm)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), b)

    @pytest.mark.parametrize("log2,words", [(13, 17), (12, 33), (11, 90)])
    def test_a_wide_comparator_below_2_14_rows_is_the_network(
            self, as_on_the_chip, log2, words):
        """A group-by or an ORDER BY over a 200-character string is ~90
        key words a row: ``lax.sort``'s compile time grows with rows x
        operands (41 s at 2^13 x 16; unfinished after 330 s at 2^13 x 91
        on the chip's host, PERF.md section 6 PR 35), so above 2^17
        word-rows the network sorts the small batches too."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.ops import ranks
        n = 1 << log2
        rng = np.random.default_rng(words)
        makers = [lambda: rng.integers(0, 2, n).astype(bool),
                  lambda: rng.integers(-2, 2, n).astype(np.int32),
                  lambda: (rng.integers(-1, 2, n) + 2**31).astype(np.uint32)]
        keys = [makers[i % 3]() for i in range(words)]
        assert ranks._network_sorts(n, [jnp.asarray(k) for k in keys])
        assert not ranks._network_sorts(n, [jnp.asarray(k)
                                            for k in keys[:words // 2]])
        lowered = jax.jit(lambda *k: ranks.lex_sort(jnp, list(k))).lower(
            *keys)
        assert "stablehlo.sort" not in lowered.as_text()
        perm, got = lowered.compile()(*keys)
        want_perm, want = ranks.lex_sort(np, keys)
        assert np.array_equal(np.asarray(perm), want_perm)
        for a, b in zip(got, want):
            assert np.array_equal(np.asarray(a), b)

    @pytest.mark.parametrize("n,kind", [(1 << 13, "i"), (1 << 17, "i"),
                                        (20_000, "i"), (1 << 15, "f")])
    def test_other_sizes_and_floats_keep_lax_sort(self, as_on_the_chip,
                                                  n, kind):
        import jax
        import jax.numpy as jnp

        from spark_rapids_tpu.ops import ranks
        k = (np.arange(n, dtype=np.int32)[::-1].copy() if kind == "i"
             else np.linspace(1.0, 0.0, n))
        assert not ranks._network_sorts(n, [jnp.asarray(k)])
        text = jax.jit(lambda a: ranks.lex_sort(jnp, [a])).lower(k).as_text()
        assert "stablehlo.sort" in text
