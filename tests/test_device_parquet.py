"""Device-side parquet decode (io_/device_parquet.py) vs the pyarrow
oracle: every supported (dtype x encoding x codec x page-version x nulls)
combination must produce a batch identical to uploading pyarrow's own
decode, and unsupported shapes must fall back per column, not per file."""

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar.convert import arrow_to_device, device_to_arrow
from spark_rapids_tpu.io_.device_parquet import decode_file


def _rng(seed=0):
    return np.random.default_rng(seed)


def _with_nulls(arr, frac, rng):
    if frac <= 0:
        return arr
    mask = rng.random(len(arr)) < frac
    return pa.array([None if m else v for m, v in
                     zip(mask, arr.to_pylist())], type=arr.type)


def _mixed_table(n=5000, null_frac=0.15, seed=7):
    rng = _rng(seed)
    cols = {
        "i32": pa.array(rng.integers(-2**31, 2**31 - 1, n), pa.int32()),
        "i64": pa.array(rng.integers(-2**62, 2**62, n), pa.int64()),
        "i8": pa.array(rng.integers(-128, 127, n).astype(np.int8)),
        "i16": pa.array(rng.integers(-2**15, 2**15 - 1, n).astype(np.int16)),
        "f32": pa.array(rng.standard_normal(n).astype(np.float32)),
        "f64": pa.array(rng.standard_normal(n) * 1e12),
        "b": pa.array(rng.random(n) < 0.5),
        "s": pa.array([f"row-{i % 97}" for i in range(n)]),
        "d": pa.array(rng.integers(0, 20000, n).astype(np.int32),
                      pa.date32()),
        "ts": pa.array(rng.integers(0, 2**45, n), pa.timestamp("us")),
    }
    return pa.table({k: _with_nulls(v, null_frac, rng)
                     for k, v in cols.items()})


def _check_file(tmp_path, table, name="t.parquet", **write_kwargs):
    path = str(tmp_path / name)
    pq.write_table(table, path, **write_kwargs)
    batch = decode_file(path)
    assert batch is not None, "no column took the device path"
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    assert got.schema.names == want.schema.names
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c
    return batch


@pytest.mark.quick
def test_plain_roundtrip(tmp_path):
    _check_file(tmp_path, _mixed_table(), use_dictionary=False)


@pytest.mark.quick
def test_dictionary_roundtrip(tmp_path):
    _check_file(tmp_path, _mixed_table(), use_dictionary=True)


@pytest.mark.parametrize("codec", ["snappy", "zstd", "gzip", "none"])
def test_codecs(tmp_path, codec):
    _check_file(tmp_path, _mixed_table(n=2000), compression=codec)


@pytest.mark.parametrize("version", ["1.0", "2.4", "2.6"])
def test_format_versions(tmp_path, version):
    _check_file(tmp_path, _mixed_table(n=2000), version=version)


def test_data_page_v2(tmp_path):
    _check_file(tmp_path, _mixed_table(n=3000),
                data_page_version="2.0")


def test_data_page_v2_uncompressed(tmp_path):
    _check_file(tmp_path, _mixed_table(n=1000),
                data_page_version="2.0", compression="none")


def test_multiple_row_groups(tmp_path):
    _check_file(tmp_path, _mixed_table(n=10_000), row_group_size=1024)


def test_multiple_pages_per_chunk(tmp_path):
    # tiny data pages force many pages (and hybrid runs) per column chunk
    _check_file(tmp_path, _mixed_table(n=20_000),
                data_page_size=1024, use_dictionary=False)


def test_dictionary_many_row_groups(tmp_path):
    # one writer => per-group dictionaries are prefixes of the same stream
    _check_file(tmp_path, _mixed_table(n=8000), row_group_size=1000,
                use_dictionary=True)


def test_divergent_dictionaries_remap_on_device(tmp_path):
    """Per-row-group dictionaries in first-occurrence order diverge for
    random data; the union+remap path must keep every column on device."""
    rng = _rng(23)
    n = 12_000
    t = pa.table({
        "i": pa.array(rng.integers(0, 500, n), pa.int32()),
        "s": pa.array([f"val-{v}" for v in
                       rng.integers(0, 300, n)]),
        "f": pa.array(rng.integers(0, 200, n).astype(np.float64)),
    })
    path = str(tmp_path / "dd.parquet")
    pq.write_table(t, path, row_group_size=997, use_dictionary=True)

    class Ctx:
        metrics = {}

        def inc_metric(self, k, v=1):
            self.metrics[k] = self.metrics.get(k, 0) + v

    ctx = Ctx()
    batch = decode_file(path, tctx=ctx)
    assert ctx.metrics.get("parquetDeviceDecodedColumns", 0) == 3
    assert not ctx.metrics.get("parquetHostDecodedColumns", 0)
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c


def test_ragged_string_dictionary_declines_whole_file(tmp_path):
    """One huge dictionary entry would blow the dense string matrix; the
    file must decline the DEVICE path entirely (host split_for_upload is
    table-level, so per-column fallback would rebuild the same matrix)."""
    t = pa.table({
        "i": pa.array(list(range(4000)), pa.int64()),
        "s": pa.array((["x" * 9000] + ["short"] * 999) * 4),
    })
    path = str(tmp_path / "rag.parquet")
    pq.write_table(t, path)

    class Conf:
        def get(self, key):
            return 1 << 20          # 1MB ragged threshold

    assert decode_file(path, conf=Conf()) is None


def test_no_nulls_required_columns(tmp_path):
    t = _mixed_table(n=1500, null_frac=0.0)
    # declare non-nullable so max_def == 0 (no def levels at all)
    fields = [pa.field(f.name, f.type, nullable=False) for f in t.schema]
    t = t.cast(pa.schema(fields))
    _check_file(tmp_path, t)


def test_all_null_column(tmp_path):
    t = pa.table({
        "x": pa.array([None] * 500, pa.int64()),
        "y": pa.array(list(range(500)), pa.int32()),
    })
    _check_file(tmp_path, t)


def test_empty_file(tmp_path):
    t = pa.table({"x": pa.array([], pa.int64())})
    path = str(tmp_path / "e.parquet")
    pq.write_table(t, path)
    # zero row groups -> engine host path; decode_file declines cleanly
    assert decode_file(path) is None or \
        device_to_arrow(decode_file(path)).num_rows == 0


def test_row_group_subset(tmp_path):
    t = _mixed_table(n=6000)
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path, row_group_size=1000)
    batch = decode_file(path, row_groups=[1, 3, 5])
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(
        pq.ParquetFile(path).read_row_groups([1, 3, 5])))
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c


@pytest.mark.parametrize("storage", ["integer", "flba"])
def test_decimal_columns(tmp_path, storage):
    """Decimals decode on device in BOTH parquet storages: INT32/INT64
    (store_decimal_as_integer) and the default FIXED_LEN_BYTE_ARRAY
    big-endian two's complement, incl. precision > 18 into the engine's
    (lo=data, hi=aux) 128-bit layout."""
    import decimal
    rng = _rng(3)
    vals = [decimal.Decimal(int(v)).scaleb(-2)
            for v in rng.integers(-10**9, 10**9, 800)]
    vals = [None if i % 13 == 0 else v for i, v in enumerate(vals)]
    big = [None if v is None else v * (10 ** 12) for v in vals]
    cols = {
        "d9": pa.array(vals, pa.decimal128(9, 2)),
        "d18": pa.array(vals, pa.decimal128(18, 2)),
    }
    if storage == "flba":
        cols["d30"] = pa.array(big, pa.decimal128(30, 2))
        cols["dneg"] = pa.array(
            [None if v is None else -v for v in big],
            pa.decimal128(30, 2))
    t = pa.table(cols)
    path = str(tmp_path / f"d_{storage}.parquet")
    pq.write_table(t, path,
                   store_decimal_as_integer=(storage == "integer"))

    class Ctx:
        metrics = {}

        def inc_metric(self, k, v=1):
            self.metrics[k] = self.metrics.get(k, 0) + v

    ctx = Ctx()
    batch = decode_file(path, tctx=ctx)
    assert batch is not None
    assert ctx.metrics.get("parquetDeviceDecodedColumns", 0) == len(cols)
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c


def test_decimal_flba_plain_pages(tmp_path):
    """PLAIN (non-dictionary) FLBA decimals exercise the byte-expansion
    kernel rather than the dictionary gather."""
    import decimal
    rng = _rng(9)
    vals = [decimal.Decimal(int(v)) * decimal.Decimal("0.001")
            for v in rng.integers(-10**15, 10**15, 600)]
    t = pa.table({"x": pa.array(vals, pa.decimal128(25, 3))})
    path = str(tmp_path / "dp.parquet")
    pq.write_table(t, path, use_dictionary=False)
    batch = decode_file(path)
    assert batch is not None
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    assert got.column("x").to_pylist() == want.column("x").to_pylist()


def test_nested_column_falls_back_per_column(tmp_path):
    t = pa.table({
        "flat": pa.array(list(range(400)), pa.int64()),
        "lst": pa.array([[i, i + 1] for i in range(400)],
                        pa.list_(pa.int32())),
    })
    path = str(tmp_path / "n.parquet")
    pq.write_table(t, path)

    class Ctx:
        metrics = {}

        def inc_metric(self, k, v=1):
            self.metrics[k] = self.metrics.get(k, 0) + v

    ctx = Ctx()
    batch = decode_file(path, tctx=ctx)
    assert batch is not None
    assert ctx.metrics.get("parquetDeviceDecodedColumns", 0) >= 1
    assert ctx.metrics.get("parquetHostDecodedColumns", 0) >= 1
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c


def test_timestamp_millis(tmp_path):
    rng = _rng(11)
    t = pa.table({"ts": pa.array(rng.integers(0, 2**40, 700),
                                 pa.timestamp("ms"))})
    _check_file(tmp_path, t)


def test_float_specials(tmp_path):
    vals = [0.0, -0.0, np.inf, -np.inf, np.nan, 1e-300, -1e300,
            np.finfo(np.float64).max, np.finfo(np.float64).min] * 50
    t = pa.table({"f": pa.array(vals, pa.float64()),
                  "g": pa.array([np.float32(v) for v in vals],
                                pa.float32())})
    path = str(tmp_path / "f.parquet")
    pq.write_table(t, path, use_dictionary=False)
    batch = decode_file(path)
    got = device_to_arrow(batch)
    want = device_to_arrow(arrow_to_device(pq.read_table(path)))
    for c in ("f", "g"):
        g = got.column(c).to_pylist()
        w = want.column(c).to_pylist()
        for a, b in zip(g, w):
            if b is None or (b != b):          # null or NaN
                assert a is None or a != a
            else:
                assert a == b, (c, a, b)


@pytest.mark.quick
def test_scan_exec_uses_device_decode(tmp_path):
    """End-to-end: session.read.parquet equality with the flag on vs off,
    and the device-decode metric fires."""
    import spark_rapids_tpu as srt

    t = _mixed_table(n=3000)
    path = str(tmp_path / "scan.parquet")
    pq.write_table(t, path, row_group_size=512)
    sess = srt.session()
    on = sess.read.parquet(path).orderBy("i32").collect().to_pandas()
    m = sess.last_query_metrics
    assert m.get("parquetDeviceDecodedColumns", 0) > 0, m
    sess.conf.set(
        "spark.rapids.sql.format.parquet.deviceDecode.enabled", "false")
    try:
        off = sess.read.parquet(path).orderBy("i32").collect().to_pandas()
    finally:
        sess.conf.set(
            "spark.rapids.sql.format.parquet.deviceDecode.enabled", "true")
    import pandas as pd
    pd.testing.assert_frame_equal(on, off)


# --------------------------------------------------------------------------
# round 5: PLAIN (non-dictionary) BYTE_ARRAY strings on device
# --------------------------------------------------------------------------

def _plain_string_metric(tmp_path, table, **kw):
    path = str(tmp_path / "ps.parquet")
    pq.write_table(table, path, use_dictionary=False, **kw)

    class _Ctx:
        metrics: dict = {}

        def inc_metric(self, k, v=1):
            self.metrics[k] = self.metrics.get(k, 0) + v

    ctx = _Ctx()
    batch = decode_file(path, tctx=ctx)
    assert batch is not None
    got = device_to_arrow(batch)
    want = pq.read_table(path)
    for c in want.schema.names:
        assert got.column(c).to_pylist() == want.column(c).to_pylist(), c
    return ctx.metrics


def test_plain_strings_device(tmp_path):
    rng = _rng(11)
    n = 8000
    t = pa.table({
        "s": pa.array([f"plain-{i % 211}-{'x' * (i % 13)}"
                       for i in range(n)]),
        "v": pa.array(rng.random(n)),
    })
    m = _plain_string_metric(tmp_path, t)
    assert m.get("parquetDeviceDecodedColumns", 0) == 2, m


def test_plain_strings_with_nulls_and_empties(tmp_path):
    rng = _rng(12)
    n = 6000
    vals = [None if rng.random() < 0.2
            else ("" if rng.random() < 0.2 else f"v{i}")
            for i in range(n)]
    t = pa.table({"s": pa.array(vals, pa.string())})
    m = _plain_string_metric(tmp_path, t)
    assert m.get("parquetDeviceDecodedColumns", 0) == 1, m


def test_plain_strings_multi_row_group_compressed(tmp_path):
    n = 20000
    t = pa.table({
        "s": pa.array([f"key-{i % 37:04d}" for i in range(n)]),
        "k": pa.array(np.arange(n, dtype=np.int64)),
    })
    m = _plain_string_metric(tmp_path, t, row_group_size=3000,
                             compression="zstd")
    assert m.get("parquetDeviceDecodedColumns", 0) == 2, m


def test_byte_array_walk_native_matches_python():
    from spark_rapids_tpu import native
    import struct as _s
    rng = _rng(13)
    vals = [bytes(rng.integers(0, 256, rng.integers(0, 20)).astype(
        np.uint8)) for _ in range(500)]
    raw = b"".join(_s.pack("<I", len(v)) + v for v in vals)
    data = np.frombuffer(raw, np.uint8)
    out = native.byte_array_walk(data, len(vals))
    if out is None:
        pytest.skip("native lib unavailable")
    starts, lens = out
    pos = 0
    for i, v in enumerate(vals):
        pos += 4
        assert starts[i] == pos and lens[i] == len(v), i
        pos += len(v)
    # truncation must raise, not overrun
    with pytest.raises(ValueError):
        native.byte_array_walk(data[:-1], len(vals))


# --------------------------------------------------------------------------
# PR 32: the expanders find each value's run by prefix sums.  The reference
# below walks the run table in a python loop (no search, no prefix sum).
# --------------------------------------------------------------------------

_BIG = np.iinfo(np.int32).max


def _pad_table(cols, pad_to):
    """Run-table columns as ``_runs_to_device`` pads them: INT32_MAX starts,
    zeros elsewhere."""
    import jax.numpy as jnp
    out = []
    for k, c in enumerate(cols):
        c = np.asarray(c)
        full = np.full(pad_to, _BIG if k == 0 else 0, c.dtype)
        full[:len(c)] = c
        out.append(jnp.asarray(full))
    return out


def _bits_of(buf: np.ndarray) -> np.ndarray:
    return np.unpackbits(buf.view(np.uint8), bitorder="little")


def _ref_runs(out_start, total):
    """(run, first output, one past its last output) for every run that
    owns outputs; a run owns [its start, the next run's start)."""
    ends = list(out_start[1:]) + [total]
    return [(r, s, e) for r, (s, e) in enumerate(zip(out_start, ends))
            if e > s]


def _ref_expand(buf, out_start, src_bit, width, rle_val, total):
    bits = _bits_of(buf)
    out = np.zeros(total, np.uint64)
    for r, s, e in _ref_runs(out_start, total):
        w = int(width[r])
        if w == 0:
            out[s:e] = np.uint32(rle_val[r])
            continue
        pos = int(src_bit[r]) + np.arange(e - s)[:, None] * w + np.arange(w)
        out[s:e] = (bits[pos].astype(np.uint64)
                    << np.arange(w, dtype=np.uint64)).sum(axis=1)
    return out


def _random_words(rng, nwords):
    return rng.integers(0, 2**32, nwords, dtype=np.uint32)


def _hybrid_table(rng, spec, total):
    """spec: list of (count, width) with width 0 = RLE.  Packed runs are
    laid out back to back from bit 40, as pages lay them."""
    out_start, src_bit, width, rle_val = [], [], [], []
    pos, bit = 0, 40
    for count, w in spec:
        out_start.append(pos)
        width.append(w)
        if w == 0:
            src_bit.append(0)
            rle_val.append(int(rng.integers(0, 2**31)))
        else:
            src_bit.append(bit)
            rle_val.append(0)
            bit += count * w + int(rng.integers(0, 3)) * 8
        pos += count
    assert pos == total
    return (np.asarray(out_start, np.int32), np.asarray(src_bit, np.int64),
            np.asarray(width, np.int32), np.asarray(rle_val, np.int32), bit)


_U32_CASES = {
    "width1": [(64, 1), (40, 1)],
    "width7": [(100, 7)],
    "width12_rle_mixed": [(30, 12), (500, 0), (77, 12), (3, 0), (90, 12)],
    "width31": [(50, 31), (9, 0), (41, 31)],
    "width32": [(33, 32), (67, 32)],
    "single_rle_run": [(128, 0)],
    "zero_length_run": [(40, 5), (0, 9), (0, 0), (60, 3), (28, 0)],
    "all_widths": [(8, w) for w in range(1, 33)],
    "many_runs": [(3, (k * 5) % 13) for k in range(300)],
}


@pytest.mark.parametrize("pad", [False, True], ids=["exact", "padded"])
@pytest.mark.parametrize("case", sorted(_U32_CASES))
def test_expand_runs_u32_matches_run_loop(case, pad):
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import (_expand_runs_u32,
                                                     _pad_pow2)
    spec = _U32_CASES[case]
    total = sum(c for c, _ in spec)
    rng = _rng(len(case))
    out_start, src_bit, width, rle_val, bit = _hybrid_table(rng, spec, total)
    buf = _random_words(rng, bit // 32 + 4)
    want = _ref_expand(buf, out_start, src_bit, width, rle_val, total)
    cols = (out_start, src_bit, width, rle_val)
    out_cap = _pad_pow2(total)
    if pad:
        cols = _pad_table(cols, _pad_pow2(len(out_start) + 1, 4))
        out_cap *= 2
    got = _expand_runs_u32(jnp.asarray(buf), *map(jnp.asarray, cols),
                           out_cap=out_cap)
    assert got.dtype == jnp.uint32 and got.shape == (out_cap,)
    np.testing.assert_array_equal(np.asarray(got)[:total], want)


@pytest.mark.parametrize("case", ["single_run", "zero_length_run",
                                  "padded_table", "first_run_late",
                                  "negative_and_wide_values"])
def test_spread_runs_matches_run_loop(case):
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import _spread_runs
    out_cap = 96
    starts = {"single_run": [0],
              "zero_length_run": [0, 10, 10, 10, 50],
              "padded_table": [0, 7, 31, _BIG, _BIG, _BIG, _BIG, _BIG],
              "first_run_late": [5, 6, 90],
              "negative_and_wide_values": [0, 1, 2, 64, 95]}[case]
    rng = _rng(len(case))
    a = rng.integers(-2**31, 2**31, len(starts)).astype(np.int32)
    b = rng.integers(0, 33, len(starts)).astype(np.int32)
    got_a, got_b = _spread_runs(jnp.asarray(starts, jnp.int32),
                                (jnp.asarray(a), jnp.asarray(b)), out_cap)
    live = [s for s in starts if s != _BIG]
    want_a = np.zeros(out_cap, np.int32)     # before the first run: 0
    want_b = np.zeros(out_cap, np.int32)
    for r, s, e in _ref_runs(live, out_cap):
        want_a[s:e], want_b[s:e] = a[r], b[r]
    np.testing.assert_array_equal(np.asarray(got_a), want_a)
    np.testing.assert_array_equal(np.asarray(got_b), want_b)


@pytest.mark.parametrize("width", [1, 12, 31, 32, 64, 128])
@pytest.mark.parametrize("src_bit", [2**31 + 40, 2**35 + 8, 17])
def test_bit_address_past_two_to_the_31(src_bit, width):
    """A merged chunk buffer can pass 256 MB: ``src_bit`` is int64 and the
    (word, shift) pair must not wrap.  Checked on the address itself (a
    buffer that large does not belong in a test)."""
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import (_bit_address,
                                                     _run_origin,
                                                     _spread_runs)
    out_cap = 4096
    out_start = np.asarray([0, 1000, 1000, 3000], np.int32)
    bits = np.asarray([src_bit + 64 * k for k in range(4)], np.int64)
    bits[1] = 0                                   # zero-length: must lose
    origin = _run_origin(jnp.asarray(out_start), jnp.asarray(bits), width)
    word0, bit0 = _spread_runs(jnp.asarray(out_start), origin, out_cap)
    w0, sh = _bit_address(word0, bit0, width, 8)
    got = np.asarray(w0).astype(object) * 32 + np.asarray(sh).astype(object)
    for r, s, e in _ref_runs(out_start.tolist(), out_cap):
        want = [int(bits[r]) + (i - s) * width + 8 for i in range(s, e)]
        assert list(got[s:e]) == want, (r, s)


@pytest.mark.parametrize("case", ["one_section", "three_sections_unaligned",
                                  "padded_table"])
def test_expand_runs_u64_matches_run_loop(case):
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import _expand_runs_u64
    rng = _rng(64)
    counts = {"one_section": [100], "three_sections_unaligned": [10, 33, 21],
              "padded_table": [7, 50]}[case]
    total = sum(counts)
    out_start = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    # PLAIN sections are byte- but not word-aligned
    src_bit, bit = [], 8
    for k, c in enumerate(counts):
        src_bit.append(bit)
        bit += c * 64 + 8 * (k + 1)
    src_bit = np.asarray(src_bit, np.int64)
    buf = _random_words(rng, bit // 32 + 4)
    want = _ref_expand(buf, out_start, src_bit, [64] * len(counts),
                       [0] * len(counts), total)
    cols = (out_start, src_bit)
    if case == "padded_table":
        cols = _pad_table(cols, 8)
    got = _expand_runs_u64(jnp.asarray(buf), *map(jnp.asarray, cols),
                           out_cap=128)
    np.testing.assert_array_equal(np.asarray(got)[:total], want)


@pytest.mark.parametrize("width", [1, 4, 8, 11, 16])
def test_expand_flba_matches_run_loop(width):
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import _expand_flba
    rng = _rng(width)
    counts = [9, 40, 15]
    total = sum(counts)
    out_start = np.concatenate([[0], np.cumsum(counts)[:-1]]).astype(np.int32)
    src_byte, at = [], 3
    for c in counts:
        src_byte.append(at)
        at += c * width + 5
    buf = _random_words(rng, at // 4 + 4)
    raw = buf.view(np.uint8)
    lo, hi = _expand_flba(
        jnp.asarray(buf),
        *_pad_table((out_start, np.asarray(src_byte, np.int64) * 8), 4),
        out_cap=64, width=width)
    lo, hi = np.asarray(lo), np.asarray(hi)
    for r, s, e in _ref_runs(out_start.tolist(), total):
        for i in range(s, e):
            b = raw[src_byte[r] + (i - s) * width:][:width].tobytes()
            want = int.from_bytes(b, "big", signed=True) & ((1 << 128) - 1)
            assert (int(hi[i]) << 64 | int(lo[i])) == want, (width, i)


@pytest.mark.parametrize("groups", [1, 4])
def test_remap_indices_matches_run_loop(groups):
    import jax.numpy as jnp
    from spark_rapids_tpu.io_.device_parquet import _remap_indices
    rng = _rng(groups)
    n = 256
    sizes = rng.integers(3, 40, groups)
    remap = rng.integers(0, 1000, int(sizes.sum())).astype(np.int32)
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int32)
    starts = np.sort(np.concatenate(
        [[0], rng.choice(np.arange(1, 200), groups - 1, replace=False)]
    )).astype(np.int32)
    idx = np.zeros(n, np.int32)
    want = np.zeros(n, np.int32)
    for g, s, e in _ref_runs(starts.tolist(), n):
        idx[s:e] = rng.integers(0, sizes[g], e - s)
        want[s:e] = remap[offsets[g] + idx[s:e]]
    got = _remap_indices(jnp.asarray(idx), *_pad_table((starts, offsets), 4),
                         jnp.asarray(remap))
    np.testing.assert_array_equal(np.asarray(got), want)


def _count_primitives(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _count_primitives(inner, counts)
    return counts


@pytest.mark.parametrize("which,word_reads", [
    ("u32", 2), ("u64", 4), ("flba", 2 * 11), ("remap", 1)])
def test_expanders_hold_no_search(which, word_reads):
    """No expander maps an output to its run by a search (a ``while`` of
    gathers on the chip, 0.3 s a call: PERF.md section 6, PR 32) or a sort,
    and the only gathers left are the reads of the data itself."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.io_ import device_parquet as dp
    words = jnp.zeros(64, jnp.uint32)
    out_start = jnp.asarray([0, 10, _BIG, _BIG], jnp.int32)
    src_bit = jnp.asarray([8, 4000, 0, 0], jnp.int64)
    i32 = jnp.asarray([12, 0, 0, 0], jnp.int32)
    jaxpr = {
        "u32": lambda: jax.make_jaxpr(
            lambda *a: dp._expand_runs_u32(*a, out_cap=64))(
                words, out_start, src_bit, i32, i32),
        "u64": lambda: jax.make_jaxpr(
            lambda *a: dp._expand_runs_u64(*a, out_cap=64))(
                words, out_start, src_bit),
        "flba": lambda: jax.make_jaxpr(
            lambda *a: dp._expand_flba(*a, out_cap=64, width=11))(
                words, out_start, src_bit),
        "remap": lambda: jax.make_jaxpr(dp._remap_indices)(
            jnp.zeros(64, jnp.int32), out_start, i32, i32),
    }[which]()
    counts = _count_primitives(jaxpr.jaxpr, {})
    assert not {"while", "sort", "scan", "cond"} & set(counts), counts
    assert counts.get("gather", 0) == word_reads, counts
