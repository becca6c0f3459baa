"""Encoded columnar execution (ISSUE 6): dictionary/RLE representations,
op parity encoded-on vs encoded-off (filter/join/group-by/sort), the
encoded-batch shuffle wire format (narrowed codes, dictionary refs),
scan-side retention in the device decoders, decode-engagement counters,
and structural kill-switch reversion (mirror of test_async_pipeline's
plan-shape reversion: with the switch off NO encoded column ever
exists, so every plan takes the raw path)."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import arrow_to_device, device_to_arrow
from spark_rapids_tpu.columnar import encoded as E
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.sql import functions as F

ROWS = 6000
CATS = [f"cat_{i:03d}" for i in range(24)]


def _conf(on: bool, **extra):
    base = {"spark.rapids.tpu.sql.encoded.enabled": on}
    base.update(extra)
    return RapidsConf.get_global().copy(base)


def _sess(on: bool, **extra):
    return srt.session(conf=_conf(on, **extra))


@pytest.fixture(scope="module")
def tables():
    rng = np.random.default_rng(29)
    fact = pa.table({
        "k": pa.array([None if rng.random() < 0.05
                       else CATS[i] for i in rng.integers(0, 24, ROWS)]),
        "q": rng.integers(0, 100, ROWS),
        "v": rng.random(ROWS)})
    dim = pa.table({"k": CATS, "w": np.arange(float(len(CATS)))})
    return fact, dim


def _rows(df):
    return df.collect().to_pylist()


# --------------------------------------------------------------------------
# representation unit tests
# --------------------------------------------------------------------------


def test_dict_encode_roundtrip_and_killswitch():
    t = pa.table({"s": pa.array(["b", "a", None, "b", "c", "a"] * 40)})
    enc = arrow_to_device(t, conf=_conf(True))
    raw = arrow_to_device(t, conf=_conf(False))
    assert isinstance(enc.columns[0], E.DictEncodedColumn)
    # structural kill switch: OFF means no encoded column is created
    assert not isinstance(raw.columns[0], E.DictEncodedColumn)
    assert device_to_arrow(enc).equals(device_to_arrow(raw))
    d = enc.columns[0].dictionary
    assert d.sorted and d.size == 3
    assert list(d.host_values()) == [b"a", b"b", b"c"]


def test_dict_materialize_zeroes_null_rows():
    t = pa.table({"s": pa.array(["xx", None, "yy"] * 50)})
    enc = arrow_to_device(t, conf=_conf(True))
    raw = arrow_to_device(t, conf=_conf(False))
    c = enc.columns[0]
    assert isinstance(c, E.DictEncodedColumn)
    # the decline path (.data/.lengths) must produce the raw pipeline's
    # exact buffers, null rows zeroed included
    np.testing.assert_array_equal(np.asarray(c.data),
                                  np.asarray(raw.columns[0].data))
    np.testing.assert_array_equal(np.asarray(c.lengths),
                                  np.asarray(raw.columns[0].lengths))


def test_rle_encode_roundtrip():
    reps = np.repeat(np.arange(40, dtype=np.int64), 50)
    t = pa.table({"r": reps})
    enc = arrow_to_device(t, conf=_conf(True))
    raw = arrow_to_device(t, conf=_conf(False))
    assert isinstance(enc.columns[0], E.RLEColumn)
    assert enc.columns[0].num_runs == 40
    assert device_to_arrow(enc).equals(device_to_arrow(raw))


def _rle_layout(kind: str):
    """(run ends, run validity, capacity) of one run layout; the ends are
    padded with capacity as every producer pads them."""
    cap = 256
    if kind == "full":                 # the last run ends at capacity
        ends = np.arange(16, cap + 1, 16)
    elif kind == "partial":            # live prefix of 200 rows
        ends = np.array([3, 50, 51, 120, 200])
    elif kind == "one_run":
        ends = np.array([180])
    elif kind == "every_row":          # a run a row, all of capacity
        ends = np.arange(1, cap + 1)
    elif kind == "zero_length":        # empty runs, as a wire frame holds
        ends = np.array([0, 0, 7, 7, 7, 30, 64, 64, 99])
    else:                              # "null_breaks": NULL runs between
        ends = np.array([10, 14, 40, 41, 90, 130, 131, 170])
    k = len(ends)
    run_cap = 1 << max(k - 1, 0).bit_length()
    padded = np.full(max(run_cap, 1), cap, dtype=np.int32)
    padded[:k] = ends
    rvalid = np.zeros(padded.shape[0], dtype=bool)
    rvalid[:k] = True
    if kind == "null_breaks":
        rvalid[1:k:2] = False
    return padded, k, rvalid, cap


def _rle_column(kind: str, dtype_name: str):
    """An RLEColumn of one layout and one value type, built as the scan
    and the wire reader build it: null runs hold zeroed values, rows past
    the last run are dead."""
    from spark_rapids_tpu.columnar.column import DeviceColumn
    ends, k, rvalid, cap = _rle_layout(kind)
    rng = np.random.default_rng(len(kind) * 7 + len(dtype_name))
    np_dt = {"int8": np.int8, "int16": np.int16, "int32": np.int32,
             "int64": np.int64, "decimal_aux": np.int64}[dtype_name]
    info = np.iinfo(np_dt)
    vals = rng.integers(info.min, info.max, ends.shape[0],
                        dtype=np_dt, endpoint=True)
    vals[~rvalid] = 0
    aux = None
    if dtype_name == "decimal_aux":
        dt = T.DecimalType(38, 2)
        aux = rng.integers(-2**40, 2**40, ends.shape[0]).astype(np.int64)
        aux[~rvalid] = 0
    else:
        dt = {"int8": T.ByteType(), "int16": T.ShortType(),
              "int32": T.IntegerType(), "int64": T.LongType()}[dtype_name]
    run_of_row = np.searchsorted(ends, np.arange(cap), side="right")
    valid = np.zeros(cap, dtype=bool)
    live = run_of_row < k
    valid[live] = rvalid[run_of_row[live]]
    runs = DeviceColumn(dt, vals, rvalid, aux=aux)
    return E.RLEColumn(dt, runs, ends, k, valid)


def _searched(col):
    """The expansion as it was before the prefix sum: a binary search of
    every row among the run ends."""
    import jax.numpy as jnp
    idx = jnp.searchsorted(jnp.asarray(col.run_ends),
                           jnp.arange(col.capacity, dtype=jnp.int32),
                           side="right")
    idx = jnp.clip(idx, 0, col.run_values.capacity - 1)
    v = jnp.asarray(col.validity)
    data = jnp.where(v, jnp.asarray(col.run_values.data)[idx], 0)
    aux = None if col.run_values.aux is None else jnp.where(
        v, jnp.asarray(col.run_values.aux)[idx], 0)
    return data, aux


@pytest.mark.parametrize("mode", ["eager", "jit"])
@pytest.mark.parametrize("dtype_name",
                         ["int8", "int16", "int32", "int64", "decimal_aux"])
@pytest.mark.parametrize("kind", ["full", "partial", "one_run", "every_row",
                                  "zero_length", "null_breaks"])
def test_rle_expansion_matches_search_and_numpy(kind, dtype_name, mode):
    import jax
    col = _rle_column(kind, dtype_name)
    if mode == "eager":
        data, aux = col.data, col.aux
    else:
        data, aux = jax.jit(lambda c: (c.data, c.aux))(col)
    old_data, old_aux = _searched(col)
    ref = E.materialize_np(col)
    data = np.asarray(data)
    assert data.dtype == np.asarray(old_data).dtype
    np.testing.assert_array_equal(data, np.asarray(old_data))
    np.testing.assert_array_equal(data, np.asarray(ref.data))
    if dtype_name == "decimal_aux":
        np.testing.assert_array_equal(np.asarray(aux), np.asarray(old_aux))
        np.testing.assert_array_equal(np.asarray(aux), np.asarray(ref.aux))
    else:
        assert aux is None and ref.aux is None
    # null rows and rows past the last run read zero
    assert not data[~np.asarray(col.validity)].any()


def test_rle_expansion_has_no_loop_and_one_program_a_capacity():
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import DeviceColumn
    cap = 1 << 16

    def col_of(num_runs):
        ends = np.full(1024, cap, dtype=np.int32)
        ends[:num_runs] = np.linspace(40, cap - 3, num_runs).astype(np.int32)
        runs = DeviceColumn(T.IntegerType(),
                            np.arange(1024, dtype=np.int32),
                            np.arange(1024) < num_runs)
        return E.RLEColumn(T.IntegerType(), runs, ends, num_runs,
                           np.arange(cap) < cap - 3)

    text = jax.jit(lambda c: c.data).lower(col_of(700)).as_text()
    assert "while" not in text
    # the control: the search it replaced lowers to a loop
    searched = jax.jit(lambda e: jnp.searchsorted(
        e, jnp.arange(cap, dtype=jnp.int32), side="right")).lower(
        jnp.zeros(1024, jnp.int32)).as_text()
    assert "while" in searched
    # two run counts of one capacity (and one run bucket): one program
    a, b = col_of(700), col_of(913)
    assert a.run_values.capacity == b.run_values.capacity
    a.materialized()
    after_first = E._expand_rle._cache_size()
    b.materialized()
    assert E._expand_rle._cache_size() == after_first
    np.testing.assert_array_equal(np.asarray(b.data),
                                  np.asarray(_searched(b)[0]))


def _ticket_table(rows: int, seed: int) -> pa.Table:
    """store_sales in miniature: rows grouped by ticket, so the ticket's
    date and demographics repeat along it, and two item-level columns."""
    rng = np.random.default_rng(seed)
    ticket = np.sort(rng.integers(0, rows // 10, rows))
    date_of = rng.integers(2450816, 2452642, rows // 10 + 1)
    cdemo_of = rng.integers(1, 1920801, rows // 10 + 1)
    return pa.table({
        "ss_ticket_number": ticket,
        "ss_sold_date_sk": date_of[ticket],
        "ss_cdemo_sk": cdemo_of[ticket],
        "ss_item_sk": rng.integers(1, 204001, rows),
        "ss_quantity": rng.integers(1, 101, rows)})


@pytest.mark.parametrize("columns,rle_a_batch", [
    (("ss_sold_date_sk", "ss_cdemo_sk", "ss_quantity"), 2),
    (("ss_item_sk", "ss_quantity"), 0)], ids=["ticket_level", "random"])
def test_scan_counts_rle_columns(columns, rle_a_batch):
    parts = 3
    sess = _sess(True)
    df = sess.create_dataframe(_ticket_table(6000, 41), num_partitions=parts)
    got = (df.select(*columns).groupBy(columns[0])
           .agg(*[F.sum(F.col(c)).alias(c) for c in columns[1:]])
           .orderBy(columns[0]).collect().to_pylist())
    assert len(got) > 0
    m = sess.last_query_metrics
    assert m.get("scanRleColumns", 0) == rle_a_batch * parts, m


def test_high_cardinality_declines():
    t = pa.table({"s": pa.array([f"u{i}" for i in range(5000)])})
    enc = arrow_to_device(
        t, conf=_conf(True, **{
            "spark.rapids.tpu.sql.encoded.maxDictionaryCardinality": 256}))
    assert not isinstance(enc.columns[0], E.DictEncodedColumn)


def test_gather_stays_encoded():
    import jax.numpy as jnp
    t = pa.table({"s": pa.array(["a", "b", "c", "d"] * 64)})
    enc = arrow_to_device(t, conf=_conf(True))
    out = enc.columns[0].gather(jnp.asarray([3, 1, 0, 2], dtype=jnp.int32))
    assert isinstance(out, E.DictEncodedColumn)
    assert out.dictionary is enc.columns[0].dictionary
    got = [bytes(np.asarray(out.data)[i, :np.asarray(out.lengths)[i]])
           for i in range(4)]
    assert got == [b"d", b"b", b"a", b"c"]


def test_concat_unifies_different_dictionaries():
    a = arrow_to_device(pa.table({"s": ["a", "b"] * 32}), conf=_conf(True))
    b = arrow_to_device(pa.table({"s": ["b", "c"] * 32}), conf=_conf(True))
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    cat = ColumnarBatch.concat([a, b])
    assert isinstance(cat.columns[0], E.DictEncodedColumn)
    assert list(cat.columns[0].dictionary.host_values()) == \
        [b"a", b"b", b"c"]
    assert device_to_arrow(cat).column(0).to_pylist() == \
        ["a", "b"] * 32 + ["b", "c"] * 32


# --------------------------------------------------------------------------
# op parity + engagement: filter / join / group-by / sort
# --------------------------------------------------------------------------


def _q_filter(sess, fact, dim):
    return (sess.create_dataframe(fact, num_partitions=3)
            .filter(F.col("k") <= "cat_011")
            .groupBy("k").agg(F.sum(F.col("q")).alias("sq"))
            .orderBy("k"))


def _q_join(sess, fact, dim):
    f = sess.create_dataframe(fact, num_partitions=3)
    d = sess.create_dataframe(dim, num_partitions=2)
    return (f.join(d, on="k", how="inner").groupBy("k")
            .agg(F.count("*").alias("n"), F.sum(F.col("v")).alias("sv"))
            .orderBy("k"))


def _q_agg_sort(sess, fact, dim):
    return (sess.create_dataframe(fact, num_partitions=3)
            .groupBy("k").agg(F.count("*").alias("c"),
                              F.sum(F.col("v")).alias("sv"))
            .orderBy(F.col("k").desc()))


@pytest.mark.parametrize("mk", [_q_filter, _q_join, _q_agg_sort],
                         ids=["filter", "join", "agg_sort"])
def test_op_parity_encoded_vs_raw(tables, mk):
    fact, dim = tables
    on = _rows(mk(_sess(True, **{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 1}), fact, dim))
    off = _rows(mk(_sess(False, **{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 1}), fact, dim))
    assert on == off


def test_filter_fast_path_engages(tables):
    from spark_rapids_tpu.sql.physical.kernel_cache import (
        release_compiled_programs)
    fact, dim = tables
    # dict_filters counts TRACE-time fast-path engagement; drop compiled
    # programs so this query's predicate actually retraces
    release_compiled_programs()
    sess = _sess(True)
    _rows(_q_filter(sess, fact, dim))
    m = sess.last_query_metrics
    assert m.get("encodedDictFilters", 0) >= 1, m
    # NOTE: encodedColumnsEncoded counts NEW encodes at upload; since
    # the serving tier made the upload/split caches process-shared
    # (docs/serving.md), a table another test already scanned encoded
    # serves its resident dict batches with zero fresh encodes — the
    # dict-filter engagement above is the proof encoded columns flowed


def test_filter_null_semantics_parity(tables):
    fact, dim = tables
    for pred in (F.col("k").isNull(), F.col("k").isNotNull(),
                 F.col("k").isin("cat_001", "cat_007")):
        on = _rows(_sess(True).create_dataframe(fact).filter(pred)
                   .groupBy("k").count().orderBy("k"))
        off = _rows(_sess(False).create_dataframe(fact).filter(pred)
                    .groupBy("k").count().orderBy("k"))
        assert on == off


def test_join_probes_on_codes(tables):
    fact, dim = tables
    sess = _sess(True, **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    _rows(_q_join(sess, fact, dim))
    assert sess.last_query_metrics.get("joinCodeLowerings", 0) >= 1


def test_broadcast_join_parity_and_lowering(tables):
    """The broadcast path: the dim side broadcasts (in-process, dict-
    aware concat), and the join still lowers to code space."""
    fact, dim = tables
    sess = _sess(True)  # default broadcast threshold: dim broadcasts
    on = _rows(_q_join(sess, fact, dim))
    assert sess.last_query_metrics.get("joinCodeLowerings", 0) >= 1
    off = _rows(_q_join(_sess(False), fact, dim))
    assert on == off


def test_join_types_parity(tables):
    fact, dim = tables
    half = dim.slice(0, 12)  # build misses exercise the -1 sentinel
    for how in ("inner", "left", "left_semi", "left_anti"):
        def q(sess):
            f = sess.create_dataframe(fact, num_partitions=2)
            d = sess.create_dataframe(half)
            j = f.join(d, on="k", how=how)
            cols = ["k"] if how in ("left_semi", "left_anti") else ["k", "w"]
            return j.groupBy(*cols).count().orderBy("k")
        on = _rows(q(_sess(True,
                           **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})))
        off = _rows(q(_sess(False,
                            **{"spark.rapids.sql.autoBroadcastJoinThreshold": 1})))
        assert on == off, how


# --------------------------------------------------------------------------
# wire format
# --------------------------------------------------------------------------


def _wire_tables():
    rng = np.random.default_rng(7)
    return pa.table({
        "s": pa.array([None if rng.random() < 0.1
                       else CATS[i] for i in rng.integers(0, 24, 2000)]),
        "r": np.repeat(np.arange(20, dtype=np.int64), 100),
        "v": rng.random(2000)})


def test_wire_roundtrip_and_narrowing():
    from spark_rapids_tpu.shuffle.serializer import (deserialize_batch,
                                                     serialize_batch)
    t = _wire_tables()
    conf = _conf(True, **{
        "spark.rapids.tpu.sql.encoded.shuffle.dictRefs.enabled": False})
    enc = arrow_to_device(t, conf=conf)
    assert isinstance(enc.columns[0], E.DictEncodedColumn)
    assert isinstance(enc.columns[1], E.RLEColumn)
    frame = serialize_batch(enc, conf)
    raw_frame = serialize_batch(arrow_to_device(t, conf=_conf(False)),
                                _conf(False))
    assert len(frame) < len(raw_frame)
    back = deserialize_batch(frame)
    assert device_to_arrow(back).equals(
        device_to_arrow(arrow_to_device(t, conf=_conf(False))))


def test_wire_dict_refs_ship_dictionary_once():
    from spark_rapids_tpu.shuffle.serializer import (deserialize_batch,
                                                     serialize_batch)
    t = pa.table({"s": pa.array([CATS[i % 24] for i in range(1000)])})
    conf = _conf(True)
    enc = arrow_to_device(t, conf=conf)
    first = serialize_batch(enc, conf)
    second = serialize_batch(enc, conf)
    # the second frame replaces the (registered) dictionary with a
    # content-hash ref: only code bytes remain
    assert len(second) < len(first)
    for frame in (first, second):
        got = deserialize_batch(frame)
        assert device_to_arrow(got).column(0).to_pylist() == \
            t.column(0).to_pylist()


def test_wire_reader_materializes_when_disabled():
    from spark_rapids_tpu.shuffle.serializer import (deserialize_batch,
                                                     serialize_batch)
    t = _wire_tables()
    conf = _conf(True)
    frame = serialize_batch(arrow_to_device(t, conf=conf), conf)
    g = RapidsConf.get_global()
    old = g.get("spark.rapids.tpu.sql.encoded.enabled")
    try:
        g.set("spark.rapids.tpu.sql.encoded.enabled", False)
        back = deserialize_batch(frame)
        # a disabled session must never observe encoded representations
        assert not E.has_encoded_columns(back)
    finally:
        g.set("spark.rapids.tpu.sql.encoded.enabled", old)
    assert device_to_arrow(back).equals(
        device_to_arrow(arrow_to_device(t, conf=_conf(False))))


def test_shuffle_bytes_on_wire_metric(tables):
    fact, dim = tables
    wire = {}
    for on in (True, False):
        sess = _sess(on, **{
            "spark.rapids.shuffle.localDeviceResident.enabled": False,
            "spark.rapids.sql.autoBroadcastJoinThreshold": 1})
        _rows(_q_join(sess, fact, dim))
        m = sess.last_query_metrics
        assert m.get("shuffleBytesOnWire", 0) > 0, m
        wire[on] = m["shuffleBytesOnWire"]
    # the encoded-vs-raw claim, measured per query: encoding must shrink
    # the join shape's wire bytes
    assert wire[True] < wire[False], wire


# --------------------------------------------------------------------------
# scan-side retention + decode engagement (satellite 1)
# --------------------------------------------------------------------------


def test_scan_retention_and_engagement(tmp_path):
    from spark_rapids_tpu.testing.scaletest import scan_engagement_report
    rep = scan_engagement_report(rows=5000, tmpdir=str(tmp_path))
    for fmt in ("parquet", "orc"):
        assert rep[fmt]["files_engaged"] >= 1, rep
        assert rep[fmt]["files_declined"] == 0, rep
    assert "decline_reasons" in rep["decode_stats"]["parquet"]


def test_parquet_dict_page_retention_parity(tmp_path):
    import pyarrow.parquet as pq
    rng = np.random.default_rng(3)
    t = pa.table({
        "k": pa.array([CATS[i] for i in rng.integers(0, 24, 4000)]),
        "v": rng.random(4000)})
    path = str(tmp_path / "t.parquet")
    pq.write_table(t, path)
    res = {}
    for on in (True, False):
        sess = _sess(on)
        q = (sess.read.parquet(path).filter(F.col("k") >= "cat_010")
             .groupBy("k").agg(F.sum(F.col("v")).alias("sv")).orderBy("k"))
        res[on] = _rows(q)
        m = sess.last_query_metrics
        assert m.get("parquetDecodeFilesEngaged", 0) >= 1, m
        enc_cols = m.get("encodedColumnsEncoded", 0)
        assert (enc_cols >= 1) == on, (on, m)
    assert res[True] == res[False]


# --------------------------------------------------------------------------
# structural kill-switch reversion (acceptance criterion)
# --------------------------------------------------------------------------


def test_killswitch_reverts_every_path(tables):
    """Mirror of test_async_pipeline's plan-shape reversion: the switch
    is structural, so OFF must mean zero encoded columns anywhere —
    scans, shuffle reads, concats — across a shuffling join query."""
    fact, dim = tables
    sess = _sess(False, **{
        "spark.rapids.shuffle.localDeviceResident.enabled": False,
        "spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    _rows(_q_join(sess, fact, dim))
    m = sess.last_query_metrics
    assert m.get("encodedColumnsEncoded", 0) == 0, m
    assert m.get("encodedDictFilters", 0) == 0, m
    assert m.get("joinCodeLowerings", 0) in (0.0, 0, None), m
    assert m.get("encodedWireDictInline", 0) == 0, m
    # and the scan upload cache keys on the switch: flipping it ON in a
    # fresh session over the SAME tables serves ENCODED batches (the
    # dict filter fast path engages), never the raw entries the OFF
    # session just cached.  The upload cache is process-shared across
    # sessions (docs/serving.md), so the encode itself may have happened
    # in an earlier test over these module-scoped tables — assert the
    # representation served, not a fresh-encode counter delta.
    from spark_rapids_tpu.sql.physical.kernel_cache import (
        release_compiled_programs)
    release_compiled_programs()  # dict_filters counts trace-time hits
    sess_on = _sess(True, **{
        "spark.rapids.sql.autoBroadcastJoinThreshold": 1})
    _rows(_q_filter(sess_on, fact, dim))
    assert sess_on.last_query_metrics.get("encodedDictFilters", 0) >= 1
