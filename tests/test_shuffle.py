"""Shuffle subsystem tests — serializer round-trips, the three manager
modes, transport SPI with a mock (reference strategy: unit-test distributed
logic at the SPI seam, RapidsShuffleClientSuite.scala:449), heartbeat
registry, and the ICI mesh data plane on the virtual 8-device mesh."""

import socket
import time

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.convert import arrow_to_device, device_to_arrow
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.shuffle import (FETCH_STATS, FrameCorrupt,
                                      LocalTransport, PeerBlacklist,
                                      ShuffleFetchFailed,
                                      ShuffleHeartbeatManager,
                                      ShuffleManager, concat_serialized,
                                      deserialize_batch, serialize_batch)
from spark_rapids_tpu.shuffle.transport import BlockId, PeerInfo


@pytest.fixture()
def sess():
    return srt.session()


def rich_table(n=200, seed=5):
    rng = np.random.default_rng(seed)
    return pa.table({
        "i": pa.array([None if k % 11 == 0 else int(v) for k, v in
                       enumerate(rng.integers(-9999, 9999, n))],
                      type=pa.int64()),
        "f": pa.array(rng.random(n), type=pa.float64()),
        "s": pa.array([None if k % 7 == 0 else f"str-{k}"
                       for k in range(n)]),
        "b": pa.array(rng.integers(0, 2, n).astype(bool)),
        "arr": pa.array([[k, k + 1] if k % 3 else [] for k in range(n)],
                        type=pa.list_(pa.int64())),
        "st": pa.array([{"a": k, "b": f"x{k}"} for k in range(n)],
                       type=pa.struct([("a", pa.int64()), ("b", pa.string())])),
    })


def test_serializer_roundtrip_rich_types():
    t = rich_table()
    b = arrow_to_device(t)
    frame = serialize_batch(b)
    rt = deserialize_batch(frame)
    back = device_to_arrow(rt)
    assert back.to_pylist() == t.to_pylist()


def test_serializer_packs_live_rows_only():
    from spark_rapids_tpu.config import RapidsConf
    t = rich_table(10)
    b = arrow_to_device(t, capacity=4096)  # huge padding
    # dictionary refs off: the second frame would otherwise replace its
    # (identical) dictionary with a registry ref, shrinking it for a
    # reason unrelated to the padding contract under test
    conf = RapidsConf(
        {"spark.rapids.tpu.sql.encoded.shuffle.dictRefs.enabled": False})
    frame_padded = serialize_batch(b, conf)
    frame_tight = serialize_batch(arrow_to_device(t), conf)
    # padding must not be shipped: both frames within a small delta
    assert abs(len(frame_padded) - len(frame_tight)) < 128


def test_concat_serialized():
    t = rich_table(50)
    b = arrow_to_device(t)
    out = concat_serialized([serialize_batch(b), serialize_batch(b)])
    assert out.num_rows_int == 100
    back = device_to_arrow(out)
    assert back.to_pylist() == t.to_pylist() + t.to_pylist()


@pytest.mark.parametrize("mode", ["SORT", "MULTITHREADED", "ICI"])
def test_manager_modes(tmp_path, mode):
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", mode)
    conf.set("spark.rapids.memory.spillDir", str(tmp_path))
    mgr = ShuffleManager(conf)
    t = rich_table(64)
    b = arrow_to_device(t)
    sid = mgr.new_shuffle_id()
    # 3 maps x 2 reduce partitions
    for m in range(3):
        mgr.write_map_output(sid, m, [b.sliced(0, 30), b.sliced(30, 34)])
    r0 = mgr.read_reduce_partition(sid, 3, 0)
    r1 = mgr.read_reduce_partition(sid, 3, 1)
    assert r0.num_rows_int == 90
    assert r1.num_rows_int == 102
    mgr.cleanup(sid)
    assert mgr.read_reduce_partition(sid, 3, 0) is None


def test_transport_spi_with_mock_fetch():
    """Unit-test the ICI fetch path with an injected transport failure +
    peer fallback — no cluster, no network (reference test strategy)."""
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", "ICI")
    hb = ShuffleHeartbeatManager()
    transport = LocalTransport()
    a = ShuffleManager(conf, transport, "exec-A", hb)
    bmgr = ShuffleManager(conf, transport, "exec-B", hb)
    t = rich_table(20)
    batch = arrow_to_device(t)
    sid = 7
    # exec-B wrote the block; exec-A's local lookup misses, peer fetch hits
    bmgr.write_map_output(sid, 0, [batch])
    got = a.read_reduce_partition(sid, 1, 0)
    assert got is not None and got.num_rows_int == 20

    # injected failure: hook returns corrupted-frame marker for B's block
    calls = []

    def hook(peer, block):
        calls.append((peer.executor_id, block))
        return None  # fall through to the real store

    transport.fetch_hook = hook
    got2 = a.read_reduce_partition(sid, 1, 0)
    assert got2 is not None and got2.num_rows_int == 20
    assert any(p == "exec-B" for p, _ in calls)


def test_heartbeat_expiry():
    hb = ShuffleHeartbeatManager(heartbeat_timeout_s=0.0)
    hb.register("e1", "ep1")
    peers = hb.register("e2", "ep2")
    assert [p.executor_id for p in peers] == ["e1"]
    # timeout 0: the next heartbeat expires everyone else
    import time
    time.sleep(0.01)
    assert hb.heartbeat("e2") == []
    assert hb.executors() == ["e2"]


def test_exchange_through_manager_end_to_end(sess):
    """Multi-partition hash exchange through the real serializer path."""
    rng = np.random.default_rng(0)
    t = pa.table({"k": rng.integers(0, 20, 3000), "v": rng.random(3000)})
    df = sess.create_dataframe(t, num_partitions=5)
    from spark_rapids_tpu.sql import functions as F
    out = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"),
                               F.count("*").alias("c"))
           .collect().to_pandas().sort_values("k"))
    exp = t.to_pandas().groupby("k").agg(s=("v", "sum"), c=("v", "count"))
    assert np.allclose(out["s"].values, exp["s"].values)
    assert (out["c"].values == exp["c"].values).all()


def test_ici_mesh_data_plane():
    """Row exchange over the 8-device mesh via lax.all_to_all: every row
    lands on its hash-designated chip exactly once."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import Mesh, PartitionSpec as P
    try:
        from jax import shard_map
    except ImportError:
        pytest.skip("jax.shard_map unavailable in this environment")
    from spark_rapids_tpu.parallel.shuffle import build_ici_shuffle

    n_dev = 8
    rows_per = 64
    devices = np.array(jax.devices()[:n_dev])
    mesh = Mesh(devices, ("data",))
    exchange = build_ici_shuffle(mesh, "data", n_dev, rows_per)

    @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
             out_specs=(P("data"), P("data")))
    def step(keys, vals):
        pids = (keys % n_dev).astype(jnp.int32)
        out, rvalid = exchange({"k": keys, "v": vals},
                               jnp.ones(keys.shape[0], bool), pids)
        # compact received rows: count + checksum per chip
        cnt = jnp.sum(rvalid).astype(jnp.int64)
        ksum = jnp.sum(jnp.where(rvalid, out["k"], 0))
        vsum = jnp.sum(jnp.where(rvalid, out["v"], 0.0))
        return cnt[None], jnp.stack([ksum.astype(jnp.float64), vsum])[None]

    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, 1000, n_dev * rows_per))
    vals = jnp.asarray(rng.random(n_dev * rows_per))
    with mesh:
        cnts, sums = jax.jit(step)(keys, vals)
    cnts = np.asarray(cnts)
    assert cnts.sum() == n_dev * rows_per  # no rows lost or duplicated
    hk = np.asarray(keys)
    hv = np.asarray(vals)
    ks = np.asarray(sums)[:, 0]
    vs = np.asarray(sums)[:, 1]
    for d in range(n_dev):
        m = (hk % n_dev) == d
        assert ks[d] == hk[m].sum(), d
        assert np.isclose(vs[d], hv[m].sum()), d


def test_device_resident_local_tier(tmp_path):
    """Local SORT/MULTITHREADED blocks stay device-resident in the spill
    catalog (no serialize round trip) and serialize only when the tier is
    off (reference RapidsCachingWriter + ShuffleBufferCatalog)."""
    for resident, mode in ((True, "MULTITHREADED"), (False, "SORT")):
        conf = RapidsConf()
        conf.set("spark.rapids.shuffle.mode", mode)
        conf.set("spark.rapids.memory.spillDir", str(tmp_path))
        conf.set("spark.rapids.shuffle.localDeviceResident.enabled",
                 str(resident).lower())
        mgr = ShuffleManager(conf)
        t = rich_table(64)
        b = arrow_to_device(t)
        sid = mgr.new_shuffle_id()
        for m in range(2):
            mgr.write_map_output(sid, m, [b.sliced(0, 30), b.sliced(30, 34)])
        if resident:
            assert mgr._resident and not mgr._files
        else:
            assert mgr._files and not mgr._resident
        r0 = mgr.read_reduce_partition(sid, 2, 0)
        r1 = mgr.read_reduce_partition(sid, 2, 1)
        assert r0.num_rows_int == 60 and r1.num_rows_int == 68
        mgr.cleanup(sid)
        assert not mgr._resident and not mgr._files
        assert mgr.read_reduce_partition(sid, 2, 0) is None


# ---------------------------------------------------------------------------
# resilient fetch protocol: retry/backoff/deadline, blacklist, recompute
# ---------------------------------------------------------------------------

def _ici_pair(fetch_conf=None):
    """exec-A reading blocks exec-B published over a shared mock
    transport — the SPI seam every protocol test drives."""
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", "ICI")
    for k, v in (fetch_conf or {}).items():
        conf.set(k, v)
    hb = ShuffleHeartbeatManager()
    transport = LocalTransport()
    a = ShuffleManager(conf, transport, "exec-A", hb)
    b = ShuffleManager(conf, transport, "exec-B", hb)
    return a, b, transport


def test_fetch_retry_backoff_ordering(monkeypatch):
    """Transient fetch failures retry with exponentially increasing
    backoff (plus jitter) and then succeed; retries are counted."""
    a, b, transport = _ici_pair({
        "spark.rapids.tpu.shuffle.fetch.maxRetries": 6,
        "spark.rapids.tpu.shuffle.fetch.backoffMs": 20,
    })
    batch = arrow_to_device(rich_table(16))
    b.write_map_output(9, 0, [batch])

    fails = [3]

    def hook(peer, block):
        if fails[0] > 0:
            fails[0] -= 1
            raise ShuffleFetchFailed("transient (test hook)")
        return None  # fall through to the real store

    transport.fetch_hook = hook
    delays = []
    real_sleep = time.sleep
    monkeypatch.setattr(time, "sleep", lambda s: delays.append(s))
    retries0 = FETCH_STATS["retries"]
    got = a.read_reduce_partition(9, 1, 0)
    monkeypatch.setattr(time, "sleep", real_sleep)
    assert got is not None and got.num_rows_int == 16
    assert FETCH_STATS["retries"] - retries0 == 3
    assert len(delays) == 3
    # exponential ordering: each delay at least the base, monotonically
    # increasing, jitter bounded at +25%
    assert delays[0] >= 0.02 and delays[0] <= 0.02 * 1.26
    assert delays[0] < delays[1] < delays[2]
    assert delays[2] <= 0.08 * 1.26


def test_fetch_deadline_expiry():
    """The per-reduce deadline bounds the retry loop even when
    maxRetries would allow many more attempts."""
    a, b, transport = _ici_pair({
        "spark.rapids.tpu.shuffle.fetch.maxRetries": 1000,
        "spark.rapids.tpu.shuffle.fetch.backoffMs": 30,
        "spark.rapids.tpu.shuffle.fetch.deadlineMs": 120,
    })
    batch = arrow_to_device(rich_table(16))
    b.write_map_output(3, 0, [batch])

    def hook(peer, block):
        raise ShuffleFetchFailed("always down (test hook)")

    transport.fetch_hook = hook
    t0 = time.monotonic()
    with pytest.raises(ShuffleFetchFailed):
        a.read_reduce_partition(3, 1, 0)
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0, "deadline must stop a 1000-retry budget early"


def test_timeout_surfaces_as_shuffle_fetch_failed():
    """Regression (satellite): a socket.timeout (OSError subclass) from
    the transport must surface as ShuffleFetchFailed — never a bare
    network exception, never a silent None masquerading as an empty
    partition."""
    a, b, transport = _ici_pair({
        "spark.rapids.tpu.shuffle.fetch.maxRetries": 0,
        "spark.rapids.tpu.shuffle.fetch.backoffMs": 1,
    })
    batch = arrow_to_device(rich_table(8))
    b.write_map_output(4, 0, [batch])

    def hook(peer, block):
        raise socket.timeout("recv timed out (test hook)")

    transport.fetch_hook = hook
    with pytest.raises(ShuffleFetchFailed) as ei:
        a.read_reduce_partition(4, 1, 0)
    assert isinstance(ei.value.__cause__, socket.timeout)


def test_peer_blacklist_unit():
    bl = PeerBlacklist(threshold=2, ttl_s=0.05)
    assert bl.record_failure("p1") is False
    assert bl.record_failure("p1") is True      # newly blacklisted
    assert bl.record_failure("p1") is False     # already benched
    assert bl.is_blacklisted("p1")
    peers = [PeerInfo("p1", "e1"), PeerInfo("p2", "e2")]
    assert [p.executor_id for p in bl.order(peers)] == ["p2", "p1"]
    time.sleep(0.06)
    assert bl.reinstate_expired() == ["p1"]     # heartbeat-driven
    assert not bl.is_blacklisted("p1")
    assert [p.executor_id for p in bl.order(peers)] == ["p1", "p2"]
    # a success clears strikes immediately
    bl.record_failure("p2")
    bl.record_success("p2")
    assert bl.record_failure("p2") is False


def test_peer_blacklist_integration():
    """A repeatedly-failing peer gets benched (counted) and drops to
    last-resort ordering; a healthy peer still serves the block."""
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", "ICI")
    conf.set("spark.rapids.tpu.shuffle.fetch.maxRetries", 0)
    conf.set("spark.rapids.tpu.shuffle.fetch.blacklistAfter", 2)
    hb = ShuffleHeartbeatManager()
    transport = LocalTransport()
    a = ShuffleManager(conf, transport, "exec-A", hb)
    bad = ShuffleManager(conf, transport, "exec-BAD", hb)
    good = ShuffleManager(conf, transport, "exec-GOOD", hb)
    batch = arrow_to_device(rich_table(12))
    good.write_map_output(5, 0, [batch])

    calls = []

    def hook(peer, block):
        calls.append(peer.executor_id)
        if peer.executor_id == "exec-BAD":
            raise ShuffleFetchFailed("peer dead (test hook)")
        return None

    transport.fetch_hook = hook
    bl0 = FETCH_STATS["blacklisted"]
    for _ in range(3):
        got = a.read_reduce_partition(5, 1, 0)
        assert got is not None and got.num_rows_int == 12
    assert FETCH_STATS["blacklisted"] - bl0 == 1
    assert a._blacklist.is_blacklisted("exec-BAD")
    # benched peer is ordered last on the next read: the healthy peer is
    # tried (and answers) before exec-BAD is ever contacted
    calls.clear()
    a.read_reduce_partition(5, 1, 0)
    peer_calls = [c for c in calls if c != "exec-A"]
    assert peer_calls and peer_calls[0] == "exec-GOOD"


def test_lost_block_recompute_bit_parity(tmp_path):
    """Destroying a committed block's backing file and re-reading through
    the registered lineage callback reproduces the partition
    bit-identically (the FetchFailed->stage-retry contract at batch
    granularity)."""
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", "SORT")
    conf.set("spark.rapids.memory.spillDir", str(tmp_path))
    conf.set("spark.rapids.shuffle.localDeviceResident.enabled", "false")
    mgr = ShuffleManager(conf)
    t = rich_table(64)
    b = arrow_to_device(t)
    sid = mgr.new_shuffle_id()
    pieces = {0: [b.sliced(0, 30), b.sliced(30, 34)],
              1: [b.sliced(34, 20), b.sliced(54, 10)]}
    for m, ps in pieces.items():
        mgr.write_map_output(sid, m, ps)
    baseline = device_to_arrow(
        mgr.read_reduce_partition(sid, 2, 0)).to_pylist()

    mgr.register_recompute(
        sid, lambda map_id: mgr.write_map_output(sid, map_id,
                                                 pieces[map_id]))
    import os
    victim = BlockId(sid, 1, 0)
    os.unlink(mgr._files[victim])
    rec0 = FETCH_STATS["recomputed"]
    again = device_to_arrow(
        mgr.read_reduce_partition(sid, 2, 0)).to_pylist()
    assert FETCH_STATS["recomputed"] - rec0 == 1
    assert again == baseline


def test_no_recompute_without_lineage_raises(tmp_path):
    """Without a registered callback, a lost committed block fails the
    read loudly — it must not read back as an empty partition."""
    conf = RapidsConf()
    conf.set("spark.rapids.shuffle.mode", "SORT")
    conf.set("spark.rapids.memory.spillDir", str(tmp_path))
    conf.set("spark.rapids.shuffle.localDeviceResident.enabled", "false")
    conf.set("spark.rapids.tpu.shuffle.fetch.backoffMs", 1)
    mgr = ShuffleManager(conf)
    b = arrow_to_device(rich_table(16))
    sid = mgr.new_shuffle_id()
    mgr.write_map_output(sid, 0, [b])
    import os
    os.unlink(mgr._files[BlockId(sid, 0, 0)])
    with pytest.raises(ShuffleFetchFailed):
        mgr.read_reduce_partition(sid, 1, 0)


def test_torn_frame_stream_raises():
    from spark_rapids_tpu.shuffle.manager import pack_frames, split_frames
    blob = pack_frames([b"abcdef", b"0123"])
    assert split_frames(blob) == [b"abcdef", b"0123"]
    with pytest.raises(FrameCorrupt):
        split_frames(blob[:-1])          # torn final frame
    with pytest.raises(FrameCorrupt):
        split_frames(blob + b"\x01")     # torn length prefix


# --------------------------------------------------------------------------
# the local plane as cached programs: one per map, one to shrink, one per
# merge of pieces
# --------------------------------------------------------------------------

def _map_outputs(attrs, parts):
    """A leaf exec whose partitions are the device batches handed to it."""
    from spark_rapids_tpu.sql.physical.base import TPU, PhysicalPlan

    class Leaf(PhysicalPlan):
        backend = TPU
        output = attrs

        def num_partitions(self):
            return len(parts)

        def execute(self, pid, tctx):
            yield from parts[pid]
    return Leaf()


def _exchange_input(kind, n_maps=4):
    """Map outputs with int64, double, string and nullable columns; over
    4,096 rows each, so the pieces shrink.  ``dict``: the string column is
    low-cardinality (the scan keeps it dict-encoded) and every map draws
    from other values, so the dictionaries differ."""
    from spark_rapids_tpu.sql.expressions.core import AttributeReference
    parts = []
    for m in range(n_maps):
        n = 5000 + 700 * m
        rng = np.random.default_rng(m)
        if kind == "dict":
            s = [f"v{m}-{k % 3}" for k in range(n)]
        else:
            s = [None if k % 7 == 0 else "s" * (k % 13) + str(k)
                 for k in range(n)]
        parts.append([arrow_to_device(pa.table({
            "i": pa.array([None if k % 11 == 0 else int(v) for k, v in
                           enumerate(rng.integers(-9999, 9999, n))],
                          type=pa.int64()),
            "f": pa.array(rng.random(n), type=pa.float64()),
            "s": pa.array(s, type=pa.string()),
        }))])
    b0 = parts[0][0]
    attrs = [AttributeReference(n, c.dtype, True)
             for n, c in zip(b0.names, b0.columns)]
    return attrs, parts


def _old_path_partitions(ex, parts, nt):
    """What the exchange returned before it ran as programs: an eager
    partitioner, one stable compaction per target, ``shrunk()`` per
    piece and the per-array concat that is still the fallback."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import batch as B
    from spark_rapids_tpu.sql.expressions.core import EvalContext
    from spark_rapids_tpu.sql.physical.basic import compact_batch
    pieces = [[] for _ in range(nt)]
    for m, (merged,) in enumerate(parts):
        pids = ex.partitioning.partition_ids(EvalContext(merged), merged, m)
        for t in range(nt):
            p = compact_batch(jnp, merged,
                              (pids == t) & merged.row_mask()).shrunk()
            if p.num_rows_int:
                pieces[t].append(p)
    out = []
    for ps in pieces:
        counts = [p.num_rows_int for p in ps]
        rows = B._EagerRows(counts, B.bucket_capacity(sum(counts)))
        cols = [B._concat_columns([p.columns[ci] for p in ps], rows)
                for ci in range(ps[0].num_cols)]
        out.append(ColumnarBatch.make(ps[0].names, cols, sum(counts)))
    return out


@pytest.mark.parametrize("kind,partitioner", [
    ("plain", "hash"), ("plain", "roundrobin"), ("dict", "hash")])
def test_local_exchange_runs_as_programs(sess, kind, partitioner):
    """4 maps x 4 targets: (a) every reduce partition holds the rows the
    old path returns, in its order; (b) a second materialization traces
    nothing; (c) plain columns never take the per-array path, pieces over
    dictionaries that differ do, and still give the right rows."""
    from spark_rapids_tpu.parallel.partitioning import (
        HashPartitioning, RoundRobinPartitioning)
    from spark_rapids_tpu.sql.physical import exchange as X
    from spark_rapids_tpu.sql.physical import kernel_cache as KC
    from spark_rapids_tpu.sql.physical.base import TaskContext
    nt = 4
    attrs, parts = _exchange_input(kind)
    part = (HashPartitioning([attrs[0]], nt) if partitioner == "hash"
            else RoundRobinPartitioning(nt))

    def materialize():
        ex = X.ShuffleExchangeExec(part, _map_outputs(attrs, parts),
                                   coalescible=False)
        before = dict(X.STATS)
        got = [list(ex.execute(t, TaskContext(0, sess.conf)))
               for t in range(nt)]
        return ex, got, {k: X.STATS[k] - before[k] for k in before}

    ex, got, stats = materialize()
    assert stats["map_programs"] == stats["shrink_programs"] == 4
    if kind == "plain":
        assert stats["eager_fallbacks"] == 0
        assert stats["concat_programs"] == nt
    else:
        assert stats["eager_fallbacks"] == nt       # the four merges
        assert stats["concat_programs"] == 0
    expected = _old_path_partitions(ex, parts, nt)
    for (g,), e in zip(got, expected):
        assert g.capacity == e.capacity
        assert g._nrows_host == e.num_rows_int
        assert device_to_arrow(g).equals(device_to_arrow(e))

    retraces = KC.cache_stats()["retraces"]
    _, again, stats2 = materialize()
    assert KC.cache_stats()["retraces"] == retraces
    assert "wrapped" not in KC.retraces_by_name()
    assert stats2 == stats
    for (g,), (a,) in zip(got, again):
        assert device_to_arrow(g).equals(device_to_arrow(a))


def test_one_dictionary_merges_in_the_program(sess):
    """Pieces dict-encoded over ONE dictionary are no reason to decline:
    their codes concatenate like any int32 column, the dictionary object
    is shared and stays outside the program."""
    from spark_rapids_tpu.columnar import batch as B
    from spark_rapids_tpu.columnar.encoded import DictEncodedColumn
    t = pa.table({"k": pa.array(range(6000), type=pa.int64()),
                  "s": pa.array([f"v{k % 3}" for k in range(6000)])})
    whole = arrow_to_device(t)
    assert isinstance(whole.column("s"), DictEncodedColumn)
    pieces = [whole.sliced(0, 2500), whole.sliced(2500, 3500)]
    assert B.concat_declined(pieces) == ""
    before = dict(B.CONCAT_STATS)
    out = ColumnarBatch.concat(pieces)
    assert B.CONCAT_STATS["programs"] - before["programs"] == 1
    assert B.CONCAT_STATS["eager"] == before["eager"]
    assert out.column("s").dictionary is whole.column("s").dictionary
    assert device_to_arrow(out).equals(t)
    from spark_rapids_tpu.memory import retention
    assert not retention.is_transient(out)   # the dictionary is shared


def test_range_exchange_keeps_the_eager_partitioner(sess):
    """A range exchange's bounds are data of one materialization: its
    maps keep the eager partitioner and count as fallbacks; the global
    sort above it is still right."""
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.sql.physical import exchange as X
    rng = np.random.default_rng(3)
    t = pa.table({"k": rng.integers(0, 10**6, 20000),
                  "v": rng.random(20000)})
    s = srt.session(**{"spark.sql.shuffle.partitions": 4,
                       "spark.sql.adaptive.enabled": False})
    before = dict(X.STATS)
    out = (s.create_dataframe(t, num_partitions=3).orderBy(F.col("k"))
           .collect().to_pandas())
    assert X.STATS["eager_fallbacks"] - before["eager_fallbacks"] >= 3
    assert X.STATS["map_programs"] == before["map_programs"]
    assert (np.diff(out["k"].values) >= 0).all() and len(out) == 20000


def test_range_exchange_over_string_keys_of_uneven_width(sess):
    """The bounds' byte matrix is as wide as the widest key sampled over
    every map output, a map output's only as wide as its own keys: the
    partitioner compares them at one width."""
    from spark_rapids_tpu.sql import functions as F
    keys = ["a" * (1 + i % 3) if i < 500 else "b" * (1 + i % 20)
            for i in range(2000)]
    t = pa.table({"s": keys, "v": np.arange(2000, dtype=np.float64)})
    s = srt.session(**{"spark.sql.shuffle.partitions": 4,
                       "spark.sql.adaptive.enabled": False})
    out = (s.create_dataframe(t, num_partitions=4).orderBy(F.col("s"))
           .collect().to_pandas())
    assert list(out["s"]) == sorted(keys)
    assert sorted(out["v"]) == list(range(2000))
