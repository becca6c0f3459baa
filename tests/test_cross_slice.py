"""Two-tier (ICI + DCN) shuffle: a 2-slice exchange where each slice
reads only the reduce partitions it owns, pulling the peer slice's
contributions over the TCP (DCN) plane while its own blocks stay on the
local (ICI-tier) store (SURVEY §2.8; reference UCX transport SPI + peer
registry)."""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.convert import arrow_to_device
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.parallel.topology import SliceTopology
from spark_rapids_tpu.shuffle import manager as M
from spark_rapids_tpu.shuffle.manager import ShuffleManager
from spark_rapids_tpu.shuffle.transport import ShuffleHeartbeatManager
from spark_rapids_tpu.shuffle.tcp import TcpShuffleTransport


def test_topology_ownership():
    t = SliceTopology(4, 1)
    owners = [t.owner_of(r, 8) for r in range(8)]
    assert owners == [0, 0, 1, 1, 2, 2, 3, 3]
    assert t.local_partitions(8) == [2, 3]
    # uneven split: ceil-block ownership covers every partition
    t2 = SliceTopology(3, 2)
    assert sorted(sum(([r for r in range(7)
                        if SliceTopology(3, s).is_local(r, 7)]
                      for s in range(3)), [])) == list(range(7))


def test_from_conf_single_slice_is_none():
    assert SliceTopology.from_conf(RapidsConf.get_global()) is None


def _mk_batch(vals):
    return arrow_to_device(pa.table({"v": pa.array(vals, pa.int64())}))


def test_two_slice_exchange_over_dcn():
    """Each slice holds one map task's output for ALL 4 reduce
    partitions; topology says slice 0 owns partitions {0,1} and slice 1
    owns {2,3}.  Each slice reduces ONLY its own partitions: its own
    map's blocks come off the local store (ICI tier), the peer's blocks
    cross the TCP (DCN) plane."""
    registry = ShuffleHeartbeatManager()
    confs, mgrs = [], []
    try:
        for sid in (0, 1):
            conf = RapidsConf.get_global().copy({
                "spark.rapids.shuffle.mode": "ICI",
                "spark.rapids.shuffle.topology.numSlices": 2,
                "spark.rapids.shuffle.topology.sliceId": sid,
            })
            t = TcpShuffleTransport(f"slice-{sid}")
            m = ShuffleManager(conf, transport=t,
                               executor_id=f"slice-{sid}",
                               heartbeats=registry)
            confs.append(conf)
            mgrs.append(m)
        nt = 4
        sid0, sid1 = mgrs
        assert sid0.topology.multi_slice
        assert sid0.topology.local_partitions(nt) == [0, 1]
        assert sid1.topology.local_partitions(nt) == [2, 3]

        # map side: slice s's map task m=s produced rows 100*s + 10*r + i
        # for each target partition r
        shuffle_id = 77
        for s, mgr in enumerate(mgrs):
            pieces = [_mk_batch([100 * s + 10 * r + i for i in range(3)])
                      for r in range(nt)]
            mgr.write_map_output(shuffle_id, s, pieces)

        M.TIER_STATS.update(local_blocks=0, dcn_fetches=0)
        got = {}
        for mgr in mgrs:
            for r in mgr.topology.local_partitions(nt):
                b = mgr.read_reduce_partition(shuffle_id, len(mgrs), r)
                assert b is not None
                import jax
                host = jax.device_get(b)
                from spark_rapids_tpu.columnar.convert import device_to_arrow
                vals = device_to_arrow(host).column("v").to_pylist()
                got[r] = sorted(vals)
        # completeness: partition r holds both slices' contributions
        for r in range(nt):
            assert got[r] == sorted([10 * r + i for i in range(3)]
                                    + [100 + 10 * r + i for i in range(3)])
        # tier accounting: each slice served its own 2 blocks locally and
        # pulled 2 from the peer over the TCP plane
        assert M.TIER_STATS["local_blocks"] == 4
        assert M.TIER_STATS["dcn_fetches"] == 4
    finally:
        for m in mgrs:
            m.close()


def test_exchange_materializes_only_local_partitions():
    """Engine-level routing: with a 2-slice topology configured, a
    planned exchange in THIS process assembles only the partitions its
    slice owns — the peer slice's partitions stay empty here (their
    blocks remain published for the peer to pull over DCN)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    import pandas as pd

    sess = srt.session(**{
        "spark.rapids.shuffle.topology.numSlices": 2,
        "spark.rapids.shuffle.topology.sliceId": 0,
        "spark.sql.adaptive.enabled": False,  # keep nt partitions
    })
    try:
        n, G = 50_000, 1_000
        rng = np.random.default_rng(0)
        t = pa.table({"k": rng.integers(0, G, n), "v": rng.random(n)})
        df = sess.create_dataframe(t, num_partitions=4)
        got = (df.groupBy("k").agg(F.sum(F.col("v")).alias("s"))
               .collect().to_pandas())
        # slice 0 produced a strict, correct subset: every returned group
        # matches the oracle, but the peer slice's share is absent
        exp = t.to_pandas().groupby("k").agg(s=("v", "sum"))
        assert 0 < len(got) < G
        for _, row in got.head(50).iterrows():
            assert abs(exp.loc[row["k"], "s"] - row["s"]) < 1e-9
    finally:
        srt.session(**{"spark.rapids.shuffle.topology.numSlices": 1,
                       "spark.sql.adaptive.enabled": True})


def test_multi_slice_map_ids_namespaced():
    """Engine exchanges namespace map ids by slice (base = sliceId *
    num_maps) so two slices' blocks for the same shuffle never collide —
    the condition that lets a reducing slice see BOTH slices'
    contributions (review r3 finding)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.shuffle.manager import get_shuffle_manager
    sess = srt.session(**{
        "spark.rapids.shuffle.topology.numSlices": 2,
        "spark.rapids.shuffle.topology.sliceId": 1,
        "spark.sql.adaptive.enabled": False})
    try:
        rng = np.random.default_rng(2)
        t = pa.table({"k": rng.integers(0, 500, 20_000),
                      "v": rng.random(20_000)})
        df = sess.create_dataframe(t, num_partitions=4)
        df.groupBy("k").agg(F.sum(F.col("v")).alias("s")).collect()
        mgr = get_shuffle_manager(sess._conf)
        ids = {b.map_id for b in mgr._files}
        assert ids, "no blocks published"
        # slice 1's bases are num_maps*1 per exchange (4 and 8 here) — no
        # id may sit in slice 0's namespace [0, num_maps)
        assert min(ids) >= 4, sorted(ids)
        # deferred cleanup keeps blocks for the peer's TTL window
        assert mgr._pending_cleanup
    finally:
        srt.session(**{"spark.rapids.shuffle.topology.numSlices": 1,
                       "spark.sql.adaptive.enabled": True})
