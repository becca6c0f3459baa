"""Whole-stage XLA compilation (ISSUE 7, docs/whole_stage.md): terminal
stage formation (aggregate + join probe), fused-vs-killswitched bit
parity over encoded x parallelism, lazy program registration, donation
safety (retention registry), and the coverage/dispatch metrics."""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.memory import retention
from spark_rapids_tpu.sql import functions as F
from spark_rapids_tpu.sql.physical.fusion import FusedStageExec
from spark_rapids_tpu.sql.physical.aggregate import HashAggregateExec
from spark_rapids_tpu.sql.physical.join import BaseJoinExec


ROWS = 4000


def _tables():
    rng = np.random.default_rng(17)
    cats = [f"cat_{i:02d}" for i in range(12)]
    fact = pa.table({
        "k": rng.integers(0, 7, ROWS).astype(np.int64),
        "ck": pa.array([cats[i] for i in rng.integers(0, 12, ROWS)]),
        "q": rng.integers(0, 100, ROWS).astype(np.int64),
        "v": rng.random(ROWS),
        "fk": rng.integers(0, 200, ROWS).astype(np.int64),
    })
    # dim covers only half the key space so anti/outer joins have teeth
    dim = pa.table({"pk": np.arange(0, 200, 2, dtype=np.int64),
                    "w": rng.random(100)})
    return fact, dim


FACT, DIM = _tables()


def _session(whole_stage=True, fusion=True, encoded=False, parallelism=1,
             **extra):
    over = {
        "spark.rapids.tpu.sql.fusion.enabled": fusion,
        "spark.rapids.tpu.sql.wholeStage.enabled": whole_stage,
        "spark.rapids.tpu.sql.encoded.enabled": encoded,
        "spark.rapids.tpu.task.parallelism": parallelism,
    }
    over.update(extra)
    return srt.session(conf=RapidsConf.get_global().copy(over))


def _canon(table: pa.Table) -> pd.DataFrame:
    df = table.to_pandas()
    return df.sort_values(list(df.columns), kind="mergesort") \
        .reset_index(drop=True)


def _q_filter_project_agg(sess):
    f = sess.create_dataframe(FACT, num_partitions=4)
    return (f.filter(F.col("q") < 60)
            .withColumn("y", F.col("v") * 2.0)
            .groupBy("k")
            .agg(F.sum(F.col("y")).alias("sy"), F.count("*").alias("c"))
            .orderBy("k"))


def _q_complete_agg(sess):
    f = sess.create_dataframe(FACT)  # single partition -> complete mode
    return (f.filter(F.col("q") >= 20).groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv")).orderBy("k"))


def _q_map_chain(sess):
    f = sess.create_dataframe(FACT, num_partitions=2)
    return (f.filter(F.col("q") < 80)
            .withColumn("y", F.col("v") + 1.0)
            .filter(F.col("v") < 0.9)
            .select("k", "y"))


def _q_probe_join(sess, how="inner"):
    f = sess.create_dataframe(FACT, num_partitions=4)
    d = sess.create_dataframe(DIM)
    return (f.filter(F.col("q") < 50)
            .withColumn("y", F.col("v") * 3.0)
            .join(d, f.fk == d.pk, how))


def _q_encoded_filter_agg(sess):
    f = sess.create_dataframe(FACT, num_partitions=4)
    return (f.filter(F.col("ck") <= "cat_07").groupBy("ck")
            .agg(F.sum(F.col("q")).alias("sq"), F.count("*").alias("n"))
            .orderBy("ck"))


# --------------------------------------------------------------------------
# plan shape
# --------------------------------------------------------------------------

def _find(plan, pred):
    out = []
    stack = [plan]
    while stack:
        n = stack.pop()
        if pred(n):
            out.append(n)
        stack.extend(n.children)
    return out


def test_agg_terminal_stage_in_plan():
    sess = _session()
    plan = sess.physical_plan(_q_filter_project_agg(sess))
    stages = _find(plan, lambda n: isinstance(n, FusedStageExec)
                   and isinstance(n.terminal, HashAggregateExec))
    assert stages, plan.tree_string()
    st = stages[0]
    assert st.terminal.mode == "partial"
    assert len(st.members) == 2  # filter + project
    assert st.terminal._pre_steps  # chain absorbed into the partial kernel


def test_probe_terminal_in_plan():
    sess = _session()
    plan = sess.physical_plan(_q_probe_join(sess))
    joins = _find(plan, lambda n: isinstance(n, BaseJoinExec))
    assert joins and joins[0]._probe_steps, plan.tree_string()
    assert "fusedProbe" in joins[0].simple_string()


def test_killswitch_reverts_plan():
    sess = _session(whole_stage=False)
    plan = sess.physical_plan(_q_filter_project_agg(sess))
    assert not _find(plan, lambda n: isinstance(n, FusedStageExec)
                     and n.terminal is not None)
    joins = _find(sess.physical_plan(_q_probe_join(sess)),
                  lambda n: isinstance(n, BaseJoinExec))
    assert joins and not joins[0]._probe_steps
    # fusion fully off: no FusedStage nodes at all
    off = _session(fusion=False)
    plan = off.physical_plan(_q_map_chain(off))
    assert not _find(plan, lambda n: isinstance(n, FusedStageExec))


def test_lazy_plan_registers_no_kernels():
    """Plan construction (incl. terminal absorption) must not touch the
    kernel cache — AQE re-plans and CPU-fallback discards pay nothing."""
    from spark_rapids_tpu.sql.physical.kernel_cache import cache_stats
    sess = _session()
    before = cache_stats()["misses"]
    sess.physical_plan(_q_filter_project_agg(sess))
    sess.physical_plan(_q_probe_join(sess))
    sess.physical_plan(_q_map_chain(sess))
    assert cache_stats()["misses"] == before


# --------------------------------------------------------------------------
# fused-vs-killswitched bit-parity matrix
# --------------------------------------------------------------------------

@pytest.mark.parametrize("encoded", [False, True])
@pytest.mark.parametrize("parallelism", [1, 4])
def test_parity_matrix(encoded, parallelism):
    shapes = {
        "filter_project_agg": _q_filter_project_agg,
        "complete_agg": _q_complete_agg,
        "map_chain": _q_map_chain,
        "probe_join": _q_probe_join,
        "encoded_filter_agg": _q_encoded_filter_agg,
    }
    on = _session(encoded=encoded, parallelism=parallelism)
    off = _session(whole_stage=False, fusion=False, encoded=encoded,
                   parallelism=parallelism)
    for name, mk in shapes.items():
        got = _canon(mk(on).collect())
        exp = _canon(mk(off).collect())
        pd.testing.assert_frame_equal(got, exp, check_exact=True), name


@pytest.mark.parametrize("how", ["inner", "left", "left_semi", "left_anti"])
def test_probe_join_parity_by_type(how):
    on = _session()
    off = _session(whole_stage=False, fusion=False)
    got = _canon(_q_probe_join(on, how).collect())
    exp = _canon(_q_probe_join(off, how).collect())
    assert len(exp) > 0  # the shape must exercise real rows
    pd.testing.assert_frame_equal(got, exp, check_exact=True)


# --------------------------------------------------------------------------
# donation safety
# --------------------------------------------------------------------------

def _device_batch(n=64):
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    col = DeviceColumn(T.LONG, jnp.arange(n, dtype=jnp.int64),
                       jnp.ones(n, dtype=bool))
    return ColumnarBatch.make(["a"], [col], n)


def test_retention_registry_unit():
    b = _device_batch()
    assert not retention.is_pinned(b)
    ok, why = retention.may_donate(b)
    assert not ok and why == "not_transient"
    retention.mark_transient(b)
    ok, why = retention.may_donate(b)
    assert ok
    retention.pin_batch(b)
    retention.pin_batch(b)
    ok, why = retention.may_donate(b)
    assert not ok and why == "pinned"
    retention.unpin_batch(b)
    assert retention.is_pinned(b)  # refcounted
    retention.unpin_batch(b)
    assert not retention.is_pinned(b)
    assert retention.may_donate(b)[0]


def test_retention_declines_encoded():
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.encoded import (DictEncodedColumn,
                                                   dictionary_from_values)
    n = 16
    d = dictionary_from_values(T.STRING, [b"a", b"b", b"c"])
    enc = DictEncodedColumn(T.STRING, jnp.zeros(n, dtype=jnp.int32), d,
                            jnp.ones(n, dtype=bool))
    b = ColumnarBatch.make(["s"], [enc], n)
    retention.mark_transient(b)
    ok, why = retention.may_donate(b)
    assert not ok and why == "encoded"


def test_donated_batch_never_reachable_from_retainers():
    """The satellite's safety proof: each retention tier pins, and a
    pinned batch is never donation-eligible."""
    # spill tier
    from spark_rapids_tpu.memory.spill import SpillableColumnarBatch
    b = retention.mark_transient(_device_batch())
    sb = SpillableColumnarBatch.create(b)
    try:
        assert retention.is_pinned(b)
        assert retention.may_donate(b) == (False, "pinned")
    finally:
        sb.close()
    # prefetch queue / transfer stager contract: pin while enqueued
    b2 = retention.mark_transient(_device_batch())
    retention.pin_batch(b2)  # what AsyncPrefetchExec does on put
    assert retention.may_donate(b2) == (False, "pinned")
    retention.unpin_batch(b2)  # consumer handoff
    assert retention.may_donate(b2)[0]
    # broadcast: the cached broadcast batch is pinned
    from spark_rapids_tpu.sql.physical.base import TaskContext
    from spark_rapids_tpu.sql.physical.exchange import BroadcastExchangeExec
    from spark_rapids_tpu.sql.physical.basic import InMemoryScanExec
    from spark_rapids_tpu.sql.expressions.core import AttributeReference
    from spark_rapids_tpu import types as T
    scan = InMemoryScanExec([AttributeReference("pk", T.LONG, False),
                             AttributeReference("w", T.DOUBLE, True)],
                            [DIM])
    bx = BroadcastExchangeExec(scan)
    bcast = bx.broadcast_batch(TaskContext(0))
    assert retention.is_pinned(bcast)
    retention.mark_transient(bcast)
    assert retention.may_donate(bcast) == (False, "pinned")


@pytest.mark.parametrize("pieces", ["fresh", "pinned"])
def test_concat_program_result_is_a_fresh_sole_owner(pieces):
    """A multi-piece concat runs as one program into fresh buffers: the
    result is marked transient whatever retains the pieces, is not
    pinned, shares no buffer with them, and leaves them usable (the
    program donates nothing)."""
    import jax
    from spark_rapids_tpu.columnar.batch import CONCAT_STATS, ColumnarBatch
    a, b = _device_batch(64), _device_batch(32)
    if pieces == "pinned":
        retention.pin_batch(a)
        retention.pin_batch(b)
    programs = CONCAT_STATS["programs"]
    out = ColumnarBatch.concat([a, b])
    assert CONCAT_STATS["programs"] == programs + 1
    assert retention.is_transient(out) and not retention.is_pinned(out)
    assert retention.may_donate(out)[0]
    assert not retention.is_transient(a)      # the pieces are as they were
    ptrs = {leaf.unsafe_buffer_pointer()
            for leaf in jax.tree_util.tree_leaves((a, b))}
    assert not ptrs & {leaf.unsafe_buffer_pointer()
                       for leaf in jax.tree_util.tree_leaves(out)}
    assert np.asarray(a.columns[0].data).tolist() == list(range(64))
    assert np.asarray(out.columns[0].data)[:96].tolist() == \
        list(range(64)) + list(range(32))
    # one live piece: the piece itself comes back, with its own marks
    assert ColumnarBatch.concat([a, b.sliced(0, 0)]) is a
    if pieces == "pinned":
        retention.unpin_batch(a)
        retention.unpin_batch(b)


def test_materialized_exchange_partitions_are_pinned():
    """What the reduce side's concat program returns is retained by the
    exchange and re-served: pinned, so never donated, though transient."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.parallel.partitioning import HashPartitioning
    from spark_rapids_tpu.sql.expressions.core import AttributeReference
    from spark_rapids_tpu.sql.physical import exchange as X
    from spark_rapids_tpu.sql.physical.base import TaskContext
    from spark_rapids_tpu.sql.physical.basic import InMemoryScanExec
    attrs = [AttributeReference("k", T.LONG, False),
             AttributeReference("v", T.DOUBLE, True)]
    fact, n = FACT.select(["k", "v"]), FACT.num_rows
    halves = [fact.slice(0, n // 2), fact.slice(n // 2)]
    ex = X.ShuffleExchangeExec(HashPartitioning([attrs[0]], 2),
                               InMemoryScanExec(attrs, halves),
                               coalescible=False)
    before = X.STATS["concat_programs"]
    served = [b for t in range(2) for b in ex.execute(t, TaskContext(0))]
    assert X.STATS["concat_programs"] == before + 2
    assert sum(b.num_rows_int for b in served) == n
    for b in served:
        assert retention.is_transient(b)
        assert retention.may_donate(b) == (False, "pinned")


def test_scan_cached_uploads_are_pinned_and_declined():
    """A fused stage directly above an in-memory scan must never donate
    the relation's resident batches."""
    sess = _session()
    f = sess.create_dataframe(FACT, num_partitions=2)
    q = (f.filter(F.col("q") < 70).filter(F.col("v") < 0.95)
         .select("k", "v"))
    before = retention.stats_snapshot()
    got = _canon(q.collect())
    m = sess.last_query_metrics
    assert m.get("wholeStageDonatedBatches", 0) == 0
    assert m.get("wholeStageDonationDeclined", 0) > 0
    # and the result still matches the unfused run
    off = _session(whole_stage=False, fusion=False)
    f2 = off.create_dataframe(FACT, num_partitions=2)
    exp = _canon(f2.filter(F.col("q") < 70).filter(F.col("v") < 0.95)
                 .select("k", "v").collect())
    pd.testing.assert_frame_equal(got, exp, check_exact=True)


def test_donation_applies_to_fresh_batches():
    """Range batches are fresh single-owner buffers: the map stage above
    them donates (the decision path runs on every backend; buffers are
    physically reclaimed only on real devices)."""
    sess = _session()
    q = (sess.range(0, 30_000, num_slices=2)
         .filter(F.col("id") % 3 == 0)
         .select((F.col("id") * 2).alias("d")))
    got = q.collect()
    assert sess.last_query_metrics.get("wholeStageDonatedBatches", 0) > 0
    noden = _session(**{
        "spark.rapids.tpu.sql.wholeStage.donation.enabled": False})
    q2 = (noden.range(0, 30_000, num_slices=2)
          .filter(F.col("id") % 3 == 0)
          .select((F.col("id") * 2).alias("d")))
    exp = q2.collect()
    assert noden.last_query_metrics.get("wholeStageDonatedBatches", 0) == 0
    assert got.to_pylist() == exp.to_pylist()


# --------------------------------------------------------------------------
# metrics / dispatch evidence
# --------------------------------------------------------------------------

def test_coverage_and_dispatch_metrics():
    on = _session()
    q = _q_filter_project_agg(on)
    q.collect()
    q.collect()  # warm: speculation recorded -> fused partial path
    m_on = dict(on.last_query_metrics)
    assert m_on["wholeStageOps"] >= 3  # filter + project + agg terminal
    assert m_on.get("deviceDispatches", 0) > 0
    off = _session(whole_stage=False, fusion=False)
    q2 = _q_filter_project_agg(off)
    q2.collect()
    q2.collect()
    m_off = dict(off.last_query_metrics)
    assert m_off["unfusedOps"] >= 3
    assert m_off["wholeStageOps"] == 0
    # the acceptance ratio: stage-scope dispatches drop >= 3x warm
    assert m_off["stageOpDispatches"] >= 3 * m_on["stageOpDispatches"], \
        (m_off["stageOpDispatches"], m_on["stageOpDispatches"])


def test_stage_trace_category():
    sess = _session(**{"spark.rapids.tpu.trace.sink": "memory"})
    _q_map_chain(sess).collect()
    events = sess._last_trace_events
    assert any(ev.get("cat") == "stage" for ev in events)
    summary = sess.last_query_trace_summary
    assert summary.get("stage_count", 0) > 0
    assert summary.get("device_dispatches", 0) > 0


def test_collect_tail_fusion_still_engages():
    """Regression: the FusedStage wrapper around a complete aggregate
    must stay transparent to the collect-tail fusion pass."""
    from spark_rapids_tpu.sql.physical import collect_fusion as CF
    sess = _session()
    q = _q_complete_agg(sess)
    before = CF.STATS["fused_collects"]
    q.collect()
    q.collect()  # second run has a recorded speculation -> fused tail
    assert CF.STATS["fused_collects"] > before
