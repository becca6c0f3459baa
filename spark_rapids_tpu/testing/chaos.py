"""Chaos soak — the degraded-conditions proof rig (docs/robustness.md).

Runs a small TPC-H-ish query suite twice over identical data: once
fault-free, once under a seeded random fault schedule (shuffle fetch
failures, permanently destroyed shuffle blocks, torn spill-disk I/O,
injected retryable OOMs), and asserts the chaos run's results are
BIT-IDENTICAL to the clean run's — the paper's transparent-acceleration
promise must survive data-movement failure, not just the happy path
(arXiv:2508.04701's correctness-under-degradation argument;
arXiv:2508.05029 treats data-movement failure as a first-class concern).

The schedule is deterministic (robustness/faults.py): a given
(seed, sites, probability) either passes forever or fails forever, so CI
can pin one.

Run standalone:  python -m spark_rapids_tpu.testing.chaos [rows]
                     [--seed N] [--trace /path/trace.json]
CI runs it in ci/run_ci.sh with two primary fault sites armed and
validates the exported trace carries ``fault``-category spans.
"""

from __future__ import annotations

import json
import sys
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

#: the default armed schedule: every site with a built-in recovery
#: protocol that this suite's query shapes actually traverse
DEFAULT_SITES = ("shuffle.fetch:0.25,shuffle.block.lost:0.12,"
                 "spill.disk_read:0.25,spill.disk_write:0.25,"
                 "memory.oom.retry:0.25")


def _q_agg(sess, t, F):
    df = sess.create_dataframe(t["fact"], num_partitions=4)
    return (df.groupBy("q").agg(F.sum(F.col("v")).alias("sv"),
                                F.count("*").alias("c"))
            .orderBy("q").collect())


def _q_join_agg(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    dim = sess.create_dataframe(t["dim"], num_partitions=2)
    return (fact.join(dim, on="k", how="inner")
            .groupBy("cat").agg(F.count("*").alias("n"),
                                F.sum(fact.v).alias("sv"))
            .orderBy("cat").collect())


def _q_left_join(sess, t, F):
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    dim = sess.create_dataframe(t["dim"], num_partitions=2)
    return (fact.join(dim, on="k", how="left").filter(fact.q >= 90)
            .select(fact.k, fact.v, dim.w)
            .orderBy("k", "v").collect())


def _q_sort(sess, t, F):
    # out-of-core sort (targetRows is forced small below): spillable runs
    # + k-way merge give the spill/OOM fault sites real traffic
    df = sess.create_dataframe(t["fact"], num_partitions=4)
    return (df.orderBy(df.v.desc_nulls_first(), "k")
            .select("k", "v", "q").collect())


def _q_enc_str_join(sess, t, F):
    # low-cardinality STRING-keyed filter+join+group: the shape the
    # encoded columnar path (docs/encoded_columns.md) rewrites — dict
    # filter on the scan, code-space join probe, group-by on codes, and
    # encoded frames (narrowed codes + dictionaries) over the serializing
    # shuffle plane.  Kept LAST in QUERIES so the exported chaos trace
    # carries its encode spans alongside the fault spans.
    fact = sess.create_dataframe(t["fact"], num_partitions=4)
    cdim = sess.create_dataframe(t["cdim"], num_partitions=2)
    return (fact.filter(F.col("ck") <= "cat_11")
            .join(cdim, on="ck", how="inner")
            .groupBy("ck").agg(F.count("*").alias("n"),
                               F.sum(fact.v).alias("sv"))
            .orderBy("ck").collect())


QUERIES: List[Tuple[str, Callable]] = [
    ("agg", _q_agg),
    ("join_agg", _q_join_agg),
    ("left_join", _q_left_join),
    ("ooc_sort", _q_sort),
    ("enc_str_join", _q_enc_str_join),
]


def augment_tables(t: dict) -> dict:
    """Add the low-cardinality string key column (and its dimension) the
    `enc_str_join` query needs, IN PLACE and idempotently — callers that
    reuse one tables dict across runs (the pipeline rig's timing loop,
    test fixtures) keep stable table identities, so the engine's upload
    cache still amortizes."""
    if "cdim" not in t:
        rng = np.random.default_rng(5)
        cats = [f"cat_{i:02d}" for i in range(16)]
        n = t["fact"].num_rows
        t["fact"] = t["fact"].append_column(
            "ck", pa.array([cats[i] for i in rng.integers(0, 16, n)]))
        t["cdim"] = pa.table({"ck": pa.array(cats),
                              "cw": np.arange(float(len(cats)))})
    return t


def _soak_tables(rows: int) -> dict:
    """scaletest tables + the dictionary-encoded string key columns so
    the suite traverses the encoded paths."""
    from .scaletest import build_tables
    return augment_tables(dict(build_tables(rows)))


def _canonical(table: pa.Table) -> pd.DataFrame:
    df = table.to_pandas()
    return df.sort_values(list(df.columns), kind="mergesort") \
        .reset_index(drop=True)


def _base_conf(tmp: str) -> Dict[str, object]:
    """Shared clean/chaos session confs: the serializing (resident-off)
    shuffle plane so block fetches actually happen, a small out-of-core
    sort target so the spill tier sees traffic, and an
    environment-independent codec."""
    return {
        "spark.rapids.shuffle.localDeviceResident.enabled": False,
        "spark.rapids.shuffle.compression.codec": "none",
        "spark.rapids.sql.sort.outOfCore.targetRows": 2048,
        "spark.rapids.memory.spillDir": tmp,
        # shuffled (not broadcast) joins: both join inputs ride exchanges
        "spark.rapids.sql.autoBroadcastJoinThreshold": 1,
    }


def run_soak(rows: int = 20_000, seed: int = 11,
             sites: str = DEFAULT_SITES,
             queries: Optional[List[str]] = None,
             trace_path: Optional[str] = None,
             strict: bool = True,
             pipeline: bool = False,
             encoded: bool = False,
             whole_stage: bool = False,
             coalesce: bool = False) -> dict:
    """Returns the soak report; raises AssertionError on any parity or
    counter-visibility failure.  ``strict=False`` (reduced smoke runs)
    keeps the bit-parity and faults-injected asserts but skips the
    per-site coverage floor (small row counts may not traverse every
    armed site).

    ``pipeline=True`` runs the CHAOS session under the async execution
    layer (task.parallelism=4 + prefetch queues + double-buffered
    transfers, concurrentGpuTasks left at 1 so semaphore contention —
    ``sem_wait`` spans — is guaranteed) while the clean run stays serial:
    injected faults must recover bit-identically even when they surface
    on prefetch producer / transfer stager / pool worker threads.

    ``encoded=True`` runs the CHAOS session with encoded columnar
    execution ON while the clean run stays on the RAW path
    (``spark.rapids.tpu.sql.encoded.enabled=false``): encoded shuffle
    frames (narrowed codes + dictionaries/refs) must survive fetch
    retries, destroyed blocks, and lost-block recompute bit-identically
    to the raw clean run — the ISSUE 6 acceptance leg.

    ``whole_stage=True`` runs the CHAOS session with whole-stage fusion +
    buffer donation forced ON while the clean run disables fusion
    entirely (``spark.rapids.tpu.sql.fusion.enabled=false``, the serial
    unfused per-op baseline): fused stage programs, absorbed aggregate /
    probe terminals, and the donation-safety guard must stay
    bit-identical under injected data-movement faults — the ISSUE 7
    acceptance leg (docs/whole_stage.md).

    ``coalesce=True`` additionally arms the ISSUE 14 dispatch set on the
    CHAOS session — the small-batch dispatch coalescer, the sort/window
    stage terminals, and the fused single-program join probe — against
    the same serial unfused clean baseline: coalesced batch-of-batches
    launches and fused terminals must recover bit-identically under
    injected faults."""
    import spark_rapids_tpu as srt
    from ..config import RapidsConf
    from ..memory.spill import BufferCatalog
    from ..robustness import disarm_chaos
    from ..robustness.faults import SITE_STATS
    from ..sql import functions as F
    tables = _soak_tables(rows)
    tmp = tempfile.mkdtemp(prefix="srt-chaos-")
    selected = [(n, fn) for n, fn in QUERIES
                if queries is None or n in queries]
    from ..sql.session import TpuSession
    prev_active = TpuSession._active

    # tiny host spill budget: an injected RetryOOM's spill_all_device
    # overflows straight to the DISK tier, so spill.disk_read/write see
    # real traffic.  Shared by both runs (the tier move is value-exact,
    # so the clean run's results are unaffected).
    BufferCatalog.reset(RapidsConf({
        "spark.rapids.memory.host.spillStorageSize": 1,
        "spark.rapids.memory.spillDir": tmp,
    }))
    try:
        clean_conf = dict(_base_conf(tmp))
        if encoded:
            # clean baseline on the RAW path: the soak then proves
            # encoded-under-faults == raw-without-faults, not just
            # encoded == encoded
            clean_conf["spark.rapids.tpu.sql.encoded.enabled"] = False
        if whole_stage or coalesce:
            # clean baseline fully UNFUSED: the soak proves
            # fused-and-donating-under-faults == per-op-without-faults
            clean_conf["spark.rapids.tpu.sql.fusion.enabled"] = False
        if coalesce:
            clean_conf.update({
                "spark.rapids.tpu.sql.dispatch.coalesce.enabled": False,
                "spark.rapids.tpu.sql.join.fusedProbe.enabled": False,
                "spark.rapids.tpu.sql.wholeStage.sortWindowTerminal"
                ".enabled": False,
            })
        clean_sess = srt.session(conf=RapidsConf.get_global().copy(
            clean_conf))
        clean: Dict[str, pd.DataFrame] = {}
        for name, fn in selected:
            clean[name] = _canonical(fn(clean_sess, tables, F))

        chaos_conf = dict(_base_conf(tmp))
        chaos_conf.update({
            "spark.rapids.tpu.chaos.enabled": True,
            "spark.rapids.tpu.chaos.seed": seed,
            "spark.rapids.tpu.chaos.sites": sites,
            "spark.rapids.tpu.shuffle.fetch.backoffMs": 1,
        })
        if encoded:
            chaos_conf["spark.rapids.tpu.sql.encoded.enabled"] = True
        if whole_stage or coalesce:
            chaos_conf.update({
                "spark.rapids.tpu.sql.fusion.enabled": True,
                "spark.rapids.tpu.sql.wholeStage.enabled": True,
                "spark.rapids.tpu.sql.wholeStage.donation.enabled": True,
            })
        if coalesce:
            chaos_conf.update({
                "spark.rapids.tpu.sql.dispatch.coalesce.enabled": True,
                # small cap so groups actually form at soak row counts
                "spark.rapids.tpu.sql.dispatch.coalesce.maxBatches": 4,
                "spark.rapids.tpu.sql.join.fusedProbe.enabled": True,
                "spark.rapids.tpu.sql.wholeStage.sortWindowTerminal"
                ".enabled": True,
            })
        if pipeline:
            chaos_conf.update({
                "spark.rapids.tpu.task.parallelism": 4,
                "spark.rapids.tpu.prefetch.enabled": True,
                "spark.rapids.tpu.prefetch.depth": 2,
                "spark.rapids.tpu.transfer.doubleBuffer.enabled": True,
                # permits intentionally BELOW the pool width: the soak
                # doubles as the sem_wait-span source for CI's
                # check_trace --require-cat sem_wait validation
                "spark.rapids.sql.concurrentGpuTasks": 1,
            })
        if trace_path:
            chaos_conf["spark.rapids.tpu.profile.enabled"] = True
        chaos_sess = srt.session(conf=RapidsConf.get_global().copy(
            chaos_conf))

        counters = {"faultsInjected": 0, "shuffleFetchRetries": 0,
                    "shuffleBlocksRecomputed": 0, "peersBlacklisted": 0}
        by_site: Dict[str, int] = {}
        per_query = {}
        mismatches = []
        exported_has_encode = False
        for name, fn in selected:
            site0 = dict(SITE_STATS)
            got = _canonical(fn(chaos_sess, tables, F))
            m = chaos_sess.last_query_metrics
            q = {k: int(m.get(k, 0)) for k in counters}
            for k in counters:
                counters[k] += q[k]
            # per-site coverage: the monotonic totals survive the
            # query-scoped registry (re-armed per query, gone at query end)
            for site, n in SITE_STATS.items():
                d = n - site0.get(site, 0)
                if d:
                    by_site[site] = by_site.get(site, 0) + d
            per_query[name] = q
            try:
                pd.testing.assert_frame_equal(got, clean[name],
                                              check_exact=True)
            except AssertionError as e:
                mismatches.append(f"{name}: {e}")
            if trace_path and q["faultsInjected"] > 0:
                # keep the last trace carrying fault spans, preferring
                # one that ALSO carries encode spans (scan-side encode
                # fires only on each table's first upload, so later
                # queries' traces lack cat `encode` — CI's encoded leg
                # validates both categories in one export)
                has_enc = int(m.get("encodedColumnsEncoded", 0)) > 0
                if has_enc or not exported_has_encode:
                    chaos_sess.export_chrome_trace(trace_path)
                    exported_has_encode = exported_has_encode or has_enc

        report = {
            "rows": rows, "seed": seed, "sites": sites,
            "pipeline": pipeline, "encoded": encoded,
            "whole_stage": whole_stage, "coalesce": coalesce,
            "queries": per_query, "counters": counters,
            "faults_by_site": by_site,
            "bit_identical": not mismatches,
        }
        assert not mismatches, \
            "chaos run diverged from the fault-free run:\n" + \
            "\n".join(mismatches)
        assert counters["faultsInjected"] > 0, report
        assert counters["shuffleFetchRetries"] > 0, report
        if strict:
            assert counters["shuffleBlocksRecomputed"] > 0, report
            assert by_site.get("shuffle.fetch", 0) > 0, report
            assert by_site.get("spill.disk_read", 0) > 0, report
        return report
    finally:
        disarm_chaos()
        BufferCatalog.reset()
        # don't leave the chaos-confed session as the cached active one:
        # a later bare ``srt.session()`` would inherit it and re-arm
        # chaos on its next query
        TpuSession._active = prev_active


def run_multi_session_soak(rows: int = 12_000, seed: int = 11,
                           sites: str = DEFAULT_SITES,
                           tenants: int = 2,
                           queries: Optional[List[str]] = None,
                           trace_path: Optional[str] = None) -> dict:
    """Multi-tenant chaos soak (docs/serving.md): ``tenants`` serving
    sessions run the TPC-H-ish suite CONCURRENTLY through one
    ServingEngine while the seeded fault registry is armed engine-scoped
    — every tenant's results must be bit-identical to the serial clean
    run.  This is the serving tier's correctness floor: admission
    interleaving, shared caches (kernel/broadcast/upload), and fault
    recovery on N driver threads at once must not perturb a single bit.

    The per-site coverage floor stays with the serial soak (fault
    ordinals shift under thread interleaving, like the --pipeline leg);
    here the asserts are bit-parity, fault visibility, per-tenant
    history attribution, and admission accounting for every tenant."""
    import threading

    import spark_rapids_tpu as srt  # noqa: F401 - engine init path
    from ..config import RapidsConf
    from ..memory.spill import BufferCatalog
    from ..robustness import disarm_chaos, stats_snapshot
    from ..serving import ServingEngine
    from ..sql import functions as F
    from ..sql.session import TpuSession
    tables = _soak_tables(rows)
    tmp = tempfile.mkdtemp(prefix="srt-mtchaos-")
    selected = [(n, fn) for n, fn in QUERIES
                if queries is None or n in queries]
    prev_active = TpuSession._active
    BufferCatalog.reset(RapidsConf({
        "spark.rapids.memory.host.spillStorageSize": 1,
        "spark.rapids.memory.spillDir": tmp,
    }))
    eng = None
    try:
        clean_sess = srt.session(conf=RapidsConf.get_global().copy(
            _base_conf(tmp)))
        clean: Dict[str, pd.DataFrame] = {}
        for name, fn in selected:
            clean[name] = _canonical(fn(clean_sess, tables, F))

        eng_conf = dict(_base_conf(tmp))
        eng_conf.update({
            "spark.rapids.tpu.chaos.enabled": True,
            "spark.rapids.tpu.chaos.seed": seed,
            "spark.rapids.tpu.chaos.sites": sites,
            "spark.rapids.tpu.shuffle.fetch.backoffMs": 1,
            "spark.rapids.tpu.serving.maxConcurrentQueries": max(
                2, tenants),
            # result sharing OFF: every tenant must EXECUTE every query
            # under faults — a cache hit would prove nothing
            "spark.rapids.tpu.serving.resultCache.enabled": False,
            "spark.rapids.tpu.serving.broadcastShare.enabled": True,
        })
        if trace_path:
            eng_conf["spark.rapids.tpu.profile.enabled"] = True
        rob0 = stats_snapshot()
        eng = ServingEngine(conf=RapidsConf.get_global().copy(eng_conf))
        results: Dict[str, Dict[str, pd.DataFrame]] = {}
        errors: Dict[str, BaseException] = {}

        def run_tenant(tname: str) -> None:
            try:
                sess = eng.session(tenant=tname)
                got = {}
                for name, fn in selected:
                    got[name] = _canonical(fn(sess, tables, F))
                results[tname] = got
            except BaseException as e:  # noqa: BLE001 - reported below
                errors[tname] = e

        threads = [threading.Thread(target=run_tenant,
                                    args=(f"tenant{i}",),
                                    name=f"srt-tenant{i}")
                   for i in range(tenants)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, f"tenant queries raised: {errors}"
        rob1 = stats_snapshot()
        faults = rob1["faultsInjected"] - rob0["faultsInjected"]
        mismatches = []
        for tname, got in sorted(results.items()):
            for name, frame in got.items():
                try:
                    pd.testing.assert_frame_equal(frame, clean[name],
                                                  check_exact=True)
                except AssertionError as e:
                    mismatches.append(f"{tname}/{name}: {e}")
        if trace_path:
            eng.export_chrome_trace(trace_path)
        adm = eng.admission_stats()
        hist = eng.query_history()
        per_tenant_hist = {t: len(eng.query_history(tenant=t))
                           for t in sorted(results)}
        report = {
            "rows": rows, "seed": seed, "sites": sites,
            "tenants": tenants, "faults_injected": faults,
            "queries_per_tenant": len(selected),
            "bit_identical": not mismatches,
            "admission": adm,
            "history_records": len(hist),
            "history_per_tenant": per_tenant_hist,
        }
        assert not mismatches, \
            "multi-session chaos run diverged from the clean run:\n" + \
            "\n".join(mismatches)
        assert faults > 0, report
        # every tenant's queries must be attributed in the shared ring
        for t, n in per_tenant_hist.items():
            assert n == len(selected), (t, n, report)
        assert adm["admitted"] == tenants * len(selected), report
        return report
    finally:
        if eng is not None:
            eng.close()
        disarm_chaos()
        BufferCatalog.reset()
        TpuSession._active = prev_active


def main() -> None:
    # runs on the platform JAX finds (CI sets JAX_PLATFORMS=cpu)
    argv = sys.argv[1:]
    trace_path = None
    seed = 11
    pipeline = False
    encoded = False
    whole_stage = False
    multi_session = False
    if "--multi-session" in argv:
        # multi-tenant soak: >=2 serving sessions run the suite
        # concurrently through one ServingEngine under engine-scoped
        # chaos; every tenant bit-identical to the serial clean run
        # (ISSUE 9 acceptance — docs/serving.md)
        multi_session = True
        argv.remove("--multi-session")
    coalesce = False
    if "--coalesce" in argv:
        # dispatch soak: chaos session with the coalescer, sort/window
        # stage terminals, and the fused join probe armed vs the serial
        # unfused clean baseline (ISSUE 14 acceptance: bit-identical
        # under faults with the dispatch set on)
        coalesce = True
        argv.remove("--coalesce")
    if "--whole-stage" in argv:
        # whole-stage soak: chaos session with fusion + donation forced
        # on vs a fully UNFUSED serial clean baseline (ISSUE 7
        # acceptance: bit-identical under faults with whole-stage on)
        whole_stage = True
        argv.remove("--whole-stage")
    if "--encoded" in argv:
        # encoded soak: chaos session runs with encoded columnar
        # execution ON against a RAW clean baseline (ISSUE 6 acceptance:
        # bit-identical under faults with encoding enabled)
        encoded = True
        argv.remove("--encoded")
    if "--pipeline" in argv:
        # pipelined soak: chaos session under parallelism=4 + prefetch +
        # double-buffered transfers vs the SERIAL clean run.  The
        # per-site coverage floor is owned by the serial soak (ordinal
        # assignment shifts with thread interleaving), so this leg runs
        # strict=False — bit-parity and fault-visibility asserts remain.
        pipeline = True
        argv.remove("--pipeline")
    if "--trace" in argv:
        i = argv.index("--trace")
        trace_path = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if "--seed" in argv:
        i = argv.index("--seed")
        seed = int(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    rows = int(argv[0]) if argv else 20_000
    if multi_session:
        report = run_multi_session_soak(rows, seed=seed,
                                        trace_path=trace_path)
        print(json.dumps(report, indent=2))
        print(f"CHAOS SOAK PASSED: {report['tenants']} concurrent "
              f"tenants bit-identical under "
              f"{report['faults_injected']} injected faults")
        return
    report = run_soak(rows, seed=seed, trace_path=trace_path,
                      strict=not pipeline, pipeline=pipeline,
                      encoded=encoded, whole_stage=whole_stage,
                      coalesce=coalesce)
    print(json.dumps(report, indent=2))
    mode = ("pipelined " if pipeline else "") + \
        ("encoded " if encoded else "") + \
        ("whole-stage " if whole_stage else "") + \
        ("coalesce-armed " if coalesce else "")
    print(f"CHAOS SOAK PASSED: {mode}results bit-identical under "
          f"{report['counters']['faultsInjected']} injected faults")


if __name__ == "__main__":
    main()
