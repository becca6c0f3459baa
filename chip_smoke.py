#!/usr/bin/env python3
"""First proof that the engine starts on the attached chip.

One process drives TPC-H at SF1 ``lineitem`` size through the path a user
calls — ``session -> planner -> exec`` — and checks every answer against
the repo's pandas oracles:

* **device**: what JAX found, the HBM it reports, the compile cache in
  use, where the native libraries were built/loaded from;
* **kernels**: both Pallas kernels pass their loud probe and agree with
  numpy at real widths;
* **scan leg**: ``lineitem`` written once as parquet, read back through
  ``sess.read.parquet`` and queried with the spec's q1 and q6 SQL;
* **query leg**: from memory through ``testing.scaletest.iter_suite``:
  the global sort and TPC-H q3 (three-table join + aggregate + sort +
  limit); ``--queries`` names others (see ``DEFAULT_QUERIES`` for why the
  semi join, the skewed join and the window are not in the default);
* **placement**: no operator placed off the TPU, batches live on a TPU.

Every phase prints one JSON object per line.  The seconds it prints are
set-up information (first collect = compile + run, second = warm), not
results to quote.  The last stdout line of a passing run is
``{"ok": true, "device": {...}}``; any failing phase, any oracle mismatch
or a platform other than ``tpu`` ends the run non-zero without it.

``--chips 4`` runs ONLY the four-chip path: a planner-driven join +
aggregate + sort whose exchanges ride the mesh ``all_to_all`` plane,
compared with the same query on the local plane.

Rehearsal off the chip: ``JAX_PLATFORMS=cpu python chip_smoke.py --rows
20000`` runs every phase and then fails on the platform check.  Without an
explicit ``--rows`` a run that finds no TPU stops at the device phase.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

SF1_LINEITEM_ROWS = 6_000_000
MESH_FACT_ROWS = 4_000_000
#: every in-memory query the issue names, cheapest-to-compile first
SUITE_QUERIES = ("q5_global_sort", "tpch_q3_full", "tpch_q4_sql_exists",
                 "q3_skewed_left_join", "q4_window_topn")
#: The query leg of a run, and its size.  Compiling is the cost (PERF.md
#: section 5), and the driver's run must end inside 1200 s cold: the leg
#: is cut to the issue's minimum, these two queries, at 1,000,000 rows
#: (at 6,000,000 rows the global sort's 2^21-row program alone compiles
#: for longer than the whole run may last).  Nothing here watches the
#: clock: a run that does not fit is cut by whoever set its limit, and
#: has then failed.
DEFAULT_QUERIES = ("q5_global_sort", "tpch_q3_full")
QUERY_ROWS = 1_000_000
NOT_ON_TPU = "cannot run on TPU"


def emit(record: dict) -> None:
    print(json.dumps(record, default=str), flush=True)


class Smoke:
    """Phase runner: a phase that raises is recorded, and fails the run."""

    def __init__(self) -> None:
        self.failures: list = []
        self.phases_run: list = []

    def fail(self, phase: str, why: str) -> None:
        self.failures.append({"phase": phase, "error": why})
        emit({"phase": phase, "failed": why})

    def phase(self, name: str, fn, *args):
        self.phases_run.append(name)
        try:
            return fn(*args)
        except Exception as e:  # noqa: BLE001 — every failure is reported
            traceback.print_exc(file=sys.stderr)
            self.fail(name, f"{type(e).__name__}: {e}"[:2000])
            return None


class CompileMeter:
    """Counts what XLA compiled, from jax's own monitoring events: every
    backend compile request with its seconds, and how many of them the
    persistent cache answered."""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.durations: list = []
        self.cache_hits = 0
        self.cache_writes = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append(float(secs))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_writes += 1

    def mark(self) -> tuple:
        return (len(self.durations), self.cache_hits, self.cache_writes)

    def since(self, mark: tuple) -> dict:
        n0, h0, w0 = mark
        d = self.durations[n0:]
        return {"programs": len(d),
                "compile_seconds": round(sum(d), 2),
                "programs_over_1s": sum(1 for x in d if x > 1.0),
                "slowest_seconds": round(max(d), 2) if d else 0.0,
                "persistent_cache_hits": self.cache_hits - h0,
                "persistent_cache_writes": self.cache_writes - w0}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_phase(smoke: Smoke) -> dict:
    import importlib.metadata as md

    import jax
    import spark_rapids_tpu as srt
    from spark_rapids_tpu import native
    from spark_rapids_tpu.native import _loader
    from spark_rapids_tpu.shuffle import native_tcp

    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    stats = d0.memory_stats() or {}
    native_ok = native.available() and native_tcp.available()
    emit({"phase": "device", **device, "versions": versions,
          "hbm_bytes_limit": stats.get("bytes_limit"),
          "compile_cache_dir": srt.compile_cache_dir(),
          "compile_cache_from_env": bool(
              os.environ.get("JAX_COMPILATION_CACHE_DIR")),
          "native_libraries": dict(_loader.LOADED)})
    if d0.platform != "tpu":
        smoke.fail("device", f"platform is {d0.platform!r}, not 'tpu'")
    elif not stats.get("bytes_limit"):
        smoke.fail("device", "the device reports no memory_stats()"
                   "['bytes_limit']")
    if not native_ok:
        smoke.fail("device", f"a native library did not build/load: "
                   f"{_loader.LOADED}")
    return device


def kernels_phase(on_tpu: bool) -> None:
    """Both probes (they raise on a TPU that refuses a kernel), then each
    kernel against numpy at real widths."""
    import numpy as np
    from spark_rapids_tpu.ops import pallas_kernels as PK

    avail = {"murmur3_available": PK.murmur3_available(),
             "seg_sum_available": PK.seg_sum_available()}
    checked = []
    if on_tpu:
        import jax
        import jax.numpy as jnp
        from spark_rapids_tpu.ops.hashing import murmur3_long
        if not all(avail.values()):
            raise AssertionError(f"Pallas probes on a TPU: {avail}")
        rng = np.random.default_rng(7)
        for n in (1 << 20, 1 << 23):
            vals = rng.integers(-(1 << 62), 1 << 62, n).astype(np.int64)
            got = np.asarray(jax.jit(
                lambda v: PK.murmur3_long_pallas(v, np.uint32(42)))(
                    jnp.asarray(vals)))
            want = murmur3_long(np, vals, np.uint32(42))
            assert np.array_equal(got, want), f"murmur3 n={n}"
            checked.append(f"murmur3 n={n}")
        n = 1 << 20
        for s, out in ((1, 8), (8, 64), (3, 4096)):
            vals = rng.random((s, n)).astype(np.float32)
            rank = rng.integers(0, out + 3, n).astype(np.int32)
            got = np.asarray(jax.jit(
                lambda v, r, out=out: PK.seg_sum_f32_pallas(v, r, out))(
                    jnp.asarray(vals), jnp.asarray(rank)))
            want = np.zeros((s, out), np.float64)
            live = rank < out
            for i in range(s):
                np.add.at(want[i], rank[live], vals[i][live])
            assert got.shape == (s, out), (got.shape, s, out)
            assert np.allclose(got, want, rtol=2e-3), \
                f"seg_sum s={s} out={out}: max rel err " \
                f"{np.max(np.abs(got - want) / np.maximum(want, 1))}"
            checked.append(f"seg_sum s={s} out={out} n={n}")
    emit({"phase": "kernels", **avail, "checked_at_real_width": checked})


def _placement(sess, df=None) -> list:
    """'cannot run on TPU' lines of the placement report (of ``df``, or of
    the session's most recent collect)."""
    return [ln.strip() for ln in sess.explain(df, all_ops=False).splitlines()
            if NOT_ON_TPU in ln]


def _timed_twice(meter: CompileMeter, run) -> tuple:
    """(result of the 2nd run, record) — first (compiling) and second
    (warm) wall seconds, labelled set-up."""
    mark = meter.mark()
    t0 = time.perf_counter()
    run()
    cold = time.perf_counter() - t0
    compiled = meter.since(mark)
    mark = meter.mark()
    t0 = time.perf_counter()
    out = run()
    warm = time.perf_counter() - t0
    rec = {"setup_first_collect_seconds": round(cold, 3),
           "setup_second_collect_seconds": round(warm, 3),
           "compiled_in_first": compiled,
           "compiled_in_second": meter.since(mark)["programs"]}
    return out, rec


def scan_phase(smoke: Smoke, sess, meter: CompileMeter, lineitem,
               out_dir: str, on_tpu: bool) -> None:
    import jax
    import pyarrow.parquet as pq
    from spark_rapids_tpu.sql.planner import Planner
    from spark_rapids_tpu.testing import scaletest as ST

    path = os.path.join(out_dir, "lineitem.parquet")
    t0 = time.perf_counter()
    pq.write_table(lineitem, path, row_group_size=1 << 20)
    emit({"phase": "scan_leg", "step": "write_parquet", "path": path,
          "rows": lineitem.num_rows, "file_bytes": os.path.getsize(path),
          "setup_seconds": round(time.perf_counter() - t0, 2)})
    sess.read.parquet(path).createOrReplaceTempView("lineitem")

    for name, sql, oracle in (
            ("tpch_q6_parquet", ST._TPCH_Q6_SQL, ST._q6_oracle_check),
            ("tpch_q1_parquet", ST._TPCH_Q1_SQL, ST._q1_oracle_check)):
        def one(name=name, sql=sql, oracle=oracle):
            df = sess.sql(sql)
            got, rec = _timed_twice(
                meter, lambda: df.collect().to_pandas())
            m = sess.last_query_metrics
            rec.update({
                "phase": "scan_leg", "query": name,
                "rows": lineitem.num_rows, "result_rows": len(got),
                "parquetDecodeFilesEngaged":
                    int(m.get("parquetDecodeFilesEngaged", 0)),
                "parquetDecodeFilesDeclined":
                    int(m.get("parquetDecodeFilesDeclined", 0))})
            oracle(got, lineitem)
            rec["oracle"] = "pass"
            off = _placement(sess, df)
            rec["not_on_tpu"] = off
            emit(rec)
            if off:
                raise AssertionError(f"{name}: operators off the TPU: {off}")
        smoke.phase(f"scan_leg:{name}", one)

    def batch_lives_on_device():
        # device batches as the session's own plan produces them: the
        # scan of two lineitem columns, before any device-to-host step
        # (a plan this small adds no program worth a second to compile)
        scan = sess.sql("SELECT l_orderkey, l_quantity FROM lineitem")
        batches = Planner(sess._conf).plan(scan._plan).execute_all(
            sess._conf)
        leaves = [leaf for b in batches
                  for leaf in jax.tree_util.tree_leaves(b.columns)]
        where = sorted({f"{d.platform}:{d.id}" for leaf in leaves
                        for d in leaf.devices()})
        emit({"phase": "placement", "device_batches": len(batches),
              "rows": sum(b.num_rows_int for b in batches),
              "lives_on": where})
        if on_tpu and not all(w.startswith("tpu:") for w in where):
            raise AssertionError(f"device batches live on {where}")
    smoke.phase("placement:device_batch", batch_lives_on_device)


def query_phase(smoke: Smoke, sess, meter: CompileMeter, rows: int,
                seed: int, tpch_tables, queries) -> None:
    """Each query through ``iter_suite`` (runner + pandas oracle, twice),
    in the order given; an ``{"error": ...}`` record fails the run."""
    from spark_rapids_tpu.testing import scaletest as ST

    base = ST.build_tables(rows, seed)
    extra = {"tpch": tpch_tables}
    for name in queries:
        phase = f"query_leg:{name}"
        smoke.phases_run.append(phase)
        mark = meter.mark()
        entries = list(ST.iter_suite(rows, queries=[name], tables=base,
                                     sess=sess, extra_tables=extra))
        if len(entries) != 1:
            smoke.fail(phase, f"unknown query (got {len(entries)} records)")
            continue
        entry = entries[0]
        if "error" in entry:
            smoke.fail(phase, entry["error"])
            continue
        off = _placement(sess)
        emit({"phase": "query_leg", "query": name, "rows": rows,
              "oracle": "pass",
              # run_suite's first run = compile + run + pandas oracle,
              # its second = warm run + oracle again
              "setup_first_run_seconds": entry["seconds"],
              "setup_second_run_seconds": entry["warm_seconds"],
              "compiled": meter.since(mark),
              "tables_bytes": entry["tables_bytes"],
              "not_on_tpu": off})
        if off:
            smoke.fail(phase, f"operators off the TPU: {off}")


def counters_phase(meter: CompileMeter) -> None:
    from spark_rapids_tpu.columnar import prepack
    from spark_rapids_tpu.sql.physical import collect_fusion, kernel_cache
    emit({"phase": "counters",
          "collect_fusion": dict(collect_fusion.STATS),
          "kernel_cache": kernel_cache.cache_stats(),
          "d2h_prepack": dict(prepack.STATS),
          "xla": meter.since((0, 0, 0))})
    # the one place left on this path that swallows a toolchain failure
    # and carries on another way: it must not have happened
    assert prepack.STATS["fallbacks"] == 0, \
        f"device-side prepack failed and fell back: {prepack.STATS}"


# ---------------------------------------------------------------------------
# the four-chip path
# ---------------------------------------------------------------------------

def mesh_phase(meter: CompileMeter, rows: int, seed: int, n_dev: int,
               on_tpu: bool) -> None:
    """Planner-driven join + aggregate + sort: once with every exchange on
    the mesh all_to_all plane, once on the local plane; results must be
    equal, and the mesh plane must not have given way."""
    import jax
    import numpy as np
    import pyarrow as pa
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.parallel import mesh as M
    from spark_rapids_tpu.sql import functions as F

    devs = jax.devices()
    if len(devs) < n_dev:
        raise AssertionError(f"--chips {n_dev} but JAX sees {len(devs)}")
    rng = np.random.default_rng(seed)
    n_keys = 4096
    left = pa.table({"k": rng.integers(0, n_keys, rows),
                     "v": rng.random(rows),
                     "q": rng.integers(0, 100, rows)})
    right = pa.table({"k": pa.array(np.arange(n_keys * 3 // 4),
                                    type=pa.int64()),
                      "w": pa.array(np.arange(n_keys * 3 // 4) * 2.0)})
    common = {"spark.sql.shuffle.partitions": n_dev,
              "spark.rapids.sql.autoBroadcastJoinThreshold": 1,
              "spark.sql.adaptive.coalescePartitions.minRows": 0}

    def query(sess):
        lf = sess.create_dataframe(left, num_partitions=n_dev)
        rt = sess.create_dataframe(right, num_partitions=2)
        return (lf.join(rt, on="k", how="inner").groupBy("k")
                .agg(F.sum(lf.v).alias("sv"), F.count("*").alias("c"),
                     F.sum(lf.q).alias("sq"), F.max(rt.w).alias("w"))
                .orderBy("k"))

    from spark_rapids_tpu.parallel import placement
    before = dict(M.STATS)
    copies_before = placement.STATS["cross_chip_copies"]
    M.RECENT_EXCHANGES.clear()
    # n_dev executors, one chip each (all of the four-chip host; the first
    # four of a rehearsal's virtual devices): the layout picks the plane
    sess = srt.session(**{"spark.executor.instances": n_dev}, **common)
    q = query(sess)
    got, rec = _timed_twice(meter, lambda: q.collect().to_pandas())
    copies = placement.STATS["cross_chip_copies"] - copies_before
    off = _placement(sess, q)
    stats = {k: M.STATS[k] - before[k] for k in M.STATS}
    # what the exchange itself recorded: the four devices' memory right
    # after each exchange program returned, where its outputs lay and
    # where the batches it handed on lie
    after_exchange = list(M.RECENT_EXCHANGES)
    emit({"phase": "mesh", "plane": "ICI", "fact_rows": rows,
          "devices": n_dev, **rec, "mesh_stats": stats,
          "cross_chip_copies": copies,
          "after_exchange": after_exchange, "not_on_tpu": off})

    sess2 = srt.session(**{"spark.executor.instances": 1}, **common)
    q2 = query(sess2)
    mark = dict(M.STATS)
    want, rec2 = _timed_twice(meter, lambda: q2.collect().to_pandas())
    emit({"phase": "mesh", "plane": "local", "fact_rows": rows, **rec2,
          "mesh_exchanges_on_local_plane":
              M.STATS["mesh_exchanges"] - mark["mesh_exchanges"]})

    exp = (left.to_pandas().merge(right.to_pandas(), on="k").groupby("k")
           .agg(sv=("v", "sum"), c=("v", "size"), sq=("q", "sum"),
                w=("w", "max")).reset_index())
    for label, a, b in (("mesh vs local", got, want),
                        ("mesh vs pandas", got, exp)):
        assert len(a) == len(b), f"{label}: {len(a)} vs {len(b)} groups"
        for col in ("k", "c", "sq"):
            assert np.array_equal(np.asarray(a[col]), np.asarray(b[col])), \
                f"{label}: {col}"
        for col in ("sv", "w"):   # tests/test_mesh_shuffle.py's tolerance
            assert np.allclose(np.asarray(a[col]), np.asarray(b[col])), \
                f"{label}: {col}"
    assert stats["mesh_exchanges"] > 0, "no exchange rode the mesh plane"
    assert stats["fallbacks"] == 0, "a mesh exchange fell back"
    assert stats["collective_timeouts"] == 0, "a collective timed out"
    assert M.STATS["mesh_exchanges"] == mark["mesh_exchanges"], \
        "the local-plane run rode the mesh"
    assert not off, f"operators off the TPU: {off}"
    assert len(after_exchange) == stats["mesh_exchanges"], after_exchange
    assert copies == 0, f"{copies} batches copied from chip to chip"
    for snap in after_exchange:
        assert len(snap["program_outputs_live_on"]) == n_dev, snap
        assert len(snap["batches_handed_on_live_on"]) == n_dev, \
            f"a chip was handed nothing: {snap}"
        if on_tpu:
            assert all(b and b > 0 for b in snap["bytes_in_use"]), \
                f"a device held nothing after the exchange: {snap}"
    emit({"phase": "mesh", "equal": True, "groups": len(got)})


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"lineitem rows (default {SF1_LINEITEM_ROWS}, the "
                         f"SF1 size; with --chips 4: fact rows, default "
                         f"{MESH_FACT_ROWS})")
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = run only the four-chip mesh path")
    ap.add_argument("--queries", default=",".join(DEFAULT_QUERIES),
                    help="in-memory suite queries, comma-separated, run at "
                         f"min(--rows, {QUERY_ROWS}) rows (default: "
                         f"%(default)s; also {','.join(SUITE_QUERIES[2:])})")
    ap.add_argument("--out", default=None,
                    help="directory for the parquet file (default: a "
                         "temporary directory, removed at the end)")
    args = ap.parse_args(argv)
    if args.out is not None:
        return run(args, args.out)
    import tempfile
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as out_dir:
        return run(args, out_dir)


def run(args, out_dir: str) -> int:
    smoke = Smoke()
    t_start = time.perf_counter()
    import jax  # noqa: F401 — as a user would: no platform is set here
    import spark_rapids_tpu as srt
    meter = CompileMeter()

    device = smoke.phase("device", device_phase, smoke)
    on_tpu = bool(device) and device["platform"] == "tpu"
    if not on_tpu and args.rows is None:
        # no accelerator and no rehearsal asked for: nothing to report
        print("chip_smoke: no TPU found; stopping (give --rows for a "
              "rehearsal of every phase on this platform)", file=sys.stderr)
        return 1

    query_rows = None
    if args.chips == 4:
        rows = args.rows or MESH_FACT_ROWS
        smoke.phase("mesh", mesh_phase, meter, rows, args.seed, 4, on_tpu)
    else:
        rows = args.rows or SF1_LINEITEM_ROWS
        os.makedirs(out_dir, exist_ok=True)
        smoke.phase("kernels", kernels_phase, on_tpu)
        from spark_rapids_tpu.testing import scaletest as ST
        t0 = time.perf_counter()
        tables = smoke.phase("datagen", ST.build_tpch_tables, rows,
                             args.seed)
        if tables is not None:
            emit({"phase": "datagen", "rows": rows, "seed": args.seed,
                  "lineitem_bytes": tables["lineitem"].nbytes,
                  "setup_seconds": round(time.perf_counter() - t0, 2)})
            sess = srt.session()
            smoke.phase("scan_leg", scan_phase, smoke, sess, meter,
                        tables["lineitem"], out_dir, on_tpu)
            queries = [q for q in args.queries.split(",") if q]
            query_rows = min(rows, QUERY_ROWS)
            query_tables = tables if query_rows == rows else smoke.phase(
                "datagen:query_leg", ST.build_tpch_tables, query_rows,
                args.seed)
            if query_tables is not None:
                smoke.phase("query_leg", query_phase, smoke, sess, meter,
                            query_rows, args.seed, query_tables, queries)
    smoke.phase("counters", counters_phase, meter)

    emit({"phase": "summary", "rows": rows, "chips": args.chips,
          "phases_run": smoke.phases_run, "failures": smoke.failures,
          "query_rows": query_rows,
          "setup_total_seconds": round(time.perf_counter() - t_start, 1)})
    if smoke.failures or not on_tpu:
        print(f"chip_smoke: FAILED: {json.dumps(smoke.failures)}",
              file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
