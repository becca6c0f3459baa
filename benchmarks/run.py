#!/usr/bin/env python3
"""One run of one benchmark cell: set-up, a timed window, the check.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

One process, no child, no server, no platform set.  The cell is the file
``workloads/<name>.json``; it names a configuration (``configs/``), its
queries (``queries/<q>.sql`` and ``.json``, ``reference/<q>.py``) and how
the tables are registered.  The tables come from ``--seed`` through the
generator the configuration names (``generators/<name>.py``), at the
configuration's own ``scale``.  Set-up warms this cell's queries and nothing
else; the window then drives ``TpuSession.sql(text).collect()`` in a closed
loop, one client, whole rounds of the queries, and times nothing else.  Once
the window has closed, every answer it collected is compared with the plain
pandas reference over the same tables (``compare.py``).

The last line of standard output is the result: ``--trace 0`` gives the
end-to-end metrics, ``--trace 1`` wraps whole rounds in a profiler trace
and gives the per-layer metrics, each read by ``metrics/<name>.py``.  A
platform other than ``tpu``, or fewer chips than the configuration asks for,
ends the run non-zero with no result.  ``--scale key=value`` is for
rehearsals off the chip (it overrides a key of the configuration's
``scale``): every phase runs, counts print, no result does, and the run ends
non-zero.  This file names no cell, query, configuration, generator or
metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import threading
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare as C  # noqa: E402
import stats  # noqa: E402

NOT_ON_TPU = "cannot run on TPU"
#: the keys a cell's file may hold; any other is an error, so that a cell
#: asking for what the harness does not do (more clients, an open loop, think
#: time) fails instead of silently running one closed-loop client
WORKLOAD_KEYS = {"name", "config", "view", "queries"}
#: warm-up collects each query this often before the window opens.  On the
#: chip the first collect compiles or loads from the compile cache, the
#: second sometimes compiles one more program (the fused collect), the third
#: none; a fixed count keeps ``setup_s`` the same work in every run, and the
#: compiles inside the window are counted, not assumed
WARM_COLLECTS = 3
#: a traced window holds the whole rounds that fit into this many seconds
#: (at least one): traces are large and reading one back takes time
TRACED_SECONDS = 20.0
#: a collect this many times its query's median is reported as slow
SLOW_FACTOR = 2.0
#: session settings of a traced run: the program's own profiler
#: annotations around each exec task (sql/physical/base.py); no sync added
TRACE_CONF = {"spark.rapids.tpu.trace.enabled": True}


def say(**record) -> None:
    """An earlier line of standard output: information, not the result."""
    print(json.dumps(record, default=str), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """The Python file ``benchmarks/<parts>`` as a module of its own: a
    query's reference, a per-layer metric's reader."""
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", "_".join(parts)), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class CompileMeter:
    """What XLA compiled, from jax's own monitoring events: every backend
    compile with its seconds, and what the persistent cache answered.
    (Copied from chip_smoke.py, where it was proven on the chip.)"""

    def __init__(self) -> None:
        import jax.monitoring as mon
        self.durations: list = []
        self.cache_hits = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append(float(secs))

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple:
        return (len(self.durations), self.cache_hits, self.cache_misses)

    def since(self, mark: tuple) -> dict:
        n0, h0, m0 = mark
        d = self.durations[n0:]
        return {"programs": len(d), "compile_seconds": sum(d),
                "slowest_seconds": max(d) if d else 0.0,
                "persistent_cache_hits": self.cache_hits - h0,
                "persistent_cache_misses": self.cache_misses - m0}


class Cell:
    """The files of one cell, read once."""

    def __init__(self, name: str, scale_override=None) -> None:
        self.workload = load_json("workloads", name + ".json")
        unknown = set(self.workload) - WORKLOAD_KEYS
        if unknown:
            raise SystemExit(f"run.py: workloads/{name}.json holds keys the "
                             f"harness does not implement: {sorted(unknown)}")
        self.config = load_json("configs", self.workload["config"] + ".json")
        self.scale = {**self.config["scale"], **(scale_override or {})}
        self.generator = load_module(
            "generators", self.config["generator"] + ".py")
        self.queries = list(self.workload["queries"])
        self.sql, self.spec, self.reference = {}, {}, {}
        for q in self.queries:
            with open(os.path.join(HERE, "queries", q + ".sql")) as f:
                self.sql[q] = f.read()
            self.spec[q] = load_json("queries", q + ".json")
            self.reference[q] = load_module("reference", q + ".py").reference
        self.scratch = os.path.join(HERE, ".cache", name)

    def build_tables(self, seed: int) -> dict:
        return self.generator.build_tables(self.scale, seed,
                                           self.config["tables"])

    def input_bytes(self, tables) -> dict:
        """Arrow bytes of the columns each query's SQL references."""
        return {q: sum(tables[t].column(c).nbytes
                       for t, cols in self.spec[q]["tables"].items()
                       for c in cols)
                for q in self.queries}


class Watch:
    """What the host was doing while a collect was slow, for the stall that
    PERF.md describes: a thread that sleeps ``period`` seconds at a time and
    notes every wake-up that came late (the whole process, or the
    interpreter, stood still), and the garbage collector's pauses.  Both
    cost microseconds; the window runs with them in every run."""

    def __init__(self, period: float = 0.01) -> None:
        self.period = period
        self.late: list = []        # (woke at, seconds late)
        self.gc_pauses: list = []   # (started at, seconds)
        self._gc_t0 = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._beat, daemon=True,
                                        name="bench-watch")

    def _beat(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.period):
            now = time.perf_counter()
            if now - last > 10 * self.period:
                self.late.append((now, now - last - self.period))
            last = now

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            now = time.perf_counter()
            if now - self._gc_t0 > 0.01:
                self.gc_pauses.append((self._gc_t0, now - self._gc_t0))
            self._gc_t0 = None

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def summary(self) -> dict:
        """All late wake-ups of the window: were normal collects free of
        them, a late one inside a slow collect is that collect's stall."""
        return {"late_wakeups": len(self.late),
                "late_total_s": sum(s for _, s in self.late),
                "late_max_s": max((s for _, s in self.late), default=0.0),
                "gc_pauses": len(self.gc_pauses)}

    def inside(self, start: float, end: float) -> dict:
        """The longest late wake-up and collector pause inside a span."""
        late = [s for t, s in self.late if start <= t <= end + 1.0]
        pauses = [s for t, s in self.gc_pauses if start <= t <= end]
        return {"watch_thread_late_s": max(late, default=0.0),
                "gc_pause_s": max(pauses, default=0.0)}


def slow_collects(cell, spans, names, cpu, watch: Watch) -> list:
    """Every collect of the window that took ``SLOW_FACTOR`` times its
    query's median or more, with what the host did meanwhile: the process's
    CPU seconds inside it (near 0: it waited; near the wall time: it
    computed), the watch thread's longest late wake-up (as long as the
    stall: the process or the interpreter stood still) and the longest
    collector pause."""
    out = []
    for q in cell.queries:
        mine = [i for i, name in enumerate(names) if name == q]
        if not mine:
            continue
        median = stats.percentile([spans[i][1] - spans[i][0] for i in mine],
                                  50)
        median_cpu = stats.percentile([cpu[i][1] - cpu[i][0] for i in mine],
                                      50)
        for i in mine:
            start, end = spans[i]
            if end - start >= SLOW_FACTOR * median:
                out.append({"query": q, "collect": i + 1,
                            "seconds": end - start, "median_s": median,
                            "process_cpu_s": cpu[i][1] - cpu[i][0],
                            "median_process_cpu_s": median_cpu,
                            **watch.inside(start, end)})
    return out


def register(sess, cell: Cell, tables) -> dict:
    """Registers every table of the configuration as the cell says: read
    from a parquet file on every collect, or held as an in-memory relation
    that the scan's upload cache keeps on the device."""
    kind = cell.workload["view"]
    storage = cell.config["storage"][kind]
    info = {"view": kind}
    if kind == "parquet":
        import pyarrow.parquet as pq
        os.makedirs(cell.scratch, exist_ok=True)
        written = 0
        for name, table in tables.items():
            path = os.path.join(cell.scratch, name + ".parquet")
            pq.write_table(table, path,
                           row_group_size=int(storage["row_group_rows"]))
            written += os.path.getsize(path)
            sess.read.parquet(path).createOrReplaceTempView(name)
        info["parquet_bytes"] = written
    elif kind == "memory":
        for name, table in tables.items():
            sess.create_dataframe(
                table, num_partitions=int(storage["partitions"])
            ).createOrReplaceTempView(name)
    else:
        raise ValueError(f"unknown view kind {kind!r}")
    return info


def warm(sess, cell: Cell, meter: CompileMeter) -> dict:
    """Collects each query ``WARM_COLLECTS`` times and says what each
    collect compiled and took.  A program that compiles something anew in
    every collect never reaches a steady state, and the compiles inside the
    window say so."""
    query_metrics = {}
    for q in cell.queries:
        for attempt in range(1, WARM_COLLECTS + 1):
            mark = meter.mark()
            t0 = time.perf_counter()
            sess.sql(cell.sql[q]).collect()
            seconds = time.perf_counter() - t0
            m = {k: v for k, v in dict(sess.last_query_metrics).items()
                 if isinstance(v, (int, float)) and v}
            say(phase="warm", query=q, collect=attempt, seconds=seconds,
                compiled=meter.since(mark), query_metrics=m)
            query_metrics[q] = dict(sess.last_query_metrics)
    return query_metrics


def drive(sess, cell: Cell, seconds: float, traced: bool):
    """The window: whole rounds of the cell's queries, so that every run
    holds the same mix.  A new round starts while less than ``seconds`` have
    passed (traced: at least one round, and another only while it would end
    inside ``TRACED_SECONDS``); the round in flight is finished.  Returns
    the first start, the (start, end) of every collect, the process's CPU
    seconds at both, the answers and the failures."""
    if traced:
        import jax.profiler
        annotate = lambda q: jax.profiler.TraceAnnotation("bench:" + q)
        budget = min(seconds, TRACED_SECONDS)
    else:
        annotate = lambda q: contextlib.nullcontext()
        budget = seconds
    spans, cpu, answers, failures = [], [], [], []
    first = time.perf_counter()
    round_s = 0.0
    while True:
        round_start = time.perf_counter()
        elapsed = round_start - first
        if (spans or failures) and (
                elapsed + (round_s if traced else 0.0) >= budget):
            break
        for q in cell.queries:
            text = cell.sql[q]
            c0 = time.process_time()
            t0 = time.perf_counter()
            try:
                with annotate(q):
                    table = sess.sql(text).collect()
            except Exception as e:  # noqa: BLE001 — a failed collect counts
                traceback.print_exc(file=sys.stderr)
                failures.append(f"{q}: {type(e).__name__}: {e}"[:500])
                continue
            spans.append((t0, time.perf_counter()))
            cpu.append((c0, time.process_time()))
            answers.append((q, table))
        round_s = time.perf_counter() - round_start
    return first, spans, cpu, answers, failures


def check(cell: Cell, tables, answers, float_dtype=None) -> dict:
    """Every answer of the window against the reference: per query the
    worst of each compared number, beside its limit.  ``float_dtype`` puts
    the reference, computed in that type, in the program's place (the
    control)."""
    compared = {}
    for q in cell.queries:
        spec = cell.spec[q]
        want = cell.reference[q](
            C.tables_for_reference(tables, spec["tables"]))
        if float_dtype is not None:
            got = [cell.reference[q](C.tables_for_reference(
                tables, spec["tables"], float_dtype))]
        else:
            got = [t.to_pandas(date_as_object=False)
                   for name, t in answers if name == q]
        numbers = C.worst(C.compare(g, want, spec) for g in got)
        for name in C.NUMBERS:
            compared[f"{q}.{name}"] = {"value": numbers[name],
                                       "limit": spec["limits"][name]}
        if numbers["float_column"]:
            compared[f"{q}.float_rel_gap"]["column"] = numbers["float_column"]
        compared[f"{q}.answers"] = {"value": len(got), "limit": None}
    return compared


def is_correct(compared: dict, failures) -> bool:
    if failures:
        return False
    for name, c in compared.items():
        if c["limit"] is None:
            if c["value"] < 1:      # a query with no answer to compare
                return False
        elif not c["value"] <= c["limit"]:
            return False
    return True


def decoder_report() -> dict:
    """How the program's file decoders answered so far in this process:
    files (row-group runs) engaged and declined, with the reasons."""
    try:
        from spark_rapids_tpu.io_ import decode_stats
        return {fmt: s for fmt, s in decode_stats.report().items()
                if s.get("files_engaged") or s.get("files_declined")}
    except Exception as e:  # noqa: BLE001 — information only
        return {"error": f"{type(e).__name__}: {e}"}


def read_metrics(names, run: dict) -> dict:
    """Each per-layer metric through its own reader, ``metrics/<name>.py``;
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for name in names:
        value = load_module("metrics", name + ".py").read(run)
        if value is not None:
            out[name] = float(value)
    return out


def manifest_metrics(workload: str):
    """(end-to-end, per-layer) metric entries of BENCHMARK.json that this
    cell reports: those without a ``workloads`` list, or listing it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)

    def mine(entries):
        return [m for m in entries
                if workload in m.get("workloads", [workload])]
    return mine(manifest["end_to_end"]), mine(manifest["per_layer"])


def execute(args) -> tuple:
    """The whole run.  Returns (result, platform is as asked)."""
    traced = bool(args.trace)
    cell = Cell(args.workload, args.scale)
    end_to_end, per_layer = manifest_metrics(args.workload)

    import importlib.metadata as md
    import jax
    devices = jax.devices()
    d0 = devices[0]
    chips = int(cell.config["chips"])
    fit = d0.platform == "tpu" and len(devices) >= chips
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            versions[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            versions[pkg] = None
    if not fit and not args.scale:
        print(f"run.py: needs {chips} tpu device(s), found {len(devices)} "
              f"of platform {d0.platform!r}", file=sys.stderr)
        return None, False

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql.physical import kernel_cache
    meter = CompileMeter()
    say(phase="device", platform=d0.platform, device_kind=d0.device_kind,
        count=len(devices), versions=versions,
        compile_cache_dir=srt.compile_cache_dir(),
        compile_cache_from_env=bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        workload=args.workload, seed=args.seed, scale=cell.scale,
        trace=int(traced), import_seconds=time.perf_counter() - _T0)

    shutil.rmtree(cell.scratch, ignore_errors=True)
    t0 = time.perf_counter()
    tables = cell.build_tables(args.seed)
    say(phase="datagen", seconds=time.perf_counter() - t0,
        tables={k: [v.num_rows, v.nbytes] for k, v in tables.items()})

    conf = dict(cell.config.get("session_conf", {}))
    if traced:
        conf.update(TRACE_CONF)
    sess = srt.session(**conf)
    t0 = time.perf_counter()
    info = register(sess, cell, tables)
    say(phase="register", seconds=time.perf_counter() - t0, **info)

    query_metrics = warm(sess, cell, meter)
    setup_compile = meter.since((0, 0, 0))
    say(phase="setup", compile=setup_compile)
    gc.collect()

    trace_dir = os.path.join(cell.scratch, "trace")
    profiler = contextlib.nullcontext()
    if traced:
        import jax.profiler
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # the host stays at its own speed
        profiler = jax.profiler.trace(trace_dir, profiler_options=options)
    mark = meter.mark()
    kc0 = kernel_cache.cache_stats()
    with profiler, Watch() as watch:
        first, spans, cpu, answers, failures = drive(
            sess, cell, float(args.seconds), traced)
    setup_s = first - _T0
    kc1 = kernel_cache.cache_stats()
    kc_delta = {k: kc1[k] - kc0[k] for k in kc1
                if isinstance(kc1[k], (int, float))}
    in_window = meter.since(mark)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:chips])

    window = stats.window_summary(spans) if spans else None
    say(phase="window", attempted=len(spans) + len(failures),
        failed=len(failures), failures=failures, window=window,
        samples=len(spans), compiled_in_window=in_window,
        kernel_cache=kc_delta, decoders=decoder_report(),
        watch=watch.summary())
    for slow in slow_collects(cell, spans, [q for q, _ in answers], cpu,
                              watch):
        say(phase="slow_collect", **slow)

    ops_off = {}
    for q in cell.queries:
        report = sess.explain(sess.sql(cell.sql[q]), all_ops=False)
        ops_off[q] = sum(NOT_ON_TPU in line for line in report.splitlines())

    reduced = None
    if traced:
        import glob
        import reduce_trace
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if files:
            t0 = time.perf_counter()
            reduced = reduce_trace.reduce(files[-1])
            say(phase="trace", file_bytes=os.path.getsize(files[-1]),
                reduce_seconds=time.perf_counter() - t0,
                **{k: v for k, v in reduced.items() if k != "collects"})
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(files[-1], args.keep_trace)

    # the reference runs last: the window has closed and the peak is read
    t0 = time.perf_counter()
    compared = check(cell, tables, answers)
    correct = is_correct(compared, failures) and window is not None
    say(phase="check", seconds=time.perf_counter() - t0)

    readable = reduced and reduced.get("collects") and reduced["device_planes"]
    run = {"window": window, "setup_s": setup_s,
           "trace": reduced if readable else None,
           "compile": {"setup": setup_compile, "window": in_window},
           "kernel_cache": kc_delta,
           "query_metrics": query_metrics, "ops_off_tpu": ops_off,
           "input_bytes": cell.input_bytes(tables),
           "peaks": None, "cell": cell.workload, "config": cell.config}
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    metrics = {}
    if fit and window is not None:
        peaks = load_json("peaks.json")
        if d0.device_kind not in peaks:
            raise SystemExit(f"run.py: no peaks for device kind "
                             f"{d0.device_kind!r} in peaks.json")
        run["peaks"] = peaks[d0.device_kind]
        if traced:
            units = {m["name"]: m["unit"] for m in per_layer}
            values = read_metrics(list(units), run)
        else:
            units = {m["name"]: m["unit"] for m in end_to_end}
            values = {k: {**window, "setup_s": setup_s}[k] for k in units}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items()}
    result = {"correct": bool(correct),
              "attempted": len(spans) + len(failures),
              "failed": len(failures), "metrics": metrics, "device": device}
    if readable:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["span_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared
    shutil.rmtree(cell.scratch, ignore_errors=True)
    for name, c in compared.items():
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r}"
              + (f" ({c['column']})" if "column" in c else ""),
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return result, fit and not args.scale


def scale_override(text: str) -> dict:
    return {k: json.loads(v) for k, v in
            (pair.split("=", 1) for pair in text.split(",") if pair)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--scale", type=scale_override, default=None,
                    metavar="KEY=VALUE[,KEY=VALUE]",
                    help="overrides keys of the configuration's scale, for "
                         "a rehearsal off the chip: the run prints no "
                         "result and ends non-zero")
    ap.add_argument("--keep-trace", default=None,
                    help="directory to copy the traced run's .xplane.pb to")
    args = ap.parse_args(argv)
    result, as_asked = execute(args)
    if result is None:
        return 1
    if not as_asked:
        say(phase="rehearsal", correct=result["correct"],
            attempted=result["attempted"], failed=result["failed"],
            compared=result["compared"])
        print("run.py: a rehearsal (not a TPU, or --scale given): no result",
              file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
