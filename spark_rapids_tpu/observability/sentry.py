"""Self-driving perf sentry — live-window detection, evidence ledger,
machine-named follow-ups (ISSUE 18, ROADMAP item 2).

Every on-chip number before this PR depended on a human noticing that the
device answered.  The sentry closes the loop as a subsystem:

1. **Probe** — :func:`device_probe` is a cancellable bounded-timeout
   device probe built on the serving tier's QueryContext deadline
   machinery: the probe op runs on a daemon thread while the caller
   polls the context; at the deadline the context is cancelled and the
   probe banks ``outcome=timeout`` with its elapsed time.  No silently
   hung probe threads, ever — every attempt is telemetry
   (``ok | degraded | timeout | refused``).  Failed probes back off
   exponentially from the base interval.
2. **Capture** — on ``ok`` (a non-CPU backend answered) the sentry runs
   the bench shape set (join/sort/window/coalesce + encoded-vs-raw)
   through ``bench.run_shape_set`` — the same ``_run_phase`` watchdog
   machinery the shell bench uses, so one wedged shape never forfeits
   the window.
3. **Diff** — the fresh artifact is ``bench_diff``-ed against the last
   **live**-evidence artifact, auto-located from the ledger (never a
   stale replay; ``no-baseline`` when the ledger holds none).
4. **Ledger** — an append-only JSONL evidence ledger
   (``.bench_capture/ledger.jsonl``, ``srt-ledger/1``): artifact path,
   evidence class, regression verdicts, the doctor's ranked
   next-bottleneck verdict, and a machine-named follow-up with
   quantified lever evidence (doctor.followup — e.g. ``sync-bound:
   readbacks=18, ms_per_readback=6.7, top_exec=...``).  Torn trailing
   lines (a crash mid-append) are skipped on read; appends are single
   O_APPEND writes so the ledger never rewrites history.

Surfaces: the telemetry server's ``/sentry`` route (ledger tail,
last-live-evidence age, probe state, current phase — served by
:func:`status_payload` for whichever sentry is active in the process)
and ``sentry_*`` registry metrics so SLO/health tooling sees evidence
staleness as a first-class signal.

Drive it from ``tools/perf_sentry.py``; embed it with::

    from spark_rapids_tpu.observability.sentry import PerfSentry
    sentry = PerfSentry.from_conf().start()   # honors sentry.* confs
    ...
    sentry.stop()   # leak-free: thread joined, probe contexts drained
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

#: evidence-ledger record schema (append-only JSONL)
LEDGER_SCHEMA = "srt-ledger/1"
#: /sentry route payload schema
STATUS_SCHEMA = "srt-sentry/1"
#: probe outcome classes (``wedged`` is bench.py's parent-side class for
#: a probe child that died without a verdict; in-process probes never
#: produce it)
PROBE_OUTCOMES = ("ok", "degraded", "timeout", "refused")
#: sentry lifecycle phases, in rough order of a capture cycle
PHASES = ("idle", "probe", "bench", "diff", "ledger", "stopped")
#: attempts kept in the in-memory probe telemetry window
PROBE_WINDOW = 64
#: exponential-backoff cap, as a multiple of the base probe interval
BACKOFF_MAX_X = 8

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_PROBE_IDS = itertools.count(1)
#: per-process artifact sequence — the timestamp in the artifact name is
#: second-resolution, so back-to-back windows (tests, tight simulated
#: loops) would otherwise collide on one path and the fresh artifact
#: would overwrite the baseline before the diff reads it
_ARTIFACT_IDS = itertools.count(1)

#: the process's active sentry (``/sentry`` route source); installed by
#: PerfSentry.start(), cleared by stop()
_ACTIVE: "Optional[PerfSentry]" = None


def _iso_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def default_ledger_path() -> str:
    return os.path.join(_REPO_ROOT, ".bench_capture", "ledger.jsonl")


# --------------------------------------------------------------------------
# cancellable bounded-timeout device probe
# --------------------------------------------------------------------------

def device_probe(timeout_s: float = 30.0,
                 op: Optional[Callable[[], Any]] = None) -> Dict[str, Any]:
    """One cancellable device probe with a hard deadline.

    The probe op (default: ``float(jnp.sum(jnp.ones(8)))`` + backend
    name) runs on a daemon thread; the caller polls a deadline-bearing
    :class:`~spark_rapids_tpu.serving.lifecycle.QueryContext` — the
    exact cancellation machinery queries use — and on expiry cancels the
    context and returns.  A wedged device orphans one daemon thread
    holding a cancelled context; it never hangs the caller and its
    result (if it ever lands) is discarded.

    Returns ``{"outcome": ok|degraded|timeout|refused,
    "elapsed_ms": float, "platform"?: str, "error"?: str}`` —
    ``degraded`` means the op answered but on the CPU platform (jax
    fell back after a failed device-plugin init: no accelerator, not a
    live window).
    """
    from ..serving import lifecycle as lc
    qctx = lc.QueryContext(query_id=next(_PROBE_IDS),
                           session_id="sentry",
                           deadline_ms=max(1, int(timeout_s * 1000)))
    lc.register(qctx)
    box: Dict[str, Any] = {}

    def _default_op() -> str:
        import jax
        import jax.numpy as jnp
        float(jnp.sum(jnp.ones(8)))
        return str(jax.default_backend())

    def run() -> None:
        try:
            platform = (op or _default_op)()
            if not qctx.cancelled:
                box["platform"] = platform
        except BaseException as e:  # noqa: BLE001 - classified below
            box["error"] = f"{type(e).__name__}: {e}"

    t0 = time.perf_counter()
    th = threading.Thread(target=run, daemon=True,
                          name="srt-sentry-probe")
    th.start()
    try:
        while th.is_alive():
            try:
                qctx.check("sentry.probe")
            except lc.QueryCancelled:  # includes QueryDeadlineExceeded
                break
            th.join(lc.POLL_S)
        out: Dict[str, Any] = {
            "elapsed_ms": round((time.perf_counter() - t0) * 1000, 1)}
        if th.is_alive():
            qctx.cancel(f"probe exceeded its {timeout_s:.0f}s budget")
            out["outcome"] = "timeout"
        elif "error" in box:
            out["outcome"] = "refused"
            out["error"] = str(box["error"])[:200]
        else:
            plat = box.get("platform")
            out["outcome"] = ("degraded" if plat in (None, "cpu")
                              else "ok")
            if plat is not None:
                out["platform"] = plat
        return out
    finally:
        lc.unregister(qctx)


def subprocess_probe(timeout_s: float = 30.0,
                     env: Optional[Dict[str, str]] = None
                     ) -> Dict[str, Any]:
    """:func:`device_probe` in a throwaway subprocess — the daemon-mode
    default: a wedged device kills a child, not the long-lived sentry,
    and timed-out probe threads can never pile up in the daemon.  The
    child holds the chip only while it runs; the daemon itself never
    touches JAX."""
    code = ("import json\n"
            "from spark_rapids_tpu.observability.sentry import "
            "device_probe\n"
            f"print('SRT-PROBE ' + json.dumps(device_probe({timeout_s!r})))"
            "\n")
    child_env = dict(env if env is not None else os.environ)
    child_env["PYTHONPATH"] = (_REPO_ROOT + os.pathsep
                               + child_env.get("PYTHONPATH", ""))
    t0 = time.perf_counter()
    try:
        # generous outer budget: the child's own deadline machinery does
        # the real bounding; this only catches a wedged interpreter
        proc = subprocess.run(
            [sys.executable, "-c", code], env=child_env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=timeout_s + 60.0)
    except subprocess.TimeoutExpired:
        return {"outcome": "timeout",
                "elapsed_ms": round((time.perf_counter() - t0) * 1000, 1),
                "error": "probe subprocess wedged past its budget"}
    for line in reversed(proc.stdout.decode(
            errors="replace").splitlines()):
        if line.startswith("SRT-PROBE "):
            try:
                return json.loads(line[len("SRT-PROBE "):])
            except ValueError:
                break
    return {"outcome": "refused",
            "elapsed_ms": round((time.perf_counter() - t0) * 1000, 1),
            "error": ("probe subprocess exited "
                      f"{proc.returncode}: "
                      + proc.stderr.decode(errors='replace')[-160:])}


# --------------------------------------------------------------------------
# append-only evidence ledger (srt-ledger/1)
# --------------------------------------------------------------------------

class EvidenceLedger:
    """Append-only JSONL evidence ledger.  One record per captured
    window; records are single ``O_APPEND`` line writes (fsync'd), reads
    skip torn or foreign lines — a crash mid-append can tear at most the
    final line and never loses banked history."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_ledger_path()

    def append(self, record: Dict[str, Any]) -> Dict[str, Any]:
        rec = dict(record)
        rec.setdefault("schema", LEDGER_SCHEMA)
        rec.setdefault("at", _iso_now())
        rec.setdefault("unix", round(time.time(), 3))
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        line = json.dumps(rec, default=str) + "\n"
        with open(self.path, "a") as fh:
            fh.write(line)
            fh.flush()
            os.fsync(fh.fileno())
        return rec

    def entries(self) -> List[Dict[str, Any]]:
        out: List[Dict[str, Any]] = []
        try:
            fh = open(self.path)
        except OSError:
            return out
        with fh:
            for line in fh:
                line = line.strip()
                if not line.startswith("{"):
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # torn line (crash mid-append)
                if isinstance(rec, dict) \
                        and rec.get("schema") == LEDGER_SCHEMA:
                    out.append(rec)
        return out

    def tail(self, n: int = 10) -> List[Dict[str, Any]]:
        return self.entries()[-max(0, n):]

    def last_live(self) -> Optional[Dict[str, Any]]:
        """Newest ``evidence: live`` entry — THE comparison baseline;
        stale replays never qualify no matter how fresh their append."""
        for rec in reversed(self.entries()):
            if rec.get("evidence") == "live":
                return rec
        return None

    def last_live_age_s(self,
                        now: Optional[float] = None) -> Optional[float]:
        rec = self.last_live()
        if rec is None:
            return None
        return max(0.0, (now if now is not None else time.time())
                   - float(rec.get("unix", 0.0)))


# --------------------------------------------------------------------------
# default bench / diff plumbing (lazy, repo-checkout based)
# --------------------------------------------------------------------------

def _load_tool(name: str):
    """Import a repo tools/ or top-level module by file path (the repo
    is not pip-installed; bench.py and tools/*.py live beside the
    package).  Returns None when the file is absent (wheel install)."""
    import importlib.util
    for rel in (name + ".py", os.path.join("tools", name + ".py")):
        path = os.path.join(_REPO_ROOT, rel)
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                f"srt_sentry_{name}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod
    return None


def run_shape_set_inprocess(shapes, rows: int, budget_s: float,
                            artifact_path: Optional[str] = None,
                            evidence: Optional[str] = None
                            ) -> Dict[str, Any]:
    """bench.run_shape_set in this process (imports jax here — tests
    and CI simulated-window runs; the daemon uses the subprocess
    variant)."""
    bench = _load_tool("bench")
    if bench is None:
        return {"error": "bench.py not found beside the package"}
    return bench.run_shape_set(shapes=shapes, rows=rows,
                               budget_s=budget_s,
                               artifact_path=artifact_path,
                               evidence=evidence)


def subprocess_shape_set(shapes, rows: int, budget_s: float,
                         artifact_path: Optional[str] = None,
                         evidence: Optional[str] = None,
                         env: Optional[Dict[str, str]] = None
                         ) -> Dict[str, Any]:
    """bench.run_shape_set in a subprocess — the daemon-mode default,
    keeping the long-lived sentry jax-free (bench.py's own parent rule).
    On a timeout the partial artifact banked shape-by-shape at
    ``artifact_path`` is recovered, so a wedged shape set still yields
    whatever finished."""
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {_REPO_ROOT!r})\n"
        "import bench\n"
        f"r = bench.run_shape_set(shapes={list(shapes)!r}, "
        f"rows={int(rows)!r}, budget_s={float(budget_s)!r}, "
        f"artifact_path={artifact_path!r}, evidence={evidence!r})\n"
        "print('SRT-ARTIFACT ' + json.dumps(r, default=str))\n")
    child_env = dict(env if env is not None else os.environ)
    child_env["PYTHONPATH"] = (_REPO_ROOT + os.pathsep
                               + child_env.get("PYTHONPATH", ""))
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], env=child_env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            timeout=budget_s + 120.0)
        for line in reversed(proc.stdout.decode(
                errors="replace").splitlines()):
            if line.startswith("SRT-ARTIFACT "):
                return json.loads(line[len("SRT-ARTIFACT "):])
        err = ("shape-set subprocess exited "
               f"{proc.returncode}: "
               + proc.stderr.decode(errors='replace')[-200:])
    except subprocess.TimeoutExpired:
        err = "shape-set subprocess exceeded its budget"
    except ValueError as e:
        err = f"unparseable shape-set artifact line: {e}"
    # recover the shape-by-shape partial artifact, if any
    if artifact_path:
        try:
            with open(artifact_path) as fh:
                rec = json.loads(fh.read())
            rec["note"] = ((rec.get("note", "") + "; ").lstrip("; ")
                           + "recovered partial artifact: " + err)
            return rec
        except (OSError, ValueError):
            pass
    return {"error": err}


# --------------------------------------------------------------------------
# the sentry daemon
# --------------------------------------------------------------------------

class PerfSentry:
    """The autonomous probe → bench → diff → ledger loop.

    Every collaborator is injectable (``probe``, ``bench``, ``ledger``)
    so tests and the CI simulated-window mode drive the full pipeline
    with a fake probe and a tiny bench.  ``start()`` runs the loop on a
    daemon thread named ``srt-sentry``; ``stop()`` is leak-free by
    contract (thread joined, probe QueryContexts unregistered —
    tools/leak_sentinel.py --sentry asserts both).
    """

    def __init__(self,
                 probe: Optional[Callable[[], Dict[str, Any]]] = None,
                 bench: Optional[Callable[[List[str]],
                                          Dict[str, Any]]] = None,
                 ledger: Any = None,
                 shapes=("join", "sort", "window", "coalesce",
                         "encoded"),
                 rows: int = 4_000_000,
                 interval_s: float = 480.0,
                 probe_timeout_s: float = 30.0,
                 bench_budget_s: float = 1800.0,
                 diff_threshold: float = 0.10,
                 capture_dir: Optional[str] = None,
                 entry_extra: Optional[Dict[str, Any]] = None):
        self._probe = probe
        self._bench = bench
        self.ledger = (ledger if isinstance(ledger, EvidenceLedger)
                       else EvidenceLedger(ledger))
        self.shapes = [str(s) for s in shapes]
        self.rows = int(rows)
        self.interval_s = float(interval_s)
        self.probe_timeout_s = float(probe_timeout_s)
        self.bench_budget_s = float(bench_budget_s)
        self.diff_threshold = float(diff_threshold)
        self.capture_dir = capture_dir or os.path.dirname(
            os.path.abspath(self.ledger.path))
        self.entry_extra = dict(entry_extra or {})
        self.phase = "idle"
        self.backoff_s = self.interval_s
        self.windows = 0
        self.probe_attempts: List[Dict[str, Any]] = []
        self.last_entry: Optional[Dict[str, Any]] = None
        self.last_error: Optional[str] = None
        self._consecutive_failures = 0
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # --- conf plumbing ----------------------------------------------------
    @classmethod
    def from_conf(cls, conf=None, **overrides) -> "PerfSentry":
        """Build from the ``spark.rapids.tpu.sentry.*`` confs (kwargs
        win over conf values)."""
        from ..config import (RapidsConf, SENTRY_LEDGER_PATH,
                              SENTRY_PROBE_INTERVAL_MS,
                              SENTRY_PROBE_TIMEOUT_MS, SENTRY_SHAPES)
        conf = conf or RapidsConf.get_global()
        kw: Dict[str, Any] = {
            "interval_s": int(conf.get(SENTRY_PROBE_INTERVAL_MS)) / 1000,
            "probe_timeout_s":
                int(conf.get(SENTRY_PROBE_TIMEOUT_MS)) / 1000,
            "shapes": [s.strip() for s in
                       str(conf.get(SENTRY_SHAPES)).split(",")
                       if s.strip()],
            "ledger": str(conf.get(SENTRY_LEDGER_PATH) or "") or None,
        }
        kw.update(overrides)
        return cls(**kw)

    @staticmethod
    def enabled(conf=None) -> bool:
        from ..config import RapidsConf, SENTRY_ENABLED
        conf = conf or RapidsConf.get_global()
        return bool(conf.get(SENTRY_ENABLED))

    # --- metrics ----------------------------------------------------------
    def _reg(self):
        from . import metrics as OM
        return OM.get_registry()

    def _metric(self, kind: str, name: str, value: float = 1.0,
                **labels: Any) -> None:
        # the sentry IS observability infrastructure: it records into
        # the registry unconditionally (tiny bounded cardinality), not
        # behind the METRICS kill switch
        try:
            reg = self._reg()
            getattr(reg, kind)(name, value, **labels)
        except Exception:  # noqa: BLE001 - metrics never take it down
            pass

    def _set_phase(self, phase: str) -> None:
        self.phase = phase
        self._metric("set_gauge", "sentry_phase_code",
                     float(PHASES.index(phase) if phase in PHASES
                           else -1))

    # --- one cycle --------------------------------------------------------
    def _probe_once(self) -> Dict[str, Any]:
        self._set_phase("probe")
        fn = self._probe or (
            lambda: device_probe(self.probe_timeout_s))
        try:
            att = dict(fn())
        except BaseException as e:  # noqa: BLE001 - probes never raise out
            att = {"outcome": "refused",
                   "error": f"{type(e).__name__}: {e}"}
        att.setdefault("outcome", "refused")
        att.setdefault("at", _iso_now())
        with self._lock:
            self.probe_attempts.append(att)
            del self.probe_attempts[:-PROBE_WINDOW]
        self._metric("inc", "sentry_probe_attempts_total",
                     outcome=str(att["outcome"]))
        if "elapsed_ms" in att:
            self._metric("observe", "sentry_probe_ms",
                         float(att["elapsed_ms"]))
        age = self.ledger.last_live_age_s()
        if age is not None:
            self._metric("set_gauge", "sentry_last_live_evidence_age_s",
                         float(age))
        return att

    def run_once(self) -> Optional[Dict[str, Any]]:
        """One probe tick; on a live window, the full capture cycle.
        Returns the appended ledger entry, or None when no window
        opened.  Exceptions are banked (``last_error`` + metrics), never
        raised — the loop must survive anything."""
        try:
            att = self._probe_once()
            if att.get("outcome") != "ok":
                self._consecutive_failures += 1
                self.backoff_s = min(
                    self.interval_s * BACKOFF_MAX_X,
                    self.interval_s
                    * (2 ** min(self._consecutive_failures - 1, 10)))
                self._set_phase("idle")
                return None
            self._consecutive_failures = 0
            self.backoff_s = self.interval_s
            self.windows += 1
            self._metric("inc", "sentry_windows_total")
            entry = self._capture_window(att)
            self._metric("inc", "sentry_runs_total", result="ok")
            return entry
        except BaseException as e:  # noqa: BLE001 - loop must survive
            self.last_error = f"{type(e).__name__}: {e}"
            self._metric("inc", "sentry_runs_total", result="error")
            return None
        finally:
            self._set_phase("idle")

    def _capture_window(self,
                        probe_att: Dict[str, Any]) -> Dict[str, Any]:
        stamp = time.strftime("%Y-%m-%dT%H-%M-%SZ", time.gmtime())
        artifact_path = os.path.join(
            self.capture_dir,
            f"sentry_{stamp}_{os.getpid()}_{next(_ARTIFACT_IDS)}.json")
        self._set_phase("bench")
        bench_fn = self._bench or (
            lambda shapes: subprocess_shape_set(
                shapes, self.rows, self.bench_budget_s,
                artifact_path=artifact_path))
        artifact = dict(bench_fn(self.shapes) or {})
        # persist the artifact beside the ledger whatever produced it
        try:
            os.makedirs(self.capture_dir, exist_ok=True)
            tmp = f"{artifact_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fh:
                fh.write(json.dumps(artifact, default=str) + "\n")
            os.replace(tmp, artifact_path)
        except OSError:
            pass

        self._set_phase("diff")
        diff_verdict = self._diff_against_baseline(artifact)

        self._set_phase("ledger")
        from . import doctor as OD
        diag = OD.diagnose_artifact(artifact)
        entry = self.ledger.append(dict(self.entry_extra, **{
            "artifact": artifact_path,
            "evidence": str(artifact.get("evidence")
                            or ("cpu-fallback"
                                if artifact.get("platform")
                                in (None, "cpu") else "live")),
            "platform": artifact.get("platform"),
            "probe": {k: probe_att.get(k)
                      for k in ("outcome", "elapsed_ms", "platform",
                                "at") if probe_att.get(k) is not None},
            "shapes": self.shapes,
            "diff": diff_verdict,
            "doctor": OD.compact(diag),
            "followup": OD.followup(diag),
        }))
        with self._lock:
            self.last_entry = entry
        self._metric("inc", "sentry_ledger_entries_total")
        age = self.ledger.last_live_age_s()
        if age is not None:
            self._metric("set_gauge", "sentry_last_live_evidence_age_s",
                         float(age))
        return entry

    def _diff_against_baseline(self,
                               artifact: Dict[str, Any]
                               ) -> Dict[str, Any]:
        """bench_diff the fresh artifact against the newest live-evidence
        ledger entry (never a stale replay — tools/bench_diff.py
        --ledger shares this resolution)."""
        base = self.ledger.last_live()
        if base is None or not base.get("artifact"):
            return {"verdict": "no-baseline", "baseline": None}
        bd = _load_tool("bench_diff")
        if bd is None:
            return {"verdict": "unavailable",
                    "baseline": base.get("artifact"),
                    "note": "tools/bench_diff.py not found"}
        try:
            a = bd.comparable_metrics(bd.load_artifact(base["artifact"]))
            b = bd.comparable_metrics(artifact)
            rows = bd.diff(a, b, self.diff_threshold)
        except (OSError, ValueError) as e:
            return {"verdict": "error",
                    "baseline": base.get("artifact"),
                    "note": f"{type(e).__name__}: {e}"}
        regressed = [r for r in rows if r["verdict"] == "REGRESSED"]
        improved = [r for r in rows if r["verdict"] == "IMPROVED"]
        out = {
            "verdict": "regressed" if regressed else "ok",
            "baseline": base["artifact"],
            "baseline_at": base.get("at"),
            "threshold": self.diff_threshold,
            "regressed": len(regressed),
            "improved": len(improved),
            "compared": len(rows),
            "top_regressions": [
                {"metric": r["metric"], "a": r["a"], "b": r["b"],
                 "ratio": r.get("ratio")}
                for r in sorted(
                    regressed,
                    key=lambda r: (r.get("ratio") or 0.0))[:5]],
        }
        self._metric("set_gauge", "sentry_last_diff_regressions",
                     float(len(regressed)))
        return out

    # --- daemon lifecycle -------------------------------------------------
    def start(self) -> "PerfSentry":
        """Run the probe loop on a daemon thread (idempotent) and
        install this sentry as the process's ``/sentry`` source."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="srt-sentry", daemon=True)
            self._thread.start()
        set_active(self)
        return self

    def _loop(self) -> None:
        while not self._stop.is_set():
            self.run_once()
            self._stop.wait(max(0.05, self.backoff_s))
        self._set_phase("stopped")

    def stop(self, timeout: float = 10.0) -> None:
        """Leak-free shutdown: signal the loop, join the thread, drop
        the active-sentry registration (idempotent)."""
        self._stop.set()
        th = self._thread
        self._thread = None
        if th is not None:
            th.join(timeout)
        if _ACTIVE is self:
            set_active(None)
        if self.phase != "stopped":
            self._set_phase("stopped")

    @property
    def running(self) -> bool:
        th = self._thread
        return th is not None and th.is_alive()

    # --- /sentry route payload --------------------------------------------
    def status(self) -> Dict[str, Any]:
        with self._lock:
            attempts = list(self.probe_attempts)
            last_entry = self.last_entry
        outcomes: Dict[str, int] = {}
        for a in attempts:
            k = str(a.get("outcome"))
            outcomes[k] = outcomes.get(k, 0) + 1
        entries = self.ledger.entries()
        return {
            "schema": STATUS_SCHEMA,
            "phase": self.phase,
            "running": self.running,
            "windows": self.windows,
            "probe": {
                "attempts": len(attempts),
                "outcomes": outcomes,
                "last": attempts[-1] if attempts else None,
                "interval_s": self.interval_s,
                "timeout_s": self.probe_timeout_s,
                "next_delay_s": self.backoff_s,
            },
            "ledger": {
                "path": self.ledger.path,
                "entries": len(entries),
                "tail": entries[-5:],
            },
            "last_live_age_s": self.ledger.last_live_age_s(),
            "last_entry_at": (last_entry or {}).get("at"),
            "last_error": self.last_error,
            "shapes": self.shapes,
        }


# --------------------------------------------------------------------------
# process-global active sentry (the /sentry telemetry route source)
# --------------------------------------------------------------------------

def set_active(sentry: Optional[PerfSentry]) -> None:
    global _ACTIVE
    _ACTIVE = sentry


def get_active() -> Optional[PerfSentry]:
    return _ACTIVE


def status_payload() -> Dict[str, Any]:
    """What the telemetry server's ``/sentry`` route serves: the active
    sentry's status, or a minimal 'none' payload that still reports the
    default ledger so staleness is visible from any process."""
    s = _ACTIVE
    if s is not None:
        return s.status()
    led = EvidenceLedger()
    return {
        "schema": STATUS_SCHEMA,
        "phase": "none",
        "running": False,
        "note": "no active sentry in this process",
        "ledger": {"path": led.path, "entries": len(led.entries()),
                   "tail": led.tail(3)},
        "last_live_age_s": led.last_live_age_s(),
    }


def maybe_start_from_conf(conf=None, **overrides) -> Optional[PerfSentry]:
    """Start a conf-configured sentry iff the master switch is on
    (``spark.rapids.tpu.sentry.enabled``); returns None otherwise."""
    if not PerfSentry.enabled(conf):
        return None
    return PerfSentry.from_conf(conf, **overrides).start()
