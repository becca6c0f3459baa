"""Module-level compiled-kernel cache.

The reference's whole perf model is one kernel launch per op per batch with
*reused* compiled kernels (``RapidsConf.scala:550``, SURVEY §3.3) — cuDF
kernels are compiled once per process.  Here the analog is: one ``jax.jit``
wrapper per *program identity* (exec type + bound expression tree + static
params), shared across every exec instance and every ``collect()``.  XLA's
own trace cache then keys on input avals (schema dtypes, capacity buckets,
batch names), so repeated queries hit compiled code instead of re-tracing.

Program identity keys are built from ``Expression.semantic_key()`` over
*bound* expression trees (BoundReference → ordinal), so two plans of the
same query constructed at different times share kernels.
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
import time
from collections import OrderedDict
from typing import Any, Callable, Dict, Optional, Tuple

import jax.monitoring

from ...observability import metrics as _om
from ...observability import tracer as _trace
from ...robustness import faults as _faults

#: LRU bound — each entry pins its exec instance (and that exec's child
#: subtree) via the jitted closure, and keys embed literal values, so an
#: unbounded cache would grow with every distinct constant a long-running
#: session ever used.  Reference analog: cuDF kernels are per-op, not
#: per-literal; bounding the per-literal programs keeps the same spirit.
_MAX_ENTRIES = int(os.environ.get("SRT_KERNEL_CACHE_SIZE", "1024"))

_CACHE: "OrderedDict[Tuple, Callable]" = OrderedDict()
_LOCK = threading.Lock()
_STATS = {"hits": 0, "misses": 0, "evictions": 0,
          "compiles": 0, "compile_ms": 0.0, "dispatches": 0,
          # what jax itself traced, lowered and compiled (or loaded from
          # the persistent cache), through this cache or past it: the
          # listener below.  0 over a warm collect is the steady state.
          "retraces": 0, "retrace_ms": 0.0,
          # fresh ``jax.jit`` wrappers built for a keyless exec program
          # (base.py ``_jit`` without a key): each one traces anew
          "unkeyed_jits": 0}

#: cache GENERATION, bumped under ``_LOCK`` by every :func:`clear_cache`.
#: The concurrent-sessions clearing contract (docs/serving.md): a clear
#: while another session executes never breaks in-flight work — handed-out
#: ``_TrackedKernel`` wrappers keep their jitted callables (the dict only
#: drops ITS references) — and learned state derived from a dead
#: generation's programs (join selectivities, aggregate group-size
#: speculations) is dropped instead of written back: learners capture the
#: generation when they first consult the cache and the recorders refuse
#: the write when it no longer matches.
_GENERATION = [0]

#: per-key trace+compile accounting (observability report: "compile ms
#: per key"); keyed by the human-readable kernel label
_COMPILE_BY_KEY: Dict[str, Dict[str, float]] = {}

#: per-program accounting of jax's own trace / lower / compile events
#: (name -> traces, ms), by the name the program carries (``srt_...``
#: through this cache; the traced function's own name past it)
_RETRACE_BY_NAME: Dict[str, Dict[str, float]] = {}
#: its own lock: the listener runs inside whatever call made jax compile
_RETRACE_LOCK = threading.Lock()

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration")   # cache loads included

try:
    from jax._src.core import trace_state_clean as _outermost_trace
except ImportError:  # pragma: no cover - a jax that moved it
    def _outermost_trace() -> bool:
        return True


def _on_jax_duration(event: str, secs: float, fun_name: str = "",
                     **_kw) -> None:
    """``jax.monitoring`` listener: fires on a cache miss of ANY jitted
    program of the process, never per launch.  A ``jnp`` call traced
    inside another program's trace reports too; its time lies inside the
    outer one's, so only the outermost trace is counted."""
    traced = event == _TRACE_EVENT
    if traced:
        if not _outermost_trace():
            return
    elif event not in _LOWER_COMPILE_EVENTS:
        return
    name = str(fun_name)
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    ms = float(secs) * 1e3
    with _RETRACE_LOCK:
        _STATS["retrace_ms"] += ms
        e = _RETRACE_BY_NAME.setdefault(name, {"traces": 0, "ms": 0.0})
        e["ms"] += ms
        if traced:
            _STATS["retraces"] += 1
            e["traces"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)

#: per-key LAUNCH accounting (doctor's dispatch-bound evidence names the
#: top kernel keys); lock-free like _STATS["dispatches"] — a lost
#: increment under contention is metric noise, a per-launch lock is
#: hot-path cost.  Keyed by the human-readable kernel label.
_DISPATCH_BY_KEY: Dict[str, int] = {}


class _TrackedKernel:
    """Thin wrapper over a jitted callable that marks every launch as a
    ``dispatch`` span (``compile`` when it traces), detects re-traces
    (via the jit wrapper's ``_cache_size``) and accounts trace+compile
    wall time per kernel key.

    Cost model: when tracing is OFF this is two dict lookups + one extra
    Python call per kernel launch (launches are per batch per op, never
    per row).  When ON (either sink), a ``_cache_size()`` probe brackets
    the call; a size increase means this call traced+compiled, and its
    wall time (dispatch included — XLA compiles synchronously inside the
    call) is recorded against the key.
    """

    __slots__ = ("_fn", "_label")

    def __init__(self, fn: Callable, label: str):
        self._fn = fn
        self._label = label

    def __call__(self, *args, **kwargs):
        _faults.maybe_inject("kernel.compile", exc=RuntimeError,
                             kernel=self._label)
        # device-dispatch accounting (whole-stage fusion evidence,
        # docs/whole_stage.md): one increment per compiled-program launch.
        # Deliberately lock-free — a lost increment under contention is
        # metric noise, a per-launch lock is hot-path cost.
        _STATS["dispatches"] = _STATS["dispatches"] + 1
        _DISPATCH_BY_KEY[self._label] = \
            _DISPATCH_BY_KEY.get(self._label, 0) + 1
        if _om.METRICS["on"]:
            reg = _om.get_registry()
            reg.inc("device_dispatches_total")
            # kernel-labeled series: the doctor's dispatch-bound verdict
            # names the top-K launch sources from these
            reg.inc("device_dispatches_by_kernel_total",
                    kernel=self._label)
        tr = _trace.TRACING
        if not (tr["on"] or tr["profiler"]):
            return self._fn(*args, **kwargs)
        if tr["on"]:
            _trace.get_tracer().counter("deviceDispatches")
        cs = getattr(self._fn, "_cache_size", None)
        before = cs() if cs is not None else -1
        t0 = time.perf_counter()
        # a wrapper that has run nothing yet is about to trace and compile
        with _trace.span("compile" if before == 0 else "dispatch",
                         self._label) as sp:
            out = self._fn(*args, **kwargs)
            traced = cs is not None and cs() > before
            if traced and before > 0:
                # a re-trace for a new input signature: known only now,
                # marked on the launch's own span (both sinks)
                sp.set_metadata(retraced=1)
        dt = time.perf_counter() - t0
        if traced:
            ms = dt * 1e3
            with _LOCK:
                _STATS["compiles"] += 1
                _STATS["compile_ms"] += ms
                e = _COMPILE_BY_KEY.setdefault(
                    self._label, {"compiles": 0, "ms": 0.0})
                e["compiles"] += 1
                e["ms"] += ms
            if _om.METRICS["on"]:
                _om.get_registry().observe("kernel_compile_ms", ms,
                                           kernel=self._label)
        return out


def donation_supported() -> bool:
    """XLA:CPU accepts but ignores donate_argnums (and warns per unusable
    buffer); only real device backends reclaim donated HBM.  The donation
    DECISION (memory/retention.py) runs everywhere — this gates only
    whether the marker reaches jax.jit."""
    try:
        import jax
        return jax.default_backend() not in ("cpu",)
    except Exception:  # pragma: no cover - backend probe failure
        return False


_ADDRESS = re.compile(r" at 0x[0-9a-fA-F]+")


def _render_key(x) -> str:
    """A rendering of a program key that is the same in every process:
    no ``hash()`` (Python salts it per process), no memory addresses, no
    set order."""
    if isinstance(x, (tuple, list)):
        return "(" + ",".join(_render_key(e) for e in x) + ")"
    if isinstance(x, (set, frozenset)):
        return "{" + ",".join(sorted(_render_key(e) for e in x)) + "}"
    if isinstance(x, dict):
        return "{" + ",".join(sorted(
            _render_key(k) + ":" + _render_key(v)
            for k, v in x.items())) + "}"
    if isinstance(x, type):
        return f"{x.__module__}.{x.__qualname__}"
    return _ADDRESS.sub("", repr(x))


def program_name(key: Tuple, fn: Callable) -> str:
    """``srt_<ExecType>_<what>_<digest>``: the one name a stage program
    carries in the profiler's ``XLA Modules`` line (as ``jit_srt_...``),
    in ``jax_log_compiles``, in the ``dispatch``/``compile`` spans and in
    the per-key stats.  ``what`` is the key's own tag (``gather``,
    ``split``, ...) or the traced function's name; the digest tells two
    programs of one exec type apart (Q1's partial aggregate from Q6's)
    and is identical in every process, so the persistent compile cache,
    whose key holds the module name, keeps hitting across runs."""
    head = (key[0] if key and isinstance(key[0], str)
            else "program").replace("_", "")
    tag = key[1] if len(key) > 1 and isinstance(key[1], str) else ""
    what = tag if re.fullmatch(r"\w{1,24}", tag) \
        else getattr(fn, "__name__", "fn").strip("_")
    digest = hashlib.sha1(_render_key(key).encode()).hexdigest()[:8]
    return re.sub(r"\W", "_", f"srt_{head}_{what}_{digest}")


def exec_of_program(name: str) -> str:
    """The exec type inside a :func:`program_name`."""
    parts = name.split("_")
    return parts[1] if len(parts) > 2 and parts[0] == "srt" else name


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``: jax names the compiled module after the
    jitted callable."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    return program


def cached_jit(key: Tuple, fn: Callable,
               donate_argnums: Optional[Tuple[int, ...]] = None) -> Callable:
    """Return the process-wide jitted callable for ``key``.

    ``fn`` is jitted and cached on first sight of ``key``; later callers get
    the cached wrapper (their own ``fn`` is dropped — the key must capture
    everything that affects the trace).  Least-recently-used entries are
    evicted past ``_MAX_ENTRIES``.

    ``donate_argnums`` requests XLA input-buffer donation for those
    argument positions (whole-stage fusion, docs/whole_stage.md).  The
    caller owns BOTH safety obligations: the key must distinguish donating
    from non-donating programs, and donated arguments must be sole-owner
    batches (retention.may_donate) that are never touched after the call.
    """
    with _LOCK:
        cached = _CACHE.get(key)
        if cached is not None:
            _STATS["hits"] += 1
            _CACHE.move_to_end(key)
            _om.inc("kernel_cache_hits_total")
            return cached
        _STATS["misses"] += 1
        _om.inc("kernel_cache_misses_total")
        import jax
        label = program_name(key, fn)
        fn = _named(fn, label)
        if donate_argnums and donation_supported():
            jitted = jax.jit(fn, donate_argnums=tuple(donate_argnums))
        else:
            jitted = jax.jit(fn)
        wrapper = _TrackedKernel(jitted, label)
        _CACHE[key] = wrapper
        while len(_CACHE) > _MAX_ENTRIES:
            _CACHE.popitem(last=False)
            _STATS["evictions"] += 1
        return wrapper


def cache_stats() -> Dict[str, int]:
    with _LOCK:
        return dict(_STATS, size=len(_CACHE))


def cache_generation() -> int:
    """Current cache generation (bumped by every clear) — learners of
    cache-coupled state (join selectivities, agg size speculations)
    capture this at lookup time and pass it back at record time so a
    concurrent clear drops, rather than resurrects, their learning."""
    with _LOCK:
        return _GENERATION[0]


def compile_stats_by_key() -> Dict[str, Dict[str, float]]:
    """Per-kernel-key trace+compile accounting (label -> compiles, ms);
    only accrues while a tracing sink is armed."""
    with _LOCK:
        return {k: dict(v) for k, v in _COMPILE_BY_KEY.items()}


def retraces_by_name() -> Dict[str, Dict[str, float]]:
    """What jax traced, lowered and compiled since the last clear, by
    program name (name -> traces, ms): ``srt_...`` for programs of this
    cache, jax's own names (``_pad``, ``dynamic_slice``, an exec's
    closure) for programs launched past it.  Always on."""
    with _RETRACE_LOCK:
        return {k: dict(v) for k, v in _RETRACE_BY_NAME.items()}


def count_unkeyed_jit() -> None:
    """A keyless exec program got a fresh ``jax.jit`` wrapper."""
    with _LOCK:
        _STATS["unkeyed_jits"] += 1


def dispatch_stats_by_key() -> Dict[str, int]:
    """Per-kernel-key launch counts (label -> dispatches) since the last
    cache clear — the doctor's dispatch-bound evidence source."""
    return dict(_DISPATCH_BY_KEY)


def clear_cache() -> None:
    """Drop every cached program and the learned state coupled to them.

    Safe under concurrent sessions: the generation bumps BEFORE the
    learned-state dicts clear, so a query mid-flight that learned against
    the old programs fails its generation check at record time instead of
    repopulating a dead generation's state; its already-handed-out kernel
    wrappers keep working (they own their jitted callables)."""
    with _LOCK:
        _GENERATION[0] += 1
        _CACHE.clear()
        _COMPILE_BY_KEY.clear()
        _STATS["hits"] = 0
        _STATS["misses"] = 0
        _STATS["evictions"] = 0
        _STATS["compiles"] = 0
        _STATS["compile_ms"] = 0.0
        _STATS["dispatches"] = 0
        _STATS["unkeyed_jits"] = 0
        _DISPATCH_BY_KEY.clear()
        with _RETRACE_LOCK:
            _STATS["retraces"] = 0
            _STATS["retrace_ms"] = 0.0
            _RETRACE_BY_NAME.clear()
    # stale group-size speculations point at programs just dropped; a
    # speculated miss would recompile a size that may immediately
    # mis-speculate
    from .aggregate import clear_speculation
    clear_speculation()
    # same rule for learned join selectivities: a stale prediction would
    # recompile gather programs for sizes that immediately mis-speculate
    from .join import clear_selectivity
    clear_selectivity()
    # and the join-order rule's estimates: a clear plans from nothing
    # learned
    from ..join_order import clear_estimates
    clear_estimates()


def release_compiled_programs() -> None:
    """Free compiled XLA executables — the ONE recipe (tests/conftest.py
    per test module, scaletest.run_suite per query): the engine kernel
    wrappers AND jax's executable caches.  Accumulated compiled-code
    state segfaults the XLA:CPU JIT inside backend_compile_and_load past
    a few hundred programs (round-4 postmortem; the round-5 60-query rig
    reproduced it as 'LLVM compilation error: Cannot allocate memory').
    Callers recompile their own plans anyway; only shared kernels pay
    again."""
    import jax
    clear_cache()
    jax.clear_caches()


#: one executable a program for every chip of a multi-executor host
#: (:func:`share_executables`): the chip the persistent cache's keys name
#: while sharing is on, jax's own key function and cache floor to put
#: back, and whether the loader re-targets (None: not tried yet)
_SHARED: Dict[str, Any] = {"first": None, "keyed": None, "floor": None,
                           "works": None}
_SHARED_LOCK = threading.Lock()


def share_executables(chips) -> Optional[bool]:
    """One executable a program for all of ``chips`` (the executors' chips
    of this host; fewer than two: sharing is taken off again and jax's own
    settings are back).  A stage program is the same on every chip, but
    jax compiles it once per chip: the device it is assigned to is part of
    the key of its persistent cache.  While sharing is on, a one-chip
    program's key names the first chip whichever chip it is for, and every
    compile is written to the cache, however short: the first chip to need
    a program compiles it, the others load that executable for themselves
    (jax hands the cache's loader the real device assignment).  Tried once
    on a program of one line: where the second chip does not get the right
    answer on itself, or this jax has no such key function, nothing is
    changed and each chip compiles its own.  The probe shows that the
    loader re-targets an executable, not that every custom call in one
    survives it.  Returns whether sharing is on (None: never asked for)."""
    first = chips[0] if len(chips) > 1 else None
    if _SHARED["first"] == first or first is not None \
            and _SHARED["works"] is False:
        return _SHARED["works"]     # as it is already: no lock on this path
    with _SHARED_LOCK:
        _unshare()
        if first is None:
            return _SHARED["works"]
        try:
            _share(first)
            ok = _SHARED["works"] or _retargets(chips[:2])
            why = "the second chip's answer was wrong or not its own"
        except Exception as e:  # noqa: BLE001 — whatever jax raises
            ok, why = False, f"{type(e).__name__}: {e}"
        _SHARED["works"] = ok
        if not ok:
            _unshare()
            import warnings
            warnings.warn("an executable compiled for one chip does not "
                          f"load for another ({why}): every chip compiles "
                          "its own")
        return ok


def _share(first) -> None:
    import copy

    import jax
    import numpy as np
    from jax._src import compiler
    from jax._src.lib import xla_client as xc
    keyed = compiler._get_cache_key

    def chip_agnostic(options, backend, computation, devices, *args, **kw):
        if devices.size == 1 and devices.flat[0] != first \
                and devices.flat[0].client is first.client:
            options = copy.deepcopy(options)
            options.device_assignment = xc.DeviceAssignment.create(
                np.array([[first.id]]))
            devices = np.array([first])
        return keyed(options, backend, computation, devices, *args, **kw)

    _SHARED.update(
        first=first, keyed=keyed,
        floor=jax.config.jax_persistent_cache_min_compile_time_secs)
    compiler._get_cache_key = chip_agnostic
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _unshare() -> None:
    if _SHARED["first"] is None:
        return
    import jax
    from jax._src import compiler
    compiler._get_cache_key = _SHARED["keyed"]
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _SHARED["floor"])
    _SHARED.update(first=None, keyed=None, floor=None)


def _retargets(two_chips) -> bool:
    import jax
    import numpy as np

    def srt_one_executable_probe(x):
        return x * 3 + 1
    probe = jax.jit(srt_one_executable_probe)
    x = np.arange(8, dtype=np.int32)
    got = [probe(jax.device_put(x, c)) for c in two_chips]
    return (set(got[1].devices()) == {two_chips[1]}
            and np.array_equal(np.asarray(got[0]), np.asarray(got[1])))


def expr_key(e) -> Tuple:
    """Stable structural key for a bound expression (or SortOrder)."""
    from ..plan import SortOrder
    if isinstance(e, SortOrder):
        return ("SortOrder", expr_key(e.child), e.ascending, e.nulls_first)
    return e.semantic_key()


def exprs_key(exprs) -> Tuple:
    return tuple(expr_key(e) for e in exprs)
