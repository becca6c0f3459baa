"""Deferred speculation validation — removes the two-phase aggregate's
group-count sync from the query's critical path.

Every host pull stalls the host until the device has drained, while async
dispatch keeps the device fed; the design premise is that the NUMBER of
host pulls per query matters more than device compute (to be measured on
the attached chip, PERF.md).  The speculative fused aggregate (aggregate.py
``_fused_partial_fn``) already runs group+reduce as one program under a
host-guessed group-table size; this module lets the *validation* of that
guess ride the query's single device→host fetch instead of paying its own
round trip:

* the aggregate registers a :class:`DeferredCheck` carrying the device-side
  observed group count and the speculated size;
* the ``DeviceToHost`` transition bundles all pending device scalars into
  the same ``device_get`` as the result batch (one pull for everything);
* after execution the session validates the fetched counts — a
  mis-speculation (observed > speculated: scatters past the table were
  dropped, the result is wrong) records the corrected size and re-runs the
  query, which then takes the exact path.

Reference analog: none — the reference pays a kernel launch per op and
never speculates; this is a design for XLA's static shapes (SURVEY §7 "hardest
risk items": dynamic shapes vs XLA compilation).
"""

from __future__ import annotations

import threading
from typing import Callable, List, Optional

#: observability for tests/metrics.  Increments go through
#: :func:`_bump` — with double-buffered transfers the bundled-fetch count
#: is bumped from the transfer stager thread while the driver may be
#: registering checks, and lost updates would break tests that assert on
#: exact deltas.
STATS = {"registered": 0, "bundled_fetches": 0, "mis_speculations": 0,
         "reruns": 0}
_STATS_LOCK = threading.Lock()


def _bump(key: str, n: int = 1) -> None:
    with _STATS_LOCK:
        STATS[key] += n


def count_bundled_fetch() -> None:
    """A pending speculation scalar rode a result fetch (D2H transition)."""
    _bump("bundled_fetches")


class DeferredCheck:
    """One pending validation: ``ng`` (device scalar) must be <= ``spec``.

    ``on_result(ng_host)`` is invoked exactly once when the value reaches
    the host (bundled into a D2H fetch or pulled at drain time); it records
    the observed size so a re-run speculates correctly.
    """

    __slots__ = ("spec", "ng", "ng_host", "on_result")

    def __init__(self, spec: int, ng, on_result: Callable[[int], None]):
        self.spec = int(spec)
        self.ng = ng
        self.ng_host: Optional[int] = None
        self.on_result = on_result

    def resolve(self, ng_host: int) -> None:
        # under double-buffered transfers two in-flight fetches can both
        # bundle a not-yet-resolved check; first resolution wins (both
        # carry the same device scalar, so the value is identical either
        # way — the lock just keeps on_result to exactly one call)
        with _STATS_LOCK:
            if self.ng_host is not None:
                return
            self.ng_host = int(ng_host)
            self.ng = None  # drop the device ref
        self.on_result(self.ng_host)

    @property
    def failed(self) -> bool:
        return self.ng_host is not None and self.ng_host > self.spec


class _State(threading.local):
    """Per-thread registry: deferral is driven by the session's collect
    loop on its own thread, and concurrent sessions on other threads must
    not steal or wipe each other's pending checks."""

    def __init__(self):
        self.pending: List[DeferredCheck] = []
        self.on = False


_state = _State()


def deferral_enabled() -> bool:
    """Deferred validation is opt-in per execution: only the session's
    pure-collect path enables it (a plan with side effects — writers —
    must never act on unvalidated results)."""
    return _state.on


def set_deferral(on: bool) -> None:
    _state.on = bool(on)


def register(spec: int, ng, on_result: Callable[[int], None]
             ) -> DeferredCheck:
    c = DeferredCheck(spec, ng, on_result)
    _state.pending.append(c)
    _bump("registered")
    return c


def unresolved():
    """Checks whose device scalar has not reached the host yet (for the
    D2H transition to bundle into its fetch).  Same-thread only — the
    driver's collect loop registers, bundles, and drains on one thread."""
    return [c for c in _state.pending if c.ng_host is None]


def drain() -> List[DeferredCheck]:
    """Take this thread's pending checks, resolving any still-device
    values (one bundled pull if needed)."""
    checks = list(_state.pending)
    _state.pending.clear()
    todo = [c for c in checks if c.ng_host is None]
    if todo:
        import jax
        vals = jax.device_get([c.ng for c in todo])
        for c, v in zip(todo, vals):
            c.resolve(int(v))
    return checks


def clear() -> None:
    _state.pending.clear()
