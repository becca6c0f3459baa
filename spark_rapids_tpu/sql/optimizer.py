"""Cost-based optimizer — the analog of the reference's
``CostBasedOptimizer.scala:54`` (``CpuCostModel``/``GpuCostModel``): a
row-count model that flips device-tagged subtrees back to the host engine
when their estimated device benefit does not cover the host<->device
transition cost.  Off by default, exactly like the reference.

Operates on the ``PlanMeta`` tree between tagging and conversion: for each
maximal device subtree, compare

    device_cost(subtree) + 2 * transition_cost(boundary rows)
    vs host_cost(subtree)

and demote the whole subtree when the host is cheaper.  Row counts come
from relation statistics propagated bottom-up (joins multiply nothing —
the reference likewise treats output rows ~= input rows by default).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from ..config import (OPTIMIZER_CPU_COST, OPTIMIZER_GPU_COST,
                      OPTIMIZER_TRANSITION_COST, OPTIMIZER_TRANSITION_FIXED,
                      RapidsConf)
from . import plan as P

#: per-op cost multipliers relative to the default per-row cost — the
#: operatorsScore.csv analog (device-friendlier ops get lower multipliers)
_DEVICE_MULTIPLIER: Dict[str, float] = {
    "Project": 0.5,
    "Filter": 0.5,
    "Aggregate": 1.0,
    "Sort": 1.5,
    "Join": 1.5,
    "Window": 2.0,
    "Generate": 1.0,
}


def _row_estimate(meta) -> Optional[int]:
    """Estimated rows, or None when unknown (e.g. file scans without
    statistics) — an unknown estimate must NOT look like `0 rows`, which
    would demote every file-based query (0 >= 0)."""
    n = meta.node
    kids = [_row_estimate(c) for c in meta.children]
    if any(k is None for k in kids):
        return None
    if isinstance(n, P.Relation):
        return n.table.num_rows
    if isinstance(n, P.Range):
        return max(0, (n.end - n.start + n.step - 1) // max(n.step, 1))
    if isinstance(n, P.Union):
        return sum(kids)
    if isinstance(n, P.Limit):
        return min(kids[0] if kids else 0, n.n)
    if not kids:
        return None  # unknown leaf (file scan etc.)
    return max(kids)


def _op_name(node) -> str:
    return type(node).__name__


#: one-time measured host<->device sync round trip (seconds) — the single
#: number that decides whether small queries are worth the device at all
_MEASURED: Dict[str, Optional[float]] = {"rtt_s": None}


def transition_fixed_seconds(conf: RapidsConf) -> float:
    """Fixed per-boundary transition cost: the configured value, or (auto)
    a once-per-process measured sync round trip on the ambient backend."""
    v = float(conf.get(OPTIMIZER_TRANSITION_FIXED))
    if v >= 0:
        return v
    if _MEASURED["rtt_s"] is None:
        _MEASURED["rtt_s"] = _probe_sync_rtt()
    return _MEASURED["rtt_s"]


def _probe_sync_rtt() -> float:
    """Measure one warm sync round trip on the device JAX found.  A backend
    that errors here raises: a device that cannot add eight numbers is not
    priced, it is reported."""
    import time

    import jax.numpy as jnp
    x = jnp.ones(8)
    float(jnp.sum(x) + 1.0)  # warm the exact timed expression
    t0 = time.perf_counter()
    float(jnp.sum(x) + 1.0)
    return time.perf_counter() - t0


def _subtree_costs(meta, cpu_rate: float, dev_rate: float,
                   trans_rate: float, trans_fixed: float
                   ) -> Optional[Tuple[float, float]]:
    """(host_cost, device_cost) over the CONTIGUOUS device region rooted
    here.  Host-tagged descendants cost the same under both alternatives
    and are excluded; each tpu/cpu boundary charges the device alternative
    one interior transition (fixed latency + per-row).  None when any row
    estimate is unknown."""
    rows = _row_estimate(meta)
    if rows is None:
        return None
    mult = _DEVICE_MULTIPLIER.get(_op_name(meta.node), 1.0)
    host = rows * cpu_rate
    dev = rows * dev_rate * mult
    for c in meta.children:
        if c.backend != "tpu":
            crows = _row_estimate(c)
            if crows is None:
                return None
            # interior host->device boundary
            dev += trans_fixed + crows * trans_rate
            continue
        sub = _subtree_costs(c, cpu_rate, dev_rate, trans_rate, trans_fixed)
        if sub is None:
            return None
        host += sub[0]
        dev += sub[1]
    return host, dev


def apply_cost_optimizer(meta, conf: RapidsConf) -> None:
    """Demote device subtrees that the cost model says are not worth the
    transitions.  Mutates ``meta.backend`` in place (pre-conversion).
    Unknown statistics keep the device placement (no evidence = no
    demotion, matching the reference's conservative default-off stance).

    Transition costs come from the MEASURED model: each boundary pays a
    fixed sync round trip (auto-measured per process) plus a per-row
    transfer rate —
    so a 100-row query is demoted to the host while an 8M-row query
    keeps its device placement under the same configuration."""
    cpu_rate = float(conf.get(OPTIMIZER_CPU_COST))
    dev_rate = float(conf.get(OPTIMIZER_GPU_COST))
    trans_rate = float(conf.get(OPTIMIZER_TRANSITION_COST))
    trans_fixed = transition_fixed_seconds(conf)

    def walk(m):
        if m.backend != "tpu":
            for c in m.children:
                walk(c)
            return
        rows = _row_estimate(m)
        costs = _subtree_costs(m, cpu_rate, dev_rate, trans_rate,
                               trans_fixed)
        if rows is None or costs is None:
            return  # unknown stats: keep the device placement
        host, dev = costs
        # device data enters and leaves the subtree once each
        dev_total = dev + 2 * (trans_fixed + rows * trans_rate)
        if dev_total > host:
            _demote(m, dev_total, host)
        # a kept device subtree keeps its children on device too — the
        # reference likewise only re-plans whole exchanges/subtrees

    def _demote(m, dev_total, host):
        m.backend = "cpu"
        m.will_not_work(
            f"cost-based optimizer: device cost {dev_total:.4f}s > host "
            f"cost {host:.4f}s (CostBasedOptimizer.scala:54 analog)")
        for c in m.children:
            if c.backend == "tpu":
                _demote(c, dev_total, host)

    walk(meta)
