"""t-digest sketches for grouped ``approx_percentile``.

TPU-native re-design of the reference's device t-digest aggregation
(``GpuApproximatePercentile.scala:1-222``, cuDF ``tdigest``
GroupByAggregations — SURVEY §2.10): instead of a per-group tree of
centroids built row-at-a-time, the whole batch is digested in ONE
data-parallel pass:

    sort rows by (group, value)               [grouped_order — one lex sort]
    q_mid(row)  = (cum_weight_before + w/2) / group_total
    cluster(row) = floor(δ/(2π)·asin(2q−1) + δ/4)     [k1 scale function]
    scatter-add (w, w·v) by (group, cluster)  → centroid means/weights

which is exactly the MergingDigest construction specialized to sorted
input.  The state per group is a FIXED [C]-centroid layout (C = δ/2+2),
so multi-batch and partial/merge flows are bounded at O(groups·C)
device memory regardless of group size — the property the exact sorted
selection lacks.

Merging digests is the same kernel run over the centroids as weighted
rows.  Quantile queries interpolate between centroid midpoints with
min/max clamping (classic t-digest quantile rule).
"""

from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def n_centroids(delta: int) -> int:
    return delta // 2 + 2


def delta_for_accuracy(accuracy: int) -> int:
    """Spark's ``accuracy`` knob (default 10000) mapped onto the t-digest
    compression δ.  The reference hands accuracy/100 to cudf's tdigest
    (GpuApproximatePercentile's ApproxPercentileFromTdigestExpr); we
    clamp to [20, 1000] to bound the [groups, δ/2] state."""
    return max(20, min(int(accuracy) // 100 * 2, 1000))


def build_grouped(xp, values, weights, value_valid, rank, contrib,
                  OUT: int, delta: int):
    """Digest one batch.

    values f64[cap], weights f64[cap] (1.0 for raw rows; centroid weights
    when merging), rank int[cap] dense group ids, contrib bool[cap].

    Returns (means f64[OUT,C], wts f64[OUT,C], vmin f64[OUT],
    vmax f64[OUT], total f64[OUT]) — a zero total marks an empty group
    (Spark's null-when-empty semantics; callers mask on it).
    """
    from .collect_ops import grouped_order
    C = n_centroids(delta)
    cap = int(rank.shape[0])
    alive_in = contrib & value_valid & (weights > 0)
    v64 = values.astype(xp.float64)
    # sort by (group, value); dead rows sort last (r_s == cap)
    okey = [k for k in _value_keys(xp, v64)]
    perm, r_s, pos, is_start = grouped_order(xp, rank, alive_in, okey)
    alive = r_s < cap
    g = xp.where(alive, r_s, OUT).astype(xp.int32)  # OUT = drop slot
    v_s = v64[perm]
    w_s = xp.where(alive, weights.astype(xp.float64)[perm], 0.0)

    # per-group totals + cumulative weight BEFORE each sorted row:
    # global inclusive cumsum, re-based at each group start
    cum_incl = xp.cumsum(w_s)
    cum_before = cum_incl - w_s
    base = _scatter_get(xp, xp.where(is_start & alive, cum_before, 0.0),
                        g, OUT, op="add")
    # base[g] is each group's global cumsum offset (one start per group)
    cum_in_g = cum_before - base[xp.clip(g, 0, OUT - 1)]
    total = _scatter_get(xp, w_s, g, OUT, op="add")
    tot_row = total[xp.clip(g, 0, OUT - 1)]
    q_mid = xp.clip((cum_in_g + 0.5 * w_s)
                    / xp.maximum(tot_row, 1e-300), 0.0, 1.0)
    k1 = (delta / (2.0 * math.pi)) * xp.arcsin(2.0 * q_mid - 1.0) \
        + delta / 4.0
    c = xp.clip(xp.floor(k1).astype(xp.int32), 0, C - 1)
    flat = xp.where(alive, g.astype(xp.int64) * C + c, OUT * C)
    if xp.__name__ == "numpy":
        wts = np.zeros(OUT * C + 1)
        np.add.at(wts, np.asarray(flat), np.asarray(w_s))
        sums = np.zeros(OUT * C + 1)
        np.add.at(sums, np.asarray(flat), np.asarray(w_s * v_s))
        wts, sums = wts[:-1], sums[:-1]
    else:
        wts = xp.zeros(OUT * C).at[flat].add(w_s, mode="drop")
        sums = xp.zeros(OUT * C).at[flat].add(w_s * v_s, mode="drop")
    wts = wts.reshape(OUT, C)
    means = (sums.reshape(OUT, C)
             / xp.maximum(wts, 1e-300))
    # forward-fill empty clusters with the previous live mean (means are
    # nondecreasing along C by construction) so quantile bracketing never
    # reads a garbage slot
    means = _cummax_axis1(xp, xp.where(wts > 0, means, -xp.inf))
    vmin = _scatter_get(xp, xp.where(alive, v_s, xp.inf), g, OUT, op="min")
    vmax = _scatter_get(xp, xp.where(alive, v_s, -xp.inf), g, OUT, op="max")
    return means, wts, vmin, vmax, total


def _value_keys(xp, v64):
    """Totally-ordered int64 sort key for float64 (sign-flip bit trick)."""
    if xp.__name__ == "numpy":
        bits = v64.view(np.int64)
    else:
        from .ranks import f64_bits_i64
        bits = f64_bits_i64(v64)
    key = xp.where(bits < 0, xp.asarray(-(2**63), dtype=xp.int64) - bits - 1,
                   bits)
    return [key]


def _scatter_get(xp, vals, g, OUT, op):
    g64 = g.astype(xp.int64)
    if xp.__name__ == "numpy":
        init = {"add": 0.0, "min": np.inf, "max": -np.inf}[op]
        out = np.full(OUT + 1, init)
        ufunc = {"add": np.add, "min": np.minimum, "max": np.maximum}[op]
        ufunc.at(out, np.asarray(np.clip(g64, 0, OUT)), np.asarray(vals))
        return out[:-1]
    zeros = {"add": xp.zeros(OUT),
             "min": xp.full(OUT, xp.inf),
             "max": xp.full(OUT, -xp.inf)}[op]
    at = zeros.at[xp.where(g64 < OUT, g64, OUT)]
    return {"add": at.add, "min": at.min, "max": at.max}[op](
        vals, mode="drop")


def _cummax_axis1(xp, a):
    if xp.__name__ == "numpy":
        return np.maximum.accumulate(a, axis=1)
    import jax.lax as lax
    return lax.associative_scan(xp.maximum, a, axis=1)


def percentiles_grouped(xp, means, wts, vmin, vmax, total,
                        ps: Sequence[float]):
    """Quantile query: per group, interpolate between centroid cumulative
    midpoints, clamped to [vmin, vmax].  Returns f64[len(ps), OUT]."""
    OUT, C = means.shape
    # compact live clusters to the front of each group row: sparse empty
    # clusters (small groups under a large delta) would otherwise break
    # the bracketing index, which counts live midpoints but gathers by
    # raw slot position
    live = wts > 0
    if xp.__name__ == "numpy":
        order = np.argsort(~live, axis=1, kind="stable")
    else:
        order = xp.argsort(~live, axis=1, stable=True)
    wts = xp.take_along_axis(wts, order, axis=1)
    means = xp.take_along_axis(means, order, axis=1)
    live = wts > 0
    cumw = xp.cumsum(wts, axis=1)
    mids = cumw - 0.5 * wts                          # [OUT, C]
    outs = []
    for p in ps:
        t = p * total                                 # [OUT]
        tcol = t[:, None]
        # j = number of live centroids whose midpoint is < t
        j = xp.sum(live & (mids < tcol), axis=1)      # [OUT] in [0, C]
        jl = xp.clip(j - 1, 0, C - 1)
        jr = xp.clip(j, 0, C - 1)
        take = lambda m, i: xp.take_along_axis(m, i[:, None], axis=1)[:, 0]
        ml, mr = take(mids, jl), take(mids, jr)
        vl, vr = take(means, jl), take(means, jr)
        # boundary handling: before the first midpoint interpolate from
        # vmin at t=0; past the last live midpoint interpolate to vmax at
        # t=total
        first = j == 0
        last = j >= xp.sum(live, axis=1)
        lo_t = xp.where(first, 0.0, ml)
        lo_v = xp.where(first, vmin, vl)
        hi_t = xp.where(last, total, mr)
        hi_v = xp.where(last, vmax, vr)
        span = xp.maximum(hi_t - lo_t, 1e-300)
        frac = xp.clip((t - lo_t) / span, 0.0, 1.0)
        est = lo_v + (hi_v - lo_v) * frac
        outs.append(xp.clip(est, vmin, vmax))
    return outs
