"""Window kernels over sorted batches — the TPU replacement for cuDF's
``RollingAggregation`` / segmented windows (reference ``GpuWindowExec.scala``
2068 LoC + ``GpuWindowExpression.scala``; SURVEY §2.3 window family).

Everything assumes the batch is already sorted by (partition keys, order
keys) with dead padding rows at the end.  The core insight that makes
windows XLA-friendly: once rows are sorted and every row knows its
``[frame_start, frame_end)`` index range (clamped to its partition segment),
*all* frame aggregations become either

* prefix-sum differences (sum/count/avg) over a global cumsum, or
* O(n log n) sparse-table range queries (min/max/first/last/nth),

with static shapes throughout.  No per-partition loops, no dynamic shapes.
"""

from __future__ import annotations

import numpy as np


def _cummax(xp, v):
    if xp.__name__ == "numpy":
        return np.maximum.accumulate(v)
    import jax
    return jax.lax.associative_scan(xp.maximum, v)


def _cummin(xp, v):
    if xp.__name__ == "numpy":
        return np.minimum.accumulate(v)
    import jax
    return jax.lax.associative_scan(xp.minimum, v)


def segment_bounds(xp, is_start):
    """Given boundary flags (True at each segment's first row) over a sorted
    array, returns (seg_start, seg_end_excl) row indices per row."""
    n = is_start.shape[0]
    idx = xp.arange(n, dtype=xp.int32)
    seg_start = _cummax(xp, xp.where(is_start, idx, xp.asarray(-1, xp.int32)))
    # last row of each segment: next row is a start (or end of array)
    is_end = xp.concatenate([is_start[1:], xp.ones((1,), dtype=bool)])
    rev_end = _cummin(xp, xp.where(is_end, idx, xp.asarray(n, xp.int32))[::-1])[::-1]
    return seg_start, rev_end + 1


def boundary_flags(xp, key_arrays, valids=None):
    """True at row 0 and wherever any key (or its validity) differs from the
    previous row."""
    n = key_arrays[0].shape[0]
    flag = xp.zeros(n - 1, dtype=bool) if n > 1 else xp.zeros(0, dtype=bool)
    for k in key_arrays:
        flag = flag | (k[1:] != k[:-1])
    if valids is not None:
        for v in valids:
            flag = flag | (v[1:] != v[:-1])
    return xp.concatenate([xp.ones((1,), dtype=bool), flag])


# ---------------------------------------------------------------------------
# Sparse table: O(n log n) precompute, O(1)-per-row range min/max queries
# ---------------------------------------------------------------------------

def _floor_log2(xp, v):
    """floor(log2(v)) for v >= 1, elementwise int32."""
    v = v.astype(xp.int32)
    out = xp.zeros_like(v)
    for b in (16, 8, 4, 2, 1):
        big = v >= (1 << b)
        out = out + xp.where(big, b, 0)
        v = xp.where(big, v >> b, v)
    return out


def range_reduce(xp, v, starts, ends, op, identity):
    """Reduce v[s:e) per row with ``op`` in {'min','max'}; empty -> identity.

    Sparse-table: levels[k][i] = reduce(v[i : i+2^k]).  A query [s, e) is
    the op of two overlapping power-of-two blocks."""
    n = v.shape[0]
    comb = xp.minimum if op == "min" else xp.maximum
    levels = [v]
    k = 1
    while (1 << k) <= n:
        prev = levels[-1]
        step = 1 << (k - 1)
        shifted = xp.concatenate(
            [prev[step:], xp.full((step,), identity, dtype=v.dtype)])
        levels.append(comb(prev, shifted))
        k += 1
    table = xp.stack(levels)  # [L, n]

    length = ends - starts
    nonempty = length > 0
    safe_len = xp.maximum(length, 1)
    kk = _floor_log2(xp, safe_len)
    pow_k = (xp.asarray(1, xp.int32) << kk)
    s = xp.clip(starts, 0, n - 1)
    e2 = xp.clip(ends - pow_k, 0, n - 1)
    a = table[kk, s]
    b = table[kk, e2]
    out = comb(a, b)
    return xp.where(nonempty, out, xp.asarray(identity, dtype=v.dtype))


# ---------------------------------------------------------------------------
# Frame aggregations
# ---------------------------------------------------------------------------

def frame_sum(xp, v, valid, starts, ends, out_dtype=None, seg_start=None):
    """Sum of valid v over [s, e) per row (null-skipping, Spark agg).

    A float sum on the device, given each row's ``seg_start`` (a frame
    never leaves its row's segment), comes from prefix sums that restart
    with every segment (:func:`_segment_prefix_sum`)."""
    dt = out_dtype or v.dtype
    vz = xp.where(valid, v, xp.asarray(0, dtype=v.dtype)).astype(dt)
    if (seg_start is not None and xp.__name__ != "numpy"
            and np.dtype(dt).kind == "f"):
        n = vz.shape[0]
        p = _segment_prefix_sum(xp, vz, seg_start)
        below = xp.where(starts > seg_start,
                         p[xp.clip(starts - 1, 0, n - 1)], 0)
        return xp.where(ends > starts,
                        p[xp.clip(ends - 1, 0, n - 1)] - below, 0)
    c = xp.cumsum(vz)
    zero = xp.zeros((1,), dtype=dt)
    cpad = xp.concatenate([zero, c])  # cpad[i] = sum of v[:i]
    return cpad[xp.maximum(ends, 0)] - cpad[xp.maximum(starts, 0)]


def _segment_prefix_sum(xp, x, seg_start):
    """Inclusive prefix sums of ``x`` that restart at each segment's first
    row, as log2(n) shifted adds: after the step of distance d a row holds
    the sum of the 2d rows that end with it, cut at its segment's start.

    Two reasons over one ``cumsum`` and its differences.  The chip's
    compiler: a float64 ``cumsum`` is a reduce-window over the X64
    rewrite's float32 pairs and takes 120 s at 2^15 rows, flat or in
    blocks, where these adds take a second (compiled for a described v5e,
    PERF.md section 6, PR 35).  And the sums themselves: a segment's total
    no longer carries the rounding of everything before it, so a segment
    of zeros sums to exactly 0 (a NULL ratio in TPC-DS q98, not a division
    by the last bit of a difference)."""
    n = x.shape[0]
    idx = xp.arange(n, dtype=xp.int32)
    shift = 1
    while shift < n:
        before = xp.concatenate([xp.zeros((shift,), x.dtype), x[:-shift]])
        x = x + xp.where(idx - shift >= seg_start, before, 0)
        shift *= 2
    return x


def frame_count(xp, valid, starts, ends):
    c = xp.cumsum(valid.astype(xp.int64))
    zero = xp.zeros((1,), dtype=xp.int64)
    cpad = xp.concatenate([zero, c])
    return cpad[xp.maximum(ends, 0)] - cpad[xp.maximum(starts, 0)]


def frame_min(xp, v, valid, starts, ends, identity):
    vv = xp.where(valid, v, xp.asarray(identity, dtype=v.dtype))
    out = range_reduce(xp, vv, starts, ends, "min", identity)
    has = frame_count(xp, valid, starts, ends) > 0
    return out, has


def frame_max(xp, v, valid, starts, ends, identity):
    vv = xp.where(valid, v, xp.asarray(identity, dtype=v.dtype))
    out = range_reduce(xp, vv, starts, ends, "max", identity)
    has = frame_count(xp, valid, starts, ends) > 0
    return out, has


def frame_first_valid_index(xp, valid, starts, ends):
    """Index of first valid row in [s, e); (idx, found)."""
    n = valid.shape[0]
    idx = xp.arange(n, dtype=xp.int32)
    cand = xp.where(valid, idx, xp.asarray(n, xp.int32))
    out = range_reduce(xp, cand, starts, ends, "min", n)
    return xp.clip(out, 0, n - 1), out < n


def frame_last_valid_index(xp, valid, starts, ends):
    n = valid.shape[0]
    idx = xp.arange(n, dtype=xp.int32)
    cand = xp.where(valid, idx, xp.asarray(-1, xp.int32))
    out = range_reduce(xp, cand, starts, ends, "max", -1)
    return xp.clip(out, 0, n - 1), out >= 0
