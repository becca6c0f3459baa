"""Segmented reductions over group ids — the TPU replacement for cuDF's
hash-based ``Table.groupBy().aggregate(...)`` (reference ``aggregate.scala``
AggHelper).  Works under jnp and numpy (ufunc.at).  Two forms under jnp:

* **scatter** (``out.at[ids].op(data)``): work grows with rows × slots,
  whatever the table size.  What XLA CPU runs for every table, and the chip
  for tables above the caller's crossover.  On the chip a float64 or int64
  scatter-add is the X64 rewrite's scatter over a 32-bit pair with a
  hand-written combiner, which applies its updates one after another
  (66–90 ns a row at 2^21 rows, PERF.md §6 PR 30);
* **dense** (``dense=True``): ``reduce_g(where(ids == g, data, identity))``
  for every g of the table — one loop fusion, no scatter, the slot's own
  dtype as the accumulator (float64 stays the chip's double-float pair,
  int64 its 32-bit pair).  Work grows with rows × slots × table size, so
  only small static tables take it; the caller
  (``aggregate._use_dense_reduce``) chooses from the table size.

Out-of-bounds segment ids are DROPPED in every form — callers rely on this
to park dead rows at ``capacity - 1``/``capacity`` while reducing into
small ``num_segments`` tables.  XLA scatter drops only the HIGH side
(negative indices wrap), so the jnp scatter paths remap negatives to
``num_segments`` first; the numpy paths mask both sides explicitly; in the
dense form an id outside ``[0, num_segments)`` equals no group."""

from __future__ import annotations

import numpy as np


def _inb(seg_ids, num_segments):
    ids = np.asarray(seg_ids)
    return ids, (ids >= 0) & (ids < num_segments)


def _nowrap(xp, seg_ids, num_segments):
    """jnp scatters WRAP negative indices; remap them out of bounds so
    they drop like the numpy paths."""
    return xp.where(seg_ids < 0, num_segments, seg_ids)


def seg_sum(xp, data, seg_ids, num_segments, dtype=None):
    out = xp.zeros((num_segments,), dtype=dtype or data.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.add.at(out, ids[m], np.asarray(data.astype(out.dtype))[m])
        return out
    return out.at[_nowrap(xp, seg_ids, num_segments)].add(data.astype(out.dtype))


def seg_min(xp, data, seg_ids, num_segments, init, dense=False):
    if dense:
        return _dense_reduce2(xp, data[:, None], seg_ids, num_segments,
                              xp.min, init)[:, 0]
    out = xp.full((num_segments,), init, dtype=data.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.minimum.at(out, ids[m], np.asarray(data)[m])
        return out
    return out.at[_nowrap(xp, seg_ids, num_segments)].min(data)


def seg_max(xp, data, seg_ids, num_segments, init):
    out = xp.full((num_segments,), init, dtype=data.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.maximum.at(out, ids[m], np.asarray(data)[m])
        return out
    return out.at[_nowrap(xp, seg_ids, num_segments)].max(data)


def _prefer_column_scatters(xp) -> bool:
    """XLA CPU lowers a [n, s] 2-D scatter ~3x slower than s separate
    1-D scatters (measured 810ms vs 277ms at 8M x 8 f64); on TPU the
    batched form amortizes the kernel pass.  Trace-time host decision."""
    if xp.__name__ == "numpy":
        return False
    try:
        import jax
        return jax.default_backend() == "cpu"
    except Exception:
        return False


def _dense_reduce2(xp, data2, seg_ids, num_segments, reduce, identity):
    """The dense form for a [n, s] slot matrix -> [num_segments, s]: every
    group reduces the rows whose id equals it, the others standing in as
    ``identity``.  Rows lie along the minor axis of the [s, groups, n]
    operand, which is never materialised: XLA fuses select and reduce into
    one loop (the [groups, n] predicate it may keep, a byte an element)."""
    groups = xp.arange(num_segments, dtype=seg_ids.dtype)
    member = groups[:, None] == seg_ids[None, :]
    masked = xp.where(member[None], data2.T[:, None, :],
                      xp.asarray(identity, dtype=data2.dtype))
    return reduce(masked, axis=2).T


def seg_sum2(xp, data2, seg_ids, num_segments, dense=False):
    """Batched segmented sum for a [n, s] slot matrix: the dense form when
    the caller asks (small tables on the chip); else one scatter pass on
    TPU, per-column 1-D scatters on XLA CPU (see _prefer_column_scatters)."""
    if dense:
        return _dense_reduce2(xp, data2, seg_ids, num_segments, xp.sum, 0)
    out = xp.zeros((num_segments, data2.shape[1]), dtype=data2.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.add.at(out, ids[m], np.asarray(data2)[m])
        return out
    ids = _nowrap(xp, seg_ids, num_segments)
    if _prefer_column_scatters(xp):
        cols = [xp.zeros(num_segments, dtype=data2.dtype).at[ids]
                .add(data2[:, j]) for j in range(data2.shape[1])]
        return xp.stack(cols, axis=1)
    return out.at[ids].add(data2)


def seg_min2(xp, data2, seg_ids, num_segments, init, dense=False):
    if dense:
        return _dense_reduce2(xp, data2, seg_ids, num_segments, xp.min, init)
    out = xp.full((num_segments, data2.shape[1]), init, dtype=data2.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.minimum.at(out, ids[m], np.asarray(data2)[m])
        return out
    ids = _nowrap(xp, seg_ids, num_segments)
    if _prefer_column_scatters(xp):
        cols = [xp.full(num_segments, init, dtype=data2.dtype).at[ids]
                .min(data2[:, j]) for j in range(data2.shape[1])]
        return xp.stack(cols, axis=1)
    return out.at[ids].min(data2)


def seg_max2(xp, data2, seg_ids, num_segments, init, dense=False):
    if dense:
        return _dense_reduce2(xp, data2, seg_ids, num_segments, xp.max, init)
    out = xp.full((num_segments, data2.shape[1]), init, dtype=data2.dtype)
    if xp.__name__ == "numpy":
        ids, m = _inb(seg_ids, num_segments)
        np.maximum.at(out, ids[m], np.asarray(data2)[m])
        return out
    ids = _nowrap(xp, seg_ids, num_segments)
    if _prefer_column_scatters(xp):
        cols = [xp.full(num_segments, init, dtype=data2.dtype).at[ids]
                .max(data2[:, j]) for j in range(data2.shape[1])]
        return xp.stack(cols, axis=1)
    return out.at[ids].max(data2)


def seg_any(xp, mask, seg_ids, num_segments):
    return seg_sum(xp, mask.astype(xp.int32), seg_ids, num_segments) > 0


def seg_count(xp, mask, seg_ids, num_segments):
    return seg_sum(xp, mask.astype(xp.int64), seg_ids, num_segments)
