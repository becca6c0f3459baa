"""Equi-join kernels — the TPU replacement for cuDF's hash join.

The reference builds device gather maps with a hash join
(``GpuHashJoin.scala:298``) and then gathers output rows lazily in
target-sized chunks (``JoinGatherer.scala``).  Hash tables don't map to
XLA (dynamic shapes, scatter contention), so key equality is established
with *exact dense ranks* (ops/ranks.py): concatenate both sides' key
columns, dense-rank the union — equal rank <=> equal key, collision-free —
then find each probe row's match range in the rank-sorted build side with
two vectorized binary searches.  Pair enumeration is a third binary search
over the prefix-sum of match counts.  Everything is static-shape sorts,
searches and gathers that XLA lowers well to TPU.

Two phases, mirroring the reference's count-then-gather contract:
* ``join_build`` (jittable per capacity pair) -> match info + output-size
  scalars the host reads to pick the output capacity bucket;
* ``gather_pairs`` (jittable per output bucket) -> left/right gather maps
  with validity (False = null side of an outer-join miss).

Join-key NULL semantics: SQL equality never matches NULL, so live rows with
a null key get sentinel ranks (-1 probe / -2 build) that cannot collide.
Dead padding rows are likewise sentineled out.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import NamedTuple, Sequence, Tuple

import numpy as np

from .. import types as T
from ..columnar.column import DeviceColumn
from .ranks import (column_sort_keys, dense_rank_columns, lex_sort,
                    prefix_sum, stable_argsort, tuple_searchsorted)


def _scope(xp, name: str):
    """jax.named_scope on the device backend (shows up as a named region in
    jax.profiler traces — the per-stage join profile), no-op under numpy."""
    if xp.__name__ == "numpy":
        return nullcontext()
    import jax
    return jax.named_scope(name)


def concat_full_columns(xp, a: DeviceColumn, b: DeviceColumn) -> DeviceColumn:
    """Concatenate two columns at FULL capacity (padding rows included) —
    static-shape, so it is legal inside jit.  Dead rows are masked by the
    caller via the combined row mask."""
    data = None
    if a.data is not None:
        da, db = a.data, b.data
        if da.ndim == 2:
            w = max(da.shape[1], db.shape[1])
            if da.shape[1] < w:
                da = xp.pad(da, ((0, 0), (0, w - da.shape[1])))
            if db.shape[1] < w:
                db = xp.pad(db, ((0, 0), (0, w - db.shape[1])))
        data = xp.concatenate([da, db], axis=0)
    validity = xp.concatenate([a.validity, b.validity])
    lengths = (xp.concatenate([a.lengths, b.lengths])
               if a.lengths is not None else None)
    aux = xp.concatenate([a.aux, b.aux]) if a.aux is not None else None
    children = tuple(concat_full_columns(xp, ca, cb)
                     for ca, cb in zip(a.children, b.children))
    return DeviceColumn(a.dtype, data, validity, lengths, aux, children)


def compact_indices(xp, flags):
    """int32 indices of True flags, compacted to the front (stable); False
    flags' indices follow, also in order.  O(n) cumsum + scatter instead of
    an argsort — the compaction primitive behind filter, split, and join
    assembly (cuDF ``apply_boolean_mask`` analog)."""
    n = flags.shape[0]
    kept_pos = xp.cumsum(flags.astype(xp.int32))
    n_keep = kept_pos[-1] if n else xp.asarray(0, dtype=xp.int32)
    dead_pos = xp.cumsum((~flags).astype(xp.int32))
    dest = xp.where(flags, kept_pos - 1, n_keep + dead_pos - 1)
    return _rows_at(xp, dest)


def _rows_at(xp, dest):
    """The inverse of a permutation: ``out[dest[i]] = i``."""
    n = dest.shape[0]
    if xp.__name__ == "numpy":
        out = np.empty(n, dtype=np.int32)
        out[dest] = np.arange(n, dtype=np.int32)
        return out
    return xp.zeros(n, dtype=xp.int32).at[dest].set(
        xp.arange(n, dtype=xp.int32))


def partition_indices(xp, keys, num_keys: int):
    """Stable counting sort of the row indices by ``keys`` (int32, every
    one in ``[0, num_keys)``): ``(perm, counts)`` with the rows of key k at
    ``perm[sum(counts[:k]) : sum(counts[:k + 1])]`` in their original
    order, and ``counts`` int32[num_keys].  One cumsum per key and one
    scatter — the all-targets form of :func:`compact_indices` (cuDF
    ``Table.partition`` analog), so a caller gathers each array once."""
    dest = xp.zeros(keys.shape[0], dtype=xp.int32)
    start = xp.asarray(0, dtype=xp.int32)
    counts = []
    for k in range(num_keys):
        mine = keys == k
        rank = xp.cumsum(mine.astype(xp.int32), dtype=xp.int32)
        dest = xp.where(mine, start + rank - 1, dest)
        counts.append(xp.sum(mine, dtype=xp.int32))
        start = start + counts[-1]
    return _rows_at(xp, dest), xp.stack(counts)


class JoinInfo(NamedTuple):
    """Device-resident match info between one probe batch and the build
    table (all arrays static-shape in (probe_cap, build_cap))."""
    counts: "np.ndarray"        # int64[lcap] matches per probe row
    csum: "np.ndarray"          # int64[lcap] inclusive prefix sum of counts
    lo: "np.ndarray"            # int64[lcap] match-range start in sorted build
    perm_b: "np.ndarray"        # int32[rcap] build rows sorted by rank
    l_unmatched: "np.ndarray"   # bool[lcap] live probe rows with no match
    b_unmatched: "np.ndarray"   # bool[rcap] live build rows with no match
    total: "np.ndarray"         # int64 scalar: total inner pairs
    n_unmatched_l: "np.ndarray"  # int64 scalar
    n_unmatched_b: "np.ndarray"  # int64 scalar
    n_null_keys: "np.ndarray"   # int64 scalar: live probe rows, a key NULL

    def sizing_scalars(self) -> tuple:
        """The three output-sizing scalars — THE one blocking host
        readback of the join path.  Exposed as a tuple so the exec layer
        fetches all three in a single batched ``device_get`` (one round
        trip, not three) and the tracer can attribute that sync to
        the join in one place."""
        return (self.total, self.n_unmatched_l, self.n_unmatched_b)


def _sentinel_ranks(xp, rank, key_cols: Sequence[DeviceColumn], mask, sentinel):
    """Replace ranks of dead rows and null-keyed rows with a sentinel that
    cannot match the other side."""
    bad = ~mask
    for c in key_cols:
        if c.validity is not None:
            bad = bad | ~c.validity
    return xp.where(bad, xp.asarray(sentinel, dtype=rank.dtype), rank)


def join_build(xp, lkeys: Sequence[DeviceColumn], rkeys: Sequence[DeviceColumn],
               lmask, rmask, null_safe: bool = False) -> JoinInfo:
    """Phase 1: compute match structure.  Jittable; host reads the three
    scalar totals to size the output bucket.  ``null_safe=True`` gives <=>
    semantics (null keys equal each other)."""
    lcap = lmask.shape[0]
    rcap = rmask.shape[0]
    combined = [concat_full_columns(xp, a, b) for a, b in zip(lkeys, rkeys)]
    mask = xp.concatenate([lmask, rmask])
    from .hash_group import group_ids
    rank = group_ids(xp, combined, mask)
    if null_safe:
        lrank = _sentinel_ranks(xp, rank[:lcap], [], lmask, -1)
        rrank = _sentinel_ranks(xp, rank[lcap:], [], rmask, -2)
    else:
        lrank = _sentinel_ranks(xp, rank[:lcap], lkeys, lmask, -1)
        rrank = _sentinel_ranks(xp, rank[lcap:], rkeys, rmask, -2)

    perm_b = stable_argsort(xp, rrank).astype(xp.int32)
    sb = rrank[perm_b]
    lo = xp.searchsorted(sb, lrank, side="left")
    hi = xp.searchsorted(sb, lrank, side="right")
    counts = (hi - lo).astype(xp.int64)
    csum = prefix_sum(xp, counts)
    total = csum[lcap - 1] if lcap else xp.asarray(0, dtype=xp.int64)

    sp = xp.sort(lrank)
    plo = xp.searchsorted(sp, rrank, side="left")
    phi = xp.searchsorted(sp, rrank, side="right")
    b_matched = (phi - plo) > 0
    l_unmatched = lmask & (counts == 0)
    b_unmatched = rmask & ~b_matched
    n_unl = xp.sum(l_unmatched.astype(xp.int64))
    n_unb = xp.sum(b_unmatched.astype(xp.int64))
    n_null = xp.sum((lmask & (lrank == -1)).astype(xp.int64))
    return JoinInfo(counts, csum, lo, perm_b, l_unmatched, b_unmatched,
                    total, n_unl, n_unb, n_null)


class JoinBuildSide(NamedTuple):
    """Build-side preparation, computed ONCE per build batch and cached on
    it (the reference builds its hash table once per broadcast build side,
    ``GpuHashJoin.scala:298``; the sort-based analog is one variadic sort).

    ``sorted_keys`` are the build rows' search-key arrays permuted into
    lexicographic order by ``perm_b``, with all BAD rows (dead padding,
    and null-keyed rows unless null_safe) sorted to the back so the live
    prefix ``[0, n_good)`` is purely value-ordered; probe batches locate
    match-range starts with ONE :func:`tuple_searchsorted` over that
    prefix and read the range ends from ``run_end`` (the precomputed
    end-of-equal-run per sorted position) — no union rank, no re-sort,
    no second binary search."""
    sorted_keys: Tuple["np.ndarray", ...]
    perm_b: "np.ndarray"       # int32[rcap] build rows in key-sorted order
    n_good: "np.ndarray"       # int32 scalar: live matchable rows (prefix)
    run_end: "np.ndarray"      # int32[rcap] end of each position's key run


def join_search_keys(xp, key_cols: Sequence[DeviceColumn],
                     null_safe: bool = False):
    """Search-key arrays for the tuple-search fast path: per key column
    its :func:`column_sort_keys` arrays (plus the null flag under
    null-safe equality, where NULL==NULL).  Rows excluded from matching
    (dead padding; null-keyed rows unless null_safe) are NOT encoded here
    — the build side sorts them behind the good prefix and the probe side
    zeroes their counts, which keeps the per-iteration search gathers to
    the value keys only."""
    keys = []
    from ..columnar.encoded import DictEncodedColumn
    for c in key_cols:
        if null_safe:
            keys.append(~c.validity)
        if isinstance(c, DictEncodedColumn):
            # join keys compare ACROSS two batches, so bare codes are only
            # sound when the exec layer lowered BOTH sides into one code
            # space (encoded.lower_join_codes sets join_codes pairwise:
            # build side keeps its sorted-dict codes, probe codes are
            # remapped with -1 for misses).  Without that coordination the
            # column materializes and takes the raw string-chunk path —
            # a structure mismatch here would corrupt the search silently.
            if c.join_codes is not None:
                keys.append(c.join_codes.astype(xp.int64))
                continue
            c = c.materialized()
        if _is_narrow_int(c):
            # an int32 key stays int32 (both sides come through here): one
            # sort operand and one gather a search round where the int64
            # form, split for the chip, costs two of each
            keys.append(c.data.astype(xp.int32))
            continue
        keys.extend(column_sort_keys(xp, c))
    return keys


def _is_narrow_int(col: DeviceColumn) -> bool:
    return (col.lengths is None and col.data is not None
            and isinstance(col.dtype, (T.ByteType, T.ShortType,
                                       T.IntegerType, T.DateType)))


def _bad_rows(xp, key_cols: Sequence[DeviceColumn], mask, null_safe: bool):
    """Rows that can never match: dead padding, plus null-keyed rows under
    SQL ``=`` semantics (the union path's -1/-2 sentinel-rank set)."""
    bad = ~mask
    if not null_safe:
        for c in key_cols:
            if c.validity is not None:
                bad = bad | ~c.validity
    return bad


def fastpath_supported(dtypes: Sequence["T.DataType"]) -> bool:
    """True when every join-key type has an exact :func:`column_sort_keys`
    encoding (everything except array/map keys, which fall back to the
    union-rank path)."""
    def ok(dt):
        if isinstance(dt, (T.ArrayType, T.MapType)):
            return False
        if isinstance(dt, T.StructType):
            return all(ok(f.data_type) for f in dt.fields)
        return True
    return all(ok(dt) for dt in dtypes)


def prepare_build_side(xp, rkeys: Sequence[DeviceColumn], rmask,
                       null_safe: bool = False) -> JoinBuildSide:
    """Sort the build side's key tuples once.  Jittable per build capacity;
    the result is cached on the build batch so B probe batches pay for ONE
    build sort instead of B union sorts."""
    rcap = rmask.shape[0]
    with _scope(xp, "join.build.key_transform"):
        bad = _bad_rows(xp, rkeys, rmask, null_safe)
        skeys = join_search_keys(xp, rkeys, null_safe)
    with _scope(xp, "join.build.sort"):
        # bad rows sort LAST (the bool key), good rows by value keys only
        perm, sorted_all = lex_sort(xp, [bad] + skeys)
        sorted_keys = tuple(sorted_all[1:])
    n_good = xp.sum((~bad).astype(xp.int32))
    # run_end[i]: end of the equal-key run containing sorted position i —
    # a reverse min-scan over next-run starts, computed once so probes
    # read match-range ENDS with one gather instead of a second search
    with _scope(xp, "join.build.run_ends"):
        if rcap > 1:
            nxt_diff = sorted_all[0][1:] != sorted_all[0][:-1]
            for k in sorted_keys:
                nxt_diff = nxt_diff | (k[1:] != k[:-1])
            idx = xp.arange(rcap - 1, dtype=xp.int32)
            ends = xp.where(nxt_diff, idx + 1,
                            xp.asarray(rcap, dtype=xp.int32))
            ends = xp.concatenate(
                [ends, xp.asarray([rcap], dtype=xp.int32)])
            if xp.__name__ == "numpy":
                run_end = np.minimum.accumulate(ends[::-1])[::-1]
            else:
                import jax
                run_end = jax.lax.cummin(ends, axis=0, reverse=True)
        else:
            run_end = xp.full((rcap,), rcap, dtype=xp.int32)
    return JoinBuildSide(sorted_keys, perm.astype(xp.int32),
                         n_good, run_end)


def probe_join_info(xp, lkeys: Sequence[DeviceColumn], lmask, rmask,
                    build: JoinBuildSide, null_safe: bool = False,
                    need_b_matched: bool = True,
                    need_l_unmatched: bool = True) -> JoinInfo:
    """Probe-only phase 1: transform probe keys with the same
    :func:`column_sort_keys` encoding, then find each probe row's match
    range in the pre-sorted build side: ONE multi-key binary search over
    the good-row prefix for the range start, one ``run_end`` gather for
    the range end.  Returns the same :class:`JoinInfo` contract as
    :func:`join_build` (``gather_pairs`` is shared), but costs
    O(L·k·log R) instead of an O((L+R)·k) union sort per probe batch.

    ``need_b_matched=False`` / ``need_l_unmatched=False`` (static) skip
    the unmatched-row flags for join types that never consume them
    (b: everything except full outer; l: everything except left/full) —
    fewer materialized outputs keeps the XLA:CPU program fused."""
    lcap = lmask.shape[0]
    rcap = rmask.shape[0]
    with _scope(xp, "join.probe.key_transform"):
        bad = _bad_rows(xp, lkeys, lmask, null_safe)
        qkeys = join_search_keys(xp, lkeys, null_safe)
    with _scope(xp, "join.probe.search"):
        lo = tuple_searchsorted(xp, build.sorted_keys, qkeys, side="left",
                                hi_init=build.n_good)
        loc = xp.clip(lo, 0, max(rcap - 1, 0))
        hit = ~bad & (lo < build.n_good)
        for s, q in zip(build.sorted_keys, qkeys):
            hit = hit & (s[loc] == q)
        hi = xp.where(hit, build.run_end[loc], lo)
    counts = xp.where(hit, hi - lo, 0).astype(xp.int64)
    csum = prefix_sum(xp, counts)
    total = csum[lcap - 1] if lcap else xp.asarray(0, dtype=xp.int64)
    if need_l_unmatched:
        l_unmatched = lmask & (counts == 0)
        n_unl = xp.sum(l_unmatched.astype(xp.int64))
    else:
        l_unmatched = xp.zeros(lcap, dtype=bool)
        n_unl = xp.asarray(0, dtype=xp.int64)

    if need_b_matched:
        # build-side match flags WITHOUT sorting the probe: each matched
        # probe row covers sorted-build positions [lo, hi); an
        # interval-cover scatter (+1 at lo, -1 at hi, prefix-sum > 0)
        # marks covered positions in O(L + R) — equal keys are contiguous
        # in the sorted build side, so covered <=> some live probe row
        # carries an equal key tuple
        with _scope(xp, "join.probe.build_cover"):
            lo_c = xp.where(hit, lo, rcap).astype(xp.int32)
            hi_c = xp.where(hit, hi, rcap).astype(xp.int32)
            if xp.__name__ == "numpy":
                cover = np.zeros(rcap + 1, dtype=np.int32)
                np.add.at(cover, lo_c, 1)
                np.add.at(cover, hi_c, -1)
                covered_sorted = np.cumsum(cover[:-1]) > 0
                b_matched = np.zeros(rcap, dtype=bool)
                b_matched[build.perm_b] = covered_sorted
            else:
                cover = (xp.zeros(rcap + 1, dtype=xp.int32)
                         .at[lo_c].add(1).at[hi_c].add(-1))
                covered_sorted = xp.cumsum(cover[:-1]) > 0
                b_matched = (xp.zeros(rcap, dtype=bool)
                             .at[build.perm_b].set(covered_sorted))
        b_unmatched = rmask & ~b_matched
        n_unb = xp.sum(b_unmatched.astype(xp.int64))
    else:
        b_unmatched = xp.zeros(rcap, dtype=bool)
        n_unb = xp.asarray(0, dtype=xp.int64)
    n_null = xp.sum((bad & lmask).astype(xp.int64))
    return JoinInfo(counts, csum, lo.astype(xp.int64), build.perm_b,
                    l_unmatched, b_unmatched, total, n_unl, n_unb, n_null)


class PairMaps(NamedTuple):
    """Gather maps for a join output batch of static capacity out_cap."""
    l_idx: "np.ndarray"   # int32[out_cap]
    r_idx: "np.ndarray"   # int32[out_cap]
    l_ok: "np.ndarray"    # bool[out_cap]  False -> left side null (right/full)
    r_ok: "np.ndarray"    # bool[out_cap]  False -> right side null (left/full)
    num_out: "np.ndarray"  # int32 scalar


def gather_pairs(xp, info: JoinInfo, out_cap: int,
                 with_unmatched_left: bool = False,
                 with_unmatched_right: bool = False,
                 offset=0) -> PairMaps:
    """Phase 2: enumerate output rows.  Layout: [inner pairs][unmatched left]
    [unmatched right] — segment starts are traced scalars, segment membership
    is a per-slot compare, so the whole thing stays static-shape.

    ``offset`` (traced scalar ok) selects the window [offset, offset+out_cap)
    of the global output — the chunked-gather contract of the reference's
    ``JoinGatherer.scala:730``: one compiled program per chunk capacity
    serves every chunk of an arbitrarily large join output."""
    lcap = info.counts.shape[0]
    rcap = info.perm_b.shape[0]
    k = xp.arange(out_cap, dtype=xp.int64) + xp.asarray(offset, dtype=xp.int64)

    i = xp.searchsorted(info.csum, k, side="right")
    i = xp.clip(i, 0, max(lcap - 1, 0)).astype(xp.int32)
    start = info.csum[i] - info.counts[i]
    j_local = k - start
    j = info.perm_b[xp.clip(info.lo[i] + j_local, 0, max(rcap - 1, 0))]

    inner = k < info.total
    l_idx = xp.where(inner, i, 0).astype(xp.int32)
    r_idx = xp.where(inner, j, 0).astype(xp.int32)
    l_ok = inner
    r_ok = inner
    num_out = info.total

    if with_unmatched_left:
        ul = compact_indices(xp, info.l_unmatched)
        sel = (k >= num_out) & (k < num_out + info.n_unmatched_l)
        t = xp.clip(k - num_out, 0, max(lcap - 1, 0)).astype(xp.int32)
        l_idx = xp.where(sel, ul[t], l_idx)
        l_ok = l_ok | sel
        num_out = num_out + info.n_unmatched_l

    if with_unmatched_right:
        ub = compact_indices(xp, info.b_unmatched)
        sel = (k >= num_out) & (k < num_out + info.n_unmatched_b)
        t = xp.clip(k - num_out, 0, max(rcap - 1, 0)).astype(xp.int32)
        r_idx = xp.where(sel, ub[t], r_idx)
        r_ok = r_ok | sel
        num_out = num_out + info.n_unmatched_b

    local = xp.clip(num_out - xp.asarray(offset, dtype=xp.int64), 0, out_cap)
    return PairMaps(l_idx, r_idx, l_ok, r_ok, local.astype(xp.int32))


def cross_pairs(xp, n_left, n_right, out_cap: int, offset=0) -> PairMaps:
    """All (i, j) combinations for nested-loop/cartesian joins.  n_left and
    n_right may be traced scalars; ``offset`` windows the pair space like
    :func:`gather_pairs`."""
    k = xp.arange(out_cap, dtype=xp.int64) + xp.asarray(offset, dtype=xp.int64)
    nr = xp.maximum(xp.asarray(n_right, dtype=xp.int64), 1)
    i = (k // nr).astype(xp.int32)
    j = (k % nr).astype(xp.int32)
    total = (xp.asarray(n_left, dtype=xp.int64)
             * xp.asarray(n_right, dtype=xp.int64))
    ok = k < total
    local = xp.clip(total - xp.asarray(offset, dtype=xp.int64), 0, out_cap)
    return PairMaps(xp.where(ok, i, 0), xp.where(ok, j, 0), ok, ok,
                    local.astype(xp.int32))


def matched_per_row(xp, pass_mask, idx, cap: int):
    """#passing pairs per source row (for condition-join fixups): segment-sum
    of the residual-condition pass mask over a gather map."""
    from .segmented import seg_sum
    return seg_sum(xp, pass_mask.astype(xp.int32), idx, cap)
