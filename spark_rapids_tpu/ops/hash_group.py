"""Exact hash-based group ids — the TPU-native replacement for cuDF's hash
groupby (reference ``Table.groupBy`` device hash tables; SURVEY §2.10) on
the path where we previously used sort-based dense ranks.

Group-by does not need *ordered* ranks, only exact ids with
``equal keys ⇔ equal id``.  A sort costs O(n log n) with a big constant in
XLA; this kernel is O(n) per probe round:

1. mix all key words into a 32-bit hash per row (murmur3-style);
2. leader election into a power-of-two table of 2×capacity slots:
   unresolved rows scatter-min their row index into ``table[slot]``;
3. every row compares its full key (all key words — exact, not hashed)
   against the slot owner's; equal rows adopt the owner as their group
   representative, the rest linear-probe the next slot (``lax.while_loop``);
   same-key rows always move in lockstep, so each key resolves exactly once.
4. representatives get dense ids by cumsum over the row order
   (first-occurrence order, deterministic).

Dead (padding) rows get id == capacity: XLA drops out-of-bounds scatters,
and every caller masks their contributions.

The numpy backend keeps the independent sort-based path (ops/ranks.py), so
host-vs-device comparisons exercise two different grouping algorithms.
"""

from __future__ import annotations

import numpy as np

from ..columnar.column import DeviceColumn
from .ranks import column_sort_keys, dense_rank_columns


def _hash_words(jnp, keys):
    """murmur3-style mix of the rows' key words into uint32."""
    h = jnp.full(keys[0].shape[0], np.uint32(0x9747b28c), dtype=jnp.uint32)
    for k in keys:
        words = [k.astype(jnp.uint32)]
        if k.dtype.itemsize == 8:
            words.append((k >> 32).astype(jnp.uint32))
        for w in words:
            w = w * np.uint32(0xcc9e2d51)
            w = (w << 15) | (w >> 17)
            w = w * np.uint32(0x1b873593)
            h = h ^ w
            h = (h << 13) | (h >> 19)
            h = h * np.uint32(5) + np.uint32(0xe6546b64)
    h = h ^ (h >> 16)
    h = h * np.uint32(0x85ebca6b)
    h = h ^ (h >> 13)
    return h


#: compact-code fast path: product of per-word value ranges must fit this
#: many codes.  64Ki keeps every intermediate product < 2^32 (no int64
#: overflow) and the remap tables cache-resident.
_COMPACT_MAX_CODES = 1 << 16


def _compact_prelude(jnp, col_words, row_mask):
    """Range-compaction feasibility + per-row codes (cheap, always run).
    ``col_words``: per key column, ``(null_flags_bool, [int64 words])`` —
    computed once by the caller and shared with the fallback kernel.

    Treats every key word as a mixed-radix digit:
    ``code = Σ (word_i - min_i) * stride_i`` where ``stride`` is the
    running product of the per-word ranges.  Null flags are {0,1} digits
    whose range comes from a boolean ``any`` (4-8x cheaper than an int64
    min/max pass).  Returns ``(ok, codes)`` — ``ok`` is a traced scalar
    that is True iff every range is sane and the total code space fits
    ``_COMPACT_MAX_CODES``; ``codes`` are exact collision-free group codes
    when ``ok`` holds (garbage otherwise — callers must gate on ``ok``
    via ``lax.cond``).

    Cost: two fused reductions per data word plus one elementwise pass —
    no serial probe rounds.  This is the common case for real group-bys
    (low-cardinality ints/dates/bools/flags); wide ranges (floats,
    strings, ids) fail ``ok`` and take the fallback kernel instead.
    """
    B = _COMPACT_MAX_CODES
    cap = int(row_mask.shape[0])
    any_live = jnp.any(row_mask)
    imax = np.int64(np.iinfo(np.int64).max)
    imin = np.int64(np.iinfo(np.int64).min)
    one = jnp.asarray(1, dtype=jnp.int64)
    ok = jnp.asarray(True)
    p = one
    codes = jnp.zeros(cap, dtype=jnp.int64)

    def add_digit(digit, r, okd):
        nonlocal ok, p, codes
        ok = ok & okd
        codes = codes + digit * p
        p_next = p * jnp.clip(r, 1, B)  # clip: bounded even pre-check
        ok = ok & (p_next <= B)
        p = jnp.where(ok, p_next, one)

    for col_nulls, words in col_words:
        nulls = col_nulls & row_mask
        has_null = jnp.any(nulls)
        # null digit: 1 for null rows; range 2 only when nulls exist
        add_digit(nulls.astype(jnp.int64),
                  jnp.where(has_null, 2, 1).astype(jnp.int64),
                  jnp.asarray(True))
        for w in words:
            wmin = jnp.min(jnp.where(row_mask, w, imax))
            wmax = jnp.max(jnp.where(row_mask, w, imin))
            r = jnp.where(any_live, wmax - wmin + 1, one)
            # r >= 1 also rejects int64 wraparound (a true range near 2^64
            # wraps to a value <= 0, never to a small positive)
            add_digit(w - wmin, r, (r >= 1) & (r <= B))
    return ok, codes


def _first_occurrence_ids(jnp, slot_of_row, row_mask, table_size):
    """Dense first-occurrence group ids from any collision-free per-row
    slot assignment (compact codes, sorted-order ranks, ...).

    One scatter-min finds each slot's first row; a row-order cumsum over
    "this row IS its slot's first" numbers the groups in first-occurrence
    order — no sort needed (an argsort-based remap here doubled TPU
    compile time; sorts are the expensive op for the remote compiler).
    ``slot_of_row`` must be in [0, table_size) for live rows."""
    cap = int(row_mask.shape[0])
    row_idx = jnp.arange(cap, dtype=jnp.int32)
    slot_live = jnp.where(row_mask, slot_of_row,
                          table_size).astype(jnp.int32)
    first_row = jnp.full(table_size, cap, dtype=jnp.int32
                         ).at[slot_live].min(row_idx)
    fr_of_row = first_row[jnp.clip(slot_live, 0, table_size - 1)]
    is_first = row_mask & (fr_of_row == row_idx)
    dense = jnp.cumsum(is_first.astype(jnp.int64)) - 1
    ids = dense[jnp.clip(fr_of_row, 0, cap - 1)]
    return jnp.where(row_mask, ids, cap - 1)


def _compact_finish(jnp, codes, row_mask):
    """Exact dense first-occurrence group ids from in-range codes —
    bit-identical to the probing kernel's numbering, so either branch of
    the ``lax.cond`` agrees with the host path."""
    B = _COMPACT_MAX_CODES
    return _first_occurrence_ids(jnp, jnp.clip(codes, 0, B), row_mask, B)


def _probe_beats_sort(jnp) -> bool:
    """Trace-time fallback choice for codes that don't compact: the
    leader-election probe loop wins on XLA CPU (0.55s vs ~1.5s sort-based
    at 4M rows), but serial while_loop rounds of scatters are catastrophic
    on TPU (measured 4.7s at 4M rows vs 0.45s for the sort-based path —
    lax.sort is a tuned TPU kernel, the probe loop is not)."""
    import jax
    return jax.default_backend() == "cpu"


def _sorted_ids(jnp, keys, row_mask):
    """Exact first-occurrence-dense group ids via ONE variadic lex sort —
    the high-cardinality fallback on backends where sorts beat probe
    rounds (TPU).  Identical output to the probe kernel: dense ids in
    [0, n_groups) in first-occurrence order, dead rows parked at cap-1."""
    from .ranks import _ranks_from_lex, lex_sort
    cap = int(row_mask.shape[0])
    # liveness leads the sort key: live rows sort first, so live ranks are
    # exactly [0, n_groups).  bool (not int64): one narrow comparator
    # operand instead of lex_sort's two 32-bit halves of an int64
    sort_keys = [~row_mask] + list(keys)
    perm, skeys = lex_sort(jnp, sort_keys)
    rank = _ranks_from_lex(jnp, perm, skeys)
    # remap sorted-key rank order -> first-occurrence order (the probe
    # kernel's order, and the host path's) without a second sort
    return _first_occurrence_ids(jnp, jnp.clip(rank, 0, cap), row_mask, cap)


def _statically_compact(cols) -> bool:
    """True when the compact prelude is certain to accept these keys
    whatever the data: every key is dictionary-encoded on a sorted
    dictionary (its one key word is a code in ``[0, size]``, plus a null
    digit), and the product of those static ranges fits the code space.
    A trace-time fact — dictionary sizes are pytree aux data."""
    from ..columnar.encoded import DictEncodedColumn, op_enabled
    space = 1
    for c in cols:
        if not (isinstance(c, DictEncodedColumn) and c.dictionary.sorted
                and op_enabled("aggsort")):
            return False
        space *= 2 * (c.dictionary.size + 1)
    return space <= _COMPACT_MAX_CODES


def _device_ids(jnp, cols, row_mask, make_probe):
    """Shared device-path scaffolding for :func:`group_ids` /
    :func:`group_ids_small`: build each key word ONCE (shared by the
    compact prelude and the fallback), then dispatch
    ``lax.cond(compact_ok, compact, fallback)`` where the fallback is the
    caller's probe kernel on XLA CPU or the sorted kernel on TPU."""
    import jax
    col_words = [((~c.validity), column_sort_keys(jnp, c)) for c in cols]
    keys = [w for nulls, ws in col_words
            for w in (nulls.astype(jnp.int64), *ws)]
    compact_ok, compact_codes = _compact_prelude(jnp, col_words, row_mask)
    # keys whose code space is small BY CONSTRUCTION never reach the
    # fallback; give them the probe kernel, which costs nothing to
    # compile, instead of a variadic sort the chip's compiler spends
    # minutes on (q1's two dictionary keys: a 10-operand sort, >600 s)
    fallback = make_probe(keys) if (
        _probe_beats_sort(jnp) or _statically_compact(cols)) else (
        lambda _: _sorted_ids(jnp, keys, row_mask))
    return jax.lax.cond(compact_ok,
                        lambda _: _compact_finish(jnp, compact_codes,
                                                  row_mask),
                        fallback, None)


def group_ids(xp, cols, row_mask):
    """int64[cap] exact group ids over the key columns.

    Live rows with equal keys (nulls equal nulls, Spark semantics — the
    validity word is part of the key) share one id; ids are dense in
    ``[0, n_groups)`` in first-occurrence order on BOTH backends (so host
    and device agree on group order bit-for-bit).  Dead rows get
    id == cap - 1, which is provably unused by live groups whenever dead
    rows exist (n_groups <= cap - n_dead).
    """
    cap_n = int(row_mask.shape[0])
    if xp.__name__ == "numpy":
        # independent sort-based host path, remapped from sorted-key order
        # to the same first-occurrence order the device hash table produces
        rank = dense_rank_columns(xp, cols, row_mask)
        row_idx = np.arange(cap_n, dtype=np.int64)
        first_row = np.full(cap_n, cap_n, dtype=np.int64)
        live = np.asarray(row_mask)
        np.minimum.at(first_row, rank[live], row_idx[live])
        order = np.argsort(first_row, kind="stable")
        remap = np.empty(cap_n, dtype=np.int64)
        remap[order] = np.arange(cap_n, dtype=np.int64)
        ids = remap[rank]
        return np.where(live, ids, cap_n - 1)
    import jax
    import jax.numpy as jnp

    cap = int(row_mask.shape[0])

    def make_probe(keys):
        return lambda _: _probe_impl(keys)

    def _probe_impl(keys):
        M = 1 << (max(2 * cap, 16) - 1).bit_length()
        mask_m = np.uint32(M - 1)
        h = _hash_words(jnp, keys)
        row_idx = jnp.arange(cap, dtype=jnp.int32)
        sentinel = jnp.asarray(cap, dtype=jnp.int32)
        # one [cap, k] matrix so the per-round owner compare is a single
        # row gather instead of k scattered 1-D gathers
        key_mat = jnp.stack(keys, axis=1)

        def cond(state):
            _table, rep, off, rounds = state
            return jnp.any(rep < 0) & (rounds < M)

        def body(state):
            table, rep, off, rounds = state
            unresolved = rep < 0
            slot = ((h + off) & mask_m).astype(jnp.int32)
            cand = jnp.where(unresolved, row_idx, sentinel)
            table = table.at[slot].min(cand)
            owner = table[slot]
            safe_owner = jnp.clip(owner, 0, cap - 1)
            eq = (owner < cap) & jnp.all(key_mat == key_mat[safe_owner],
                                         axis=1)
            newly = unresolved & eq
            rep = jnp.where(newly, owner, rep)
            off = jnp.where(unresolved & ~eq, off + np.uint32(1), off)
            return table, rep, off, rounds + 1

        table0 = jnp.full(M, cap, dtype=jnp.int32)
        # dead rows resolve to themselves immediately (masked by callers)
        rep0 = jnp.where(row_mask, -1, row_idx)
        off0 = jnp.zeros(cap, dtype=jnp.uint32)
        _table, rep, _off, _r = jax.lax.while_loop(
            cond, body, (table0, rep0, off0, jnp.asarray(0, dtype=jnp.int32)))

        # defensive: the M-round bound guarantees resolution (a cohort
        # visits every slot within M probes); if that invariant ever broke,
        # making the row its own group keeps results mergeable instead of
        # corrupting them
        rep = jnp.where(rep < 0, row_idx, rep)

        is_rep = row_mask & (rep == row_idx)
        dense = jnp.cumsum(is_rep.astype(jnp.int64)) - 1
        ids = dense[jnp.clip(rep, 0, cap - 1)]
        return jnp.where(row_mask, ids, cap - 1)

    return _device_ids(jnp, cols, row_mask, make_probe)


def group_ids_small(xp, cols, row_mask, expected_groups: int):
    """Speculative small-table variant of :func:`group_ids`.

    The exact kernel's leader-election table is sized 2x capacity (16M
    slots for an 8M-row batch) — correct for any cardinality but ~60% of
    a fused aggregate's runtime.  When the speculation layer already
    predicts ``expected_groups`` (<= the group-table size), a table of
    ``4 * expected_groups`` slots with a BOUNDED probe suffices; rows
    still unresolved when the bound hits report ``expected_groups`` extra
    groups, which makes the observed count exceed any speculation <= it —
    the deferred-validation re-run then takes the exact path.  So the
    fast path is exact whenever it reports success, and mis-speculation
    (too many distinct keys OR pathological clustering) is detected by
    the SAME group-count check that guards table sizing.
    """
    cap = int(row_mask.shape[0])
    if xp.__name__ == "numpy":  # host path has no table to size
        return group_ids(xp, cols, row_mask)
    import jax
    import jax.numpy as jnp

    def make_probe(keys):
        return lambda _: _probe_impl(keys)

    def _probe_impl(keys):
        M = 1 << (max(4 * int(expected_groups), 64) - 1).bit_length()
        M2 = min(M, 1 << (max(2 * cap, 16) - 1).bit_length())
        max_rounds = min(M2, 64)
        mask_m = np.uint32(M2 - 1)
        h = _hash_words(jnp, keys)
        row_idx = jnp.arange(cap, dtype=jnp.int32)
        sentinel = jnp.asarray(cap, dtype=jnp.int32)
        key_mat = jnp.stack(keys, axis=1)

        def cond(state):
            _table, rep, off, rounds = state
            return jnp.any(rep < 0) & (rounds < max_rounds)

        def body(state):
            table, rep, off, rounds = state
            unresolved = rep < 0
            slot = ((h + off) & mask_m).astype(jnp.int32)
            cand = jnp.where(unresolved, row_idx, sentinel)
            table = table.at[slot].min(cand)
            owner = table[slot]
            # gather each slot WINNER's keys once into the tiny [M, k]
            # table, then compare rows against win_keys[slot] — streaming
            # reads of key_mat plus cache-resident table lookups, instead
            # of a cap-wide random gather into key_mat (the big kernel's
            # cost)
            win_keys = key_mat[jnp.clip(table, 0, cap - 1)]
            eq = (owner < cap) & jnp.all(key_mat == win_keys[slot], axis=1)
            newly = unresolved & eq
            rep = jnp.where(newly, owner, rep)
            off = jnp.where(unresolved & ~eq, off + np.uint32(1), off)
            return table, rep, off, rounds + 1

        table0 = jnp.full(M2, cap, dtype=jnp.int32)
        rep0 = jnp.where(row_mask, -1, row_idx)
        off0 = jnp.zeros(cap, dtype=jnp.uint32)
        _table, rep, _off, _r = jax.lax.while_loop(
            cond, body, (table0, rep0, off0, jnp.asarray(0, dtype=jnp.int32)))

        overflow = row_mask & (rep < 0)
        rep = jnp.where(rep < 0, row_idx, rep)
        is_rep = row_mask & (rep == row_idx)
        dense = jnp.cumsum(is_rep.astype(jnp.int64)) - 1
        ids = dense[jnp.clip(rep, 0, cap - 1)]
        # unresolved rows: burn the count so ng > any speculation <=
        # expected (their own ids are representatives already counted by
        # the cumsum; adding `expected_groups` to them guarantees the
        # overflow is visible in max(rank)+1 regardless of how many groups
        # resolved)
        ids = jnp.where(overflow, ids + int(expected_groups), ids)
        return jnp.where(row_mask, ids, cap - 1)

    # compact branch is EXACT (no burning needed): whenever the code space
    # fits, the ids are the true dense first-occurrence ids, and a count
    # above the speculated table size is caught by the same ng check.
    # The sorted fallback (TPU) is likewise exact — overflow burning only
    # applies to the bounded probe.
    return _device_ids(jnp, cols, row_mask, make_probe)
