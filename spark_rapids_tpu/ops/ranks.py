"""Exact dense-rank machinery — the TPU answer to cuDF's hash-based groupby
and join (reference ``Table.groupBy``/``Table.join`` device kernels).

Hash tables don't map to XLA (dynamic shapes, scatter contention).  Instead,
keys are reduced to *exact dense ranks* with integer sorts:

* each key column → dense int rank (order-preserving within the column);
* multiple columns → iterated pair-densification: rank = dense-rank of
  (rank_so_far, next_col_rank) pairs via one stable sort each;
* strings → big-endian 8-byte chunks, one densification per chunk (exact,
  no hash collisions; embedded NULs disambiguated by a length pass).

The resulting int32 rank array is a collision-free group id usable for
grouping, joins (rank equality == key equality), and distinct.  All ops are
static-shape sorts/cumsums that XLA maps well to TPU.
"""

from __future__ import annotations

import numpy as np

from ..columnar.column import DeviceColumn
from .. import types as T


def stable_argsort(xp, keys):
    if xp.__name__ == "numpy":
        return np.argsort(keys, kind="stable")
    return xp.argsort(keys, stable=True)


def _apply_perm(xp, perm, *arrays):
    return tuple(a[perm] for a in arrays)


def lex_sort(xp, keys):
    """ONE stable lexicographic sort over multiple key arrays
    (most-significant first).  Returns (perm, sorted_keys).

    This is the workhorse primitive: XLA's variadic ``lax.sort`` compares
    whole key tuples in a single fused sort pass (``num_keys``), so a k-key
    sort costs one O(n log n) pass instead of k chained argsorts — the
    difference between beating and trailing a host engine on group-by/sort
    heavy queries.  numpy path uses the equivalent ``np.lexsort``.

    64-bit integer keys are split into (hi int32, lo uint32) comparator
    pairs: under the TPU toolchain's x64 rewrite a 64-bit sort comparator
    lowers poorly (the split measured faster to compile and no slower to
    run), and the lexicographic order
    of (hi, lo-as-unsigned) equals the 64-bit order exactly (same hi =>
    two's-complement low words compare unsigned).  Sorted key values are
    reconstructed from the sorted pairs, so callers see the same
    (perm, sorted_keys) contract.

    Between 2^14 and 2^16 rows, and below that where the comparator is
    wide, the chip's sort is ``_network_sort``: the same order from a
    program that compiles at once (``_NETWORK_ROWS``, ``_NETWORK_WORDS``).
    """
    keys = list(keys)
    if xp.__name__ == "numpy":
        perm = np.lexsort(tuple(reversed(keys)))  # lexsort: LAST key primary
        return perm, [k[perm] for k in keys]
    import jax

    n = keys[0].shape[0]
    iota = xp.arange(n, dtype=xp.int32)
    sort_keys = []
    split = []  # per original key: False, or the signedness of the 64-bit
    for k in keys:
        if k.dtype == xp.int64:
            sort_keys.append((k >> 32).astype(xp.int32))
            sort_keys.append((k & 0xFFFFFFFF).astype(xp.uint32))
            split.append("i")
        elif k.dtype == xp.uint64:
            sort_keys.append((k >> xp.uint64(32)).astype(xp.uint32))
            sort_keys.append((k & xp.uint64(0xFFFFFFFF)).astype(xp.uint32))
            split.append("u")
        else:
            sort_keys.append(k)
            split.append(False)
    if _network_sorts(n, sort_keys):
        out = _network_sort(tuple(sort_keys) + (iota,))
    else:
        out = jax.lax.sort(tuple(sort_keys) + (iota,),
                           num_keys=len(sort_keys), is_stable=True)
    perm = out[-1]
    sorted_keys = []
    idx = 0
    for tag in split:
        if tag == "i":
            hi, lo = out[idx], out[idx + 1]
            idx += 2
            sorted_keys.append((hi.astype(xp.int64) << 32)
                               | lo.astype(xp.int64))
        elif tag == "u":
            hi, lo = out[idx], out[idx + 1]
            idx += 2
            sorted_keys.append((hi.astype(xp.uint64) << xp.uint64(32))
                               | lo.astype(xp.uint64))
        else:
            sorted_keys.append(out[idx])
            idx += 1
    return perm, sorted_keys


#: rows between which ``lex_sort`` is the rolled network below.  The chip's
#: compiler takes 8 s (2^14 rows, 3 operands) to 100 s (2^15 rows and
#: above, 5 operands; ~10 s more an operand whatever the row count) for ONE
#: ``lax.sort`` program and 1.3 s at 2^13 rows; the network compiles in
#: under a second at any width (compiled for a described v5e, PERF.md
#: section 6, PR 33).  Above 2^16 rows ``lax.sort`` stays: its run time is
#: what counts there.
_NETWORK_ROWS = (1 << 14, 1 << 16)
#: rows x key operands above which ``lex_sort`` is the network below 2^14
#: rows too.  One ``lax.sort`` program's compile time grows with both:
#: 1.8 s at 2^11 rows x 16 operands, 9.3 s at 2^12 x 16, 12.5 s at 2^13 x
#: 8, then 41.3 s at 2^13 x 16, 38.7 s at 2^12 x 32, 46.1 s at 2^11 x 90,
#: and at 2^13 x 91 (a group-by keyed by a 200-character string) the
#: chip's host had not finished after 330 s; the network takes 3.5-6.8 s at
#: 2^12-2^13 rows x 90 operands (compiled for a described v5e, PERF.md
#: section 6, PR 35).  The narrow sorts below 2^14 rows stay ``lax.sort``.
_NETWORK_WORDS = 1 << 17


def _network_sorts(n: int, sort_keys) -> bool:
    """Trace-time choice of the sort's form.  Not on XLA:CPU, whose
    ``lax.sort`` compiles at once; integer and bool keys only (a NaN has
    no place in the network's total order)."""
    import jax
    return ((_NETWORK_ROWS[0] <= n or n * len(sort_keys) > _NETWORK_WORDS)
            and n <= _NETWORK_ROWS[1] and n & (n - 1) == 0
            and jax.default_backend() != "cpu"
            and all(k.dtype.kind in "biu" for k in sort_keys))


def _network_sort(operands):
    """Ascending lexicographic sort of ``operands`` (most significant
    first, every one a key, the last one making the tuples distinct: the
    row's index, so the order is the stable order) as a bitonic network in
    ONE rolled loop: log2(n)(log2(n)+1)/2 compare-exchange steps, each a
    partner read at distance ``j`` (two contiguous slices of the doubled
    array, no gather) and a select.  The program is the loop's body, so it
    compiles in under a second where ``lax.sort`` takes minutes."""
    import jax
    import jax.numpy as jnp
    n = operands[0].shape[0]
    spans, dists = [], []
    for a in range(1, n.bit_length()):
        for b in range(a - 1, -1, -1):
            spans.append(1 << a)
            dists.append(1 << b)
    spans = jnp.asarray(spans, jnp.int32)
    dists = jnp.asarray(dists, jnp.int32)
    iota = jnp.arange(n, dtype=jnp.int32)

    def step(s, ops):
        k, j = spans[s], dists[s]
        low = (iota & j) == 0           # the pair's lower index

        def partner(x):
            twice = jnp.concatenate([x, x])
            return jnp.where(low, jax.lax.dynamic_slice(twice, (j,), (n,)),
                             jax.lax.dynamic_slice(twice, (n - j,), (n,)))
        theirs = tuple(partner(x) for x in ops)
        less = jnp.zeros((n,), jnp.bool_)
        same = jnp.ones((n,), jnp.bool_)
        for a, b in zip(theirs, ops):
            less = less | (same & (a < b))
            same = same & (a == b)
        # ascending blocks keep the smaller tuple at the lower index
        keeps_smaller = ((iota & k) == 0) == low
        take = (less == keeps_smaller) & ~same
        return tuple(jnp.where(take, a, b) for a, b in zip(theirs, ops))

    return jax.lax.fori_loop(0, int(spans.shape[0]), step, tuple(operands))


def tuple_searchsorted(xp, sorted_keys, query_keys, side="left",
                       hi_init=None):
    """Vectorized multi-key ``searchsorted``: insertion points of the query
    key *tuples* into the lexicographically sorted key tuples, without ever
    materializing a combined rank (the probe-only half of the join fast
    path — the build side is sorted once, probes just binary-search it).

    ``sorted_keys`` / ``query_keys`` are parallel lists of key arrays,
    most-significant first, with matching dtypes per position (the
    :func:`column_sort_keys` contract).  The sorted length is static, so
    the search is a fixed ``ceil(log2(n))+1`` rounds of gather+compare —
    no sort, no dynamic shapes, jittable.

    ``hi_init`` (traced scalar ok) restricts the search to the prefix
    ``[0, hi_init)`` — the join fast path searches only the good-row
    prefix of the sorted build side, which keeps sentinel/category keys
    OUT of the per-iteration gathers entirely."""
    n = int(sorted_keys[0].shape[0])
    m = query_keys[0].shape[0]
    lo = xp.zeros(m, dtype=xp.int32)
    hi = (xp.full(m, n, dtype=xp.int32) if hi_init is None
          else xp.broadcast_to(xp.asarray(hi_init, dtype=xp.int32), (m,)))
    if n == 0:
        return lo
    for _ in range(n.bit_length() + 1):
        mid = (lo + hi) >> 1
        midc = xp.clip(mid, 0, n - 1)
        lt = xp.zeros(m, dtype=bool)
        eq = xp.ones(m, dtype=bool)
        for s, q in zip(sorted_keys, query_keys):
            sv = s[midc]
            lt = lt | (eq & (sv < q))
            eq = eq & (sv == q)
        go = (lt | eq) if side == "right" else lt
        go = go & (lo < hi)
        stay = ~go & (lo < hi)
        lo = xp.where(go, mid + 1, lo)
        hi = xp.where(stay, mid, hi)
    return lo


def prefix_sum(xp, x):
    """``xp.cumsum(x)`` of a 1-D array, in two levels on the
    device backend: inside blocks of 1,024 rows, then the blocks' carries.
    The same sums at the same run time; the TPU compiler takes about a
    second for this form where the flat sum takes 20-40 s for int64 and
    4-14 s for int32 at 2^18-2^21 rows (PERF.md section 6, PR 32 and
    PR 33) - per program that holds one, in every cold process."""
    n = x.shape[0]
    block = 1024
    if xp.__name__ == "numpy" or n % block or n <= block:
        return xp.cumsum(x)
    inner = xp.cumsum(x.reshape(n // block, block), axis=1)
    total = inner[:, -1]
    carry = xp.cumsum(total) - total
    return (inner + carry[:, None]).reshape(n)


def dense_rank_from_sorted(xp, sorted_boundary_flags):
    """Given boundary flags in sorted order (True at the first row of each
    distinct key), returns 0-based dense ranks in sorted order."""
    return xp.cumsum(sorted_boundary_flags.astype(xp.int64)) - 1


def _ranks_from_lex(xp, perm, sorted_keys):
    """Dense ranks (unsorted order) from a lex_sort result."""
    n = perm.shape[0]
    diff = xp.zeros((n - 1,), dtype=bool) if n > 1 else xp.zeros((0,), dtype=bool)
    for k in sorted_keys:
        diff = diff | (k[1:] != k[:-1])
    first = xp.concatenate([xp.ones((1,), dtype=bool), diff])
    ranks_sorted = dense_rank_from_sorted(xp, first)
    out = xp.zeros((n,), dtype=xp.int64)
    if xp.__name__ == "numpy":
        out[perm] = ranks_sorted
        return out
    return out.at[perm].set(ranks_sorted)


def dense_rank_pairs(xp, a, b):
    """Dense rank of lexicographic (a, b) pairs.  a, b int64 arrays."""
    perm, sorted_keys = lex_sort(xp, [a, b])
    return _ranks_from_lex(xp, perm, sorted_keys)


def f64_bits_i64(x):
    """float64 -> its IEEE-754 bit pattern as int64 on device, WITHOUT
    64-bit bitcast-convert — the TPU X64 rewrite doesn't implement it
    (first live-chip run failed here; CPU accepts the bitcast, so this
    branches on backend).  The arithmetic path flushes denormals to
    signed zero, matching the engine's f64 DAZ semantics on TPU."""
    import jax
    import jax.numpy as jnp
    if jax.default_backend() == "cpu":
        return jax.lax.bitcast_convert_type(x, jnp.int64)
    from ..columnar.convert import _f64_bits, u64_to_i64
    return u64_to_i64(_f64_bits(x))


def _float_orderable_bits(xp, x, bits_dtype, canonical_nan):
    """Map floats to integers whose order matches Spark float ordering
    (-inf < ... < -0=0 < ... < inf < NaN), with NaN canonicalized."""
    if xp.__name__ == "numpy":
        b = x.view(bits_dtype)
    elif bits_dtype == xp.int64:
        b = f64_bits_i64(x)
    else:
        import jax
        b = jax.lax.bitcast_convert_type(x, bits_dtype)
    b = xp.where(xp.isnan(x), xp.asarray(canonical_nan, dtype=bits_dtype), b)
    zero = xp.asarray(0, dtype=bits_dtype)
    b = xp.where(x == 0.0, zero, b)  # -0.0 -> +0.0
    # IEEE trick: negative floats order-reversed; flip
    nbits = np.dtype(np.int64).itemsize * 8 if bits_dtype == xp.int64 else 32
    return xp.where(b < 0, ~b | (xp.asarray(1, dtype=bits_dtype)
                                 << (nbits - 1)), b)


def orderable_int64(xp, col: DeviceColumn):
    """Per-column transform to an int64 whose numeric order equals Spark's
    value order (nulls NOT handled here; strings NOT handled here)."""
    dt = col.dtype
    if isinstance(dt, (T.FloatType,)):
        return _float_orderable_bits(xp, col.data, xp.int32,
                                     0x7fc00000).astype(xp.int64)
    if isinstance(dt, T.DoubleType):
        return _float_orderable_bits(xp, col.data, xp.int64,
                                     0x7ff8000000000000)
    if isinstance(dt, T.BooleanType):
        return col.data.astype(xp.int64)
    return col.data.astype(xp.int64)


def string_chunks_be(xp, chars, lengths):
    """Yield int64 big-endian 8-byte chunks (masked past length) so that
    uint-compare order == lexicographic byte order.  Returned values are
    bias-shifted into signed int64 preserving order."""
    rows, width = chars.shape
    c = chars.astype(xp.uint64)
    out = []
    for start in range(0, width, 8):
        chunk = xp.zeros((rows,), dtype=xp.uint64)
        for b in range(8):
            col = start + b
            if col < width:
                byte = xp.where(col < lengths, c[:, col],
                                xp.asarray(0, dtype=xp.uint64))
                chunk = chunk | (byte << np.uint64(8 * (7 - b)))
        # order-preserving uint64 -> int64
        out.append((chunk ^ np.uint64(1 << 63)).astype(xp.int64))
    return out


def column_sort_keys(xp, col: DeviceColumn):
    """List of int64 key arrays for this column, most-significant first.
    Equality of all keys <=> Spark equality; lexicographic order of keys ==
    Spark ascending null-last order of *values* (null handling is separate,
    via the validity array)."""
    from ..columnar.encoded import DictEncodedColumn, op_enabled
    if isinstance(col, DictEncodedColumn):
        # Sorted dictionaries make code order == value order, so sorts and
        # group-bys run on ONE int32-code key instead of width/8 string
        # chunks + a length key.  Only sound within a single column (one
        # shared dictionary); cross-column comparability (joins) goes
        # through join_search_keys, which requires exec-layer coordinated
        # join_codes and never takes this branch.
        if col.dictionary.sorted and op_enabled("aggsort"):
            return [col.codes.astype(xp.int64)]
        col = col.materialized()
    if isinstance(col.dtype, T.StructType):
        keys = []
        for ch in col.children:
            keys.append(ch.validity)   # bool: a narrow sort operand
            keys.extend(column_sort_keys(xp, ch))
        return keys
    if col.lengths is not None:
        return string_chunks_be(xp, col.data, col.lengths) + \
            [col.lengths.astype(xp.int64)]
    return [orderable_int64(xp, col)]


def dense_rank_columns(xp, cols, num_rows_mask=None):
    """Combined 0-based dense rank over multiple key columns (exact group
    ids).  Nulls form their own group per column.  ``num_rows_mask`` (bool,
    False=dead padding row) folds dead rows into the key so they can't merge
    with live groups (callers still mask them out)."""
    keys = []
    if num_rows_mask is not None:
        keys.append(~num_rows_mask)            # bool flags stay narrow:
    for c in cols:                             # one byte a row, not 8
        keys.append(~c.validity)
        keys.extend(column_sort_keys(xp, c))
    if len(keys) == 1 and num_rows_mask is not None:
        # no key columns: mask is the only key (0 live / 1 dead); callers
        # expect int64 ranks, not the raw bool flag
        return keys[0].astype(xp.int64)
    perm, sorted_keys = lex_sort(xp, keys)
    return _ranks_from_lex(xp, perm, sorted_keys)
