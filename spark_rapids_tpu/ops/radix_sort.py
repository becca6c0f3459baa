"""Radix argsort as an XLA program — the TPU-first alternative to the
comparator sort (`jax.lax.sort` lowers to a bitonic network on TPU,
O(n log^2 n) compare-exchange passes; the reference leans on cuDF's GPU
radix sort for exactly this reason, SURVEY §2.10 ``Table.sort``).

Construction: classic stable LSD 1-bit splits.  Each pass is pure
VPU-friendly vector work — bit extract, two cumsums, a select, and a
scatter — so an int64 sort costs 64 linear passes instead of ~log^2(n)
full-width compare-exchange stages.  Stability follows from cumsum
preserving original order within each bit class, which also makes the
chained multi-key form lexicographic.

Whether this beats ``lax.sort`` depends on backend and size, so the
engine decides by a one-time BAKE-OFF per backend (measure both on a
representative input, cache the winner) rather than by assumption —
``spark.rapids.sql.sort.radix`` = auto|on|off.
"""

from __future__ import annotations

import time
from typing import List, Optional, Tuple

import numpy as np

#: conf key registered in config.py (string to avoid import cycles)
_CONF_KEY = "spark.rapids.sql.sort.radix"

#: backend -> (radix_us_for_64_passes, lax_us) frozen base measurement,
#: or None (CPU / failed probe: comparator sort)
_BAKEOFF: dict = {}

#: bake-off input size — big enough that fixed overheads don't decide,
#: small enough to stay cheap at first use
_PROBE_N = 1 << 18


def _to_orderable_u64(xp, k):
    """Integer key -> uint64 whose unsigned order equals the key's order
    (sign-bit flip); n_bits = the key's true width so narrow dtypes pay
    narrow passes."""
    dt = k.dtype
    if dt == xp.int64:
        u = k.astype(xp.uint64) ^ (xp.uint64(1) << xp.uint64(63))
        return u, 64
    if dt == xp.uint64:
        return k, 64
    if dt in (xp.int32, xp.int16, xp.int8):
        bits = np.dtype(str(dt)).itemsize * 8
        u = (k.astype(xp.int64) + (1 << (bits - 1))).astype(xp.uint64)
        return u, bits
    if dt in (xp.uint32, xp.uint16, xp.uint8):
        bits = np.dtype(str(dt)).itemsize * 8
        return k.astype(xp.uint64), bits
    if dt == xp.bool_:
        return k.astype(xp.uint64), 1
    return None, 0


def _radix_pass(xp, u, perm, b, iota1):
    bit = ((u >> xp.uint64(b)) & xp.uint64(1)).astype(xp.int32)
    ones_before = xp.cumsum(bit)
    # zeros_before[i] == (i+1) - ones_before[i]: one scan per pass, the
    # second is arithmetic
    zeros_before = iota1 - ones_before
    total0 = zeros_before[-1]
    pos = xp.where(bit == 1, total0 + ones_before - 1, zeros_before - 1)
    # pos is a permutation by construction — tell the scatter lowering
    scatter = dict(unique_indices=True, mode="promise_in_bounds")
    u = xp.zeros_like(u).at[pos].set(u, **scatter)
    perm = xp.zeros_like(perm).at[pos].set(perm, **scatter)
    return u, perm


def radix_argsort(xp, keys: List, n_bits_list: Optional[List[int]] = None):
    """Stable lexicographic argsort of integer key arrays (most-
    significant key first) via chained LSD radix: sort by the LAST key
    first; stability makes the chain lexicographic.  Returns perm
    (int32).  Caller guarantees every key maps through
    ``_to_orderable_u64``."""
    n = keys[0].shape[0]
    perm = xp.arange(n, dtype=xp.int32)
    if n == 0:
        return perm
    iota1 = xp.arange(1, n + 1, dtype=xp.int32)
    for ki in range(len(keys) - 1, -1, -1):
        u, bits = _to_orderable_u64(xp, keys[ki])
        if n_bits_list is not None:
            bits = n_bits_list[ki]
        u = u[perm]
        for b in range(bits):
            u, perm = _radix_pass(xp, u, perm, b, iota1)
    return perm


#: dtype name -> radix pass count (bit width); matches _to_orderable_u64
_DTYPE_BITS = {"int64": 64, "uint64": 64, "int32": 32, "uint32": 32,
               "int16": 16, "uint16": 16, "int8": 8, "uint8": 8,
               "bool": 1}

#: pass budget: beyond this the linear passes lose to the comparator
#: sort regardless of backend (three full int64 keys = 192)
_MAX_PASSES = 160


def total_passes(keys) -> Optional[int]:
    """Total radix passes for a key list, or None when any dtype is
    outside the envelope.  Pure dtype predicate — no device work."""
    bits = 0
    for k in keys:
        b = _DTYPE_BITS.get(str(k.dtype))
        if b is None:
            return None
        bits += b
    return bits


def supported_keys(xp, keys) -> bool:
    if not keys:
        return False
    p = total_passes(keys)
    return p is not None and p <= _MAX_PASSES


def bakeoff_base(xp) -> Optional[Tuple[int, int]]:
    """ONE frozen measurement per backend: (radix microseconds for a
    64-pass sort, lax.sort microseconds) at _PROBE_N.  Every pass-count
    verdict derives from it linearly, so the kernel-cache trace salt
    stays a single stable value.  None on CPU (measured: the comparator
    sort wins ~3x there — no probe tax) and on probe failure."""
    import jax
    backend = jax.default_backend()
    if backend in _BAKEOFF:
        return _BAKEOFF[backend]
    if backend == "cpu":
        _BAKEOFF[backend] = None
        return None
    try:
        rng = np.random.default_rng(0)
        k = xp.asarray(rng.integers(-(1 << 62), 1 << 62, _PROBE_N))

        # probe inputs are jit ARGUMENTS, never closure constants: XLA
        # constant-folds closed-over arrays, i.e. it would run the whole
        # 64-pass sort in the COMPILER (minutes, and it segfaulted the
        # CPU backend on the full suite)
        def run_radix(k):
            return radix_argsort(xp, [k])

        def run_lax(k):
            iota = xp.arange(_PROBE_N, dtype=xp.int32)
            cols = ((k >> 32).astype(xp.int32),
                    (k & 0xFFFFFFFF).astype(xp.uint32))
            return jax.lax.sort(cols + (iota,), num_keys=2,
                                is_stable=True)[-1]

        jit_radix = jax.jit(run_radix)
        jit_lax = jax.jit(run_lax)

        def timed(f):
            jax.block_until_ready(f(k))      # compile + settle
            best = float("inf")
            for _rep in range(3):  # min-of-3: one noisy sample must not
                t0 = time.perf_counter()  # freeze the wrong sort forever
                jax.block_until_ready(f(k))
                best = min(best, time.perf_counter() - t0)
            return best

        base = (max(int(timed(jit_radix) * 1e6), 1),
                max(int(timed(jit_lax) * 1e6), 1))
    except Exception as e:
        import warnings
        warnings.warn(f"radix bake-off probe failed ({e!r}); keeping the "
                      f"comparator sort on {backend}")
        base = None
    _BAKEOFF[backend] = base
    return base


def radix_wins(xp, passes: int) -> bool:
    """Derive the verdict for a total pass count from the frozen base
    measurement: per-pass cost scales linearly; the lax.sort baseline is
    held constant across key widths (slightly optimistic for it — the
    0.9 win margin absorbs the slop)."""
    from ..config import RapidsConf
    try:
        mode = str(RapidsConf.get_global().get(_CONF_KEY, "auto")).lower()
    except Exception:
        mode = "auto"
    if mode == "on":
        return True
    if mode == "off":
        return False
    base = bakeoff_base(xp)
    if base is None:
        return False
    t_radix64, t_lax = base
    return (t_radix64 / 64.0) * passes < t_lax * 0.9
