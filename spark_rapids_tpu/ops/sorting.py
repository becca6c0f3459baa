"""Total-order sort over columnar batches (reference ``GpuSortExec``/
``SortUtils.scala``, backed there by cudf radix sort).

TPU approach: ONE fused variadic stable sort (``lax.sort`` with
``num_keys``; ``np.lexsort`` on host) over per-column integer sort keys,
most-significant first.  Handles asc/desc, nulls-first/last, Spark float
ordering (NaN largest, -0.0 == 0.0), strings (big-endian chunk keys) and
dead-row padding (always sorted last).  Descending uses bitwise NOT (order
reversal without the int64-min negation overflow).
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

from ..columnar.column import DeviceColumn
from .ranks import column_sort_keys, lex_sort


def sort_permutation(xp, specs: Sequence[Tuple[DeviceColumn, bool, bool]],
                     row_mask) -> "xp.ndarray":
    """specs: [(column, ascending, nulls_first), ...] in sort-priority order
    (most significant first).  row_mask: bool[capacity] live-row mask.
    Returns int32 permutation putting rows in order, dead rows last."""
    # flags stay NARROW (bool / int8): lex_sort splits every int64 key
    # into two 32-bit comparator operands, so a 0/1 flag must not be one
    keys = [~row_mask]                     # dead rows last, most significant
    for col, asc, nulls_first in specs:
        null_flag = (~col.validity).astype(xp.int8)
        keys.append(-null_flag if nulls_first else null_flag)
        for k in column_sort_keys(xp, col):  # most-significant first
            keys.append(k if asc else ~k)
    perm, _ = lex_sort(xp, keys)
    return perm.astype(xp.int32)
