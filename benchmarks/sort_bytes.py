"""The bytes a sort of a query's answer has to move, whatever implements it.

A total order over the answer reads every row of it once and writes every
row once: rows x the Arrow width of the answer's columns (data, offsets and
validity bits), twice.  The answer is the reference's (``reference/<q>.py``
over the tables the generator left in ``join_bytes.BUILT``: the same
objects the harness keeps for the check), so nothing is read from the
program and the count is the same work whatever the sort is made of: a
comparator network over key words, a radix pass, a merge of runs.
``metrics/sort_roofline.py`` divides it by the chip's HBM peak and by the
device time of the ``SortExec`` programs.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, Optional

import compare
import join_bytes

HERE = os.path.dirname(os.path.abspath(__file__))


def answer_bytes(answer) -> int:
    """Arrow bytes of a reference's answer (a pandas frame): a number at
    its width, a string as its UTF-8 bytes and a four-byte offset (one
    more than rows), a validity bit a row for a column that holds a
    NULL."""
    rows = len(answer)
    total = 0
    for name in answer.columns:
        column = answer[name]
        if column.dtype.kind in "fiub":
            total += column.dtype.itemsize * rows
        else:
            total += 4 * (rows + 1) + sum(
                len(str(v).encode()) for v in column.dropna())
        if column.isna().any():
            total += (rows + 7) // 8
    return total


def sort_bytes(query: str, tables: Dict[str, object]) -> Optional[float]:
    """Bytes one collect's sort of the whole answer must move; None for a
    query whose text orders nothing or keeps only the first rows (a top-n
    need not move the answer)."""
    with open(os.path.join(HERE, "queries", query + ".sql")) as f:
        text = f.read().lower()
    if "order by" not in text or "limit" in text.split("order by")[-1]:
        return None
    with open(os.path.join(HERE, "queries", query + ".json")) as f:
        referenced = json.load(f)["tables"]
    if any(t not in tables for t in referenced):
        return None
    spec = importlib.util.spec_from_file_location(
        "sort_bytes_reference_" + query,
        os.path.join(HERE, "reference", query + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    reference = module.reference
    answer = reference(compare.tables_for_reference(tables, referenced))
    return 2.0 * answer_bytes(answer)


def sort_bytes_per_collect(run: dict) -> Optional[float]:
    """Mean over the cell's queries, from the tables of this run; None
    where nothing was built here or a query sorts no whole answer."""
    if not join_bytes.BUILT:
        return None
    per_query = [sort_bytes(q, join_bytes.BUILT)
                 for q in run["cell"]["queries"]]
    if not per_query or any(b is None for b in per_query):
        return None
    return sum(per_query) / len(per_query)
