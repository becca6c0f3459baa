"""The comparison that decides ``correct``: one collected answer against
the plain reference's answer for the same query over the same tables.

Three numbers are compared, each with a limit of its own (the query's
``limits``): ``rows_gap`` (difference in row count), ``exact_mismatches``
(cells of the key, count and integer columns that differ, row by row in the
answer's order) and ``float_rel_gap`` (the widest gap of a float cell from
the reference's, relative to the reference's value or the column's median,
whichever is larger).  A missing column or a NaN on one side only reads
as ``WRONG``: a number no limit admits (and that JSON can hold).
"""

from __future__ import annotations

from typing import Dict

import numpy as np
import pandas as pd

NUMBERS = ("rows_gap", "exact_mismatches", "float_rel_gap")
WRONG = 1e300


def tables_for_reference(tables, columns_by_table, float_dtype=None
                         ) -> Dict[str, pd.DataFrame]:
    """The referenced columns of each Arrow table as pandas frames; dates
    as datetime64.  ``float_dtype`` (the control) narrows every float
    column before the reference sees it."""
    out = {}
    for name, columns in columns_by_table.items():
        frame = tables[name].select(columns).to_pandas(date_as_object=False)
        if float_dtype is not None:
            floats = frame.select_dtypes("floating").columns
            frame = frame.astype({c: float_dtype for c in floats})
        out[name] = frame
    return out


def _plain(column: pd.Series) -> np.ndarray:
    """Values of an exact column in a form that compares across the
    engine's Arrow types and pandas': dates as days, the rest as is."""
    if pd.api.types.is_datetime64_any_dtype(column.dtype):
        return column.to_numpy().astype("datetime64[D]").astype(np.int64)
    values = column.to_numpy()
    if values.dtype == object and len(values) and hasattr(values[0], "toordinal"):
        return np.array([np.datetime64(v, "D") for v in values]
                        ).astype(np.int64)
    if values.dtype.kind in "OUT":
        return values.astype(str)
    return values


def compare(got: pd.DataFrame, want: pd.DataFrame, spec: dict) -> dict:
    """The three numbers for one answer, and the float column that read the
    widest gap.  ``spec`` names the ``exact`` and the ``float`` columns;
    rows are compared in the order they came."""
    rows = min(len(got), len(want))
    numbers = {"rows_gap": abs(len(got) - len(want)),
               "exact_mismatches": 0, "float_rel_gap": 0.0,
               "float_column": None}
    for name in spec.get("exact", ()):
        if name not in got.columns:
            numbers["exact_mismatches"] += max(len(want), 1)
            continue
        a = _plain(got[name].iloc[:rows])
        b = _plain(want[name].iloc[:rows])
        numbers["exact_mismatches"] += int(np.sum(a != b))
    for name in spec.get("float", ()):
        if name not in got.columns:
            numbers["float_rel_gap"], numbers["float_column"] = WRONG, name
            continue
        a = got[name].iloc[:rows].to_numpy(dtype=np.float64, na_value=np.nan)
        b = want[name].iloc[:rows].to_numpy(dtype=np.float64,
                                            na_value=np.nan)
        if rows == 0:
            continue
        if np.any(np.isnan(a) != np.isnan(b)):
            numbers["float_rel_gap"], numbers["float_column"] = WRONG, name
            continue
        both = ~np.isnan(b)
        if not both.any():
            continue
        scale = np.maximum(np.abs(b[both]), np.median(np.abs(b[both])))
        scale = np.where(scale > 0, scale, 1.0)
        gap = float(np.max(np.abs(a[both] - b[both]) / scale))
        if gap > numbers["float_rel_gap"]:
            numbers["float_rel_gap"], numbers["float_column"] = gap, name
    return numbers


def worst(readings) -> dict:
    """The worst of each number over several answers."""
    out = {name: 0 for name in NUMBERS}
    out["float_column"] = None
    for numbers in readings:
        if numbers["float_rel_gap"] > out["float_rel_gap"]:
            out["float_column"] = numbers.get("float_column")
        for name in NUMBERS:
            out[name] = max(out[name], numbers[name])
    return out


def within(numbers: dict, limits: dict) -> bool:
    return all(numbers[name] <= limits[name] for name in NUMBERS)
