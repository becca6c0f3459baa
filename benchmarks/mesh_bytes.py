"""The bytes a collect has to move between chips, and the chip's peak for
moving them.

What must cross: every live row whose target partition is owned by another
chip than the one its map output lies on, at the row's width in the
exchange's arrays (data, validity, lengths; codes for a dictionary-encoded
column).  The program knows both from the counts its map programs return
and from the arrays' shapes, and sums them per collect in the counter
``meshCrossChipBytes`` of ``last_query_metrics``; rows that stay on their
chip, padding and dead rows are not in it.  This file turns the counter
into the roofline's operands; the peak comes from ``peaks_ici.json`` by
``device_kind``, and a kind that is not there is an error."""

from __future__ import annotations

import json
import os
from typing import Optional

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTER = "meshCrossChipBytes"


def cross_chip_bytes_per_collect(run: dict) -> Optional[float]:
    """Mean over the cell's queries of the bytes one collect of the query
    moved between chips (its last warm collect: the same tables and the
    same plan as every collect of the window); None where the program has
    no such counter or nothing crossed."""
    per_query = [float((run["query_metrics"].get(q) or {}).get(COUNTER) or 0)
                 for q in run["cell"]["queries"]]
    total = sum(per_query)
    return total / len(per_query) if total > 0 else None


def ici_bytes_per_s(device_kind: str) -> float:
    """One chip's peak for sending to its neighbours."""
    with open(os.path.join(HERE, "peaks_ici.json")) as f:
        peaks = json.load(f)
    if device_kind not in peaks:
        raise SystemExit(f"mesh_bytes.py: no ICI peak for device kind "
                         f"{device_kind!r} in peaks_ici.json")
    return float(peaks[device_kind]["ici_bytes_per_s"])


def device_kind() -> str:
    import jax
    return jax.devices()[0].device_kind
