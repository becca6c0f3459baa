"""Device-decode engagement counters.

Every device decoder (parquet/ORC/CSV/JSON/Avro) either ENGAGES a file
(builds device columns straight from raw bytes) or DECLINES it to the
host pyarrow path.  The decline is silent by design (correctness first),
which made the engagement *rate* unobservable — a regression that
declined every file would still pass every test.  This module is the
shared scoreboard: files/bytes engaged vs declined per format, with a
per-reason decline breakdown, surfaced per query in
``last_query_metrics`` (``<fmt>DecodeFilesEngaged`` / ``…Declined`` /
``…BytesEngaged`` / ``…BytesDeclined``) and in the scale-rig report.

Decoders that know WHY they declined call :func:`set_decline_reason`
just before returning None; the exec layer folds it into the per-reason
map (default reason: ``decoder-declined``).
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

FORMATS = ("parquet", "orc", "csv", "json", "avro")

#: per-format counters; decline_reasons maps reason -> file count
DECODE_STATS: Dict[str, dict] = {
    fmt: {"files_engaged": 0, "files_declined": 0,
          "bytes_engaged": 0, "bytes_declined": 0,
          "decline_reasons": {}}
    for fmt in FORMATS}

#: the parquet device decoder's host work: compressed column-chunk bytes
#: read off files, pages walked, and their bytes once decompressed
PARQUET_PAGES = {"chunk_bytes_read": 0, "pages_decoded": 0,
                 "bytes_decompressed": 0}

_LOCK = threading.Lock()
_TLS = threading.local()


def record_chunk_read(nbytes: int) -> None:
    with _LOCK:
        PARQUET_PAGES["chunk_bytes_read"] += int(nbytes)


def record_pages(pages: int, out_bytes: int) -> None:
    with _LOCK:
        PARQUET_PAGES["pages_decoded"] += int(pages)
        PARQUET_PAGES["bytes_decompressed"] += int(out_bytes)


def set_decline_reason(reason: str) -> None:
    """Record the reason for the decline this thread is about to report
    (consumed once by the next :func:`record_declined`)."""
    _TLS.reason = reason


def _take_reason(default: str) -> str:
    r = getattr(_TLS, "reason", None)
    _TLS.reason = None
    return r or default


def record_engaged(fmt: str, nbytes: int = 0) -> None:
    _TLS.reason = None  # stale hints must not leak into a later decline
    if fmt not in DECODE_STATS:
        return
    with _LOCK:
        s = DECODE_STATS[fmt]
        s["files_engaged"] += 1
        s["bytes_engaged"] += int(nbytes)


def record_declined(fmt: str, nbytes: int = 0,
                    reason: Optional[str] = None) -> str:
    """Counts the decline and returns the reason it was counted under
    (the scan's ``host_decode`` span carries it)."""
    reason = reason or _take_reason("decoder-declined")
    if fmt not in DECODE_STATS:
        return reason
    with _LOCK:
        s = DECODE_STATS[fmt]
        s["files_declined"] += 1
        s["bytes_declined"] += int(nbytes)
        s["decline_reasons"][reason] = \
            s["decline_reasons"].get(reason, 0) + 1


def snapshot() -> Dict[str, float]:
    """Flat counter snapshot (reasons excluded) — the per-query metrics
    delta base, mirroring robustness.stats_snapshot."""
    out: Dict[str, float] = {}
    with _LOCK:
        for fmt, s in DECODE_STATS.items():
            out[f"{fmt}DecodeFilesEngaged"] = s["files_engaged"]
            out[f"{fmt}DecodeFilesDeclined"] = s["files_declined"]
            out[f"{fmt}DecodeBytesEngaged"] = s["bytes_engaged"]
            out[f"{fmt}DecodeBytesDeclined"] = s["bytes_declined"]
        out["parquetChunkBytesRead"] = PARQUET_PAGES["chunk_bytes_read"]
        out["parquetPagesDecoded"] = PARQUET_PAGES["pages_decoded"]
        out["parquetBytesDecompressed"] = \
            PARQUET_PAGES["bytes_decompressed"]
    return out


def report() -> Dict[str, dict]:
    """Deep copy for human-facing reports (scale rig, bench artifacts)."""
    with _LOCK:
        return {fmt: {**s, "decline_reasons": dict(s["decline_reasons"])}
                for fmt, s in DECODE_STATS.items()}
