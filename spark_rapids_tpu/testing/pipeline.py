"""Pipelined-execution rig — parity and speedup evidence for the async
execution layer (docs/async_pipeline.md).

Runs the same TPC-H-ish multi-partition suite the chaos soak uses
(testing/chaos.py QUERIES over scaletest.build_tables data) twice: once
on the serial engine, once with the parallel partition scheduler +
prefetch queues + double-buffered transfers, asserts the results are
BIT-IDENTICAL, and reports the wall-clock delta.  Used by

* tests/test_async_pipeline.py — the parity matrix, and
* ad hoc:  python -m spark_rapids_tpu.testing.pipeline [rows]

On a single-core XLA:CPU host the speedup is bounded by how much real
blocking (file/network I/O, device round trips) the workload has to
hide; host<->device transfers are what the overlap reclaims.
"""

from __future__ import annotations

import time
from typing import Dict, Optional

import pandas as pd


def pipeline_conf(parallelism: int = 4, prefetch: bool = True,
                  depth: int = 2, double_buffer: bool = True
                  ) -> Dict[str, object]:
    """Conf overrides enabling the three pipeline features.  Device
    admission (concurrentGpuTasks) follows the scheduler width so the
    pool can actually overlap; set it lower to measure admission
    contention (sem_wait spans)."""
    return {
        "spark.rapids.tpu.task.parallelism": parallelism,
        "spark.rapids.sql.concurrentGpuTasks": max(1, parallelism),
        "spark.rapids.tpu.prefetch.enabled": prefetch,
        "spark.rapids.tpu.prefetch.depth": depth,
        "spark.rapids.tpu.transfer.doubleBuffer.enabled": double_buffer,
    }


def _suite_conf() -> Dict[str, object]:
    # shuffled (not broadcast) joins so the exchanges see real traffic —
    # same shape the chaos soak exercises
    return {"spark.rapids.sql.autoBroadcastJoinThreshold": 1}


def run_suite(sess, tables) -> Dict[str, pd.DataFrame]:
    """Canonicalized result frames for every suite query."""
    from ..sql import functions as F
    from .chaos import QUERIES, _canonical, augment_tables
    tables = augment_tables(tables)
    return {name: _canonical(fn(sess, tables, F)) for name, fn in QUERIES}


def measure(rows: int = 120_000, repeats: int = 2,
            parallelism: int = 4,
            tables: Optional[dict] = None) -> dict:
    """Serial vs pipelined wall clock over the suite with a bit-parity
    assert; returns the two wall clocks and their ratio."""
    import spark_rapids_tpu as srt
    from ..config import RapidsConf
    from .scaletest import build_tables
    if tables is None:
        tables = build_tables(rows)

    def timed(sess):
        run_suite(sess, tables)  # warm: compiles + upload cache
        best, last = None, None
        for _ in range(max(1, repeats)):
            t0 = time.perf_counter()
            last = run_suite(sess, tables)
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best, last

    base = RapidsConf.get_global()
    off_sess = srt.session(conf=base.copy(_suite_conf()))
    off_s, off_res = timed(off_sess)

    on_conf = dict(_suite_conf())
    on_conf.update(pipeline_conf(parallelism=parallelism))
    on_sess = srt.session(conf=base.copy(on_conf))
    on_s, on_res = timed(on_sess)

    mismatches = []
    for name in off_res:
        try:
            pd.testing.assert_frame_equal(on_res[name], off_res[name],
                                          check_exact=True)
        except AssertionError as e:
            mismatches.append(f"{name}: {e}")
    assert not mismatches, \
        "pipelined run diverged from the serial run:\n" + \
        "\n".join(mismatches)

    return {
        "pipeline_rows": rows,
        "pipeline_queries": len(off_res),
        "pipeline_parallelism": parallelism,
        "pipeline_off_seconds": round(off_s, 4),
        "pipeline_on_seconds": round(on_s, 4),
        "pipeline_speedup": round(off_s / max(on_s, 1e-9), 3),
        "pipeline_bit_identical": True,
    }


def main() -> None:
    import json
    import sys
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 120_000
    print(json.dumps(measure(rows), indent=2))


if __name__ == "__main__":
    main()
