#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (PERF.md §2).

    python3 benchmarks/control.py --workload <name> --seeds 1,2,3,... \\
        [--control-seeds 1,2,3] [--scale scale_factor=0.003]

One process, the cell's own size and session: for every seed it builds the
tables, registers them as the cell does, collects each query ``--collects``
times through ``sess.sql(text).collect()`` and prints the numbers
``compare.py`` gives against the float64 pandas reference (the lower
reading is the largest of them).  For every control seed it puts the
reference computed in float32, the precision below the float64 the
configuration states, in the program's place and prints the same numbers
(the upper reading is the smallest of them).  The benchmark's own runs never
call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--collects", type=int, default=1)
    ap.add_argument("--scale", type=R.scale_override, default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]
    cell = R.Cell(args.workload, args.scale)
    scratch = cell.scratch
    try:
        if seeds:
            import jax
            import spark_rapids_tpu as srt
            d0 = jax.devices()[0]
            R.say(phase="device", platform=d0.platform,
                  device_kind=d0.device_kind, scale=cell.scale)
            sess = srt.session(**cell.config.get("session_conf", {}))
        for seed in seeds:
            tables = cell.build_tables(seed)
            cell.scratch = os.path.join(scratch, f"seed-{seed}")
            R.register(sess, cell, tables)
            answers, failures = [], []
            for q in cell.queries:
                for _ in range(args.collects):
                    try:
                        answers.append((q, sess.sql(cell.sql[q]).collect()))
                    except Exception as e:  # noqa: BLE001 — counted
                        failures.append(f"{q}: {type(e).__name__}: {e}")
            compared = R.check(cell, tables, answers)
            R.say(side="program", workload=args.workload, seed=seed,
                  correct=R.is_correct(compared, failures),
                  failures=failures,
                  numbers={k: v["value"] for k, v in compared.items()})
            shutil.rmtree(cell.scratch, ignore_errors=True)
            del tables, answers
            gc.collect()
        for seed in control_seeds:
            tables = cell.build_tables(seed)
            compared = R.check(cell, tables, [], float_dtype=np.float32)
            R.say(side="control_float32", workload=args.workload, seed=seed,
                  correct=R.is_correct(compared, []),
                  numbers={k: v["value"] for k, v in compared.items()})
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
