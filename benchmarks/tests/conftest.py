"""Tests of the benchmark's own arithmetic, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The repo's tier-1 command collects ``tests/`` only, so these neither raise
nor lower its count."""

import argparse
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCH, os.path.dirname(BENCH)):
    if path not in sys.path:
        sys.path.insert(0, path)

#: every cell (file under workloads/) at a size a test run can hold:
#: about 20,000 LINEITEM rows
SCALE = {"scale_factor": 1 / 300}


def cells():
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "workloads"))
                  if f.endswith(".json"))


@pytest.fixture
def run_args():
    def make(workload, seed=11, seconds=0.5, trace=0):
        return argparse.Namespace(workload=workload, seed=seed,
                                  seconds=seconds, trace=trace, scale=SCALE,
                                  keep_trace=None)
    return make
