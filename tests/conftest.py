"""Test bootstrap: run everything on a virtual 8-device CPU mesh so
multi-chip sharding logic is exercised without TPU hardware (the chip
itself is exercised by ``chip_smoke.py``, through the chip tool).

The device-count flag and the platform must be in place before any backend
initializes, so both are set here, ahead of the first ``import jax``.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pyarrow as pa  # noqa: E402
import pytest  # noqa: E402


#: the <3-minute smoke tier (`pytest -m quick`): one module per major
#: layer — columnar model, expressions, SQL front-end+planner, joins,
#: memory/spill/retry, native lib.  Everything else is marked slow; the
#: full matrix runs in ci/run_ci.sh.
QUICK_MODULES = {
    "test_columnar", "test_expressions", "test_sql", "test_joins",
    "test_join_fastpath",
    # the TPC-DS star join (ISSUE 33): side choice, NULL keys, the share
    "test_tpcds_star",
    # the planner's column pruning (ISSUE 34): the rule by node type, the
    # same answers with and without it, the join cells' plans at their sizes
    "test_column_pruning",
    # TPC-DS q98 (ISSUE 35): the window, the global sort and the range
    # exchange against the benchmark's reference, NULLs, ties, zero totals
    "test_tpcds_report",
    # the planner's star-join order: q98, q7 and Q3 planned with the rule and
    # without it, the estimates, the runs it leaves alone
    "test_join_order",
    "test_memory", "test_native", "test_cross_slice", "test_hive_udf",
    # observability tracer: tier-1 per ISSUE 3 (trace regressions must
    # surface in the quick gate, not only in full CI)
    "test_tracer",
    # robustness: chaos-schedule determinism + the resilient shuffle
    # fetch protocol (retry/deadline/blacklist/recompute) are tier-1 per
    # ISSUE 4 — a silent regression here only shows up under failure
    "test_chaos", "test_shuffle",
    # the jax ShimProvider exercised end-to-end every CI run, resolved
    # by probing and injected
    "test_shims",
    # pipelined async execution (ISSUE 5): scheduler/prefetch/transfer
    # bit-parity and exception propagation are tier-1 — a silent
    # ordering or queue-hang regression must surface in the quick gate
    "test_async_pipeline",
    # encoded columnar execution (ISSUE 6): representation round-trips,
    # op parity encoded-on vs -off, the encoded wire format, and the
    # kill-switch reversion are tier-1 — an encoding bug is silent data
    # corruption, not a crash
    "test_encoded",
    # whole-stage XLA compilation (ISSUE 7): terminal stage formation,
    # fused-vs-killswitched bit parity, and the donation-safety guard
    # are tier-1 — a fusion or donation bug is silent data corruption
    "test_whole_stage",
    # performance flight recorder (ISSUE 8): metrics-registry accounting
    # under the parallel scheduler, doctor verdicts on known injected
    # bottlenecks are tier-1 — wrong attribution silently misdirects
    # every perf decision downstream
    "test_metrics_registry", "test_doctor",
    # multi-tenant serving (ISSUE 9): weighted-fair admission, tenant
    # budgets, the cross-query result/broadcast sharing tiers and the
    # generation-safe kernel-cache clear are tier-1 — a sharing bug is
    # silent cross-tenant data corruption, an admission bug is silent
    # starvation
    "test_serving",
    # query lifecycle (ISSUE 10): the cancellation race matrix
    # (semaphore/retention/queue accounting at every poll site), the
    # WFQ vft rollback, pressure degradation and the poison-query
    # quarantine are tier-1 — a cancel leak is a slow engine death, a
    # quarantine bug re-kills the device
    "test_lifecycle",
    # the telemetry plane is pure-stdlib and loopback-local (embedded
    # HTTP server, SLO arithmetic, wire trace stitching) — fast, and a
    # regression here blinds every production scrape target
    "test_telemetry",
    # dispatch budgets (ISSUE 14): per-shape launch counts, the fused
    # join probe's <=1-readback contract, and dispatch-coalescer parity
    # are tier-1 — a launch-count regression is a silent perf cliff
    # that no correctness test would ever fail
    "test_dispatch_budget",
    # pod-scale fault domain (ISSUE 19): the phi-accrual detector state
    # machine, epoch fencing, speculative fetch and the blacklist
    # generation race are tier-1 — a regression here is silent data
    # loss that only manifests when a peer actually dies
    "test_failure_detector",
    # first run on the chip (ISSUE 22): the chip-compiler compiles of the
    # two Pallas kernels, the four-device mesh exchange and the q1
    # aggregate at real widths, the loud Pallas gate, and chip_smoke.py's
    # phases run in-process on the CPU — what keeps `chip_smoke.py`
    # starting on the chip between chip runs
    "test_tpu_compile", "test_chip_smoke",
    # several executors on one host (spark.executor.instances): partitions
    # on their own chip, stages where their data lies, exchanges over the
    # all_to_all — against pandas with placement asserted, and the
    # planned-query mesh tests that ride it
    "test_mesh_placement", "test_mesh_shuffle",
    # the dense form of the group-table reductions (ISSUE 30): what the
    # chip runs for small tables and no other CPU test executes, against
    # the scatter form — a wrong sum here is a silent wrong answer
    "test_dense_group_reduce",
    # what the benchmark's cells run and the next PRs rework (ISSUE 31):
    # the device parquet decoder against pyarrow (the parquet cell's
    # longest device programs, Queue 1 item 3), the prepacked D2H every
    # collect ends in, and the kernel cache every cell's programs live
    # in — tier-1 time goes where the cells go
    "test_device_parquet", "test_prepack", "test_kernel_cache",
    # a file scan the planner narrowed (ISSUE 36): every reader path of
    # FileScanExec, narrowed, against the whole scan; the wide string that
    # is not read no longer declines the file, the one that is read does
    "test_scan_pruning",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("slow"):
            continue     # an explicit slow mark wins over module tiering
        mod = item.module.__name__.rsplit(".", 1)[-1] if item.module else ""
        item.add_marker(pytest.mark.quick if mod in QUICK_MODULES
                        else pytest.mark.slow)


def release_compiled_caches():
    """Free XLA executables (per test module here; scaletest.run_suite
    does the same per query) — accumulated compiled-code state segfaults
    the XLA:CPU JIT inside backend_compile_and_load past a few hundred
    programs (reproduced repeatedly, never in isolation).  Engine-level
    import: pulling in the whole scale rig here would turn any rig-corpus
    import error into a suite-wide teardown failure."""
    from spark_rapids_tpu.sql.physical.kernel_cache import (
        release_compiled_programs)
    release_compiled_programs()


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """Free XLA executables between test modules (see
    release_compiled_caches); modules recompile their shared kernels,
    which is noise next to the crash it prevents."""
    yield
    release_compiled_caches()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture()
def session():
    import spark_rapids_tpu as srt
    s = srt.session()
    yield s
