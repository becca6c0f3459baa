"""Out-of-core machinery driven END-TO-END through the planner by real
scale-rig queries: the spill catalog, OOM retry/split and
out-of-core sort paths are covered by unit suites at their seams — this
exercises them through planned joins/aggregates/sorts with the pandas
oracle still checking results.  Reference: inject_oom in every
integration run (conftest.py:113-265) + the out-of-core strategy set
(SURVEY §2.7 item 5)."""

import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.memory.spill import BufferCatalog
from spark_rapids_tpu.sql.physical import sortlimit as SL
from spark_rapids_tpu.testing import scaletest

ROWS = 120_000

#: every Nth guarded kernel throws a synthetic RetryOOM (spill-all then
#: retry) / SplitAndRetryOOM (halve the input); tight out-of-core targets
#: force the chunked sort/merge paths even between injections
CONF = {
    "spark.rapids.sql.test.injectRetryOOM": 7,
    "spark.rapids.sql.test.injectSplitAndRetryOOM": 11,
    "spark.rapids.sql.sort.outOfCore.targetRows": 4096,
}


@pytest.fixture(scope="module")
def sess():
    yield srt.session(**CONF)
    # later modules must not inherit armed synthetic OOMs
    srt.session()


@pytest.fixture(scope="module")
def rig(sess):
    """Datagen amortized across the module (run_suite's tables/
    extra_tables contract) — the 120k-row sets build once, not per
    query."""
    return {"tables": scaletest.build_tables(ROWS), "extra": {}}


@pytest.mark.parametrize("query", ["tpch_q9_full", "q3_skewed_left_join",
                                   "q5_global_sort"])
def test_scale_query_exercises_out_of_core(sess, rig, query):
    cat = BufferCatalog.get()
    spills_before = cat.spill_count
    ooc_before = SL.STATS["ooc_sorts"]
    # run_suite embeds the pandas oracle: a return IS a verified result
    rep = scaletest.run_suite(ROWS, queries=[query], sess=sess,
                              tables=rig["tables"],
                              extra_tables=rig["extra"])
    assert len(rep) == 1, f"{query} did not run"
    engaged = (cat.spill_count > spills_before
               or SL.STATS["ooc_sorts"] > ooc_before)
    assert engaged, (
        f"{query} exercised neither the spill catalog "
        f"({spills_before} -> {cat.spill_count}) nor the out-of-core "
        f"sort ({ooc_before} -> {SL.STATS['ooc_sorts']})")


def test_spill_catalog_fires(sess, rig):
    """Self-contained spill proof: real bytes move through the catalog's
    DEVICE->HOST demotion path (synchronousSpill analog) during one
    injected-OOM query — independent of which tests ran before."""
    cat = BufferCatalog.get()
    before = cat.spill_count
    scaletest.run_suite(ROWS, queries=["q2_join_agg"], sess=sess,
                        tables=rig["tables"], extra_tables=rig["extra"])
    assert cat.spill_count > before, "injected OOMs caused no spill"
