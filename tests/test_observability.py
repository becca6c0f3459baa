"""Tracing, metric levels, failure dumps, docgen, and the version-shim
provider system (reference §5 aux subsystems + §2.11)."""

import os

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu.sql import functions as F


def _q(sess, n=5000):
    rng = np.random.default_rng(2)
    t = pa.table({"k": rng.integers(0, 20, n), "v": rng.random(n)})
    df = sess.create_dataframe(t, num_partitions=2)
    return df.groupBy("k").agg(F.sum(df.v).alias("s")).orderBy("k")


def test_query_metrics_collected():
    sess = srt.session()
    _q(sess).collect()
    m = sess.last_query_metrics
    assert m, "no metrics collected"
    assert any(k.startswith("d2h") or k.startswith("h2d")
               or "Batches" in k for k in m), m


def test_metrics_level_essential_drops_moderate():
    sess = srt.session(**{"spark.rapids.sql.metrics.level": "ESSENTIAL"})
    _q(sess).collect()
    moderate = sess.last_query_metrics
    # the default metrics are tagged MODERATE; ESSENTIAL drops them
    assert all(not k.startswith(("h2d", "d2h")) for k in moderate), moderate


def test_trace_annotation_smoke():
    """trace.enabled must execute the TraceAnnotation path end-to-end
    (the flag was dead in round 1)."""
    sess = srt.session(**{"spark.rapids.tpu.trace.enabled": True})
    out = _q(sess).collect()
    assert out.num_rows == 20


def test_dump_on_error(tmp_path):
    sess = srt.session(**{"spark.rapids.sql.debug.dumpPath": str(tmp_path)})
    t = pa.table({"a": [1.0, 2.0]})
    df = sess.create_dataframe(t)
    f = F.udf(lambda a: {}[a], returnType=srt.DOUBLE)  # raises KeyError
    with pytest.raises(KeyError):
        df.select(f(df.a).alias("r")).collect()
    dumps = list(tmp_path.iterdir())
    assert dumps, "no failure dump written"
    assert any((d / "error.txt").exists() for d in dumps)


def test_docgen_writes_files(tmp_path):
    from spark_rapids_tpu.docgen import generate
    written = generate(str(tmp_path))
    assert len(written) == 5
    cfg = (tmp_path / "docs" / "configs.md").read_text()
    assert "spark.rapids.sql.batchSizeRows" in cfg
    ops = (tmp_path / "docs" / "supported_ops.md").read_text()
    assert "ShuffleExchangeExec" in ops and "RegExpReplace" in ops
    csv = (tmp_path / "tools" / "generated_files"
           / "supportedExprs.csv").read_text()
    assert csv.count("\n") > 150  # expression breadth


def test_shim_provider_selection():
    import jax
    from spark_rapids_tpu import shims
    shim = shims.get_shim()
    assert shim.matches(shims._jax_version())
    # the shimmed APIs are callable and functional.  shard_map uses the
    # same availability skip as tests/test_shuffle.py: some environments'
    # jax exposes no shard_map entry point at all, and tier-1 must be
    # green-or-skip there.
    try:
        sm = shim.shard_map()
    except (ImportError, AttributeError):
        pytest.skip("shard_map unavailable in this environment")
    assert callable(sm)
    tm = shim.tree_map()
    assert tm(lambda x: x + 1, {"a": 1}) == {"a": 2}
    leaves, treedef = shim.tree_flatten()({"a": 1, "b": 2})
    assert shim.tree_unflatten()(treedef, leaves) == {"a": 1, "b": 2}


def test_shim_version_ranges():
    from spark_rapids_tpu.shims import JaxModernShim
    assert JaxModernShim.matches((0, 6, 0))
    assert JaxModernShim.matches((0, 7, 1))
    assert not JaxModernShim.matches((0, 5, 9))


def test_api_validation_contract_clean():
    """api_validation analog (reference ApiValidation.scala): the current
    build satisfies its recorded exec/expression contract and the running
    jax exposes every entry point the shims lean on."""
    import sys, os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    import api_validation as av
    problems = av.check()
    assert problems == [], problems


def test_per_rule_enable_flags():
    """Per-expression and per-exec enable flags force host placement
    (reference: auto-generated conf per GpuOverrides rule)."""
    import pyarrow as pa
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    try:
        s = srt.session(**{"spark.rapids.sql.expression.Upper": False})
        df = s.create_dataframe(pa.table({"s": ["ab"]}))
        q = df.select(F.upper(df.s).alias("u"))
        assert "disabled" in s.explain(q)
        assert q.collect()["u"].to_pylist() == ["AB"]  # host still answers
        s2 = srt.session(**{"spark.rapids.sql.exec.ProjectExec": False})
        df2 = s2.create_dataframe(pa.table({"x": [1]}))
        assert "disabled" in s2.explain(df2.select((df2.x + 1).alias("y")))
    finally:
        srt.session(**{"spark.rapids.sql.enabled": True})


def test_collect_aggs_planned_on_device():
    import pyarrow as pa
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    s = srt.session()
    df = s.create_dataframe(pa.table({"k": [1], "v": [1.0]}))
    ex = s.explain(df.groupBy("k").agg(F.collect_list(df.v).alias("l")))
    assert "TpuHashAggregate" in ex


def test_query_profile_report(session):
    import numpy as np
    import pyarrow as pa

    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql import functions as F
    sess = srt.session(**{"spark.rapids.tpu.profile.enabled": True})
    rng = np.random.default_rng(0)
    df = sess.create_dataframe(pa.table({"k": rng.integers(0, 5, 10_000),
                                         "v": rng.random(10_000)}))
    q = df.filter(df.v > 0.5).groupBy("k").agg(F.sum(df.v).alias("s"))
    q.collect()
    report = sess.profile_last_query()
    lines = report.splitlines()
    assert "incl_ms" in lines[0] and "batches" in lines[0]
    assert len(lines) >= 3  # at least a sink + a scan
    assert "Scan" in report
    # profiling off -> no accounting overhead path
    sess2 = srt.session()
    df2 = sess2.create_dataframe(pa.table({"a": [1, 2]}))
    df2.collect()
    assert "exec" in sess2.profile_last_query()


def test_public_assert_framework(session):
    import numpy as np
    import pyarrow as pa

    from spark_rapids_tpu.testing import (
        assert_equal_with_pandas, assert_tpu_and_cpu_are_equal_collect)
    from spark_rapids_tpu.sql import functions as F
    rng = np.random.default_rng(1)
    t = pa.table({"k": rng.integers(0, 4, 500), "v": rng.random(500)})
    df = session.create_dataframe(t)
    q = df.groupBy("k").agg(F.sum(df.v).alias("s"))
    assert_tpu_and_cpu_are_equal_collect(q, sort_by=["k"])
    exp = (t.to_pandas().groupby("k").agg(s=("v", "sum")).reset_index())
    assert_equal_with_pandas(q, exp, sort_by=["k"], rtol=1e-6)


def test_fallback_assert(session):
    import pyarrow as pa

    from spark_rapids_tpu.sql import functions as F
    from spark_rapids_tpu.testing import assert_tpu_fallback_collect
    df = session.create_dataframe(pa.table({"a": [2, 3]}))
    # sequence is documented host-only -> its Generate falls back
    q = df.select(F.explode(F.sequence(F.lit(1), df.a)).alias("x"))
    out = assert_tpu_fallback_collect(q, "Generate")
    assert out.num_rows == 5
