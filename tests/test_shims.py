"""Shim-axis proof: the jax ShimProvider loads and serves the engine code
end-to-end — the reference's ShimLoader pattern (``ShimLoader.scala:46-76``).

One provider exists (jax >= 0.6, the installed line).  Each case runs two
ways: with the provider RESOLVED by version probing from a cold start, and
with it INJECTED (the seam a future provider's tests will use) — and drives
real engine work through every shimmed entry point: batch pytrees
(tree_map/flatten/unflatten ride every collect via columnar/convert and
collect_fusion) and the mesh ``shard_map`` data plane."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import shims
from spark_rapids_tpu.sql import functions as F


@pytest.fixture(params=["resolved", "injected"])
def forced_shim(request):
    """The one provider, reached by probing or by injection; restored
    afterwards."""
    cls = shims.JaxModernShim
    old = shims._active
    shims._active = None if request.param == "resolved" else cls()
    try:
        yield cls
    finally:
        shims._active = old


def _shard_map_or_skip(provider):
    """The provider's shard_map entry point, or skip — the same
    availability skip tests/test_shuffle.py uses."""
    try:
        return provider.shard_map()
    except (ImportError, AttributeError):
        pytest.skip("this provider's shard_map entry point is "
                    "unavailable in this environment")


def test_provider_probing_matches_versions():
    assert shims.JaxModernShim.matches((0, 6, 0))
    assert shims.JaxModernShim.matches((0, 9, 0))
    assert not shims.JaxModernShim.matches((0, 5, 3))
    assert not shims.JaxModernShim.matches((0, 4, 30))
    # the running jax resolves to exactly one provider
    v = shims._jax_version()
    assert sum(c.matches(v) for c in shims.PROVIDERS) == 1


def test_provider_supplies_working_apis(forced_shim):
    """The provider's four entry points work against the installed jax."""
    s = shims.get_shim()
    assert type(s) is forced_shim
    tree = {"a": np.arange(3), "b": (np.ones(2),)}
    doubled = shims.tree_map(lambda x: x * 2, tree)
    assert doubled["a"][2] == 4 and doubled["b"][0][1] == 2.0
    leaves, treedef = shims.tree_flatten(tree)
    assert len(leaves) == 2
    back = shims.tree_unflatten(treedef, leaves)
    assert np.array_equal(back["a"], tree["a"])
    assert callable(_shard_map_or_skip(s))


def test_engine_query_end_to_end_through_the_provider(forced_shim):
    """A real query (filter + join + agg + sort -> collect) runs through
    the forced provider: batch pytrees traverse tree_flatten/unflatten
    in the packed D2H fetch, tree_map in transitions."""
    sess = srt.session()
    rng = np.random.default_rng(1)
    fact = pa.table({"k": rng.integers(0, 50, 20_000),
                     "v": rng.random(20_000)})
    dim = pa.table({"k": np.arange(50, dtype=np.int64),
                    "w": rng.random(50)})
    f = sess.create_dataframe(fact, num_partitions=3)
    d = sess.create_dataframe(dim, num_partitions=2)
    got = (f.filter(f.v > 0.25).join(d, on="k", how="inner")
           .groupBy("k").agg(F.sum(F.col("v")).alias("sv"),
                             F.count("*").alias("c"))
           .orderBy("k").collect().to_pandas())
    fp, dp = fact.to_pandas(), dim.to_pandas()
    m = fp[fp.v > 0.25].merge(dp, on="k")
    exp = (m.groupby("k").agg(sv=("v", "sum"), c=("v", "size"))
           .sort_index().reset_index())
    assert np.array_equal(got["k"], exp["k"])
    assert np.array_equal(got["c"], exp["c"])
    assert np.allclose(got["sv"], exp["sv"])


def test_mesh_shard_map_through_the_provider(forced_shim):
    """The ICI mesh data plane compiles and runs through the forced
    provider's shard_map on the 8-device virtual mesh."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    from spark_rapids_tpu.parallel.mesh import device_mesh
    from spark_rapids_tpu.shims import get_shim
    from jax.sharding import PartitionSpec as P
    mesh = device_mesh(len(jax.devices()))
    if mesh is None:
        pytest.skip("no mesh available")
    sm = _shard_map_or_skip(get_shim())
    import jax.numpy as jnp

    def body(x):
        return jax.lax.psum(x, "data")

    n = len(jax.devices())
    fn = jax.jit(sm(body, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data")))
    x = jnp.arange(n * 2, dtype=jnp.float32).reshape(n, 2)
    out = np.asarray(fn(x))
    assert np.allclose(out, np.tile(x.sum(axis=0), (n, 1)))
