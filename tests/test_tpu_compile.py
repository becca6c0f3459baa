"""Chip-compiler compiles, without the chip (on-chip-measurement guide §2,
rehearsal 3): the programs of the main path are lowered and compiled for a
DESCRIBED ``v5e:2x2`` topology at real widths, so what the TPU compiler
refuses — and the CPU interpreter accepts — fails here, at no chip time.

Nothing runs: a compile that passes is not a chip run (``chip_smoke.py`` is).

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), and all of these tests live in this ONE file so one
worker holds the library.  The persistent compile cache is off around
them: an AOT TPU entry cannot be read back without a chip.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import spark_rapids_tpu  # noqa: F401  (x64 on: the condition that broke Mosaic)
from spark_rapids_tpu.ops import pallas_kernels as PK


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes, sortless=None):
    """``sortless``: why the program must hold no sort — checked on the
    lowered text BEFORE compiling, because the chip's compiler takes
    minutes per sort program and a test that met one would only hang."""
    lowered = jax.jit(fn).lower(*shapes)
    if sortless:
        assert "stablehlo.sort" not in lowered.as_text(), sortless
    compiled = lowered.compile()
    return compiled, compiled.as_text()


# --------------------------------------------------------------------------
# the two Pallas kernels at real widths
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1 << 20, 1 << 23])
def test_murmur3_kernel_compiles_for_v5e(one_chip, n):
    _, text = _compile(
        lambda v: PK.murmur3_long_pallas(v, np.uint32(42)),
        jax.ShapeDtypeStruct((n,), jnp.int64, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s,out,n", [(1, 8, 1 << 23), (8, 64, 1 << 20),
                                     (3, 256, 1 << 21)])
def test_seg_sum_kernel_compiles_for_v5e(one_chip, s, out, n):
    """(slots, groups, rows) spanning the one-hot-matmul envelope the
    aggregate uses it in (groups <= _MATMUL_MAX_GROUPS)."""
    _, text = _compile(
        lambda v, r: PK.seg_sum_f32_pallas(v, r, out),
        jax.ShapeDtypeStruct((s, n), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("s,out", [(7, 64), (1, 1)])
def test_dense_group_reduce_compiles_for_v5e(one_chip, s, out):
    """The dense form of a float64 group-table sum at the resident cell's
    batch size (Q1: 7 slots, Q6: 1): no scatter left in the optimised
    program — the X64 rewrite turns a float64 scatter-add into a scatter
    over a float32 pair that applies its updates one after another — and
    nothing rows x groups x slots wide among its temporaries."""
    from spark_rapids_tpu.ops import segmented as S
    n = 1 << 21
    compiled, text = _compile(
        lambda v, r: S.seg_sum2(jnp, v, r, out, dense=True),
        jax.ShapeDtypeStruct((n, s), jnp.float64, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    assert "scatter" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("log2", [13, 15, 18, 21])
def test_join_probe_prefix_sum_compiles_for_v5e(one_chip, log2):
    """The probe's running total of matches per row (``ops/join.py``: an
    int64 sum over a probe batch) in the two-level form: it compiles at
    the batch sizes the cells use, and holds no flat int64 scan, whose
    lowering took 20-40 s of every cold process for each probe program
    (PERF.md section 6, PR 33)."""
    from spark_rapids_tpu.ops.ranks import prefix_sum
    n = 1 << log2

    def running_total(hit, lo, hi):
        counts = jnp.where(hit, hi - lo, 0).astype(jnp.int64)
        csum = prefix_sum(jnp, counts)
        return csum, csum[n - 1]

    compiled, text = _compile(
        running_total,
        jax.ShapeDtypeStruct((n,), jnp.bool_, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip))
    windows = re.findall(r"window=\{size=([0-9x]+)", text)
    assert all(int(w.split("x")[-1]) <= 1024 for w in windows), windows


@pytest.mark.parametrize("log2,operands", [(15, 2), (14, 6), (16, 6),
                                           (13, 91), (15, 58)])
def test_small_lex_sort_is_a_rolled_network_for_v5e(one_chip, monkeypatch,
                                                    log2, operands):
    """A join's build sort (a bool and an int32 key at 2^15 rows) and a
    string-keyed sort (six 32-bit words) between 2^14 and 2^16 rows hold
    no ``lax.sort``: one such program took the chip's compiler 20-200 s in
    every cold process (PERF.md section 6, PR 33); the network's loop body
    compiles at once.  So do TPC-DS q98's group ids (2^13 rows x 91 key
    words: a 200-character string among the keys; ``lax.sort`` had not
    compiled after 330 s on the chip's host, PERF.md section 6, PR 35) and
    the sort of its answer (2^15 rows x 58 words)."""
    import time

    from spark_rapids_tpu.ops import ranks
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 1 << log2
    kinds = [jnp.bool_] + [jnp.int32, jnp.uint32] * (operands // 2)
    t0 = time.perf_counter()
    _compile(lambda *k: ranks.lex_sort(jnp, list(k)),
             *[jax.ShapeDtypeStruct((n,), kinds[i], sharding=one_chip)
               for i in range(operands)],
             sortless="a sort of 2^14..2^16 rows is the rolled network")
    assert time.perf_counter() - t0 < 30


# --------------------------------------------------------------------------
# the gate: loud on a TPU, silent nowhere
# --------------------------------------------------------------------------

@pytest.fixture()
def as_if_on_tpu(monkeypatch):
    """Steer the gate's TPU branch on from the test; kernels run in
    interpret mode so the probe can execute on the CPU."""
    monkeypatch.setattr(PK, "on_tpu", lambda: True)
    monkeypatch.setattr(PK, "_PROBE_OK", {})
    real_m, real_s = PK.murmur3_long_pallas, PK.seg_sum_f32_pallas
    monkeypatch.setattr(PK, "murmur3_long_pallas",
                        functools.partial(real_m, interpret=True))
    monkeypatch.setattr(PK, "seg_sum_f32_pallas",
                        functools.partial(real_s, interpret=True))
    return real_m, real_s


def test_probe_is_safe_inside_a_trace(as_if_on_tpu):
    """Both call sites sit inside jitted stages: the probe must compile,
    run and verify for real while an outer trace is being staged."""
    @jax.jit
    def stage(x):
        assert PK.murmur3_available() and PK.seg_sum_available()
        return x + 1

    assert int(stage(jnp.arange(3))[2]) == 3
    assert PK._PROBE_OK == {"murmur3": True, "seg_sum": True}


def test_probe_raises_on_tpu_when_kernel_does_not_compile(
        as_if_on_tpu, monkeypatch):
    real_m, _ = as_if_on_tpu
    # non-interpret Pallas cannot compile on the CPU backend: the stand-in
    # for a kernel Mosaic refuses
    monkeypatch.setattr(PK, "murmur3_long_pallas", real_m)
    with pytest.raises(RuntimeError, match="murmur3.*failed its probe"):
        jax.jit(lambda x: (PK.murmur3_available(), x)[1])(jnp.arange(3))
    assert "murmur3" not in PK._PROBE_OK


def test_probe_raises_on_tpu_when_kernel_answers_wrongly(
        as_if_on_tpu, monkeypatch):
    monkeypatch.setattr(
        PK, "seg_sum_f32_pallas",
        lambda v, r, out: jnp.zeros((v.shape[0], out), jnp.float32))
    with pytest.raises(RuntimeError, match="seg_sum.*wrong answer"):
        PK.seg_sum_available()


def test_probe_answers_false_off_tpu_without_trying(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("the kernel was tried off the TPU")
    monkeypatch.setattr(PK, "murmur3_long_pallas", boom)
    monkeypatch.setattr(PK, "_PROBE_OK", {})
    assert PK.murmur3_available() is False


# --------------------------------------------------------------------------
# the four-device mesh exchange program
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cap,columns", [
    (1 << 12, "small"),
    # a shard of the four-chip cell's LINEITEM exchange: four maps of 2^18
    # rows merged, all 16 columns (tpch-1m-join-q3-mesh4)
    (1 << 20, "lineitem"),
])
def test_mesh_exchange_program_compiles_for_four_chips(topo, cap, columns):
    """The very step ``mesh_shuffle_batches`` jits (the partition pass,
    the all_to_all, the received rows at the front) on a Mesh of the
    described devices.  It holds no sort, so it compiles in seconds at the
    cell's real size too, and its per-device footprint there must fit one
    v5e chip beside the resident tables."""
    from jax.sharding import Mesh
    from spark_rapids_tpu.parallel.mesh import exchange_program
    n_dev = 4
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",))
    sh = NamedSharding(mesh, P("data"))

    def g(shape, dt, rows=cap):
        return jax.ShapeDtypeStruct((n_dev * rows,) + shape, dt, sharding=sh)

    if columns == "small":
        leaves = [g((), jnp.int64), g((), jnp.float64), g((), jnp.bool_)]
    else:
        fixed = [jnp.int64] * 3 + [jnp.int32] + [jnp.float64] * 4 \
            + [jnp.int32] * 7
        leaves = [g((), dt) for dt in fixed] + [g((), jnp.bool_)] * 16 \
            + [g((48,), jnp.uint8), g((), jnp.int32)]
    compiled, text = _compile(
        exchange_program(mesh, n_dev, cap, len(leaves)),
        g((), jnp.int32, rows=1), g((), jnp.int32), *leaves,
        sortless="the pack is a partition pass, not a sort")
    assert "all-to-all" in text
    # per-device footprint must fit one v5e chip with room to spare
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes + ma.output_size_in_bytes < 8 << 30


# --------------------------------------------------------------------------
# the q1 aggregate program, non-CPU branches steered on
# --------------------------------------------------------------------------

def test_q1_aggregate_programs_compile_for_v5e(one_chip, monkeypatch):
    """TPC-H q1 through the session on the CPU at a small size, recording
    every aggregate program the kernel cache builds; each is then lowered
    again for the v5e with ``jax.default_backend()`` answering "tpu" — so
    the batched reduce, the dense group-table reductions, the
    one-hot-matmul aggregate and the Pallas ``seg_sum`` call (branches no
    CPU test executes) are what compiles.  None of them may hold a scatter
    over a float32 pair: the X64 form of a float64 scatter-add, which the
    chip applies one update after another (PERF.md §6 PR 30)."""
    import spark_rapids_tpu as srt
    from spark_rapids_tpu.sql.physical import kernel_cache as KC
    from spark_rapids_tpu.testing import scaletest as ST

    recorded = []
    real_cached_jit = KC.cached_jit

    def recording_cached_jit(key, fn, donate_argnums=None):
        inner = real_cached_jit(key, fn, donate_argnums=donate_argnums)
        if key[0] != "HashAggregateExec":
            return inner

        def call(*args):
            recorded.append((fn, args))
            return inner(*args)
        return call

    KC.clear_cache()
    monkeypatch.setattr(KC, "cached_jit", recording_cached_jit)
    lineitem = ST.build_tpch_tables(20_000)["lineitem"]
    # a session of its own: a bare ``srt.session()`` hands back whatever
    # the module before left active, and with encoding off q1's string
    # keys take the sort fallback, which the chip's compiler takes many
    # minutes over
    sess = srt.session(**{"spark.rapids.tpu.sql.encoded.enabled": True})
    sess.create_dataframe(lineitem, num_partitions=1) \
        .createOrReplaceTempView("lineitem")
    got = sess.sql(ST._TPCH_Q1_SQL).collect().to_pandas()
    ST._q1_oracle_check(got, lineitem)
    monkeypatch.undo()
    KC.clear_cache()
    assert recorded, "q1 built no aggregate program"

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PK, "_PROBE_OK", {"murmur3": True, "seg_sum": True})

    def abstract(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
        return x

    seen, pallas_calls = set(), 0
    for fn, args in recorded:
        shapes = jax.tree_util.tree_map(abstract, args)
        sig = (id(fn), str(jax.tree_util.tree_structure(shapes)),
               tuple((s.shape, str(s.dtype))
                     for s in jax.tree_util.tree_leaves(shapes)))
        if sig in seen:
            continue
        seen.add(sig)
        # a fresh wrapper: jit caches traces by function identity, and
        # the CPU run already traced ``fn`` down its CPU branches
        _, text = _compile(
            lambda *a, fn=fn: fn(*a), *shapes, sortless="q1's dictionary "
            "keys are statically compact: no sort fallback")
        pallas_calls += text.count("tpu_custom_call")
        pair_scatters = re.findall(
            r"= \(f32\[[^=]*\) scatter\(", text)
        assert not pair_scatters, pair_scatters
    assert pallas_calls > 0, "no aggregate program called the Pallas seg_sum"


# --------------------------------------------------------------------------
# the local exchange's three programs, non-CPU branches steered on
# --------------------------------------------------------------------------

def test_local_exchange_programs_compile_for_v5e(one_chip, monkeypatch):
    """A 4-map x 8-target hash exchange at Q3's map size (2^18-row
    batches: int64 key, double, a dict-encoded string) through the exec
    on the CPU, recording the map, shrink and concat programs the kernel
    cache builds; each is then lowered for the v5e with the backend
    answering "tpu", so the Pallas murmur3 call sits INSIDE the map
    program, as it does on the chip."""
    import pyarrow as pa

    from spark_rapids_tpu.columnar.convert import arrow_to_device
    from spark_rapids_tpu.parallel.partitioning import HashPartitioning
    from spark_rapids_tpu.sql.expressions.core import AttributeReference
    from spark_rapids_tpu.sql.physical import exchange as X
    from spark_rapids_tpu.sql.physical import kernel_cache as KC
    from spark_rapids_tpu.sql.physical.base import (TPU, PhysicalPlan,
                                                    TaskContext)

    n, nt = 200_000, 8
    rng = np.random.default_rng(0)
    flags = np.array(["A", "N", "R"])
    parts = [[arrow_to_device(pa.table({
        "k": rng.integers(0, 1 << 40, n),
        "v": rng.random(n),
        "s": pa.array(flags[rng.integers(0, 3, n)])}))] for _ in range(4)]
    b0 = parts[0][0]
    assert b0.capacity == 1 << 18
    attrs = [AttributeReference(name, c.dtype, True)
             for name, c in zip(b0.names, b0.columns)]

    class Leaf(PhysicalPlan):
        backend = TPU
        output = attrs

        def num_partitions(self):
            return len(parts)

        def execute(self, pid, tctx):
            yield from parts[pid]

    recorded = []
    real_cached_jit = KC.cached_jit

    def recording_cached_jit(key, fn, donate_argnums=None):
        inner = real_cached_jit(key, fn, donate_argnums=donate_argnums)
        if key[1] not in ("map", "shrink", "concat"):
            return inner

        def call(*args):
            recorded.append((key[1], fn, args))
            return inner(*args)
        return call

    KC.clear_cache()
    monkeypatch.setattr(KC, "cached_jit", recording_cached_jit)
    ex = X.ShuffleExchangeExec(HashPartitioning([attrs[0]], nt), Leaf(),
                               coalescible=False)
    rows = sum(b.num_rows_int for t in range(nt)
               for b in ex.execute(t, TaskContext(0)))
    monkeypatch.undo()
    KC.clear_cache()
    assert rows == 4 * n
    assert {what for what, _, _ in recorded} == {"map", "shrink", "concat"}

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(PK, "_PROBE_OK", {"murmur3": True, "seg_sum": True})

    def abstract(x):
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                        sharding=one_chip)
        return x

    seen = set()
    for what, fn, args in recorded:
        shapes = jax.tree_util.tree_map(abstract, args)
        sig = (what, str(jax.tree_util.tree_structure(shapes)),
               tuple((s.shape, str(s.dtype))
                     for s in jax.tree_util.tree_leaves(shapes)))
        if sig in seen:
            continue
        seen.add(sig)
        compiled, text = _compile(
            lambda *a, fn=fn: fn(*a), *shapes,
            sortless="the local exchange orders rows by a partition pass")
        if what == "map":
            assert text.count("tpu_custom_call") == 1
            # one pass: the output is the input re-ordered, not a copy
            # per target
            ma = compiled.memory_analysis()
            assert ma.output_size_in_bytes < 2 * ma.argument_size_in_bytes
        if what == "concat":
            assert "dynamic-update-slice" in text and " gather(" not in text
