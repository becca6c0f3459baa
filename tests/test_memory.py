"""Memory runtime tests — spill tiers, retry framework, semaphore, task
completion (reference suites: RapidsDiskStoreSuite, RapidsHostMemoryStoreSuite,
WithRetrySuite, GpuSortRetrySuite; SURVEY §4 tier 2)."""
import os

import threading
import time

import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import make_fixed_column
from spark_rapids_tpu.config import (HOST_SPILL_STORAGE_SIZE, RapidsConf,
                                     SPILL_DIR, TEST_INJECT_RETRY_OOM,
                                     TEST_INJECT_SPLIT_OOM)
from spark_rapids_tpu.memory import (BufferCatalog, DeviceManager, RetryOOM,
                                     ScalableTaskCompletion,
                                     SpillableColumnarBatch,
                                     SplitAndRetryOOM, TpuSemaphore,
                                     arm_oom_injection, batch_device_bytes,
                                     split_spillable_in_half, with_retry,
                                     with_retry_no_split)


def make_batch(n=100, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    from spark_rapids_tpu.columnar.column import bucket_capacity
    cap = bucket_capacity(n)
    a = np.zeros(cap, dtype=np.int64)
    a[:n] = rng.integers(0, 1000, n)
    b = np.zeros(cap, dtype=np.float64)
    b[:n] = rng.random(n)
    cols = (make_fixed_column(T.LONG, jnp.asarray(a)),
            make_fixed_column(T.DOUBLE, jnp.asarray(b)))
    return ColumnarBatch.make(("a", "b"), cols, n)


def batches_equal(x: ColumnarBatch, y: ColumnarBatch) -> bool:
    if x.num_rows_int != y.num_rows_int:
        return False
    n = x.num_rows_int
    for cx, cy in zip(x.columns, y.columns):
        if not np.array_equal(np.asarray(cx.data)[:n], np.asarray(cy.data)[:n]):
            return False
        if not np.array_equal(np.asarray(cx.validity)[:n],
                              np.asarray(cy.validity)[:n]):
            return False
    return True


@pytest.fixture()
def catalog(tmp_path):
    conf = RapidsConf({SPILL_DIR.key: str(tmp_path)})
    cat = BufferCatalog.reset(conf)
    yield cat
    cat.close_all()
    BufferCatalog.reset()


class TestSpillFramework:
    def test_roundtrip_device(self, catalog):
        b = make_batch(50)
        h = catalog.add_batch(b)
        assert catalog.tier_of(h) == "device"
        assert batches_equal(catalog.get_batch(h), b)
        catalog.remove(h)
        assert catalog.device_bytes == 0

    def test_spill_to_host_and_unspill(self, catalog):
        b = make_batch(200)
        h = catalog.add_batch(b)
        spilled = catalog.synchronous_spill(0)
        assert spilled > 0
        assert catalog.tier_of(h) == "host"
        assert catalog.device_bytes == 0
        got = catalog.get_batch(h)           # unspill back to device
        assert catalog.tier_of(h) == "device"
        assert batches_equal(got, b)
        assert catalog.unspill_count >= 1

    def test_host_overflow_to_disk(self, tmp_path):
        conf = RapidsConf({SPILL_DIR.key: str(tmp_path),
                           HOST_SPILL_STORAGE_SIZE.key: 1})  # 1 byte budget
        cat = BufferCatalog.reset(conf)
        try:
            b = make_batch(500)
            h = cat.add_batch(b)
            cat.synchronous_spill(0)
            assert cat.tier_of(h) == "disk"
            assert cat.disk_bytes > 0
            assert batches_equal(cat.get_batch(h), b)  # disk -> host -> device
            assert cat.tier_of(h) == "device"
        finally:
            cat.close_all()
            BufferCatalog.reset()

    def test_spill_priority_order(self, catalog):
        from spark_rapids_tpu.memory import (ACTIVE_ON_DECK_PRIORITY,
                                             OUTPUT_FOR_SHUFFLE_PRIORITY)
        hi = catalog.add_batch(make_batch(50, 1), ACTIVE_ON_DECK_PRIORITY)
        lo = catalog.add_batch(make_batch(50, 2), OUTPUT_FOR_SHUFFLE_PRIORITY)
        # spill just enough for one buffer: the low-priority one must go
        one = batch_device_bytes(make_batch(50, 2))
        catalog.synchronous_spill(catalog.device_bytes - one)
        assert catalog.tier_of(lo) == "host"
        assert catalog.tier_of(hi) == "device"

    def test_ensure_headroom_spills(self, tmp_path):
        conf = RapidsConf({SPILL_DIR.key: str(tmp_path)})
        cat = BufferCatalog.reset(conf)
        b = make_batch(100)
        size = batch_device_bytes(b)
        DeviceManager.initialize(pool_limit_override=int(size * 1.5))
        try:
            h1 = cat.add_batch(make_batch(100, 1))
            assert cat.ensure_headroom(size)      # must evict h1
            assert cat.tier_of(h1) == "host"
        finally:
            DeviceManager.shutdown()
            cat.close_all()
            BufferCatalog.reset()

    def test_spillable_batch_wrapper(self, catalog):
        b = make_batch(77)
        sb = SpillableColumnarBatch.create(b, catalog=catalog)
        assert sb.num_rows == 77
        catalog.synchronous_spill(0)
        assert batches_equal(sb.get(), b)
        sb.close()
        with pytest.raises(ValueError):
            sb.get()


class TestRetryFramework:
    def test_retry_oom_recovers(self, catalog):
        b = make_batch(64)
        sb = SpillableColumnarBatch.create(b, catalog=catalog)
        calls = {"n": 0}

        def fn(s):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RetryOOM("synthetic")
            return s.get().num_rows_int

        assert with_retry_no_split(sb, fn, catalog=catalog) == 64
        assert calls["n"] == 3

    def test_split_and_retry(self, catalog):
        b = make_batch(64)
        sb = SpillableColumnarBatch.create(b, catalog=catalog)
        failed = {"first": True}

        def fn(s):
            if failed["first"]:
                failed["first"] = False
                raise SplitAndRetryOOM("synthetic")
            return s.get().num_rows_int

        out = list(with_retry([sb], fn, split=split_spillable_in_half,
                              catalog=catalog))
        assert out == [32, 32]

    def test_split_below_one_row_raises(self, catalog):
        sb = SpillableColumnarBatch.create(make_batch(1), catalog=catalog)
        with pytest.raises(SplitAndRetryOOM):
            split_spillable_in_half(sb)

    def test_injection_armed(self, catalog):
        arm_oom_injection(retry=1)
        sb = SpillableColumnarBatch.create(make_batch(10), catalog=catalog)
        calls = {"n": 0}

        def fn(s):
            calls["n"] += 1
            return s.num_rows

        assert with_retry_no_split(sb, fn, catalog=catalog) == 10
        assert calls["n"] == 1  # injection throws before fn on attempt 1

    def test_query_correct_under_oom_injection(self):
        """End-to-end: inject RetryOOM + SplitAndRetryOOM into an aggregate
        query and require identical results (integration-test inject_oom
        marker behavior)."""
        data = {"k": np.arange(1000) % 7, "v": np.arange(1000, dtype=np.float64)}
        from spark_rapids_tpu.sql import functions as F
        s = srt.session()
        df = s.create_dataframe(data)
        expected = df.groupBy("k").agg(F.sum("v").alias("s")) \
                     .orderBy("k").collect()
        conf = RapidsConf({TEST_INJECT_RETRY_OOM.key: 1,
                           TEST_INJECT_SPLIT_OOM.key: 1})
        s2 = srt.session(conf=conf)
        df2 = s2.create_dataframe(data)
        got = df2.groupBy("k").agg(F.sum("v").alias("s")) \
                 .orderBy("k").collect()
        assert got.equals(expected)


class TestSemaphore:
    def test_limits_concurrency(self):
        sem = TpuSemaphore(2)
        active, peak = [0], [0]
        lock = threading.Lock()

        def task(tid):
            sem.acquire_if_necessary(tid)
            with lock:
                active[0] += 1
                peak[0] = max(peak[0], active[0])
            time.sleep(0.02)
            with lock:
                active[0] -= 1
            sem.release_if_necessary(tid)

        threads = [threading.Thread(target=task, args=(i,)) for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert peak[0] <= 2
        assert sem.active_tasks() == 0

    def test_reentrant_per_task(self):
        sem = TpuSemaphore(1)
        sem.acquire_if_necessary(7)
        sem.acquire_if_necessary(7)   # no deadlock: deduped
        assert sem.holds(7)
        sem.release_if_necessary(7)
        assert sem.holds(7)           # still held (depth 2)
        sem.release_if_necessary(7)
        assert not sem.holds(7)


class TestTaskCompletion:
    def test_dedup_and_fire(self):
        stc = ScalableTaskCompletion()
        fired = []
        owner = object()
        assert stc.on_task_completion(1, owner, lambda: fired.append("a"))
        assert not stc.on_task_completion(1, owner, lambda: fired.append("b"))
        assert stc.on_task_completion(1, object(), lambda: fired.append("c"))
        stc.task_completed(1)
        assert fired == ["a", "c"]
        assert stc.pending(1) == 0


class TestRealAllocatorHookup:
    """DeviceMemoryEventHandler analog: real allocation failure -> spill ->
    retry -> split."""

    def test_add_batch_raises_split_when_batch_exceeds_pool(self, tmp_path):
        from spark_rapids_tpu.config import SPILL_DIR, RapidsConf
        from spark_rapids_tpu.memory.device import DeviceManager
        from spark_rapids_tpu.memory.retry import SplitAndRetryOOM
        conf = RapidsConf({SPILL_DIR.key: str(tmp_path)})
        cat = BufferCatalog.reset(conf)
        b = make_batch(100)
        size = batch_device_bytes(b)
        DeviceManager.initialize(pool_limit_override=size // 2)
        try:
            with pytest.raises(SplitAndRetryOOM):
                cat.add_batch(b)
        finally:
            DeviceManager.shutdown()
            cat.close_all()
            BufferCatalog.reset()

    def test_oversized_input_survives_via_retry_split(self, tmp_path):
        """with_retry + split halves a batch that cannot fit the pool."""
        from spark_rapids_tpu.config import SPILL_DIR, RapidsConf
        from spark_rapids_tpu.memory.device import DeviceManager
        from spark_rapids_tpu.memory.retry import (split_spillable_in_half,
                                                   with_retry)
        conf = RapidsConf({SPILL_DIR.key: str(tmp_path)})
        cat = BufferCatalog.reset(conf)
        big = make_batch(400)
        DeviceManager.initialize(
            pool_limit_override=batch_device_bytes(big) * 4)
        try:
            sb = SpillableColumnarBatch.create(big, catalog=cat)
            seen_rows = []

            def consume(s):
                got = s.get()
                # registering a copy simulates an op output that must fit
                h = cat.add_batch(got)
                cat.remove(h)
                seen_rows.append(got.num_rows_int)
                return got.num_rows_int

            # shrink the pool below ONE whole batch so the copy can only
            # ever fit after the input is split in half
            DeviceManager.initialize(
                pool_limit_override=int(batch_device_bytes(big) * 0.9))
            total = sum(with_retry([sb], consume, split_spillable_in_half))
            assert total == 400
            assert len(seen_rows) >= 2  # was split at least once
        finally:
            DeviceManager.shutdown()
            cat.close_all()
            BufferCatalog.reset()

    def test_device_oom_guard_spills_and_retries(self):
        from spark_rapids_tpu.memory import oom_guard as G

        class XlaRuntimeError(Exception):
            pass

        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory "
                                      "allocating 1048576 bytes")
            return 42

        before = G.STATS["oom_retry_ok"]
        assert G.guard_device_oom(flaky)() == 42
        assert calls["n"] == 2
        assert G.STATS["oom_retry_ok"] == before + 1

    def test_device_oom_guard_escalates_to_split(self):
        from spark_rapids_tpu.memory import oom_guard as G
        from spark_rapids_tpu.memory.retry import SplitAndRetryOOM

        class XlaRuntimeError(Exception):
            pass

        def always_oom():
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: Out of memory")

        with pytest.raises(SplitAndRetryOOM):
            G.guard_device_oom(always_oom)()

    def test_guard_passes_through_other_errors(self):
        from spark_rapids_tpu.memory import oom_guard as G

        def boom():
            raise ValueError("not an oom")

        with pytest.raises(ValueError):
            G.guard_device_oom(boom)()


class TestFatalDeviceErrors:
    """GpuCoreDumpHandler analog: fatal XlaRuntimeErrors capture a
    diagnostics bundle and surface as FatalDeviceError (never entering
    the OOM spill/retry protocol)."""

    def _fake_xla_error(self, msg):
        XlaRuntimeError = type("XlaRuntimeError", (RuntimeError,), {})
        return XlaRuntimeError(msg)

    def test_fatal_classification(self):
        from spark_rapids_tpu.memory.fatal import is_fatal_device_error
        assert is_fatal_device_error(self._fake_xla_error("INTERNAL: boom"))
        assert not is_fatal_device_error(
            self._fake_xla_error("RESOURCE_EXHAUSTED: out of memory"))
        assert not is_fatal_device_error(ValueError("x"))

    def test_guard_raises_fatal_with_dump(self, tmp_path):
        import spark_rapids_tpu as srt
        from spark_rapids_tpu.memory.fatal import FatalDeviceError
        from spark_rapids_tpu.memory.oom_guard import guard_device_oom
        s = srt.session(**{"spark.rapids.tpu.fatalDump.path": str(tmp_path)})
        try:
            err = self._fake_xla_error("INTERNAL: compilation blew up")

            def kernel():
                raise err
            from spark_rapids_tpu.sql.physical.base import TaskContext
            with pytest.raises(FatalDeviceError) as ei, \
                    TaskContext(0, s._conf).as_current():
                guard_device_oom(kernel)()
            assert ei.value.dump_path and os.path.exists(ei.value.dump_path)
            body = open(ei.value.dump_path).read()
            assert "compilation blew up" in body
            assert "spill catalog" in body
        finally:
            srt.session(**{"spark.rapids.sql.enabled": True})

    def test_oom_still_routes_to_retry_protocol(self):
        from spark_rapids_tpu.memory import fatal as FT
        from spark_rapids_tpu.memory.oom_guard import guard_device_oom
        from spark_rapids_tpu.memory.retry import SplitAndRetryOOM
        before = FT.STATS["fatal_errors"]
        err = self._fake_xla_error("RESOURCE_EXHAUSTED: out of memory")

        def kernel():
            raise err
        with pytest.raises(SplitAndRetryOOM):
            guard_device_oom(kernel)()
        assert FT.STATS["fatal_errors"] == before  # not classified fatal


class TestLeakDetection:
    """Spill-catalog leak tracking (MemoryCleaner analog): queries must
    leave no registered buffers behind, and debug mode names the site."""

    def test_queries_leak_no_buffers(self):
        import pyarrow as pa
        from spark_rapids_tpu.memory.spill import BufferCatalog
        import spark_rapids_tpu as srt
        from spark_rapids_tpu.sql import functions as F
        BufferCatalog.reset()
        s = srt.session()
        df = s.create_dataframe(pa.table({
            "k": list(range(100)), "v": [float(i) for i in range(100)]}),
            num_partitions=4)
        (df.filter(df.v > 10).groupBy("k")
         .agg(F.sum(F.col("v")).alias("s")).orderBy("k").collect())
        leaks = BufferCatalog.get().leak_report()
        assert leaks == [], leaks

    def test_debug_mode_records_origin(self):
        import numpy as np
        from spark_rapids_tpu.columnar.batch import ColumnarBatch
        from spark_rapids_tpu.columnar.column import make_fixed_column
        from spark_rapids_tpu.memory.spill import (BufferCatalog,
                                                   SpillableColumnarBatch)
        import spark_rapids_tpu as srt
        try:
            s = srt.session(**{"spark.rapids.memory.gpu.debug": True})
            cat = BufferCatalog.reset(s._conf)
            col = make_fixed_column(T.LONG, np.arange(8))
            b = ColumnarBatch.make(("x",), (col,), 8)
            sb = SpillableColumnarBatch.create(b, catalog=cat)
            rep = cat.leak_report()
            assert len(rep) == 1
            assert "test_memory" in rep[0]["origin"]
            sb.close()
            assert cat.leak_report() == []
        finally:
            srt.session(**{"spark.rapids.sql.enabled": True})
            BufferCatalog.reset()


class TestConfRegistry:
    """Every registered key is read by the engine (ISSUE 31): a key that
    is documented and read by nothing is a promise the docs make and the
    program does not keep."""

    @staticmethod
    def orphans(config_source=None):
        """Registered entries that no module of the package other than
        config.py and docgen.py references, by entry name or key string.
        A use inside config.py counts only through what carries it out
        (a RapidsConf accessor, a module-level table) when the package
        references that carrier."""
        import ast
        import pathlib
        import re
        from spark_rapids_tpu import config
        root = pathlib.Path(config.__file__).parent
        package = "\n".join(
            p.read_text() for p in sorted(root.rglob("*.py"))
            if p.relative_to(root).as_posix() not in ("config.py",
                                                      "docgen.py"))
        tree = ast.parse(config_source
                         or (root / "config.py").read_text())
        entries = {}    # entry name -> key string
        carriers = {}   # entry name -> names that use it inside config.py
        for top in tree.body:
            call = getattr(top, "value", None)
            if (isinstance(top, ast.Assign) and isinstance(call, ast.Call)
                    and getattr(call.func, "id", "") == "register"):
                entries[top.targets[0].id] = call.args[0].value
                continue
            scopes = top.body if isinstance(top, ast.ClassDef) else [top]
            for scope in scopes:
                name = getattr(scope, "name", None) or next(
                    (t.id for t in getattr(scope, "targets", [])
                     if isinstance(t, ast.Name)), None)
                for node in ast.walk(scope):
                    if isinstance(node, ast.Name) and name:
                        carriers.setdefault(node.id, set()).add(name)

        def used(word):
            return re.search(rf"\b{re.escape(word)}\b", package) is not None

        return sorted(
            key for name, key in entries.items()
            if key not in package and not used(name)
            and not any(used(c) for c in carriers.get(name, ())))

    def test_every_registered_key_is_read_by_the_engine(self):
        orphans = self.orphans()
        assert not orphans, (
            "registered in config.py, documented, and read by nothing "
            "in spark_rapids_tpu/: " + ", ".join(orphans))

    def test_guard_sees_a_dead_key(self):
        import pathlib
        from spark_rapids_tpu import config
        source = pathlib.Path(config.__file__).read_text()
        dead = source + (
            '\nFORMAT_NOTHING_ENABLED = register(\n'
            '    "spark.rapids.sql.format.nothing.enabled", "Dead.", True)\n')
        assert self.orphans(dead) == ["spark.rapids.sql.format.nothing.enabled"]

    def test_unknown_key_is_kept_verbatim(self):
        # a job that still sets a deleted key behaves as before: nothing
        # read it then either
        conf = RapidsConf({"spark.rapids.sql.format.parquet.enabled": False})
        assert conf.get("spark.rapids.sql.format.parquet.enabled") is False
        assert srt.session(**{"spark.rapids.sql.hasNans": False}) is not None
