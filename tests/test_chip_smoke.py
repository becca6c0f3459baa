"""``chip_smoke.py``'s phases, run in-process on the CPU at a tiny size:
every phase ran, every oracle passed, nothing was placed off the device
backend — and the script refuses to report success off a TPU.

The chip run itself goes through the chip tool; this keeps the script's
control flow, arguments and oracles working between chip runs."""

import contextlib
import importlib.util
import io
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS = 4000


def _load():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(argv):
    """(exit code, parsed stdout records, raw stdout lines)."""
    mod = _load()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = mod.main(argv)
    lines = [ln for ln in out.getvalue().splitlines() if ln.strip()]
    return rc, [json.loads(ln) for ln in lines], lines


@pytest.fixture(scope="module", autouse=True)
def _fresh_default_session():
    """The four-chip phase makes sessions with forced-shuffle confs and
    spreads partitions over four devices; the next module's bare
    ``srt.session()`` must inherit neither."""
    yield
    from spark_rapids_tpu.memory.device import DeviceManager
    from spark_rapids_tpu.sql.physical import kernel_cache
    from spark_rapids_tpu.sql.session import TpuSession
    TpuSession._active = None
    DeviceManager.shutdown()
    kernel_cache.share_executables(())


@pytest.fixture(scope="module")
def smoke_run(tmp_path_factory):
    """One run of the one-chip phases with every query the issue names,
    shared by the asserts."""
    out_dir = str(tmp_path_factory.mktemp("smoke"))
    return _run(["--rows", str(ROWS), "--out", out_dir,
                 "--queries", ",".join(_load().SUITE_QUERIES)])


def test_every_phase_runs_and_every_oracle_passes(smoke_run):
    _rc, recs, _ = smoke_run
    mod = _load()
    summary = [r for r in recs if r.get("phase") == "summary"][-1]
    ran = set(summary["phases_run"])
    want = {"device", "kernels", "datagen", "scan_leg",
            "scan_leg:tpch_q1_parquet", "scan_leg:tpch_q6_parquet",
            "placement:device_batch", "query_leg", "counters"}
    want |= {f"query_leg:{q}" for q in mod.SUITE_QUERIES}
    assert want <= ran, want - ran
    # the ONLY failure on the CPU is the platform check
    assert [f["phase"] for f in summary["failures"]] == ["device"]
    assert "not 'tpu'" in summary["failures"][0]["error"]
    checked = {r["query"]: r for r in recs if r.get("oracle") == "pass"}
    assert set(checked) == {"tpch_q1_parquet", "tpch_q6_parquet",
                            *mod.SUITE_QUERIES}
    for rec in checked.values():
        assert rec["rows"] == ROWS and rec["not_on_tpu"] == []


def test_scan_leg_reads_parquet_through_the_device_decoder(smoke_run):
    _rc, recs, _ = smoke_run
    for q in ("tpch_q1_parquet", "tpch_q6_parquet"):
        (rec,) = [r for r in recs if r.get("query") == q]
        assert rec["parquetDecodeFilesEngaged"] >= 1
        assert rec["parquetDecodeFilesDeclined"] == 0
        assert rec["compiled_in_first"]["programs"] > 0


def test_device_record_names_versions_cache_and_native_libraries(smoke_run):
    _rc, recs, _ = smoke_run
    dev = [r for r in recs if r.get("phase") == "device"][0]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert dev["versions"]["jax"] and dev["versions"]["jaxlib"]
    # JAX_PLATFORMS=cpu: the package leaves the persistent cache off
    assert dev["compile_cache_dir"] is None
    libs = dev["native_libraries"]
    assert set(libs) == {"libsrt_native.so", "libsrt_transport.so"}
    for lib in libs.values():
        assert lib["error"] is None
        assert os.path.basename(os.path.dirname(lib["path"])) == "native"
    (k,) = [r for r in recs if r.get("phase") == "kernels"]
    assert k["murmur3_available"] is False      # off the TPU: jnp path,
    assert k["seg_sum_available"] is False      # nothing tried


def test_refuses_to_report_success_off_a_tpu(smoke_run):
    rc, recs, lines = smoke_run
    assert rc != 0
    assert not any(r.get("ok") for r in recs)
    assert '"ok": true' not in "\n".join(lines)


def test_default_query_leg_is_the_issues_minimum_and_a_bad_query_fails(
        tmp_path):
    """With no ``--queries`` the leg holds the join and the sort the issue
    says to keep; nothing skips one.  A query that yields no oracle-checked
    record is a failure of its own, next to the platform's."""
    mod = _load()
    assert mod.DEFAULT_QUERIES == ("q5_global_sort", "tpch_q3_full")
    assert mod.QUERY_ROWS >= 1_000_000
    rc, recs, _ = _run(["--rows", "2000", "--out", str(tmp_path),
                        "--queries", "q5_global_sort,no_such_query"])
    summary = [r for r in recs if r.get("phase") == "summary"][-1]
    assert summary["query_rows"] == 2000
    assert [f["phase"] for f in summary["failures"]] == [
        "device", "query_leg:no_such_query"]
    assert [r["query"] for r in recs if r.get("phase") == "query_leg"
            and r.get("oracle") == "pass"] == ["q5_global_sort"]
    assert rc != 0


def test_without_rows_a_run_off_the_tpu_stops_at_the_device_phase():
    rc, recs, lines = _run([])
    assert rc != 0
    assert {r.get("phase") for r in recs} == {"device"}
    assert '"ok": true' not in "\n".join(lines)


def test_four_chip_phase_on_virtual_devices():
    """``--chips 4`` on four of conftest's virtual CPU devices: the mesh
    plane carries the exchanges, agrees with the local plane and with
    pandas, and the run still fails for not being on a TPU."""
    rc, recs, lines = _run(["--chips", "4", "--rows", "8000"])
    assert rc != 0 and '"ok": true' not in "\n".join(lines)
    summary = [r for r in recs if r.get("phase") == "summary"][-1]
    assert summary["chips"] == 4
    assert [f["phase"] for f in summary["failures"]] == ["device"]
    ici = [r for r in recs if r.get("plane") == "ICI"][0]
    assert ici["mesh_stats"]["mesh_exchanges"] > 0
    assert ici["mesh_stats"]["fallbacks"] == 0
    assert ici["mesh_stats"]["collective_timeouts"] == 0
    assert ici["not_on_tpu"] == []
    assert ici["cross_chip_copies"] == 0
    # the exchange's own record: one entry per exchange, outputs over the
    # four devices, the batches handed on living where the exchange left
    # them
    assert len(ici["after_exchange"]) == ici["mesh_stats"]["mesh_exchanges"]
    for snap in ici["after_exchange"]:
        assert len(snap["bytes_in_use"]) == 4
        assert len(snap["program_outputs_live_on"]) == 4
        assert snap["batches_handed_on_live_on"] == [
            "cpu:0", "cpu:1", "cpu:2", "cpu:3"]
    local = [r for r in recs if r.get("plane") == "local"][0]
    assert local["mesh_exchanges_on_local_plane"] == 0
    assert [r for r in recs if r.get("equal")] and \
        [r for r in recs if r.get("equal")][0]["groups"] > 0
    # only this path ran
    assert not [r for r in recs if r.get("phase") in ("scan_leg",
                                                      "query_leg")]
