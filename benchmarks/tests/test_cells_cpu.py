"""Each cell at about 20,000 LINEITEM rows on the CPU: the window's answers are what the
reference returns, the float32 control is not, and a timed path broken
underneath comes out as not correct."""

import numpy as np
import pytest

import compare as C
import run as R
from conftest import SCALE, cells


@pytest.mark.parametrize("workload", cells())
def test_cell_returns_what_the_reference_returns(workload, run_args):
    result, as_asked = R.execute(run_args(workload))
    assert not as_asked          # the CPU is never the platform asked for
    assert result["metrics"] == {}   # so no metric carries a CPU number
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["correct"], result["compared"]
    cell = R.Cell(workload, SCALE)
    for q in cell.queries:
        assert result["compared"][f"{q}.answers"]["value"] >= 1


@pytest.mark.parametrize("workload", cells())
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float32_control_is_not_correct(workload, seed):
    cell = R.Cell(workload, SCALE)
    tables = cell.build_tables(seed)
    same = R.check(cell, tables, [], float_dtype=np.float64)
    assert R.is_correct(same, [])
    control = R.check(cell, tables, [], float_dtype=np.float32)
    assert not R.is_correct(control, []), control


def _alter_float(table):
    import pyarrow as pa
    import pyarrow.compute as pc
    for i, field in enumerate(table.schema):
        if pa.types.is_floating(field.type):
            bumped = pc.multiply(table.column(i), 1.0 + 1e-6)
            return table.set_column(i, field.name, bumped)
    raise AssertionError("no float column to alter")


def _drop_last_row(table):
    return table.slice(0, table.num_rows - 1)


def _alter_key(table):
    import pyarrow as pa
    import pyarrow.compute as pc
    for i, field in enumerate(table.schema):
        if pa.types.is_integer(field.type):
            return table.set_column(i, field.name,
                                    pc.add(table.column(i), 1))
    return _drop_last_row(table)


@pytest.fixture
def window_collects(monkeypatch):
    """Wraps the program's ``DataFrame.collect`` with ``hook(n, call)`` for
    the n-th collect of the window (1, 2, ...); set-up is left alone."""
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real, real_drive = DataFrame.collect, R.drive
    state = {"n": None, "hook": None}

    def collect(self, *a, **kw):
        if state["n"] is None:
            return real(self, *a, **kw)
        state["n"] += 1
        return state["hook"](state["n"], lambda: real(self, *a, **kw))

    def drive(*a, **kw):
        state["n"] = 0
        return real_drive(*a, **kw)
    monkeypatch.setattr(DataFrame, "collect", collect)
    monkeypatch.setattr(R, "drive", drive)

    def install(hook):
        state["hook"] = hook
        return state
    return install


@pytest.mark.parametrize("fault", [_alter_float, _drop_last_row, _alter_key])
@pytest.mark.parametrize("workload", cells())
def test_answer_altered_where_it_is_produced(workload, fault, run_args,
                                             window_collects):
    """The rest of a run, with the program's collect handing back an
    altered answer for every other collect of the window."""
    state = window_collects(
        lambda n, call: fault(call()) if n % 2 == 0 else call())
    result, _ = R.execute(run_args(workload, seed=21, seconds=3.0))
    assert state["n"] >= 2
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("workload", cells())
def test_a_partition_left_out(workload, run_args, monkeypatch):
    """A quarter of the widest table never reaches the program: the sums
    come from three quarters of the rows and the check sees it."""
    real = R.register

    def short(sess, cell, tables):
        cut = dict(tables)
        cut["lineitem"] = tables["lineitem"].slice(
            0, tables["lineitem"].num_rows * 3 // 4)
        return real(sess, cell, cut)
    monkeypatch.setattr(R, "register", short)
    result, _ = R.execute(run_args(workload, seed=22))
    assert result["correct"] is False, result["compared"]


def test_a_failed_collect_is_counted_and_not_correct(run_args,
                                                     window_collects):
    def hook(n, call):
        if n == 2:      # the window's second collect never comes
            raise RuntimeError("planted")
        return call()
    window_collects(hook)
    result, _ = R.execute(run_args(cells()[0], seed=23, seconds=3.0))
    assert result["failed"] == 1 and result["attempted"] >= 2
    assert result["correct"] is False


def test_compare_reads_each_number():
    import pandas as pd
    spec = {"exact": ["k"], "float": ["v"]}
    want = pd.DataFrame({"k": [1, 2, 3], "v": [10.0, 20.0, 30.0]})
    assert C.compare(want, want, spec) == {
        "rows_gap": 0, "exact_mismatches": 0, "float_rel_gap": 0.0,
        "float_column": None}
    got = pd.DataFrame({"k": [1, 5], "v": [10.0, 20.2]})
    n = C.compare(got, want, spec)
    assert n["rows_gap"] == 1 and n["exact_mismatches"] == 1
    assert n["float_rel_gap"] == pytest.approx(0.2 / 20.0)
    assert n["float_column"] == "v"
    assert C.compare(want.drop(columns="v"), want, spec)[
        "float_rel_gap"] == C.WRONG
    nan = want.assign(v=[10.0, float("nan"), 30.0])
    assert C.compare(nan, want, spec)["float_rel_gap"] == C.WRONG
