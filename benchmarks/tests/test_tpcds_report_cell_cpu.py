"""The TPC-DS reporting cell (``tpcds-sf100-report-q98``) off the chip: the
rehearsal runs every phase and agrees with the reference; a planted fault (a
row dropped from the middle of the answer, two adjacent rows swapped, a
ratio scaled by 1 + 1e-6, a class total taken over the category) comes out
not correct; the five trace and span readers on a small recorded trace
against numbers worked out from the events it holds, and on a trace without
the programs; ``sort_bytes.py`` against a count by hand.

``data/trace_report_small.pbtxt`` is the first collect (q98) of a traced run
of that deployment on one v5e chip (PR 35's chip call pr35_4, seed
2147484462, a 32nd of ``store_sales`` in 18 partitions), cut down to the
program runs and device operations of 2 ms or more, every run of a
``WindowExec``, ``SortExec`` or ``ShuffleExchangeExec`` program, the
``bench:`` annotation and the ``srt:`` spans of 2 ms or more or of the sort,
window and exchange kind; the tests work the sums out again with a sweep of
their own.

``data/trace_spans_small.pbtxt`` (PR 26) is Q6 and Q1: no window, no sort,
no exchange program.
"""

import os

import pandas as pd
import pyarrow as pa
import pytest

import compare as C
import exec_trace as ET
import join_bytes as JB
import program_spans as PS
import reduce_trace as RT
import run as R
import sort_bytes as SB

CELL = "tpcds-sf100-report-q98"
Q = "tpcds_q98"
DATA = os.path.join(os.path.dirname(__file__), "data")
REPORT = os.path.join(DATA, "trace_report_small.pbtxt")
NO_SORT = os.path.join(DATA, "trace_spans_small.pbtxt")
TRACE_READERS = ("window_ms", "sort_ms", "shuffle_ms", "range_bounds_ms",
                 "sort_roofline")
EXECS = {"window_ms": "WindowExec", "sort_ms": "SortExec",
         "shuffle_ms": "ShuffleExchangeExec"}


# --- the rehearsal, and planted faults -------------------------------------

def test_the_rehearsal_runs_every_phase(run_args):
    result, as_asked = R.execute(run_args(CELL, seed=2147484999, trace=1))
    assert not as_asked and result["metrics"] == {}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["correct"], result["compared"]
    assert result["compared"][Q + ".answers"]["value"] >= 1
    assert set(JB.BUILT) == {"store_sales", "item", "date_dim"}
    # the answer's sort moves the reference's answer twice
    moved = SB.sort_bytes_per_collect({"cell": {"queries": [Q]}})
    assert moved and moved > 0


def _drop_a_middle_row(table):
    mid = table.num_rows // 2
    return pa.concat_tables([table.slice(0, mid), table.slice(mid + 1)])


def _swap_two_rows(table):
    mid = table.num_rows // 2
    order = list(range(table.num_rows))
    order[mid], order[mid + 1] = order[mid + 1], order[mid]
    return table.take(order)


def _scale_a_ratio(table):
    import pyarrow.compute as pc
    i = table.schema.get_field_index("revenueratio")
    return table.set_column(i, "revenueratio",
                            pc.multiply(table.column(i), 1.0 + 1e-6))


def _total_over_the_category(table):
    frame = table.to_pandas()
    total = frame.groupby(frame.i_category.astype(object)).itemrevenue \
        .transform(lambda v: v.sum(min_count=1))
    i = table.schema.get_field_index("revenueratio")
    return table.set_column(i, "revenueratio", pa.array(
        frame.itemrevenue * 100 / total, from_pandas=True))


@pytest.mark.parametrize("fault", [_drop_a_middle_row, _swap_two_rows,
                                   _scale_a_ratio, _total_over_the_category])
def test_an_altered_answer_is_not_correct(fault, run_args, monkeypatch):
    from spark_rapids_tpu.sql.dataframe import DataFrame
    real, real_drive = DataFrame.collect, R.drive
    state = {"window": False}

    def collect(self, *a, **kw):
        out = real(self, *a, **kw)
        return fault(out) if state["window"] else out

    def drive(*a, **kw):
        state["window"] = True
        try:
            return real_drive(*a, **kw)
        finally:
            state["window"] = False
    monkeypatch.setattr(DataFrame, "collect", collect)
    monkeypatch.setattr(R, "drive", drive)
    result, _ = R.execute(run_args(CELL, seed=41))
    assert result["correct"] is False, result["compared"]


def test_a_partition_left_out(run_args, monkeypatch):
    """A quarter of ``store_sales`` never reaches the program."""
    real = R.register

    def short(sess, cell, tables):
        fact = tables["store_sales"]
        return real(sess, cell, {
            **tables, "store_sales": fact.slice(0, fact.num_rows * 3 // 4)})
    monkeypatch.setattr(R, "register", short)
    result, _ = R.execute(run_args(CELL, seed=42))
    assert result["correct"] is False, result["compared"]


# --- the readers --------------------------------------------------------------

def swept(path):
    """Per exec type (device seconds, runs) of its programs inside the
    collect, and the seconds of the range-bounds spans, by a loop over the
    events of its own."""
    data = RT.load(path)
    planes = {p.name: p for p in data.planes}
    events = [(e.name, float(e.start_ns), float(e.duration_ns))
              for line in planes["/host:CPU"].lines for e in line.events]
    lo, hi = next((s, s + d) for n, s, d in events if n.startswith("bench:"))
    bounds = sum(d for n, s, d in events
                 if lo <= s < hi and n == "srt:sort:range_bounds")
    marks = sum(1 for n, s, d in events
                if lo <= s < hi and n == "srt:sort:compute")
    out = {}
    for line in planes["/device:TPU:0"].lines:
        if line.name != "XLA Modules":
            continue
        for e in line.events:
            if lo <= float(e.start_ns) < hi and e.name.startswith("jit_srt_"):
                seconds, runs = out.get(e.name.split("_")[2], (0.0, 0))
                out[e.name.split("_")[2]] = (
                    seconds + float(e.duration_ns) * 1e-9, runs + 1)
    return out, bounds * 1e-9, marks


@pytest.fixture
def small_tables():
    """Three groups in two classes, worked out by hand below."""
    fact = pa.table({
        "ss_sold_date_sk": pa.array([1, 1, 1, 2, None], pa.int32()),
        "ss_item_sk": pa.array([1, 1, 2, 3, 3], pa.int32()),
        "ss_ext_sales_price": pa.array([10.0, 30.0, 60.0, None, 5.0])})
    item = pa.table({
        "i_item_sk": pa.array([1, 2, 3, 4], pa.int32()),
        "i_item_id": ["AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA",
                      "AAAAAAAADAAAAAAA", "AAAAAAAAEAAAAAAA"],
        "i_item_desc": ["one", "two", "three", "four"],
        "i_current_price": [1.5, 2.5, 3.5, 4.5],
        "i_class": ["a", "a", "b", "b"],
        "i_category": ["Books", "Books", "Home", "Music"]})
    dates = pa.table({
        "d_date_sk": pa.array([1, 2, 3], pa.int32()),
        "d_date": pa.array(pd.to_datetime(
            ["1999-02-22", "1999-03-24", "1999-03-25"]).date)})
    return {"store_sales": fact, "item": item, "date_dim": dates}


def run_record():
    return {"trace": {"collects": [{}]}, "peaks": {"hbm_bytes_per_s": 819e9},
            "cell": {"name": "x", "queries": [Q]},
            "query_metrics": {Q: {"d2h_bytes": 11501568.0}}}


def test_sort_bytes_against_a_count_by_hand(small_tables):
    # groups: item 1 (40 of class a's 100), item 2 (60), item 3 (its one
    # row inside the window has a NULL price; the other has a NULL date)
    want = R.load_module("reference", Q + ".py").reference(
        C.tables_for_reference(
            small_tables, R.load_json("queries", Q + ".json")["tables"]))
    assert list(want.i_item_desc) == ["one", "two", "three"]
    assert list(want.revenueratio[:2]) == [40.0, 60.0]
    assert want.revenueratio.isna().tolist() == [False, False, True]
    # three rows: 16 + 3|3|5 characters of id and description, 5|5|4 and
    # 1 of category and class, four-byte offsets (rows + 1) for the four
    # strings, three doubles, one validity byte each for the two doubles
    # with a NULL
    strings = 3 * 16 + (3 + 3 + 5) + (5 + 5 + 4) + 3
    by_hand = strings + 4 * 4 * 4 + 3 * 3 * 8 + 2 * 1
    assert SB.answer_bytes(want) == by_hand
    assert SB.sort_bytes(Q, small_tables) == 2.0 * by_hand
    # a top-n need not move the answer; a table may be missing
    assert SB.sort_bytes("tpcds_q7", small_tables) is None
    assert SB.sort_bytes(Q, {"item": small_tables["item"]}) is None


def test_readers_on_the_report_trace(monkeypatch, small_tables):
    monkeypatch.setattr(PS, "trace_file", lambda run: REPORT)
    JB.remember(small_tables)
    got = R.read_metrics(list(TRACE_READERS) + ["result_bytes"], run_record())
    execs, bounds_s, marks = swept(REPORT)
    for name, exec_name in EXECS.items():
        seconds, runs = execs[exec_name]
        assert runs >= 1 and seconds > 0
        assert got[name] == pytest.approx(seconds * 1e3, rel=1e-9)
    reduced = ET.reduce(REPORT)
    assert reduced["collects"] == 1
    assert {k: v["runs"] for k, v in reduced["execs"].items()} == {
        k: runs for k, (_, runs) in execs.items()}
    # the spans of the sort are there; what the range exchange sampled is
    # what the trace holds of it (nothing where the exchange coalesced)
    assert marks >= 1
    assert got["range_bounds_ms"] == pytest.approx(bounds_s * 1e3, rel=1e-9)
    moved = SB.sort_bytes(Q, small_tables)
    assert got["sort_roofline"] == pytest.approx(
        100 * (moved / 819e9) / execs["SortExec"][0], rel=1e-9)
    assert 0 < got["sort_roofline"] <= 100
    assert got["result_bytes"] == 11501568.0
    # no tables left by a generator: no share of the roofline, never 0
    JB.remember({})
    assert "sort_roofline" not in R.read_metrics(list(TRACE_READERS),
                                                 run_record())


def test_readers_say_nothing_without_the_programs(monkeypatch, small_tables):
    JB.remember(small_tables)
    monkeypatch.setattr(PS, "trace_file", lambda run: NO_SORT)
    reduced = ET.reduce(NO_SORT)
    assert reduced is None or not set(EXECS.values()) & set(reduced["execs"])
    # a program from before the spans and counters existed (the parent)
    assert R.read_metrics(list(TRACE_READERS), run_record()) == {}
    assert R.read_metrics(["result_bytes"], {
        **run_record(), "query_metrics": {Q: {}}}) == {}
    untraced = {**run_record(), "trace": None,
                "cell": {"name": "no-such-cell", "queries": [Q]}}
    monkeypatch.undo()
    assert R.read_metrics(list(TRACE_READERS), untraced) == {}
    JB.remember({})
