"""The TPC-DS star-join cell (``tpcds-sf100-star-q7``) off the chip: the
rehearsal runs every phase and agrees with the reference; a planted fault (a
float scaled by 1 + 1e-6, a NULL key made to match, a dropped row, a quarter
of ``store_sales`` left out) comes out not correct; the three trace readers
on a small recorded trace against numbers worked out from the events it
holds, and on a trace without the programs; ``join_bytes.py`` against a
count by hand on ten rows; the generator hands its listed-value strings over
dictionary-encoded, one dictionary a table.

``data/trace_star_small.pbtxt`` is the first collect (q7) of a traced run of
that deployment on one v5e chip (PR 33's chip call 6, seed 2147483941, a
32nd of ``store_sales`` in 18 partitions), cut down to the program runs and
device operations of 2 ms or more, every join probe program run, the
``bench:`` annotation and the ``srt:`` spans of 2 ms or more or of the
build and probe kind.  It holds 72 probe runs of five programs
(``jit_srt_BroadcastHashJoinExec_probe_855c4b8d``, the first join's, 18
runs of ~76 ms), four ``srt:broadcast:build`` spans and two
``srt:join:adaptive.materialize`` spans; the tests work the sums out again
with a sweep of their own.

``data/trace_spans_small.pbtxt`` (PR 26) is Q6 and Q1: no join, no build.
"""

import os

import pyarrow as pa
import pytest

import join_bytes as JB
import join_trace as JT
import program_spans as PS
import reduce_trace as RT
import run as R

CELL = "tpcds-sf100-star-q7"
DATA = os.path.join(os.path.dirname(__file__), "data")
STAR = os.path.join(DATA, "trace_star_small.pbtxt")
NO_JOIN = os.path.join(DATA, "trace_spans_small.pbtxt")
READERS = ("broadcast_build_ms", "join_probe_ms", "join_probe_roofline")


def swept(path):
    """(probe programs' device seconds, build spans' seconds, probe runs)
    inside the collect, by a loop over the events of its own."""
    data = RT.load(path)
    planes = {p.name: p for p in data.planes}
    events = [(e.name, float(e.start_ns), float(e.duration_ns))
              for line in planes["/host:CPU"].lines for e in line.events]
    lo, hi = next((s, s + d) for n, s, d in events if n.startswith("bench:"))
    build = sum(d for n, s, d in events if lo <= s < hi and n in (
        "srt:broadcast:build", "srt:join:adaptive.materialize"))
    probes = [float(e.duration_ns)
              for line in planes["/device:TPU:0"].lines
              if line.name == "XLA Modules" for e in line.events
              if lo <= float(e.start_ns) < hi
              and "JoinExec_probe_" in e.name]
    return sum(probes) * 1e-9, build * 1e-9, len(probes)



# --- the rehearsal, and planted faults -------------------------------------

def test_the_rehearsal_runs_every_phase(run_args):
    result, as_asked = R.execute(run_args(CELL, seed=2147483999, trace=1))
    assert not as_asked and result["metrics"] == {}
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["correct"], result["compared"]
    assert result["compared"]["tpcds_q7.answers"]["value"] >= 1
    # the generator left its tables where the byte count finds them
    assert set(JB.BUILT) == {"store_sales", "date_dim", "item",
                             "customer_demographics", "promotion"}


def test_listed_value_strings_come_dictionary_encoded():
    """What the configuration's ``schema`` states and a tree before PR 33
    cannot register (``types.from_arrow``: unsupported arrow type)."""
    cd = JB.BUILT.get("customer_demographics") or R.Cell(
        CELL, {"scale_factor": 1 / 300}).build_tables(5)[
            "customer_demographics"]
    for name in ("cd_gender", "cd_marital_status", "cd_education_status"):
        kind = cd.schema.field(name).type
        assert pa.types.is_dictionary(kind) and pa.types.is_string(
            kind.value_type)
        assert len(cd.column(name).chunks) == 1


def _scale_floats(table):
    import pyarrow.compute as pc
    i = table.schema.get_field_index("agg2")
    return table.set_column(i, "agg2",
                            pc.multiply(table.column(i), 1.0 + 1e-6))


def _drop_last_row(table):
    return table.slice(0, table.num_rows - 1)


@pytest.mark.parametrize("fault", [_scale_floats, _drop_last_row])
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
    result, _ = R.execute(run_args(CELL, seed=31))
    assert result["correct"] is False, result["compared"]


def test_a_null_key_made_to_match_is_not_correct(run_args, monkeypatch):
    """The program is handed a fact table whose NULL ``ss_cdemo_sk`` and
    ``ss_promo_sk`` point at rows that pass the filters; the reference
    keeps the NULLs, so the program's averages hold rows they must not."""
    import pyarrow.compute as pc
    real = R.register

    def matching(sess, cell, tables):
        fact = tables["store_sales"]
        cd = tables["customer_demographics"]
        keep = pc.and_(pc.and_(pc.equal(cd.column("cd_gender"), "M"),
                               pc.equal(cd.column("cd_marital_status"), "S")),
                       pc.equal(cd.column("cd_education_status"), "College"))
        demo = cd.filter(keep).column("cd_demo_sk")[0].as_py()
        for name, value in (("ss_cdemo_sk", demo), ("ss_promo_sk", 1),
                            ("ss_sold_date_sk", 2451545 + 100)):
            i = fact.schema.get_field_index(name)
            fact = fact.set_column(i, fact.schema.field(i), pc.fill_null(
                fact.column(i), pa.scalar(value, fact.schema.field(i).type)))
        return real(sess, cell, {**tables, "store_sales": fact})
    monkeypatch.setattr(R, "register", matching)
    result, _ = R.execute(run_args(CELL, seed=32))
    assert result["correct"] is False, result["compared"]


def test_a_partition_left_out(run_args, monkeypatch):
    """A quarter of ``store_sales`` never reaches the program
    (``test_cells_cpu.py`` cuts ``lineitem``, which this deployment lacks)."""
    real = R.register

    def short(sess, cell, tables):
        fact = tables["store_sales"]
        return real(sess, cell, {
            **tables, "store_sales": fact.slice(0, fact.num_rows * 3 // 4)})
    monkeypatch.setattr(R, "register", short)
    result, _ = R.execute(run_args(CELL, seed=33))
    assert result["correct"] is False, result["compared"]


# --- the readers --------------------------------------------------------------

def run_record():
    return {"trace": {"collects": [{}]}, "peaks": {"hbm_bytes_per_s": 819e9},
            "cell": {"name": "x", "queries": ["tpcds_q7"]},
            "query_metrics": {"tpcds_q7": {"aggGroupRows": 48000.0}}}


@pytest.fixture
def ten_row_star():
    """A star of ten fact rows whose counts can be worked out by hand."""
    fact = pa.table({
        "ss_sold_date_sk": pa.array([1, 1, 2, 2, None, 1, 1, 3, 1, 1],
                                    pa.int32()),
        "ss_item_sk": pa.array([1, 2, 1, 2, 1, 2, 1, 2, 9, 1], pa.int32()),
        "ss_cdemo_sk": pa.array([1, 1, 1, 1, 1, None, 2, 1, 1, 1],
                                pa.int32()),
        "ss_promo_sk": pa.array([1, 2, 1, 1, 1, 1, 1, 1, 1, None],
                                pa.int32()),
        "ss_quantity": pa.array(range(10), pa.int32()),
        "ss_list_price": pa.array([1.0] * 10),
        "ss_coupon_amt": pa.array([0.0] * 10),
        "ss_sales_price": pa.array([1.0] * 10)})
    cd = pa.table({"cd_demo_sk": pa.array([1, 2, 3], pa.int32()),
                   "cd_gender": ["M", "F", "M"],
                   "cd_marital_status": ["S", "S", "S"],
                   "cd_education_status": ["College"] * 3})
    dates = pa.table({"d_date_sk": pa.array([1, 2, 3], pa.int32()),
                      "d_year": pa.array([2000, 2000, 1999], pa.int32())})
    item = pa.table({"i_item_sk": pa.array([1, 2], pa.int32()),
                     "i_item_id": ["AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA"]})
    promo = pa.table({"p_promo_sk": pa.array([1, 2], pa.int32()),
                      "p_channel_email": ["N", "Y"],
                      "p_channel_event": ["Y", "Y"]})
    return {"store_sales": fact, "customer_demographics": cd,
            "date_dim": dates, "item": item, "promotion": promo}


def test_join_bytes_against_a_count_by_hand(ten_row_star):
    rows = JB.join_rows("tpcds_q7", ten_row_star)
    # demographics: rows 5 (NULL) and 6 (F) go; dates: row 4 (NULL) and
    # row 7 (1999) go; item: row 8 (no item 9) goes; promotion: row 1 (no
    # 'N') and row 9 (NULL) go
    assert [(r["probe_rows"], r["build_rows"], r["matched_rows"])
            for r in rows] == [(10, 2, 8), (8, 2, 6), (6, 2, 5), (5, 1, 3)]
    referenced = R.load_json("queries", "tpcds_q7.json")["tables"]
    t = ten_row_star
    width = {name: JB.row_width(t[name], referenced[name]) for name in t}
    # five int32 columns, three of them with NULLs and so with a validity
    # bitmap (two bytes for ten rows), and three doubles without
    assert width["store_sales"] == pytest.approx(
        (5 * 40 + 3 * 2 + 3 * 80) / 10)
    carried, total = width["store_sales"], 0.0
    for (reach, build, matched), dim in zip(
            [(10, 2, 8), (8, 2, 6), (6, 2, 5), (5, 1, 3)],
            ["customer_demographics", "date_dim", "item", "promotion"]):
        total += reach * carried + build * width[dim]
        carried += width[dim]
        total += matched * carried
    assert JB.probe_bytes("tpcds_q7", t, referenced) == pytest.approx(total)
    assert JB.probe_bytes("tpch_q1", t, referenced) is None


def test_readers_on_the_star_trace(monkeypatch, ten_row_star):
    monkeypatch.setattr(PS, "trace_file", lambda run: STAR)
    JB.remember(ten_row_star)
    got = R.read_metrics(list(READERS) + ["group_rows"], run_record())
    probe_s, build_s, runs = swept(STAR)
    assert runs == 72 and 1.4 < probe_s < 1.5 and 0.4 < build_s < 0.5
    assert got["join_probe_ms"] == pytest.approx(probe_s * 1e3, rel=1e-9)
    assert got["broadcast_build_ms"] == pytest.approx(build_s * 1e3,
                                                      rel=1e-9)
    moved = JB.probe_bytes(
        "tpcds_q7", ten_row_star,
        R.load_json("queries", "tpcds_q7.json")["tables"])
    assert got["join_probe_roofline"] == pytest.approx(
        100 * (moved / 819e9) / probe_s, rel=1e-9)
    assert 0 < got["join_probe_roofline"] <= 100
    assert got["group_rows"] == 48000.0
    reduced = JT.reduce(STAR)
    assert reduced["probe_runs"] == runs and reduced["collects"] == 1
    # no tables left by a generator: no share of the roofline, never 0
    JB.remember({})
    assert "join_probe_roofline" not in R.read_metrics(list(READERS),
                                                       run_record())


def test_readers_say_nothing_without_the_programs(monkeypatch, ten_row_star):
    JB.remember(ten_row_star)
    monkeypatch.setattr(PS, "trace_file", lambda run: NO_JOIN)
    assert JT.reduce(NO_JOIN) is None
    assert R.read_metrics(list(READERS), run_record()) == {}
    untraced = {**run_record(), "trace": None,
                "cell": {"name": "no-such-cell", "queries": ["tpcds_q7"]}}
    monkeypatch.undo()
    assert R.read_metrics(list(READERS), untraced) == {}
    # a program from before the counter existed
    assert R.read_metrics(["group_rows"], {**run_record(), "query_metrics": {
        "tpcds_q7": {}}}) == {}
    JB.remember({})
