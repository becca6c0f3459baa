"""BENCHMARK.json against the contract's character rules, and every name in
it against the files the harness finds by that name."""

import json
import os
import re

import pytest

import run as R
from conftest import BENCH, SCALE

ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_keys_names_and_units():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    assert m["paths"] == ["benchmarks"]
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert all(NAME.match(k) for k in c["reduced"])
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line(w["why"])
    names = [x["name"] for k in ("end_to_end", "per_layer") for x in m[k]]
    assert len(names) == len(set(names))
    for e in m["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= e["bound"] <= 0.25
        assert e["source"] in {"host_clock", "device_trace"}
    for p in m["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert p["source"] in SOURCES and line(p["layer"])
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
    assert "setup_s" in {e["name"] for e in m["end_to_end"]}


def test_every_name_has_its_file():
    m = manifest()
    configs = {c["name"]: c for c in m["configs"]}
    cells = {w["name"] for w in m["workloads"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    for c in m["configs"]:
        assert c["file"] == f"benchmarks/configs/{c['name']}.json"
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert set(c["reduced"]) == set(held["reduced"])
        assert held["chips"] == 1
        assert os.path.exists(os.path.join(
            BENCH, "generators", held["generator"] + ".py"))
    for w in m["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            cell = json.load(f)
        assert cell["config"] == w["config"]
        assert set(cell) <= R.WORKLOAD_KEYS
        for q in cell["queries"]:
            for path in (("queries", q + ".sql"), ("queries", q + ".json"),
                         ("reference", q + ".py")):
                assert os.path.exists(os.path.join(BENCH, *path)), path
    for p in m["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           p["name"] + ".py")), p["name"]
    for x in m["end_to_end"] + m["per_layer"]:
        assert set(x.get("workloads", cells)) <= cells
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    for cell in cells:
        e2e = [e["name"] for e in m["end_to_end"]
               if cell in e.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in p.get("workloads", cells) for p in m["per_layer"])


def test_files_under_paths_use_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__",
                                                ".pytest_cache")]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert allowed.match(rel) and len(rel) <= 200, rel


def test_a_key_the_harness_does_not_implement_is_refused(tmp_path,
                                                         monkeypatch):
    """A cell that asks for more clients or an open loop must not silently
    run one closed-loop client."""
    import shutil
    shutil.copytree(BENCH, tmp_path / "b", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__", ".pytest_cache"))
    path = tmp_path / "b" / "workloads" / "tpch-1m-join-q3.json"
    cell = json.loads(path.read_text())
    cell["clients"] = 8
    path.write_text(json.dumps(cell))
    monkeypatch.setattr(R, "HERE", str(tmp_path / "b"))
    with pytest.raises(SystemExit, match="clients"):
        R.Cell("tpch-1m-join-q3")


def test_generators_make_the_schema_their_configuration_states():
    """Every column of every table, at the type the file gives, and the
    spec's key rules where a rule can be read off the tables."""
    m = manifest()
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        generator = R.load_module("generators", held["generator"] + ".py")
        tables = generator.build_tables({**held["scale"], **SCALE}, 5,
                                        held["tables"])
        assert list(tables) == held["tables"]
        for name, table in tables.items():
            stated = held["schema"][name]["columns"]
            assert {f.name: str(f.type) for f in table.schema} == stated
        full = generator.sizes(held["scale"])
        for name in held["tables"]:
            if name in full:
                assert held["schema"][name]["rows"] == full[name]


def test_tpch_tables_follow_clause_4_2_3():
    generator = R.load_module("generators", "tpch.py")
    t = {k: v.to_pandas(date_as_object=False) for k, v in generator.build_tables(
        {"scale_factor": 0.01}, 7, generator.TABLES).items()}
    li, o, c, ps = t["lineitem"], t["orders"], t["customer"], t["partsupp"]
    assert len(c) == 1500 and len(o) == 15000 and len(t["part"]) == 2000
    assert len(t["supplier"]) == 100 and len(ps) == 8000
    per_order = li.groupby("l_orderkey").l_linenumber
    assert per_order.count().between(1, 7).all()
    assert (per_order.max() == per_order.count()).all()
    assert set(li.l_orderkey) == set(o.o_orderkey)
    assert ((o.o_orderkey - 1) % 32 < 8).all()
    assert (o.o_custkey % 3 != 0).all() and o.o_custkey.between(1, 1500).all()
    assert set(zip(li.l_returnflag, li.l_linestatus)) == {
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")}
    both = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    ship = (both.l_shipdate - both.o_orderdate).dt.days
    assert ship.between(1, 121).all()
    assert (both.l_receiptdate - both.l_shipdate).dt.days.between(1, 30).all()
    assert set(zip(li.l_partkey, li.l_suppkey)) <= set(
        zip(ps.ps_partkey, ps.ps_suppkey))
    price = li.merge(t["part"], left_on="l_partkey", right_on="p_partkey")
    assert (abs(price.l_extendedprice
                - price.l_quantity * price.p_retailprice) < 1e-6).all()
    assert li.l_comment.str.len().between(10, 43).all()
    assert o.o_comment.str.len().between(19, 78).all()
    again = generator.build_tables({"scale_factor": 0.01}, 7, ["lineitem"])
    assert again["lineitem"].equals(
        generator.build_tables({"scale_factor": 0.01}, 7,
                               generator.TABLES)["lineitem"])
    other = generator.build_tables({"scale_factor": 0.01}, 8, ["lineitem"])
    assert other["lineitem"].num_rows == len(li)
