"""The bytes a star join's probes have to move, whatever implements them.

For each join of a query, in the order its text names the dimensions: the
rows that reach the probe at the width of the columns the query references
on the fact side (the fact table's live rows for the first join, the rows
the join before let through after it), the build rows that pass the
dimension's own filter at the width of its referenced columns (key and
payload), and the matched rows at the width of what the join hands on.
Widths are Arrow bytes a row (data, offsets and validity bits) of the
generated tables; row counts come from the tables themselves, by the
reference's semantics (a NULL key matches nothing) - nothing is read from
the program, so the count is the same work whatever the probe is made of.
``metrics/join_probe_roofline.py`` divides it by the chip's HBM peak and by
the device time of the probe programs.

The harness hands a metric's reader the run and not the tables, so the
generator leaves the tables of its last build here (``remember``): the same
objects the harness keeps for the check, nothing copied.
"""

from __future__ import annotations

import json
import os
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import pyarrow as pa
import pyarrow.compute as pc

#: the tables of the generator's last build in this process
BUILT: Dict[str, pa.Table] = {}


def remember(tables: Dict[str, pa.Table]) -> None:
    BUILT.clear()
    BUILT.update(tables)


def _eq(column: str, value) -> Callable[[pa.Table], pa.Array]:
    return lambda t: pc.equal(t.column(column), value)


def _q7_promotion(t: pa.Table):
    return pc.or_(pc.equal(t.column("p_channel_email"), "N"),
                  pc.equal(t.column("p_channel_event"), "N"))


def _q7_demographics(t: pa.Table):
    return pc.and_(pc.and_(pc.equal(t.column("cd_gender"), "M"),
                           pc.equal(t.column("cd_marital_status"), "S")),
                   pc.equal(t.column("cd_education_status"), "College"))


#: per query: the fact table, then (fact key, dimension, its key, its
#: filter or None) for each join in the order of the text's FROM list
JOINS: Dict[str, Tuple[str, List[tuple]]] = {
    "tpcds_q7": ("store_sales", [
        ("ss_cdemo_sk", "customer_demographics", "cd_demo_sk",
         _q7_demographics),
        ("ss_sold_date_sk", "date_dim", "d_date_sk", _eq("d_year", 2000)),
        ("ss_item_sk", "item", "i_item_sk", None),
        ("ss_promo_sk", "promotion", "p_promo_sk", _q7_promotion),
    ]),
    "tpcds_q3": ("store_sales", [
        ("ss_sold_date_sk", "date_dim", "d_date_sk", _eq("d_moy", 11)),
        ("ss_item_sk", "item", "i_item_sk", _eq("i_manufact_id", 128)),
    ]),
}


def row_width(table: pa.Table, columns) -> float:
    """Arrow bytes a row of these columns."""
    if not table.num_rows:
        return 0.0
    return sum(table.column(c).nbytes for c in columns) / table.num_rows


def survivors(query: str, tables: Dict[str, pa.Table]) -> Iterator[tuple]:
    """Join by join: (dimension, its rows that pass its filter, the fact
    rows still alive after this join as a boolean mask).  A NULL key is
    in no dimension."""
    fact_name, joins = JOINS[query]
    fact = tables[fact_name]
    alive = None
    for key, dim_name, dim_key, predicate in joins:
        dim = tables[dim_name]
        if predicate is not None:
            dim = dim.filter(predicate(dim))
        hit = pc.fill_null(pc.is_in(fact.column(key),
                                    value_set=dim.column(dim_key)), False)
        alive = hit if alive is None else pc.and_(alive, hit)
        yield dim_name, dim.num_rows, alive


def join_rows(query: str, tables: Dict[str, pa.Table]) -> List[dict]:
    """Per join: the rows that reach the probe, the build rows and the
    matched rows."""
    reach = tables[JOINS[query][0]].num_rows
    out = []
    for dim_name, build_rows, alive in survivors(query, tables):
        matched = pc.sum(alive).as_py() or 0
        out.append({"dimension": dim_name, "probe_rows": reach,
                    "build_rows": build_rows, "matched_rows": matched})
        reach = matched
    return out


def probe_bytes(query: str, tables: Dict[str, pa.Table],
                referenced: Dict[str, list]) -> Optional[float]:
    """Bytes one collect's probes must move; ``referenced`` is the query
    file's ``tables`` (the columns the SQL names, by table).  None for a
    query this file does not describe."""
    if query not in JOINS:
        return None
    fact_name, _ = JOINS[query]
    carried = row_width(tables[fact_name], referenced[fact_name])
    total = 0.0
    for row in join_rows(query, tables):
        dim = row["dimension"]
        dim_width = row_width(tables[dim], referenced[dim])
        total += row["probe_rows"] * carried
        total += row["build_rows"] * dim_width
        carried += dim_width
        total += row["matched_rows"] * carried
    return total


def probe_bytes_per_collect(run: dict) -> Optional[float]:
    """Mean over the cell's queries, from the tables of this run; None
    where nothing was built here or a query is not described."""
    if not BUILT:
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    per_query = []
    for q in run["cell"]["queries"]:
        with open(os.path.join(here, "queries", q + ".json")) as f:
            referenced = json.load(f)["tables"]
        if any(t not in BUILT for t in referenced):
            return None
        got = probe_bytes(q, BUILT, referenced)
        if got is None:
            return None
        per_query.append(got)
    return sum(per_query) / len(per_query) if per_query else None
