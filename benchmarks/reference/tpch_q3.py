"""TPC-H Q3 (shipping priority), SEGMENT = BUILDING, DATE = 1995-03-15, in
plain pandas.  Filters go before the joins; the answer is the same."""

import pandas as pd


def reference(tables):
    cut = pd.Timestamp("1995-03-15")
    c = tables["customer"]
    o = tables["orders"]
    li = tables["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"][["c_custkey"]]
    o = o[o.o_orderdate < cut]
    li = li[li.l_shipdate > cut]
    m = (c.merge(o, left_on="c_custkey", right_on="o_custkey")
         .merge(li, left_on="o_orderkey", right_on="l_orderkey"))
    m = m.assign(revenue=m.l_extendedprice * (1 - m.l_discount))
    out = (m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])
           .revenue.sum().reset_index()
           .sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                        kind="stable")
           .head(10).reset_index(drop=True))
    return out[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
