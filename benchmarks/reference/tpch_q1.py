"""TPC-H Q1 (pricing summary report), DELTA = 90, in plain pandas."""

import pandas as pd


def reference(tables):
    li = tables["lineitem"]
    li = li[li.l_shipdate <= pd.Timestamp("1998-09-02")]
    disc_price = li.l_extendedprice * (1 - li.l_discount)
    frame = pd.DataFrame({
        "l_returnflag": li.l_returnflag, "l_linestatus": li.l_linestatus,
        "q": li.l_quantity, "p": li.l_extendedprice, "dp": disc_price,
        "ch": disc_price * (1 + li.l_tax), "d": li.l_discount})
    return (frame.groupby(["l_returnflag", "l_linestatus"])
            .agg(sum_qty=("q", "sum"), sum_base_price=("p", "sum"),
                 sum_disc_price=("dp", "sum"), sum_charge=("ch", "sum"),
                 avg_qty=("q", "mean"), avg_price=("p", "mean"),
                 avg_disc=("d", "mean"), count_order=("q", "size"))
            .sort_index().reset_index())
