"""TPC-DS q98 (each item's share of its class's store revenue over thirty
days; CATEGORY = Sports, Books, Home, SDATE = 1999-02-22), in plain pandas
and float64.  Filters go before the joins; a NULL key matches nothing
(pandas would pair NaN with NaN, so such rows are dropped first).

Departures from the template, each for what SQL says and pandas does not by
itself: ``sum`` over a group whose every price is NULL is NULL
(``min_count=1``; pandas gives 0), and so is a class total over such
groups alone; a ratio whose class total is NULL or 0 is NULL (Spark's ``/``
gives NULL for a zero divisor; pandas gives inf or NaN); ``between`` with
``+ 30 days`` is both ends inclusive, 31 days; NULLs sort first, strings by
their code points (the tables hold ASCII, so by their bytes); rows that tie
on all five keys keep the group table's order (a stable sort), which
nothing in the answer depends on."""

import pandas as pd

KEYS = ["i_item_id", "i_item_desc", "i_category", "i_class",
        "i_current_price"]
ORDER = ["i_category", "i_class", "i_item_id", "i_item_desc", "revenueratio"]


def reference(tables):
    ss = tables["store_sales"]
    i = tables["item"]
    d = tables["date_dim"]
    first = pd.Timestamp("1999-02-22")
    d = d[(d.d_date >= first) & (d.d_date <= first + pd.Timedelta(days=30))][
        ["d_date_sk"]]
    i = i[i.i_category.isin(["Sports", "Books", "Home"])]
    i = i.astype({"i_category": object, "i_class": object})
    ss = ss.dropna(subset=["ss_item_sk", "ss_sold_date_sk"])
    m = (ss.merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(i, left_on="ss_item_sk", right_on="i_item_sk"))
    out = (m.groupby(KEYS, dropna=False, sort=False)["ss_ext_sales_price"]
           .sum(min_count=1).rename("itemrevenue").reset_index())
    total = out.groupby("i_class", dropna=False)["itemrevenue"].transform(
        lambda v: v.sum(min_count=1))
    out["revenueratio"] = out.itemrevenue * 100 / total.where(total != 0)
    return (out.sort_values(ORDER, kind="stable", na_position="first")
            .reset_index(drop=True))
