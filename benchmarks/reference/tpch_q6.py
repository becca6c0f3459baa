"""TPC-H Q6 (forecasting revenue change), DATE = 1994-01-01, DISCOUNT =
0.06, QUANTITY = 24, in plain pandas."""

import pandas as pd


def reference(tables):
    li = tables["lineitem"]
    keep = ((li.l_shipdate >= pd.Timestamp("1994-01-01"))
            & (li.l_shipdate < pd.Timestamp("1995-01-01"))
            & (li.l_discount >= 0.05) & (li.l_discount <= 0.07)
            & (li.l_quantity < 24))
    revenue = (li.l_extendedprice[keep] * li.l_discount[keep]).sum()
    return pd.DataFrame({"revenue": [revenue]})
