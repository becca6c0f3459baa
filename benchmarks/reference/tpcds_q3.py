"""TPC-DS q3 (brand sales of one manufacturer in one month of every year),
MANUFACT = 128, MONTH = 11, in plain pandas.  Filters go before the joins;
a NULL key matches nothing; ``sum`` skips NULL measures and an all-NULL
group's sum is NULL (``min_count=1``), which sorts last under DESC."""


def reference(tables):
    d = tables["date_dim"]
    ss = tables["store_sales"]
    i = tables["item"]
    d = d[d.d_moy == 11][["d_date_sk", "d_year"]]
    i = i[i.i_manufact_id == 128][["i_item_sk", "i_brand_id", "i_brand"]]
    ss = ss.dropna(subset=["ss_sold_date_sk", "ss_item_sk"])
    m = (ss.merge(i, left_on="ss_item_sk", right_on="i_item_sk")
         .merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk"))
    out = (m.groupby(["d_year", "i_brand", "i_brand_id"])
           .ss_ext_sales_price.sum(min_count=1).reset_index()
           .sort_values(["d_year", "ss_ext_sales_price", "i_brand_id"],
                        ascending=[True, False, True], kind="stable",
                        na_position="last")
           .head(100).reset_index(drop=True))
    out = out.rename(columns={"i_brand_id": "brand_id", "i_brand": "brand",
                              "ss_ext_sales_price": "sum_agg"})
    return out[["d_year", "brand_id", "brand", "sum_agg"]]
