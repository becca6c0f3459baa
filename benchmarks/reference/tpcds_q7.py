"""TPC-DS q7 (promotional items bought by one demographic in one year),
GEN = M, MS = S, ES = College, YEAR = 2000, in plain pandas.  Filters go
before the joins; a NULL key matches nothing (pandas would pair NaN with
NaN, so such rows are dropped first); ``mean`` skips NULL measures and an
all-NULL group's average is NaN."""


def reference(tables):
    ss = tables["store_sales"]
    cd = tables["customer_demographics"]
    d = tables["date_dim"]
    i = tables["item"]
    p = tables["promotion"]
    cd = cd[(cd.cd_gender == "M") & (cd.cd_marital_status == "S")
            & (cd.cd_education_status == "College")][["cd_demo_sk"]]
    d = d[d.d_year == 2000][["d_date_sk"]]
    p = p[(p.p_channel_email == "N") | (p.p_channel_event == "N")][
        ["p_promo_sk"]]
    ss = ss.dropna(subset=["ss_cdemo_sk", "ss_sold_date_sk", "ss_item_sk",
                           "ss_promo_sk"])
    m = (ss.merge(cd, left_on="ss_cdemo_sk", right_on="cd_demo_sk")
         .merge(d, left_on="ss_sold_date_sk", right_on="d_date_sk")
         .merge(i[["i_item_sk", "i_item_id"]], left_on="ss_item_sk",
                right_on="i_item_sk")
         .merge(p, left_on="ss_promo_sk", right_on="p_promo_sk"))
    out = (m.groupby("i_item_id")[["ss_quantity", "ss_list_price",
                                   "ss_coupon_amt", "ss_sales_price"]]
           .mean().reset_index()
           .sort_values("i_item_id", kind="stable").head(100)
           .reset_index(drop=True))
    out.columns = ["i_item_id", "agg1", "agg2", "agg3", "agg4"]
    return out
