"""TPC-DS breadth for the scale rig.

The reference's milestone ladder ends at full TPC-DS (BASELINE configs
3-4) and its scale suite spans join/agg/window shapes
(``integration_tests/.../scaletest/QuerySpecs.scala``).  Round 3 carried
5 TPC-DS shapes; this module adds 11 more in their REAL spec SQL form —
comma FROM star joins, derived tables, window-over-aggregate via
subquery, multi-alias dimension reuse, cross-joined scalar-subquery
blocks (q88), HAVING-range ticket analyses (q34/q73) — each checked
against an independent pandas oracle.

``build_tables`` is a superset of round 3's ``build_tpcds_tables``: the
original columns keep their names so the existing q3/q7/q19/q42/q89
runners work unchanged; new dimensions (store, household_demographics,
time_dim, customer, customer_address) and fact columns extend the star.
Filter constants are the spec's where possible, tuned only so scaled-down
data keeps results non-empty (plan-shape coverage is the point).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

# ---------------------------------------------------------------------------
# schema
# ---------------------------------------------------------------------------

_BUY_POTENTIAL = ["0-500", "501-1000", "1001-5000", "5001-10000", ">10000"]
_CITIES = ["Fairview", "Midway", "Oakdale", "Springdale", "Riverside",
           "Centerville", "Glendale", "Marion"]
_COUNTIES = ["C1", "C2", "C3", "C4"]
_STORE_NAMES = ["ese", "ought", "able", "pri", "bar"]
_FIRST = ["Ann", "Bob", "Cara", "Dev", "Eli", "Fay", "Gus", "Hana"]
_LAST = ["Ames", "Brown", "Cole", "Diaz", "Egan", "Ford", "Gray", "Hale"]
_STATES = ["CA", "WA", "GA", "TX", "NY", "OH", "FL", "MI"]
_ZIPS = [f"{z:05d}" for z in
         (85669, 86197, 88274, 83405, 80348, 81891, 60099, 90831,
          73065, 24128, 41904, 12477, 31678, 56557, 62544, 29741,
          48933, 74330, 95315, 67853)]
_SM_TYPES = ["EXPRESS", "OVERNIGHT", "REGULAR", "TWO DAY", "LIBRARY"]
_WH_NAMES = ["Conventional childr", "Important issues liv",
             "Doors canno", "Bad cards must make", "Rooms cook"]


def build_tables(rows: int, seed: int = 31) -> Dict[str, pa.Table]:
    rng = np.random.default_rng(seed)
    n_items = max(rows // 50, 20)
    n_dates = 365 * 5
    n_cd = 200
    n_promo = 50
    n_stores = 12
    n_hd = 144
    n_times = 24 * 12            # 5-minute buckets
    n_cust = max(rows // 20, 50)
    n_addr = max(n_cust // 2, 25)

    day = np.arange(n_dates)
    date_dim = pa.table({
        "d_date_sk": pa.array(day, type=pa.int64()),
        "d_year": pa.array(1998 + (day // 365), type=pa.int32()),
        "d_moy": pa.array(1 + (day % 365) // 31 % 12, type=pa.int32()),
        "d_dom": pa.array(1 + day % 28, type=pa.int32()),
        "d_dow": pa.array(day % 7, type=pa.int32()),
        "d_qoy": pa.array(1 + ((day % 365) // 92) % 4, type=pa.int32()),
    })
    item = pa.table({
        "i_item_sk": pa.array(np.arange(n_items), type=pa.int64()),
        "i_manufact_id": pa.array(rng.integers(0, 100, n_items),
                                  type=pa.int32()),
        "i_brand_id": pa.array(rng.integers(0, 40, n_items),
                               type=pa.int32()),
        "i_category_id": pa.array(rng.integers(0, 10, n_items),
                                  type=pa.int32()),
        "i_manager_id": pa.array(rng.integers(0, 100, n_items),
                                 type=pa.int32()),
        "i_brand": pa.array([f"brand#{b}" for b in
                             rng.integers(0, 40, n_items)]),
        "i_item_id": pa.array([f"ITEM{k:08d}" for k in range(n_items)]),
        "i_class_id": pa.array(rng.integers(0, 16, n_items),
                               type=pa.int32()),
        "i_current_price": pa.array(np.round(rng.random(n_items) * 99, 2)),
    })
    customer_demographics = pa.table({
        "cd_demo_sk": pa.array(np.arange(n_cd), type=pa.int64()),
        "cd_gender": pa.array(rng.choice(["M", "F"], n_cd)),
        "cd_marital_status": pa.array(rng.choice(["S", "M", "D", "W"],
                                                 n_cd)),
        "cd_education_status": pa.array(rng.choice(
            ["College", "Primary", "Secondary", "Advanced Degree"], n_cd)),
    })
    promotion = pa.table({
        "p_promo_sk": pa.array(np.arange(n_promo), type=pa.int64()),
        "p_channel_email": pa.array(rng.choice(["Y", "N"], n_promo)),
        "p_channel_event": pa.array(rng.choice(["Y", "N"], n_promo)),
    })
    store = pa.table({
        "s_store_sk": pa.array(np.arange(n_stores), type=pa.int64()),
        "s_store_name": pa.array(rng.choice(_STORE_NAMES, n_stores)),
        "s_city": pa.array(rng.choice(_CITIES, n_stores)),
        "s_county": pa.array(rng.choice(_COUNTIES, n_stores)),
        "s_number_employees": pa.array(rng.integers(150, 350, n_stores),
                                       type=pa.int32()),
    })
    household_demographics = pa.table({
        "hd_demo_sk": pa.array(np.arange(n_hd), type=pa.int64()),
        "hd_dep_count": pa.array(rng.integers(0, 10, n_hd),
                                 type=pa.int32()),
        "hd_vehicle_count": pa.array(rng.integers(0, 5, n_hd),
                                     type=pa.int32()),
        "hd_buy_potential": pa.array(rng.choice(_BUY_POTENTIAL, n_hd)),
    })
    tmark = np.arange(n_times)
    time_dim = pa.table({
        "t_time_sk": pa.array(tmark, type=pa.int64()),
        "t_hour": pa.array(tmark // 12, type=pa.int32()),
        "t_minute": pa.array((tmark % 12) * 5, type=pa.int32()),
    })
    customer = pa.table({
        "c_customer_sk": pa.array(np.arange(n_cust), type=pa.int64()),
        "c_first_name": pa.array(rng.choice(_FIRST, n_cust)),
        "c_last_name": pa.array(rng.choice(_LAST, n_cust)),
        "c_current_addr_sk": pa.array(rng.integers(0, n_addr, n_cust),
                                      type=pa.int64()),
        "c_current_cdemo_sk": pa.array(rng.integers(0, n_cd, n_cust),
                                       type=pa.int64()),
    })
    customer_address = pa.table({
        "ca_address_sk": pa.array(np.arange(n_addr), type=pa.int64()),
        "ca_city": pa.array(rng.choice(_CITIES, n_addr)),
        "ca_county": pa.array(rng.choice(_COUNTIES, n_addr)),
        "ca_state": pa.array(rng.choice(_STATES, n_addr)),
        "ca_zip": pa.array(rng.choice(_ZIPS, n_addr)),
    })
    n_wh = 5
    warehouse = pa.table({
        "w_warehouse_sk": pa.array(np.arange(n_wh), type=pa.int64()),
        "w_warehouse_name": pa.array(_WH_NAMES[:n_wh]),
        # deterministic round-robin, NOT rng.choice: q94/q95 filter on
        # w_state = 'CA' and a seed that drew no CA warehouse would
        # empty them at every scale
        "w_state": pa.array([_STATES[i % len(_STATES)]
                             for i in range(n_wh)]),
    })
    n_sm = len(_SM_TYPES)
    ship_mode = pa.table({
        "sm_ship_mode_sk": pa.array(np.arange(n_sm), type=pa.int64()),
        "sm_type": pa.array(_SM_TYPES),
    })

    # ticket-coherent fact generation: a ticket (basket) shares ONE
    # date/time/store/hdemo/customer/addr across its line items — the
    # property q34/q68/q73/q79's per-ticket count/sum semantics rely on
    # (per-row-independent dims would scatter each ticket across filters
    # and leave count-range predicates empty)
    n_tickets = max(rows // 8, 10)
    tk_date = rng.integers(0, n_dates, n_tickets)
    tk_time = rng.integers(0, n_times, n_tickets)
    tk_store = rng.integers(0, n_stores, n_tickets)
    tk_hd = rng.integers(0, n_hd, n_tickets)
    tk_cust = rng.integers(0, n_cust, n_tickets)
    tk_addr = rng.integers(0, n_addr, n_tickets)
    ticket = rng.integers(0, n_tickets, rows)
    store_sales = pa.table({
        "ss_sold_date_sk": pa.array(tk_date[ticket], type=pa.int64()),
        "ss_item_sk": pa.array(rng.integers(0, n_items, rows),
                               type=pa.int64()),
        "ss_ext_sales_price": pa.array(
            np.round(rng.random(rows) * 1000, 2)),
        "ss_cdemo_sk": pa.array(rng.integers(0, n_cd, rows),
                                type=pa.int64()),
        "ss_promo_sk": pa.array(rng.integers(0, n_promo, rows),
                                type=pa.int64()),
        "ss_quantity": pa.array(rng.integers(1, 100, rows),
                                type=pa.int32()),
        "ss_list_price": pa.array(np.round(rng.random(rows) * 200, 2)),
        "ss_coupon_amt": pa.array(np.round(rng.random(rows) * 50, 2)),
        "ss_store_sk": pa.array(tk_store[ticket], type=pa.int64()),
        "ss_hdemo_sk": pa.array(tk_hd[ticket], type=pa.int64()),
        "ss_sold_time_sk": pa.array(tk_time[ticket], type=pa.int64()),
        "ss_ticket_number": pa.array(ticket, type=pa.int64()),
        "ss_customer_sk": pa.array(tk_cust[ticket], type=pa.int64()),
        "ss_addr_sk": pa.array(tk_addr[ticket], type=pa.int64()),
        "ss_net_profit": pa.array(np.round(rng.random(rows) * 100 - 20, 2)),
        "ss_sales_price": pa.array(np.round(rng.random(rows) * 150, 2)),
        "ss_ext_list_price": pa.array(np.round(rng.random(rows) * 250, 2)),
        "ss_ext_tax": pa.array(np.round(rng.random(rows) * 30, 2)),
    })
    # cross-channel facts (round 5): catalog_sales/web_sales share the
    # customer and item keyspaces with store_sales so the INTERSECT/
    # EXCEPT/FULL-OUTER channel queries (q38/q87/q97/q11/q60...) produce
    # non-degenerate overlaps; store_returns derives from store_sales rows
    # so ticket+item joins (q93) and per-store return totals (q1) hit.
    n_cs = max(rows // 2, 20)
    catalog_sales = pa.table({
        "cs_sold_date_sk": pa.array(rng.integers(0, n_dates, n_cs),
                                    type=pa.int64()),
        "cs_bill_customer_sk": pa.array(rng.integers(0, n_cust, n_cs),
                                        type=pa.int64()),
        "cs_item_sk": pa.array(rng.integers(0, n_items, n_cs),
                               type=pa.int64()),
        "cs_quantity": pa.array(rng.integers(1, 100, n_cs),
                                type=pa.int32()),
        "cs_list_price": pa.array(np.round(rng.random(n_cs) * 200, 2)),
        "cs_ext_sales_price": pa.array(np.round(rng.random(n_cs) * 1000,
                                                2)),
        "cs_sales_price": pa.array(np.round(rng.random(n_cs) * 600, 2)),
        "cs_net_profit": pa.array(np.round(rng.random(n_cs) * 120 - 25,
                                           2)),
        "cs_sold_time_sk": pa.array(rng.integers(0, n_times, n_cs),
                                    type=pa.int64()),
        "cs_order_number": pa.array(
            rng.integers(0, max(n_cs // 3, 8), n_cs), type=pa.int64()),
        "cs_warehouse_sk": pa.array(rng.integers(0, n_wh, n_cs),
                                    type=pa.int64()),
        "cs_cdemo_sk": pa.array(rng.integers(0, n_cd, n_cs),
                                type=pa.int64()),
        "cs_promo_sk": pa.array(rng.integers(0, n_promo, n_cs),
                                type=pa.int64()),
    })
    n_cr = max(n_cs // 5, 8)
    cr_idx = rng.choice(n_cs, size=n_cr, replace=False)
    catalog_returns = pa.table({
        "cr_order_number": pa.array(
            np.asarray(catalog_sales.column("cs_order_number"))[cr_idx],
            type=pa.int64()),
        "cr_item_sk": pa.array(
            np.asarray(catalog_sales.column("cs_item_sk"))[cr_idx],
            type=pa.int64()),
        "cr_refunded_cash": pa.array(np.round(rng.random(n_cr) * 80, 2)),
    })
    n_inv = max(rows // 2, 40)
    # inventory concentrates on 50 items so per-(warehouse,item,month)
    # groups hold several samples — q39's stddev/mean needs group sizes
    # > 1 (stddev_samp of a singleton is NULL and the group drops)
    inv_items = min(n_items, 50)
    inventory = pa.table({
        "inv_date_sk": pa.array(rng.integers(800, 1100, n_inv),
                                type=pa.int64()),
        "inv_item_sk": pa.array(rng.integers(0, inv_items, n_inv),
                                type=pa.int64()),
        "inv_warehouse_sk": pa.array(rng.integers(0, n_wh, n_inv),
                                     type=pa.int64()),
        "inv_quantity_on_hand": pa.array(rng.integers(0, 1000, n_inv),
                                         type=pa.int32()),
    })
    n_ws = max(rows // 3, 20)
    ws_sold = rng.integers(0, n_dates, n_ws)
    n_orders = max(n_ws // 3, 8)
    web_sales = pa.table({
        "ws_sold_date_sk": pa.array(ws_sold, type=pa.int64()),
        "ws_bill_customer_sk": pa.array(rng.integers(0, n_cust, n_ws),
                                        type=pa.int64()),
        "ws_item_sk": pa.array(rng.integers(0, n_items, n_ws),
                               type=pa.int64()),
        "ws_quantity": pa.array(rng.integers(1, 100, n_ws),
                                type=pa.int32()),
        "ws_list_price": pa.array(np.round(rng.random(n_ws) * 200, 2)),
        "ws_ext_sales_price": pa.array(np.round(rng.random(n_ws) * 1000,
                                                2)),
        # shipping lag spreads across the 30/60/90/120-day bucket edges
        # (q62's CASE counts need every bucket populated)
        "ws_ship_date_sk": pa.array(
            np.minimum(ws_sold + rng.integers(1, 140, n_ws), n_dates - 1),
            type=pa.int64()),
        "ws_sold_time_sk": pa.array(rng.integers(0, n_times, n_ws),
                                    type=pa.int64()),
        "ws_order_number": pa.array(rng.integers(0, n_orders, n_ws),
                                    type=pa.int64()),
        "ws_warehouse_sk": pa.array(rng.integers(0, n_wh, n_ws),
                                    type=pa.int64()),
        "ws_ship_mode_sk": pa.array(rng.integers(0, n_sm, n_ws),
                                    type=pa.int64()),
        "ws_ship_hdemo_sk": pa.array(rng.integers(0, n_hd, n_ws),
                                     type=pa.int64()),
        "ws_ext_discount_amt": pa.array(np.round(rng.random(n_ws) * 80,
                                                 2)),
        "ws_ext_ship_cost": pa.array(np.round(rng.random(n_ws) * 40, 2)),
        "ws_net_profit": pa.array(np.round(rng.random(n_ws) * 110 - 20,
                                           2)),
    })
    n_wr = max(n_orders // 4, 4)
    web_returns = pa.table({
        "wr_order_number": pa.array(
            rng.choice(n_orders, size=n_wr, replace=False),
            type=pa.int64()),
        "wr_return_amt": pa.array(np.round(rng.random(n_wr) * 200, 2)),
    })
    n_sr = max(rows // 5, 10)
    ret_idx = rng.choice(rows, size=n_sr, replace=False)
    store_returns = pa.table({
        "sr_returned_date_sk": pa.array(rng.integers(0, n_dates, n_sr),
                                        type=pa.int64()),
        "sr_customer_sk": pa.array(
            np.asarray(store_sales.column("ss_customer_sk"))[ret_idx],
            type=pa.int64()),
        "sr_store_sk": pa.array(
            np.asarray(store_sales.column("ss_store_sk"))[ret_idx],
            type=pa.int64()),
        "sr_item_sk": pa.array(
            np.asarray(store_sales.column("ss_item_sk"))[ret_idx],
            type=pa.int64()),
        "sr_ticket_number": pa.array(
            np.asarray(store_sales.column("ss_ticket_number"))[ret_idx],
            type=pa.int64()),
        "sr_return_amt": pa.array(np.round(rng.random(n_sr) * 300, 2)),
        "sr_net_loss": pa.array(np.round(rng.random(n_sr) * 90, 2)),
    })
    # round-5 wave 5 extensions, drawn from a SEPARATE rng and appended
    # to the already-built tables so every earlier draw — and therefore
    # every existing table's bytes and every tuned oracle constant —
    # stays identical.  store.s_state is deterministic round-robin like
    # warehouse.w_state (rank/rollup queries must see every state at
    # every scale).
    rng2 = np.random.default_rng(seed + 101)
    store = store.append_column(
        "s_state", pa.array([_STATES[i % len(_STATES)]
                             for i in range(n_stores)]))
    n_cc = 6
    call_center = pa.table({
        "cc_call_center_sk": pa.array(np.arange(n_cc), type=pa.int64()),
        "cc_name": pa.array([f"call center {i}" for i in range(n_cc)]),
    })
    cs_sold = np.asarray(catalog_sales.column("cs_sold_date_sk"))
    catalog_sales = catalog_sales.append_column(
        "cs_ship_date_sk", pa.array(
            np.minimum(cs_sold + rng2.integers(1, 140, n_cs), n_dates - 1),
            type=pa.int64()))
    catalog_sales = catalog_sales.append_column(
        "cs_ship_mode_sk", pa.array(rng2.integers(0, n_sm, n_cs),
                                    type=pa.int64()))
    catalog_sales = catalog_sales.append_column(
        "cs_call_center_sk", pa.array(rng2.integers(0, n_cc, n_cs),
                                      type=pa.int64()))
    return {
        "store_sales": store_sales, "date_dim": date_dim, "item": item,
        "customer_demographics": customer_demographics,
        "promotion": promotion, "store": store,
        "household_demographics": household_demographics,
        "time_dim": time_dim, "customer": customer,
        "customer_address": customer_address,
        "catalog_sales": catalog_sales, "web_sales": web_sales,
        "store_returns": store_returns, "warehouse": warehouse,
        "ship_mode": ship_mode, "web_returns": web_returns,
        "catalog_returns": catalog_returns, "inventory": inventory,
        "call_center": call_center,
    }


# ---------------------------------------------------------------------------
# oracle helpers
# ---------------------------------------------------------------------------

def _sorted_frames(got: pd.DataFrame, exp: pd.DataFrame):
    """Sort both frames by the non-float columns first (every query here
    projects a unique non-float key set, so these fully determine row
    order), with rounded floats as inert tiebreakers."""
    def prep(df):
        df = df.copy()
        df.columns = list(range(len(df.columns)))
        keys = {}
        for c in df.columns:
            if df[c].dtype.kind not in "fc":
                keys[f"a{c}"] = df[c]
        for c in df.columns:
            if df[c].dtype.kind in "fc":
                keys[f"z{c}"] = df[c].astype(float).round(3)
        key_df = pd.DataFrame(keys)
        order = key_df.sort_values(list(key_df.columns),
                                   na_position="first").index
        return df.loc[order].reset_index(drop=True)
    return prep(got), prep(exp)


def _assert_rows(got: pd.DataFrame, exp: pd.DataFrame):
    """Order-insensitive frame equality with float tolerance (ORDER BY
    columns in these queries are not total orders, so row order between
    engines is not comparable — the multiset is)."""
    assert len(got) == len(exp), f"{len(got)} rows != {len(exp)}"
    assert len(got.columns) == len(exp.columns)
    assert len(exp) > 0, "oracle produced empty result — tune constants"
    g, e = _sorted_frames(got, exp)
    for c in g.columns:
        if g[c].dtype.kind == "f" or e[c].dtype.kind == "f":
            assert np.allclose(g[c].astype(float).fillna(np.nan),
                               e[c].astype(float).fillna(np.nan),
                               rtol=1e-6, atol=1e-6, equal_nan=True), c
        else:
            ga = np.asarray(g[c].astype(object).values)
            ea = np.asarray(e[c].astype(object).values)
            gm, em = pd.isna(ga), pd.isna(ea)
            # isna-masked equality: fillna('\0') is dtype-dependent under
            # pandas-3 str columns (object-cast NaN fills to '')
            assert (gm == em).all(), c
            assert (ga[~gm] == ea[~em]).all(), c


#: to_pandas results per table-set, STRONG-ref keyed by identity (the
#: strong ref makes id() recycling impossible; the rig passes one table
#: dict per suite, so at most one entry is live)
_pd_cache = [None, None]         # [tables_dict, {name: DataFrame}]


def _pd(t: Dict[str, pa.Table], name: str) -> pd.DataFrame:
    if _pd_cache[0] is not t:
        _pd_cache[0] = t
        _pd_cache[1] = {}
    cache = _pd_cache[1]
    if name not in cache:
        cache[name] = t[name].to_pandas()
    return cache[name].copy()


def _merged(t: Dict[str, pa.Table], with_: List[str]) -> pd.DataFrame:
    """store_sales joined to the requested dims, pandas-side (cached
    conversions: oracle pandas work lands in warm_seconds otherwise)."""
    keys = {
        "date_dim": ("ss_sold_date_sk", "d_date_sk"),
        "item": ("ss_item_sk", "i_item_sk"),
        "store": ("ss_store_sk", "s_store_sk"),
        "household_demographics": ("ss_hdemo_sk", "hd_demo_sk"),
        "time_dim": ("ss_sold_time_sk", "t_time_sk"),
        "customer": ("ss_customer_sk", "c_customer_sk"),
        "customer_demographics": ("ss_cdemo_sk", "cd_demo_sk"),
        "customer_address": ("ss_addr_sk", "ca_address_sk"),
    }
    pdf = _pd(t, "store_sales")
    for name in with_:
        l, r = keys[name]
        pdf = pdf.merge(_pd(t, name), left_on=l, right_on=r)
    return pdf


# ---------------------------------------------------------------------------
# queries: (name, sql, oracle(got_pdf, tables))
# ---------------------------------------------------------------------------

def _oracle_q34(got, t):
    pdf = _merged(t, ["date_dim", "store", "household_demographics"])
    pdf = pdf[((pdf.d_dom.between(1, 3)) | (pdf.d_dom.between(25, 28)))
              & (pdf.hd_buy_potential == "1001-5000")
              & (pdf.hd_vehicle_count > 0)
              & (pdf.d_year.isin([1998, 1999, 2000]))
              & (pdf.s_county == "C1")]
    dn = (pdf.groupby(["ss_ticket_number", "ss_customer_sk"])
          .size().reset_index(name="cnt"))
    dn = dn[dn.cnt.between(2, 20)]
    cust = _pd(t, "customer")
    exp = dn.merge(cust, left_on="ss_customer_sk",
                   right_on="c_customer_sk")[
        ["c_last_name", "c_first_name", "ss_ticket_number", "cnt"]]
    _assert_rows(got, exp)


_Q34 = """
SELECT c_last_name, c_first_name, ss_ticket_number, cnt
FROM (SELECT ss_ticket_number, ss_customer_sk, count(*) AS cnt
      FROM store_sales, date_dim, store, household_demographics
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND ss_hdemo_sk = hd_demo_sk
        AND (d_dom BETWEEN 1 AND 3 OR d_dom BETWEEN 25 AND 28)
        AND hd_buy_potential = '1001-5000' AND hd_vehicle_count > 0
        AND d_year IN (1998, 1999, 2000) AND s_county = 'C1'
      GROUP BY ss_ticket_number, ss_customer_sk) dn, customer
WHERE ss_customer_sk = c_customer_sk AND cnt BETWEEN 2 AND 20
ORDER BY c_last_name, c_first_name, ss_ticket_number DESC
"""


def _oracle_q52(got, t):
    pdf = _merged(t, ["date_dim", "item"])
    pdf = pdf[(pdf.i_manager_id <= 10) & (pdf.d_moy == 11)
              & (pdf.d_year == 2000)]
    exp = (pdf.groupby(["d_year", "i_brand_id"])
           .agg(ext_price=("ss_ext_sales_price", "sum")).reset_index())
    _assert_rows(got, exp)


_Q52 = """
SELECT d_year, i_brand_id, sum(ss_ext_sales_price) AS ext_price
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manager_id <= 10 AND d_moy = 11 AND d_year = 2000
GROUP BY d_year, i_brand_id
ORDER BY d_year, ext_price DESC
"""


def _oracle_q53(got, t):
    pdf = _merged(t, ["item", "date_dim", "store"])
    pdf = pdf[pdf.d_qoy.isin([1, 2]) & (pdf.i_class_id < 8)]
    grouped = (pdf.groupby(["i_manufact_id", "d_qoy"])
               .agg(sum_sales=("ss_sales_price", "sum")).reset_index())
    grouped["avg_quarterly_sales"] = grouped.groupby(
        "i_manufact_id")["sum_sales"].transform("mean")
    exp = grouped[["i_manufact_id", "d_qoy", "sum_sales",
                   "avg_quarterly_sales"]]
    _assert_rows(got, exp)


_Q53 = """
SELECT i_manufact_id, d_qoy, sum_sales,
       avg(sum_sales) OVER (PARTITION BY i_manufact_id)
         AS avg_quarterly_sales
FROM (SELECT i_manufact_id, d_qoy, sum(ss_sales_price) AS sum_sales
      FROM item, store_sales, date_dim, store
      WHERE ss_item_sk = i_item_sk AND ss_sold_date_sk = d_date_sk
        AND ss_store_sk = s_store_sk AND d_qoy IN (1, 2)
        AND i_class_id < 8
      GROUP BY i_manufact_id, d_qoy) tmp1
ORDER BY avg_quarterly_sales, sum_sales, i_manufact_id
"""


def _oracle_q55(got, t):
    pdf = _merged(t, ["date_dim", "item"])
    pdf = pdf[(pdf.i_manager_id.between(20, 40)) & (pdf.d_moy == 11)
              & (pdf.d_year == 1999)]
    exp = (pdf.groupby(["i_brand", "i_brand_id"])
           .agg(ext_price=("ss_ext_sales_price", "sum")).reset_index())
    exp = exp[["i_brand_id", "i_brand", "ext_price"]]
    _assert_rows(got, exp)


_Q55 = """
SELECT i_brand_id, i_brand, sum(ss_ext_sales_price) AS ext_price
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manager_id BETWEEN 20 AND 40 AND d_moy = 11 AND d_year = 1999
GROUP BY i_brand, i_brand_id
ORDER BY ext_price DESC, i_brand_id
"""


def _oracle_q68(got, t):
    pdf = _merged(t, ["date_dim", "store", "household_demographics",
                      "customer_address"])
    pdf = pdf[(pdf.d_dom.between(1, 2))
              & ((pdf.hd_dep_count == 4) | (pdf.hd_vehicle_count == 3))
              & (pdf.d_year.isin([1998, 1999, 2000]))
              & (pdf.s_city.isin(["Fairview", "Midway"]))]
    dn = (pdf.groupby(["ss_ticket_number", "ss_customer_sk", "ss_addr_sk",
                       "ca_city"])
          .agg(extended_price=("ss_ext_sales_price", "sum"),
               list_price=("ss_ext_list_price", "sum"),
               extended_tax=("ss_ext_tax", "sum")).reset_index()
          .rename(columns={"ca_city": "bought_city"}))
    cust = _pd(t, "customer")
    addr = _pd(t, "customer_address")
    exp = (dn.merge(cust, left_on="ss_customer_sk",
                    right_on="c_customer_sk")
           .merge(addr, left_on="c_current_addr_sk",
                  right_on="ca_address_sk"))
    exp = exp[exp.ca_city != exp.bought_city][
        ["c_last_name", "c_first_name", "ca_city", "bought_city",
         "ss_ticket_number", "ss_addr_sk", "extended_price",
         "extended_tax", "list_price"]]
    _assert_rows(got, exp)


_Q68 = """
SELECT c_last_name, c_first_name, current_addr.ca_city, bought_city,
       ss_ticket_number, ss_addr_sk, extended_price, extended_tax,
       list_price
FROM (SELECT ss_ticket_number, ss_customer_sk, ss_addr_sk,
             ca_city AS bought_city,
             sum(ss_ext_sales_price) AS extended_price,
             sum(ss_ext_list_price) AS list_price,
             sum(ss_ext_tax) AS extended_tax
      FROM store_sales, date_dim, store, household_demographics,
           customer_address
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND ss_hdemo_sk = hd_demo_sk AND ss_addr_sk = ca_address_sk
        AND d_dom BETWEEN 1 AND 2
        AND (hd_dep_count = 4 OR hd_vehicle_count = 3)
        AND d_year IN (1998, 1999, 2000)
        AND s_city IN ('Fairview', 'Midway')
      GROUP BY ss_ticket_number, ss_customer_sk, ss_addr_sk, ca_city) dn,
     customer, customer_address current_addr
WHERE ss_customer_sk = c_customer_sk
  AND customer.c_current_addr_sk = current_addr.ca_address_sk
  AND current_addr.ca_city <> bought_city
ORDER BY c_last_name, ss_ticket_number
"""


def _oracle_q73(got, t):
    pdf = _merged(t, ["date_dim", "store", "household_demographics"])
    pdf = pdf[(pdf.d_dom.between(1, 2))
              & (pdf.hd_buy_potential.isin(["501-1000", ">10000"]))
              & (pdf.hd_vehicle_count > 0)
              & (pdf.d_year.isin([1998, 1999, 2000]))
              & (pdf.s_county.isin(["C1", "C2"]))]
    dn = (pdf.groupby(["ss_ticket_number", "ss_customer_sk"])
          .size().reset_index(name="cnt"))
    dn = dn[dn.cnt.between(1, 5)]
    cust = _pd(t, "customer")
    exp = dn.merge(cust, left_on="ss_customer_sk",
                   right_on="c_customer_sk")[
        ["c_last_name", "c_first_name", "ss_ticket_number", "cnt"]]
    _assert_rows(got, exp)


_Q73 = """
SELECT c_last_name, c_first_name, ss_ticket_number, cnt
FROM (SELECT ss_ticket_number, ss_customer_sk, count(*) AS cnt
      FROM store_sales, date_dim, store, household_demographics
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND ss_hdemo_sk = hd_demo_sk AND d_dom BETWEEN 1 AND 2
        AND hd_buy_potential IN ('501-1000', '>10000')
        AND hd_vehicle_count > 0 AND d_year IN (1998, 1999, 2000)
        AND s_county IN ('C1', 'C2')
      GROUP BY ss_ticket_number, ss_customer_sk) dj, customer
WHERE ss_customer_sk = c_customer_sk AND cnt BETWEEN 1 AND 5
ORDER BY cnt DESC, c_last_name
"""


def _oracle_q79(got, t):
    pdf = _merged(t, ["date_dim", "store", "household_demographics"])
    pdf = pdf[((pdf.hd_dep_count == 6) | (pdf.hd_vehicle_count > 2))
              & (pdf.d_dow == 1) & (pdf.d_year.isin([1998, 1999, 2000]))
              & (pdf.s_number_employees.between(200, 295))]
    ms = (pdf.groupby(["ss_ticket_number", "ss_customer_sk", "ss_addr_sk",
                       "s_city"])
          .agg(amt=("ss_coupon_amt", "sum"),
               profit=("ss_net_profit", "sum")).reset_index())
    cust = _pd(t, "customer")
    exp = ms.merge(cust, left_on="ss_customer_sk",
                   right_on="c_customer_sk")
    exp["city30"] = exp.s_city.str[:30]
    exp = exp[["c_last_name", "c_first_name", "city30",
               "ss_ticket_number", "ss_addr_sk", "amt", "profit"]]
    _assert_rows(got, exp)


_Q79 = """
SELECT c_last_name, c_first_name, substr(s_city, 1, 30) AS city30,
       ss_ticket_number, ss_addr_sk, amt, profit
FROM (SELECT ss_ticket_number, ss_customer_sk, ss_addr_sk, s_city,
             sum(ss_coupon_amt) AS amt, sum(ss_net_profit) AS profit
      FROM store_sales, date_dim, store, household_demographics
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND ss_hdemo_sk = hd_demo_sk
        AND (hd_dep_count = 6 OR hd_vehicle_count > 2)
        AND d_dow = 1 AND d_year IN (1998, 1999, 2000)
        AND s_number_employees BETWEEN 200 AND 295
      GROUP BY ss_ticket_number, ss_customer_sk, ss_addr_sk, s_city) ms,
     customer
WHERE ss_customer_sk = c_customer_sk
ORDER BY c_last_name, c_first_name, city30, profit
"""


def _count_bucket(t, h0, m0, m1, dep):
    pdf = _merged(t, ["household_demographics", "time_dim", "store"])
    pdf = pdf[(pdf.t_hour == h0) & (pdf.t_minute >= m0)
              & (pdf.t_minute < m1) & (pdf.hd_dep_count == dep)
              & (pdf.s_store_name == "ese")]
    return len(pdf)


def _oracle_q88(got, t):
    exp = pd.DataFrame({
        "h8_30_to_9": [_count_bucket(t, 8, 30, 60, 3)],
        "h9_to_9_30": [_count_bucket(t, 9, 0, 30, 3)],
        "h9_30_to_10": [_count_bucket(t, 9, 30, 60, 3)],
        "h10_to_10_30": [_count_bucket(t, 10, 0, 30, 3)],
    })
    _assert_rows(got, exp)


def _q88_block(alias, hour, m0, m1):
    cmp_m = f"t_minute >= {m0} AND t_minute < {m1}"
    return (f"(SELECT count(*) AS {alias} "
            f"FROM store_sales, household_demographics, time_dim, store "
            f"WHERE ss_sold_time_sk = t_time_sk "
            f"AND ss_hdemo_sk = hd_demo_sk AND ss_store_sk = s_store_sk "
            f"AND t_hour = {hour} AND {cmp_m} "
            f"AND hd_dep_count = 3 AND s_store_name = 'ese')")


_Q88 = f"""
SELECT * FROM
 {_q88_block('h8_30_to_9', 8, 30, 60)} s1,
 {_q88_block('h9_to_9_30', 9, 0, 30)} s2,
 {_q88_block('h9_30_to_10', 9, 30, 60)} s3,
 {_q88_block('h10_to_10_30', 10, 0, 30)} s4
"""


def _oracle_q96(got, t):
    pdf = _merged(t, ["household_demographics", "time_dim", "store"])
    pdf = pdf[(pdf.t_hour == 20) & (pdf.t_minute >= 30)
              & (pdf.hd_dep_count == 7) & (pdf.s_store_name == "ese")]
    _assert_rows(got, pd.DataFrame({"cnt": [len(pdf)]}))


_Q96 = """
SELECT count(*) AS cnt
FROM store_sales, household_demographics, time_dim, store
WHERE ss_sold_time_sk = t_time_sk AND ss_hdemo_sk = hd_demo_sk
  AND ss_store_sk = s_store_sk AND t_hour = 20 AND t_minute >= 30
  AND hd_dep_count = 7 AND s_store_name = 'ese'
"""


def _oracle_q98(got, t):
    """A simplified q98 (ids and ``d_year`` in place of the strings and
    the thirty days).  The official ``query98.tpl`` text with its
    qualification parameters is ``benchmarks/queries/tpcds_q98.sql``, tested
    against ``benchmarks/reference/tpcds_q98.py`` in
    ``tests/test_tpcds_report.py`` and measured by the cell
    ``tpcds-sf100-report-q98``."""
    pdf = _merged(t, ["date_dim", "item"])
    pdf = pdf[pdf.i_category_id.isin([1, 2, 3]) & (pdf.d_year == 1999)]
    grouped = (pdf.groupby(["i_item_id", "i_category_id", "i_class_id",
                            "i_current_price"])
               .agg(itemrevenue=("ss_ext_sales_price", "sum"))
               .reset_index())
    grouped["revenueratio"] = (grouped.itemrevenue * 100 /
                               grouped.groupby("i_class_id")["itemrevenue"]
                               .transform("sum"))
    _assert_rows(got, grouped)


_Q98 = """
SELECT i_item_id, i_category_id, i_class_id, i_current_price,
       itemrevenue,
       itemrevenue * 100 / sum(itemrevenue)
         OVER (PARTITION BY i_class_id) AS revenueratio
FROM (SELECT i_item_id, i_category_id, i_class_id, i_current_price,
             sum(ss_ext_sales_price) AS itemrevenue
      FROM store_sales, item, date_dim
      WHERE ss_item_sk = i_item_sk AND ss_sold_date_sk = d_date_sk
        AND i_category_id IN (1, 2, 3) AND d_year = 1999
      GROUP BY i_item_id, i_category_id, i_class_id,
               i_current_price) grouped
ORDER BY i_category_id, i_class_id, i_item_id, revenueratio
"""


def _oracle_q42(got, t):
    pdf = _merged(t, ["date_dim", "item"])
    pdf = pdf[(pdf.i_manager_id <= 15) & (pdf.d_moy == 12)
              & (pdf.d_year == 2000)]
    exp = (pdf.groupby(["d_year", "i_category_id"])
           .agg(s=("ss_ext_sales_price", "sum")).reset_index())
    _assert_rows(got, exp)


_Q42_SQL = """
SELECT d_year, i_category_id, sum(ss_ext_sales_price) AS s
FROM date_dim, store_sales, item
WHERE d_date_sk = ss_sold_date_sk AND ss_item_sk = i_item_sk
  AND i_manager_id <= 15 AND d_moy = 12 AND d_year = 2000
GROUP BY d_year, i_category_id
ORDER BY s DESC, d_year, i_category_id
"""


def _oracle_q59ish(got, t):
    """Weekly revenue by (store, dow) with a self-comparison ratio across
    two year halves — the q59 shape reduced to one join level."""
    pdf = _merged(t, ["date_dim", "store"])
    h1 = pdf[pdf.d_year == 1998]
    h2 = pdf[pdf.d_year == 1999]
    a = (h1.groupby(["s_store_name", "d_dow"])
         .agg(rev1=("ss_ext_sales_price", "sum")).reset_index())
    b = (h2.groupby(["s_store_name", "d_dow"])
         .agg(rev2=("ss_ext_sales_price", "sum")).reset_index())
    exp = a.merge(b, on=["s_store_name", "d_dow"])
    exp["ratio"] = exp.rev2 / exp.rev1
    _assert_rows(got, exp)


_Q59ISH = """
SELECT y1.s_store_name, y1.d_dow, y1.rev1, y2.rev2,
       y2.rev2 / y1.rev1 AS ratio
FROM (SELECT s_store_name, d_dow, sum(ss_ext_sales_price) AS rev1
      FROM store_sales, date_dim, store
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND d_year = 1998
      GROUP BY s_store_name, d_dow) y1,
     (SELECT s_store_name, d_dow, sum(ss_ext_sales_price) AS rev2
      FROM store_sales, date_dim, store
      WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
        AND d_year = 1999
      GROUP BY s_store_name, d_dow) y2
WHERE y1.s_store_name = y2.s_store_name AND y1.d_dow = y2.d_dow
ORDER BY y1.s_store_name, y1.d_dow
"""


# ---------------------------------------------------------------------------
# round-5 additions: multi-CTE / set-operation / subquery planner stress
# (the TPC-DS stragglers that exercise INTERSECT/EXCEPT,
# FULL OUTER JOIN, CTE self-joins, correlated subqueries, EXISTS chains
# and ROLLUP rather than re-covering star joins)
# ---------------------------------------------------------------------------

def _channel_customers(t, fact, cust_col, date_col, year):
    """Distinct (last, first, customer_sk) triples active in a channel.
    customer_sk keeps the domain customer-sized: the 8x8 name-pair pool
    saturates at rig scale, which would let a no-op INTERSECT or an
    always-empty EXCEPT pass undetected."""
    f = _pd(t, fact)
    f = f[f[date_col].map(
        _pd(t, "date_dim").set_index("d_date_sk")["d_year"]) == year]
    cust = _pd(t, "customer")
    m = f.merge(cust, left_on=cust_col, right_on="c_customer_sk")
    return set(zip(m.c_last_name, m.c_first_name, m.c_customer_sk))


def _oracle_q38(got, t):
    s = _channel_customers(t, "store_sales", "ss_customer_sk",
                           "ss_sold_date_sk", 1999)
    c = _channel_customers(t, "catalog_sales", "cs_bill_customer_sk",
                           "cs_sold_date_sk", 1999)
    w = _channel_customers(t, "web_sales", "ws_bill_customer_sk",
                           "ws_sold_date_sk", 1999)
    exp = pd.DataFrame({"num": [len(s & c & w)]})
    _assert_rows(got, exp)


_Q38 = """
SELECT count(*) AS num FROM (
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM store_sales, date_dim, customer
  WHERE ss_sold_date_sk = d_date_sk AND ss_customer_sk = c_customer_sk
    AND d_year = 1999
  INTERSECT
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM catalog_sales, date_dim, customer
  WHERE cs_sold_date_sk = d_date_sk AND cs_bill_customer_sk = c_customer_sk
    AND d_year = 1999
  INTERSECT
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM web_sales, date_dim, customer
  WHERE ws_sold_date_sk = d_date_sk AND ws_bill_customer_sk = c_customer_sk
    AND d_year = 1999
) hot_cust
"""


def _oracle_q87(got, t):
    s = _channel_customers(t, "store_sales", "ss_customer_sk",
                           "ss_sold_date_sk", 1999)
    c = _channel_customers(t, "catalog_sales", "cs_bill_customer_sk",
                           "cs_sold_date_sk", 1999)
    w = _channel_customers(t, "web_sales", "ws_bill_customer_sk",
                           "ws_sold_date_sk", 1999)
    exp = pd.DataFrame({"num": [len(s - c - w)]})
    _assert_rows(got, exp)


_Q87 = """
SELECT count(*) AS num FROM (
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM store_sales, date_dim, customer
  WHERE ss_sold_date_sk = d_date_sk AND ss_customer_sk = c_customer_sk
    AND d_year = 1999
  EXCEPT
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM catalog_sales, date_dim, customer
  WHERE cs_sold_date_sk = d_date_sk AND cs_bill_customer_sk = c_customer_sk
    AND d_year = 1999
  EXCEPT
  SELECT DISTINCT c_last_name, c_first_name, c_customer_sk
  FROM web_sales, date_dim, customer
  WHERE ws_sold_date_sk = d_date_sk AND ws_bill_customer_sk = c_customer_sk
    AND d_year = 1999
) cool_cust
"""


def _channel_pairs(t, fact, cust_col, item_col, date_col, year):
    f = _pd(t, fact)
    f = f[f[date_col].map(
        _pd(t, "date_dim").set_index("d_date_sk")["d_year"]) == year]
    return f[[cust_col, item_col]].drop_duplicates()


def _oracle_q97(got, t):
    s = _channel_pairs(t, "store_sales", "ss_customer_sk", "ss_item_sk",
                       "ss_sold_date_sk", 1999)
    c = _channel_pairs(t, "catalog_sales", "cs_bill_customer_sk",
                       "cs_item_sk", "cs_sold_date_sk", 1999)
    m = s.merge(c, left_on=["ss_customer_sk", "ss_item_sk"],
                right_on=["cs_bill_customer_sk", "cs_item_sk"],
                how="outer", indicator=True)
    exp = pd.DataFrame({
        "store_only": [int((m._merge == "left_only").sum())],
        "catalog_only": [int((m._merge == "right_only").sum())],
        "store_and_catalog": [int((m._merge == "both").sum())],
    })
    _assert_rows(got, exp)


_Q97 = """
WITH ssci AS (
  SELECT ss_customer_sk AS customer_sk, ss_item_sk AS item_sk
  FROM store_sales, date_dim
  WHERE ss_sold_date_sk = d_date_sk AND d_year = 1999
  GROUP BY ss_customer_sk, ss_item_sk),
csci AS (
  SELECT cs_bill_customer_sk AS customer_sk, cs_item_sk AS item_sk
  FROM catalog_sales, date_dim
  WHERE cs_sold_date_sk = d_date_sk AND d_year = 1999
  GROUP BY cs_bill_customer_sk, cs_item_sk)
SELECT sum(CASE WHEN ssci.customer_sk IS NOT NULL
                 AND csci.customer_sk IS NULL THEN 1 ELSE 0 END)
         AS store_only,
       sum(CASE WHEN ssci.customer_sk IS NULL
                 AND csci.customer_sk IS NOT NULL THEN 1 ELSE 0 END)
         AS catalog_only,
       sum(CASE WHEN ssci.customer_sk IS NOT NULL
                 AND csci.customer_sk IS NOT NULL THEN 1 ELSE 0 END)
         AS store_and_catalog
FROM ssci FULL OUTER JOIN csci
  ON (ssci.customer_sk = csci.customer_sk
      AND ssci.item_sk = csci.item_sk)
"""


def _year_totals(t, fact, cust_col, date_col, price_col):
    f = _pd(t, fact)
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    f = f.assign(dyear=f[date_col].map(dd))
    return (f.groupby([cust_col, "dyear"])[price_col].sum()
            .reset_index().rename(columns={cust_col: "customer_sk",
                                           price_col: "year_total"}))


def _oracle_q11(got, t):
    s = _year_totals(t, "store_sales", "ss_customer_sk",
                     "ss_sold_date_sk", "ss_ext_sales_price")
    w = _year_totals(t, "web_sales", "ws_bill_customer_sk",
                     "ws_sold_date_sk", "ws_ext_sales_price")

    def year(df, y):
        return df[df.dyear == y].set_index("customer_sk")["year_total"]
    sf, ss2 = year(s, 1999), year(s, 2000)
    wf, ws2 = year(w, 1999), year(w, 2000)
    idx = sf.index.intersection(ss2.index).intersection(
        wf.index).intersection(ws2.index)
    idx = idx[(sf[idx] > 0) & (wf[idx] > 0)]
    keep = idx[(ws2[idx] / wf[idx]) > (ss2[idx] / sf[idx])]
    exp = pd.DataFrame({"customer_sk": sorted(keep)})
    _assert_rows(got, exp)


_Q11 = """
WITH year_total AS (
  SELECT ss_customer_sk AS customer_sk, d_year AS dyear,
         sum(ss_ext_sales_price) AS year_total, 's' AS sale_type
  FROM store_sales, date_dim
  WHERE ss_sold_date_sk = d_date_sk
  GROUP BY ss_customer_sk, d_year
  UNION ALL
  SELECT ws_bill_customer_sk, d_year, sum(ws_ext_sales_price), 'w'
  FROM web_sales, date_dim
  WHERE ws_sold_date_sk = d_date_sk
  GROUP BY ws_bill_customer_sk, d_year)
SELECT t_s_secyear.customer_sk
FROM year_total t_s_firstyear, year_total t_s_secyear,
     year_total t_w_firstyear, year_total t_w_secyear
WHERE t_s_secyear.customer_sk = t_s_firstyear.customer_sk
  AND t_s_firstyear.customer_sk = t_w_secyear.customer_sk
  AND t_s_firstyear.customer_sk = t_w_firstyear.customer_sk
  AND t_s_firstyear.sale_type = 's' AND t_w_firstyear.sale_type = 'w'
  AND t_s_secyear.sale_type = 's' AND t_w_secyear.sale_type = 'w'
  AND t_s_firstyear.dyear = 1999 AND t_s_secyear.dyear = 2000
  AND t_w_firstyear.dyear = 1999 AND t_w_secyear.dyear = 2000
  AND t_s_firstyear.year_total > 0 AND t_w_firstyear.year_total > 0
  AND t_w_secyear.year_total / t_w_firstyear.year_total
      > t_s_secyear.year_total / t_s_firstyear.year_total
ORDER BY t_s_secyear.customer_sk
"""


def _oracle_q31(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")
    addr = _pd(t, "customer_address")
    ss = _merged(t, ["customer_address"])
    ss = ss.assign(d_qoy=ss.ss_sold_date_sk.map(dd.d_qoy),
                   d_year=ss.ss_sold_date_sk.map(dd.d_year))
    ssg = (ss[ss.d_year == 2000].groupby(["ca_county", "d_qoy"])
           ["ss_ext_sales_price"].sum())
    ws = _pd(t, "web_sales").merge(
        _pd(t, "customer"), left_on="ws_bill_customer_sk",
        right_on="c_customer_sk").merge(
        addr, left_on="c_current_addr_sk", right_on="ca_address_sk")
    ws = ws.assign(d_qoy=ws.ws_sold_date_sk.map(dd.d_qoy),
                   d_year=ws.ws_sold_date_sk.map(dd.d_year))
    wsg = (ws[ws.d_year == 2000].groupby(["ca_county", "d_qoy"])
           ["ws_ext_sales_price"].sum())
    rows = []
    for county in addr.ca_county.unique():
        try:
            sg = ssg[(county, 2)] / ssg[(county, 1)]
            wg = wsg[(county, 2)] / wsg[(county, 1)]
        except KeyError:
            continue
        rows.append((county, sg, wg, 1 if wg > sg else 0))
    exp = pd.DataFrame(rows, columns=["ca_county", "store_growth",
                                      "web_growth", "web_faster"])
    _assert_rows(got, exp)


_Q31 = """
WITH ss AS (
  SELECT ca_county, d_qoy, d_year,
         sum(ss_ext_sales_price) AS store_sales_total
  FROM store_sales, date_dim, customer_address
  WHERE ss_sold_date_sk = d_date_sk AND ss_addr_sk = ca_address_sk
  GROUP BY ca_county, d_qoy, d_year),
ws AS (
  SELECT ca_county, d_qoy, d_year,
         sum(ws_ext_sales_price) AS web_sales_total
  FROM web_sales, date_dim, customer, customer_address
  WHERE ws_sold_date_sk = d_date_sk
    AND ws_bill_customer_sk = c_customer_sk
    AND c_current_addr_sk = ca_address_sk
  GROUP BY ca_county, d_qoy, d_year)
SELECT ss1.ca_county,
       ss2.store_sales_total / ss1.store_sales_total AS store_growth,
       ws2.web_sales_total / ws1.web_sales_total AS web_growth,
       CASE WHEN ws2.web_sales_total / ws1.web_sales_total
                 > ss2.store_sales_total / ss1.store_sales_total
            THEN 1 ELSE 0 END AS web_faster
FROM ss ss1, ss ss2, ws ws1, ws ws2
WHERE ss1.ca_county = ss2.ca_county AND ss1.ca_county = ws1.ca_county
  AND ss1.ca_county = ws2.ca_county
  AND ss1.d_qoy = 1 AND ss2.d_qoy = 2 AND ws1.d_qoy = 1 AND ws2.d_qoy = 2
  AND ss1.d_year = 2000 AND ss2.d_year = 2000
  AND ws1.d_year = 2000 AND ws2.d_year = 2000
ORDER BY ss1.ca_county
"""


def _oracle_q60(got, t):
    item = _pd(t, "item")
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]

    def chan(fact, item_col, date_col, price):
        f = _pd(t, fact)
        f = f[f[date_col].map(dd) == 1999]
        m = f.merge(item, left_on=item_col, right_on="i_item_sk")
        m = m[m.i_category_id == 3]
        return m.groupby("i_item_id")[price].sum()
    tot = (chan("store_sales", "ss_item_sk", "ss_sold_date_sk",
                "ss_ext_sales_price")
           .add(chan("catalog_sales", "cs_item_sk", "cs_sold_date_sk",
                     "cs_ext_sales_price"), fill_value=0)
           .add(chan("web_sales", "ws_item_sk", "ws_sold_date_sk",
                     "ws_ext_sales_price"), fill_value=0))
    exp = tot.reset_index()
    exp.columns = ["i_item_id", "total_sales"]
    _assert_rows(got, exp)


_Q60 = """
WITH ss AS (
  SELECT i_item_id, sum(ss_ext_sales_price) AS total_sales
  FROM store_sales, date_dim, item
  WHERE ss_sold_date_sk = d_date_sk AND ss_item_sk = i_item_sk
    AND i_category_id = 3 AND d_year = 1999
  GROUP BY i_item_id),
cs AS (
  SELECT i_item_id, sum(cs_ext_sales_price) AS total_sales
  FROM catalog_sales, date_dim, item
  WHERE cs_sold_date_sk = d_date_sk AND cs_item_sk = i_item_sk
    AND i_category_id = 3 AND d_year = 1999
  GROUP BY i_item_id),
ws AS (
  SELECT i_item_id, sum(ws_ext_sales_price) AS total_sales
  FROM web_sales, date_dim, item
  WHERE ws_sold_date_sk = d_date_sk AND ws_item_sk = i_item_sk
    AND i_category_id = 3 AND d_year = 1999
  GROUP BY i_item_id)
SELECT i_item_id, sum(total_sales) AS total_sales
FROM (SELECT * FROM ss UNION ALL SELECT * FROM cs
      UNION ALL SELECT * FROM ws) tmp1
GROUP BY i_item_id
ORDER BY i_item_id, total_sales
"""


def _oracle_q1(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    sr = _pd(t, "store_returns")
    sr = sr[sr.sr_returned_date_sk.map(dd) == 2000]
    ctr = (sr.groupby(["sr_customer_sk", "sr_store_sk"])["sr_return_amt"]
           .sum().reset_index(name="ctr_total_return"))
    avg = (ctr.groupby("sr_store_sk")["ctr_total_return"].mean() * 1.2)
    ctr = ctr[ctr.ctr_total_return > ctr.sr_store_sk.map(avg)]
    store = _pd(t, "store")
    keep_stores = set(store[store.s_county == "C1"].s_store_sk)
    ctr = ctr[ctr.sr_store_sk.isin(keep_stores)]
    cust = _pd(t, "customer")
    exp = ctr.merge(cust, left_on="sr_customer_sk",
                    right_on="c_customer_sk")[
        ["c_customer_sk", "c_first_name", "c_last_name"]]
    _assert_rows(got, exp)


_Q1 = """
WITH customer_total_return AS (
  SELECT sr_customer_sk AS ctr_customer_sk, sr_store_sk AS ctr_store_sk,
         sum(sr_return_amt) AS ctr_total_return
  FROM store_returns, date_dim
  WHERE sr_returned_date_sk = d_date_sk AND d_year = 2000
  GROUP BY sr_customer_sk, sr_store_sk)
SELECT c_customer_sk, c_first_name, c_last_name
FROM customer_total_return ctr1, store, customer
WHERE ctr1.ctr_total_return >
      (SELECT avg(ctr_total_return) * 1.2
       FROM customer_total_return ctr2
       WHERE ctr1.ctr_store_sk = ctr2.ctr_store_sk)
  AND s_store_sk = ctr1.ctr_store_sk AND s_county = 'C1'
  AND ctr1.ctr_customer_sk = c_customer_sk
ORDER BY c_customer_sk
"""


def _oracle_q93(got, t):
    ss = _pd(t, "store_sales")
    sr = _pd(t, "store_returns")[["sr_ticket_number", "sr_item_sk",
                                  "sr_return_amt"]]
    m = ss.merge(sr, left_on=["ss_ticket_number", "ss_item_sk"],
                 right_on=["sr_ticket_number", "sr_item_sk"], how="left")
    act = np.where(m.sr_ticket_number.notna(),
                   m.ss_sales_price * (m.ss_quantity - 1),
                   m.ss_sales_price * m.ss_quantity)
    exp = (pd.DataFrame({"ss_customer_sk": m.ss_customer_sk,
                         "act_sales": act})
           .groupby("ss_customer_sk")["act_sales"].sum()
           .reset_index(name="sumsales"))
    _assert_rows(got, exp)


_Q93 = """
SELECT ss_customer_sk, sum(act_sales) AS sumsales
FROM (SELECT ss_customer_sk,
             CASE WHEN sr_ticket_number IS NOT NULL
                  THEN ss_sales_price * (ss_quantity - 1)
                  ELSE ss_sales_price * ss_quantity END AS act_sales
      FROM store_sales LEFT JOIN store_returns
        ON sr_ticket_number = ss_ticket_number
       AND sr_item_sk = ss_item_sk) t
GROUP BY ss_customer_sk
ORDER BY sumsales, ss_customer_sk
"""


def _oracle_q69(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]

    def active(fact, cust_col, date_col):
        f = _pd(t, fact)
        return set(f[f[date_col].map(dd) == 2000][cust_col])
    s = active("store_sales", "ss_customer_sk", "ss_sold_date_sk")
    w = active("web_sales", "ws_bill_customer_sk", "ws_sold_date_sk")
    c = active("catalog_sales", "cs_bill_customer_sk", "cs_sold_date_sk")
    cust = _pd(t, "customer")
    addr = _pd(t, "customer_address")
    cd = _pd(t, "customer_demographics")
    m = cust.merge(addr, left_on="c_current_addr_sk",
                   right_on="ca_address_sk")
    m = m[m.ca_county.isin(["C1", "C2"])]
    m = m[m.c_customer_sk.isin(s - w - c)]
    m = m.merge(cd, left_on="c_current_cdemo_sk", right_on="cd_demo_sk")
    exp = (m.groupby(["cd_gender", "cd_marital_status",
                      "cd_education_status"])
           .size().reset_index(name="cnt"))
    _assert_rows(got, exp)


_Q69 = """
SELECT cd_gender, cd_marital_status, cd_education_status,
       count(*) AS cnt
FROM customer c, customer_address ca, customer_demographics
WHERE c.c_current_addr_sk = ca.ca_address_sk
  AND ca_county IN ('C1', 'C2')
  AND cd_demo_sk = c.c_current_cdemo_sk
  AND EXISTS (SELECT * FROM store_sales, date_dim
              WHERE c.c_customer_sk = ss_customer_sk
                AND ss_sold_date_sk = d_date_sk AND d_year = 2000)
  AND NOT EXISTS (SELECT * FROM web_sales, date_dim
                  WHERE c.c_customer_sk = ws_bill_customer_sk
                    AND ws_sold_date_sk = d_date_sk AND d_year = 2000)
  AND NOT EXISTS (SELECT * FROM catalog_sales, date_dim
                  WHERE c.c_customer_sk = cs_bill_customer_sk
                    AND cs_sold_date_sk = d_date_sk AND d_year = 2000)
GROUP BY cd_gender, cd_marital_status, cd_education_status
ORDER BY cd_gender, cd_marital_status, cd_education_status
"""


def _oracle_q65(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    ss = _pd(t, "store_sales")
    ss = ss[ss.ss_sold_date_sk.map(dd) == 1999]
    sa = (ss.groupby(["ss_store_sk", "ss_item_sk"])["ss_sales_price"]
          .sum().reset_index(name="revenue"))
    ave = sa.groupby("ss_store_sk")["revenue"].mean()
    sa = sa[sa.revenue <= 0.5 * sa.ss_store_sk.map(ave)]
    store = _pd(t, "store")
    item = _pd(t, "item")
    exp = (sa.merge(store, left_on="ss_store_sk", right_on="s_store_sk")
           .merge(item, left_on="ss_item_sk", right_on="i_item_sk")[
               ["s_store_name", "i_item_id", "revenue"]])
    _assert_rows(got, exp)


_Q65 = """
WITH sa AS (
  SELECT ss_store_sk, ss_item_sk, sum(ss_sales_price) AS revenue
  FROM store_sales, date_dim
  WHERE ss_sold_date_sk = d_date_sk AND d_year = 1999
  GROUP BY ss_store_sk, ss_item_sk),
sc AS (
  SELECT ss_store_sk, avg(revenue) AS ave FROM sa GROUP BY ss_store_sk)
SELECT s_store_name, i_item_id, sa.revenue
FROM store, item, sa, sc
WHERE sa.ss_store_sk = sc.ss_store_sk AND sa.revenue <= 0.5 * sc.ave
  AND s_store_sk = sa.ss_store_sk AND i_item_sk = sa.ss_item_sk
ORDER BY s_store_name, i_item_id
"""


def _oracle_q2ish(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")
    ws = _pd(t, "web_sales")
    cs = _pd(t, "catalog_sales")
    frames = [
        pd.DataFrame({"d_year": ws.ws_sold_date_sk.map(dd.d_year),
                      "d_dow": ws.ws_sold_date_sk.map(dd.d_dow),
                      "sales_price": ws.ws_ext_sales_price}),
        pd.DataFrame({"d_year": cs.cs_sold_date_sk.map(dd.d_year),
                      "d_dow": cs.cs_sold_date_sk.map(dd.d_dow),
                      "sales_price": cs.cs_ext_sales_price}),
    ]
    allc = pd.concat(frames)
    exp = (allc.groupby(["d_year", "d_dow"])["sales_price"].sum()
           .reset_index(name="total"))
    _assert_rows(got, exp)


_Q2ISH = """
WITH wscs AS (
  SELECT d_year, d_dow, ws_ext_sales_price AS sales_price
  FROM web_sales, date_dim WHERE ws_sold_date_sk = d_date_sk
  UNION ALL
  SELECT d_year, d_dow, cs_ext_sales_price
  FROM catalog_sales, date_dim WHERE cs_sold_date_sk = d_date_sk)
SELECT d_year, d_dow, sum(sales_price) AS total
FROM wscs GROUP BY d_year, d_dow ORDER BY d_year, d_dow
"""


def _oracle_q27(got, t):
    pdf = _merged(t, ["customer_demographics", "date_dim", "store",
                      "item"])
    pdf = pdf[(pdf.cd_gender == "M") & (pdf.cd_marital_status == "S")
              & (pdf.cd_education_status == "College")
              & (pdf.d_year == 2000)]

    def level(keys):
        if keys:
            g = pdf.groupby(keys).agg(
                agg1=("ss_quantity", "mean"),
                agg2=("ss_list_price", "mean"),
                agg3=("ss_coupon_amt", "mean"),
                agg4=("ss_sales_price", "mean")).reset_index()
        else:
            g = pd.DataFrame({"agg1": [pdf.ss_quantity.mean()],
                              "agg2": [pdf.ss_list_price.mean()],
                              "agg3": [pdf.ss_coupon_amt.mean()],
                              "agg4": [pdf.ss_sales_price.mean()]})
        for col in ("i_item_id", "s_county"):
            if col not in g.columns:
                # np.nan (not None): pandas-3 str-dtype concat coerces
                # None to '' but keeps nan as missing
                g[col] = np.nan
        return g[["i_item_id", "s_county", "agg1", "agg2", "agg3",
                  "agg4"]]
    exp = pd.concat([level(["i_item_id", "s_county"]),
                     level(["i_item_id"]), level([])], ignore_index=True)
    _assert_rows(got, exp)


_Q27 = """
SELECT i_item_id, s_county, avg(ss_quantity) AS agg1,
       avg(ss_list_price) AS agg2, avg(ss_coupon_amt) AS agg3,
       avg(ss_sales_price) AS agg4
FROM store_sales, customer_demographics, date_dim, store, item
WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
  AND ss_cdemo_sk = cd_demo_sk AND ss_item_sk = i_item_sk
  AND cd_gender = 'M' AND cd_marital_status = 'S'
  AND cd_education_status = 'College' AND d_year = 2000
GROUP BY ROLLUP(i_item_id, s_county)
ORDER BY i_item_id, s_county
"""


# ---------------------------------------------------------------------------
# round-5 wave 2: shipping/returns/promotion shapes over the extended star
# (warehouse, ship_mode, web_returns; zip/state address attributes; time-
# keyed catalog/web facts).  New plan shapes vs wave 1: fact-fact-fact
# chain joins (q25), IN-subquery channel CTEs (q33), date-lag CASE
# buckets (q50/q62), scalar-block ratio cross joins (q61/q90), correlated
# threshold subqueries (q92), and DISTINCT-count over a non-equi
# correlated EXISTS self-join (q94).
# ---------------------------------------------------------------------------

def _oracle_q15(got, t):
    dd = _pd(t, "date_dim")
    cs = (_pd(t, "catalog_sales")
          .merge(_pd(t, "customer"), left_on="cs_bill_customer_sk",
                 right_on="c_customer_sk")
          .merge(_pd(t, "customer_address"), left_on="c_current_addr_sk",
                 right_on="ca_address_sk")
          .merge(dd, left_on="cs_sold_date_sk", right_on="d_date_sk"))
    cs = cs[(cs.d_qoy == 1) & (cs.d_year == 2000)
            & (cs.ca_zip.str[:5].isin(_ZIPS[:5])
               | cs.ca_state.isin(["CA", "WA", "GA"])
               | (cs.cs_sales_price > 500))]
    exp = (cs.groupby("ca_zip")["cs_sales_price"].sum()
           .reset_index(name="total"))
    _assert_rows(got, exp)


_Q15 = f"""
SELECT ca_zip, sum(cs_sales_price) AS total
FROM catalog_sales, customer, customer_address, date_dim
WHERE cs_bill_customer_sk = c_customer_sk
  AND c_current_addr_sk = ca_address_sk
  AND (substr(ca_zip, 1, 5) IN ({", ".join(repr(z) for z in _ZIPS[:5])})
       OR ca_state IN ('CA', 'WA', 'GA') OR cs_sales_price > 500)
  AND cs_sold_date_sk = d_date_sk AND d_qoy = 1 AND d_year = 2000
GROUP BY ca_zip
ORDER BY ca_zip
"""


def _oracle_q25(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    ss = _pd(t, "store_sales")
    ss = ss[ss.ss_sold_date_sk.map(dd) == 2000]
    sr = _pd(t, "store_returns")
    sr = sr[sr.sr_returned_date_sk.map(dd).isin([2000, 2001])]
    cs = _pd(t, "catalog_sales")
    cs = cs[cs.cs_sold_date_sk.map(dd).isin([2000, 2001])]
    m = ss.merge(sr, left_on=["ss_customer_sk", "ss_item_sk",
                              "ss_ticket_number"],
                 right_on=["sr_customer_sk", "sr_item_sk",
                           "sr_ticket_number"])
    m = m.merge(cs, left_on=["sr_customer_sk", "sr_item_sk"],
                right_on=["cs_bill_customer_sk", "cs_item_sk"])
    m = (m.merge(_pd(t, "item"), left_on="ss_item_sk",
                 right_on="i_item_sk")
         .merge(_pd(t, "store"), left_on="ss_store_sk",
                right_on="s_store_sk"))
    exp = (m.groupby(["i_item_id", "s_store_name"])
           .agg(store_profit=("ss_net_profit", "sum"),
                return_loss=("sr_net_loss", "sum"),
                catalog_profit=("cs_net_profit", "sum")).reset_index())
    _assert_rows(got, exp)


_Q25 = """
SELECT i_item_id, s_store_name,
       sum(ss_net_profit) AS store_profit,
       sum(sr_net_loss) AS return_loss,
       sum(cs_net_profit) AS catalog_profit
FROM store_sales, store_returns, catalog_sales, date_dim d1, date_dim d2,
     date_dim d3, item, store
WHERE d1.d_year = 2000 AND d1.d_date_sk = ss_sold_date_sk
  AND i_item_sk = ss_item_sk AND s_store_sk = ss_store_sk
  AND ss_customer_sk = sr_customer_sk AND ss_item_sk = sr_item_sk
  AND ss_ticket_number = sr_ticket_number
  AND sr_returned_date_sk = d2.d_date_sk AND d2.d_year IN (2000, 2001)
  AND sr_customer_sk = cs_bill_customer_sk AND sr_item_sk = cs_item_sk
  AND cs_sold_date_sk = d3.d_date_sk AND d3.d_year IN (2000, 2001)
GROUP BY i_item_id, s_store_name
ORDER BY i_item_id, s_store_name
"""


def _oracle_q33(got, t):
    item = _pd(t, "item")
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    manufacts = set(item[item.i_category_id == 3].i_manufact_id)

    def chan(fact, item_col, date_col, price):
        f = _pd(t, fact)
        f = f[f[date_col].map(dd) == 1999]
        m = f.merge(item, left_on=item_col, right_on="i_item_sk")
        m = m[m.i_manufact_id.isin(manufacts)]
        return m.groupby("i_manufact_id")[price].sum()
    tot = (chan("store_sales", "ss_item_sk", "ss_sold_date_sk",
                "ss_ext_sales_price")
           .add(chan("catalog_sales", "cs_item_sk", "cs_sold_date_sk",
                     "cs_ext_sales_price"), fill_value=0)
           .add(chan("web_sales", "ws_item_sk", "ws_sold_date_sk",
                     "ws_ext_sales_price"), fill_value=0))
    exp = tot.reset_index()
    exp.columns = ["i_manufact_id", "total_sales"]
    _assert_rows(got, exp)


def _q33_chan(fact, item_col, date_col, price):
    return f"""
  SELECT i_manufact_id, sum({price}) AS total_sales
  FROM {fact}, date_dim, item
  WHERE {date_col} = d_date_sk AND {item_col} = i_item_sk
    AND i_manufact_id IN (SELECT i_manufact_id FROM item
                          WHERE i_category_id = 3)
    AND d_year = 1999
  GROUP BY i_manufact_id"""


_Q33 = f"""
WITH ss AS ({_q33_chan('store_sales', 'ss_item_sk', 'ss_sold_date_sk',
                       'ss_ext_sales_price')}),
cs AS ({_q33_chan('catalog_sales', 'cs_item_sk', 'cs_sold_date_sk',
                  'cs_ext_sales_price')}),
ws AS ({_q33_chan('web_sales', 'ws_item_sk', 'ws_sold_date_sk',
                  'ws_ext_sales_price')})
SELECT i_manufact_id, sum(total_sales) AS total_sales
FROM (SELECT * FROM ss UNION ALL SELECT * FROM cs
      UNION ALL SELECT * FROM ws) tmp1
GROUP BY i_manufact_id
ORDER BY i_manufact_id
"""


#: the 30/60/90/120-day lag buckets shared by q50 (return lag) and q62
#: (ship lag) — one definition each for the SQL CASE chain and the
#: oracle columns so a bucket-edge tweak cannot desynchronize them
_LAG_EDGES = [(None, 30, "d30"), (30, 60, "d60"), (60, 90, "d90"),
              (90, 120, "d120"), (120, None, "dmore")]


def _lag_bucket_sql(lag_expr: str) -> str:
    parts = []
    for lo, hi, name in _LAG_EDGES:
        conds = []
        if lo is not None:
            conds.append(f"{lag_expr} > {lo}")
        if hi is not None:
            conds.append(f"{lag_expr} <= {hi}")
        parts.append(f"  sum(CASE WHEN {' AND '.join(conds)}\n"
                     f"           THEN 1 ELSE 0 END) AS {name}")
    return ",\n".join(parts)


def _lag_bucket_agg(m: pd.DataFrame, lag: pd.Series, keys: List[str]):
    cols = {}
    for lo, hi, name in _LAG_EDGES:
        mask = pd.Series(True, index=lag.index)
        if lo is not None:
            mask &= lag > lo
        if hi is not None:
            mask &= lag <= hi
        cols[name] = mask.astype(int)
    return (m.assign(**cols).groupby(keys)
            [[name for _, _, name in _LAG_EDGES]].sum().reset_index())


def _oracle_q50(got, t):
    dd = _pd(t, "date_dim")
    ss = _pd(t, "store_sales")
    sr = _pd(t, "store_returns")
    m = ss.merge(sr, left_on=["ss_ticket_number", "ss_item_sk",
                              "ss_customer_sk"],
                 right_on=["sr_ticket_number", "sr_item_sk",
                           "sr_customer_sk"])
    m = m.merge(dd, left_on="sr_returned_date_sk", right_on="d_date_sk")
    m = m[m.d_year == 2000]
    m = m.merge(_pd(t, "store"), left_on="ss_store_sk",
                right_on="s_store_sk")
    exp = _lag_bucket_agg(m, m.sr_returned_date_sk - m.ss_sold_date_sk,
                          ["s_store_name"])
    _assert_rows(got, exp)


_Q50 = f"""
SELECT s_store_name,
{_lag_bucket_sql('sr_returned_date_sk - ss_sold_date_sk')}
FROM store_sales, store_returns, store, date_dim d2
WHERE ss_ticket_number = sr_ticket_number AND ss_item_sk = sr_item_sk
  AND ss_customer_sk = sr_customer_sk
  AND sr_returned_date_sk = d2.d_date_sk AND d2.d_year = 2000
  AND ss_store_sk = s_store_sk
GROUP BY s_store_name
ORDER BY s_store_name
"""


def _oracle_q61(got, t):
    base = _merged(t, ["date_dim", "store", "customer", "item"])
    base = base.merge(_pd(t, "customer_address"),
                      left_on="c_current_addr_sk",
                      right_on="ca_address_sk")
    base = base[(base.d_year == 2000) & (base.s_county == "C1")
                & (base.ca_county.isin(["C1", "C2"]))
                & (base.i_category_id == 3)]
    promo = base.merge(_pd(t, "promotion"), left_on="ss_promo_sk",
                       right_on="p_promo_sk")
    promo = promo[(promo.p_channel_email == "Y")
                  | (promo.p_channel_event == "Y")]
    p, tot = promo.ss_ext_sales_price.sum(), base.ss_ext_sales_price.sum()
    exp = pd.DataFrame({"promotions": [p], "total": [tot],
                        "ratio": [p / tot * 100]})
    _assert_rows(got, exp)


_Q61_COMMON = """
  FROM store_sales{extra_tables}, store, date_dim, customer,
       customer_address, item
  WHERE ss_sold_date_sk = d_date_sk AND ss_store_sk = s_store_sk
    AND ss_customer_sk = c_customer_sk
    AND ca_address_sk = c_current_addr_sk AND ss_item_sk = i_item_sk
    AND s_county = 'C1' AND ca_county IN ('C1', 'C2')
    AND i_category_id = 3 AND d_year = 2000"""

_Q61 = f"""
SELECT promotions, total, promotions / total * 100 AS ratio
FROM (SELECT sum(ss_ext_sales_price) AS promotions
  {_Q61_COMMON.format(extra_tables=', promotion')}
    AND ss_promo_sk = p_promo_sk
    AND (p_channel_email = 'Y' OR p_channel_event = 'Y')) promotional,
 (SELECT sum(ss_ext_sales_price) AS total
  {_Q61_COMMON.format(extra_tables='')}) all_sales
"""


def _oracle_q62(got, t):
    ws = _pd(t, "web_sales")
    m = (ws.merge(_pd(t, "warehouse"), left_on="ws_warehouse_sk",
                  right_on="w_warehouse_sk")
         .merge(_pd(t, "ship_mode"), left_on="ws_ship_mode_sk",
                right_on="sm_ship_mode_sk")
         .merge(_pd(t, "date_dim"), left_on="ws_ship_date_sk",
                right_on="d_date_sk"))
    m = m[m.d_year == 2000]
    m = m.assign(wname=m.w_warehouse_name.str[:20])
    exp = _lag_bucket_agg(m, m.ws_ship_date_sk - m.ws_sold_date_sk,
                          ["wname", "sm_type"])
    _assert_rows(got, exp)


_Q62 = f"""
SELECT substr(w_warehouse_name, 1, 20) AS wname, sm_type,
{_lag_bucket_sql('ws_ship_date_sk - ws_sold_date_sk')}
FROM web_sales, warehouse, ship_mode, date_dim
WHERE ws_ship_date_sk = d_date_sk AND d_year = 2000
  AND ws_warehouse_sk = w_warehouse_sk
  AND ws_ship_mode_sk = sm_ship_mode_sk
GROUP BY substr(w_warehouse_name, 1, 20), sm_type
ORDER BY wname, sm_type
"""


def _oracle_q71(got, t):
    item = _pd(t, "item")
    item = item[item.i_manager_id <= 20]
    dd = _pd(t, "date_dim")
    td = _pd(t, "time_dim")

    def chan(fact, item_col, date_col, time_col, price):
        f = _pd(t, fact)
        m = f.merge(dd, left_on=date_col, right_on="d_date_sk")
        m = m[(m.d_moy == 11) & (m.d_year == 1999)]
        return pd.DataFrame({"price": m[price], "item_sk": m[item_col],
                             "time_sk": m[time_col]})
    allc = pd.concat([
        chan("web_sales", "ws_item_sk", "ws_sold_date_sk",
             "ws_sold_time_sk", "ws_ext_sales_price"),
        chan("catalog_sales", "cs_item_sk", "cs_sold_date_sk",
             "cs_sold_time_sk", "cs_ext_sales_price"),
        chan("store_sales", "ss_item_sk", "ss_sold_date_sk",
             "ss_sold_time_sk", "ss_ext_sales_price")])
    m = (allc.merge(item, left_on="item_sk", right_on="i_item_sk")
         .merge(td, left_on="time_sk", right_on="t_time_sk"))
    m = m[m.t_hour.between(8, 10)]
    exp = (m.groupby(["i_brand_id", "i_brand", "t_hour", "t_minute"])
           ["price"].sum().reset_index(name="ext_price"))
    exp = exp[["i_brand_id", "i_brand", "t_hour", "t_minute",
               "ext_price"]]
    _assert_rows(got, exp)


_Q71 = """
SELECT i_brand_id, i_brand, t_hour, t_minute,
       sum(ext_price) AS ext_price
FROM item,
 (SELECT ws_ext_sales_price AS ext_price, ws_item_sk AS sold_item_sk,
         ws_sold_time_sk AS time_sk
  FROM web_sales, date_dim
  WHERE d_date_sk = ws_sold_date_sk AND d_moy = 11 AND d_year = 1999
  UNION ALL
  SELECT cs_ext_sales_price, cs_item_sk, cs_sold_time_sk
  FROM catalog_sales, date_dim
  WHERE d_date_sk = cs_sold_date_sk AND d_moy = 11 AND d_year = 1999
  UNION ALL
  SELECT ss_ext_sales_price, ss_item_sk, ss_sold_time_sk
  FROM store_sales, date_dim
  WHERE d_date_sk = ss_sold_date_sk AND d_moy = 11
    AND d_year = 1999) tmp,
 time_dim
WHERE sold_item_sk = i_item_sk AND i_manager_id <= 20
  AND time_sk = t_time_sk AND t_hour BETWEEN 8 AND 10
GROUP BY i_brand_id, i_brand, t_hour, t_minute
ORDER BY ext_price DESC, i_brand_id, t_hour, t_minute
"""


def _q90_count(t, h0, h1):
    ws = _pd(t, "web_sales")
    m = (ws.merge(_pd(t, "household_demographics"),
                  left_on="ws_ship_hdemo_sk", right_on="hd_demo_sk")
         .merge(_pd(t, "time_dim"), left_on="ws_sold_time_sk",
                right_on="t_time_sk"))
    return len(m[(m.t_hour.between(h0, h1)) & (m.hd_dep_count == 3)])


def _oracle_q90(got, t):
    amc, pmc = _q90_count(t, 7, 9), _q90_count(t, 17, 19)
    exp = pd.DataFrame({"am_pm_ratio": [amc * 1.0 / pmc]})
    _assert_rows(got, exp)


def _q90_block(alias, h0, h1):
    return (f"(SELECT count(*) AS {alias} "
            f"FROM web_sales, household_demographics, time_dim "
            f"WHERE ws_ship_hdemo_sk = hd_demo_sk "
            f"AND ws_sold_time_sk = t_time_sk "
            f"AND t_hour BETWEEN {h0} AND {h1} "
            f"AND hd_dep_count = 3)")


_Q90 = f"""
SELECT amc * 1.0 / pmc AS am_pm_ratio
FROM {_q90_block('amc', 7, 9)} at, {_q90_block('pmc', 17, 19)} pt
"""


def _oracle_q92(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    ws = _pd(t, "web_sales")
    ws = ws[ws.ws_sold_date_sk.map(dd) == 2000]
    item = _pd(t, "item")
    thresh = (ws.groupby("ws_item_sk")["ws_ext_discount_amt"]
              .mean() * 1.3)
    m = ws.merge(item, left_on="ws_item_sk", right_on="i_item_sk")
    m = m[m.i_manufact_id <= 30]
    m = m[m.ws_ext_discount_amt > m.ws_item_sk.map(thresh)]
    exp = pd.DataFrame({"excess": [m.ws_ext_discount_amt.sum()]})
    _assert_rows(got, exp)


_Q92 = """
SELECT sum(ws_ext_discount_amt) AS excess
FROM web_sales ws1, item, date_dim
WHERE i_item_sk = ws1.ws_item_sk AND i_manufact_id <= 30
  AND ws1.ws_sold_date_sk = d_date_sk AND d_year = 2000
  AND ws1.ws_ext_discount_amt >
      (SELECT 1.3 * avg(ws_ext_discount_amt)
       FROM web_sales ws2, date_dim d2
       WHERE ws2.ws_item_sk = ws1.ws_item_sk
         AND ws2.ws_sold_date_sk = d2.d_date_sk AND d2.d_year = 2000)
"""


def _ws_order_stats(t, returned_polarity: bool):
    """Shared q94/q95 oracle: multi-warehouse CA-shipped year-2000 web
    orders, kept (q95) or excluded (q94) by web_returns membership;
    returns the (order_count, shipping, profit) frame with SQL's
    sum-over-zero-rows-is-NULL semantics."""
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    ws = _pd(t, "web_sales")
    wh_per_order = ws.groupby("ws_order_number")["ws_warehouse_sk"] \
        .nunique()
    returned = set(_pd(t, "web_returns").wr_order_number)
    m = ws[ws.ws_ship_date_sk.map(dd) == 2000]
    m = m.merge(_pd(t, "warehouse"), left_on="ws_warehouse_sk",
                right_on="w_warehouse_sk")
    m = m[m.w_state == "CA"]
    m = m[m.ws_order_number.map(wh_per_order) > 1]
    is_ret = m.ws_order_number.isin(returned)
    m = m[is_ret] if returned_polarity else m[~is_ret]
    return pd.DataFrame({
        "order_count": [m.ws_order_number.nunique()],
        "total_shipping_cost": [m.ws_ext_ship_cost.sum()
                                if len(m) else np.nan],
        "total_net_profit": [m.ws_net_profit.sum()
                             if len(m) else np.nan],
    })


def _oracle_q94(got, t):
    _assert_rows(got, _ws_order_stats(t, returned_polarity=False))


_Q94 = """
SELECT count(DISTINCT ws_order_number) AS order_count,
       sum(ws_ext_ship_cost) AS total_shipping_cost,
       sum(ws_net_profit) AS total_net_profit
FROM web_sales ws1, date_dim, warehouse
WHERE ws1.ws_ship_date_sk = d_date_sk AND d_year = 2000
  AND ws1.ws_warehouse_sk = w_warehouse_sk AND w_state = 'CA'
  AND EXISTS (SELECT * FROM web_sales ws2
              WHERE ws1.ws_order_number = ws2.ws_order_number
                AND ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
  AND NOT EXISTS (SELECT * FROM web_returns wr1
                  WHERE ws1.ws_order_number = wr1.wr_order_number)
"""


# ---------------------------------------------------------------------------
# round-5 wave 3: inventory / catalog-returns shapes.  New plan stress:
# HAVING on a ratio of conditional sums (q21), inventory semi-join window
# (q37), LEFT JOIN on a composite key + coalesce in a CASE split (q40),
# day-of-week CASE pivot (q43), OR-of-ANDs with join predicates inside
# the disjunction — the common-conjunct factoring path (q48), and
# CTE-backed IN-subquery chains over a self-join (q95).
# ---------------------------------------------------------------------------

def _oracle_q21(got, t):
    inv = _pd(t, "inventory")
    inv = inv[(inv.inv_date_sk >= 840) & (inv.inv_date_sk <= 960)]
    m = (inv.merge(_pd(t, "warehouse"), left_on="inv_warehouse_sk",
                   right_on="w_warehouse_sk")
         .merge(_pd(t, "item"), left_on="inv_item_sk",
                right_on="i_item_sk"))
    m = m.assign(
        before=np.where(m.inv_date_sk < 900, m.inv_quantity_on_hand, 0),
        after=np.where(m.inv_date_sk >= 900, m.inv_quantity_on_hand, 0))
    g = (m.groupby(["w_warehouse_name", "i_item_id"])
         .agg(inv_before=("before", "sum"),
              inv_after=("after", "sum")).reset_index())
    exp = g[(g.inv_before > 0) & (g.inv_after * 3 >= g.inv_before * 2)
            & (g.inv_after * 2 <= g.inv_before * 3)]
    _assert_rows(got, exp)


_Q21 = """
SELECT w_warehouse_name, i_item_id,
       sum(CASE WHEN inv_date_sk < 900
                THEN inv_quantity_on_hand ELSE 0 END) AS inv_before,
       sum(CASE WHEN inv_date_sk >= 900
                THEN inv_quantity_on_hand ELSE 0 END) AS inv_after
FROM inventory, warehouse, item, date_dim
WHERE inv_item_sk = i_item_sk AND inv_warehouse_sk = w_warehouse_sk
  AND inv_date_sk = d_date_sk AND d_date_sk BETWEEN 840 AND 960
GROUP BY w_warehouse_name, i_item_id
HAVING sum(CASE WHEN inv_date_sk < 900
                THEN inv_quantity_on_hand ELSE 0 END) > 0
   AND sum(CASE WHEN inv_date_sk >= 900
                THEN inv_quantity_on_hand ELSE 0 END) * 3
       >= sum(CASE WHEN inv_date_sk < 900
                   THEN inv_quantity_on_hand ELSE 0 END) * 2
   AND sum(CASE WHEN inv_date_sk >= 900
                THEN inv_quantity_on_hand ELSE 0 END) * 2
       <= sum(CASE WHEN inv_date_sk < 900
                   THEN inv_quantity_on_hand ELSE 0 END) * 3
ORDER BY w_warehouse_name, i_item_id
"""


def _oracle_q37(got, t):
    item = _pd(t, "item")
    item = item[item.i_current_price.between(20, 50)
                & (item.i_manufact_id <= 40)]
    inv = _pd(t, "inventory")
    inv = inv[(inv.inv_date_sk.between(900, 960))
              & (inv.inv_quantity_on_hand.between(100, 500))]
    cs_items = set(_pd(t, "catalog_sales").cs_item_sk)
    m = item[item.i_item_sk.isin(set(inv.inv_item_sk)) &
             item.i_item_sk.isin(cs_items)]
    exp = (m[["i_item_id", "i_current_price"]].drop_duplicates())
    _assert_rows(got, exp)


_Q37 = """
SELECT i_item_id, i_current_price
FROM item, inventory, date_dim, catalog_sales
WHERE i_current_price BETWEEN 20 AND 50 AND i_manufact_id <= 40
  AND inv_item_sk = i_item_sk AND d_date_sk = inv_date_sk
  AND d_date_sk BETWEEN 900 AND 960
  AND inv_quantity_on_hand BETWEEN 100 AND 500
  AND cs_item_sk = i_item_sk
GROUP BY i_item_id, i_current_price
ORDER BY i_item_id
"""


def _oracle_q40(got, t):
    cs = _pd(t, "catalog_sales")
    cr = _pd(t, "catalog_returns")
    m = cs.merge(cr, left_on=["cs_order_number", "cs_item_sk"],
                 right_on=["cr_order_number", "cr_item_sk"], how="left")
    m = (m.merge(_pd(t, "warehouse"), left_on="cs_warehouse_sk",
                 right_on="w_warehouse_sk")
         .merge(_pd(t, "item"), left_on="cs_item_sk",
                right_on="i_item_sk"))
    m = m[m.i_current_price.between(20, 70)
          & m.cs_sold_date_sk.between(840, 960)]
    net = m.cs_sales_price - m.cr_refunded_cash.fillna(0.0)
    m = m.assign(before=np.where(m.cs_sold_date_sk < 900, net, 0.0),
                 after=np.where(m.cs_sold_date_sk >= 900, net, 0.0))
    exp = (m.groupby(["w_state", "i_item_id"])
           .agg(sales_before=("before", "sum"),
                sales_after=("after", "sum")).reset_index())
    _assert_rows(got, exp)


_Q40 = """
SELECT w_state, i_item_id,
  sum(CASE WHEN cs_sold_date_sk < 900
           THEN cs_sales_price - coalesce(cr_refunded_cash, 0)
           ELSE 0 END) AS sales_before,
  sum(CASE WHEN cs_sold_date_sk >= 900
           THEN cs_sales_price - coalesce(cr_refunded_cash, 0)
           ELSE 0 END) AS sales_after
FROM catalog_sales LEFT JOIN catalog_returns
  ON (cs_order_number = cr_order_number AND cs_item_sk = cr_item_sk),
  warehouse, item, date_dim
WHERE i_current_price BETWEEN 20 AND 70 AND i_item_sk = cs_item_sk
  AND cs_warehouse_sk = w_warehouse_sk AND cs_sold_date_sk = d_date_sk
  AND d_date_sk BETWEEN 840 AND 960
GROUP BY w_state, i_item_id
ORDER BY w_state, i_item_id
"""


def _oracle_q43(got, t):
    pdf = _merged(t, ["date_dim", "store"])
    pdf = pdf[pdf.d_year == 2000]
    cols = {}
    for d, nm in enumerate(("sun", "mon", "tue", "wed", "thu", "fri",
                            "sat")):
        cols[f"{nm}_sales"] = np.where(pdf.d_dow == d,
                                       pdf.ss_sales_price, 0.0)
    exp = (pd.DataFrame({"s_store_name": pdf.s_store_name, **cols})
           .groupby("s_store_name").sum().reset_index())
    _assert_rows(got, exp)


_Q43 = """
SELECT s_store_name,
  sum(CASE WHEN d_dow = 0 THEN ss_sales_price ELSE 0 END) AS sun_sales,
  sum(CASE WHEN d_dow = 1 THEN ss_sales_price ELSE 0 END) AS mon_sales,
  sum(CASE WHEN d_dow = 2 THEN ss_sales_price ELSE 0 END) AS tue_sales,
  sum(CASE WHEN d_dow = 3 THEN ss_sales_price ELSE 0 END) AS wed_sales,
  sum(CASE WHEN d_dow = 4 THEN ss_sales_price ELSE 0 END) AS thu_sales,
  sum(CASE WHEN d_dow = 5 THEN ss_sales_price ELSE 0 END) AS fri_sales,
  sum(CASE WHEN d_dow = 6 THEN ss_sales_price ELSE 0 END) AS sat_sales
FROM date_dim, store_sales, store
WHERE d_date_sk = ss_sold_date_sk AND s_store_sk = ss_store_sk
  AND d_year = 2000
GROUP BY s_store_name
ORDER BY s_store_name
"""


def _oracle_q48(got, t):
    ss = _pd(t, "store_sales")
    cd = _pd(t, "customer_demographics")
    ca = _pd(t, "customer_address")
    m = (ss.merge(_pd(t, "store"), left_on="ss_store_sk",
                  right_on="s_store_sk")
         .merge(_pd(t, "date_dim"), left_on="ss_sold_date_sk",
                right_on="d_date_sk")
         .merge(cd, left_on="ss_cdemo_sk", right_on="cd_demo_sk")
         .merge(ca, left_on="ss_addr_sk", right_on="ca_address_sk"))
    m = m[m.d_year == 2000]
    c1 = ((m.cd_marital_status == "M")
          & (m.cd_education_status == "Advanced Degree")
          & m.ss_sales_price.between(100.0, 150.0))
    c2 = ((m.cd_marital_status == "S")
          & (m.cd_education_status == "College")
          & m.ss_sales_price.between(50.0, 100.0))
    c3 = ((m.cd_marital_status == "W")
          & (m.cd_education_status == "Secondary")
          & m.ss_sales_price.between(0.0, 50.0))
    a1 = m.ca_state.isin(["CA", "WA"]) & m.ss_net_profit.between(0, 50)
    a2 = m.ca_state.isin(["GA", "TX"]) & m.ss_net_profit.between(50, 80)
    a3 = m.ca_state.isin(["NY", "OH"]) & m.ss_net_profit.between(-20, 20)
    m = m[(c1 | c2 | c3) & (a1 | a2 | a3)]
    exp = pd.DataFrame({"total_quantity": [int(m.ss_quantity.sum())]})
    _assert_rows(got, exp)


_Q48 = """
SELECT sum(ss_quantity) AS total_quantity
FROM store_sales, store, customer_demographics, customer_address,
     date_dim
WHERE s_store_sk = ss_store_sk AND ss_sold_date_sk = d_date_sk
  AND d_year = 2000
  AND ((cd_demo_sk = ss_cdemo_sk AND cd_marital_status = 'M'
        AND cd_education_status = 'Advanced Degree'
        AND ss_sales_price BETWEEN 100.00 AND 150.00)
    OR (cd_demo_sk = ss_cdemo_sk AND cd_marital_status = 'S'
        AND cd_education_status = 'College'
        AND ss_sales_price BETWEEN 50.00 AND 100.00)
    OR (cd_demo_sk = ss_cdemo_sk AND cd_marital_status = 'W'
        AND cd_education_status = 'Secondary'
        AND ss_sales_price BETWEEN 0.00 AND 50.00))
  AND ((ss_addr_sk = ca_address_sk AND ca_state IN ('CA', 'WA')
        AND ss_net_profit BETWEEN 0 AND 50)
    OR (ss_addr_sk = ca_address_sk AND ca_state IN ('GA', 'TX')
        AND ss_net_profit BETWEEN 50 AND 80)
    OR (ss_addr_sk = ca_address_sk AND ca_state IN ('NY', 'OH')
        AND ss_net_profit BETWEEN -20 AND 20))
"""


def _oracle_q95(got, t):
    # q95's second IN keeps only orders that appear in web_returns (the
    # join to ws_wh re-asserts multi-warehouse): inverted polarity vs q94
    _assert_rows(got, _ws_order_stats(t, returned_polarity=True))


_Q95 = """
WITH ws_wh AS (
  SELECT ws1.ws_order_number
  FROM web_sales ws1, web_sales ws2
  WHERE ws1.ws_order_number = ws2.ws_order_number
    AND ws1.ws_warehouse_sk <> ws2.ws_warehouse_sk)
SELECT count(DISTINCT ws_order_number) AS order_count,
       sum(ws_ext_ship_cost) AS total_shipping_cost,
       sum(ws_net_profit) AS total_net_profit
FROM web_sales ws1, date_dim, warehouse
WHERE ws1.ws_ship_date_sk = d_date_sk AND d_year = 2000
  AND ws1.ws_warehouse_sk = w_warehouse_sk AND w_state = 'CA'
  AND ws1.ws_order_number IN (SELECT ws_order_number FROM ws_wh)
  AND ws1.ws_order_number IN (SELECT wr_order_number
                              FROM web_returns, ws_wh
                              WHERE wr_order_number = ws_wh.ws_order_number)
"""


# ---------------------------------------------------------------------------
# round-5 wave 4: catalog demographics (q26), inventory coefficient-of-
# variation with STDDEV_SAMP + month self-join (q39), three-channel
# revenue-band join over a thrice-reused CTE (q58), 3-level ROLLUP over
# the catalog star (q18 shape).
# ---------------------------------------------------------------------------

def _oracle_q26(got, t):
    m = (_pd(t, "catalog_sales")
         .merge(_pd(t, "customer_demographics"), left_on="cs_cdemo_sk",
                right_on="cd_demo_sk")
         .merge(_pd(t, "date_dim"), left_on="cs_sold_date_sk",
                right_on="d_date_sk")
         .merge(_pd(t, "item"), left_on="cs_item_sk",
                right_on="i_item_sk")
         .merge(_pd(t, "promotion"), left_on="cs_promo_sk",
                right_on="p_promo_sk"))
    m = m[(m.cd_gender == "F") & (m.cd_marital_status == "S")
          & (m.cd_education_status == "College")
          & ((m.p_channel_email == "N") | (m.p_channel_event == "N"))
          & (m.d_year == 2000)]
    exp = (m.groupby("i_item_id")
           .agg(agg1=("cs_quantity", "mean"),
                agg2=("cs_list_price", "mean"),
                agg3=("cs_sales_price", "mean")).reset_index())
    _assert_rows(got, exp)


_Q26 = """
SELECT i_item_id, avg(cs_quantity) AS agg1,
       avg(cs_list_price) AS agg2, avg(cs_sales_price) AS agg3
FROM catalog_sales, customer_demographics, date_dim, item, promotion
WHERE cs_sold_date_sk = d_date_sk AND cs_item_sk = i_item_sk
  AND cs_cdemo_sk = cd_demo_sk AND cs_promo_sk = p_promo_sk
  AND cd_gender = 'F' AND cd_marital_status = 'S'
  AND cd_education_status = 'College'
  AND (p_channel_email = 'N' OR p_channel_event = 'N')
  AND d_year = 2000
GROUP BY i_item_id
ORDER BY i_item_id
"""


def _inv_cov(t, moy):
    m = (_pd(t, "inventory")
         .merge(_pd(t, "date_dim"), left_on="inv_date_sk",
                right_on="d_date_sk"))
    m = m[(m.d_year == 2000) & (m.d_moy == moy)]
    g = (m.groupby(["inv_warehouse_sk", "inv_item_sk"])
         ["inv_quantity_on_hand"].agg(["mean", "std"]).reset_index())
    g = g[g["std"] / g["mean"] > 0.5]
    g["cov"] = g["std"] / g["mean"]
    return g


def _oracle_q39(got, t):
    a, b = _inv_cov(t, 4), _inv_cov(t, 5)
    exp = a.merge(b, on=["inv_warehouse_sk", "inv_item_sk"],
                  suffixes=("_1", "_2"))[
        ["inv_warehouse_sk", "inv_item_sk", "mean_1", "cov_1",
         "mean_2", "cov_2"]]
    _assert_rows(got, exp)


def _q39_cte(moy):
    return f"""
  SELECT inv_warehouse_sk AS w, inv_item_sk AS i,
         avg(inv_quantity_on_hand) AS qty_mean,
         stddev_samp(inv_quantity_on_hand)
           / avg(inv_quantity_on_hand) AS qty_cov
  FROM inventory, date_dim
  WHERE inv_date_sk = d_date_sk AND d_year = 2000 AND d_moy = {moy}
  GROUP BY inv_warehouse_sk, inv_item_sk
  HAVING stddev_samp(inv_quantity_on_hand)
           / avg(inv_quantity_on_hand) > 0.5"""


_Q39 = f"""
WITH inv1 AS ({_q39_cte(4)}), inv2 AS ({_q39_cte(5)})
SELECT inv1.w, inv1.i, inv1.qty_mean AS mean_1, inv1.qty_cov AS cov_1,
       inv2.qty_mean AS mean_2, inv2.qty_cov AS cov_2
FROM inv1, inv2
WHERE inv1.w = inv2.w AND inv1.i = inv2.i
ORDER BY inv1.w, inv1.i
"""


def _oracle_q58(got, t):
    dd = _pd(t, "date_dim").set_index("d_date_sk")["d_year"]
    item = _pd(t, "item")

    def chan(fact, item_col, date_col, price):
        f = _pd(t, fact)
        f = f[f[date_col].map(dd) == 1999]
        m = f.merge(item, left_on=item_col, right_on="i_item_sk")
        return m.groupby("i_item_id")[price].sum()
    ss = chan("store_sales", "ss_item_sk", "ss_sold_date_sk",
              "ss_ext_sales_price")
    cs = chan("catalog_sales", "cs_item_sk", "cs_sold_date_sk",
              "cs_ext_sales_price")
    ws = chan("web_sales", "ws_item_sk", "ws_sold_date_sk",
              "ws_ext_sales_price")
    j = (ss.rename("ss_rev").to_frame()
         .join(cs.rename("cs_rev"), how="inner")
         .join(ws.rename("ws_rev"), how="inner"))
    avg = (j.ss_rev + j.cs_rev + j.ws_rev) / 3.0
    keep = ((j.ss_rev.between(0.5 * avg, 2.0 * avg))
            & (j.cs_rev.between(0.5 * avg, 2.0 * avg))
            & (j.ws_rev.between(0.5 * avg, 2.0 * avg)))
    exp = j[keep].reset_index()
    exp["average"] = avg[keep].values
    _assert_rows(got, exp)


def _q58_cte(alias, fact, item_col, date_col, price):
    return f"""
{alias} AS (
  SELECT i_item_id AS item_id, sum({price}) AS revenue
  FROM {fact}, item, date_dim
  WHERE {item_col} = i_item_sk AND {date_col} = d_date_sk
    AND d_year = 1999
  GROUP BY i_item_id)"""


_Q58 = f"""
WITH {_q58_cte('ss_items', 'store_sales', 'ss_item_sk',
               'ss_sold_date_sk', 'ss_ext_sales_price')},
{_q58_cte('cs_items', 'catalog_sales', 'cs_item_sk', 'cs_sold_date_sk',
          'cs_ext_sales_price')},
{_q58_cte('ws_items', 'web_sales', 'ws_item_sk', 'ws_sold_date_sk',
          'ws_ext_sales_price')}
SELECT ss_items.item_id, ss_items.revenue AS ss_rev,
       cs_items.revenue AS cs_rev, ws_items.revenue AS ws_rev,
       (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
         AS average
FROM ss_items, cs_items, ws_items
WHERE ss_items.item_id = cs_items.item_id
  AND ss_items.item_id = ws_items.item_id
  AND ss_items.revenue BETWEEN
      0.5 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
      AND 2.0 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
  AND cs_items.revenue BETWEEN
      0.5 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
      AND 2.0 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
  AND ws_items.revenue BETWEEN
      0.5 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
      AND 2.0 * (ss_items.revenue + cs_items.revenue + ws_items.revenue) / 3
ORDER BY ss_items.item_id
"""


def _oracle_q18(got, t):
    m = (_pd(t, "catalog_sales")
         .merge(_pd(t, "customer_demographics"), left_on="cs_cdemo_sk",
                right_on="cd_demo_sk")
         .merge(_pd(t, "customer"), left_on="cs_bill_customer_sk",
                right_on="c_customer_sk")
         .merge(_pd(t, "customer_address"), left_on="c_current_addr_sk",
                right_on="ca_address_sk")
         .merge(_pd(t, "date_dim"), left_on="cs_sold_date_sk",
                right_on="d_date_sk")
         .merge(_pd(t, "item"), left_on="cs_item_sk",
                right_on="i_item_sk"))
    m = m[(m.cd_gender == "F") & (m.cd_education_status == "College")
          & (m.d_year == 2000)]

    def level(keys):
        if keys:
            g = (m.groupby(keys)
                 .agg(agg1=("cs_quantity", "mean"),
                      agg2=("cs_list_price", "mean")).reset_index())
        else:
            g = pd.DataFrame({"agg1": [m.cs_quantity.mean()],
                              "agg2": [m.cs_list_price.mean()]})
        for col in ("i_item_id", "ca_state", "ca_county"):
            if col not in g.columns:
                g[col] = np.nan
        return g[["i_item_id", "ca_state", "ca_county", "agg1", "agg2"]]
    exp = pd.concat([level(["i_item_id", "ca_state", "ca_county"]),
                     level(["i_item_id", "ca_state"]),
                     level(["i_item_id"]), level([])],
                    ignore_index=True)
    _assert_rows(got, exp)


_Q18 = """
SELECT i_item_id, ca_state, ca_county,
       avg(cs_quantity) AS agg1, avg(cs_list_price) AS agg2
FROM catalog_sales, customer_demographics, customer, customer_address,
     date_dim, item
WHERE cs_sold_date_sk = d_date_sk AND cs_item_sk = i_item_sk
  AND cs_bill_customer_sk = c_customer_sk
  AND cs_cdemo_sk = cd_demo_sk
  AND c_current_addr_sk = ca_address_sk
  AND cd_gender = 'F' AND cd_education_status = 'College'
  AND d_year = 2000
GROUP BY ROLLUP(i_item_id, ca_state, ca_county)
ORDER BY i_item_id, ca_state, ca_county
"""


#: (name, sql, oracle) — consumed by scaletest.QUERIES via make_runner
QUERY_SET: List[Tuple[str, str, Callable]] = [
    ("q34_ticket_counts", _Q34, _oracle_q34),
    ("q42_category_rev_sql", _Q42_SQL, _oracle_q42),
    ("q52_brand_rev", _Q52, _oracle_q52),
    ("q53_manufact_window", _Q53, _oracle_q53),
    ("q55_brand_rev_mgr", _Q55, _oracle_q55),
    ("q59_weekly_ratio", _Q59ISH, _oracle_q59ish),
    ("q68_city_tickets", _Q68, _oracle_q68),
    ("q73_ticket_counts", _Q73, _oracle_q73),
    ("q79_amt_profit", _Q79, _oracle_q79),
    ("q88_time_buckets", _Q88, _oracle_q88),
    ("q96_time_count", _Q96, _oracle_q96),
    ("q98_revenue_ratio", _Q98, _oracle_q98),
    # round 5: multi-CTE / set-op / subquery planner stress
    ("q1_returns_corr_subq", _Q1, _oracle_q1),
    ("q2_weekly_channels", _Q2ISH, _oracle_q2ish),
    ("q11_yoy_ratio", _Q11, _oracle_q11),
    ("q27_rollup", _Q27, _oracle_q27),
    ("q31_county_growth", _Q31, _oracle_q31),
    ("q38_intersect", _Q38, _oracle_q38),
    ("q60_three_channels", _Q60, _oracle_q60),
    ("q65_low_revenue", _Q65, _oracle_q65),
    ("q69_channel_gap", _Q69, _oracle_q69),
    ("q87_except", _Q87, _oracle_q87),
    ("q93_returns_net", _Q93, _oracle_q93),
    ("q97_full_outer", _Q97, _oracle_q97),
    # round-5 wave 2: shipping/returns/promotion shapes
    ("q15_zip_or_filter", _Q15, _oracle_q15),
    ("q25_fact_chain", _Q25, _oracle_q25),
    ("q33_in_subq_channels", _Q33, _oracle_q33),
    ("q50_return_lag", _Q50, _oracle_q50),
    ("q61_promo_ratio", _Q61, _oracle_q61),
    ("q62_ship_lag", _Q62, _oracle_q62),
    ("q71_brand_time", _Q71, _oracle_q71),
    ("q90_am_pm", _Q90, _oracle_q90),
    ("q92_excess_discount", _Q92, _oracle_q92),
    ("q94_multi_warehouse", _Q94, _oracle_q94),
    # round-5 wave 3: inventory / catalog-returns shapes
    ("q21_inventory_ratio", _Q21, _oracle_q21),
    ("q37_inventory_window", _Q37, _oracle_q37),
    ("q40_returns_split", _Q40, _oracle_q40),
    ("q43_dow_pivot", _Q43, _oracle_q43),
    ("q48_or_of_ands", _Q48, _oracle_q48),
    ("q95_cte_in_chains", _Q95, _oracle_q95),
    # round-5 wave 4: catalog demographics / inventory CoV / revenue bands
    ("q18_rollup3", _Q18, _oracle_q18),
    ("q26_catalog_demo", _Q26, _oracle_q26),
    ("q39_inventory_cov", _Q39, _oracle_q39),
    ("q58_revenue_bands", _Q58, _oracle_q58),
]


def register_views(sess, t: Dict[str, pa.Table]) -> None:
    parts = {"store_sales": 4}
    for name, tbl in t.items():
        sess.create_dataframe(
            tbl, num_partitions=parts.get(name, 2)
        ).createOrReplaceTempView(name)


from .rig_util import ViewCache  # noqa: E402  (needs register_views)

_views = ViewCache(register_views)


def make_runner(sql: str, oracle: Callable) -> Callable:
    """Adapt one query to the scaletest (sess, tables, F) protocol."""
    def run(sess, t, F):
        _views.ensure(sess, t)
        got = sess.sql(sql).collect().to_pandas()
        oracle(got, t)
    return run
