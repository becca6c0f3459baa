"""TPC-DS's store-sales star from a seed, to clause 2 of the specification
(v3.2.0), vectorised: ``store_sales`` (clause 2.3.1, 23 columns) and four of
its dimensions (clause 2.4: ``date_dim`` 28, ``item`` 22,
``customer_demographics`` 9, ``promotion`` 19), every column at the spec's
type and width; row counts from clause 3's Table 3-2.

What the queries depend on is the spec's: ``customer_demographics`` is the
full cross product of its attribute domains (2 x 5 x 7 x 20 x 4 x 7 x 7 x 7
= 1,920,800 rows, the key counting through them); ``date_dim`` is one row a
day from 1900-01-02 (``d_date_sk`` 2415022) with year, month, quarter and
the sequences derived from the date; ``i_item_id`` is shared by the two
successive revisions of an item, ``i_manufact_id`` lies in 1..1000,
``i_brand`` is a function of ``i_brand_id``; a ticket holds 8 to 16 lines
that share its date, time, customer, demographics, address and store; money
follows dsdgen's pricing (wholesale 1.00-100.00, list = wholesale x
(1 + markup <= 2.00), sales = list x (1 - discount), extended = x quantity
1..100, coupon, tax <= 9 %), rounded to cents; every nullable column of
``store_sales`` (all but ``ss_item_sk`` and ``ss_ticket_number``, its
primary key) is NULL independently at ``NULL_RATE``.

What is not dsdgen's is listed under ``assumed`` in the configuration: the
random streams are numpy's, decimals are doubles, sales fall uniformly on
1998-2002, the dimensions carry no NULLs but ``i_rec_end_date``, columns of
a few listed values come dictionary-encoded (``_coded``).

**One chip's share.**  ``scale`` is ``{"scale_factor": 100, "share_of": 32}``
(and, for tests, ``"share": k``): the rows of the tickets with
``ss_ticket_number % share_of == share`` (0 where not given).  Ticket
numbers fall into 32 residue classes; every class has the same number of
rows (the whole table's over 32: 287,997,024 = 32 x 8,999,907 at SF100) and
a random stream of its own, so a share is made without the rest, the shares
of any ``share_of`` that divides 32 add up to the whole table row for row,
and a share's row count is the same for every seed.  Within a class the
tickets' line counts are shuffles of 8..16 in turn, the last ticket cut to
fit.

``build_tables(scale, seed, tables)`` and ``sizes(scale)`` are the
interface every generator module has; nothing of the program is imported.
"""

from __future__ import annotations

import math
import os
import sys
from typing import Dict, Iterable

import numpy as np
import pyarrow as pa

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _BENCH not in sys.path:
    sys.path.insert(0, _BENCH)

import join_bytes  # noqa: E402  (benchmarks/join_bytes.py: keeps the last build)

TABLES = ("store_sales", "date_dim", "item", "customer_demographics",
          "promotion")

#: Table 3-2, by scale factor (from memory of v3.2.0: no copy of the spec
#: on this machine; listed under ``assumed``)
_LISTED = (1, 10, 100, 300, 1000, 3000, 10000, 30000, 100000)
_ROWS = {
    "item": (18_000, 102_000, 204_000, 264_000, 300_000, 360_000, 402_000,
             462_000, 502_000),
    "promotion": (300, 500, 1_000, 1_300, 1_500, 1_800, 2_000, 2_300, 2_500),
    # the dimensions store_sales points into and this module does not build
    "customer": (100_000, 500_000, 2_000_000, 5_000_000, 12_000_000,
                 30_000_000, 65_000_000, 80_000_000, 100_000_000),
    "customer_address": (50_000, 250_000, 1_000_000, 2_500_000, 6_000_000,
                         15_000_000, 32_500_000, 40_000_000, 50_000_000),
    "store": (12, 102, 402, 804, 1_002, 1_350, 1_500, 1_704, 1_902),
}
_STORE_SALES_SF100 = 287_997_024
_FIXED = {"date_dim": 73_049, "customer_demographics": 1_920_800,
          "household_demographics": 7_200, "time_dim": 86_400}

CLASSES = 32                     # residue classes of ss_ticket_number
#: a rehearsal's table (scale factors under 0.137) keeps this many rows a
#: class, 393,216 in all and about 4,100 tickets in an eighth: q7 keeps one
#: ticket in 380, whole, and must still return rows for the check to compare
_CLASS_ROWS_FLOOR = 12288
_LINES = np.arange(8, 17)        # lines a ticket, each once in every nine
NULL_RATE = 2621 / 65536         # 3.9993 %: dsdgen's "about 4 %" a column
_DATE0 = np.datetime64("1900-01-02")
_DATE0_SK = 2415022
_SALES_FROM = int((np.datetime64("1998-01-01") - _DATE0).astype(int))
_SALES_DAYS = int((np.datetime64("2003-01-01")
                   - np.datetime64("1998-01-01")).astype(int))

_GENDER = ["M", "F"]
_MARITAL = ["M", "S", "D", "W", "U"]
_EDUCATION = ["Primary", "Secondary", "College", "2 yr Degree",
              "4 yr Degree", "Advanced Degree", "Unknown"]
_CREDIT = ["Good", "Low Risk", "High Risk", "Unknown"]
_DAYS = ["Sunday", "Monday", "Tuesday", "Wednesday", "Thursday", "Friday",
         "Saturday"]
_CATEGORIES = ["Women", "Men", "Children", "Shoes", "Music", "Jewelry",
               "Home", "Sports", "Books", "Electronics"]
_CLASSES = ["dresses", "fragrances", "maternity", "swimwear", "accessories",
            "pants", "shirts", "sports-apparel", "infants", "newborn",
            "school-uniforms", "toddlers", "athletic", "kids", "mens",
            "womens"]
_SYLLABLES = ["ought", "able", "pri", "ese", "anti", "cally", "ation",
              "eing", "n st", "bar"]
_BRAND_WORDS = ["amalg", "importo", "edu pack", "exporti", "scholar",
                "corp", "brand", "univ", "maxi", "nameless"]
_SIZES = ["petite", "small", "medium", "large", "extra large", "economy",
          "N/A"]
_COLORS = ("almond antique aquamarine azure beige bisque black blanched blue "
           "blush brown burlywood burnished chartreuse chiffon chocolate "
           "coral cornflower cornsilk cream cyan dark deep dim dodger drab "
           "firebrick floral forest frosted gainsboro ghost goldenrod green "
           "grey honeydew hot indian ivory khaki lace lavender lawn lemon "
           "light lime linen magenta maroon medium metallic midnight mint "
           "misty moccasin navajo navy olive orange orchid pale papaya peach "
           "peru pink plum powder puff purple red rose rosy royal saddle "
           "salmon sandy seashell sienna sky slate smoke snow spring steel "
           "tan thistle tomato turquoise violet wheat white yellow").split()
_UNITS = ["Unknown", "Each", "Dozen", "Case", "Pallet", "Gross", "Carton",
          "Box", "Bunch", "Bundle", "Oz", "Lb", "Ton", "Ounce", "Pound",
          "Tsp", "Tbl", "Cup", "Dram", "Gram", "N/A"]
_PURPOSES = ["Unknown"]
_WORDS = ("able about above according across actually after again against "
          "ago agree ahead almost alone along already also although always "
          "among amount and another answer any appear area around as ask at "
          "available away back bad base be bear beat because become before "
          "begin behind believe below best better between big black blue "
          "board body book both bring build business but buy by call can "
          "capital car care carry case catch cause central century certain "
          "chance change charge child choose church circle city claim class "
          "clear close cold college colour come common community company "
          "concern condition consider contain continue control cost could "
          "country course court cover create cut dark data day deal death "
          "decide deep degree describe design detail develop die different "
          "difficult direct do doctor door double down draw drive drop dry "
          "during each early east easy eat economic education effect either "
          "else end enjoy enough enter even evening ever every evidence "
          "exactly example expect experience explain eye face fact fall "
          "family far fast father feel few field fight figure fill final "
          "find fine finish fire first follow food foot for force foreign "
          "form former forward free friend from front full further future "
          "game garden general get girl give glass go good great green "
          "ground group grow half hand hang happen happy hard have he head "
          "health hear heart heavy help here high himself history hold home "
          "hope hospital hot hour house how however human idea if important "
          "in include increase indeed individual industry inside instead "
          "interest into issue it job join just keep kind know labour land "
          "language large last late later laugh law lead learn leave left "
          "legal less let letter level lie life light like likely line list "
          "listen little live local long look lose lot love low machine main "
          "major make man many market matter may mean measure meet member "
          "mention method middle might military mind minute miss model "
          "modern moment money month more morning most mother move much "
          "music must name national natural nature near necessary need never "
          "new news next nice night no normal north not note nothing notice "
          "now number obvious of off offer office often old on once one only "
          "open operate or order other ought out over own page paper parent "
          "part particular party pass past pay people per perhaps period "
          "person picture piece place plan play point police policy "
          "political poor position possible power prepare present press "
          "pretty price private probably problem produce product programme "
          "project provide public pull purpose put quality question quickly "
          "quite rate rather reach read ready real really reason receive "
          "recent record red reduce refer regard relation remain remember "
          "report represent require research rest result return right rise "
          "road role room round rule run safe same save say school sea "
          "second secretary section see seem sell send sense separate "
          "serious serve service set several shall share she short should "
          "show side sign similar simple since single sit situation size "
          "small so social society some soon sort sound south space speak "
          "special spend staff stage stand standard start state stay step "
          "still stop story street strong student study subject succeed such "
          "suggest summer support suppose sure system table take talk tax "
          "teach team tell term test than that the then there therefore "
          "they thing think this though through throw time to today "
          "together too top total touch toward town trade train travel treat "
          "tree trouble true try turn type under understand union unit "
          "until up upon use usual value various very view visit voice "
          "vote wait walk wall want war watch water way we week well west "
          "what when where whether which while white who whole why wide "
          "will win wind window wish with within without woman wonder word "
          "work world worth would write wrong year yes yet young").split()


def _interpolate(listed, sf: float) -> int:
    """A dimension's rows at a scale factor Table 3-2 does not list: along
    the table's own growth, linear in log10(SF) between the two listed
    factors around it; SF1's rows below 1 (dsdgen makes nothing smaller)."""
    if sf <= _LISTED[0]:
        return listed[0]
    if sf >= _LISTED[-1]:
        return listed[-1]
    hi = next(i for i, s in enumerate(_LISTED) if s >= sf)
    lo = hi - 1
    t = ((math.log10(sf) - math.log10(_LISTED[lo]))
         / (math.log10(_LISTED[hi]) - math.log10(_LISTED[lo])))
    return int(round(listed[lo] + t * (listed[hi] - listed[lo])))


def _share(scale: dict):
    share_of = int(scale.get("share_of", 1))
    share = int(scale.get("share", 0))
    if CLASSES % share_of or not 0 <= share < share_of:
        raise ValueError(f"share_of must divide {CLASSES} and share lie "
                         f"below it: {scale}")
    return share_of, share


def _all_sizes(scale: dict) -> Dict[str, int]:
    sf = float(scale["scale_factor"])
    share_of, _ = _share(scale)
    n = {k: _interpolate(v, sf) for k, v in _ROWS.items()}
    n.update(_FIXED)
    n["class_rows"] = max(
        _CLASS_ROWS_FLOOR, int(_STORE_SALES_SF100 * sf / 100.0) // CLASSES)
    n["store_sales"] = n["class_rows"] * (CLASSES // share_of)
    return n


def sizes(scale: dict) -> Dict[str, int]:
    """Rows of each table this module builds; ``store_sales`` is the
    share's."""
    n = _all_sizes(scale)
    return {t: n[t] for t in TABLES}


def _rng(seed: int, stream: str, sub: int = 0):
    streams = ("tickets", "lines", "nulls", "item", "promotion")
    return np.random.default_rng([int(seed), streams.index(stream), sub])


def _coded(codes: np.ndarray, values) -> pa.Array:
    """A column of a few listed values, handed over as Arrow readers hand
    over a parquet dictionary page: ``dictionary<values=string,
    indices=int32>``, one dictionary for the whole table."""
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)), pa.array(list(values)))


def _business_ids(numbers: np.ndarray) -> pa.Array:
    """dsdgen's 16-character business keys: 'AAAAAAAA' then eight letters
    A..P, one for each four bits of the number, the lowest first."""
    numbers = numbers.astype(np.int64)
    letters = np.empty((len(numbers), 16), dtype=np.uint8)
    letters[:, :8] = ord("A")
    for k in range(8):
        letters[:, 8 + k] = ord("A") + ((numbers >> (4 * k)) & 15)
    offsets = np.arange(len(numbers) + 1, dtype=np.int32) * 16
    return pa.StringArray.from_buffers(
        len(numbers), pa.py_buffer(offsets), pa.py_buffer(letters.ravel()))


def _dates(days_from_date0: np.ndarray, mask=None) -> pa.Array:
    days = (_DATE0 + days_from_date0.astype("timedelta64[D]")
            ).astype("datetime64[D]").astype(np.int32)
    return pa.array(days, type=pa.int32(), mask=mask).cast(pa.date32())


def _cents(values: np.ndarray) -> np.ndarray:
    return np.round(values * 100.0) / 100.0


_POOL: dict = {}


def _text_pool() -> np.ndarray:
    """Words end to end, as bytes; the same for every seed (the seed picks
    where each text starts)."""
    if "text" not in _POOL:
        rng = np.random.default_rng(20000101)
        words = " ".join(_WORDS[i] for i in
                         rng.integers(0, len(_WORDS), 1 << 18).tolist())
        _POOL["text"] = np.frombuffer(words.encode("ascii"), dtype=np.uint8)
    return _POOL["text"]


def _words(rng, rows: int, low: int, high: int) -> pa.Array:
    """``rows`` texts of ``low``..``high`` characters, each a piece of the
    word pool from a random start, built as one Arrow buffer."""
    pool = _text_pool()
    lens = rng.integers(low, high + 1, rows).astype(np.int32)
    starts = rng.integers(0, len(pool) - high, rows).astype(np.int64)
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    index = np.repeat(starts - offsets[:-1], lens)
    index += np.arange(int(offsets[-1]), dtype=np.int64)
    return pa.Array.from_buffers(
        pa.string(), rows,
        [None, pa.py_buffer(offsets.astype(np.int32)),
         pa.py_buffer(pool[index])])


# --- dimensions -----------------------------------------------------------

def _date_dim(n: dict) -> pa.Table:
    rows = n["date_dim"]
    at = np.arange(rows)
    date = (_DATE0 + at.astype("timedelta64[D]")).astype("datetime64[D]")
    year = date.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = date.astype("datetime64[M]")
    moy = month0.astype(np.int64) % 12 + 1
    dom = (date - month0).astype(np.int64) + 1
    qoy = (moy - 1) // 3 + 1
    dow = (date.astype(np.int64) + 4) % 7            # 1970-01-01: Thursday
    month_seq = (year - 1900) * 12 + moy - 1
    week_seq = (at + 1) // 7 + 1                     # 1900-01-02: a Tuesday
    quarter_seq = (year - 1900) * 4 + qoy - 1
    first_dom = (month0 - _DATE0).astype(np.int64) + _DATE0_SK
    next_month = (month0 + np.timedelta64(1, "M")).astype("datetime64[D]")
    last_dom = (next_month - _DATE0).astype(np.int64) - 1 + _DATE0_SK
    holiday = (((moy == 1) & (dom == 1)) | ((moy == 7) & (dom == 4))
               | ((moy == 12) & (dom == 25)))
    yn = lambda flags: _coded(np.where(flags, 0, 1), ["Y", "N"])
    i32 = lambda a: pa.array(a.astype(np.int32))
    sk = at + _DATE0_SK
    quarter_name = pa.array(
        [f"{y}Q{q}" for y, q in zip(year.tolist(), qoy.tolist())],
        type=pa.string())
    return pa.table({
        "d_date_sk": i32(sk),
        "d_date_id": _business_ids(sk),
        "d_date": _dates(at),
        "d_month_seq": i32(month_seq),
        "d_week_seq": i32(week_seq),
        "d_quarter_seq": i32(quarter_seq),
        "d_year": i32(year),
        "d_dow": i32(dow),
        "d_moy": i32(moy),
        "d_dom": i32(dom),
        "d_qoy": i32(qoy),
        "d_fy_year": i32(year),
        "d_fy_quarter_seq": i32(quarter_seq),
        "d_fy_week_seq": i32(week_seq),
        "d_day_name": _coded(dow, _DAYS),
        "d_quarter_name": quarter_name,
        "d_holiday": yn(holiday),
        "d_weekend": yn((dow == 0) | (dow == 6)),
        "d_following_holiday": yn(np.roll(holiday, 1)),
        "d_first_dom": i32(first_dom),
        "d_last_dom": i32(last_dom),
        "d_same_day_ly": i32(sk - 365),
        "d_same_day_lq": i32(sk - 91),
        "d_current_day": yn(np.zeros(rows, bool)),
        "d_current_week": yn(np.zeros(rows, bool)),
        "d_current_month": yn(np.zeros(rows, bool)),
        "d_current_quarter": yn(np.zeros(rows, bool)),
        "d_current_year": yn(year == 2003),
    })


def _customer_demographics(n: dict) -> pa.Table:
    at = np.arange(n["customer_demographics"])
    i32 = lambda a: pa.array(a.astype(np.int32))
    return pa.table({
        "cd_demo_sk": i32(at + 1),
        "cd_gender": _coded(at % 2, _GENDER),
        "cd_marital_status": _coded(at // 2 % 5, _MARITAL),
        "cd_education_status": _coded(at // 10 % 7, _EDUCATION),
        "cd_purchase_estimate": i32((at // 70 % 20 + 1) * 500),
        "cd_credit_rating": _coded(at // 1400 % 4, _CREDIT),
        "cd_dep_count": i32(at // 5600 % 7),
        "cd_dep_employed_count": i32(at // 39200 % 7),
        "cd_dep_college_count": i32(at // 274400 % 7),
    })


def _brand_name(brand_id: np.ndarray) -> pa.Array:
    """A function of ``i_brand_id`` alone: two words picked by the id's
    class and category digits, then '#' and its number within the class."""
    number = brand_id % 1000
    first = np.array(_BRAND_WORDS)[(brand_id // 1000) % 10]
    second = np.array(_BRAND_WORDS)[(brand_id // 1_000_000) % 10]
    return pa.array([f"{a}{b} #{k}" for a, b, k in
                     zip(first.tolist(), second.tolist(), number.tolist())],
                    type=pa.string())


def _syllables(numbers: np.ndarray) -> pa.Array:
    """dsdgen's names spelt from a number's decimal digits."""
    out = []
    for v in numbers.tolist():
        text = ""
        while True:
            text += _SYLLABLES[v % 10]
            v //= 10
            if not v:
                break
        out.append(text)
    return pa.array(out, type=pa.string())


def _item(n: dict, seed: int) -> pa.Table:
    rows = n["item"]
    rng = _rng(seed, "item")
    sk = np.arange(1, rows + 1)
    current = (sk % 2 == 0) | (sk == rows)      # the later of two revisions
    start = rng.integers(_SALES_FROM - 730, _SALES_FROM, rows)
    start = np.where(sk % 2 == 0, start + 1095, start)
    end = np.where(current, 0, start + 1094)
    category = rng.integers(1, len(_CATEGORIES) + 1, rows)
    klass = rng.integers(1, len(_CLASSES) + 1, rows)
    brand_id = category * 1_000_000 + klass * 1000 + rng.integers(1, 11, rows)
    manufact_id = rng.integers(1, 1001, rows)
    wholesale = rng.integers(2, 8800, rows) / 100.0
    i32 = lambda a: pa.array(a.astype(np.int32))
    return pa.table({
        "i_item_sk": i32(sk),
        "i_item_id": _business_ids((sk - 1) // 2 + 1),
        "i_rec_start_date": _dates(start),
        "i_rec_end_date": _dates(end, mask=current),
        "i_item_desc": _words(rng, rows, 1, 200),
        "i_current_price": pa.array(_cents(
            wholesale * (1 + rng.integers(0, 101, rows) / 100.0))),
        "i_wholesale_cost": pa.array(wholesale),
        "i_brand_id": i32(brand_id),
        "i_brand": _brand_name(brand_id),
        "i_class_id": i32(klass),
        "i_class": _coded(klass - 1, _CLASSES),
        "i_category_id": i32(category),
        "i_category": _coded(category - 1, _CATEGORIES),
        "i_manufact_id": i32(manufact_id),
        "i_manufact": _syllables(manufact_id),
        "i_size": _coded(rng.integers(0, len(_SIZES), rows), _SIZES),
        "i_formulation": pa.array(
            [f"{v:020d}" for v in rng.integers(0, 10**18, rows).tolist()],
            type=pa.string()),
        "i_color": _coded(rng.integers(0, len(_COLORS), rows), _COLORS),
        "i_units": _coded(rng.integers(0, len(_UNITS), rows), _UNITS),
        "i_container": _coded(np.zeros(rows, np.int64), ["Unknown"]),
        "i_manager_id": i32(rng.integers(1, 101, rows)),
        "i_product_name": _syllables(sk),
    })


def _promotion(n: dict, seed: int) -> pa.Table:
    rows = n["promotion"]
    rng = _rng(seed, "promotion")
    sk = np.arange(1, rows + 1)
    start = rng.integers(_SALES_FROM, _SALES_FROM + _SALES_DAYS - 60, rows)
    i32 = lambda a: pa.array(a.astype(np.int32))
    flag = lambda: _coded(rng.integers(0, 2, rows), ["Y", "N"])
    return pa.table({
        "p_promo_sk": i32(sk),
        "p_promo_id": _business_ids(sk),
        "p_start_date_sk": i32(start + _DATE0_SK),
        "p_end_date_sk": i32(start + rng.integers(1, 61, rows) + _DATE0_SK),
        "p_item_sk": i32(rng.integers(1, n["item"] + 1, rows)),
        "p_cost": pa.array(np.full(rows, 1000.0)),
        "p_response_target": i32(np.ones(rows)),
        "p_promo_name": _syllables(sk),
        "p_channel_dmail": flag(),
        "p_channel_email": flag(),
        "p_channel_catalog": flag(),
        "p_channel_tv": flag(),
        "p_channel_radio": flag(),
        "p_channel_press": flag(),
        "p_channel_event": flag(),
        "p_channel_demo": flag(),
        "p_channel_details": _words(rng, rows, 20, 100),
        "p_purpose": _coded(np.zeros(rows, np.int64), _PURPOSES),
        "p_discount_active": flag(),
    })


# --- the fact table ---------------------------------------------------------

_NULLABLE = ("ss_sold_date_sk", "ss_sold_time_sk", "ss_customer_sk",
             "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk",
             "ss_promo_sk", "ss_quantity", "ss_wholesale_cost",
             "ss_list_price", "ss_sales_price", "ss_ext_discount_amt",
             "ss_ext_sales_price", "ss_ext_wholesale_cost",
             "ss_ext_list_price", "ss_ext_tax", "ss_coupon_amt",
             "ss_net_paid", "ss_net_paid_inc_tax", "ss_net_profit")
_INT32 = ("ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk",
          "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk",
          "ss_store_sk", "ss_promo_sk", "ss_quantity")
_ORDER = ("ss_sold_date_sk", "ss_sold_time_sk", "ss_item_sk",
          "ss_customer_sk", "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk",
          "ss_store_sk", "ss_promo_sk", "ss_ticket_number", "ss_quantity",
          "ss_wholesale_cost", "ss_list_price", "ss_sales_price",
          "ss_ext_discount_amt", "ss_ext_sales_price",
          "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
          "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
          "ss_net_profit")


def _sales_class(n: dict, seed: int, klass: int) -> pa.Table:
    """The rows of the tickets ``klass, klass + 32, klass + 64, ..``
    (``klass`` in 1..32): ``n['class_rows']`` of them whatever the seed."""
    rows = n["class_rows"]
    rng = _rng(seed, "tickets", klass)
    cycles = -(-rows // int(_LINES.sum()))
    lines_of = rng.permuted(np.tile(_LINES, (cycles, 1)), axis=1).ravel()
    tickets = int(np.searchsorted(np.cumsum(lines_of), rows)) + 1
    lines_of = lines_of[:tickets]
    lines_of[-1] -= int(lines_of.sum()) - rows
    spread = lambda per_ticket: np.repeat(per_ticket, lines_of)
    draw = lambda high: rng.integers(1, high + 1, tickets, dtype=np.int32)
    cols = {
        "ss_sold_date_sk": spread((rng.integers(
            0, _SALES_DAYS, tickets) + _SALES_FROM + _DATE0_SK
        ).astype(np.int32)),
        "ss_sold_time_sk": spread(rng.integers(
            28800, 75600, tickets, dtype=np.int32)),
        "ss_customer_sk": spread(draw(n["customer"])),
        "ss_cdemo_sk": spread(draw(n["customer_demographics"])),
        "ss_hdemo_sk": spread(draw(n["household_demographics"])),
        "ss_addr_sk": spread(draw(n["customer_address"])),
        "ss_store_sk": spread(draw(n["store"])),
        "ss_ticket_number": spread(
            klass + CLASSES * np.arange(tickets, dtype=np.int64)),
    }
    rng = _rng(seed, "lines", klass)
    cols["ss_item_sk"] = rng.integers(1, n["item"] + 1, rows, dtype=np.int32)
    cols["ss_promo_sk"] = rng.integers(1, n["promotion"] + 1, rows,
                                       dtype=np.int32)
    quantity = rng.integers(1, 101, rows, dtype=np.int32)
    cols["ss_quantity"] = quantity
    wholesale = rng.integers(100, 10001, rows) / 100.0
    list_price = _cents(wholesale * (1 + rng.integers(0, 201, rows) / 100.0))
    sales_price = _cents(list_price * (1 - rng.integers(0, 101, rows) / 100.0))
    ext_sales = _cents(sales_price * quantity)
    ext_list = _cents(list_price * quantity)
    ext_wholesale = _cents(wholesale * quantity)
    # dsdgen: one sale in five carries a coupon, worth a share of the sale
    coupon = np.where(rng.integers(0, 5, rows) == 0,
                      _cents(ext_sales * rng.integers(0, 101, rows) / 100.0),
                      0.0)
    net_paid = _cents(ext_sales - coupon)
    tax = _cents(net_paid * rng.integers(0, 10, rows) / 100.0)
    cols.update({
        "ss_wholesale_cost": wholesale,
        "ss_list_price": list_price,
        "ss_sales_price": sales_price,
        "ss_ext_discount_amt": _cents(ext_list - ext_sales),
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": ext_wholesale,
        "ss_ext_list_price": ext_list,
        "ss_ext_tax": tax,
        "ss_coupon_amt": coupon,
        "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": _cents(net_paid + tax),
        "ss_net_profit": _cents(net_paid - ext_wholesale),
    })
    rng = _rng(seed, "nulls", klass)
    threshold = int(round(NULL_RATE * 65536))
    arrays = {}
    for name in _ORDER:
        mask = None
        if name in _NULLABLE:
            mask = rng.integers(0, 65536, rows, dtype=np.uint16) < threshold
        arrays[name] = pa.array(cols.pop(name), mask=mask)
    return pa.table(arrays)


def _store_sales(n: dict, seed: int, scale: dict) -> pa.Table:
    share_of, share = _share(scale)
    classes = [k for k in range(1, CLASSES + 1) if k % share_of == share]
    return pa.concat_tables([_sales_class(n, seed, k) for k in classes])


def build_tables(scale: dict, seed: int, tables: Iterable[str]
                 ) -> Dict[str, pa.Table]:
    n = _all_sizes(scale)
    makers = {
        "store_sales": lambda: _store_sales(n, seed, scale),
        "date_dim": lambda: _date_dim(n),
        "item": lambda: _item(n, seed),
        "customer_demographics": lambda: _customer_demographics(n),
        "promotion": lambda: _promotion(n, seed),
    }
    out = {name: makers[name]() for name in tables}
    join_bytes.remember(out)
    return out
