"""TPC-H's eight tables from a seed, to clause 4.2.3 of the specification
(v3.0.1), vectorised: every column of every table at the spec's type and
width, the spec's cardinalities (SUPPLIER SF*10,000, PART SF*200,000,
PARTSUPP 4 a part, CUSTOMER SF*150,000, ORDERS SF*1,500,000, LINEITEM one to
seven an order) and its key rules: O_ORDERKEY uses the first 8 of every 32
keys, O_CUSTKEY is never divisible by 3, L_SUPPKEY and PS_SUPPKEY follow the
spec's formula over the part key, L_EXTENDEDPRICE is quantity times the
part's retail price (itself a function of the part key), ship, commit and
receipt dates follow the order's date, return flag and line status follow
CURRENTDATE 1995-06-17, O_ORDERSTATUS and O_TOTALPRICE follow the order's
lines.

What is not dbgen's, listed under ``assumed`` in each configuration: the
random streams are numpy's (``default_rng([seed, stream])``), so the rows
differ from dbgen's row for row; the lines of the orders are a shuffle of a
fixed multiset (1..7 in turn), so that LINEITEM has the same number of rows
for every seed (SF1: 5,999,995, dbgen's 6,001,215); text comes from a 4 MiB
pool built by the spec's grammar (4.2.2.14; dbgen's pool is 300 MB) and
addresses from a pool of the 64 characters of 4.2.2.7; decimals are doubles.

``build_tables(scale, seed, tables)`` is the interface every generator
module has: ``scale`` is the configuration's own ``scale`` object.
"""

from __future__ import annotations

from typing import Dict, Iterable

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

TABLES = ("lineitem", "orders", "customer", "part", "supplier", "partsupp",
          "nation", "region")

_NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
_REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
_SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY",
             "HOUSEHOLD"]
_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
_INSTRUCTIONS = ["DELIVER IN PERSON", "COLLECT COD", "NONE",
                 "TAKE BACK RETURN"]
_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_TYPES = [f"{a} {b} {c}"
          for a in ("STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY",
                    "PROMO")
          for b in ("ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED")
          for c in ("TIN", "NICKEL", "BRASS", "STEEL", "COPPER")]
_CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
               for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                         "DRUM")]
_COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush "
    "brown burlywood burnished chartreuse chiffon chocolate coral cornflower "
    "cornsilk cream cyan dark deep dim dodger drab firebrick floral forest "
    "frosted gainsboro ghost goldenrod green grey honeydew hot indian ivory "
    "khaki lace lavender lawn lemon light lime linen magenta maroon medium "
    "metallic midnight mint misty moccasin navajo navy olive orange orchid "
    "pale papaya peach peru pink plum powder puff purple red rose rosy royal "
    "saddle salmon sandy seashell sienna sky slate smoke snow spring steel "
    "tan thistle tomato turquoise violet wheat white yellow").split()

_NOUNS = ("foxes,ideas,theodolites,pinto beans,instructions,dependencies,"
          "excuses,platelets,asymptotes,courts,dolphins,multipliers,"
          "sauternes,warthogs,frets,dinos,attainments,somas,Tiresias',"
          "patterns,forges,braids,hockey players,frays,warhorses,dugouts,"
          "notornis,epitaphs,pearls,tithes,waters,orbits,gifts,sheaves,"
          "depths,sentiments,decoys,realms,pains,grouches,escapades"
          ).split(",")
_VERBS = ("sleep,wake,are,cajole,haggle,nag,use,boost,affix,detect,"
          "integrate,maintain,nod,was,lose,sublate,solve,thrash,promise,"
          "engage,hinder,print,x-ray,breach,eat,grow,impress,mold,poach,"
          "serve,run,dazzle,snooze,doze,unwind,kindle,play,hang,believe,"
          "doubt").split(",")
_ADJECTIVES = ("furious,sly,careful,blithe,quick,fluffy,slow,quiet,ruthless,"
               "thin,close,dogged,daring,brave,stealthy,permanent,enticing,"
               "idle,busy,regular,final,ironic,even,bold,silent").split(",")
_ADVERBS = ("sometimes,always,never,furiously,slyly,carefully,blithely,"
            "quickly,fluffily,slowly,quietly,ruthlessly,thinly,closely,"
            "doggedly,daringly,bravely,stealthily,permanently,enticingly,"
            "idly,busily,regularly,finally,ironically,evenly,boldly,"
            "silently").split(",")
_PREPOSITIONS = ("about,above,according to,across,after,against,along,"
                 "alongside of,among,around,at,atop,before,behind,beneath,"
                 "beside,besides,between,beyond,by,despite,during,except,"
                 "for,from,in place of,inside,instead of,into,near,of,on,"
                 "outside,over,past,since,through,throughout,to,toward,"
                 "under,until,up,upon,without,with,within").split(",")
_AUXILIARIES = ("do,may,might,shall,will,would,can,could,should,ought to,"
                "must,will have to,shall have to,could have to,"
                "should have to,must have to,need to,try to").split(",")
_TERMINATORS = [".", ";", ":", "?", "!", "--"]
# the grammar of 4.2.2.14: N noun, V verb, J adjective, D adverb,
# P preposition, X auxiliary, T terminator
_NOUN_PHRASES = ["N", "J N", "J, J N", "D J N"]
_VERB_PHRASES = ["V", "X V", "V D", "X V D"]
_SENTENCES = ["n v T", "n v p T", "n v n T", "n p v n T", "n p v p T"]
_WORDS = {"N": _NOUNS, "V": _VERBS, "J": _ADJECTIVES, "D": _ADVERBS,
          "P": _PREPOSITIONS, "X": _AUXILIARIES, "T": _TERMINATORS}

_POOL_BYTES = 4 << 20
_ALPHANUMERIC = ("0123456789abcdefghijklmnopqrstuvwxyz"
                 "ABCDEFGHIJKLMNOPQRSTUVWXYZ, ")
_BASE = np.datetime64("1992-01-01")
_LAST_ORDER_DAY = 2405      # ENDDATE 1998-12-31 less 151 days, from _BASE
_CURRENT_DAY = 1263         # CURRENTDATE 1995-06-17, from _BASE
_POOLS: dict = {}


def _text_pool() -> np.ndarray:
    """Sentences of the spec's grammar, end to end, as bytes.  The same for
    every seed, as dbgen's pool is; the seed picks where each text starts."""
    if "text" not in _POOLS:
        rng = np.random.default_rng(19920101)
        draws = rng.integers(0, 1 << 30, size=(_POOL_BYTES // 24, 24))

        def phrase(kinds, row, at):
            words = []
            for token in kinds[row[at] % len(kinds)].split():
                kind, tail = token[0], token[1:]
                at += 1
                words.append(_WORDS[kind][row[at] % len(_WORDS[kind])] + tail)
            return " ".join(words), at

        parts, size = [], 0
        for row in draws:
            at, words = 0, []
            for slot in _SENTENCES[row[0] % len(_SENTENCES)].split():
                if slot == "n":
                    text, at = phrase(_NOUN_PHRASES, row, at + 1)
                elif slot == "v":
                    text, at = phrase(_VERB_PHRASES, row, at + 1)
                elif slot == "p":
                    at += 1
                    prep = _PREPOSITIONS[row[at] % len(_PREPOSITIONS)]
                    text, at = phrase(_NOUN_PHRASES, row, at + 1)
                    text = f"{prep} the {text}"
                else:
                    at += 1
                    text = _TERMINATORS[row[at] % len(_TERMINATORS)]
                words.append(text)
            sentence = " ".join(words[:-1]) + words[-1] + " "
            parts.append(sentence)
            size += len(sentence)
            if size >= _POOL_BYTES:
                break
        pool = np.frombuffer("".join(parts).encode("ascii"), dtype=np.uint8)
        _POOLS["text"] = pool[:_POOL_BYTES]
    return _POOLS["text"]


def _address_pool() -> np.ndarray:
    if "address" not in _POOLS:
        rng = np.random.default_rng(19920102)
        letters = np.frombuffer(_ALPHANUMERIC.encode("ascii"), dtype=np.uint8)
        _POOLS["address"] = letters[rng.integers(0, len(letters), 1 << 20)]
    return _POOLS["address"]


def _substrings(rng, pool: np.ndarray, rows: int, low: int, high: int
                ) -> pa.Array:
    """``rows`` strings of ``low``..``high`` bytes, each a piece of
    ``pool`` from a random start, built as one Arrow buffer."""
    lens = rng.integers(low, high + 1, rows).astype(np.int32)
    starts = rng.integers(0, len(pool) - high, rows).astype(np.int32)
    offsets = np.zeros(rows + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    if offsets[-1] >= 1 << 31:
        raise ValueError("a string column over 2 GiB: split the table")
    data = np.empty(int(offsets[-1]), dtype=np.uint8)
    step = 1 << 20
    for a in range(0, rows, step):
        b = min(a + step, rows)
        lo, hi = int(offsets[a]), int(offsets[b])
        first = (starts[a:b] - (offsets[a:b] - lo)).astype(np.int32)
        index = np.repeat(first, lens[a:b])
        index += np.arange(hi - lo, dtype=np.int32)
        data[lo:hi] = pool[index]
    return pa.Array.from_buffers(
        pa.string(), rows,
        [None, pa.py_buffer(offsets.astype(np.int32)), pa.py_buffer(data)])


def _text(rng, rows, low, high) -> pa.Array:
    return _substrings(rng, _text_pool(), rows, low, high)


def _pick(rng, values, n) -> pa.Array:
    """``n`` uniform draws from ``values`` as a plain string column."""
    return _coded(rng.integers(0, len(values), n), values)


def _coded(codes: np.ndarray, values) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(codes.astype(np.int32)),
        pa.array(values, type=pa.string())).cast(pa.string())


def _numbered(prefix: str, numbers: np.ndarray, width: int = 9) -> pa.Array:
    digits = pc.utf8_lpad(pa.array(numbers).cast(pa.string()), width, "0")
    return pc.binary_join_element_wise(prefix, digits, "")


def _phone(rng, nationkey: np.ndarray) -> pa.Array:
    rows = len(nationkey)
    return pc.binary_join_element_wise(
        pa.array(nationkey + 10).cast(pa.string()),
        pa.array(rng.integers(100, 1000, rows)).cast(pa.string()),
        pa.array(rng.integers(100, 1000, rows)).cast(pa.string()),
        pa.array(rng.integers(1000, 10000, rows)).cast(pa.string()), "-")


def _dates(days: np.ndarray) -> pa.Array:
    return pa.array((_BASE + days.astype("timedelta64[D]"))
                    .astype("datetime64[D]"))


def _money(rng, rows, low_cents, high_cents) -> pa.Array:
    return pa.array(rng.integers(low_cents, high_cents + 1, rows) / 100.0)


def _retail_cents(partkey: np.ndarray) -> np.ndarray:
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def _supplier_of(partkey, i, n_supp):
    """The spec's PS_SUPPKEY / L_SUPPKEY: the i-th of a part's suppliers."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1


def sizes(scale: dict) -> Dict[str, int]:
    """Row count of every table (LINEITEM's follows from ORDERS')."""
    sf = float(scale["scale_factor"])
    orders = max(int(round(sf * 1_500_000)), 8)
    return {"supplier": max(int(round(sf * 10_000)), 4),
            "part": max(int(round(sf * 200_000)), 4),
            "customer": max(int(round(sf * 150_000)), 3),
            "orders": orders,
            "lineitem": int((np.arange(orders) % 7 + 1).sum()),
            "clerks": max(int(round(sf * 1_000)), 1),
            "nation": 25, "region": 5}


class _Build:
    """One call's shared state: the orders' keys, dates and line counts
    (LINEITEM needs them) and LINEITEM's prices and statuses (ORDERS needs
    them).  Each has a random stream of its own, so a configuration that
    holds LINEITEM alone makes the LINEITEM that one with all eight makes."""

    def __init__(self, scale: dict, seed: int) -> None:
        self.n = sizes(scale)
        self.seed = int(seed)
        self._lines = None
        rng = self.rng("order_core")
        orders = self.n["orders"]
        at = np.arange(orders, dtype=np.int64)
        self.orderkey = (at // 8) * 32 + at % 8 + 1
        self.orderday = rng.integers(0, _LAST_ORDER_DAY + 1, orders)
        self.lines_of = rng.permutation(at % 7 + 1)

    def rng(self, stream: str):
        streams = ("order_core", "line_core") + TABLES
        return np.random.default_rng([self.seed, streams.index(stream)])

    def lines(self) -> dict:
        """LINEITEM's numeric columns, as numpy arrays."""
        if self._lines is None:
            rng = self.rng("line_core")
            rows = self.n["lineitem"]
            order = np.repeat(np.arange(self.n["orders"]), self.lines_of)
            starts = np.cumsum(self.lines_of) - self.lines_of
            partkey = rng.integers(1, self.n["part"] + 1, rows)
            quantity = rng.integers(1, 51, rows)
            # per-part tables, looked up: 64-bit division a row is slow
            parts = np.arange(self.n["part"] + 1)
            retail = _retail_cents(parts)
            suppliers = _supplier_of(parts[:, None], np.arange(4)[None, :],
                                     self.n["supplier"])
            orderday = self.orderday[order]
            shipday = orderday + rng.integers(1, 122, rows)
            self._lines = {
                "order": order, "starts": starts, "partkey": partkey,
                "suppkey": suppliers[partkey, rng.integers(0, 4, rows)],
                "linenumber": (np.arange(rows) - starts[order] + 1),
                "quantity": quantity,
                "price": quantity * retail[partkey] / 100.0,
                "discount": rng.integers(0, 11, rows) / 100.0,
                "tax": rng.integers(0, 9, rows) / 100.0,
                "shipday": shipday,
                "commitday": orderday + rng.integers(30, 91, rows),
                "receiptday": shipday + rng.integers(1, 31, rows),
            }
        return self._lines


def _lineitem(b: _Build) -> pa.Table:
    li, rng = b.lines(), b.rng("lineitem")
    rows = b.n["lineitem"]
    returned = li["receiptday"] <= _CURRENT_DAY
    flag = np.where(returned, rng.integers(0, 2, rows), 2)   # R, A | N
    return pa.table({
        "l_orderkey": pa.array(b.orderkey[li["order"]]),
        "l_partkey": pa.array(li["partkey"]),
        "l_suppkey": pa.array(li["suppkey"]),
        "l_linenumber": pa.array(li["linenumber"].astype(np.int32)),
        "l_quantity": pa.array(li["quantity"].astype(np.float64)),
        "l_extendedprice": pa.array(li["price"]),
        "l_discount": pa.array(li["discount"]),
        "l_tax": pa.array(li["tax"]),
        "l_returnflag": _coded(flag, ["R", "A", "N"]),
        "l_linestatus": _coded((li["shipday"] > _CURRENT_DAY), ["F", "O"]),
        "l_shipdate": _dates(li["shipday"]),
        "l_commitdate": _dates(li["commitday"]),
        "l_receiptdate": _dates(li["receiptday"]),
        "l_shipinstruct": _pick(rng, _INSTRUCTIONS, rows),
        "l_shipmode": _pick(rng, _MODES, rows),
        "l_comment": _text(rng, rows, 10, 43),
    })


def _orders(b: _Build) -> pa.Table:
    li, rng = b.lines(), b.rng("orders")
    rows, n_cust = b.n["orders"], b.n["customer"]
    at = rng.integers(0, n_cust - n_cust // 3, rows)
    open_lines = np.add.reduceat(
        (li["shipday"] > _CURRENT_DAY).astype(np.int64), li["starts"])
    status = np.where(open_lines == 0, 0,
                      np.where(open_lines == b.lines_of, 1, 2))
    total = np.add.reduceat(
        li["price"] * (1 + li["tax"]) * (1 - li["discount"]), li["starts"])
    return pa.table({
        "o_orderkey": pa.array(b.orderkey),
        "o_custkey": pa.array(at + at // 2 + 1),     # never divisible by 3
        "o_orderstatus": _coded(status, ["F", "O", "P"]),
        "o_totalprice": pa.array(np.round(total, 2)),
        "o_orderdate": _dates(b.orderday),
        "o_orderpriority": _pick(rng, _PRIORITIES, rows),
        "o_clerk": _numbered("Clerk#",
                             rng.integers(1, b.n["clerks"] + 1, rows)),
        "o_shippriority": pa.array(np.zeros(rows, dtype=np.int32)),
        "o_comment": _text(rng, rows, 19, 78),
    })


def _customer(b: _Build) -> pa.Table:
    rng, rows = b.rng("customer"), b.n["customer"]
    keys = np.arange(1, rows + 1)
    nation = rng.integers(0, 25, rows)
    return pa.table({
        "c_custkey": pa.array(keys),
        "c_name": _numbered("Customer#", keys),
        "c_address": _substrings(rng, _address_pool(), rows, 10, 40),
        "c_nationkey": pa.array(nation),
        "c_phone": _phone(rng, nation),
        "c_acctbal": _money(rng, rows, -99999, 999999),
        "c_mktsegment": _pick(rng, _SEGMENTS, rows),
        "c_comment": _text(rng, rows, 29, 116),
    })


def _part(b: _Build) -> pa.Table:
    rng, rows = b.rng("part"), b.n["part"]
    keys = np.arange(1, rows + 1)
    five = np.argsort(rng.random((rows, len(_COLORS))), axis=1)[:, :5]
    colors = pa.array(_COLORS, type=pa.string())
    maker = rng.integers(1, 6, rows)
    brand = maker * 10 + rng.integers(1, 6, rows)
    return pa.table({
        "p_partkey": pa.array(keys),
        "p_name": pc.binary_join_element_wise(
            *[colors.take(pa.array(five[:, k])) for k in range(5)], " "),
        "p_mfgr": pc.binary_join_element_wise(
            "Manufacturer#", pa.array(maker).cast(pa.string()), ""),
        "p_brand": pc.binary_join_element_wise(
            "Brand#", pa.array(brand).cast(pa.string()), ""),
        "p_type": _pick(rng, _TYPES, rows),
        "p_size": pa.array(rng.integers(1, 51, rows).astype(np.int32)),
        "p_container": _pick(rng, _CONTAINERS, rows),
        "p_retailprice": pa.array(_retail_cents(keys) / 100.0),
        "p_comment": _text(rng, rows, 5, 22),
    })


def _supplier(b: _Build) -> pa.Table:
    rng, rows = b.rng("supplier"), b.n["supplier"]
    keys = np.arange(1, rows + 1)
    nation = rng.integers(0, 25, rows)
    comment = _text(rng, rows, 25, 100).to_pylist()
    # SF*5 rows each hold "Customer ... Complaints" / "... Recommends"
    marked = rng.permutation(rows)[:2 * max(rows // 2000, 1)]
    for k, row in enumerate(marked):
        word = ("Complaints", "Recommends")[k % 2]
        text = comment[row]
        comment[row] = ("Customer " + text[9:len(text) - len(word)] + word
                        )[:100]
    return pa.table({
        "s_suppkey": pa.array(keys),
        "s_name": _numbered("Supplier#", keys),
        "s_address": _substrings(rng, _address_pool(), rows, 10, 40),
        "s_nationkey": pa.array(nation),
        "s_phone": _phone(rng, nation),
        "s_acctbal": _money(rng, rows, -99999, 999999),
        "s_comment": pa.array(comment, type=pa.string()),
    })


def _partsupp(b: _Build) -> pa.Table:
    rng, n_part = b.rng("partsupp"), b.n["part"]
    rows = 4 * n_part
    partkey = np.repeat(np.arange(1, n_part + 1), 4)
    return pa.table({
        "ps_partkey": pa.array(partkey),
        "ps_suppkey": pa.array(_supplier_of(
            partkey, np.tile(np.arange(4), n_part), b.n["supplier"])),
        "ps_availqty": pa.array(rng.integers(1, 10000, rows)
                                .astype(np.int32)),
        "ps_supplycost": _money(rng, rows, 100, 100000),
        "ps_comment": _text(rng, rows, 49, 198),
    })


def _nation(b: _Build) -> pa.Table:
    return pa.table({
        "n_nationkey": pa.array(np.arange(25)),
        "n_name": pa.array([name for name, _ in _NATIONS]),
        "n_regionkey": pa.array(np.array([r for _, r in _NATIONS])),
        "n_comment": _text(b.rng("nation"), 25, 31, 114),
    })


def _region(b: _Build) -> pa.Table:
    return pa.table({"r_regionkey": pa.array(np.arange(5)),
                     "r_name": pa.array(_REGIONS),
                     "r_comment": _text(b.rng("region"), 5, 31, 115)})


_MAKERS = {"lineitem": _lineitem, "orders": _orders, "customer": _customer,
           "part": _part, "supplier": _supplier, "partsupp": _partsupp,
           "nation": _nation, "region": _region}


def build_tables(scale: dict, seed: int,
                 tables: Iterable[str] = TABLES) -> Dict[str, pa.Table]:
    """The named tables at ``scale["scale_factor"]``.  The same scale, seed
    and table name give the same table, whatever else is made."""
    build = _Build(scale, seed)
    out = {}
    for name in tables:
        if name not in _MAKERS:
            raise ValueError(f"unknown table {name!r}; known: {TABLES}")
        out[name] = _MAKERS[name](build)
    return out
