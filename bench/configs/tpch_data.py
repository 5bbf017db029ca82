"""TPC-H data for the benchmark, made from the run's seed.

A statistical reimplementation of dbgen kept with the benchmark, so that
the data the engine is measured on and checked against cannot change with
the program. It keeps dbgen's key layout and value rules (TPC-H spec
rev. 3.0.1, clause 4.2.3): dense keys 1..N for part, supplier and customer;
sparse order keys (8 of every 32 values, dbgen's ``mk_sparse``); partsupp's
four suppliers per part by the spec's formula, and each lineitem's supplier
one of its part's four; customers whose key is a multiple of 3 place no
orders; ``l_extendedprice = l_quantity * p_retailprice`` with the spec's
retail price of the part; ``o_totalprice`` summed from the order's lines;
``p_name`` five distinct words of the spec's 92 colours. Dates are int days
since 1992-01-01, strings dictionary codes (``p_name`` is one code per
part, its dictionary the names themselves), all columns float64.
``generate`` returns plain arrays; the reference reads them directly and
the engine receives them wrapped in its ``Database`` (see ``harness.cell``).
"""

from __future__ import annotations

import hashlib
from typing import Dict, List, Tuple

import numpy as np

SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
ORDER_PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
RETURN_FLAGS = ["R", "A", "N"]
LINE_STATUS = ["O", "F"]
SHIP_MODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1), ("EGYPT", 4),
    ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3), ("INDIA", 2), ("INDONESIA", 2),
    ("IRAN", 4), ("IRAQ", 4), ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0),
    ("MOROCCO", 0), ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3), ("UNITED KINGDOM", 3),
    ("UNITED STATES", 1),
]
# the words of P_NAME (spec clause 4.2.3), also the domain of Q9's COLOR
COLORS = (
    "almond antique aquamarine azure beige bisque black blanched blue blush brown "
    "burlywood burnished chartreuse chiffon chocolate coral cornflower cornsilk cream "
    "cyan dark deep dim dodger drab firebrick floral forest frosted gainsboro ghost "
    "goldenrod green grey honeydew hot indian ivory khaki lace lavender lawn lemon "
    "light lime linen magenta maroon medium metallic midnight mint misty moccasin "
    "navajo navy olive orange orchid pale papaya peach peru pink plum powder puff "
    "purple red rose rosy royal saddle salmon sandy seashell sienna sky slate smoke "
    "snow spring steel tan thistle tomato turquoise violet wheat white yellow"
).split()
NAME_WORDS = 5
TYPE_SYLL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYLL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYLL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
TYPES = [f"{a} {b} {c}" for a in TYPE_SYLL1 for b in TYPE_SYLL2 for c in TYPE_SYLL3]

Columns = Dict[str, np.ndarray]
Tables = Dict[str, Tuple[Columns, Dict[str, List[str]]]]

EPOCH = np.datetime64("1992-01-01")


def days(datestr: str) -> int:
    """'YYYY-MM-DD' as int days since 1992-01-01."""
    return int((np.datetime64(datestr) - EPOCH).astype(int))


def year(day: np.ndarray) -> np.ndarray:
    """The calendar year of each day number."""
    dates = EPOCH + day.astype("timedelta64[D]")
    return (dates.astype("datetime64[Y]").astype(np.int64) + 1970).astype(np.float64)


MAX_ORDER_DATE = days("1998-08-02")  # ENDDATE - 151 days
CURRENT_DATE = days("1995-06-17")


def sparse_orderkey(i: np.ndarray) -> np.ndarray:
    """dbgen's ``mk_sparse`` for row numbers 1..N: the low 3 bits kept, 2
    zero bits inserted above them, so 8 of every 32 key values are used."""
    return ((i >> 3) << 5) | (i & 7)


def retail_price(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE of each part key (spec clause 4.2.3)."""
    return (90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)) / 100.0


def part_supplier(partkey: np.ndarray, i: np.ndarray, n_supp: int) -> np.ndarray:
    """PS_SUPPKEY of part ``partkey``'s ``i``-th supplier, i in 0..3."""
    return (partkey + i * (n_supp // 4 + (partkey - 1) // n_supp)) % n_supp + 1


def _name_words(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` rows of NAME_WORDS distinct colour indices."""
    words = rng.integers(0, len(COLORS), (n, NAME_WORDS))
    while True:
        s = np.sort(words, axis=1)
        dup = np.flatnonzero((s[:, 1:] == s[:, :-1]).any(axis=1))
        if not len(dup):
            return words
        words[dup] = rng.integers(0, len(COLORS), (len(dup), NAME_WORDS))


def generate(scale_factor: float, seed: int) -> Tables:
    """The eight tables: ``{name: (columns, dictionaries)}``."""
    rng = np.random.default_rng(seed)
    sf = scale_factor
    n_supp = max(int(10_000 * sf), 4)
    n_part = max(int(200_000 * sf), 10)
    n_cust = max(int(150_000 * sf), 15)
    n_ord = max(int(1_500_000 * sf), 150)
    n_ps = 4  # suppliers per part
    f64 = np.float64
    t: Tables = {}
    t["region"] = (
        {"r_regionkey": np.arange(5, dtype=f64), "r_name": np.arange(5, dtype=f64)},
        {"r_name": REGIONS},
    )
    t["nation"] = (
        {
            "n_nationkey": np.arange(25, dtype=f64),
            "n_name": np.arange(25, dtype=f64),
            "n_regionkey": np.array([r for _, r in NATIONS], dtype=f64),
        },
        {"n_name": [n for n, _ in NATIONS]},
    )
    t["supplier"] = (
        {
            "s_suppkey": np.arange(1, n_supp + 1, dtype=f64),
            "s_nationkey": rng.integers(0, 25, n_supp).astype(f64),
            "s_acctbal": rng.integers(-99_999, 999_999, n_supp, endpoint=True) / 100.0,
        },
        {},
    )
    partkey = np.arange(1, n_part + 1)
    words = _name_words(rng, n_part)
    t["part"] = (
        {
            "p_partkey": partkey.astype(f64),
            "p_name": np.arange(n_part, dtype=f64),
            "p_type": rng.integers(0, len(TYPES), n_part).astype(f64),
            "p_size": rng.integers(1, 51, n_part).astype(f64),
            "p_retailprice": retail_price(partkey),
        },
        {"p_name": [" ".join(COLORS[w] for w in row) for row in words.tolist()],
         "p_type": TYPES},
    )
    ps_part = np.repeat(partkey, n_ps)
    t["partsupp"] = (
        {
            "ps_partkey": ps_part.astype(f64),
            "ps_suppkey": part_supplier(ps_part, np.tile(np.arange(n_ps), n_part), n_supp).astype(f64),
            "ps_supplycost": rng.integers(100, 100_000, len(ps_part), endpoint=True) / 100.0,
            "ps_availqty": rng.integers(1, 10_000, len(ps_part)).astype(f64),
        },
        {},
    )
    t["customer"] = (
        {
            "c_custkey": np.arange(1, n_cust + 1, dtype=f64),
            "c_mktsegment": rng.integers(0, 5, n_cust).astype(f64),
            "c_nationkey": rng.integers(0, 25, n_cust).astype(f64),
            "c_acctbal": rng.integers(-99_999, 999_999, n_cust, endpoint=True) / 100.0,
        },
        {"c_mktsegment": SEGMENTS},
    )
    # o_custkey: uniform over the customers whose key is not a multiple of 3
    live = np.arange(1, n_cust + 1)
    live = live[live % 3 != 0]
    o_orderdate = rng.integers(0, MAX_ORDER_DATE + 1, n_ord).astype(f64)
    orderkey = sparse_orderkey(np.arange(1, n_ord + 1))
    lines = rng.integers(1, 8, n_ord)
    o_row = np.repeat(np.arange(n_ord), lines)
    n_li = len(o_row)
    l_partkey = rng.integers(1, n_part + 1, n_li)
    l_suppkey = part_supplier(l_partkey, rng.integers(0, n_ps, n_li), n_supp)
    l_shipdate = o_orderdate[o_row] + rng.integers(1, 122, n_li)
    l_commitdate = o_orderdate[o_row] + rng.integers(30, 91, n_li)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n_li)
    quantity = rng.integers(1, 51, n_li).astype(f64)
    extprice = quantity * retail_price(l_partkey)
    discount = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    # returnflag R|A when received by CURRENTDATE, else N; linestatus O
    # when shipped after it, else F
    rflag = np.where(l_receiptdate <= CURRENT_DATE, rng.integers(0, 2, n_li), 2).astype(f64)
    lstatus = np.where(l_shipdate > CURRENT_DATE, 0, 1).astype(f64)
    t["orders"] = (
        {
            "o_orderkey": orderkey.astype(f64),
            "o_custkey": live[rng.integers(0, len(live), n_ord)].astype(f64),
            "o_orderdate": o_orderdate,
            "o_orderyear": year(o_orderdate),
            "o_shippriority": np.zeros(n_ord),
            "o_orderpriority": rng.integers(0, 5, n_ord).astype(f64),
            "o_totalprice": np.bincount(o_row, weights=extprice * (1 + tax) * (1 - discount),
                                        minlength=n_ord),
        },
        {"o_orderpriority": ORDER_PRIORITIES},
    )
    t["lineitem"] = (
        {
            "l_orderkey": orderkey[o_row].astype(f64),
            "l_partkey": l_partkey.astype(f64),
            "l_suppkey": l_suppkey.astype(f64),
            "l_quantity": quantity,
            "l_extendedprice": extprice,
            "l_discount": discount,
            "l_tax": tax,
            "l_returnflag": rflag,
            "l_linestatus": lstatus,
            "l_shipdate": l_shipdate,
            "l_shipyear": year(l_shipdate),
            "l_commitdate": l_commitdate,
            "l_receiptdate": l_receiptdate,
            "l_shipmode": rng.integers(0, 7, n_li).astype(f64),
        },
        {"l_returnflag": RETURN_FLAGS, "l_linestatus": LINE_STATUS, "l_shipmode": SHIP_MODES},
    )
    for cols, _ in t.values():
        for a in cols.values():
            a.setflags(write=False)  # the reference reads these after the run
    return t


def fingerprint(tables: Tables) -> Dict[str, object]:
    """Row counts and a digest of a few columns, to tell two runs' data apart."""
    h = hashlib.sha256()
    for name, col in (
        ("lineitem", "l_extendedprice"),
        ("lineitem", "l_shipdate"),
        ("orders", "o_custkey"),
        ("partsupp", "ps_supplycost"),
    ):
        h.update(np.ascontiguousarray(tables[name][0][col]).tobytes())
    h.update("|".join(tables["part"][1]["p_name"]).encode())
    return {
        "rows": {name: len(next(iter(cols.values()))) for name, (cols, _) in tables.items()},
        "sha256": h.hexdigest()[:16],
    }
