"""Plain reference for the TPC-H configurations: Q1 and Q3-Q10 in NumPy.

Each query is written out by hand from its TPC-H definition, in this data's
encoding (``tpch_data``): one pass of joins on unique build keys, a group-by,
and the ORDER BY with its LIMIT. Nothing here comes from the engine: no plan,
no predicate, no operator state. ``answer(tables, template, params)`` returns
``{column: array}`` with the GROUP BY keys and the aggregates in ORDER BY
order, float64 throughout. ``dtype=np.float32`` computes every expression
and sum in float32, the precision below the configuration's.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

Answer = Dict[str, np.ndarray]


def days(datestr: str) -> int:
    """'YYYY-MM-DD' as int days since 1992-01-01."""
    return int((np.datetime64(datestr) - np.datetime64("1992-01-01")).astype(int))


def _lookup(build_keys: np.ndarray, probe_keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Equi-join on a unique build key: (probe rows that match, build row of each)."""
    if len(build_keys) == 0:
        return np.zeros(len(probe_keys), bool), np.zeros(len(probe_keys), np.int64)
    order = np.argsort(build_keys, kind="stable")
    sk = build_keys[order]
    pos = np.minimum(np.searchsorted(sk, probe_keys), len(sk) - 1)
    return sk[pos] == probe_keys, order[pos]


def _group(keys: Dict[str, np.ndarray], sums: Dict[str, np.ndarray], dtype) -> Answer:
    """GROUP BY ``keys``: each column of ``sums`` summed per group."""
    names = list(keys)
    n = len(next(iter({**keys, **sums}.values())))
    code = np.zeros(n, np.int64)
    levels = []
    for k in names:  # mixed-radix code over each key's distinct values
        vals, inv_k = np.unique(keys[k], return_inverse=True)
        code = code * len(vals) + inv_k.ravel()
        levels.append(vals)
    ucode, inv = np.unique(code, return_inverse=True) if names else (np.zeros(1, np.int64), code)
    inv = np.asarray(inv).ravel()
    ng = len(ucode)
    out: Answer = {}
    for k, vals in reversed(list(zip(names, levels))):
        out[k] = vals[ucode % len(vals)].astype(np.float64)
        ucode = ucode // len(vals)
    out = {k: out[k] for k in names}
    for name, vals in sums.items():
        if dtype == np.float64:
            acc = np.bincount(inv, weights=vals, minlength=ng)
        else:
            acc = np.zeros(ng, dtype)
            np.add.at(acc, inv, np.asarray(vals, dtype))
        out[name] = acc.astype(np.float64)
    out["__count"] = np.bincount(inv, minlength=ng).astype(np.float64)
    return out


def _order(ans: Answer, keys: Sequence[str], ascending: Sequence[bool], limit=None) -> Answer:
    cols = [ans[k] if asc else -ans[k] for k, asc in zip(reversed(keys), reversed(ascending))]
    n = len(next(iter(ans.values())))
    order = np.lexsort(cols) if cols else np.arange(n)
    if limit is not None:
        order = order[:limit]
    return {k: v[order] for k, v in ans.items() if not k.startswith("__")}


def _revenue(li, rows, dtype):
    ext = li["l_extendedprice"][rows].astype(dtype)
    return ext * (dtype(1.0) - li["l_discount"][rows].astype(dtype))


def q1(t, p, dtype):
    li = t["lineitem"][0]
    rows = np.flatnonzero(li["l_shipdate"] <= days("1998-12-01") - p["delta"])
    disc_price = _revenue(li, rows, dtype)
    charge = disc_price * (dtype(1.0) + li["l_tax"][rows].astype(dtype))
    g = _group(
        {"l_returnflag": li["l_returnflag"][rows], "l_linestatus": li["l_linestatus"][rows]},
        {
            "sum_qty": li["l_quantity"][rows],
            "sum_base_price": li["l_extendedprice"][rows],
            "sum_disc_price": disc_price,
            "sum_charge": charge,
            "__disc": li["l_discount"][rows],
        },
        dtype,
    )
    cnt = g["__count"]
    g["avg_qty"] = g["sum_qty"] / cnt
    g["avg_price"] = g["sum_base_price"] / cnt
    g["avg_disc"] = g.pop("__disc") / cnt
    g["count_order"] = cnt.copy()
    return _order(g, ("l_returnflag", "l_linestatus"), (True, True))


def q3(t, p, dtype):
    cu, od, li = t["customer"][0], t["orders"][0], t["lineitem"][0]
    custs = cu["c_custkey"][cu["c_mktsegment"] == p["segment"]]
    o_rows = np.flatnonzero(od["o_orderdate"] < p["date"])
    o_rows = o_rows[np.isin(od["o_custkey"][o_rows], custs)]
    l_rows = np.flatnonzero(li["l_shipdate"] > p["date"])
    hit, b = _lookup(od["o_orderkey"][o_rows], li["l_orderkey"][l_rows])
    l_rows, o_match = l_rows[hit], o_rows[b[hit]]
    g = _group(
        {
            "l_orderkey": li["l_orderkey"][l_rows],
            "o_orderdate": od["o_orderdate"][o_match],
            "o_shippriority": od["o_shippriority"][o_match],
        },
        {"revenue": _revenue(li, l_rows, dtype)},
        dtype,
    )
    return _order(g, ("revenue", "o_orderdate"), (False, True), limit=10)


def q4(t, p, dtype):
    od, li = t["orders"][0], t["lineitem"][0]
    d0 = p["date"]
    o_rows = np.flatnonzero((od["o_orderdate"] >= d0) & (od["o_orderdate"] < d0 + 92))
    l_rows = np.flatnonzero(li["l_commitdate"] < li["l_receiptdate"])
    hit, b = _lookup(od["o_orderkey"][o_rows], li["l_orderkey"][l_rows])
    o_match = o_rows[b[hit]]
    # count(distinct o_orderkey) per priority: each matching order once
    o_once = np.unique(o_match)
    g = _group({"o_orderpriority": od["o_orderpriority"][o_once]}, {}, dtype)
    g["order_count"] = g["__count"].copy()
    return _order(g, ("o_orderpriority",), (True,))


def q5(t, p, dtype):
    na, cu, od, su, li = (t[k][0] for k in ("nation", "customer", "orders", "supplier", "lineitem"))
    d0 = p["date"]
    nations = na["n_nationkey"][na["n_regionkey"] == p["region"]]
    o_rows = np.flatnonzero((od["o_orderdate"] >= d0) & (od["o_orderdate"] < d0 + 365))
    hit, b = _lookup(cu["c_custkey"], od["o_custkey"][o_rows])
    o_rows, c_of_o = o_rows[hit], b[hit]
    l_rows = np.arange(len(li["l_orderkey"]))
    hit, b = _lookup(od["o_orderkey"][o_rows], li["l_orderkey"])
    l_rows, c_nat = l_rows[hit], cu["c_nationkey"][c_of_o[b[hit]]]
    hit, b = _lookup(su["s_suppkey"], li["l_suppkey"][l_rows])
    s_nat = su["s_nationkey"][b]
    keep = hit & (s_nat == c_nat)
    l_rows, s_nat = l_rows[keep], s_nat[keep]
    hit, b = _lookup(nations, s_nat)
    l_rows, s_nat = l_rows[hit], s_nat[hit]
    hit, b = _lookup(na["n_nationkey"], s_nat)
    g = _group({"n_name": na["n_name"][b]}, {"revenue": _revenue(li, l_rows, dtype)}, dtype)
    return _order(g, ("revenue",), (False,))


def q6(t, p, dtype):
    li = t["lineitem"][0]
    d0, disc, qty = p["date"], p["discount"], p["quantity"]
    rows = np.flatnonzero(
        (li["l_shipdate"] >= d0)
        & (li["l_shipdate"] < d0 + 365)
        & (li["l_discount"] >= round(disc - 0.01, 4))
        & (li["l_discount"] <= round(disc + 0.01, 4))
        & (li["l_quantity"] < qty)
    )
    vals = li["l_extendedprice"][rows].astype(dtype) * li["l_discount"][rows].astype(dtype)
    g = _group({}, {"revenue": vals}, dtype)
    return _order(g, (), ())


def q7(t, p, dtype):
    na, su, cu, od, li = (t[k][0] for k in ("nation", "supplier", "customer", "orders", "lineitem"))
    pair = na["n_nationkey"][np.isin(na["n_name"], [p["nation1"], p["nation2"]])]
    s_in = np.isin(su["s_nationkey"], pair)
    c_in = np.isin(cu["c_nationkey"], pair)
    l_rows = np.flatnonzero(
        (li["l_shipdate"] >= days("1995-01-01")) & (li["l_shipdate"] <= days("1996-12-31"))
    )
    s_rows = np.flatnonzero(s_in)
    hit, b = _lookup(su["s_suppkey"][s_rows], li["l_suppkey"][l_rows])
    l_rows, supp_nation = l_rows[hit], su["s_nationkey"][s_rows[b[hit]]]
    hit, b = _lookup(od["o_orderkey"], li["l_orderkey"][l_rows])
    l_rows, supp_nation, custkey = l_rows[hit], supp_nation[hit], od["o_custkey"][b[hit]]
    c_rows = np.flatnonzero(c_in)
    hit, b = _lookup(cu["c_custkey"][c_rows], custkey)
    l_rows, supp_nation = l_rows[hit], supp_nation[hit]
    cust_nation = cu["c_nationkey"][c_rows[b[hit]]]
    keep = supp_nation != cust_nation
    l_rows = l_rows[keep]
    _, sn = _lookup(na["n_nationkey"], supp_nation[keep])
    _, cn = _lookup(na["n_nationkey"], cust_nation[keep])
    g = _group(
        {
            "supp_nation": na["n_name"][sn],
            "cust_nation": na["n_name"][cn],
            "l_shipyear": li["l_shipyear"][l_rows],
        },
        {"revenue": _revenue(li, l_rows, dtype)},
        dtype,
    )
    return _order(g, ("supp_nation", "cust_nation", "l_shipyear"), (True, True, True))


def q8(t, p, dtype):
    pa, su, na, cu, od, li = (
        t[k][0] for k in ("part", "supplier", "nation", "customer", "orders", "lineitem")
    )
    nations = na["n_nationkey"][na["n_regionkey"] == p["region"]]
    custs = cu["c_custkey"][np.isin(cu["c_nationkey"], nations)]
    o_rows = np.flatnonzero(
        (od["o_orderdate"] >= days("1995-01-01")) & (od["o_orderdate"] <= days("1996-12-31"))
    )
    o_rows = o_rows[np.isin(od["o_custkey"][o_rows], custs)]
    parts = pa["p_partkey"][pa["p_type"] == p["type"]]
    l_rows = np.flatnonzero(np.isin(li["l_partkey"], parts))
    hit, b = _lookup(su["s_suppkey"], li["l_suppkey"][l_rows])
    l_rows, s_nat = l_rows[hit], su["s_nationkey"][b[hit]]
    hit, b = _lookup(od["o_orderkey"][o_rows], li["l_orderkey"][l_rows])
    l_rows, s_nat, year = l_rows[hit], s_nat[hit], od["o_orderyear"][o_rows[b[hit]]]
    hit, b = _lookup(na["n_nationkey"], s_nat)
    supp_nation = na["n_name"][b]
    vol = _revenue(li, l_rows, dtype)
    g = _group(
        {"o_orderyear": year},
        {"nation_volume": np.where(supp_nation == p["nation"], vol, dtype(0.0)), "total_volume": vol},
        dtype,
    )
    return _order(g, ("o_orderyear",), (True,))


def q9(t, p, dtype):
    pa, ps, su, od, na, li = (
        t[k][0] for k in ("part", "partsupp", "supplier", "orders", "nation", "lineitem")
    )
    # p_name LIKE '%<color>%', matched on each part's name string
    names = np.asarray(t["part"][1]["p_name"])[pa["p_name"].astype(np.int64)]
    parts = pa["p_partkey"][np.char.find(names, p["color"]) >= 0]
    l_rows = np.flatnonzero(np.isin(li["l_partkey"], parts))
    radix = float(1 << 21)
    hit, b = _lookup(
        ps["ps_partkey"] * radix + ps["ps_suppkey"],
        li["l_partkey"][l_rows] * radix + li["l_suppkey"][l_rows],
    )
    l_rows, cost = l_rows[hit], ps["ps_supplycost"][b[hit]]
    hit, b = _lookup(su["s_suppkey"], li["l_suppkey"][l_rows])
    l_rows, cost, s_nat = l_rows[hit], cost[hit], su["s_nationkey"][b[hit]]
    hit, b = _lookup(od["o_orderkey"], li["l_orderkey"][l_rows])
    l_rows, cost, s_nat, year = l_rows[hit], cost[hit], s_nat[hit], od["o_orderyear"][b[hit]]
    hit, b = _lookup(na["n_nationkey"], s_nat)
    profit = _revenue(li, l_rows, dtype) - cost.astype(dtype) * li["l_quantity"][l_rows].astype(dtype)
    g = _group({"n_name": na["n_name"][b], "o_orderyear": year}, {"sum_profit": profit}, dtype)
    return _order(g, ("n_name", "o_orderyear"), (True, False))


def q10(t, p, dtype):
    cu, od, na, li = (t[k][0] for k in ("customer", "orders", "nation", "lineitem"))
    d0 = p["date"]
    o_rows = np.flatnonzero((od["o_orderdate"] >= d0) & (od["o_orderdate"] < d0 + 92))
    hit, b = _lookup(cu["c_custkey"], od["o_custkey"][o_rows])
    o_rows, c_rows = o_rows[hit], b[hit]
    l_rows = np.flatnonzero(li["l_returnflag"] == 0.0)  # 'R'
    hit, b = _lookup(od["o_orderkey"][o_rows], li["l_orderkey"][l_rows])
    l_rows, c_rows = l_rows[hit], c_rows[b[hit]]
    hit, b = _lookup(na["n_nationkey"], cu["c_nationkey"][c_rows])
    g = _group(
        {"c_custkey": cu["c_custkey"][c_rows], "n_name": na["n_name"][b]},
        {"revenue": _revenue(li, l_rows, dtype)},
        dtype,
    )
    # c_acctbal is one value per customer, so its max is that value
    hit, b = _lookup(cu["c_custkey"], g["c_custkey"])
    g["c_acctbal"] = cu["c_acctbal"][b].astype(dtype).astype(np.float64)
    return _order(g, ("revenue",), (False,), limit=20)


TEMPLATES = {
    "q1": q1, "q3": q3, "q4": q4, "q5": q5, "q6": q6,
    "q7": q7, "q8": q8, "q9": q9, "q10": q10,
}


def answer(tables, template: str, params: Dict[str, float], dtype=np.float64) -> Answer:
    return TEMPLATES[template](tables, params, dtype)
