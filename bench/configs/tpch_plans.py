"""The engine's plan of each TPC-H query, on this data's encoding.

Q1 and Q3-Q8 and Q10 are the program's own plans
(``repro.relational.queries.BUILDERS``). Q9 is built here: its part filter
is the spec's ``p_name LIKE '%<color>%'``, which a dictionary-coded store
answers by matching the pattern against the dictionary of ``p_name`` and
keeping the codes that match (an ``InSet``); the rest of the plan is the
program's. Every query of a run goes through ``make_query``, so query ids
are unique within the run.
"""

from __future__ import annotations

import itertools
from typing import Dict

import numpy as np

_qids = itertools.count(1)


def name_codes(db, color: str) -> frozenset:
    """Codes of the part names that contain ``color``, kept on ``db``."""
    cache = db.__dict__.setdefault("_bench_like_codes", {})
    if color not in cache:
        names = np.asarray(db.tables["part"].dictionaries["p_name"])
        hit = np.char.find(names, color) >= 0
        cache[color] = frozenset(np.flatnonzero(hit).astype(np.float64).tolist())
    return cache[color]


def q9_plan(db, p: Dict):
    from repro.core.plans import AggSpec, Aggregate, BinOp, Col, HashJoin, OrderBy, Scan
    from repro.core.predicates import TRUE, InSet
    from repro.relational.queries import REVENUE

    part = Scan("part", InSet("p_name", name_codes(db, p["color"])), ("p_partkey",))
    supplier = Scan("supplier", TRUE, ("s_suppkey", "s_nationkey"))
    partsupp = Scan("partsupp", TRUE, ("ps_partkey", "ps_suppkey", "ps_supplycost"))
    orders = Scan("orders", TRUE, ("o_orderkey", "o_orderyear"))
    nation = Scan("nation", TRUE, ("n_nationkey", "n_name"))
    lineitem = Scan(
        "lineitem",
        TRUE,
        ("l_orderkey", "l_partkey", "l_suppkey", "l_quantity", "l_extendedprice", "l_discount"),
    )
    j1 = HashJoin(part, lineitem, ("p_partkey",), ("l_partkey",), ())
    j2 = HashJoin(
        partsupp, j1, ("ps_partkey", "ps_suppkey"), ("l_partkey", "l_suppkey"), ("ps_supplycost",)
    )
    j3 = HashJoin(supplier, j2, ("s_suppkey",), ("l_suppkey",), ("s_nationkey",))
    j4 = HashJoin(orders, j3, ("o_orderkey",), ("l_orderkey",), ("o_orderyear",))
    j5 = HashJoin(nation, j4, ("n_nationkey",), ("s_nationkey",), ("n_name",))
    profit = BinOp("-", REVENUE, BinOp("*", Col("ps_supplycost"), Col("l_quantity")))
    agg = Aggregate(j5, ("n_name", "o_orderyear"), (AggSpec("sum", profit, name="sum_profit"),))
    return OrderBy(agg, ("n_name", "o_orderyear"), (True, False))


def make_query(db, template: str, params: Dict, arrival: float = 0.0):
    from repro.core.plans import Query
    from repro.relational.queries import BUILDERS

    build = q9_plan if template == "q9" else BUILDERS[template]
    return Query(qid=next(_qids), template=template, plan=build(db, params),
                 params=params, arrival=arrival)
