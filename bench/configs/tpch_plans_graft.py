"""The engine's plan of each TPC-H query in graft mode, on this data's
encoding.

Q4 is clause 2.4.4's ``EXISTS`` as the program's semi-join
(``repro.relational.queries.q4_semi_plan``): orders in the quarter that
have a line received after its commit date. Every other query is built
exactly as ``tpch_plans`` builds it, and every query's id comes from
``tpch_plans``'s counter, so ids stay unique within the run.
"""

from __future__ import annotations

from pathlib import Path
from typing import Dict

from bench.harness.spec import load_module

_base = load_module(Path(__file__).resolve().parent, "tpch_plans")


def make_query(db, template: str, params: Dict, arrival: float = 0.0):
    if template != "q4":
        return _base.make_query(db, template, params, arrival=arrival)
    from repro.core.plans import Query
    from repro.relational.queries import q4_semi_plan

    return Query(qid=next(_base._qids), template=template, plan=q4_semi_plan(db, params),
                 params=params, arrival=arrival)
