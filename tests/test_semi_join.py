"""Semi-joins (``HashJoin(kind="semi")``): TPC-H Q4's EXISTS, its shared
key state, the existence lens, and EXPLAIN GRAFT's accounting of it."""

import sys
from pathlib import Path

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.core.descriptors import StateSignature
from repro.core.plans import AggSpec, Aggregate, HashJoin, OrderBy, Query, Scan
from repro.core.predicates import Cmp, ColCmp
from repro.core.state import SharedHashBuildState
from repro.relational import queries, refexec
from repro.relational.table import Database, Table

ROOT = Path(__file__).resolve().parents[1]
DATES = (366.0, 823.0, 1461.0)  # 1993-01-01, 1994-04-03, 1996-01-01


def _q4(db, date, qid, arrival=0.0):
    p = {"date": float(date)}
    return Query(qid=qid, template="q4", plan=queries.q4_semi_plan(db, p), params=p,
                 arrival=arrival)


def _semi_count(build_pred, qid, date=823.0):
    """Orders of a quarter with a line that ``build_pred`` accepts, counted
    by priority: a semi-join over the lineitem key state Q4 uses."""
    orders = Scan("orders", Cmp("o_orderdate", ">=", date), ("o_orderkey", "o_orderpriority"))
    lines = Scan("lineitem", build_pred, ("l_orderkey",))
    j = HashJoin(lines, orders, ("l_orderkey",), ("o_orderkey",), (), kind="semi")
    agg = Aggregate(j, ("o_orderpriority",), (AggSpec("count", name="n"),))
    return Query(qid=qid, template="semi", plan=OrderBy(agg, ("o_orderpriority",), (True,)))


def _same(got, want):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=1e-9, atol=0)


def _bench_db(seed):
    sys.path.insert(0, str(ROOT))
    from bench.harness.spec import load_module

    configs = ROOT / "bench" / "configs"
    tables = load_module(configs, "tpch_data").generate(0.01, seed)
    db = Database({n: Table(n, dict(c), dict(d)) for n, (c, d) in tables.items()}, 0.01)
    return tables, db, load_module(configs, "tpch_reference")


@pytest.mark.parametrize("seed", [11, 2**31 + 5])
def test_semi_plan_equals_refexec_reference_and_emulated_plan(seed):
    tables, db, ref = _bench_db(seed)
    for date in DATES:
        p = {"date": date}
        got = refexec.execute(db, queries.q4_semi_plan(db, p))
        want = ref.answer(tables, "q4", p)
        emulated = refexec.execute(db, queries.q4_plan(db, p))
        np.testing.assert_array_equal(got["o_orderpriority"], want["o_orderpriority"])
        np.testing.assert_array_equal(got["order_count"], want["order_count"])
        np.testing.assert_array_equal(got["order_count"], emulated["order_count"])
        session = graftdb.connect(db, EngineConfig(mode="isolated", morsel_size=4096))
        fut = session.submit(Query(qid=1, template="q4", plan=queries.q4_semi_plan(db, p)))
        session.run()
        _same(fut.result(), got)


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_concurrent_q4s_share_one_semi_state(db, backend):
    session = graftdb.connect(
        db, EngineConfig(mode="graft", backend=backend, morsel_size=4096)
    )
    qs = [_q4(db, d, 10 + i, arrival=0.01 * i) for i, d in enumerate(DATES + (1000.0,))]
    futs = session.submit_all(qs)
    session.run()
    for q, f in zip(qs, futs):
        _same(f.result(), refexec.execute(db, q.plan))
    c = session.counters
    assert c["semi_builds"] == 1 and c["semi_attaches"] == 3


def test_later_q4_is_represented_by_the_retained_semi_state(db):
    session = graftdb.connect(
        db, EngineConfig(mode="graft", backend="pallas", morsel_size=4096, retention="epoch")
    )
    first = session.submit(_q4(db, DATES[0], 1))
    session.run()
    later = _q4(db, DATES[1], 2, arrival=session.now)
    exp = session.explain_graft(later)
    (b,) = exp.boundaries
    assert b.decision == "represented" and b.build_table == "lineitem"
    assert b.demand_rows == b.represented_rows == len(
        np.unique(db["lineitem"].columns["l_orderkey"][
            db["lineitem"].columns["l_commitdate"] < db["lineitem"].columns["l_receiptdate"]
        ])
    )
    fut = session.submit(later)
    session.run()
    _same(first.result(), refexec.execute(db, queries.q4_semi_plan(db, {"date": DATES[0]})))
    _same(fut.result(), refexec.execute(db, later.plan))
    c = session.counters
    assert c["semi_builds"] == 1 and c["semi_attaches"] == 1
    assert c["graft_represented_rows"] == b.demand_rows
    assert c["graft_demand_rows"] == 2 * b.demand_rows


def test_each_query_sees_only_keys_its_build_predicate_accepts():
    """The existence lens: a key is visible to a query iff some row its
    predicate accepts was incorporated for it (the OR of the rows' masks)."""
    sig = StateSignature("hash_build", ("semi-test",))
    s = SharedHashBuildState(1, sig, ("k",), (), semi=True)
    keys = np.array([1, 1, 2, 2, 3, 4, 4, 4], dtype=np.int64)
    qty = np.array([5, 25, 15, 35, 40, 2, 50, 20], dtype=np.float64)
    b1 = np.uint64(1) << np.uint64(s.slots.get(101))  # l_quantity < 10
    b2 = np.uint64(1) << np.uint64(s.slots.get(102))  # l_quantity < 30
    vis = np.where(qty < 10, b1, np.uint64(0)) | np.where(qty < 30, b2, np.uint64(0))
    ins, _ = s.insert_or_mark(np.arange(8) + 100, keys, {"k": keys.astype(float)},
                              vis, np.zeros(8, np.uint64))
    assert ins == 4 and s.n_entries == 4  # one entry per key
    probe = np.array([1, 2, 3, 4, 9], dtype=np.int64)
    pi, ei = s.probe(probe)
    assert list(probe[pi]) == [1, 2, 3, 4]  # each probe row at most once
    assert list(probe[pi][s.visible_mask(101, ei)]) == [1, 4]
    assert list(probe[pi][s.visible_mask(102, ei)]) == [1, 2, 4]


@pytest.mark.parametrize("backend", ["reference", "pallas"])
def test_two_build_predicates_over_one_semi_state(db, backend):
    session = graftdb.connect(db, EngineConfig(mode="graft", backend=backend, morsel_size=4096))
    qa = _semi_count(Cmp("l_quantity", "<", 10.0), 1)
    qb = _semi_count(Cmp("l_quantity", "<", 30.0), 2, date=1200.0)
    qc = _semi_count(Cmp("l_quantity", ">=", 45.0), 3)
    futs = session.submit_all([qa, qb, qc])
    session.run()
    for q, f in zip((qa, qb, qc), futs):
        _same(f.result(), refexec.execute(db, q.plan))
    assert session.counters["semi_builds"] == 1 and session.counters["semi_attaches"] == 2


def test_inner_join_never_attaches_to_a_semi_state(db):
    session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=4096, retention="epoch")
    )
    session.submit(_q4(db, DATES[0], 1))
    session.run()
    pred = ColCmp("l_commitdate", "<", "l_receiptdate")
    orders = Scan("orders", Cmp("o_orderdate", ">=", 823.0), ("o_orderkey", "o_orderpriority"))
    lines = Scan("lineitem", pred, ("l_orderkey",))
    inner = HashJoin(lines, orders, ("l_orderkey",), ("o_orderkey",), ())
    agg = Aggregate(inner, ("o_orderpriority",), (AggSpec("count", name="n"),))
    q = Query(qid=2, template="inner", plan=OrderBy(agg, ("o_orderpriority",), (True,)))
    (b,) = session.explain_graft(q).boundaries
    assert b.decision == "ordinary" and b.state_id is None
    fut = session.submit(q)
    session.run()
    _same(fut.result(), refexec.execute(db, q.plan))
    assert session.counters["semi_attaches"] == 0


@pytest.mark.parametrize("retention", ["refcount", "epoch"])
def test_explain_accounting_is_exact_for_semi_states(db, retention):
    session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=4096, retention=retention)
    )
    session.submit(_semi_count(Cmp("l_quantity", "<", 10.0), 1))
    pending = [_semi_count(Cmp("l_quantity", "<", 30.0), 2), _q4(db, DATES[2], 3)]
    for q in pending:
        for b in (bb for root in session.explain_graft(q).boundaries for bb in root.flat()):
            assert b.demand_rows == b.represented_rows + b.residual_rows + b.unattached_rows
    session.run()
    session.submit(pending[0])
    exp = session.explain_graft(pending[1])
    assert exp.total_demand_rows == (
        exp.represented_rows + exp.residual_rows + exp.unattached_rows
    )
    session.run()
