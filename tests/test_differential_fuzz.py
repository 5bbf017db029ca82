"""Differential workload fuzzer + eviction-safety properties (DESIGN.md §10).

The fuzzer generates seeded random TPC-H query mixes and replays each one
through the full overload path — graft mode with ``retention='epoch'``, a
deliberately tiny ``memory_budget`` (so the evictor fires mid-run), and
``admission='adaptive'`` (so arrivals queue) — under ``workers ∈ {1, 4}``,
plus an isolated-mode run of the same workload. Every completed query is
checked for exact parity against the reference executor
(``relational/refexec.py``); the suite asserts >= 200 such parity instances
so the acceptance floor is self-checking.

Eviction safety is tested as properties: an evicted state hard-fails any
observation (the runtime guard IS the soundness mechanism — a fuzz run that
completes cleanly never read reclaimed fragments), EXPLAIN GRAFT's
per-partition represented + residual + unattached == demand identity
survives forced evictions, and re-admitting a query whose state range was
evicted recomputes from scratch, correctly.

Uses ``tests/_hypothesis_compat.py`` so tier-1 passes without hypothesis.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import graftdb
from graftdb import EngineConfig
from repro.relational import queries, refexec
from repro.relational.table import days

# The overload path under stress: tiny budget -> constant evictions; small
# max_inflight -> real queueing; small morsels -> many scheduling steps.
EVICT = dict(
    mode="graft",
    morsel_size=4096,
    retention="epoch",
    memory_budget=200_000,
    admission="adaptive",
    admission_max_inflight=3,
    admission_share_threshold=0.4,
)

#: parity-checked (query, engine-run) instances across the fuzz sweep —
#: the acceptance criterion requires >= 200 in the tier-1 budget
FUZZ_SEEDS = range(24)


def _canon(res):
    keys = sorted(res)
    order = np.lexsort([np.asarray(res[k]) for k in keys])
    return {k: np.asarray(res[k])[order] for k in keys}


def _assert_parity(engine_res, ref_res, ctx):
    ca, cb = _canon(engine_res), _canon(ref_res)
    assert set(ca) == set(cb), ctx
    for k in ca:
        assert ca[k].shape == cb[k].shape, (ctx, k)
        np.testing.assert_allclose(
            ca[k], cb[k], rtol=1e-12, atol=1e-12, err_msg=f"{ctx}/{k}"
        )


def _fuzz_workload(db, rng):
    """3-5 queries from the Zipf template mix; arrivals interleave racing
    (same-instant) and spread gaps so completions — and therefore the
    retire/evict/revive cycle — overlap admissions."""
    n = int(rng.integers(3, 6))
    qs, t = [], 0.0
    for _ in range(n):
        t += float(rng.choice([0.0, 0.002, 0.02, 0.08]))
        qs.append(queries.sample_query(db, rng, arrival=t))
    return qs


def _rebuild(db, qs):
    """Fresh Query objects (unique qids) with identical plans/arrivals."""
    return [
        queries.make_query(db, q.template, q.params, arrival=q.arrival) for q in qs
    ]


def _run_all(db, qs, **cfg):
    session = graftdb.connect(db, EngineConfig(**cfg))
    futs = session.submit_all(qs)
    session.run()
    return session, futs


def test_differential_fuzzer_parity(db):
    """>= 200 seeded workload parity instances: graft + eviction + admission
    under workers 1 and 4, and isolated mode, all vs the reference executor."""
    checks = 0
    evictions = queued = spills = hits = 0
    for seed in FUZZ_SEEDS:
        rng = np.random.default_rng(10_000 + seed)
        qs = _fuzz_workload(db, rng)
        refs = [refexec.execute(db, q.plan) for q in qs]
        runs = (
            ("graft-w1", dict(EVICT, workers=1, partitions=1)),
            ("graft-w4", dict(EVICT, workers=4, partitions=4)),
            # the reuse plane under stress (§12): a cache small enough that
            # the artifact tier itself evicts mid-run, so parity covers
            # spill -> age-out -> recompute alongside spill -> rehydrate
            ("graft-w1-cache", dict(EVICT, workers=1, partitions=1,
                                    memory_budget=100_000,
                                    reuse_cache_budget=400_000)),
            ("isolated", dict(mode="isolated", morsel_size=4096, workers=1, partitions=1)),
        )
        for label, cfg in runs:
            session, futs = _run_all(db, _rebuild(db, qs), **cfg)
            for i, (f, ref) in enumerate(zip(futs, refs)):
                _assert_parity(f.result(), ref, ctx=f"seed{seed}/{label}/q{i}")
                checks += 1
            st_ = session.stats()
            evictions += st_["evictions"]
            queued += st_["queued_admissions"]
            spills += st_.get("cache_spills", 0)
            hits += st_.get("cache_hits", 0)
            if "cache_high_water_bytes" in st_:
                assert st_["cache_high_water_bytes"] <= 400_000
            assert st_["queued_pending"] == 0  # run() drained the admit queue
            session.close()
    assert checks >= 200, f"only {checks} parity instances — raise FUZZ_SEEDS"
    # the sweep must actually exercise the overload machinery, not idle it
    assert evictions > 0, "no evictions across the fuzz sweep — budget too loose"
    assert queued > 0, "no queued admissions across the fuzz sweep"
    assert spills > 0, "the cache leg never spilled — budget too loose"
    assert hits > 0, "the cache leg never rehydrated an artifact"


# ---------------------------------------------------------------------------
# Semi-join differential leg
# ---------------------------------------------------------------------------

#: seeds of the semi-join leg
SEMI_SEEDS = range(8)


def _semi_query(db, rng, arrival):
    """Q4 as its EXISTS semi-join, or a semi-join of a year of orders on the
    lineitem rows in a random quantity band (the same key state as Q4's,
    under another build predicate)."""
    from repro.core.plans import AggSpec, Aggregate, HashJoin, OrderBy, Query, Scan
    from repro.core.predicates import And, Cmp

    if rng.random() < 0.5:
        p = queries._sample_params("q4", rng)
        return Query(0, "q4", queries.q4_semi_plan(db, p), p, arrival)
    lo = float(rng.integers(1, 40))
    p = {"lo": lo, "hi": lo + float(rng.integers(1, 20)), "date": float(rng.integers(0, 6)) * 365}
    lines = Scan("lineitem", And((Cmp("l_quantity", ">=", p["lo"]),
                                  Cmp("l_quantity", "<", p["hi"]))), ("l_orderkey",))
    orders = Scan("orders", And((Cmp("o_orderdate", ">=", p["date"]),
                                 Cmp("o_orderdate", "<", p["date"] + 365))),
                  ("o_orderkey", "o_orderpriority"))
    j = HashJoin(lines, orders, ("l_orderkey",), ("o_orderkey",), (), kind="semi")
    agg = Aggregate(j, ("o_orderpriority",), (AggSpec("count", name="n"),))
    return Query(0, "semi", OrderBy(agg, ("o_orderpriority",), (True,)), p, arrival)


def test_semi_join_differential_leg(db):
    """Semi-joins among the other templates, racing and spread, in graft
    mode (with eviction and admission, partitioned, on the device path) and
    in isolated mode, all vs the reference executor."""
    from dataclasses import replace

    checks = attaches = 0
    for seed in SEMI_SEEDS:
        rng = np.random.default_rng(20_000 + seed)
        qs, t = [], 0.0
        for _ in range(int(rng.integers(3, 7))):
            t += float(rng.choice([0.0, 0.002, 0.02]))
            qs.append(_semi_query(db, rng, t) if rng.random() < 0.6
                      else queries.sample_query(db, rng, arrival=t))
        refs = [refexec.execute(db, q.plan) for q in qs]
        runs = (
            ("graft-w1", dict(EVICT, workers=1, partitions=1)),
            ("graft-w4", dict(EVICT, workers=4, partitions=4)),
            ("graft-pallas", dict(mode="graft", backend="pallas", morsel_size=4096)),
            ("isolated", dict(mode="isolated", morsel_size=4096)),
        )
        for label, cfg in runs:
            fresh = [replace(q, qid=i + 1) for i, q in enumerate(qs)]
            session, futs = _run_all(db, fresh, **cfg)
            for i, (f, ref) in enumerate(zip(futs, refs)):
                _assert_parity(f.result(), ref, ctx=f"seed{seed}/{label}/q{i}")
                checks += 1
            attaches += session.stats()["semi_attaches"]
            session.close()
    assert checks >= 100
    assert attaches > 0, "no query ever grafted onto a semi state"


# ---------------------------------------------------------------------------
# Batch-planning differential leg (§15)
# ---------------------------------------------------------------------------

#: seeds for the burst-heavy batch leg (ties dominate so cohorts form)
BATCH_SEEDS = range(10)


def _burst_workload(db, rng):
    """3-6 queries with same-instant ties the rule, not the exception: the
    gap mix is weighted toward 0.0 so most decision steps hold a cohort."""
    n = int(rng.integers(3, 7))
    qs, t = [], 0.0
    for _ in range(n):
        t += float(rng.choice([0.0, 0.0, 0.0, 0.002, 0.02]))
        qs.append(queries.sample_query(db, rng, arrival=t))
    return qs


def _explain_accounting(exp, ctx):
    """EXPLAIN GRAFT exactness: represented + residual + unattached == demand
    in total and per key partition, for every boundary."""
    for root in exp.boundaries:
        for b in root.flat():
            assert (
                b.represented_rows + b.residual_rows + b.unattached_rows
                == b.demand_rows
            ), (ctx, b)
            assert sum(b.part_demand_rows) == b.demand_rows, (ctx, b)
            for p in range(len(b.part_demand_rows)):
                assert (
                    b.part_represented_rows[p]
                    + b.part_residual_rows[p]
                    + b.part_unattached_rows[p]
                    == b.part_demand_rows[p]
                ), (ctx, b, p)
    assert exp.total_demand_rows == (
        exp.represented_rows + exp.residual_rows + exp.unattached_rows
    ), ctx


def test_graft_batch_differential_leg(db):
    """Randomized burst arrivals replayed through greedy grafting, batch
    planning (workers 1 and 4), and isolated execution: every leg matches
    the reference executor bit-for-bit (canonical order), the two batch
    worker counts match each other, and each batch-admitted query's captured
    EXPLAIN satisfies the per-partition accounting identity."""
    checks = cohorts = 0
    for seed in BATCH_SEEDS:
        rng = np.random.default_rng(21_000 + seed)
        qs = _burst_workload(db, rng)
        refs = [refexec.execute(db, q.plan) for q in qs]
        runs = (
            ("greedy-w1", dict(EVICT, workers=1, partitions=1)),
            ("batch-w1", dict(EVICT, workers=1, partitions=1,
                              batch_planning=True, capture_explain=True)),
            ("batch-w4", dict(EVICT, workers=4, partitions=4,
                              batch_planning=True)),
            ("isolated", dict(mode="isolated", morsel_size=4096,
                              workers=1, partitions=1)),
        )
        leg_results = {}
        for label, cfg in runs:
            session, futs = _run_all(db, _rebuild(db, qs), **cfg)
            leg_results[label] = [_canon(f.result()) for f in futs]
            for i, (f, ref) in enumerate(zip(futs, refs)):
                _assert_parity(f.result(), ref, ctx=f"seed{seed}/{label}/q{i}")
                checks += 1
            if label.startswith("batch"):
                cohorts += int(session.counters["batch_cohorts"])
                assert session.stats()["queued_pending"] == 0
                assert (
                    session.counters["batch_planned_queries"]
                    >= 2 * session.counters["batch_cohorts"]
                )
            if label == "batch-w1":
                for i, f in enumerate(futs):
                    _explain_accounting(f.explain(), ctx=f"seed{seed}/q{i}")
            session.close()
        # worker-count independence of the batched engine
        for a, b in zip(leg_results["batch-w1"], leg_results["batch-w4"]):
            for k in a:
                np.testing.assert_allclose(
                    a[k], b[k], rtol=1e-12, atol=1e-12, err_msg=f"seed{seed}/w1-vs-w4/{k}"
                )
    assert checks >= 100, f"only {checks} parity instances"
    assert cohorts > 0, "the burst sweep never formed a cohort — gaps too wide"


# ---------------------------------------------------------------------------
# Eviction safety properties
# ---------------------------------------------------------------------------


def _q3(db, date, seg=1.0, arrival=0.0):
    return queries.make_query(
        db, "q3", {"segment": seg, "date": float(days(date))}, arrival
    )


def test_evicted_state_observation_hard_fails():
    """The lens-soundness guard: every observation path of an evicted state
    raises instead of answering from reclaimed fragments."""
    from repro.core.descriptors import StateSignature
    from repro.core.state import SharedHashBuildState

    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    s = SharedHashBuildState(1, sig, ("k",), ("x",))
    s.insert_or_mark(
        np.arange(8),
        np.arange(8),
        {"k": np.arange(8.0), "x": np.arange(8.0)},
        np.ones(8, dtype=np.uint64),
        np.ones(8, dtype=np.uint64),
    )
    s.evicted = True
    for op in (
        lambda: s.probe(np.arange(4)),
        lambda: s.visible_mask(1, np.arange(2)),
        lambda: s.attach(2),
        lambda: s.insert_or_mark(
            np.arange(2), np.arange(2), {"k": np.zeros(2), "x": np.zeros(2)},
            np.ones(2, dtype=np.uint64), np.ones(2, dtype=np.uint64),
        ),
        lambda: s.pin("token"),
    ):
        with pytest.raises(RuntimeError, match="evicted"):
            op()


def test_pinned_state_never_evicted(db):
    """Pins (live lenses or admission pins) keep a state out of the
    evictor's reach; forcing eviction on a pinned state raises."""
    session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=4096, retention="epoch")
    )
    session.submit(_q3(db, "1995-03-15"))
    eng = session.engine
    live = [s for lst in eng.state_index.values() for s in lst]
    assert live and all(not s.evictable for s in live)  # lens refs pin them
    with pytest.raises(RuntimeError, match="pinned"):
        eng._evict(live[0])
    session.run()
    # after completion the refs dropped: states retired, now evictable
    retired = list(eng.lifecycle.retired.values())
    assert retired and all(s.evictable for s in retired)
    # an explicit admission pin blocks retirement-eviction again
    retired[0].pin("admission-tok")
    with pytest.raises(RuntimeError, match="pinned"):
        eng._evict(retired[0])
    retired[0].unpin("admission-tok")
    assert eng.enforce_memory_budget(0) == len(retired)  # force-evict all
    assert all(s.evicted for s in retired)
    assert not any(lst for lst in eng.state_index.values())


@given(seed=st.integers(0, 10_000), partitions=st.integers(1, 6))
@settings(max_examples=6, deadline=None)
def test_explain_sums_to_demand_after_forced_eviction(db, seed, partitions):
    """EXPLAIN GRAFT accounting survives eviction: after force-evicting all
    retained state, per-partition represented + residual + unattached still
    equals demand exactly (everything falls back to ordinary/fresh)."""
    session = graftdb.connect(
        db,
        EngineConfig(
            mode="graft", morsel_size=4096, retention="epoch",
            workers=1, partitions=partitions,
        ),
    )
    rng = np.random.default_rng(seed)
    session.submit_all([queries.sample_query(db, rng, arrival=i * 0.01) for i in range(3)])
    session.run()
    eng = session.engine
    assert eng.lifecycle.retired  # something was retired
    probe = queries.sample_query(db, rng, arrival=session.now)
    before = session.explain_graft(probe)
    evicted = eng.enforce_memory_budget(0)
    assert evicted > 0
    after = session.explain_graft(probe)
    for exp in (before, after):
        for b in [x for root in exp.boundaries for x in root.flat()]:
            assert sum(b.part_demand_rows) == b.demand_rows
            for p in range(len(b.part_demand_rows)):
                assert (
                    b.part_represented_rows[p]
                    + b.part_residual_rows[p]
                    + b.part_unattached_rows[p]
                    == b.part_demand_rows[p]
                ), (b, p)
        assert exp.total_demand_rows == (
            exp.represented_rows + exp.residual_rows + exp.unattached_rows
        )
    # identical plan, identical demand — only the attachment classes moved
    assert after.total_demand_rows == before.total_demand_rows
    # evicted hash states can no longer represent anything
    assert all(
        b.state_id is None
        for root in after.boundaries
        for b in root.flat()
        if b.decision in ("represented", "partial", "residual")
    ) or after.represented_rows + after.residual_rows <= before.represented_rows + before.residual_rows


def test_readmitting_evicted_range_recomputes_correctly(db_mid):
    """Re-admission after eviction: the second identical query rebuilds from
    scratch (no represented observation of reclaimed fragments) and still
    matches the reference executor and the pre-eviction result."""
    session = graftdb.connect(
        db_mid, EngineConfig(mode="graft", morsel_size=4096, retention="epoch")
    )
    qa = _q3(db_mid, "1995-03-15")
    fa = session.submit(qa)
    session.run()
    ra = fa.result()
    eng = session.engine
    rep_before = eng.counters["represented_rows"]
    assert eng.enforce_memory_budget(0) > 0  # evict every retained state
    qb = _q3(db_mid, "1995-03-15", arrival=session.now)
    fb = session.submit(qb)
    session.run()
    rb = fb.result()
    ref = refexec.execute(db_mid, qb.plan)
    _assert_parity(rb, ref, ctx="readmit-vs-ref")
    _assert_parity(rb, ra, ctx="readmit-vs-first-run")
    # no represented-extent observation happened against evicted state
    assert eng.counters["represented_rows"] == rep_before
    assert eng.counters["evictions"] > 0


def test_retained_state_serves_represented_after_release(db_mid):
    """The point of epoch retention: a later narrower arrival grafts fully
    represented extents off a *retired* state (refcount would rebuild)."""
    session = graftdb.connect(
        db_mid,
        EngineConfig(mode="graft", morsel_size=4096, retention="epoch",
                     capture_explain=True),
    )
    fa = session.submit(_q3(db_mid, "1995-03-20"))
    session.run()
    assert session.engine.lifecycle.retired  # qa's states retired, not dropped
    qb = _q3(db_mid, "1995-03-10", arrival=session.now)
    exp = session.explain_graft(qb)
    assert exp.represented_rows > 0
    assert any(
        b.state_retired for root in exp.boundaries for b in root.flat()
    ), "explain did not flag the retired candidate"
    fb = session.submit(qb)
    session.run()
    _assert_parity(fb.result(), refexec.execute(db_mid, qb.plan), ctx="retained-graft")
    assert session.counters["state_revivals"] > 0


@given(budget=st.integers(0, 400_000), seed=st.integers(0, 9_999))
@settings(max_examples=6, deadline=None)
def test_memory_budget_respected_under_any_budget(db, budget, seed):
    """Property: for any budget, the retained high-water never exceeds it
    (the evictor runs at every retire) and results stay correct."""
    rng = np.random.default_rng(seed)
    qs = [queries.sample_query(db, rng, arrival=i * 0.01) for i in range(4)]
    session, futs = _run_all(
        db, qs, **dict(EVICT, memory_budget=budget, workers=1, partitions=1)
    )
    for i, f in enumerate(futs):
        _assert_parity(f.result(), refexec.execute(db, qs[i].plan), ctx=f"budget{budget}/q{i}")
    assert session.stats()["retained_high_water_bytes"] <= budget


def test_queued_arrival_pins_candidates_against_eviction(db_mid):
    """A deferred-but-admissible arrival pins its candidate states: while
    it queues, even zero-budget enforcement cannot reclaim them (pins block
    eviction, not retirement), and admission unpins + grafts represented
    extents off the survivor."""
    from repro.core.scheduler import AdmissionController

    session = graftdb.connect(
        db_mid,
        EngineConfig(mode="graft", morsel_size=4096, retention="epoch"),
    )
    session.submit(_q3(db_mid, "1995-03-20"))
    session.run()
    eng = session.engine
    retired = list(eng.lifecycle.retired.values())
    assert retired  # qa's states retired, retained (no budget)
    runner = session._runner

    class DeferOnce(AdmissionController):
        def __init__(self):
            super().__init__(max_inflight=1)
            self.deferred = 0

        def decide(self, engine, query):
            if self.deferred == 0:
                self.deferred += 1
                return ("defer", "overload")
            return ("admit", "graft")

    runner.admission = DeferOnce()
    qc = _q3(db_mid, "1995-03-10", arrival=session.now)
    fc = session.submit(qc)  # deferred: pins the retired candidates
    pinned = runner._queued_pins.get(qc.qid, [])
    assert pinned, "deferred arrival pinned nothing"
    assert all(not s.evictable for s in pinned)
    # zero-budget enforcement while queued: pinned candidates survive,
    # everything else retired goes
    eng.enforce_memory_budget(0)
    assert all(not s.evicted for s in pinned), "evictor reclaimed pinned state"
    # pins block eviction but NOT retirement: still stamped, still indexed
    assert all(s.retired_epoch is not None for s in pinned)
    done = session.run()
    assert {f.qid for f in done} >= {fc.qid}
    assert not runner._queued_pins, "pins must release at admission"
    assert all(not s.pins for s in pinned)
    _assert_parity(fc.result(), refexec.execute(db_mid, qc.plan), ctx="pinned-graft")
    # the narrower qc grafted off the pinned survivor
    assert eng.counters["represented_rows"] > 0
    assert eng.counters["state_revivals"] > 0
