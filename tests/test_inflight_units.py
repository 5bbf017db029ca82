"""Two units in flight (DESIGN.md §11), on the CPU.

Under the wall clock, with the ``pallas`` backend, ``Runner.run`` starts a
second independent unit while the first waits on its device launch. These
tests hold it to the answers of the same sessions under the work clock,
where every unit runs to its end in turn, and check its independence rule
and its drain before a deadline.
"""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.core.engine import GraftEngine
from repro.core.runtime import Pipeline
from repro.core.scheduler import Runner
from repro.relational import queries

TEMPLATES = ("q3", "q5", "q10", "q1", "q7", "q9", "q4", "q8")


def _session(db, mode, clock):
    return graftdb.connect(
        db, EngineConfig(mode=mode, backend="pallas", clock=clock, morsel_size=8192)
    )


def _queries(db, session, templates, seed):
    rng = np.random.default_rng(seed)
    return [
        queries.make_query(db, t, queries._sample_params(t, rng), arrival=session.now)
        for t in templates
    ]


def _answers(db, mode, clock, templates, seed, spy=None):
    session = _session(db, mode, clock)
    futs = session.submit_all(_queries(db, session, templates, seed))
    if spy is not None:
        spy(session)
    session.run()
    assert all(f.status == "done" for f in futs)
    return [f.result() for f in futs], session


def _same(a, b):
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        assert set(ra) == set(rb)
        for k in ra:
            assert np.array_equal(np.asarray(ra[k]), np.asarray(rb[k])), k


def _touched(node, part):
    """States a unit reads and writes, from its live pipelines."""
    reads, writes = set(), set()
    for p in node.pipelines:
        ms = [m for m in p.members if m.active and not m.done and m.pending_in(part)]
        if not ms:
            continue
        reads |= {id(op.state) for op in p.ops}
        if p.build_target is not None:
            writes.add(id(p.build_target.state))
        writes |= {id(m.sink.agg_state) for m in ms if m.sink is not None}
    return reads, writes


def _watch_pairs(monkeypatch):
    """Records every set of units in progress together at a resume: those
    suspended at a launch and the one resumed. At most two are ever
    suspended; a third in progress is one that never launches."""
    seen = []
    resume = Runner._resume

    def spy(self, unit, on_complete):
        assert len(self._inflight) <= 2
        group = self._inflight + [unit]
        if len(group) > 1:
            seen.append([(u.node, u.part, _touched(u.node, u.part)) for u in group])
        held = len(self._inflight)
        resume(self, unit, on_complete)
        if held == 2:
            assert unit not in self._inflight  # ran to its end: no launch

    monkeypatch.setattr(Runner, "_resume", spy)
    return seen


def test_isolated_queries_overlap_and_answer_as_in_turn(db, monkeypatch):
    want, _ = _answers(db, "isolated", "work", TEMPLATES, seed=3)
    seen = _watch_pairs(monkeypatch)
    got, session = _answers(db, "isolated", "wall", TEMPLATES, seed=3)
    _same(got, want)
    assert session.counters["overlapped_launches"] > 0
    launches = session.backend.stats()["device_launches"]
    assert 0 < session.counters["overlapped_launches"] <= launches
    assert seen and all(len(group) <= 3 for group in seen)
    for group in seen:
        shards = [(node, part) for node, part, _ in group]
        assert len(set(shards)) == len(shards)


def test_units_sharing_a_build_state_are_never_in_flight_together(db, monkeypatch):
    """Two graft-mode q5s share their dimension builds: no two units in
    flight together ever write a state that the other reads or writes."""
    templates = ("q5", "q5")
    want, _ = _answers(db, "graft", "work", templates, seed=0)
    shared = []

    def note_shared(session):
        h1, h2 = session.engine.active_handles
        shared.extend(set(map(id, h1.attached_states)) & set(map(id, h2.attached_states)))

    seen = _watch_pairs(monkeypatch)
    got, session = _answers(db, "graft", "wall", templates, seed=0, spy=note_shared)
    _same(got, want)
    assert shared  # the two queries do share build states
    for group in seen:
        for i, (_, _, (ra, wa)) in enumerate(group):
            for _, _, (rb, wb) in group[i + 1:]:
                assert not (wa & (rb | wb)) and not (wb & ra)


def test_deadline_falling_while_a_unit_is_suspended_drains_it_first(db, monkeypatch):
    """The deadline of the query whose unit waits on its launch falls at
    once: that unit runs to its end before the cancellation, and nothing
    serves the cancelled query afterwards."""
    session = _session(db, "isolated", "wall")
    qs = _queries(db, session, ("q5", "q3", "q10", "q9"), seed=5)
    futs = session.submit_all(qs)
    runner = session._runner
    log, fell = [], []
    resume = Runner._resume

    def set_deadline(self, unit, on_complete):
        resume(self, unit, on_complete)
        if self._inflight and not fell:
            node = self._inflight[-1].node
            fell.append(next(m.qid for p in node.pipelines for m in p.members))
            self.deadlines[fell[0]] = 0.0  # due now

    cancel = GraftEngine.cancel_query

    def note_cancel(self, handle, reason="cancelled", doomed=None):
        log.append(("cancel", handle.qid, len(runner._inflight)))
        return cancel(self, handle, reason, doomed)

    steps = Pipeline.steps

    def note_served(self, engine, cols, row_ids, part=0, overlap=False):
        log.append(("served", {m.qid for m in self.active_members_for(part)}))
        return (yield from steps(self, engine, cols, row_ids, part, overlap))

    monkeypatch.setattr(Runner, "_resume", set_deadline)
    monkeypatch.setattr(GraftEngine, "cancel_query", note_cancel)
    monkeypatch.setattr(Pipeline, "steps", note_served)
    session.run()

    victim = fell[0]
    at = next(i for i, e in enumerate(log) if e[0] == "cancel")
    assert log[at] == ("cancel", victim, 0)  # nothing in flight at the cancel
    assert all(victim not in e[1] for e in log[at:] if e[0] == "served")
    by_qid = {q.qid: f for q, f in zip(qs, futs)}
    assert by_qid[victim].status == "deadline"
    rest = [q for q in qs if q.qid != victim]
    assert all(by_qid[q.qid].status == "done" for q in rest)
    from repro.relational.refexec import execute

    for q in rest:
        want = execute(db, q.plan)
        got = by_qid[q.qid].result()
        for k in want:
            assert np.allclose(
                np.sort(np.asarray(got[k], dtype=float)),
                np.sort(np.asarray(want[k], dtype=float)),
                rtol=1e-9,
            ), (q.template, k)


@pytest.mark.parametrize("config", [
    dict(mode="isolated", backend="reference", clock="wall"),
    dict(mode="isolated", backend="pallas", clock="work"),
])
def test_no_overlap_without_a_launching_backend_on_the_wall_clock(db, config, monkeypatch):
    """The reference backend and the work clock run every unit to its end
    in turn: the runner never holds one in flight."""
    seen = _watch_pairs(monkeypatch)
    session = graftdb.connect(db, EngineConfig(morsel_size=8192, **config))
    futs = session.submit_all(_queries(db, session, ("q3", "q5"), seed=1))
    session.run()
    assert all(f.status == "done" for f in futs)
    assert not seen and session.counters["overlapped_launches"] == 0
