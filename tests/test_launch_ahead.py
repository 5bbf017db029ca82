"""Launch-ahead within a unit (DESIGN.md §11), on the CPU.

Under the wall clock, with the ``pallas`` backend, ``ScanNode.steps``
issues every pipeline's first device launch of a morsel before it collects
the first, and collects them in pipeline order. These tests hold it to the
answers of the work clock and of ``refexec``, to the states that
``advance`` leaves, to its read-after-write stop, and to the runner's drain
before a deadline.
"""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.api.backends import PallasBackend
from repro.core.engine import GraftEngine
from repro.core.plans import AggSpec, Aggregate, HashJoin, OrderBy, Query, Scan
from repro.core.predicates import Cmp
from repro.core.runtime import Pipeline, run_steps
from repro.core.scheduler import Runner, extract_ready_units
from repro.relational import queries, refexec

#: templates that share lineitem and orders in graft mode; Q4 as a semi-join
SHARED = ("q3", "q5", "q10", "q4", "q6", "q3", "q10", "q5")


def _session(db, mode, clock, morsel_size=8192):
    return graftdb.connect(
        db, EngineConfig(mode=mode, backend="pallas", clock=clock, morsel_size=morsel_size)
    )


def _queries(db, templates, seed):
    rng = np.random.default_rng(seed)
    out = []
    for t in templates:
        q = queries.make_query(db, t, queries._sample_params(t, rng))
        if t == "q4":
            q = Query(qid=q.qid, template=t, plan=queries.q4_semi_plan(db, q.params),
                      params=q.params)
        out.append(q)
    return out


def _agree(db, qs, got):
    """Each answer equals the reference executor's (the repo's oracle)."""
    for q, res in zip(qs, got):
        want = refexec.execute(db, q.plan)
        assert set(res) == set(want), q.template
        for k in want:
            assert np.allclose(
                np.sort(np.asarray(res[k], dtype=float)),
                np.sort(np.asarray(want[k], dtype=float)),
                rtol=1e-9,
            ), (q.template, k)


def _run(db, mode, clock, qs):
    session = _session(db, mode, clock)
    futs = session.submit_all(qs)
    session.run()
    assert all(f.status == "done" for f in futs)
    return [f.result() for f in futs], session


@pytest.mark.parametrize("mode", ["graft", "isolated"])
def test_launch_ahead_answers_as_in_turn(db, mode, monkeypatch):
    """Graft mode's shared scans launch ahead and answer as the work clock
    and the reference do; isolated mode, one pipeline a scan, never does.
    A launch issued ahead never counts as overlapping another unit's."""
    want, _ = _run(db, mode, "work", _queries(db, SHARED, seed=4))
    issued = []  # (launch, whether another unit was in flight at its issue)
    launched = PallasBackend._launched

    def note_launch(self, name, outs, post):
        launch = launched(self, name, outs, post)
        issued.append((launch, bool(runner._inflight)))
        return launch

    monkeypatch.setattr(PallasBackend, "_launched", note_launch)
    qs = _queries(db, SHARED, seed=4)
    session = _session(db, mode, "wall")
    runner = session._runner
    futs = session.submit_all(qs)
    session.run()
    assert all(f.status == "done" for f in futs)
    _agree(db, qs, [f.result() for f in futs])
    _agree(db, qs, want)
    ahead = session.counters["ahead_launches"]
    overlapped = session.counters["overlapped_launches"]
    assert len(issued) == session.backend.stats()["device_launches"]
    assert ahead == sum(launch.ahead for launch, _ in issued)
    assert overlapped == sum(other and not launch.ahead for launch, other in issued)
    if mode == "graft":
        assert ahead > 0
    else:
        assert ahead == 0 and overlapped > 0


def _snapshot(engine):
    """Every live hash state's SoA and every open query's aggregate."""
    out = []
    states = sorted((s for ss in engine.state_index.values() for s in ss),
                    key=lambda s: s.state_id)
    for s in states:
        arrays = [s.keycode.data, s.did.data, s.vis.data, s.emask.data]
        arrays += [s.cols[a].data for a in sorted(s.cols)]
        out.append((s.state_id, arrays))
    for h in engine.active_handles:
        out.append((h.qid, h.agg_state.rows_consumed, h.agg_state.result()))
    return out


def _same_tree(a, b):
    if isinstance(a, np.ndarray):
        assert a.dtype == b.dtype and np.array_equal(a, b, equal_nan=True)
    elif isinstance(a, dict):
        assert a.keys() == b.keys()
        for k in a:
            _same_tree(a[k], b[k])
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            _same_tree(x, y)
    else:
        assert a == b


def test_launch_ahead_leaves_states_as_advance_does(db):
    """Two engines admit the same queries; one drives each unit with
    ``advance``, the other with ``steps(overlap=True)``. After every unit
    their build states and aggregates are bit-identical."""
    qs = _queries(db, SHARED, seed=9)
    sessions = [_session(db, "graft", "work") for _ in range(2)]
    for s in sessions:
        for q in qs:
            s._runner.submit_now(q)
        s._runner._after_events(None)
    (serial, ahead) = sessions
    while serial.engine.has_active_work():
        here = extract_ready_units(serial.engine)
        there = extract_ready_units(ahead.engine)
        assert [(n.sid, p) for n, p in here] == [(n.sid, p) for n, p in there]
        (node, part), (node2, _) = here[0], there[0]
        node.advance(serial.engine, part)
        run_steps(node2.steps(ahead.engine, part, overlap=True))
        for s in sessions:
            s._runner._after_events(None)
        _same_tree(_snapshot(serial.engine), _snapshot(ahead.engine))
    assert not ahead.engine.has_active_work()
    assert ahead.counters["ahead_launches"] > 0
    assert serial.counters["ahead_launches"] == 0
    for q in qs:
        _same_tree(serial.engine.handles[q.qid].result, ahead.engine.handles[q.qid].result)


def _self_join(qid, order_date, ship_date):
    """Lines of orders placed before ``order_date`` and shipped before
    ``ship_date`` as a key state, probed by a second scan of lineitem."""
    orders = Scan("orders", Cmp("o_orderdate", "<", order_date), ("o_orderkey",))
    shipped = Scan("lineitem", Cmp("l_shipdate", "<", ship_date), ("l_orderkey",))
    build = HashJoin(orders, shipped, ("o_orderkey",), ("l_orderkey",), ())
    lines = Scan("lineitem", Cmp("l_quantity", "<", 30.0), ("l_orderkey", "l_returnflag"))
    j = HashJoin(build, lines, ("l_orderkey",), ("l_orderkey",), (), kind="semi")
    agg = Aggregate(j, ("l_returnflag",), (AggSpec("count", name="n"),))
    return Query(qid=qid, template="self", plan=OrderBy(agg, ("l_returnflag",), (True,)))


def test_a_pipeline_reading_a_state_an_earlier_one_writes_is_not_launched_ahead(
    db, monkeypatch
):
    """A self-join on lineitem: the first query's probe pipeline reads the
    key state while a second query's residual rows are still being built
    into it by an earlier pipeline on the same scan. The reader starts only
    after the writer is collected, and both answers are the reference's."""
    log = []
    steps = Pipeline.steps

    def spy(self, engine, cols, row_ids, part=0, overlap=False):
        reads, writes = self.states(part)
        log.append(("start", self, reads, writes))
        gen = steps(self, engine, cols, row_ids, part, overlap)
        result = None
        while True:
            try:
                launch = gen.send(result)
            except StopIteration as stop:
                log.append(("end", self))
                return stop.value
            log.append(("launch", self))
            result = yield launch

    monkeypatch.setattr(Pipeline, "steps", spy)
    session = _session(db, "graft", "wall", morsel_size=4096)
    engine, runner = session.engine, session._runner
    qs = [_self_join(901, 1000.0, 1000.0), _self_join(902, 1000.0, 1100.0)]
    handles = [runner.submit_now(qs[0])]
    runner._after_events(None)
    while engine.has_active_work():
        node, part = extract_ready_units(engine)[0]
        if len(handles) == 1 and node.table.name == "lineitem" and node.cursors[part] == 4:
            handles.append(runner.submit_now(qs[1]))
            runner._after_events(None)
            continue
        log.append(("unit",))
        run_steps(node.steps(engine, part, overlap=True))
        runner._after_events(None)
    _agree(db, qs, [h.result for h in handles])

    stops = 0
    units = [[]]
    for e in log:
        if e[0] == "unit":
            units.append([])
        else:
            units[-1].append(e)
    for unit in units:
        launched = {e[1] for e in unit if e[0] == "launch"}
        starts = [(i, e) for i, e in enumerate(unit) if e[0] == "start" and e[1] in launched]
        for k, (_, (_, writer, _, w)) in enumerate(starts):
            for j, (_, reader, r, _) in starts[k + 1:]:
                if r & w:
                    stops += 1
                    ended = next(i for i, e in enumerate(unit) if e == ("end", writer))
                    assert ended < j  # collected before the reader started
    assert stops > 0


def test_deadline_falling_while_launches_are_held_drains_them_first(db, monkeypatch):
    """A deadline falls while a unit holds launches issued ahead: every
    launch is collected and no unit is in flight at the cancellation; the
    other queries still answer as the reference does."""
    session = _session(db, "graft", "wall")
    qs = _queries(db, SHARED, seed=6)
    futs = session.submit_all(qs)
    runner = session._runner
    issued, at_cancel, fell = [], [], []

    launched = PallasBackend._launched

    def note_launch(self, name, outs, post):
        launch = launched(self, name, outs, post)
        issued.append(launch)
        return launch

    resume = Runner._resume

    def set_deadline(self, unit, on_complete):
        resume(self, unit, on_complete)
        held = [launch for launch in issued if launch.pending]
        if self._inflight and not fell and sum(launch.ahead for launch in held):
            node = self._inflight[-1].node
            fell.append(next(m.qid for p in node.pipelines for m in p.active_members()))
            self.deadlines[fell[0]] = 0.0  # due now

    cancel = GraftEngine.cancel_query

    def note_cancel(self, handle, reason="cancelled", doomed=None):
        at_cancel.append((handle.qid, len(runner._inflight),
                          sum(launch.pending for launch in issued)))
        return cancel(self, handle, reason, doomed)

    monkeypatch.setattr(PallasBackend, "_launched", note_launch)
    monkeypatch.setattr(Runner, "_resume", set_deadline)
    monkeypatch.setattr(GraftEngine, "cancel_query", note_cancel)
    session.run()

    assert fell and at_cancel[0] == (fell[0], 0, 0)
    by_qid = {q.qid: (q, f) for q, f in zip(qs, futs)}
    assert by_qid[fell[0]][1].status == "deadline"
    rest = [(q, f) for q, f in by_qid.values() if q.qid != fell[0]]
    assert all(f.status == "done" for _, f in rest)
    _agree(db, [q for q, _ in rest], [f.result() for _, f in rest])
