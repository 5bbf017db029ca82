"""The reference against the engine, and ``correct`` against faults planted
in the timed path, on the CPU at ``TINY_SF``."""

import time

import numpy as np
import pytest

from bench.harness import cell, check
from bench.harness.spec import resolve

SEED = 2**31 + 77


CELLS = ["isolated_c8", "graft_c8"]


def _run(root, name="isolated_c8", **kw):
    return cell.run(name, SEED, 6.0, False, time.perf_counter(), log=lambda *a: None,
                    root=root, **kw)


def test_column_gap_reads_every_kind_of_wrong_value():
    want = np.array([4.0, -2.0])
    assert check.column_gap(want, want) == 0.0
    assert check.column_gap(np.array([4.0, -1.0]), want) == 0.25
    assert check.column_gap(np.array([4.0, np.nan]), want) == float("inf")
    assert check.column_gap(np.array([1.0]), np.array([0.0])) == float("inf")


@pytest.mark.parametrize("mode", ["graft", "isolated"])
def test_reference_agrees_with_the_engine_on_every_template(tiny_root, mode):
    spec = resolve("graft_c8", tiny_root)
    config = dict(spec["config"], engine=dict(spec["config"]["engine"], mode=mode))
    tables = spec["data"].generate(config["scale_factor"], SEED)
    db, session = cell.open_session(config, tables)
    rng = np.random.default_rng(SEED)
    sent = []
    for t in spec["mix"]["templates"] * 2:
        p = spec["params"].sample(t, rng)
        sent.append((t, p, session.submit(spec["plans"].make_query(db, t, p, arrival=session.now))))
    session.run()
    answers = [{"template": t, "params": p, "result": f.result()} for t, p, f in sent]
    read = check.readings(answers, lambda t, p: spec["reference"].answer(tables, t, p), 0)
    assert read["compared"] == 18 and read["wrong_shape"] == 0
    assert read["max_rel_err"] <= config["limits"]["max_rel_err"]


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct(tiny_root, name):
    rec = _run(tiny_root, name)
    assert rec["correct"], rec["checks"]
    assert rec["completed"] > 0 and rec["readings"]["compared"] == rec["completed"]


def _alter_answer(monkeypatch):
    from repro.core import engine

    real = engine._apply_orderby

    def altered(result, ob):
        out = real(result, ob)
        last = list(out)[-1]
        col = np.array(out[last], dtype=np.float64)
        if len(col):
            col[0] += 1.0 + abs(col[0]) * 1e-6
        return {**out, last: col}

    monkeypatch.setattr(engine, "_apply_orderby", altered)


def _drop_half_of_each_batch(monkeypatch):
    from repro.relational.table import Table

    real = Table.morsel

    def half(self, start, size):
        cols = real(self, start, size)
        n = len(next(iter(cols.values())))
        return {k: v[: max(1, n // 2)] for k, v in cols.items()}

    monkeypatch.setattr(Table, "morsel", half)


def _leave_state_unchanged(monkeypatch):
    from repro.core.state import SharedAggregateState

    real = SharedAggregateState.update
    calls = [0]

    def every_other(self, *a, **kw):
        calls[0] += 1
        if calls[0] % 2:
            return real(self, *a, **kw)

    monkeypatch.setattr(SharedAggregateState, "update", every_other)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [_alter_answer, _drop_half_of_each_batch, _leave_state_unchanged])
def test_a_planted_fault_makes_the_run_incorrect(tiny_root, monkeypatch, fault, name):
    fault(monkeypatch)
    rec = _run(tiny_root, name)
    assert not rec["correct"], rec["checks"]


def test_the_control_is_not_correct(tiny_root):
    """The reference computed in float32, the precision below the
    configuration's float64, in the program's place: the check must fail it."""
    ref = resolve("isolated_c8", tiny_root)["reference"]

    def float32(tables, template, params):
        return ref.answer(tables, template, params, dtype=np.float32)

    rec = _run(tiny_root, stand_in=float32)
    assert rec["readings"]["compared"] > 0
    assert rec["program_readings"]["max_rel_err"] <= rec["checks"]["max_rel_err"]["limit"]
    assert not rec["correct"], rec["checks"]
    assert rec["readings"]["max_rel_err"] > rec["checks"]["max_rel_err"]["limit"]
