"""The committed ``graft_c8`` cell (config ``tpch_sf0.3_graft``, Q4 as a
semi-join) through ``cell.run`` and ``check.verdict``, on the CPU at
``TINY_SF``: a sound run is correct, planted faults and the float32 control
are not."""

import json
import time

import numpy as np
import pytest

from benchutil import GRAFT_CELL, GRAFT_CONFIG
from test_check import _alter_answer, _drop_half_of_each_batch, _leave_state_unchanged

from bench.harness import cell
from bench.harness.spec import resolve

SEED = 2**31 + 113


@pytest.fixture
def committed_root(tiny_root):
    """The tests' tiny copy without the ``graft_c8`` entry the helpers add,
    so the cell resolves to the committed configuration."""
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"] = [c for c in bench["configs"] if c != GRAFT_CONFIG]
    bench["workloads"] = [w for w in bench["workloads"] if w != GRAFT_CELL]
    path.write_text(json.dumps(bench))
    assert resolve("graft_c8", tiny_root)["config"]["name"] == "tpch_sf0.3_graft"
    return tiny_root


def _run(root, **kw):
    return cell.run("graft_c8", SEED, 6.0, False, time.perf_counter(), log=lambda *a: None,
                    root=root, **kw)


def test_a_sound_run_of_the_committed_cell_is_correct(committed_root):
    rec = _run(committed_root)
    assert rec["correct"], rec["checks"]
    assert rec["completed"] > 0 and rec["readings"]["compared"] == rec["completed"]
    counters = rec["counters"]
    # Q4 ran as a semi-join in the window, on shapes of the ladder alone
    assert counters["semi_builds"] + counters["semi_attaches"] > 0
    assert counters["graft_demand_rows"] > 0
    assert rec["backend"]["program_keys"] == 0


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half_of_each_batch, _leave_state_unchanged])
def test_a_planted_fault_makes_the_committed_cell_incorrect(committed_root, monkeypatch, fault):
    fault(monkeypatch)
    rec = _run(committed_root)
    assert not rec["correct"], rec["checks"]


def test_the_control_is_not_correct_on_the_committed_cell(committed_root):
    ref = resolve("graft_c8", committed_root)["reference"]

    def float32(tables, template, params):
        return ref.answer(tables, template, params, dtype=np.float32)

    rec = _run(committed_root, stand_in=float32)
    assert rec["readings"]["compared"] > 0
    assert rec["program_readings"]["max_rel_err"] <= rec["checks"]["max_rel_err"]["limit"]
    assert not rec["correct"], rec["checks"]
