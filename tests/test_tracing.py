"""Program spans, transfer counters and measured worker time, on the CPU.

A small session on the ``pallas`` backend runs under
``jax.profiler.trace``; its ``.xplane.pb`` is read back with the
benchmark's span loader (``bench/harness/program_spans.py``).
"""

import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.core.descriptors import StateSignature
from repro.core.runtime import ScanNode
from repro.core.state import SharedHashBuildState
from repro.relational import queries

ROOT = Path(__file__).resolve().parents[1]

#: every span the program records in a session that probes and chains on
#: the device, and the backend calls made directly below
SPANS = (
    "graftdb.admit",
    "graftdb.graft",
    "graftdb.unit",
    "graftdb.schedule",
    "graftdb.scan",
    "graftdb.plan",
    "graftdb.filter",
    "graftdb.join",
    "graftdb.build",
    "graftdb.aggregate",
    "graftdb.complete",
    "graftdb.backend.probe",
    "graftdb.backend.probe_visible",
    "graftdb.backend.probe_visible_multi",
    "graftdb.backend.probe_chain",
    "graftdb.backend.sync_mirrors",
    "graftdb.backend.insert_keys",
    "graftdb.h2d",
    "graftdb.device_wait",
    "graftdb.d2h",
)


def _mini_state(n=256):
    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    s = SharedHashBuildState(1, sig, ("k",), ("x",))
    keys = np.arange(n, dtype=np.int64) * 3
    s.insert_or_mark(
        keys, keys, {"k": keys.astype(float), "x": keys.astype(float)},
        np.full(n, np.uint64(1) << np.uint64(s.slots.get(7))), np.zeros(n, np.uint64),
    )
    return s, keys


@pytest.fixture(scope="module")
def traced(db, tmp_path_factory):
    """One traced session: q1, q3 and q5 twice each, graft mode, plus one
    direct call of each probe on a hand-built state."""
    import jax

    sys.path.insert(0, str(ROOT))
    from bench.harness import program_spans, trace

    out = tmp_path_factory.mktemp("xplane")
    session = graftdb.connect(db, EngineConfig(mode="graft", backend="pallas"))
    rng = np.random.default_rng(3)
    qs = [queries.make_query(db, t, queries._sample_params(t, rng), arrival=0.0)
          for t in ("q1", "q3", "q5", "q1", "q3", "q5")]
    with jax.profiler.trace(str(out)):
        futs = session.submit_all(qs)
        session.run()
        s, keys = _mini_state()
        session.backend.probe_launch(s, keys).collect()
        session.backend.probe_visible_launch(s, keys, 7).collect()
        session.backend.probe_visible_multi_launch(s, keys).collect()
    assert all(f.status == "done" for f in futs)
    return program_spans.load(trace.latest_xplane(str(out))), qs


def test_each_span_occurs(traced):
    t, _ = traced
    names = {n for spans in t["threads"].values() for _, _, n in spans}
    missing = [n for n in SPANS if n not in names]
    assert not missing, missing


@pytest.fixture(scope="module")
def pipelined(db, tmp_path_factory):
    """One traced session with two units in flight: four isolated queries
    on the ``pallas`` backend under the wall clock."""
    import jax

    sys.path.insert(0, str(ROOT))
    from bench.harness import program_spans, trace

    out = tmp_path_factory.mktemp("xplane")
    session = graftdb.connect(db, EngineConfig(mode="isolated", backend="pallas", clock="wall",
                                               morsel_size=8192))
    rng = np.random.default_rng(5)
    qs = [queries.make_query(db, t, queries._sample_params(t, rng), arrival=session.now)
          for t in ("q3", "q5", "q10", "q9")]
    with jax.profiler.trace(str(out)):
        futs = session.submit_all(qs)
        session.run()
    assert all(f.status == "done" for f in futs)
    assert session.counters["overlapped_launches"] > 0
    return program_spans.load(trace.latest_xplane(str(out)))


def _nested_spans(t):
    """Checks that every span closes inside the span open around its start
    on its thread; returns how many program spans have a parent."""
    nested = 0
    for spans in t["threads"].values():
        stack = []
        for s, e, name in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
            while stack and stack[-1][1] <= s:
                stack.pop()
            if stack:
                assert e <= stack[-1][1], (name, "inside", stack[-1][2])
                nested += name.startswith("graftdb.")
            stack.append((s, e, name))
    return nested


def test_children_close_inside_their_parents(traced):
    t, _ = traced
    assert _nested_spans(t) > 100


def test_spans_nest_with_units_in_flight(pipelined):
    """No span straddles a unit's yield: every span nests on its thread,
    and the self times on each thread add up to no more than its extent."""
    from bench.harness import program_spans

    assert _nested_spans(pipelined) > 100
    units = 0
    for key, spans in pipelined["threads"].items():
        own = [sp for sp in spans if sp[2].startswith("graftdb.")]
        if not own:
            continue
        lo, hi = min(s for s, _, _ in own), max(e for _, e, _ in own)
        self_s = program_spans.self_times({key: own}, lo, hi)
        assert sum(self_s.values()) <= (hi - lo) / 1e9 + 1e-9
        units += sum(n == "graftdb.unit" for _, _, n in own)
    metas = [m for (_, _, n), m in pipelined["meta"].items() if n == "graftdb.unit"]
    # a unit resumed after its launch records one span per piece, each
    # with the unit's own scan, partition and morsel
    assert units == len(metas) and all({"scan", "part", "morsel"} <= set(m) for m in metas)
    pieces = {}
    for m in metas:
        k = (m["scan"], m["part"], m["morsel"])
        pieces[k] = pieces.get(k, 0) + 1
    assert max(pieces.values()) > 1


def test_admit_spans_carry_their_query_ids(traced):
    t, qs = traced
    admits = [meta for (_, _, name), meta in t["meta"].items() if name == "graftdb.admit"]
    assert sorted(m["qid"] for m in admits) == sorted(q.qid for q in qs)
    units = [meta for (_, _, name), meta in t["meta"].items() if name == "graftdb.unit"]
    assert units and all({"scan", "part", "morsel"} <= set(m) for m in units)


def test_transfer_counters_count_one_probe_by_hand():
    """A second ``probe_visible_launch`` of 100 keys against an unchanged state
    ships 128 padded uint32 keys (the all-ones ownership words of 128 rows
    stay on the device from the first) and reads back 128 int32 entries;
    nothing of the state moves again."""
    from repro.api.backends import PallasBackend

    s, keys = _mini_state()
    backend = PallasBackend()
    backend.probe_visible_launch(s, keys[:100], 7).collect()  # mirrors upload
    before = backend.stats()
    pair = backend.probe_visible_launch(s, keys[:100], 7).collect()
    after = backend.stats()
    assert len(pair[0]) == 100
    delta = {k: after[k] - before[k] for k in after}
    assert delta["h2d_bytes"] == 128 * 4
    assert delta["d2h_bytes"] == 128 * 4
    assert (delta["device_rows"], delta["device_padded_rows"]) == (100, 128)


def test_span_is_a_no_op_without_jax():
    code = (
        "import sys\n"
        "from repro.core.tracing import span\n"
        "before = set(sys.modules)\n"
        "with span('graftdb.unit', scan=1, part=0, morsel=2):\n"
        "    pass\n"
        "assert span('a') is span('b')\n"
        "assert 'jax' not in sys.modules and set(sys.modules) == before\n"
        "import graftdb\n"
        "from repro.relational import tpch\n"
        "db = tpch.get_database(0.002, seed=1)\n"
        "graftdb.connect(db, backend='reference').close()\n"
        "assert 'jax' not in sys.modules\n"
    )
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 0, p.stderr


def test_span_records_only_under_a_profiler(tmp_path):
    import jax

    from repro.core.tracing import span

    assert span("graftdb.unit", scan=1, part=0, morsel=2) is span("graftdb.h2d")
    with jax.profiler.trace(str(tmp_path)):
        inside = span("graftdb.h2d")
        assert isinstance(inside, jax.profiler.TraceAnnotation)
    assert span("graftdb.h2d") is not inside


def test_wall_clock_busy_time_is_measured(db, monkeypatch):
    """Under the wall clock a worker's busy seconds are the host seconds of
    its units: each morsel advance here sleeps 5 ms, which the cost model
    never sees."""
    advance = ScanNode.advance
    calls = []

    def slow(self, engine, part=0):
        calls.append(part)
        time.sleep(0.005)
        return advance(self, engine, part)

    monkeypatch.setattr(ScanNode, "advance", slow)
    session = graftdb.connect(db, EngineConfig(mode="isolated", clock="wall"))
    rng = np.random.default_rng(4)
    session.submit(queries.make_query(db, "q6", queries._sample_params("q6", rng),
                                      arrival=session.now))
    session.run()
    w = session.worker_stats()
    assert w["busy_s"][0] >= 0.005 * len(calls) > 0
    assert 0.0 < w["utilization"][0] <= 1.0


def test_work_clock_busy_time_stays_modelled(db):
    session = graftdb.connect(db, EngineConfig(mode="isolated", workers=1, partitions=1))
    rng = np.random.default_rng(4)
    session.submit(queries.make_query(db, "q6", queries._sample_params("q6", rng)))
    session.run()
    w = session.worker_stats()
    # one worker, nothing idle: modelled busy time is the whole makespan
    assert w["busy_s"][0] == pytest.approx(w["makespan_s"])


def test_precompile_and_semi_join_spans_nest(db, tmp_path):
    """A session opened under the profiler records its ladder compile as
    ``graftdb.precompile``, and a semi-join stage's work as ``graftdb.join``
    with ``kind="semi"``; every span nests on its thread."""
    import jax

    sys.path.insert(0, str(ROOT))
    from bench.harness import program_spans, trace

    rng = np.random.default_rng(9)
    with jax.profiler.trace(str(tmp_path)):
        session = graftdb.connect(db, EngineConfig(mode="graft", backend="pallas"))
        qs = []
        for t in ("q4", "q3", "q4"):
            p = queries._sample_params(t, rng)
            plan = queries.q4_semi_plan(db, p) if t == "q4" else queries.BUILDERS[t](db, p)
            qs.append(queries.Query(qid=len(qs) + 1, template=t, plan=plan, params=p))
        futs = session.submit_all(qs)
        session.run()
    assert all(f.status == "done" for f in futs)
    t = program_spans.load(trace.latest_xplane(str(tmp_path)))
    names = {n for spans in t["threads"].values() for _, _, n in spans}
    assert "graftdb.precompile" in names
    kinds = [m.get("kind") for (_, _, name), m in t["meta"].items() if name == "graftdb.join"]
    assert "semi" in kinds and "inner" in kinds
    assert _nested_spans(t) > 10
