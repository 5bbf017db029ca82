"""The device programs' shape ladder (DESIGN.md §13): fixed by the database
and the morsel size when a session opens, compiled then, and never left by
a launch however many queries run at once."""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.api.backends import ShapeLadder
from repro.core.plans import Query
from repro.relational import queries, refexec

TEMPLATES = ("q1", "q3", "q4", "q5", "q6", "q7", "q8", "q9", "q10")


def test_ladder_comes_from_the_morsel_size_and_the_tables():
    lad = ShapeLadder.for_database(65536, [5, 25, 3000, 45000, 60000, 450000, 1800000])
    assert lad.rows == (4096, 16384, 65536)
    assert lad.entries == (4096, 65536, 524288, 2097152)
    assert lad.rows_for(1) == 4096 and lad.rows_for(4097) == 16384
    assert lad.entries_for(450000) == 524288
    # beyond the ladder: the next power of two, a program outside it
    assert lad.rows_for(70000) == 131072
    assert ShapeLadder().rows_for(100) == 128


def test_session_open_compiles_the_ladder(db):
    session = graftdb.connect(db, EngineConfig(mode="graft", backend="pallas", morsel_size=4096))
    lad = session.backend.ladder
    stats = session.backend.stats()
    # per row length: one stage program per entry capacity and a sink; per
    # capacity: a fill and a patch per row length, for mirrors and tables
    want = len(lad.rows) * (len(lad.entries) + 1) + 2 * len(lad.entries) * (1 + len(lad.rows))
    assert stats["precompiled_programs"] == stats["program_keys"] == want


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
@pytest.mark.parametrize("n", [1, 4, 8])
def test_launches_stay_within_the_ladder(db, seed, n):
    session = graftdb.connect(db, EngineConfig(mode="graft", backend="pallas", morsel_size=4096))
    rng = np.random.default_rng(seed)
    qs = []
    for i in range(n):
        t = TEMPLATES[int(rng.integers(len(TEMPLATES)))] if i else "q4"
        p = queries._sample_params(t, rng)
        plan = queries.q4_semi_plan(db, p) if t == "q4" else queries.BUILDERS[t](db, p)
        qs.append(Query(qid=i + 1, template=t, plan=plan, params=p, arrival=0.001 * i))
    futs = session.submit_all(qs)
    session.run()
    for q, f in zip(qs, futs):
        want = refexec.execute(db, q.plan)
        for k in want:
            np.testing.assert_allclose(np.sort(f.result()[k]), np.sort(want[k]), rtol=1e-9)
    stats = session.backend.stats()
    assert stats["device_launches"] > 0
    assert stats["program_keys"] == stats["precompiled_programs"]
