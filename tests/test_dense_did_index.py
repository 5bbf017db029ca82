"""Direct-addressed derivation-id index (DESIGN.md §8): ``DenseIndex``
against ``HashIndex`` and ``dict.setdefault``, ``insert_or_mark`` through
either index bit-identical at 1 and 4 partitions, the rule that picks the
index from a state's bounds, and a dense state through the engine and the
reuse plane."""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.core.descriptors import StateSignature
from repro.core.hashindex import DenseIndex, HashIndex
from repro.core.plans import Query
from repro.core.state import DENSE_DID_FACTOR, DENSE_DID_MAX, SharedHashBuildState
from repro.relational import queries, refexec

DOMAIN = 500


def _batches(kind, rng):
    """Batches of keys in [0, DOMAIN) shaped like the engine's did streams."""
    if kind == "scan":  # ascending row ids, re-delivered on a second cycle
        cuts = [0, 64, 64, 200, 350, DOMAIN]
        first = [np.arange(a, b) for a, b in zip(cuts, cuts[1:])]
        return first + [np.sort(rng.choice(DOMAIN, 90, replace=False))]
    if kind == "sorted-dups":  # a semi morsel: sorted key runs
        return [np.sort(rng.integers(0, DOMAIN, n)) for n in (40, 0, 120, 300)]
    if kind == "unsorted-dups":
        return [rng.integers(0, DOMAIN, n) for n in (1, 50, 0, 200, 200)]
    if kind == "whole-domain":  # grows until every key of the domain has an id
        perm = rng.permutation(DOMAIN)
        return [perm[:100], np.repeat(perm[100:300], 2), perm[250:], perm]
    raise ValueError(kind)


BATCH_KINDS = ["scan", "sorted-dups", "unsorted-dups", "whole-domain"]


@pytest.mark.parametrize("kind", BATCH_KINDS)
def test_dense_index_matches_hash_index_and_dict(kind):
    rng = np.random.default_rng(BATCH_KINDS.index(kind))
    dense, hashed, oracle = DenseIndex(DOMAIN), HashIndex(capacity=8), {}
    for batch in _batches(kind, rng):
        keys = np.asarray(batch, dtype=np.int64)
        ids, is_new = dense.lookup_or_insert(keys)
        hids, hnew = hashed.lookup_or_insert(keys)
        want_new = []
        for k in keys.tolist():
            want_new.append(k not in oracle)
            oracle.setdefault(k, len(oracle))
        assert ids.dtype == np.int64 and is_new.dtype == bool
        np.testing.assert_array_equal(ids, [oracle[k] for k in keys.tolist()])
        np.testing.assert_array_equal(is_new, np.array(want_new, dtype=bool))
        np.testing.assert_array_equal(ids, hids)
        np.testing.assert_array_equal(is_new, hnew)
        assert dense.n == len(dense) == hashed.n == len(oracle)
    if kind == "whole-domain":
        assert dense.n == DOMAIN


@pytest.mark.parametrize("key", [-1, DOMAIN, DOMAIN + 7, np.iinfo(np.int64).min])
def test_dense_index_rejects_key_outside_domain(key):
    idx = DenseIndex(DOMAIN)
    idx.lookup_or_insert(np.arange(3))
    with pytest.raises(ValueError):
        idx.lookup_or_insert(np.array([1, key, 2], dtype=np.int64))
    assert idx.n == 3  # the refused batch handed out nothing


# ---------------------------------------------------------------------------
# insert_or_mark through either index
# ---------------------------------------------------------------------------

SIG = StateSignature("hash_build", ("dense-test", ("k",), ("x",)))
N_ROWS = 3000
KEY_DOMAIN = 700


def _state(semi, dense, n_partitions):
    if semi:
        return SharedHashBuildState(
            1, SIG, ("k",), (), did_domain=N_ROWS, n_partitions=n_partitions, semi=True,
            max_entries=KEY_DOMAIN, key_domain=KEY_DOMAIN if dense else None,
        )
    return SharedHashBuildState(
        1, SIG, ("k",), ("x",), did_domain=N_ROWS, n_partitions=n_partitions,
        max_entries=N_ROWS if dense else None,
    )


def _deliveries(semi, rng):
    """(dids, keycodes, cols, vis, emask) batches: a row's keycode and
    columns are functions of its did; rows come in ascending morsels, then
    re-delivered shuffled with in-batch duplicates (marks)."""
    row_key = rng.integers(0, KEY_DOMAIN, N_ROWS)
    if semi:
        row_key = np.sort(row_key)  # lineitem's layout: key runs
    row_x = rng.normal(size=N_ROWS)
    order = [np.arange(a, min(a + 512, N_ROWS)) for a in range(0, N_ROWS, 512)]
    order += [rng.integers(0, N_ROWS, 800), rng.permutation(N_ROWS)[:1500]]
    for rows in order:
        rows = rows.astype(np.int64)
        n = len(rows)
        vis = np.uint64(1) << rng.integers(0, 64, n).astype(np.uint64)
        emask = np.uint64(1) << rng.integers(0, 4, n).astype(np.uint64)
        cols = {"k": row_key[rows].astype(np.float64), "x": row_x[rows]}
        yield rows, row_key[rows], cols, vis, emask


@pytest.mark.parametrize("n_partitions", [1, 4])
@pytest.mark.parametrize("semi", [False, True], ids=["scan", "semi"])
def test_insert_or_mark_dense_bit_identical_to_hashed(semi, n_partitions):
    dense = _state(semi, True, n_partitions)
    hashed = _state(semi, False, n_partitions)
    assert dense.dense_dids and not hashed.dense_dids
    for batch in _deliveries(semi, np.random.default_rng(5)):
        assert dense.insert_or_mark(*batch) == hashed.insert_or_mark(*batch)
    assert dense.rows_marked > 0 and dense.n_entries < (KEY_DOMAIN if semi else N_ROWS) + 1
    for name in ("did", "keycode", "vis", "emask", "mark_log"):
        a, b = getattr(dense, name).data, getattr(hashed, name).data
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b, err_msg=name)
    for a in dense.retained_attrs:
        np.testing.assert_array_equal(dense.cols[a].data, hashed.cols[a].data)
    for attr in ("rows_inserted", "rows_marked", "mark_log_epoch"):
        assert getattr(dense, attr) == getattr(hashed, attr)


def test_insert_or_mark_counts_rows_per_index():
    counters = {"dense_did_rows": 0, "hashed_did_rows": 0}
    batch = next(_deliveries(False, np.random.default_rng(1)))
    for dense in (True, False):
        st = _state(False, dense, 1)
        st._counters = counters
        st.insert_or_mark(*batch)
    assert counters == {"dense_did_rows": len(batch[0]), "hashed_did_rows": len(batch[0])}


@pytest.mark.parametrize(
    "semi, did_domain, max_entries, key_domain, dense",
    [
        (False, 1000, 1000, None, True),  # plain scan: did_domain == rows
        (False, DENSE_DID_FACTOR * 1000, 1000, None, True),  # at the factor
        (False, DENSE_DID_FACTOR * 1000 + 1, 1000, None, False),  # composed, too wide
        (False, DENSE_DID_MAX + 1, DENSE_DID_MAX, None, False),  # past the cap
        (False, 1000, None, None, False),  # no entry bound
        (True, 1000, 100, DENSE_DID_FACTOR * 100, True),  # semi at the factor
        (True, 1000, 100, DENSE_DID_FACTOR * 100 + 1, False),
        (True, 1000, 100, None, False),  # semi with no key bound
    ],
)
@pytest.mark.parametrize("n_partitions", [1, 4])
def test_index_choice_follows_the_state_bounds(
    semi, did_domain, max_entries, key_domain, dense, n_partitions
):
    st = SharedHashBuildState(
        1, SIG, ("k",), (), did_domain=did_domain, n_partitions=n_partitions, semi=semi,
        max_entries=max_entries, key_domain=key_domain,
    )
    assert st.dense_dids is dense
    if dense:
        assert isinstance(st._did_index, DenseIndex)
        assert len(st._did_index._ids) == (key_domain if semi else did_domain)
    elif n_partitions == 1:
        assert isinstance(st._did_index, HashIndex)
    else:
        assert st._did_index is None and len(st._did_shards) == n_partitions


# ---------------------------------------------------------------------------
# Through the engine and the reuse plane
# ---------------------------------------------------------------------------


def _same(got, want):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(
            np.asarray(got[k], dtype=np.float64), np.asarray(want[k], dtype=np.float64),
            rtol=1e-9,
        )


def _states(session):
    return [st for h in session.engine.handles.values() for st in h.attached_states
            if isinstance(st, SharedHashBuildState)]


@pytest.mark.parametrize("partitions", [1, 4])
def test_engine_picks_dense_for_scan_and_semi_builds(db, partitions):
    session = graftdb.connect(db, EngineConfig(mode="graft", partitions=partitions,
                                               morsel_size=4096))
    p4 = {"date": 823.0}
    q4 = Query(qid=10**6, template="q4", plan=queries.q4_semi_plan(db, p4), params=p4)
    q3 = queries.make_query(db, "q3", {"segment": 1.0, "date": 750.0}, 0.0)
    futs = session.submit_all([q4, q3])
    session.run()
    for q, f in zip((q4, q3), futs):
        _same(f.result(), refexec.execute(db, q.plan))
    by_table = {}
    for st in _states(session):
        by_table.setdefault((st.semi, st.did_domain), st.dense_dids)
    semi = [d for (is_semi, _), d in by_table.items() if is_semi]
    assert semi == [True]  # Q4's lineitem key state
    assert by_table[(False, db["customer"].nrows)] is True  # Q3's customer scan
    composed = db["orders"].nrows * db["customer"].nrows
    assert by_table[(False, composed)] is False  # orders ⋈ customer keeps the hash
    c = session.counters
    assert c["dense_did_rows"] > 0 and c["hashed_did_rows"] > 0
    session.close()


def test_dense_state_spills_rehydrates_and_attaches(db):
    """A dense state spilled to the reuse plane comes back dense, its did
    index resolving every stored did to its entry, and a query attaches to
    it with results equal to the reference."""
    cache = dict(retention="epoch", memory_budget=0, reuse_cache_budget=64_000_000)
    session = graftdb.connect(db, EngineConfig(mode="graft", **cache))
    first = session.submit(queries.make_query(db, "q3", {"segment": 1.0, "date": 750.0}, 0.0))
    session.run()
    assert session.counters["cache_spills"] > 0
    later = session.submit(queries.make_query(db, "q3", {"segment": 1.0, "date": 800.0}, 1.0))
    session.run()
    assert session.counters["cache_hits"] > 0
    _same(later.result(), refexec.execute(db, later.query.plan))
    _same(first.result(), refexec.execute(db, first.query.plan))
    handle = session.engine.handles[later.query.qid]
    rehydrated = [st for st in handle.attached_states
                  if isinstance(st, SharedHashBuildState) and st.dense_dids
                  and st not in session.engine.handles[first.query.qid].attached_states]
    assert rehydrated
    for st in rehydrated:
        n = st.n_entries
        ids, sel = st.resolve_dids(st.did.data, st.keycode.data, n)
        np.testing.assert_array_equal(ids, np.arange(n))
        assert len(sel) == 0
    session.close()
