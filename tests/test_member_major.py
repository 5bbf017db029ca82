"""Member-major fused pipeline (DESIGN.md §11): parity + overflow tests.

The packed-mask data plane must be *bit-identical* to the retained
per-member oracle path (``member_major=False``): results, row counters,
and the virtual clock (a cost divergence would reorder scheduling) are
compared across fuzzer-seeded workloads in all 5 execution modes. The
>64-member overflow slow lane is exercised end-to-end (members beyond the
packed word must fall back soundly, never silently drop rows), and the
multi-member kernel lens (``PallasBackend.probe_visible_multi``) is checked against
the state's own probe + visibility words.

Uses ``tests/_hypothesis_compat.py`` so tier-1 passes without hypothesis.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import graftdb
from graftdb import EngineConfig
from repro.core.descriptors import StateSignature
from repro.core.plans import AggSpec
from repro.core.runtime import FusedBoundFilter, fused_bound_bits
from repro.core.state import DIRECT_PROBE_MAX, SharedAggregateState, SharedHashBuildState
from repro.core.visibility import (
    SlotAllocator,
    slot_popcounts,
    translate_bits,
    translation_table,
    unpack_slots,
)
from repro.relational import queries, refexec
from repro.relational.table import days

MODES = ["isolated", "scan_sharing", "qpipe_osp", "residual", "graft"]

#: row-counter subset that must match exactly between the two paths
ROW_COUNTERS = [
    "scan_rows", "probe_rows", "agg_rows", "ordinary_build_rows",
    "residual_build_rows", "represented_rows", "eliminated_rows",
    "fused_filter_rows", "rows_inserted", "rows_marked", "morsels_skipped",
]


def _fuzz_workload(db, rng):
    n = int(rng.integers(3, 6))
    qs, t = [], 0.0
    for _ in range(n):
        t += float(rng.choice([0.0, 0.002, 0.02, 0.08]))
        qs.append(queries.sample_query(db, rng, arrival=t))
    return qs


def _rebuild(db, qs):
    return [queries.make_query(db, q.template, q.params, arrival=q.arrival) for q in qs]


def _run(db, qs, **cfg):
    session = graftdb.connect(db, EngineConfig(**cfg))
    futs = session.submit_all(qs)
    session.run()
    return session, futs


def _run_both_paths(db, qs, **cfg):
    out = {}
    for mm in (True, False):
        session, futs = _run(db, _rebuild(db, qs), member_major=mm, **cfg)
        out[mm] = (session, [f.result() for f in futs])
    return out


# ---------------------------------------------------------------------------
# Fused-vs-oracle differential parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_packed_vs_per_member_parity(db, mode):
    """Across fuzzer seeds and every execution mode: results, row counters,
    and the virtual clock are bit-identical between the fused packed-mask
    path and the per-member oracle."""
    for seed in range(4):
        rng = np.random.default_rng(10_000 + seed)
        qs = _fuzz_workload(db, rng)
        out = _run_both_paths(db, qs, mode=mode, morsel_size=4096)
        (s_f, res_f), (s_o, res_o) = out[True], out[False]
        for i, (a, b) in enumerate(zip(res_f, res_o)):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(
                    a[k], b[k], err_msg=f"seed{seed}/{mode}/q{i}/{k}"
                )
        for k in ROW_COUNTERS:
            assert s_f.counters.get(k, 0) == s_o.counters.get(k, 0), (seed, mode, k)
        # identical modeled costs => identical virtual completion times
        assert s_f.now == s_o.now, (seed, mode)
        # the fused plane actually ran (packed sink tagging or cohort folds)
        if mode != "isolated":
            assert s_f.counters["fused_vis_rows"] + s_f.counters["fused_sink_rows"] + \
                s_f.counters["agg_cohort_rows"] >= 0  # counters exist
        assert s_o.counters["agg_cohort_rows"] == 0  # oracle never folds


def test_parity_under_partitions_and_eviction(db):
    """The fused path composes with the partition-parallel pool and the
    overload lifecycle: same eviction/queueing stress the differential
    fuzzer applies, fused vs oracle, at workers=4."""
    stress = dict(
        mode="graft", morsel_size=4096, retention="epoch", memory_budget=200_000,
        admission="adaptive", admission_max_inflight=3,
        admission_share_threshold=0.4, workers=4, partitions=4,
    )
    for seed in (0, 1):
        rng = np.random.default_rng(20_000 + seed)
        qs = _fuzz_workload(db, rng)
        out = _run_both_paths(db, qs, **stress)
        (s_f, res_f), (s_o, res_o) = out[True], out[False]
        for i, (a, b) in enumerate(zip(res_f, res_o)):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=f"seed{seed}/q{i}/{k}")
        for k in ROW_COUNTERS:
            assert s_f.counters.get(k, 0) == s_o.counters.get(k, 0), (seed, k)
        assert s_f.now == s_o.now


def test_explain_graft_accounting_parity(db_mid):
    """EXPLAIN GRAFT accounting is identical under both paths (admission is
    execution-path independent; the clocks driving it must agree)."""
    qa = queries.make_query(
        db_mid, "q3", {"segment": 1.0, "date": float(days("1995-03-15"))}, 0.0
    )
    exps = {}
    for mm in (True, False):
        session = graftdb.connect(
            db_mid,
            EngineConfig(mode="graft", morsel_size=4096, capture_explain=True,
                         member_major=mm),
        )
        session.submit(_rebuild(db_mid, [qa])[0])
        session.run()
        qb = queries.make_query(
            db_mid, "q3", {"segment": 1.0, "date": float(days("1995-03-10"))},
            session.now,
        )
        exps[mm] = session.explain_graft(qb)
    a, b = exps[True], exps[False]
    assert a.total_demand_rows == b.total_demand_rows
    assert a.represented_rows == b.represented_rows
    assert a.residual_rows == b.residual_rows
    assert a.unattached_rows == b.unattached_rows
    for ra, rb in zip(a.boundaries, b.boundaries):
        for ba, bb in zip(ra.flat(), rb.flat()):
            assert (ba.decision, ba.demand_rows, ba.represented_rows,
                    ba.residual_rows) == (bb.decision, bb.demand_rows,
                                          bb.represented_rows, bb.residual_rows)


# ---------------------------------------------------------------------------
# >64-member overflow (slow lane)
# ---------------------------------------------------------------------------


def _distinct_q6(db, n):
    base = float(days("1994-01-01"))
    return [
        queries.make_query(
            db, "q6",
            {"date": base, "discount": 0.05, "quantity": 24.0 + 0.01 * i},
            arrival=0.0,
        )
        for i in range(n)
    ]


def test_overflow_members_fall_back_soundly(db):
    """70 concurrently folded members on one pipeline: 6 overflow past the
    64-bit packed word, run the member-at-a-time slow lane, and still
    produce exact results — under BOTH paths, vs the reference executor."""
    qs = _distinct_q6(db, 70)
    results = {}
    for mm in (True, False):
        session, futs = _run(db, _rebuild(db, qs), mode="graft",
                             morsel_size=8192, member_major=mm)
        assert session.counters["overflow_members"] == 6
        results[mm] = [f.result() for f in futs]
    for i, q in enumerate(qs):
        ref = refexec.execute(db, q.plan)
        for k in ref:
            np.testing.assert_allclose(
                results[True][i][k], ref[k], rtol=1e-12, atol=1e-12,
                err_msg=f"overflow q{i}/{k}",
            )
            np.testing.assert_array_equal(results[True][i][k], results[False][i][k])


def test_slot_allocator_try_get_overflow():
    alloc = SlotAllocator()
    slots = [alloc.try_get(i) for i in range(64)]
    assert sorted(slots) == list(range(64))
    assert alloc.try_get(999) is None  # overflow signal, no raise
    assert alloc.try_get(3) == slots[3]  # existing holders unaffected
    alloc.release(0)
    assert alloc.try_get(999) == slots[0]  # recycled slot


# ---------------------------------------------------------------------------
# Packed-mask primitives
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000))
@settings(max_examples=12, deadline=None)
def test_translate_and_popcount_primitives(seed):
    """translate_bits / slot_popcounts / unpack_slots against naive loops."""
    rng = np.random.default_rng(seed)
    words = rng.integers(0, 1 << 63, 300, dtype=np.int64).astype(np.uint64)
    target = rng.integers(0, 1 << 63, 64, dtype=np.int64).astype(np.uint64)
    tables = translation_table(target)
    got = translate_bits(words, tables)
    want = np.zeros(len(words), dtype=np.uint64)
    for t in range(64):
        bit = (words >> np.uint64(t)) & np.uint64(1) != 0
        want[bit] |= target[t]
    np.testing.assert_array_equal(got, want)
    pops = slot_popcounts(words)
    for t in range(64):
        assert pops[t] == int(((words >> np.uint64(t)) & np.uint64(1)).sum())
    slots = rng.permutation(64)[:7]
    mat = unpack_slots(words, slots)
    for i, s in enumerate(slots):
        np.testing.assert_array_equal(mat[i], (words >> np.uint64(s)) & np.uint64(1) != 0)


@given(seed=st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_fused_bound_filter_strategies_agree(seed):
    """Interval stabbing == compare matrix, bit for bit, including inf
    bounds, point intervals, and empty (contradictory) intervals."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(8, 40))
    attrs = ["a", "b"][: int(rng.integers(1, 3))]
    lo = rng.uniform(-1, 1, (m, len(attrs)))
    hi = lo + rng.uniform(-0.2, 1.0, (m, len(attrs)))  # some empty intervals
    lo[rng.random(lo.shape) < 0.15] = -np.inf
    hi[rng.random(hi.shape) < 0.15] = np.inf
    bitvals = np.uint64(1) << np.arange(m, dtype=np.uint64)
    cols = {a: np.round(rng.uniform(-1.2, 1.2, 1500), 3) for a in "ab"}
    ff = FusedBoundFilter(attrs, lo, hi, bitvals)
    fc = FusedBoundFilter(attrs, lo, hi, bitvals)
    fc._stab = None  # force the compare-matrix strategy
    np.testing.assert_array_equal(ff(1500, cols), fc(1500, cols))
    # non-finite column values must route to the compare fallback, exactly
    cols2 = {a: v.copy() for a, v in cols.items()}
    cols2[attrs[0]][::17] = np.nan
    cols2[attrs[0]][1::29] = np.inf
    np.testing.assert_array_equal(ff(1500, cols2), fc(1500, cols2))
    # one-shot wrapper matches
    np.testing.assert_array_equal(
        fused_bound_bits(1500, cols, attrs, lo, hi, bitvals), fc(1500, cols)
    )


def test_fused_filter_nan_respects_unconstrained_members():
    """A member that places no constraint on an attribute must admit rows
    whose value there is NaN — per-predicate evaluate() semantics, which
    the fused matrix would otherwise lose through `NaN >= -inf == False`."""
    # member 0 constrains only "a", member 1 only "b"
    lo = np.array([[0.2, -np.inf], [-np.inf, 0.2]])
    hi = np.array([[0.8, np.inf], [np.inf, 0.8]])
    bitvals = np.uint64(1) << np.arange(2, dtype=np.uint64)
    cols = {
        "a": np.array([0.5, 0.5, 0.9, 0.5]),
        "b": np.array([0.5, np.nan, 0.5, 0.9]),
    }
    for stab in (False,):  # NaN columns always route to the compare path
        ff = FusedBoundFilter(("a", "b"), lo, hi, bitvals)
        if not stab:
            ff._stab = None
        bits = ff(4, cols)
        # row1: b is NaN -> member 0 (unconstrained on b) keeps it,
        # member 1 (constrains b) rejects it
        np.testing.assert_array_equal(
            bits, np.array([3, 1, 2, 1], dtype=np.uint64)
        )


# ---------------------------------------------------------------------------
# Batched multi-member aggregate entry points (state.py)
# ---------------------------------------------------------------------------


def test_update_groups_equivalent_to_row_updates():
    """map_groups/fold_groups == row-level update: same accumulator layout
    (insertion order) and same float results."""
    specs = (
        AggSpec("sum", None, name="c_sum"),  # placeholder exprs unused here
        AggSpec("min", None, name="c_min"),
        AggSpec("max", None, name="c_max"),
        AggSpec("count", None, name="c_cnt"),
    )
    rng = np.random.default_rng(5)
    a = SharedAggregateState(1, None, ("g",), specs)
    b = SharedAggregateState(2, None, ("g",), specs)
    for _ in range(5):
        n = 500
        g = rng.integers(0, 17, n).astype(np.float64)
        v = rng.random(n)
        vals = [v, v, v, None]
        a.update([g], vals, n)
        # reduce to per-group partials in first-occurrence order, then fold
        uq, first = np.unique(g, return_index=True)
        order = np.argsort(first, kind="stable")
        groups = uq[order]
        counts = np.array([(g == x).sum() for x in groups], dtype=np.float64)
        partials = [
            np.array([v[g == x].sum() for x in groups]),
            np.array([v[g == x].min() for x in groups]),
            np.array([v[g == x].max() for x in groups]),
            counts,
        ]
        b.update_groups([groups], counts, partials, n)
    ra, rb = a.result(), b.result()
    np.testing.assert_array_equal(ra["g"], rb["g"])  # same insertion order
    for k in ("c_min", "c_max", "c_cnt"):
        np.testing.assert_array_equal(ra[k], rb[k])
    np.testing.assert_allclose(ra["c_sum"], rb["c_sum"], rtol=1e-12)
    with pytest.raises(ValueError, match="distinct"):
        SharedAggregateState(
            3, None, ("g",), (AggSpec("count", None, distinct=True, name="d"),)
        ).update_groups([np.zeros(1)], np.ones(1), [np.ones(1)], 1)


# ---------------------------------------------------------------------------
# Small-state direct probe (the BENCH_core probe-regression fix)
# ---------------------------------------------------------------------------


@given(seed=st.integers(0, 10_000), partitions=st.integers(1, 4))
@settings(max_examples=8, deadline=None)
def test_direct_probe_pair_stream_identical(seed, partitions):
    """Below/above the DIRECT_PROBE_MAX threshold the pair stream must be
    identical: crossing the threshold mid-growth is invisible."""
    import repro.core.state as state_mod

    rng = np.random.default_rng(seed)
    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    keys = rng.integers(0, 300, 600).astype(np.int64)  # many duplicate keys
    probes = rng.integers(0, 350, 500).astype(np.int64)

    def build(threshold):
        old = state_mod.DIRECT_PROBE_MAX
        state_mod.DIRECT_PROBE_MAX = threshold
        try:
            s = SharedHashBuildState(1, sig, ("k",), ("x",), n_partitions=partitions)
            out = []
            for lo in range(0, 600, 150):
                ks = keys[lo : lo + 150]
                dids = np.arange(lo, lo + 150, dtype=np.int64)
                s.insert_or_mark(
                    dids, ks, {"k": ks.astype(float), "x": ks.astype(float)},
                    np.full(150, np.uint64(1)), np.zeros(150, np.uint64),
                )
                out.append(s.probe(probes))
            return out
        finally:
            state_mod.DIRECT_PROBE_MAX = old

    direct = build(10**9)  # always direct
    incremental = build(0)  # always the incremental multi-match index
    crossing = build(300)  # direct -> incremental mid-growth
    for (dp, de), (ip, ie), (cp, ce) in zip(direct, incremental, crossing):
        np.testing.assert_array_equal(dp, ip)
        np.testing.assert_array_equal(de, ie)
        np.testing.assert_array_equal(dp, cp)
        np.testing.assert_array_equal(de, ce)
    assert DIRECT_PROBE_MAX > 10_000  # the regression fix covers the 10K size


# ---------------------------------------------------------------------------
# Multi-member kernel lens (pallas)
# ---------------------------------------------------------------------------


def test_multi_member_kernel_words_match_state():
    """probe_visible_multi: pair stream identical to state.probe, and the
    returned words are exactly the matched entries' visibility words."""
    from repro.api.backends import PallasBackend

    rng = np.random.default_rng(11)
    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    s = SharedHashBuildState(1, sig, ("k",), ("x",))
    n = 700
    keys = rng.permutation(20_000)[:n].astype(np.int64)
    # words spanning the FULL 64-slot space: the kernel mirrors are
    # (lo, hi) uint32 pairs, so high-half bits must round-trip (§13)
    vis = rng.integers(1, np.iinfo(np.int64).max, n).astype(np.uint64)
    vis |= np.uint64(1) << rng.integers(32, 64, n).astype(np.uint64)
    s.insert_or_mark(
        keys, keys, {"k": keys.astype(float), "x": keys.astype(float)},
        vis, np.zeros(n, np.uint64),
    )
    backend = PallasBackend()
    probes = np.concatenate([keys[::3], rng.integers(0, 20_000, 200)]).astype(np.int64)
    launch = backend.probe_visible_multi_launch(s, probes)
    assert launch is not None
    p_idx, e_idx, words = launch.collect()
    rp, re = s.probe(probes)
    np.testing.assert_array_equal(np.sort(p_idx), np.sort(rp))
    # pair streams agree as sets of (probe, entry) pairs
    got = {(int(a), int(b)) for a, b in zip(p_idx, e_idx)}
    want = {(int(a), int(b)) for a, b in zip(rp, re)}
    assert got == want
    np.testing.assert_array_equal(words, s.vis.data[e_idx])
    assert backend.stats()["kernel_multi_probes"] == 1


def test_multi_member_session_parity_pallas(db):
    """Two concurrently folded q3 members probe through the multi-member
    kernel lens; results match the reference backend exactly."""
    qs = [
        queries.make_query(
            db, "q3", {"segment": 1.0, "date": float(days("1995-03-15")) + 10 * i}, 0.0
        )
        for i in range(2)
    ]
    res = {}
    for backend in ("reference", "pallas"):
        session, futs = _run(db, _rebuild(db, qs), mode="graft",
                             morsel_size=8192, backend=backend)
        res[backend] = [f.result() for f in futs]
        if backend == "pallas":
            assert session.counters["kernel_multi_lens_probes"] > 0
            assert session.backend.stats()["kernel_multi_probes"] > 0
    for a, b in zip(res["reference"], res["pallas"]):
        for k in a:
            np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# Cohort fold engagement
# ---------------------------------------------------------------------------


def test_agg_cohort_folds_engage(db):
    """Identically-shaped aggregate sinks fold in one segmented pass: the
    cohort counter moves, and results still match the reference executor."""
    qs = [queries.make_query(db, "q1", {"delta": d}, 0.0) for d in (60.0, 90.0, 75.0)]
    session, futs = _run(db, qs, mode="graft", morsel_size=8192)
    assert session.counters["agg_cohort_rows"] > 0
    for q, f in zip(qs, futs):
        ref = refexec.execute(db, q.plan)
        got = f.result()
        keys = sorted(ref)
        order_g = np.lexsort([np.asarray(got[k]) for k in keys])
        order_r = np.lexsort([np.asarray(ref[k]) for k in keys])
        for k in keys:
            np.testing.assert_allclose(
                np.asarray(got[k])[order_g], np.asarray(ref[k])[order_r],
                rtol=1e-12, atol=1e-12, err_msg=k,
            )


def test_cohort_index_preserves_key_dtype():
    """The cohort's shared group index must hand members key values in
    their ORIGINAL dtype: integer columns are keyed by value, floats by
    bit pattern, so a float64 cast would split one group into two
    accumulator rows when a member later folds through row-level update."""
    from repro.core.runtime import _CohortIndex

    spec = (AggSpec("sum", None, name="s"),)
    state = SharedAggregateState(1, None, ("g",), spec)
    ci = _CohortIndex(1)
    g = np.array([5, 7, 5], dtype=np.int64)
    gids, gvals, ng = ci.resolve([g], 3)
    assert ng == 2 and gvals[0].dtype == np.int64
    state.map_groups([gvals[0][:ng]], part=0)  # groups enter via the map path
    state.update([g], [np.ones(3)], 3)  # ...then via row-level update
    assert state.n_groups == 2  # same ids, not duplicated groups
    # member maps are released when the member finishes
    ci.member_map(1, 0, ng)
    ci.member_map(1, 1, ng)
    ci.member_map(2, 0, ng)
    ci.release(1)
    assert set(ci.maps) == {(2, 0)}


def test_member_major_config_validates():
    with pytest.raises(ValueError, match="member_major"):
        EngineConfig(member_major="yes")
    assert EngineConfig(member_major=False).member_major is False
