"""Device-resident fused stage-chain data plane (DESIGN.md §13) tests.

The chain kernel fuses a morsel's whole stage sequence — hash probe →
lens-word translation → compiled grant predicates → interval stage
filters → sink word translation — into one Pallas launch over
entry-indexed device mirrors. Everything it returns must leave results,
row counters and the virtual clock bit-identical to both the NumPy
member-major path and the per-member oracle, so these tests are all
differential: total-order float encoding vs IEEE compares, chain-served
sessions vs reference/oracle sessions across modes and pool geometries,
grant-compiled and >32-slot chains, per-reason fallback attribution,
incremental mirror maintenance, and the spill -> rehydrate -> chain-probe
round trip through the reuse plane (§12)."""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.relational import queries, refexec
from repro.relational.table import days

jax = pytest.importorskip("jax")

MODES = ["isolated", "scan_sharing", "qpipe_osp", "residual", "graft"]

#: row-counter subset that must match exactly across execution paths
ROW_COUNTERS = [
    "scan_rows", "probe_rows", "agg_rows", "ordinary_build_rows",
    "residual_build_rows", "represented_rows", "eliminated_rows",
    "fused_filter_rows", "rows_inserted", "rows_marked", "morsels_skipped",
]


def _q3(db, date, seg=1.0, arrival=0.0):
    return queries.make_query(
        db, "q3", {"segment": seg, "date": float(days(date))}, arrival
    )


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
    return session, [f.result() for f in futs]


def _assert_bitequal(got, want, ctx=""):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=f"{ctx}/q{i}/{k}")


# ---------------------------------------------------------------------------
# Total-order float64 encoding (the kernel's compare substrate)
# ---------------------------------------------------------------------------


def test_total_order_encoding_matches_ieee_compares():
    """Unsigned-lexicographic compares on the encoding reproduce IEEE
    ``<=`` exactly, including ±inf, denormals, and the two zeros."""
    from repro.kernels.fused_chain import total_order_u32

    vals = np.array(
        [-np.inf, -1e300, -1.5, -5e-324, -0.0, 0.0, 5e-324, 1.0, 1e300, np.inf]
    )
    hi, lo = total_order_u32(vals)
    enc = (hi.astype(np.uint64) << np.uint64(32)) | lo.astype(np.uint64)
    for i, a in enumerate(vals):
        for j, b in enumerate(vals):
            assert (a <= b) == (enc[i] <= enc[j]), (a, b)
    # -0.0 canonicalizes: the zeros encode equal
    assert enc[4] == enc[5]


def test_total_order_encoding_rejects_nan_from_every_interval():
    """NaN encodes strictly outside the [-inf, +inf] band (on its sign's
    side), so a constrained interval compare can never admit it — matching
    NumPy's ``(x >= lo) & (x <= hi)`` on NaN."""
    from repro.kernels.fused_chain import total_order_u32

    def enc(v):
        hi, lo = total_order_u32(np.asarray([v]))
        return (np.uint64(hi[0]) << np.uint64(32)) | np.uint64(lo[0])

    lo_inf, hi_inf = enc(-np.inf), enc(np.inf)
    for nan in (np.nan, -np.nan, np.float64.fromhex("nan")):
        e = enc(nan)
        assert e > hi_inf or e < lo_inf


def test_total_order_bound_scalar_matches_array():
    from repro.kernels.fused_chain import total_order_bound, total_order_u32

    for v in (-np.inf, -3.25, 0.0, 7.5, np.inf):
        hi, lo = total_order_u32(np.asarray([v]))
        assert total_order_bound(v) == (int(hi[0]), int(lo[0]))


@pytest.mark.parametrize("seed", [0, 11])
def test_device_translation_equals_the_host_byte_tables(seed):
    """The chain's word translation (one select per slot bit, reading each
    slot's target off the split byte tables) ORs exactly what
    ``translate_bits`` does, for any targets and any words."""
    from repro.core.visibility import join_words, split_words, translate_bits, translation_table
    from repro.kernels.fused_chain import _translate

    rng = np.random.default_rng(seed)
    top = np.iinfo(np.uint64).max
    target = rng.integers(0, top, 64, dtype=np.uint64, endpoint=True)
    target[rng.random(64) < 0.3] = 0
    words = rng.integers(0, top, 4096, dtype=np.uint64, endpoint=True)
    words &= rng.integers(0, top, 4096, dtype=np.uint64, endpoint=True)  # sparser bits
    words[:64] = np.uint64(1) << np.arange(64, dtype=np.uint64)
    words[64] = 0
    tables = translation_table(target)
    tlo, thi = split_words(tables.ravel())
    wlo, whi = split_words(words)
    olo, ohi = _translate(jax.numpy.asarray(wlo), jax.numpy.asarray(whi),
                          jax.numpy.asarray(tlo.reshape(8, 256)),
                          jax.numpy.asarray(thi.reshape(8, 256)))
    got = join_words(np.asarray(olo), np.asarray(ohi))
    np.testing.assert_array_equal(got, translate_bits(words, tables))


# ---------------------------------------------------------------------------
# Chain-served sessions: bit-exact against oracle + reference, all modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", MODES)
def test_chain_parity_all_modes(db, mode):
    """Fuzzer workloads through the chain-dispatching Pallas backend match
    the per-member oracle AND the NumPy member-major path bit-for-bit:
    results, row counters, and the virtual clock."""
    launched = 0
    for seed in range(2):
        rng = np.random.default_rng(42_000 + seed)
        qs = _fuzz_workload(db, rng)
        cfg = dict(mode=mode, morsel_size=4096)
        s_c, res_c = _run(db, _rebuild(db, qs), backend="pallas",
                          member_major=True, **cfg)
        s_o, res_o = _run(db, _rebuild(db, qs), backend="pallas",
                          member_major=False, **cfg)
        s_n, res_n = _run(db, _rebuild(db, qs), member_major=True, **cfg)
        _assert_bitequal(res_c, res_o, f"{mode}/seed{seed}/oracle")
        _assert_bitequal(res_c, res_n, f"{mode}/seed{seed}/numpy")
        for k in ROW_COUNTERS:
            assert s_c.counters.get(k, 0) == s_o.counters.get(k, 0), (mode, seed, k)
            assert s_c.counters.get(k, 0) == s_n.counters.get(k, 0), (mode, seed, k)
        # the virtual clock is backend-relative (the fused-lens probe models
        # fewer match ops than the reference probe), so exact clock identity
        # holds within a backend: chain-served fused vs per-member oracle
        assert s_c.now == s_o.now, (mode, seed)
        launched += int(s_c.counters["kernel_chain_launches"])
    if mode != "isolated":
        assert launched > 0, "the fused chain never served a morsel"


def test_chain_parity_partition_parallel(db):
    """Chain dispatch composes with the partition pool (workers=4) and the
    eviction/admission lifecycle without perturbing parity."""
    stress = dict(
        mode="graft", morsel_size=4096, retention="epoch", memory_budget=200_000,
        admission="adaptive", admission_max_inflight=3,
        admission_share_threshold=0.4, workers=4, partitions=4,
    )
    rng = np.random.default_rng(77_000)
    qs = _fuzz_workload(db, rng)
    s_c, res_c = _run(db, _rebuild(db, qs), backend="pallas",
                      member_major=True, **stress)
    s_o, res_o = _run(db, _rebuild(db, qs), backend="pallas",
                      member_major=False, **stress)
    s_n, res_n = _run(db, _rebuild(db, qs), member_major=True, **stress)
    _assert_bitequal(res_c, res_o, "partitioned/oracle")
    _assert_bitequal(res_c, res_n, "partitioned/numpy")
    for k in ROW_COUNTERS:
        assert s_c.counters.get(k, 0) == s_o.counters.get(k, 0), k
        assert s_c.counters.get(k, 0) == s_n.counters.get(k, 0), k
    assert s_c.now == s_o.now
    assert s_c.counters["kernel_chain_launches"] > 0


def test_chain_serves_slots_beyond_32(db):
    """Members holding slots >= 32 probe through the chain (the lens
    mirrors are (lo, hi) uint32 pairs): the former uint32 slot<32 kernel
    limit is gone, so ``fallback_probes_slot_limit`` stays zero forever."""
    dates = [f"1995-03-{d:02d}" for d in range(1, 29)]
    qs = [
        _q3(db, d, seg=float(s % 3), arrival=0.0)
        for s, d in enumerate(dates + dates[:12])
    ]  # 40 concurrent members on the shared build states
    s_c, res_c = _run(db, qs, backend="pallas", member_major=True,
                      mode="scan_sharing", morsel_size=8192)
    s_n, res_n = _run(
        db,
        [queries.make_query(db, q.template, q.params, arrival=q.arrival) for q in qs],
        member_major=True, mode="scan_sharing", morsel_size=8192,
    )
    _assert_bitequal(res_c, res_n, "slots>=32")
    assert s_c.counters["kernel_chain_launches"] > 0
    assert s_c.counters["fallback_probes_slot_limit"] == 0
    assert s_c.backend.fallback_reasons["slot_limit"] == 0


def test_grant_compiled_chain_parity(db):
    """Extent-scoped grants whose conjunctions canonicalize to intervals
    compile into the chain launch (grants no longer force the staged
    fallback); near-miss grafted repeats exercise them end-to-end."""
    seq = [
        ("q3", {"segment": 1.0, "date": 750.0}),
        ("q3", {"segment": 1.0, "date": 760.0}),
        ("q3", {"segment": 1.0, "date": 750.0}),
        ("q3", {"segment": 1.0, "date": 800.0}),
    ]
    res = {}
    sessions = {}
    for label, cfg in (
        ("chain", dict(backend="pallas", member_major=True)),
        ("numpy", dict(member_major=True)),
        ("oracle", dict(backend="pallas", member_major=False)),
    ):
        session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=4096, **cfg))
        futs = [
            session.submit(queries.make_query(db, t, p, arrival=float(i) * 0.01))
            for i, (t, p) in enumerate(seq)
        ]
        session.run()
        res[label] = [f.result() for f in futs]
        sessions[label] = session
    _assert_bitequal(res["chain"], res["numpy"], "grants/numpy")
    _assert_bitequal(res["chain"], res["oracle"], "grants/oracle")
    for k in ROW_COUNTERS:
        assert sessions["chain"].counters.get(k, 0) == sessions["numpy"].counters.get(k, 0), k
        assert sessions["chain"].counters.get(k, 0) == sessions["oracle"].counters.get(k, 0), k
    assert sessions["chain"].now == sessions["oracle"].now  # same backend
    assert sessions["chain"].counters["kernel_chain_launches"] > 0


# ---------------------------------------------------------------------------
# Per-reason fallback attribution (satellite: split fallback_probes)
# ---------------------------------------------------------------------------


def test_predicate_decline_counted_and_parity_kept(db):
    """q5's column-equality post-filter cannot canonicalize to intervals:
    its pipeline declines the chain with reason ``predicate`` (counted in
    the session counters AND on the backend) and runs the staged path —
    results still bit-match the NumPy plane."""
    q5 = queries.make_query(db, "q5", {"region": 1.0, "date": 730.0}, 0.0)
    s_c, res_c = _run(db, [q5], backend="pallas", member_major=True,
                      mode="graft", morsel_size=8192)
    s_n, res_n = _run(
        db,
        [queries.make_query(db, "q5", {"region": 1.0, "date": 730.0}, 0.0)],
        member_major=True, mode="graft", morsel_size=8192,
    )
    _assert_bitequal(res_c, res_n, "q5")
    assert s_c.counters["fallback_probes_predicate"] > 0
    assert s_c.backend.fallback_reasons["predicate"] > 0
    stats = s_c.backend.stats()
    assert stats["fallback_predicate"] == s_c.backend.fallback_reasons["predicate"]


def test_fallback_reason_counters_surface_in_stats(db):
    """QueryFuture.stats()["counters"] carries every per-reason decline
    counter; a clean q3 run leaves them all zero."""
    session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=8192, backend="pallas")
    )
    fut = session.submit(_q3(db, "1995-03-15"))
    fut.result()
    counters = fut.stats()["counters"]
    for reason in ("grants", "slot_limit", "keyrange", "capacity", "predicate"):
        assert counters[f"fallback_probes_{reason}"] == 0
    assert counters["kernel_chain_launches"] > 0
    assert session.backend.fallback_probes == 0


# ---------------------------------------------------------------------------
# Entry-indexed mirror maintenance (satellite: no rebuild invalidation)
# ---------------------------------------------------------------------------


def _mini_state(n0=64):
    from repro.core.descriptors import StateSignature
    from repro.core.state import SharedHashBuildState

    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    s = SharedHashBuildState(1, sig, ("k",), ("x",))
    keys = np.arange(n0, dtype=np.int64)
    # seed visibility in the HIGH word half (bit 63): exercises the uint32
    # pair split and leaves the low slots free for the test's allocations
    s.insert_or_mark(
        keys, keys, {"k": keys.astype(float), "x": keys.astype(float)},
        np.full(n0, np.uint64(1) << np.uint64(63)), np.zeros(n0, np.uint64),
    )
    return s, keys


def test_mirror_appends_and_marks_patch_incrementally():
    """Growing the state (within the entry capacity its ``max_entries``
    bound sizes) and marking existing entries must NOT regather the lens
    mirror: appends and mark-log entries patch in place
    (``mirror_patched_rows``), and so do the probe table's new slots
    (``mirror_full_regathers == 0``)."""
    from repro.api.backends import PallasBackend

    s, keys = _mini_state(64)
    s.max_entries = 256
    slot = s.slots.get(7)
    backend = PallasBackend(interpret=True)
    first = backend.probe_visible(s, keys, 7)
    assert first is not None and len(first[0]) == 0  # nothing marked for q7
    assert backend.mirror_full_regathers == 0

    # append 136 keys (64 -> 200) inside the mirror's entry capacity, and
    # mark a few entries visible to q7's slot — both must patch
    new = np.arange(64, 200, dtype=np.int64)
    s.insert_or_mark(
        new, new, {"k": new.astype(float), "x": new.astype(float)},
        np.full(len(new), np.uint64(1) << np.uint64(63)), np.zeros(len(new), np.uint64),
    )
    marked = np.array([3, 5, 11], dtype=np.int64)
    s.insert_or_mark(
        marked, marked,
        {"k": marked.astype(float), "x": marked.astype(float)},
        np.full(3, np.uint64(1) << np.uint64(slot)), np.zeros(3, np.uint64),
    )
    second = backend.probe_visible(s, np.arange(200, dtype=np.int64), 7)
    assert second is not None
    np.testing.assert_array_equal(np.sort(second[0]), marked)
    assert backend.mirror_full_regathers == 0
    assert backend.mirror_patched_rows > 0


def test_detach_bumps_vis_epoch_and_regathers_once():
    """``detach`` clears a slot's bit across all vis words without touching
    the mark log; the vis-epoch stamp must force exactly one mirror
    regather so stale visibility can never leak out of the kernel."""
    from repro.api.backends import PallasBackend

    s, keys = _mini_state(64)
    slot = s.slots.get(9)
    s.insert_or_mark(
        keys, keys, {"k": keys.astype(float), "x": keys.astype(float)},
        np.full(64, np.uint64(1) << np.uint64(slot)), np.zeros(64, np.uint64),
    )
    backend = PallasBackend(interpret=True)
    first = backend.probe_visible(s, keys, 9)
    assert first is not None and len(first[0]) == 64

    epoch_before = s.vis_epoch
    s.detach(9)
    assert s.vis_epoch == epoch_before + 1
    s.slots.get(9)  # reattach: same qid, fresh (unmarked) slot
    again = backend.probe_visible(s, keys, 9)
    assert again is not None and len(again[0]) == 0  # cleared bits observed
    assert backend.mirror_full_regathers == 1


# ---------------------------------------------------------------------------
# Reuse plane round trip (satellite: spill -> rehydrate -> chain probe)
# ---------------------------------------------------------------------------


def test_spill_rehydrate_then_chain_probe_parity(db):
    """A state that retires to the artifact cache, rehydrates on a repeat
    (§12), and then probes through the fused chain returns bit-equal
    results to a never-evicted NumPy oracle — the rehydrated SoA feeds the
    device mirrors exactly like a fresh build."""
    seq = [
        ("q3", {"segment": 1.0, "date": 750.0}),
        ("q6", {"date": 400.0, "discount": 0.05, "quantity": 25.0}),
        ("q3", {"segment": 1.0, "date": 750.0}),  # fingerprint hit -> rehydrate
        ("q3", {"segment": 1.0, "date": 800.0}),
        ("q3", {"segment": 1.0, "date": 750.0}),
    ]
    cache = dict(retention="epoch", memory_budget=0, reuse_cache_budget=64_000_000)

    def run_seq(extra):
        session = graftdb.connect(db, EngineConfig(mode="graft", **extra))
        futs = [
            session.submit(queries.make_query(db, t, p, arrival=float(i)))
            for i, (t, p) in enumerate(seq)
        ]
        session.run()
        return session, [f.result() for f in futs]

    s_o, oracle = run_seq(dict(retention="epoch", member_major=True))
    s_c, cached = run_seq(dict(cache, backend="pallas", member_major=True))
    _assert_bitequal(cached, oracle, "reuse")
    assert s_c.counters["cache_spills"] > 0
    assert s_c.counters["cache_hits"] > 0
    assert s_c.counters["kernel_chain_launches"] > 0

    # and a rehydrate-served repeat equals the reference executor
    session = graftdb.connect(
        db, EngineConfig(mode="graft", backend="pallas", member_major=True, **cache)
    )
    f0 = session.submit(queries.make_query(db, "q3", {"segment": 1.0, "date": 750.0}, 0.0))
    f0.result()
    f1 = session.submit(queries.make_query(db, "q3", {"segment": 1.0, "date": 750.0}, 1.0))
    got = f1.result()
    want = refexec.execute(db, f1.query.plan)
    assert set(got) == set(want)
    for k in got:
        np.testing.assert_allclose(
            np.asarray(got[k], np.float64), np.asarray(want[k], np.float64), rtol=1e-9
        )
