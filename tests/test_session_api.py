"""Unified Session API: EngineConfig validation, QueryFuture equivalence,
EXPLAIN GRAFT extent accounting, backend selection, and SlotAllocator
lifecycle (the visibility substrate the Session's sharing relies on)."""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig, PallasBackend, ReferenceBackend, ServingConfig
from repro.core.visibility import MAX_SLOTS, SlotAllocator
from repro.relational import queries, refexec
from repro.relational.table import days

ALL_MODES = ["isolated", "scan_sharing", "qpipe_osp", "residual", "graft"]


def _q3(db, date, seg=1.0, arrival=0.0):
    return queries.make_query(db, "q3", {"segment": seg, "date": float(days(date))}, arrival)


# ---------------------------------------------------------------------------
# EngineConfig validation
# ---------------------------------------------------------------------------


def test_engine_config_defaults_valid():
    cfg = EngineConfig()
    assert cfg.mode == "graft" and cfg.backend == "reference"
    assert cfg.make_backend().name == "reference"


@pytest.mark.parametrize(
    "kw",
    [
        {"mode": "turbo"},
        {"morsel_size": 0},
        {"morsel_size": -4},
        {"clock": "lamport"},
        {"clock": object()},
        {"backend": "cuda"},
        {"retention": "lru"},
        {"cost_model": {"warp": 1e-9}},
        {"max_steps": 0},
    ],
)
def test_engine_config_rejects_bad_values(kw):
    with pytest.raises((ValueError, TypeError)):
        EngineConfig(**kw)


def test_serving_config_rejects_bad_values():
    with pytest.raises(ValueError):
        ServingConfig(min_share=-1)
    with pytest.raises(ValueError):
        ServingConfig(prefill_tok_s=0.0)


def test_connect_kwargs_shortcut(db):
    session = graftdb.connect(db, mode="isolated", morsel_size=4096)
    assert session.mode == "isolated"
    with pytest.raises(TypeError):
        graftdb.connect(db, EngineConfig(), mode="graft")


# ---------------------------------------------------------------------------
# QueryFuture.result() equivalence with the isolated baseline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ALL_MODES)
def test_future_results_match_isolated_baseline(db, mode):
    """Same queries through every sharing mode return exactly the isolated
    (reference-executor) results — futures hide none of the semantics."""
    rng = np.random.default_rng(123)
    qs = [queries.sample_query(db, rng, arrival=i * 0.001) for i in range(4)]
    session = graftdb.connect(db, EngineConfig(mode=mode, morsel_size=8192))
    futures = session.submit_all(qs)
    for q, fut in zip(qs, futures):
        res = fut.result()  # drives the session on first call
        ref = refexec.execute(db, q.plan)
        assert set(res) == set(ref)
        for k in ref:
            np.testing.assert_allclose(
                np.sort(np.asarray(res[k], float)),
                np.sort(np.asarray(ref[k], float)),
                rtol=1e-9,
                atol=1e-6,
                err_msg=f"{q.template}/{k}/{mode}",
            )
        assert fut.done and fut.latency() >= 0.0
        assert fut.stats()["done"] is True


def test_future_wait_false_raises_before_run(db):
    session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=8192))
    fut = session.submit(_q3(db, "1995-03-15"))
    with pytest.raises(RuntimeError):
        fut.result(wait=False)
    assert fut.result() is not None


# ---------------------------------------------------------------------------
# EXPLAIN GRAFT extent accounting
# ---------------------------------------------------------------------------


def test_explain_graft_extents_sum_to_demand(db_mid):
    """TPC-H Q3 overlap scenario (the paper's Fig. 3 instance): the captured
    EXPLAIN GRAFT partitions every boundary's demand exactly into
    represented + residual + unattached."""
    # workers/partitions pinned: the 0.02s offset must land mid-flight in
    # single-stream time (the pool finishes Q_A earlier at higher worker
    # counts; overlap under workers>1 is covered in test_partition_parallel)
    session = graftdb.connect(
        db_mid,
        EngineConfig(
            mode="graft", morsel_size=4096, capture_explain=True, workers=1, partitions=1
        ),
    )
    qa = _q3(db_mid, "1995-03-15")
    qb = _q3(db_mid, "1995-03-20", arrival=0.02)  # broader, arrives mid-flight
    fa, fb = session.submit_all([qa, qb])
    session.run()

    for fut in (fa, fb):
        exp = fut.explain()
        assert exp.total_demand_rows > 0
        for b in [x for root in exp.boundaries for x in root.flat()]:
            assert (
                b.represented_rows + b.residual_rows + b.unattached_rows
                == b.demand_rows
            ), b
        assert (
            exp.represented_rows + exp.residual_rows + exp.unattached_rows
            == exp.total_demand_rows
        )

    # Q_A found an empty engine: all demand is unattached ordinary work.
    ea = fa.explain()
    assert ea.unattached_rows == ea.total_demand_rows
    # Q_B grafted onto Q_A's live state: some demand is represented and the
    # attachment targets Q_A's states.
    eb = fb.explain()
    assert eb.represented_rows > 0
    assert any(b.state_id is not None for root in eb.boundaries for b in root.flat())
    # rendering and dict export stay consistent
    d = eb.to_dict()
    assert d["total_demand_rows"] == eb.total_demand_rows
    assert "EXPLAIN GRAFT" in eb.render()


def test_explain_graft_preflight_is_read_only(db_mid):
    session = graftdb.connect(db_mid, EngineConfig(mode="graft", morsel_size=4096))
    session.submit(_q3(db_mid, "1995-03-15"))  # creates live shared states
    before = session.stats()["live_states"]
    qb = _q3(db_mid, "1995-03-20", arrival=0.0)
    exp = session.explain_graft(qb)
    # analysis attaches nothing: no new states, no refs, no grants
    assert session.stats()["live_states"] == before
    assert exp.total_demand_rows == (
        exp.represented_rows + exp.residual_rows + exp.unattached_rows
    )
    # pre-flight against incomplete coverage: attachment is residual-only
    assert exp.residual_rows > 0
    session.run()


def test_explain_requires_capture_flag(db):
    session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=8192))
    fut = session.submit(_q3(db, "1995-03-15"))
    session.run()
    with pytest.raises(RuntimeError, match="capture_explain"):
        fut.explain()


# ---------------------------------------------------------------------------
# Execution backends
# ---------------------------------------------------------------------------


def test_pallas_backend_matches_reference(db):
    jax = pytest.importorskip("jax")
    qa = _q3(db, "1995-03-15")
    qb = _q3(db, "1995-03-20", arrival=0.01)
    ref_session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=16384))
    pal_session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=16384, backend="pallas")
    )
    r_futs = ref_session.submit_all([_q3(db, "1995-03-15"), _q3(db, "1995-03-20", arrival=0.01)])
    p_futs = pal_session.submit_all([qa, qb])
    for rf, pf in zip(r_futs, p_futs):
        rres, pres = rf.result(), pf.result()
        assert set(rres) == set(pres)
        for k in rres:
            np.testing.assert_allclose(
                np.sort(np.asarray(pres[k], float)),
                np.sort(np.asarray(rres[k], float)),
                rtol=1e-9,
                atol=1e-6,
            )
    assert pal_session.backend.kernel_probes > 0  # the Pallas path actually ran


def test_pallas_batch_insert_detects_in_batch_duplicates(db):
    """Duplicate keycodes arriving in ONE insert batch must mark the probe
    table unservable (fall back to the reference multi-match probe), not
    silently drop the second entry."""
    pytest.importorskip("jax")
    from repro.core.descriptors import StateSignature
    from repro.core.state import SharedHashBuildState

    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    state = SharedHashBuildState(1, sig, ("k",), ("x",))
    kc = np.array([7, 7, 9], dtype=np.int64)
    dids = np.arange(3, dtype=np.int64)
    state.insert_or_mark(
        dids,
        kc,
        {"k": kc.astype(float), "x": kc.astype(float)},
        np.full(3, np.uint64(1)),
        np.zeros(3, np.uint64),
    )
    pal, ref = PallasBackend(), ReferenceBackend()
    probe = np.array([7, 9], dtype=np.int64)
    p_pairs = pal.probe_launch(state, probe).collect()
    r_pairs = ref.probe_launch(state, probe).collect()
    np.testing.assert_array_equal(p_pairs[0], r_pairs[0])
    np.testing.assert_array_equal(p_pairs[1], r_pairs[1])
    assert pal.fallback_probes == 1  # multi-match state: reference path


@pytest.mark.parametrize("kind", ["reference", "pallas"])
def test_backend_implements_interface(db, kind):
    """Each backend has every ``ExecutionBackend`` member, with the
    protocol's parameters, and runs a query through a bare ``GraftEngine``
    — which, given no backend, runs on a ``ReferenceBackend``."""
    import inspect

    from repro.api.backends import ExecutionBackend
    from repro.core.engine import GraftEngine
    from repro.core.scheduler import Runner

    cls = {"reference": ReferenceBackend, "pallas": PallasBackend}[kind]
    backend = cls()
    assert isinstance(backend, ExecutionBackend)
    methods = [n for n, v in vars(ExecutionBackend).items()
               if callable(v) and not n.startswith("_")]
    assert len(methods) == 7
    for name in methods:
        want = list(inspect.signature(getattr(ExecutionBackend, name)).parameters)
        assert list(inspect.signature(getattr(cls, name)).parameters) == want, name
    assert cls.launches_on_device is (kind == "pallas")
    assert cls.name == kind

    engine = GraftEngine(db) if kind == "reference" else GraftEngine(db, backend=backend)
    assert isinstance(engine.backend, cls)
    q = _q3(db, "1995-03-15")
    (handle,) = Runner(engine).run([q])
    want = refexec.execute(db, q.plan)
    assert set(handle.result) == set(want)
    for k in want:
        np.testing.assert_allclose(
            np.sort(np.asarray(handle.result[k], float)),
            np.sort(np.asarray(want[k], float)),
            rtol=1e-9,
        )
    assert (engine.backend.stats().get("kernel_probes", 0) > 0) is (kind == "pallas")


def test_backend_instance_passthrough(db):
    backend = ReferenceBackend()
    session = graftdb.connect(db, EngineConfig(backend=backend))
    assert session.backend is backend


# ---------------------------------------------------------------------------
# Data-plane perf counters (vectorized state plane, DESIGN.md §8)
# ---------------------------------------------------------------------------


def test_stats_expose_data_plane_counters(db):
    """QueryFuture.stats carries the shared-plane counters; a graft run
    exercises the fused filter and the batched did-index growth path."""
    session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=8192))
    fut = session.submit(_q3(db, "1995-03-15"))
    fut.result()
    counters = fut.stats()["counters"]
    assert set(counters) == {
        "index_rebuilds",
        "kernel_lens_probes",
        "fused_filter_rows",
        "kernel_multi_lens_probes",
        "fused_vis_rows",
        "fused_stage_filter_rows",
        "fused_sink_rows",
        "kernel_chain_launches",
        "fallback_probes_grants",
        "fallback_probes_slot_limit",
        "fallback_probes_keyrange",
        "fallback_probes_capacity",
        "fallback_probes_predicate",
        "agg_cohort_rows",
        "overflow_members",
        "partition_merges",
        "partition_probe_merges",
        "evictions",
        "evicted_bytes",
        "state_revivals",
        "queued_admissions",
        "forced_admissions",
        "admission_evals",
        "batch_cohorts",
        "batch_planned_queries",
        "batch_coverage_gain_rows",
        "cache_hits",
        "cache_spills",
        "cache_evictions",
        "cache_corrupt",
        "rehydrate_bytes",
    }
    assert counters["fused_filter_rows"] > 0  # source predicates ran fused
    assert counters["fused_sink_rows"] > 0  # member-major build tagging ran (§11)
    assert counters["overflow_members"] == 0  # nothing spilled past 64 slots
    # refcount retention + always-admission (defaults): lifecycle idle
    assert counters["evictions"] == 0 and counters["queued_admissions"] == 0
    assert fut.stats()["admission"] is None  # no controller on this session
    assert fut.stats()["queue_delay_s"] == 0.0
    # hash indexes doubled under growth: the did index of the orders ⋈
    # customer build (composed dids, too wide to address directly) and the
    # aggregate's group index
    assert counters["index_rebuilds"] > 0
    # the customer scan build resolved its row ids by direct address (§8)
    assert session.counters["dense_did_rows"] > 0 and session.counters["hashed_did_rows"] > 0
    assert counters["kernel_lens_probes"] == 0  # reference backend: no kernel lens
    # the worker-pool utilization block rides along on every stats dict
    wstats = fut.stats()["workers"]
    assert wstats["n"] >= 1 and len(wstats["busy_s"]) == wstats["n"]
    # engine-level stats mirror the same counters
    stats = session.stats()
    for k, v in counters.items():
        assert stats[k] == v


def test_pallas_lens_probe_resolves_in_kernel(db):
    """Single-member probes route through the fused-lens kernel with the
    state's real visibility words — and still match the reference result."""
    pytest.importorskip("jax")
    q = _q3(db, "1995-03-15")
    ref_session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=16384))
    pal_session = graftdb.connect(
        db, EngineConfig(mode="graft", morsel_size=16384, backend="pallas")
    )
    rres = ref_session.submit(_q3(db, "1995-03-15")).result()
    pfut = pal_session.submit(q)
    pres = pfut.result()
    for k in rres:
        np.testing.assert_allclose(
            np.sort(np.asarray(pres[k], float)),
            np.sort(np.asarray(rres[k], float)),
            rtol=1e-9,
            atol=1e-6,
        )
    counters = pfut.stats()["counters"]
    assert counters["kernel_lens_probes"] > 0
    assert pal_session.backend.kernel_lens_probes == counters["kernel_lens_probes"]
    # unique-key dimension states must not have fallen back to the
    # reference probe (acceptance: no new Pallas fallbacks)
    assert pal_session.backend.fallback_probes == 0


# ---------------------------------------------------------------------------
# SlotAllocator lifecycle (visibility substrate)
# ---------------------------------------------------------------------------


def test_slot_allocator_exhaustion_and_recycling():
    alloc = SlotAllocator()
    slots = [alloc.get(qid) for qid in range(MAX_SLOTS)]
    assert sorted(slots) == list(range(MAX_SLOTS))
    # 65th concurrent query on one state must raise
    with pytest.raises(RuntimeError, match="slots exhausted"):
        alloc.get(MAX_SLOTS)
    # idempotent for an already-attached query
    assert alloc.get(3) == slots[3]
    # release recycles: the freed bit is handed to the next attach
    alloc.release(10)
    assert alloc.peek(10) is None
    assert alloc.get(MAX_SLOTS) == slots[10]
    # releasing an unknown qid is a no-op
    alloc.release(99999)


def test_run_reports_each_completion_once(db):
    """A reused session's run() returns only the round's new completions."""
    session = graftdb.connect(db, EngineConfig(mode="graft", morsel_size=8192))
    session.submit(_q3(db, "1995-03-15"))
    first = session.run()
    assert len(first) == 1
    session.submit(_q3(db, "1995-03-20"))
    second = session.run()
    assert len(second) == 1
    assert first[0].qid != second[0].qid
    assert session.run() == []  # drained: nothing new to report


def test_session_lifecycle(db):
    session = graftdb.connect(db, EngineConfig(mode="isolated", morsel_size=8192))
    with session:
        fut = session.submit(_q3(db, "1995-03-15"))
        assert fut.result() is not None
    with pytest.raises(RuntimeError, match="closed"):
        session.submit(_q3(db, "1995-03-20"))
