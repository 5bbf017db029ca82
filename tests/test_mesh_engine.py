"""Mesh-sharded shared state (DESIGN.md §14): the smoke-mesh session is
bit-identical to the mesh-less 1×1 oracle in every mode, the config layer
pins partitions = workers = data-axis size, the per-device state views and
the real exchange validation hold, and the db-plane dry-run record
validates on the smoke mesh. Multi-device parity (2/4/8 host devices) runs
in benchmarks/mesh_sweep.py — jax pins the device count at first init, so
tier-1 stays on the single real device."""

import numpy as np
import pytest

import graftdb
from graftdb import EngineConfig
from repro.launch.mesh import make_smoke_mesh, mesh_data_size, resolve_mesh
from repro.relational import queries

ALL_MODES = ["isolated", "scan_sharing", "qpipe_osp", "residual", "graft"]


def _workload(db, n=6, seed=123, spacing=0.001):
    rng = np.random.default_rng(seed)
    return [queries.sample_query(db, rng, arrival=i * spacing) for i in range(n)]


def _run(db, qs, **cfg):
    session = graftdb.connect(db, EngineConfig(morsel_size=4096, **cfg))
    futs = session.submit_all(qs)
    session.run()
    return session, [f.result() for f in futs]


def _assert_bit_identical(ra, rb, ctx=""):
    assert set(ra) == set(rb), ctx
    for k in ra:
        np.testing.assert_array_equal(
            np.asarray(ra[k]), np.asarray(rb[k]), err_msg=f"{ctx}/{k}"
        )


# ---------------------------------------------------------------------------
# Parity: smoke-mesh session vs the mesh-less 1×1 oracle, all five modes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ALL_MODES)
def test_smoke_mesh_bit_identical_to_oracle(db, mode):
    _, r1 = _run(db, _workload(db), mode=mode, workers=1, partitions=1)
    sm, r2 = _run(db, _workload(db), mode=mode, mesh="smoke")
    for a, b in zip(r1, r2):
        _assert_bit_identical(a, b, ctx=mode)
    assert sm.engine.n_partitions == 1
    assert sm.stats()["mesh_data_shards"] == 1


def test_smoke_mesh_clock_identical_to_oracle(db):
    s1, _ = _run(db, _workload(db), mode="graft", workers=1, partitions=1)
    s2, _ = _run(db, _workload(db), mode="graft", mesh="smoke")
    # virtual completion clocks are part of the §14 determinism contract
    assert s1.now == s2.now


# ---------------------------------------------------------------------------
# Config layer: mesh spec resolution + partition/worker pinning
# ---------------------------------------------------------------------------


def test_mesh_config_pins_partitions_and_workers():
    cfg = EngineConfig(mesh=4)
    assert cfg.partitions == 4 and cfg.workers == 4
    cfg = EngineConfig(mesh="smoke")
    assert cfg.partitions == 1 and cfg.workers == 1
    # explicit matching values are fine
    cfg = EngineConfig(mesh=2, partitions=2, workers=2)
    assert cfg.partitions == 2


def test_mesh_config_rejects_mismatch_and_bad_specs():
    with pytest.raises(ValueError, match="partitions"):
        EngineConfig(mesh=4, partitions=3)
    with pytest.raises(ValueError, match="workers"):
        EngineConfig(mesh=4, workers=3)
    with pytest.raises(ValueError):
        EngineConfig(mesh="nope")
    with pytest.raises(ValueError):
        EngineConfig(mesh=0)
    with pytest.raises(ValueError):
        EngineConfig(mesh=True)
    with pytest.raises(ValueError, match="clock"):
        EngineConfig(mesh=2, clock="wall")


def test_resolve_mesh_layer():
    assert mesh_data_size("smoke") == 1
    assert mesh_data_size(8) == 8
    mesh = resolve_mesh("smoke")
    assert mesh.shape["data"] == 1
    assert mesh_data_size(mesh) == 1
    with pytest.raises(ValueError):
        resolve_mesh(None)


# ---------------------------------------------------------------------------
# Per-device state views + the real exchange on the session mesh
# ---------------------------------------------------------------------------


def test_mesh_stats_and_device_layout(db):
    # retention='epoch' keeps retired states resident so the per-device
    # layout is inspectable after the trace drains
    sm, _ = _run(db, _workload(db), mode="graft", mesh="smoke", retention="epoch")
    st = sm.mesh_stats()
    assert st["data_shards"] == 1
    assert len(st["devices"]) == 1
    assert st["mesh_exchange_rows"] == 0  # single shard: no exchange modeled
    assert st["bucket_overflow_rows"] == 0
    layouts = st["states"]
    assert layouts, "graft run must leave shared build state behind"
    for lay in layouts:
        assert lay["n_shards"] == 1
        assert len(lay["entries_by_device"]) == 1
        assert sum(lay["entries_by_device"]) > 0
        assert len(lay["bytes_by_device"]) == 1
        # replicated control plane: every extent frontier committed fully
        for done, total in lay["extent_frontiers"].values():
            assert done == total


def test_state_shard_views_partition_everything(db):
    sm, _ = _run(db, _workload(db), mode="graft", mesh="smoke", retention="epoch")
    states = [s for sts in sm.engine.state_index.values() for s in sts]
    states += [
        s
        for s in sm.engine.lifecycle.retired.values()
        if hasattr(s, "shard_entry_counts")
    ]
    assert states
    for st_ in states:
        counts = st_.shard_entry_counts(4)
        assert counts.sum() == len(st_.keycode.data)
        fr = st_.device_frontiers()
        assert set(fr) == set(st_.extents)
        for eid, (done, total) in fr.items():
            assert (done, total) == st_.extent_partition_frontier(eid)


def test_validate_mesh_plane_round_trips(db):
    sm, _ = _run(db, _workload(db), mode="graft", mesh="smoke")
    rec = sm.validate_mesh_plane(sample_rows=512)
    assert rec["data_shards"] == 1
    assert rec["rows"] > 0
    assert rec["rows_lost"] == 0
    assert rec["rows_placed"] == rec["rows"]
    assert rec["routing_matches_state_shards"] is True


def test_mesh_explain_accounting_per_shard(db):
    """EXPLAIN GRAFT accounting is preserved exactly per shard:
    represented + residual + unattached == demand on every device."""
    qs = _workload(db, n=4)
    session = graftdb.connect(db, EngineConfig(mode="graft", mesh="smoke"))
    futs = session.submit_all(qs[:3])
    session.run()
    ex = session.explain_graft(qs[3])
    for pt in ex.partition_totals():
        assert (
            pt["represented"] + pt["residual"] + pt["unattached"] == pt["demand"]
        )
    assert (
        ex.represented_rows + ex.residual_rows + ex.unattached_rows
        == ex.total_demand_rows
    )


# ---------------------------------------------------------------------------
# db-plane dry-run record on the smoke mesh (satellite: promoted function)
# ---------------------------------------------------------------------------


def test_db_plane_record_validates_on_smoke_mesh():
    from repro.launch.db_plane import db_plane_record, validate_db_plane_record

    rec = db_plane_record(make_smoke_mesh(), rows=1 << 12, chain_rows=512)
    validate_db_plane_record(rec)  # raises on any structural problem
    assert rec["status"] == "ok"
    assert rec["data_shards"] == 1
    assert rec["chain"]["parity"] is True
    assert rec["chain"]["matched_rows"] > 0
    assert rec["hlo_stats"]["mem_bytes_per_device"] > 0


def test_db_plane_validator_rejects_broken_records():
    from repro.launch.db_plane import db_plane_record, validate_db_plane_record

    rec = db_plane_record(make_smoke_mesh(), rows=1 << 12, chain_rows=512)
    bad = dict(rec)
    bad["status"] = "fail"
    with pytest.raises(ValueError, match="failed"):
        validate_db_plane_record(bad)
    bad = dict(rec)
    del bad["hlo_stats"]
    with pytest.raises(ValueError, match="missing"):
        validate_db_plane_record(bad)
    bad = dict(rec)
    bad["chain"] = {"parity": False}
    with pytest.raises(ValueError, match="bit-identical"):
        validate_db_plane_record(bad)


def test_sharded_chain_launch_parity_on_smoke_mesh():
    """graft_chain_stage_sharded wraps the identical stage in shard_map;
    on the smoke mesh every output is bit-identical to the plain launch."""
    from repro.launch.db_plane import _chain_parity

    block = _chain_parity(make_smoke_mesh(), rows=1024)
    assert block["parity"] is True
    assert block["matched_rows"] > 0
