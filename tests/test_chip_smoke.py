"""chip_smoke.py's query-and-compare phase, run on the CPU at SF 0.01.

Only ``main()`` insists on a TPU; the phase it drives is exercised here so
the script cannot rot between chip runs."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from graftdb import EngineConfig
from repro.relational import queries, tpch

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def db():
    return tpch.get_database(0.01, seed=7)


def test_mix_is_one_staggered_query_per_template(smoke, db):
    mix = smoke.make_mix(db)
    assert [q.template for q in mix] == queries.DEFAULT_TEMPLATES
    assert [q.arrival for q in mix] == [i * smoke.STAGGER_S for i in range(len(mix))]
    again = smoke.make_mix(db)
    assert [q.params for q in again] == [q.params for q in mix]


def test_query_and_compare_on_device_plane(smoke, db):
    seen = {}

    def on_first(session):
        seen["mirrors"] = {d.platform for d in session.backend.mirror_devices()}

    rep = smoke.query_and_compare(
        db,
        EngineConfig(mode="graft", backend="pallas", workers=1, capture_explain=True),
        on_first=on_first,
    )
    assert sorted(rep["results"]) == sorted(queries.DEFAULT_TEMPLATES)
    stats = rep["backend_stats"]
    assert stats["chain_launches"] > 0
    assert stats["kernel_probes"] > 0
    assert 0.0 < smoke._probe_share(stats) <= 1.0
    assert 0.0 <= rep["first_result_s"] <= rep["last_result_s"]
    assert seen["mirrors"] == {"cpu"}
    backend = rep["session"].backend
    assert {d.platform for d in backend.chain_devices} == {"cpu"}
    # later arrivals graft onto the state earlier ones are building
    assert any(smoke.grafted_rows(f.explain()) > 0 for f in rep["futures"])
    rep["session"].close()


def test_same_result_rejects_a_changed_value(smoke):
    want = {"a": np.array([1.0, 2.0, 3.0]), "b": np.array([4, 5, 6])}
    assert smoke.same_result({"a": want["a"][::-1], "b": want["b"]}, want)
    assert smoke.same_result(want, want, exact=True)
    off = {"a": np.array([1.0, 2.0, 3.0 + 1e-6]), "b": want["b"]}
    assert not smoke.same_result(off, want)
    assert not smoke.same_result({"a": want["a"]}, want)
    assert not smoke.same_result({"a": want["a"][:2], "b": want["b"]}, want)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""
