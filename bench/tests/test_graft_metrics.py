"""The readers of ``graft_c8``'s per-layer metrics, on hand-made records."""

import pytest

from bench.harness.spec import resolve


def _metric(name):
    for m, mod in resolve("graft_c8")["metrics"][1]:
        if m["name"] == name:
            return mod
    raise KeyError(name)


def _rec(counters=None, backend=None):
    return {"completed": 10, "seconds": 10.0, "latencies": [1.0] * 10, "setup_s": 3.0,
            "counters": dict(counters or {}), "backend": dict(backend or {})}


def test_graft_attach_share_reads_represented_over_demand():
    share = _metric("graft_attach_share")
    rec = _rec({"graft_demand_rows": 400, "graft_represented_rows": 100})
    assert share.read(rec) == 25.0
    assert share.read(_rec({"graft_demand_rows": 400, "graft_represented_rows": 0})) == 0.0


def test_semi_attach_share_reads_attaches_over_boundaries():
    share = _metric("semi_attach_share")
    assert share.read(_rec({"semi_attaches": 3, "semi_builds": 1})) == 75.0
    assert share.read(_rec({"semi_attaches": 0, "semi_builds": 2})) == 0.0


def test_new_program_keys_reads_the_windows_rise():
    keys = _metric("new_program_keys")
    assert keys.read(_rec(backend={"program_keys": 0})) == 0
    assert keys.read(_rec(backend={"program_keys": 3})) == 3


def test_grant_pad_share_reads_padded_over_carried():
    share = _metric("grant_pad_share")
    assert share.read(_rec(backend={"grant_rows": 128, "grant_padded_rows": 120})) == 93.75


@pytest.mark.parametrize("name", ["graft_attach_share", "semi_attach_share",
                                  "new_program_keys", "grant_pad_share"])
def test_each_reader_reads_nothing_without_its_counters(name):
    # a program without the counters, as the parent commit's
    assert _metric(name).read(_rec()) is None


@pytest.mark.parametrize("name,rec", [
    ("graft_attach_share", _rec({"graft_demand_rows": 0, "graft_represented_rows": 0})),
    ("semi_attach_share", _rec({"semi_attaches": 0, "semi_builds": 0})),
    ("grant_pad_share", _rec(backend={"grant_rows": 0, "grant_padded_rows": 0})),
])
def test_each_share_reads_nothing_over_zero(name, rec):
    assert _metric(name).read(rec) is None
