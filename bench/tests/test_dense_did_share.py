import pytest

from bench.harness.spec import resolve


def _metric(name, cell):
    for m, mod in resolve(cell)["metrics"][1]:
        if m["name"] == name:
            return mod
    raise KeyError(name)


def _rec(dense=None, hashed=None):
    rec = {"completed": 10, "seconds": 10.0, "latencies": [1.0] * 10, "setup_s": 3.0,
           "counters": {"scan_rows": 10000, "index_rebuilds": 4},
           "backend": {"kernel_probes": 3, "fallback_probes": 1}}
    if dense is not None:
        rec["counters"]["dense_did_rows"] = dense
    if hashed is not None:
        rec["counters"]["hashed_did_rows"] = hashed
    return rec


@pytest.mark.parametrize("cell", ["graft_c8", "isolated_c8"])
def test_dense_did_share_is_reported(cell):
    assert _metric("dense_did_share", cell) is not None


@pytest.mark.parametrize(
    "dense, hashed, share",
    [(90, 10, 90.0), (0, 40, 0.0), (40, 0, 100.0), (1, 3, 25.0)],
)
def test_dense_did_share_reads_dense_over_all_build_rows(dense, hashed, share):
    assert _metric("dense_did_share", "graft_c8").read(_rec(dense, hashed)) == share


@pytest.mark.parametrize(
    "dense, hashed",
    [(0, 0), (None, None), (None, 40), (40, None)],
    ids=["no-build-rows", "no-counters", "no-dense-counter", "no-hashed-counter"],
)
def test_dense_did_share_without_rows_or_counters_reads_nothing(dense, hashed):
    assert _metric("dense_did_share", "graft_c8").read(_rec(dense, hashed)) is None
