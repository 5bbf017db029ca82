import pytest

from bench.harness.spec import resolve


def _metric(name):
    for m, mod in resolve("isolated_c8")["metrics"][1]:
        if m["name"] == name:
            return mod
    raise KeyError(name)


def _rec(completed, backend):
    return {"completed": completed, "backend": backend}


def test_transfer_and_padding_counters_per_query():
    rec = _rec(4, {"h2d_bytes": 1000, "d2h_bytes": 600, "device_rows": 96,
                   "device_padded_rows": 128, "kernel_probes": 3})
    assert _metric("h2d_bytes_per_query").read(rec) == 250.0
    assert _metric("d2h_bytes_per_query").read(rec) == 150.0
    assert _metric("device_pad_share").read(rec) == pytest.approx(25.0)


def test_counters_with_nothing_to_read_return_nothing():
    # a program without the counters, a window with no completion or launch
    for rec in (_rec(4, {"kernel_probes": 3}),
                _rec(0, {"h2d_bytes": 10, "d2h_bytes": 10, "device_rows": 0,
                         "device_padded_rows": 0})):
        for name in ("h2d_bytes_per_query", "d2h_bytes_per_query", "device_pad_share"):
            assert _metric(name).read(rec) is None, (name, rec)
