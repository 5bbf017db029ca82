import numpy as np
import pytest

from bench.harness.spec import resolve


def _metric(name):
    for trace in (0, 1):
        for m, mod in resolve("isolated_c8")["metrics"][trace]:
            if m["name"] == name:
                return mod
    raise KeyError(name)


def _rec(lat, seconds=10.0):
    return {"completed": len(lat), "seconds": seconds, "latencies": list(lat), "setup_s": 3.0,
            "counters": {"scan_rows": 1000 * len(lat)},
            "backend": {"kernel_probes": 3, "fallback_probes": 1},
            "window_compiles": [{"seconds": 0.5}, {"seconds": 0.25}],
            "trace": {"window_s": 10.0, "busy_s": 0.5,
                      "program_s": {"graft_chain": 0.02, "graft_probe": 0.01}}}


def test_rates_and_percentiles_cover_every_completion():
    # two chunks with different medians: a median of chunk medians would differ
    lat = [1.0] * 45 + [10.0] * 10 + [2.0] * 45
    rec = _rec(lat, seconds=20.0)
    assert _metric("qps").read(rec) == 100 / 20.0
    assert _metric("p50_s").read(rec) == pytest.approx(float(np.percentile(lat, 50)))
    assert _metric("p50_s").read(rec) == 2.0
    chunks = [np.median(lat[i:i + 25]) for i in range(0, 100, 25)]
    assert _metric("p50_s").read(rec) != np.median(chunks)


def test_layer_metrics_read_the_window_record():
    rec = _rec([1.0] * 10)
    assert _metric("scan_rows_per_query").read(rec) == 1000
    assert _metric("device_probe_share").read(rec) == 75.0
    assert _metric("window_compiles").read(rec) == 2
    assert _metric("window_compile_s").read(rec) == 0.75
    assert _metric("device_idle_share").read(rec) == pytest.approx(95.0)
    assert _metric("kernel_ms_per_query").read(rec) == pytest.approx(3.0)


def test_readers_with_nothing_to_read_return_nothing():
    rec = _rec([])
    rec["trace"] = None
    rec["backend"] = {}
    for name in ("p50_s", "scan_rows_per_query", "device_probe_share",
                 "device_idle_share", "kernel_ms_per_query"):
        assert _metric(name).read(rec) is None, name
