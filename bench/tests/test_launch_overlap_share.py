from bench.harness.spec import resolve


def _metric(name):
    for trace in (0, 1):
        for m, mod in resolve("isolated_c8")["metrics"][trace]:
            if m["name"] == name:
                return mod
    raise KeyError(name)


def _rec(overlapped=None, launches=None):
    rec = {"completed": 10, "seconds": 10.0, "latencies": [1.0] * 10, "setup_s": 3.0,
           "counters": {"scan_rows": 10000},
           "backend": {"kernel_probes": 3, "fallback_probes": 1}}
    if overlapped is not None:
        rec["counters"]["overlapped_launches"] = overlapped
    if launches is not None:
        rec["backend"]["device_launches"] = launches
    return rec


def test_launch_overlap_share_reads_overlapped_over_launches():
    share = _metric("launch_overlap_share")
    assert share.read(_rec(30, 40)) == 75.0
    assert share.read(_rec(0, 40)) == 0.0
    assert share.read(_rec(40, 40)) == 100.0


def test_launch_overlap_share_without_launches_reads_nothing():
    share = _metric("launch_overlap_share")
    # no launch in the window
    assert share.read(_rec(0, 0)) is None
    # a program without the counters
    assert share.read(_rec()) is None
