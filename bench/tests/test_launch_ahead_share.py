from bench.harness.spec import resolve


def _metric(name, cell):
    for m, mod in resolve(cell)["metrics"][1]:
        if m["name"] == name:
            return mod
    raise KeyError(name)


def _rec(ahead=None, launches=None):
    rec = {"completed": 10, "seconds": 10.0, "latencies": [1.0] * 10, "setup_s": 3.0,
           "counters": {"scan_rows": 10000, "overlapped_launches": 5},
           "backend": {"kernel_probes": 3, "fallback_probes": 1}}
    if ahead is not None:
        rec["counters"]["ahead_launches"] = ahead
    if launches is not None:
        rec["backend"]["device_launches"] = launches
    return rec


def test_launch_ahead_share_is_reported_in_both_cells():
    for cell in ("graft_c8", "isolated_c8"):
        assert _metric("launch_ahead_share", cell) is not None


def test_launch_ahead_share_reads_ahead_over_launches():
    share = _metric("launch_ahead_share", "graft_c8")
    assert share.read(_rec(30, 40)) == 75.0
    assert share.read(_rec(0, 40)) == 0.0
    assert share.read(_rec(40, 40)) == 100.0


def test_launch_ahead_share_without_launches_or_counter_reads_nothing():
    share = _metric("launch_ahead_share", "graft_c8")
    # no launch in the window
    assert share.read(_rec(0, 0)) is None
    # a program without the counter (the parent of launch-ahead)
    assert share.read(_rec(launches=40)) is None
    assert share.read(_rec()) is None
