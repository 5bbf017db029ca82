import pytest

from bench.harness import program_spans as ps
from bench.harness import trace

S = 1_000_000_000  # ns per second


def _synthetic():
    """Two threads. The engine's: a unit [1, 9] s holding a filter [2.5, 3]
    and a probe chain [4, 8], which holds its device wait [5, 7]; an
    admission [11, 12] under the benchmark's submit [10, 13]. The other
    thread: one aggregate [2, 6]. Device busy [0, 1], [3, 4], [8, 10]."""
    engine = [
        (1 * S, 9 * S, "graftdb.unit"),
        (5 * S // 2, 3 * S, "graftdb.filter"),
        (4 * S, 8 * S, "graftdb.backend.probe_chain"),
        (5 * S, 7 * S, "graftdb.device_wait"),
        (10 * S, 13 * S, "bench.submit"),
        (11 * S, 12 * S, "graftdb.admit"),
    ]
    other = [(2 * S, 6 * S, "graftdb.aggregate")]
    window = (0, 20 * S, "bench.window")
    ops = [(0, 1 * S, "%a"), (3 * S, 4 * S, "%b"), (8 * S, 10 * S, "%c")]
    return {
        "host": [window, (10 * S, 13 * S, "bench.submit")],
        "threads": {"/host:CPU#0": [window] + engine, "/host:CPU#1": other},
        "devices": {"/device:TPU:0": {"ops": ops, "programs": []}},
    }


def test_self_time_by_hand_on_two_threads():
    t = _synthetic()
    self_s = ps.self_times(t["threads"], 0, 20 * S)
    assert self_s["graftdb.unit"] == pytest.approx(8 - 0.5 - 4)  # less filter, chain
    assert self_s["graftdb.filter"] == pytest.approx(0.5)
    assert self_s["graftdb.backend.probe_chain"] == pytest.approx(4 - 2)
    assert self_s["graftdb.device_wait"] == pytest.approx(2)
    assert self_s["bench.submit"] == pytest.approx(3 - 1)
    assert self_s["graftdb.aggregate"] == pytest.approx(4)  # its own thread: no children
    # clipped to a window that cuts the unit and the chain
    cut = ps.self_times(t["threads"], 6 * S, 20 * S)
    assert cut["graftdb.unit"] == pytest.approx(3 - 2)  # [6, 9] less the chain's [6, 8]
    assert cut["graftdb.device_wait"] == pytest.approx(1)
    assert "graftdb.filter" not in cut


def test_idle_gaps_go_to_the_innermost_program_span():
    r = ps.reduce(_synthetic(), seconds=20.0)
    assert r["busy_s"] == pytest.approx(4.0)
    # gaps: [1, 3] mid 2 -> the aggregate started at 2 s on the other thread,
    # after the unit (1 s); [4, 8] mid 6 -> device wait; [10, 20] mid 15 -> none
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"graftdb.aggregate": 2.0, "graftdb.device_wait": 4.0, trace.ENGINE: 10.0})
    assert r["layers_s"]["backend_host"] == pytest.approx(2.0)
    assert r["layers_s"]["device_wait"] == pytest.approx(2.0)
    assert r["layers_s"]["admit"] == pytest.approx(1.0)
    assert r["layers_s"]["runtime_host"] == pytest.approx(3.5 + 0.5)
    assert r["layers_s"]["agg_host"] == pytest.approx(4.0)


def test_a_parent_with_a_thousand_children_labels_a_gap_in_it():
    """The parent started long before the instant, with 1,000 closed
    children between: the sweep still finds it."""
    parent = (0, 10_000, "graftdb.unit")
    kids = [(1 + 4 * i, 3 + 4 * i, "graftdb.filter") for i in range(1000)]
    threads = {"t": [parent] + kids}
    # 2 is inside the first child; 4 and 9_000 fall between or after children
    assert ps.innermost(threads, [9_000, 2, 4, 20_000], "none") == [
        "graftdb.unit", "graftdb.filter", "graftdb.unit", "none"]


def test_a_span_ending_at_the_instant_still_covers_it():
    threads = {"t": [(0, 10, "graftdb.unit"), (5, 8, "graftdb.scan")]}
    assert ps.innermost(threads, [8, 10, 11], "none") == ["graftdb.scan", "graftdb.unit", "none"]


def test_layers_sum_backend_calls_by_prefix():
    layers = ps.layer_seconds({"graftdb.backend.probe": 1.0, "graftdb.backend.sync_mirrors": 0.5,
                               "graftdb.h2d": 0.25, "graftdb.complete": 2.0, "bench.submit": 9.0})
    assert layers["backend_host"] == 1.75 and layers["agg_host"] == 2.0
    assert sum(layers.values()) == 3.75


def test_a_trace_without_the_window_is_refused():
    t = _synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(ValueError):
        ps.reduce(t, 10.0)
