from pathlib import Path

import pytest

from bench.harness import trace

S = 1_000_000_000  # ns per second
PROGRAMS = {"graft_chain": ("graft_chain",), "graft_probe": ("hash_probe_lens",)}


def _synthetic():
    host = [
        (0, 20 * S, "bench.window"),
        (3 * S, 4 * S, "bench.submit"),
        (7 * S, 9 * S, "backend.probe_chain"),
        (8 * S, 8 * S + 10, "TpuCompiler::Compile"),
    ]
    programs = [
        (S // 2, 3 * S // 2, "graft_chain"),
        (6 * S // 5, 2 * S, "hash_probe_lens64"),
        (11 * S // 2, 6 * S, "convert_element_type"),
        (11 * S, 12 * S, "graft_chain"),  # after the window
    ]
    ops = [
        (S // 2, S, "%fusion.1"),
        (S, 3 * S // 2, "%while"),
        (6 * S // 5, 9 * S // 5, "%fusion.1"),
        (11 * S // 2, 28 * S // 5, "%copy.3"),
        (11 * S, 12 * S, "%fusion.1"),
    ]
    return {
        "host": host,
        "devices": {
            "/device:TPU:0": {"ops": ops, "programs": programs},
            "/device:TPU:1": {"ops": [(15 * S, 16 * S, "x")], "programs": []},
        },
    }


def test_reduce_by_hand():
    r = trace.reduce(_synthetic(), PROGRAMS, seconds=10.0)
    assert r["window_s"] == 10.0
    assert r["n_devices"] == 1  # TPU:1 ran nothing inside the window
    assert r["busy_s"] == pytest.approx(1.3 + 0.1)  # [0.5, 1.8] and [5.5, 5.6]
    assert r["program_s"] == pytest.approx({"graft_chain": 1.0, "graft_probe": 0.8})
    # each op under the program running when it starts
    assert dict(r["device_ops"]) == pytest.approx({
        "graft_chain/%fusion.1": 0.5, "graft_chain/%while": 0.5,
        "hash_probe_lens64/%fusion.1": 0.6, "convert_element_type/%copy.3": 0.1})
    # gaps: [0, 0.5] engine; [1.8, 5.5] mid 3.65 in bench.submit; [5.6, 10] mid 7.8 in
    # backend.probe_chain (the 10 ns compile event inside it does not cover 7.8 s)
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"backend.probe_chain": 4.4, "bench.submit": 3.7, trace.ENGINE: 0.5})


def test_a_compile_event_over_a_gap_labels_it_compile():
    t = _synthetic()
    t["host"].append((7 * S, 9 * S, "backend_compile_and_load"))
    gaps = dict(trace.reduce(t, PROGRAMS, seconds=10.0)["idle_gaps"])
    assert gaps[trace.COMPILE] == pytest.approx(4.4)


def test_names_from_the_tpu_trace():
    assert trace.program_name("jit_graft_chain(7348603012640228264)") == "graft_chain"
    assert trace.op_name("%fusion.12 = u32[32768]{0:T(1024)} fusion(u32[256] %a)") == "%fusion.12"


def test_union_merges_overlaps():
    assert trace.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_a_trace_without_the_window_or_device_is_refused():
    t = _synthetic()
    with pytest.raises(ValueError):
        trace.reduce({"host": t["host"][1:], "devices": t["devices"]}, PROGRAMS, 10.0)
    with pytest.raises(ValueError):
        trace.reduce({"host": t["host"], "devices": {}}, PROGRAMS, 10.0)


def test_a_trace_recorded_on_the_chip(tmp_path):
    """``data/graft_c8_tiny.xplane.pb.gz``: a two-second traced window of
    ``graft_c8`` at SF 0.01 on one TPU v5 lite (``record_trace.py``). The
    numbers were checked by hand: the three ``jit_graft_chain`` executions
    inside the window last 3,453,346 + 1,763,952 + 61,473 ns, and the ops'
    union on a 10 ns grid (ends rounded down) gives 5,278,070 ns busy."""
    import gzip

    from bench.harness.cell import PROGRAMS

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.open(Path(__file__).parent / "data" / "graft_c8_tiny.xplane.pb.gz").read())
    t = trace.load(str(path))
    assert list(t["devices"]) == ["/device:TPU:0"]
    dev = t["devices"]["/device:TPU:0"]
    assert (len(dev["ops"]), len(dev["programs"])) == (985, 35)
    r = trace.reduce(t, PROGRAMS, seconds=2.0)
    assert r["window_s"] == 2.0 and r["n_devices"] == 1
    assert r["busy_s"] == pytest.approx(0.005278019, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.00527807, abs=1e-7)  # grid rounding
    assert r["program_s"]["graft_chain"] == pytest.approx(0.005278771, abs=1e-9)
    assert r["program_s"]["graft_probe"] == 0.0
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(2.0, abs=1e-6)
    assert gaps == pytest.approx({"backend.probe_chain": 1.069947653, trace.COMPILE: 0.894836342,
                                  trace.ENGINE: 0.029937986}, abs=1e-9)
    assert r["device_ops"][0][0].startswith("graft_chain/%")
