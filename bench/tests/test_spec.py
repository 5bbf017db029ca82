import json

from bench.harness.spec import BENCH, ROOT, benchmark, resolve
from benchutil import copy_benchmark


def test_every_cell_resolves_to_its_files():
    bench = benchmark()
    for w in bench["workloads"]:
        spec = resolve(w["name"])
        assert spec["config"]["name"] == w["config"]
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        for trace in (0, 1):
            for m, mod in spec["metrics"][trace]:
                assert callable(mod.read), m["name"]
        names = {m["name"] for m, _ in spec["metrics"][0]}
        assert {"setup_s", "qps"} <= names
        assert spec["metrics"][1], "every cell reports a per-layer metric"
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in c["reduced"]:
            assert key in cfg and key in cfg["reduced"]


def test_an_added_cell_is_found_without_code(tmp_path):
    root = copy_benchmark(tmp_path)
    mix = json.loads((root / "bench/traffic/tpch_streams_c8.json").read_text())
    mix["clients"] = 2
    (root / "bench/traffic/tpch_streams_c2.json").write_text(json.dumps(mix))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "isolated_c2", "config": "tpch_sf0.3_isolated",
                               "traffic": "tpch_streams_c2", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = resolve("isolated_c2", root)
    assert spec["mix"]["clients"] == 2
    assert spec["config"]["engine"]["mode"] == "isolated"
    assert {m["name"] for m, _ in spec["metrics"][0]} == {"qps", "p50_s", "setup_s"}
    # per-layer metrics that list their cells stay off the new one
    assert not spec["metrics"][1]


def test_loading_a_cell_leaves_the_import_path_alone(tmp_path):
    import sys

    before = list(sys.path)
    root = copy_benchmark(tmp_path)
    resolve("isolated_c8", root)
    assert sys.path == before
