"""Shared helpers of the harness tests."""

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

#: scale of the CPU runs; the cells run at their configuration's scale
TINY_SF = 0.01


def copy_benchmark(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` (without its tests) under ``dest``."""
    shutil.copytree(ROOT / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    return dest




#: the graft-mode cell, kept out of ``BENCHMARK.json`` for now (PERF.md,
#: Open questions) and added to the tests' copies, which run it on the CPU
GRAFT_CONFIG = {"name": "tpch_sf0.1_graft", "source": "see the file",
                "file": "bench/configs/tpch_sf0.1_graft.json", "reduced": ["scale_factor"],
                "why": "grafting"}
GRAFT_CELL = {"name": "graft_c8", "config": "tpch_sf0.1_graft", "traffic": "tpch_streams_c8",
              "chips": 1, "why": "grafting"}


def make_tiny_root(dest: Path) -> Path:
    """A copy of the benchmark, with the ``graft_c8`` cell, whose
    configurations run at ``TINY_SF`` and whose warm-up is one query a
    client, so a whole run fits a CPU test."""
    root = copy_benchmark(dest)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(GRAFT_CONFIG)
    bench["workloads"].append(GRAFT_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    for f in (root / "bench" / "configs").glob("*.json"):
        c = json.loads(f.read_text())
        c["scale_factor"] = TINY_SF
        f.write_text(json.dumps(c))
    for f in (root / "bench" / "traffic").glob("*.json"):
        m = json.loads(f.read_text())
        m["warmup_per_client"] = 1
        f.write_text(json.dumps(m))
    return root
