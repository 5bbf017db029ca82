"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names its configuration and its traffic mix.
The configuration is ``bench/configs/<config>.json``; it names the modules
beside it that make its data (``data``), build the engine's plans
(``plans``) and compute its plain reference (``reference``). The mix is ``bench/traffic/<traffic>.json``; its ``kind``
names the generator ``bench/traffic/<kind>.py`` and its ``params`` the
parameter sampler there. Each metric is read by ``bench/metrics/<name>.py``.
Adding any of these is adding files and entries, never editing code.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_module(directory: Path, name: str) -> ModuleType:
    """Import ``<directory>/<name>.py`` under a key of its own. ``sys.path``
    is left alone, so a file added beside it cannot shadow another import."""
    path = directory / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module {name!r} at {path}")
    key = f"bench_{directory.name}_{name}_{abs(hash(str(path)))}".replace(".", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict:
    return _json(root / "BENCHMARK.json")


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(cell: str, root: Path = ROOT) -> Dict:
    """Everything one cell needs: its entry, configuration, mix, the modules
    they name, and the metrics it reports with and without a trace."""
    bench = benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json; have {sorted(cells)}")
    entry = cells[cell]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[entry["config"]]["file"])
    bench_dir = root / "bench"
    mix = _json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    e2e: List[Dict] = [m for m in bench["end_to_end"] if _applies(m, cell)]
    layer: List[Dict] = [m for m in bench["per_layer"] if _applies(m, cell)]
    return {
        "entry": entry,
        "config": config,
        "mix": mix,
        "data": load_module(bench_dir / "configs", config["data"]),
        "plans": load_module(bench_dir / "configs", config["plans"]),
        "reference": load_module(bench_dir / "configs", config["reference"]),
        "kind": load_module(bench_dir / "traffic", mix["kind"]),
        "params": load_module(bench_dir / "traffic", mix["params"]),
        "metrics": {
            0: [(m, load_module(bench_dir / "metrics", m["name"])) for m in e2e],
            1: [(m, load_module(bench_dir / "metrics", m["name"])) for m in layer],
        },
    }
