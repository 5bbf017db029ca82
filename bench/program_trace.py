"""One traced run of a cell, reduced by the program's own spans.

    python3 bench/program_trace.py --workload isolated_c8 --seed 7 --seconds 51

Runs the cell as ``bench/run.py --trace 1`` does, keeps the trace, and
reduces it twice: with ``harness/trace.py`` (the benchmark's breakdown)
and with ``harness/program_spans.py`` (each idle gap put down to the
innermost ``graftdb.*`` span, and each span's self time). The last line of
standard output is one JSON object: completions, ``p50_s``, the per-layer
metrics of the result line that ``bench/run.py --trace 1`` prints, the two idle-gap
breakdowns, self seconds by span and by layer, per completion in ms, the
shares of idle time left to unlabelled engine work and to ``graftdb.unit``
itself, and the share of the window that the layers and the benchmark's
own spans (``bench.*``, ``backend.*``) account for. Needs the chip.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench.harness import cell as cell_mod  # noqa: E402
from bench.harness import program_spans, trace  # noqa: E402
from bench.harness.spec import resolve  # noqa: E402
from bench.run import enable_compile_cache, result_line  # noqa: E402


def summary(rec, reduced, metrics) -> dict:
    n = rec["completed"]
    window = reduced["window_s"]
    idle = window - reduced["busy_s"]
    gaps = dict(reduced["idle_gaps"])
    self_s = reduced["program_self_s"]
    bench_s = sum(v for k, v in self_s.items()
                  if not k.startswith(program_spans.PROGRAM) and k != trace.COMPILE)
    return {
        "seed": rec["seed"],
        "completed": n,
        "correct": rec["correct"],
        "p50_s": float(np.percentile(rec["latencies"], 50)) if n else None,
        "metrics": result_line(rec, metrics, {})["metrics"],
        "window_s": window,
        "busy_s": reduced["busy_s"],
        "idle_gaps_benchmark": rec["trace"]["idle_gaps"],
        "idle_gaps_program": reduced["idle_gaps"],
        "engine_share_of_idle": 100.0 * gaps.get(trace.ENGINE, 0.0) / idle,
        "unit_share_of_idle": 100.0 * gaps.get("graftdb.unit", 0.0) / idle,
        "self_s": dict(sorted(self_s.items(), key=lambda kv: -kv[1])),
        "layer_ms_per_query": {k: 1000.0 * v / n for k, v in reduced["layers_s"].items()},
        "bench_self_ms_per_query": 1000.0 * bench_s / n,
        "compile_ms_per_query": 1000.0 * self_s.get(trace.COMPILE, 0.0) / n,
        "window_ms_per_query": 1000.0 * window / n,
        "coverage": (sum(reduced["layers_s"].values()) + bench_s) / window,
        "backend": {k: rec["backend"].get(k) for k in
                    ("h2d_bytes", "d2h_bytes", "device_rows", "device_padded_rows")},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    keep = cell_mod.shutil.rmtree
    cell_mod.shutil.rmtree = lambda *a, **k: None  # keep the trace for the second reduction
    try:
        rec = cell_mod.run(args.workload, args.seed, args.seconds, True, T_PROCESS)
    finally:
        cell_mod.shutil.rmtree = keep
    trace_dir = cell_mod.OUT / f"trace-{args.workload}-{args.seed}"
    reduced = program_spans.reduce(
        program_spans.load(trace.latest_xplane(str(trace_dir))), args.seconds)
    shutil.rmtree(trace_dir, ignore_errors=True)
    line = summary(rec, reduced, resolve(args.workload)["metrics"][1])
    out = cell_mod.OUT / f"program-trace-{args.workload}-{args.seed}.json"
    out.write_text(json.dumps({"summary": line, "reduced": reduced}, indent=1))
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
