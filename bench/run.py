"""Runs one benchmark cell once on the chip and prints its result line.

    python3 bench/run.py --workload isolated_c8 --seed 7 --seconds 51 --trace 0

The cell, its configuration, traffic and metrics are looked up by name in
``BENCHMARK.json`` (see ``bench/harness/spec.py``). Exits 2 with no result
unless JAX's devices are TPUs, as many as the cell asks for. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1`` the
per-layer ones), ``device``, with a trace ``breakdown``, and last
``checks``, each number compared beside its limit; the same numbers end
standard error. The full run record goes to ``bench_runs/``.
"""

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench.harness import cell as cell_mod  # noqa: E402
from bench.harness.spec import resolve  # noqa: E402


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, so that
    only a checkout's first run of a cell compiles."""
    import jax

    path = str(BENCH.parent / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(rec, metrics, device) -> dict:
    values = {}
    for m, mod in metrics:
        v = mod.read(rec)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {
        "correct": rec["correct"],
        "attempted": rec["completed"] + rec["failed"],
        "failed": rec["failed"],
        "metrics": values,
        "device": device,
    }
    if rec["trace"] is not None:
        out["breakdown"] = {k: rec["trace"][k] for k in ("device_ops", "idle_gaps")}
    out["checks"] = rec["checks"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = resolve(args.workload)
    chips = spec["entry"]["chips"]

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"needs {chips} TPU chip(s); JAX has {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {enable_compile_cache()}")
    rec = cell_mod.run(args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS)
    cell_mod.OUT.mkdir(parents=True, exist_ok=True)
    out = cell_mod.OUT / f"{args.workload}-{args.seed}-trace{args.trace}.json"
    out.write_text(json.dumps(rec, indent=1, default=str))
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": rec["memory_peak_bytes"],
    }
    if rec["trace"] is not None:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    line = result_line(rec, spec["metrics"][args.trace], device)
    r = rec["readings"]
    print(f"compared {r['compared']} answers ({r['distinct']} distinct) with the reference "
          f"in {r['reference_s']:.3f} s; widest gap at {r['worst_column'] or '-'}")
    print(json.dumps(line))
    for name, c in rec["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
