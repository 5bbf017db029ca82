"""Readings of the correctness check for the program and for its control.

    python3 bench/control.py --workload isolated_c8 --seeds 11,12,13 --seconds 51

Each seed is a whole run of the cell at its own size and load, with the
window given, all in one process. Its completed queries are then
compared with the float64 reference twice: the program's own answers give
the lower readings that a limit is set above; the reference computed in
float32, the precision below the configuration's, put in the program's
place, gives the control's readings, which the limit must stay below. One
JSON line per seed. The benchmark's own runs never take the control path.
Needs the chip.

The program's own float32 path (``PallasBackend(use_agg_kernel=True)``)
would be the natural control, but its Pallas kernel does not compile for
the TPU v5e (a Mosaic layout error on an ``s32[64512]`` operand).
"""

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH.parent))

from bench.harness import cell as cell_mod  # noqa: E402
from bench.harness.spec import resolve  # noqa: E402
from bench.run import enable_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    ref = resolve(args.workload)["reference"]

    def float32(tables, template, params):
        return ref.answer(tables, template, params, dtype=np.float32)

    for seed in (int(s) for s in args.seeds.split(",")):
        rec = cell_mod.run(args.workload, seed, args.seconds, False, time.perf_counter(),
                           log=lambda *a: None, stand_in=float32)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "completed": rec["completed"],
                          "program": rec["program_readings"], "control": rec["readings"],
                          "control_correct": rec["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
