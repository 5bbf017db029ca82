"""Benchmark orchestrator: one module per paper figure.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run fig7 fig9  # subset

Prints CSV rows (bench,<fields...>) and writes JSON to benchmarks/results/.
"""

from __future__ import annotations

import sys
import time

BENCHES = ["fig6", "fig7", "fig9", "fig10", "fig11", "fig12", "serve_fold"]


def main() -> None:
    which = sys.argv[1:] or BENCHES
    t0 = time.time()
    for name in which:
        print(f"\n=== {name} ===", flush=True)
        t = time.time()
        if name == "fig6":
            from . import fig6_arrival_sweep as m

            m.run()
        elif name == "fig7":
            from . import fig7_closed_loop as m

            m.run()
        elif name == "fig9":
            from . import fig9_mechanism as m

            m.run()
        elif name == "fig10":
            from . import fig10_open_loop as m

            m.run()
        elif name == "fig11":
            from . import fig11_skew as m

            m.run()
        elif name == "fig12":
            from . import fig12_scale as m

            m.run()
        elif name == "serve_fold":
            from . import serve_fold as m

            m.run()
        else:
            print(f"unknown bench {name}")
        print(f"# {name} took {time.time()-t:.1f}s", flush=True)
    print(f"\n# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
