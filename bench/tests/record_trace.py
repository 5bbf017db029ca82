"""Records the small chip trace that ``test_trace.py`` checks by hand.

    python bench/tests/record_trace.py   # on the chip

One traced ``graft_c8`` run at ``TINY_SF`` with a two-second window, traced
as the benchmark traces. Its ``.xplane.pb`` is written gzipped to
``bench_runs/``, whence it is copied to ``bench/tests/data/``. Run
it twice in one process's checkout so that the kept trace's window finds
its programs in the persistent cache.
"""

import gzip
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1]), str(HERE)]

from bench.harness import cell, trace  # noqa: E402
from bench.run import enable_compile_cache  # noqa: E402
from benchutil import make_tiny_root  # noqa: E402

SEED = 20261016


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    root = make_tiny_root(Path(tempfile.mkdtemp()))
    keep = cell.shutil.rmtree
    cell.shutil.rmtree = lambda *a, **k: None  # keep the trace this once
    try:
        rec = cell.run("graft_c8", SEED, 2.0, True, time.perf_counter(), root=root)
    finally:
        cell.shutil.rmtree = keep
    src = trace.latest_xplane(str(cell.OUT / f"trace-graft_c8-{SEED}"))
    with open(src, "rb") as f, gzip.open(cell.OUT / "graft_c8_tiny.xplane.pb.gz", "wb") as g:
        shutil.copyfileobj(f, g)
    print(rec["trace"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
