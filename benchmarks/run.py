"""Benchmark orchestrator: one module per paper figure.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run fig7 fig9  # subset

Prints CSV rows (bench,<fields...>) and writes JSON to benchmarks/results/.
The kernel micro-benchmarks report name,us_per_call,derived.
"""

from __future__ import annotations

import sys
import time


def _kernel_microbench():
    """Per-kernel host-clock timing vs pure-jnp oracle on the default
    platform (off-TPU the Pallas kernels run interpreted; these validate
    dispatch, not device speed)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    rows = [("kernel", "name", "us_per_call", "derived")]

    def timeit(fn, n=3):
        fn()  # compile
        t0 = time.perf_counter()
        for _ in range(n):
            r = fn()
        try:
            r.block_until_ready()
        except AttributeError:
            pass
        return (time.perf_counter() - t0) / n * 1e6

    keys = rng.choice(1 << 20, 65536, replace=False).astype(np.int32)
    vis = np.full(65536, 0xFFFFFFFF, np.uint32)
    tk, tv, _ = ops.build_hash_table(keys, vis)
    pk = jnp.asarray(rng.choice(1 << 21, 65536).astype(np.int32))
    qm = jnp.asarray([1], jnp.uint32)
    us = timeit(lambda: ops.probe(pk, tk, tv, qm))
    rows.append(("kernel", "hash_probe_lens[64k]", round(us, 1), "xla"))
    us = timeit(lambda: ref.hash_probe_lens_ref(pk[:4096], tk, tv, qm))
    rows.append(("kernel", "hash_probe_ref[4k]", round(us, 1), "oracle"))

    codes = jnp.asarray(rng.integers(0, 128, 65536).astype(np.int32))
    vals = jnp.asarray(rng.normal(size=(65536, 8)).astype(np.float32))
    us = timeit(lambda: ops.segmented_sum(codes, vals, 128))
    rows.append(("kernel", "seg_aggregate[64k,8]", round(us, 1), "interpret"))

    q = jnp.asarray(rng.normal(size=(4, 512, 64)).astype(np.float32))
    us = timeit(lambda: ops.attention(q, q, q))
    rows.append(("kernel", "flash_attention[4,512,64]", round(us, 1), "interpret"))

    a = jnp.asarray(rng.uniform(0.9, 0.999, size=(2, 1024, 128)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(2, 1024, 128)).astype(np.float32))
    us = timeit(lambda: ops.linear_recurrence(a, b))
    rows.append(("kernel", "linrec[2,1024,128]", round(us, 1), "interpret"))
    for r in rows:
        print(",".join(str(x) for x in r), flush=True)


BENCHES = ["fig6", "fig7", "fig9", "fig10", "fig11", "fig12", "serve_fold", "kernels"]


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
        elif name == "kernels":
            _kernel_microbench()
        else:
            print(f"unknown bench {name}")
        print(f"# {name} took {time.time()-t:.1f}s", flush=True)
    print(f"\n# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
