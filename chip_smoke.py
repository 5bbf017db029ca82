"""Smoke run of GraftDB's main path on the TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the four-chip mesh path only

One chip: load TPC-H at SF 1 (seed 7), open a graft-mode session on the
device data plane (``backend="pallas"``), submit one query per template of
``queries.DEFAULT_TEMPLATES`` 1 ms apart so later arrivals graft onto
running state, run them, and check every result against the reference
executor. Fails unless the fused stage chain launched and the state
mirrors lived on the TPU.

Four chips: the same mix on an ``EngineConfig(mesh=4)`` session, checked
against the reference executor and against a single-host ``workers=4,
partitions=4`` session, plus one real all_to_all exchange
(``validate_mesh_plane``) on the live states' keys that must lose no row.

Exits nonzero, before printing a result, unless JAX's first device is a
TPU. The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Everything runs in this one process, which holds the chips.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SCALE_FACTOR = 1.0
SEED = 7
STAGGER_S = 0.001  # virtual seconds between arrivals
RTOL = 1e-9


class SmokeFailure(AssertionError):
    """A phase of the smoke run produced a wrong or missing result."""


def make_mix(db, seed: int = SEED):
    """One query per default template, parameters drawn from a seeded rng,
    arrivals staggered by ``STAGGER_S``."""
    from repro.relational import queries

    rng = np.random.default_rng(seed)
    return [
        queries.sample_query(db, rng, arrival=i * STAGGER_S, templates=[t])
        for i, t in enumerate(queries.DEFAULT_TEMPLATES)
    ]


def _sorted_cols(res):
    return {k: np.sort(np.asarray(v, dtype=np.float64)) for k, v in res.items()}


def same_result(got, want, exact: bool = False) -> bool:
    """Sorted-column comparison: allclose at ``RTOL``, or bit-equality."""
    g, w = _sorted_cols(got), _sorted_cols(want)
    if set(g) != set(w):
        return False
    for k in w:
        if g[k].shape != w[k].shape:
            return False
        if exact:
            if not np.array_equal(g[k], w[k]):
                return False
        elif not np.allclose(g[k], w[k], rtol=RTOL):
            return False
    return True


def query_and_compare(db, config, seed: int = SEED, on_first=None):
    """Run the query mix on one session and check it against refexec.

    Returns a report: the session, per-template results, wall seconds to
    the first and the last result, and the backend's counters. Raises
    ``SmokeFailure`` on a query that did not finish or differs from the
    reference executor. ``on_first(session)`` runs at the first
    completion, while the other queries' shared state is still live."""
    import graftdb
    from repro.relational import refexec

    mix = make_mix(db, seed)
    session = graftdb.connect(db, config)
    futures = session.submit_all(mix)
    done_at = []
    t0 = time.perf_counter()

    def on_complete(fut):
        done_at.append(time.perf_counter() - t0)
        if len(done_at) == 1 and on_first is not None:
            on_first(session)
        return None

    session.run(on_complete=on_complete)
    results = {}
    for q, fut in zip(mix, futures):
        if fut.status != "done":
            raise SmokeFailure(f"{q.template} q{q.qid} ended {fut.status!r}")
        got = fut.result()
        if not same_result(got, refexec.execute(db, q.plan)):
            raise SmokeFailure(f"{q.template} q{q.qid} differs from refexec")
        results[q.template] = got
    return {
        "session": session,
        "futures": futures,
        "results": results,
        "first_result_s": done_at[0],
        "last_result_s": done_at[-1],
        "backend_stats": session.backend.stats(),
    }


def grafted_rows(exp) -> int:
    """Demand rows a query took from shared state at admission: observed
    through its lens (represented) or added to it (residual)."""
    return exp.represented_rows + exp.residual_rows


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _CompileLog:
    """Counts XLA compilations (persistent-cache reads included) and
    persistent-cache hits through JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on_duration)
        self._jax.monitoring.unregister_event_listener(self._on_event)

    def line(self) -> str:
        return (
            f"compilations: {self.n} in {self.seconds:.3f} s "
            f"({self.cache_hits} persistent-cache hits)"
        )


def _probe_share(stats) -> float:
    served = stats["kernel_probes"]
    total = served + stats["fallback_probes"]
    return served / total if total else 0.0


def _print_backend(stats, label: str) -> None:
    print(f"[{label}] backend counters: {json.dumps(stats, sort_keys=True)}")
    print(
        f"[{label}] probe calls served on device: "
        f"{stats['kernel_probes']} of {stats['kernel_probes'] + stats['fallback_probes']} "
        f"({_probe_share(stats):.4f})"
    )


def one_chip(db) -> None:
    from graftdb import EngineConfig

    seen = {}

    def on_first(session):
        seen["mirrors"] = {d.platform for d in session.backend.mirror_devices()}

    config = EngineConfig(
        mode="graft", backend="pallas", workers=1, capture_explain=True
    )
    rep = query_and_compare(db, config, on_first=on_first)
    session, stats = rep["session"], rep["backend_stats"]
    print(f"[1 chip] all {len(rep['results'])} results equal refexec (rtol {RTOL})")
    print(
        f"[1 chip] first result after {rep['first_result_s']:.3f} s, "
        f"last after {rep['last_result_s']:.3f} s (host clock)"
    )
    _print_backend(stats, "1 chip")
    print(f"[1 chip] state mirrors on platforms: {sorted(seen.get('mirrors', ()))}")
    print(
        "[1 chip] chain programs ran on: "
        f"{sorted(str(d) for d in session.backend.chain_devices)}"
    )
    grafted = [f for f in rep["futures"] if grafted_rows(f.explain()) > 0]
    if grafted:
        exp = grafted[0].explain()
        print(
            f"[1 chip] EXPLAIN GRAFT q{exp.qid} ({exp.template}): "
            f"demand {exp.total_demand_rows} = represented {exp.represented_rows} "
            f"+ residual {exp.residual_rows} + unattached {exp.unattached_rows}"
        )
    else:
        print("[1 chip] EXPLAIN GRAFT: no query attached to shared state")
    _require(stats["chain_launches"] > 0, "the fused stage chain never launched")
    _require(seen.get("mirrors") == {"tpu"}, f"state mirrors on {seen.get('mirrors')}")
    session.close()


def four_chips(db) -> None:
    from graftdb import EngineConfig

    checks = {}

    def on_first(session):
        checks["plane"] = session.validate_mesh_plane()

    mesh_cfg = EngineConfig(mode="graft", backend="pallas", mesh=4)
    mesh_rep = query_and_compare(db, mesh_cfg, on_first=on_first)
    print(f"[4 chips] mesh session: all {len(mesh_rep['results'])} results equal refexec")
    host_cfg = EngineConfig(mode="graft", backend="pallas", workers=4, partitions=4)
    host_rep = query_and_compare(db, host_cfg)
    # the mesh charges its modeled exchange on the virtual clock, so the
    # staggered arrivals can graft differently and sum in another order:
    # equal at RTOL, and bit-equal where the grafts coincide
    pairs = [(mesh_rep["results"][t], r) for t, r in host_rep["results"].items()]
    equal = all(same_result(m, h) for m, h in pairs)
    bit_equal = sum(same_result(m, h, exact=True) for m, h in pairs)
    print(
        f"[4 chips] mesh results equal to the workers=4 session (rtol {RTOL}): "
        f"{equal}; bit-equal {bit_equal} of {len(pairs)}"
    )
    plane = checks["plane"]
    print(f"[4 chips] validate_mesh_plane: {json.dumps(plane, sort_keys=True)}")
    mesh_session = mesh_rep["session"]
    print(
        "[4 chips] chain programs ran on: "
        f"{sorted(str(d) for d in mesh_session.backend.chain_devices)}"
    )
    print(
        f"[4 chips] mesh session: first result after {mesh_rep['first_result_s']:.3f} s, "
        f"last after {mesh_rep['last_result_s']:.3f} s (host clock)"
    )
    _print_backend(mesh_rep["backend_stats"], "4 chips")
    _require(equal, "mesh results differ from the workers=4 session")
    _require(plane["rows_lost"] == 0, "the all_to_all exchange lost rows")
    _require(plane["routing_matches_state_shards"], "exchange routing mismatch")
    _require(mesh_rep["backend_stats"]["chain_launches"] > 0, "no chain launch")
    mesh_session.close()
    host_rep["session"].close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0]}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    from repro.relational import tpch

    print(f"device: {devices[0].device_kind} x{len(devices)}")
    print(f"compile cache: {enable_compile_cache()}")
    compiles = _CompileLog()
    t0 = time.perf_counter()
    db = tpch.get_database(SCALE_FACTOR, seed=SEED)
    print(
        f"TPC-H SF {SCALE_FACTOR} seed {SEED}: lineitem {db['lineitem'].nrows} rows, "
        f"{db.nbytes()} bytes, loaded in {time.perf_counter() - t0:.3f} s"
    )
    if args.chips == 1:
        one_chip(db)
    else:
        four_chips(db)
    print(compiles.line())
    compiles.close()
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
