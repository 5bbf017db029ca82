"""Member-concurrency sweep: per-morsel data-plane cost vs folded members.

The member-major fused pipeline (DESIGN.md §11) claims the shared data
plane is O(1) in the number of concurrently folded queries. This benchmark
measures that directly, two ways:

* **Per-morsel micro harness** — one pipeline (source filter -> shared
  hash-probe -> per-member aggregate sinks) driven morsel-by-morsel with
  1..32 members at *fixed total data volume*: each of the M members owns a
  disjoint predicate range of width TOTAL_SEL / M, so the rows flowing
  through every stage are ~constant and the sweep isolates the member-count
  overhead (the per-member Python passes the fused path eliminates). The
  acceptance criterion is per-morsel cost at 32 members <= 1.3x the
  1-member cost on the fused path; the retained per-member oracle path is
  measured alongside to record the linear growth it exhibits.
* **Session sweep** — M concurrently folded Q6-family queries through the
  real Session API, graft vs isolated, recording modeled elapsed time and
  wall time so the end-to-end folding win stays on the record.

Writes ``BENCH_members.json`` at the repo root (same schema discipline as
``BENCH_core.json``).

  PYTHONPATH=src python -m benchmarks.member_sweep            # full sweep
  PYTHONPATH=src python -m benchmarks.member_sweep --smoke    # CI smoke job
"""

from __future__ import annotations

import argparse
import json
import math
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List

import numpy as np

import graftdb
from graftdb import EngineConfig
from repro.api.backends import ReferenceBackend
from repro.core.descriptors import StateSignature
from repro.core.engine import DEFAULT_COST_MODEL
from repro.core.plans import AggSpec, BinOp, Col
from repro.core.predicates import And, Cmp
from repro.core.runtime import AggSink, Member, Pipeline, ProbeOp
from repro.core.state import SharedAggregateState, SharedHashBuildState
from repro.relational import queries
from repro.relational.table import days

from .common import get_db

REPO_ROOT = Path(__file__).resolve().parent.parent

MEMBERS = [1, 2, 4, 8, 16, 32]
SMOKE_MEMBERS = [1, 4, 32]
MORSEL = 65536  # the engine-default morsel (EngineConfig.morsel_size)
TOTAL_SEL = 0.5  # fraction of rows selected across ALL members (fixed volume)
RATIO_TARGET = 1.3


class _BenchEngine:
    """Minimal engine surface for driving ``Pipeline.process`` directly."""

    def __init__(self, member_major: bool):
        self.cost_model = dict(DEFAULT_COST_MODEL)
        self.counters = defaultdict(float)
        self.backend = ReferenceBackend()
        self.member_major = member_major
        self.mesh_plan = None

    def on_member_part_finished(self, pipeline, m, part):
        pass

    def on_member_finished(self, pipeline, m):
        pass


class _NullScan:
    """Stand-in scan so the Pipeline constructor has something to attach to."""

    def attach(self, p):
        pass


def _build_micro(n_members: int, member_major: bool, n_rows: int, seed: int):
    """One pipeline with ``n_members`` members: disjoint interval predicates
    of total selectivity TOTAL_SEL, a two-stage shared probe chain (the
    canonical analytical join spine) every member observes through its slot
    bits, and one aggregate sink each (sum/min grouped by a 256-key)."""
    rng = np.random.default_rng(seed)
    engine = _BenchEngine(member_major)
    n_keys = 4096
    states = []
    for s_i, payload in ((0, "y"), (1, "z")):
        sig = StateSignature("hash_build", (f"dim{s_i}", ("k",), (payload,)))
        states.append(
            SharedHashBuildState(s_i + 1, sig, (f"k{s_i}",), (payload,), did_domain=1 << 40)
        )
    pipeline = Pipeline(
        1,
        ("bench",),
        _NullScan(),
        [ProbeOp(states[0], ("k0",), ("y",)), ProbeOp(states[1], ("k1",), ("z",))],
        counters=engine.counters,
    )
    width = TOTAL_SEL / n_members
    vis = [np.uint64(0), np.uint64(0)]
    members: List[Member] = []
    for i in range(n_members):
        lo = i * width
        pred = And((Cmp("a", ">=", lo), Cmp("a", "<", lo + width)))
        agg = SharedAggregateState(
            100 + i, None, ("g",),
            (AggSpec("sum", BinOp("*", Col("x"), Col("y")), name="s"),
             AggSpec("min", Col("z"), name="lo")),
        )
        m = Member(i + 1, i + 1, pred, [],
                   sink=AggSink(agg, ("g",), agg.aggs))
        m.pipeline = pipeline
        pipeline.add_member(m)
        m.active = True
        m.need = 1 << 60
        for s_i, st in enumerate(states):
            st.attach(m.qid)
            vis[s_i] |= st.slots.mask(m.qid)
        members.append(m)
    keys = np.arange(n_keys, dtype=np.int64)
    for s_i, (st, payload) in enumerate(zip(states, ("y", "z"))):
        st.insert_or_mark(
            keys, keys,
            {f"k{s_i}": keys.astype(float), payload: rng.random(n_keys)},
            np.full(n_keys, vis[s_i]), np.zeros(n_keys, np.uint64),
        )
    cols = {
        "a": rng.random(n_rows),
        "k0": rng.integers(0, n_keys, n_rows).astype(np.float64),
        "k1": rng.integers(0, n_keys, n_rows).astype(np.float64),
        "g": rng.integers(0, 256, n_rows).astype(np.float64),
        "x": rng.random(n_rows),
    }
    return engine, pipeline, cols


def run_micro(members: List[int], n_morsels: int, rounds: int) -> Dict[str, List[Dict]]:
    """Per-morsel cost per (path, member count).

    Shared-host CPU noise drifts on second scales, so independent
    per-config timings decorrelate. Each M is therefore measured PAIRED
    with its own single-member baseline: the two pipelines alternate
    morsel-by-morsel inside every round (same cache and CPU weather), one
    round yields one cost ratio, and the reported ratio is the median over
    rounds. Only the pair under test is alive, keeping the working set
    cache-resident as in the real engine."""
    row_ids = np.arange(MORSEL, dtype=np.int64)
    out: Dict[str, List[Dict]] = {"fused": [], "per_member": [], "chain": []}
    # "chain" is the §13 device path: same member-major pipeline, but the
    # whole probe chain runs as one Pallas launch per morsel
    for label, mm, dev in (
        ("fused", True, False),
        ("per_member", False, False),
        ("chain", True, True),
    ):
        for m in members:
            pair = []
            for n_mem in (members[0], m):
                engine, pipeline, cols = _build_micro(n_mem, mm, MORSEL, seed=7)
                if dev:
                    from repro.api.backends import PallasBackend

                    engine.backend = PallasBackend()
                for _ in range(2):  # warm caches / wave plans / chain jit
                    pipeline.process(engine, cols, row_ids)
                pair.append((engine, pipeline, cols))
            launch0 = pair[1][0].counters["kernel_chain_launches"]
            ratios, costs = [], []
            for _ in range(rounds * n_morsels):
                t = [0.0, 0.0]
                for side, (engine, pipeline, cols) in enumerate(pair):
                    t0 = time.perf_counter()
                    pipeline.process(engine, cols, row_ids)
                    t[side] = time.perf_counter() - t0
                ratios.append(t[1] / t[0])
                costs.append(t[1])
            # median of adjacent-pair ratios rejects bursty outliers
            # (page-cache refills, allocator spikes)
            row = {
                "members": m,
                "per_morsel_s": round(float(np.median(costs)), 7),
                "ratio_vs_1": round(float(np.median(ratios)), 3),
            }
            if dev:
                launches = pair[1][0].counters["kernel_chain_launches"] - launch0
                row["launches_per_morsel"] = round(
                    float(launches) / (rounds * n_morsels), 3
                )
            out[label].append(row)
            print(f"{label:11s} members={m:2d} per-morsel={row['per_morsel_s']*1e3:8.3f} ms "
                  f"ratio={row['ratio_vs_1']:.3f}"
                  + (f" launches/morsel={row['launches_per_morsel']}" if dev else ""),
                  flush=True)
    return out


def _distinct_q6(db, n: int):
    """n structurally distinct Q6 instances (distinct quantity bound keeps
    aggregate identities apart so each query is a real member)."""
    base = float(days("1994-01-01"))
    return [
        queries.make_query(
            db, "q6",
            {"date": base, "discount": 0.05, "quantity": 24.0 + 0.01 * i},
            arrival=0.0,
        )
        for i in range(n)
    ]


def run_session(db, members: List[int]) -> List[Dict]:
    rows = []
    for m in members:
        rec: Dict[str, float] = {"members": m}
        for mode in ("graft", "isolated"):
            session = graftdb.connect(
                db, EngineConfig(mode=mode, morsel_size=MORSEL, workers=1, partitions=1)
            )
            session.submit_all(_distinct_q6(db, m))
            w0 = time.perf_counter()
            session.run()
            rec[f"{mode}_wall_s"] = round(time.perf_counter() - w0, 4)
            rec[f"{mode}_elapsed_s"] = round(session.now, 6)
        rec["modeled_speedup"] = round(rec["isolated_elapsed_s"] / rec["graft_elapsed_s"], 3)
        rec["wall_speedup"] = round(
            rec["isolated_wall_s"] / max(rec["graft_wall_s"], 1e-9), 3
        )
        rows.append(rec)
        print(f"session members={m:2d} graft={rec['graft_elapsed_s']:.4f}s "
              f"isolated={rec['isolated_elapsed_s']:.4f}s "
              f"x{rec['modeled_speedup']} modeled / x{rec['wall_speedup']} wall", flush=True)
    return rows


def run(smoke: bool = False, out_path: Path | None = None) -> Dict:
    members = SMOKE_MEMBERS if smoke else MEMBERS
    n_morsels = 2 if smoke else 4
    rounds = 3 if smoke else 10
    # Shared-host weather (CPU steal) varies on minute scales; attempt the
    # sweep a few times and keep the attempt that ran on the cleanest host
    # — selected by absolute speed (weather), never by the ratio outcome.
    attempts = 1 if smoke else 3
    micro = None
    micro_speed = math.inf
    for a in range(attempts):
        if attempts > 1:
            print(f"--- micro attempt {a + 1}/{attempts}")
        cand = run_micro(members, n_morsels, rounds)
        speed = sum(r["per_morsel_s"] for rows in cand.values() for r in rows)
        if speed < micro_speed:
            micro, micro_speed = cand, speed
    db = get_db(0.005 if smoke else 0.02)
    session_rows = run_session(db, members)
    fused_last = micro["fused"][-1]["ratio_vs_1"]
    pm_last = micro["per_member"][-1]["ratio_vs_1"]
    chain_last = micro["chain"][-1]["ratio_vs_1"]
    chain_lpm = max(r["launches_per_morsel"] for r in micro["chain"])
    out = {
        "bench": "graftdb_member_sweep",
        "version": 1,
        "smoke": smoke,
        "morsel_size": MORSEL,
        "total_selectivity": TOTAL_SEL,
        "members": members,
        "per_morsel": micro,
        "session": session_rows,
        "acceptance": {
            "criterion": "fused per-morsel cost at max members <= "
                         f"{RATIO_TARGET}x the 1-member cost (fixed data volume)",
            "max_members": members[-1],
            "fused_ratio": fused_last,
            "per_member_ratio": pm_last,
            # §13 device chain: stays flat in members AND every morsel's
            # stage chain is served by exactly one kernel launch
            "chain_ratio": chain_last,
            "chain_launches_per_morsel": chain_lpm,
            "ratio_target": RATIO_TARGET,
            "pass": bool(fused_last <= RATIO_TARGET and chain_lpm == 1.0),
        },
    }
    if not smoke:
        # Also record the CI smoke grid on this machine: the committed
        # artifact then carries the reference numbers that
        # benchmarks.regression_gate holds CI's fresh smoke runs against.
        print("-- smoke_ref grid --")
        out["smoke_ref"] = {
            "members": SMOKE_MEMBERS,
            "per_morsel": run_micro(SMOKE_MEMBERS, 2, 3),
            "session": run_session(get_db(0.005), SMOKE_MEMBERS),
        }
    target = out_path or (REPO_ROOT / "BENCH_members.json")
    target.write_text(json.dumps(out, indent=1) + "\n")
    print(f"# fused {members[-1]}-member per-morsel ratio: {fused_last}x "
          f"(target <= {RATIO_TARGET}x; per-member oracle: {pm_last}x; "
          f"chain: {chain_last}x at {chain_lpm} launches/morsel)")
    print(f"wrote {target}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="CI smoke sizes")
    ap.add_argument("--out", type=Path, default=None,
                    help="output path (default: repo-root BENCH_members.json)")
    args = ap.parse_args()
    run(smoke=args.smoke, out_path=args.out)
