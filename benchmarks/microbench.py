"""Core data-plane microbenchmarks: before/after the vectorized state plane.

Measures the four shared-state hot paths the morsel loop hits per batch —
insert-or-mark, probe (including index maintenance under growth), aggregate
group-update, and the multi-member source filter — at three state sizes,
against inline replicas of the pre-PR implementations (per-row dict walks,
full re-argsort probe index, per-unique-group Python loops, per-member
predicate evaluation). Writes ``BENCH_core.json`` at the repo root so
subsequent PRs have a recorded perf trajectory.

  PYTHONPATH=src python -m benchmarks.microbench            # full sizes
  PYTHONPATH=src python -m benchmarks.microbench --smoke    # CI smoke job
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

from repro.core.descriptors import StateSignature
from repro.core.predicates import And, Cmp, evaluate
from repro.core.runtime import FusedBoundFilter, member_bound_matrices
from repro.core.state import GrowArray, SharedAggregateState, SharedHashBuildState

REPO_ROOT = Path(__file__).resolve().parent.parent
BATCH = 8192
FULL_SIZES = [10_000, 100_000, 1_000_000]
SMOKE_SIZES = [1_000, 4_000, 16_000]


def _mk_state() -> SharedHashBuildState:
    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    return SharedHashBuildState(1, sig, ("k",), ("x",), did_domain=1 << 40)


# ---------------------------------------------------------------------------
# Pre-PR replicas (the seed implementations this PR replaced)
# ---------------------------------------------------------------------------


class LegacyDidTable:
    """insert_or_mark as it was: per-row dict walk + per-duplicate merge."""

    def __init__(self):
        self._did_index: Dict[int, int] = {}
        self.did = GrowArray(np.int64)
        self.vis = GrowArray(np.uint64)
        self.emask = GrowArray(np.uint64)
        self.col = GrowArray(np.float64)

    def insert_or_mark(self, dids, col, vismask, emask):
        idx_map = self._did_index
        pos = np.empty(len(dids), dtype=np.int64)
        is_new = np.zeros(len(dids), dtype=bool)
        for i, d in enumerate(dids.tolist()):
            j = idx_map.get(d, -1)
            if j < 0:
                is_new[i] = True
            else:
                pos[i] = j
        old = ~is_new
        if old.any():
            p = pos[old]
            np.bitwise_or.at(self.vis.data, p, vismask[old])
            np.bitwise_or.at(self.emask.data, p, emask[old])
        if is_new.any():
            sel_all = np.flatnonzero(is_new)
            nd = dids[sel_all]
            uniq, first = np.unique(nd, return_index=True)
            sel = sel_all[np.sort(first)]
            if len(uniq) != len(sel_all):
                vis_new = np.zeros(len(sel), dtype=np.uint64)
                em_new = np.zeros(len(sel), dtype=np.uint64)
                order = {int(d): k for k, d in enumerate(dids[sel].tolist())}
                for i in sel_all.tolist():
                    k = order[int(dids[i])]
                    vis_new[k] |= vismask[i]
                    em_new[k] |= emask[i]
            else:
                vis_new = vismask[sel]
                em_new = emask[sel]
            base = self.did.n
            self.did.append(dids[sel])
            self.vis.append(vis_new)
            self.emask.append(em_new)
            self.col.append(col[sel])
            for k, d in enumerate(dids[sel].tolist()):
                idx_map[int(d)] = base + k


class LegacySortProbe:
    """The sort-based probe index: full re-argsort on every growth."""

    def __init__(self):
        self.keycode = GrowArray(np.int64)
        self._built = -1
        self._order = None
        self._sorted = None

    def append(self, keys):
        self.keycode.append(keys)

    def probe(self, pk):
        if self._built != self.keycode.n:
            keys = self.keycode.data
            self._order = np.argsort(keys, kind="stable")
            self._sorted = keys[self._order]
            self._built = self.keycode.n
        lo = np.searchsorted(self._sorted, pk, side="left")
        hi = np.searchsorted(self._sorted, pk, side="right")
        counts = hi - lo
        total = int(counts.sum())
        if total == 0:
            return np.empty(0, np.int64), np.empty(0, np.int64)
        probe_idx = np.repeat(np.arange(len(pk), dtype=np.int64), counts)
        starts = np.repeat(lo, counts)
        offs = np.arange(total, dtype=np.int64) - np.repeat(
            np.concatenate(([0], np.cumsum(counts)[:-1])), counts
        )
        return probe_idx, self._order[starts + offs]


class LegacyAggState:
    """Group-id assignment as it was: tuple dict + per-unique-group loop."""

    def __init__(self):
        self._gid_of: Dict[tuple, int] = {}
        self.group_col = GrowArray(np.float64)
        self.acc = GrowArray(np.float64)
        self.counts = GrowArray(np.float64)

    def update(self, key_col, vals):
        stacked = np.stack([key_col], axis=1)
        uniq, inv = np.unique(stacked, axis=0, return_inverse=True)
        gids = np.empty(len(uniq), dtype=np.int64)
        for i, row in enumerate(uniq):
            t = tuple(row.tolist())
            g = self._gid_of.get(t)
            if g is None:
                g = len(self._gid_of)
                self._gid_of[t] = g
                self.group_col.append(np.array([row[0]], dtype=np.float64))
                self.acc.append(np.zeros(1))
                self.counts.append(np.zeros(1))
            gids[i] = g
        gids = gids[np.asarray(inv).ravel()]
        n_groups = len(self._gid_of)
        cnt = np.bincount(gids, minlength=n_groups).astype(np.float64)
        self.counts.data[:] += cnt
        self.acc.data[:] += np.bincount(gids, weights=vals, minlength=n_groups)


# ---------------------------------------------------------------------------
# Scenarios
# ---------------------------------------------------------------------------


def bench_insert_or_mark(size: int, rng) -> Dict:
    """2x size rows of ~50/50 fresh/re-delivered derivations, batched."""
    n_rows = 2 * size
    dids = rng.integers(0, size, n_rows).astype(np.int64)
    col = rng.random(n_rows)
    vism = np.full(n_rows, np.uint64(1))
    emk = np.full(n_rows, np.uint64(2))
    batches = [slice(i, i + BATCH) for i in range(0, n_rows, BATCH)]

    legacy = LegacyDidTable()
    t0 = time.perf_counter()
    for b in batches:
        legacy.insert_or_mark(dids[b], col[b], vism[b], emk[b])
    before = time.perf_counter() - t0

    state = _mk_state()
    t0 = time.perf_counter()
    for b in batches:
        d = dids[b]
        state.insert_or_mark(
            d, d, {"k": col[b], "x": col[b]}, vism[b], emk[b]
        )
    after = time.perf_counter() - t0
    assert state.n_entries == legacy.did.n
    return _row("insert_or_mark", size, n_rows, before, after)


def bench_probe(size: int, rng) -> Dict:
    """Interleaved growth + probe: the morsel loop's pattern. The legacy
    index re-argsorts the full state on every growth episode; the
    incremental index pays O(batch)."""
    keys = rng.permutation(size).astype(np.int64)
    probes = rng.integers(0, size, size).astype(np.int64)
    batches = [slice(i, i + BATCH) for i in range(0, size, BATCH)]

    legacy = LegacySortProbe()
    before = 0.0
    for b in batches:
        legacy.append(keys[b])
        t0 = time.perf_counter()
        lp = legacy.probe(probes[b])
        before += time.perf_counter() - t0

    state = _mk_state()
    after = 0.0
    for b in batches:
        k = keys[b]
        state.insert_or_mark(
            k, k, {"k": k.astype(float), "x": k.astype(float)},
            np.full(len(k), np.uint64(1)), np.zeros(len(k), np.uint64),
        )
        t0 = time.perf_counter()
        np_ = state.probe(probes[b])
        after += time.perf_counter() - t0
    assert len(lp[0]) == len(np_[0])
    return _row("probe", size, size, before, after)


def bench_group_update(size: int, rng) -> Dict:
    """sum() over ~size distinct groups, batched morsel-style."""
    n_rows = 2 * size
    gkeys = rng.integers(0, size, n_rows).astype(np.float64)
    vals = rng.random(n_rows)
    batches = [slice(i, i + BATCH) for i in range(0, n_rows, BATCH)]

    legacy = LegacyAggState()
    t0 = time.perf_counter()
    for b in batches:
        legacy.update(gkeys[b], vals[b])
    before = time.perf_counter() - t0

    spec = SimpleNamespace(func="sum", name="s", expr=None, distinct=False)
    state = SharedAggregateState(1, None, ("g",), (spec,))
    t0 = time.perf_counter()
    for b in batches:
        state.update([gkeys[b]], [vals[b]], len(vals[b]))
    after = time.perf_counter() - t0
    assert state.n_groups == len(legacy._gid_of)
    np.testing.assert_allclose(
        np.sort(state.result()["s"]), np.sort(legacy.acc.data), rtol=1e-9
    )
    return _row("group_update", size, n_rows, before, after)


def bench_filter(size: int, rng) -> Dict:
    """16 members x 3 range attrs over one morsel-sized column batch:
    per-member evaluate loop vs one fused SoA bound-check pass."""
    n_members = 16
    cols = {a: rng.random(size) for a in ("a", "b", "c")}
    members = []
    for i in range(n_members):
        lo = rng.random(3) * 0.5
        hi = lo + 0.4
        pred = And(
            (
                Cmp("a", ">=", lo[0]), Cmp("a", "<", hi[0]),
                Cmp("b", ">=", lo[1]), Cmp("b", "<", hi[1]),
                Cmp("c", ">=", lo[2]), Cmp("c", "<", hi[2]),
            )
        )
        members.append(SimpleNamespace(pred=pred, bitval=np.uint64(1) << np.uint64(i)))

    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        bits_b = np.zeros(size, dtype=np.uint64)
        for m in members:
            mask = evaluate(m.pred, cols)
            bits_b |= np.where(mask, m.bitval, np.uint64(0))
    before = (time.perf_counter() - t0) / reps

    attrs, lo_m, hi_m, fused, slow = member_bound_matrices(members)
    assert len(fused) == n_members and not slow
    bitvals = np.array([m.bitval for m in fused], dtype=np.uint64)
    # compile once, as the pipeline does (the per-wave plan caches the
    # FusedBoundFilter); only the per-morsel evaluation is timed
    ff = FusedBoundFilter(attrs, lo_m, hi_m, bitvals)
    t0 = time.perf_counter()
    for _ in range(reps):
        bits_a = ff(size, cols)
    after = (time.perf_counter() - t0) / reps
    np.testing.assert_array_equal(bits_a, bits_b)
    return _row("filter", size, size * n_members, before, after)


def bench_fused_chain(size: int, rng) -> Dict:
    """64 folded members (the full slot space) x 2-stage shared probe
    chain, morsel by morsel: the per-member stage loop (the pre-§13
    default whenever any member sat on slot >= 32, where the uint32 lens
    kernel declined) vs one fused Pallas chain launch per morsel over
    device-resident mirrors. Results stay bit-identical (DESIGN.md §13);
    only the data plane changes. The legs alternate morsel-by-morsel and
    the reported times are per-rep medians, as in member_sweep, so shared
    -host CPU weather hits both sides alike."""
    from repro.api.backends import PallasBackend, ReferenceBackend

    from .member_sweep import _build_micro

    n_members = 64  # > 32 slots: forces the pre-§13 per-member fallback
    morsel = min(size, 65536)  # EngineConfig.morsel_size default
    n_morsels = max(1, size // morsel)
    reps = 3

    legs = []
    for member_major, backend in ((False, ReferenceBackend()), (True, PallasBackend())):
        engine, pipeline, cols = _build_micro(n_members, member_major, morsel, seed=7)
        engine.backend = backend
        row_ids = np.arange(morsel, dtype=np.int64)
        for _ in range(2):  # warm plans / jit the chain
            pipeline.process(engine, cols, row_ids)
        legs.append((engine, pipeline, cols, row_ids))
    times = np.zeros((reps, 2))
    for rep in range(reps):
        for _ in range(n_morsels):
            for side, (engine, pipeline, cols, row_ids) in enumerate(legs):
                t0 = time.perf_counter()
                pipeline.process(engine, cols, row_ids)
                times[rep, side] += time.perf_counter() - t0
    before, after = np.median(times, axis=0)
    eng_a = legs[1][0]
    assert eng_a.counters["kernel_chain_launches"] >= reps * n_morsels
    # both legs saw the same morsels the same number of times: member
    # aggregates must agree bit-exactly
    for m_b, m_a in zip(legs[0][1].members, legs[1][1].members):
        r_b, r_a = m_b.sink.agg_state.result(), m_a.sink.agg_state.result()
        for k in r_b:
            np.testing.assert_array_equal(np.sort(r_b[k]), np.sort(r_a[k]))
    return _row("fused_chain", size, n_morsels * morsel, float(before), float(after))


def _row(op: str, size: int, rows: int, before: float, after: float) -> Dict:
    before = max(before, 1e-9)
    after = max(after, 1e-9)
    return {
        "op": op,
        "size": size,
        "rows": rows,
        "before_s": round(before, 6),
        "after_s": round(after, 6),
        "before_rows_per_s": round(rows / before, 1),
        "after_rows_per_s": round(rows / after, 1),
        "speedup": round(before / after, 2),
    }


BENCHES = {
    "insert_or_mark": bench_insert_or_mark,
    "probe": bench_probe,
    "group_update": bench_group_update,
    "filter": bench_filter,
    "fused_chain": bench_fused_chain,
}


def main(argv=None) -> Path:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny sizes (CI smoke job)")
    ap.add_argument("--out", type=Path, default=REPO_ROOT / "BENCH_core.json")
    args = ap.parse_args(argv)

    sizes = SMOKE_SIZES if args.smoke else FULL_SIZES
    rng = np.random.default_rng(0)
    # warmup: touch every path once at tiny size so first-call overheads
    # (allocator, caches) don't skew the smallest measurement
    for fn in BENCHES.values():
        fn(512, np.random.default_rng(1))

    results: Dict[str, List[Dict]] = {}
    print(f"{'op':<16} {'size':>9} {'before rows/s':>15} {'after rows/s':>15} {'speedup':>8}")
    for name, fn in BENCHES.items():
        results[name] = []
        for size in sizes:
            row = fn(size, np.random.default_rng(size))
            results[name].append(row)
            print(
                f"{name:<16} {size:>9} {row['before_rows_per_s']:>15.0f} "
                f"{row['after_rows_per_s']:>15.0f} {row['speedup']:>7.2f}x"
            )

    payload = {
        "bench": "graftdb_core_microbench",
        "version": 1,
        "smoke": bool(args.smoke),
        "batch": BATCH,
        "sizes": sizes,
        "ops": results,
    }
    if not args.smoke:
        # Also record the CI smoke grid, measured on the same machine as
        # the full-size numbers: benchmarks.regression_gate compares CI's
        # fresh smoke runs against this block (machine-relative speedups).
        print("-- smoke_ref grid --")
        smoke: Dict[str, List[Dict]] = {}
        for name, fn in BENCHES.items():
            smoke[name] = [fn(size, np.random.default_rng(size)) for size in SMOKE_SIZES]
            print(f"{name:<16} speedups "
                  + " ".join(f"{r['speedup']:.2f}x" for r in smoke[name]))
        payload["smoke_ref"] = {"sizes": SMOKE_SIZES, "ops": smoke}
    args.out.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"wrote {args.out}")
    return args.out


if __name__ == "__main__":
    main()
