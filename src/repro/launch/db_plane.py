"""GraftDB data-plane dry-run as a validated record (DESIGN.md §14).

Promotes the former print-only ``dryrun.py --db-plane`` path into a
function: lower + compile the distributed data plane — the bucketed
all_to_all hash join, the psum aggregate, and the shard-local fused stage
chain — on an arbitrary mesh, and return one record that
``validate_db_plane_record`` checks structurally. The dry-run script and
the tier-1 smoke-mesh test share this code, so the path CI exercises on a
single device is byte-for-byte the path the 256-device dry-run compiles.

No XLA_FLAGS side effects here: callers choose the device count (the
dry-run script sets --xla_force_host_platform_device_count before any jax
import; tests run on the single real device via ``make_smoke_mesh``).
"""

from __future__ import annotations

import time
from typing import Dict, Optional

REQUIRED_FIELDS = (
    "arch",
    "shape",
    "mesh",
    "data_shards",
    "rows",
    "status",
    "hlo_stats",
    "aggregate",
    "chain",
    "total_s",
)
HLO_STAT_FIELDS = (
    "flops_per_device",
    "mem_bytes_per_device",
    "coll_bytes_per_device",
    "coll_count",
)


def _mesh_label(mesh) -> str:
    return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)


def _chain_parity(mesh, rows: int) -> Dict:
    """Run one fused chain stage (``graft_chain_stage``) both unsharded and
    shard-locally inside shard_map on ``mesh``, and compare every output
    bit-for-bit (stats are psum'd global; row outputs gather in shard
    order, which is row order for row-partitioned inputs). The stage
    translates its lens through identity tables, filters on a row operand
    and exports an entry column, so every output block carries data."""
    import numpy as np

    from ..kernels import fused_chain as ch
    from ..kernels.hash_probe import EMPTY, MULT

    d = int(mesh.shape["data"])
    rows = max(rows, d)
    rows = (rows // d) * d
    cap = 64
    ecap = 32
    rng = np.random.default_rng(7)
    n_entries = 24
    # open-addressed table: entry keys 1..n_entries at their probe slots
    keys_host = np.arange(1, n_entries + 1, dtype=np.int32)
    tkeys = np.full(cap, EMPTY, np.int32)
    tentry = np.zeros(cap, np.int32)
    for e, k in enumerate(keys_host):
        pos = (int(k) * MULT) & (cap - 1)
        while tkeys[pos] != EMPTY:
            pos = (pos + 1) & (cap - 1)
        tkeys[pos] = k
        tentry[pos] = e
    evlo = np.full(ecap, 0xFFFFFFFF, np.uint32)
    evhi = np.full(ecap, 0xFFFFFFFF, np.uint32)
    # identity byte translation tables
    ttlo = np.zeros((8, 256), np.uint32)
    tthi = np.zeros((8, 256), np.uint32)
    for b in range(4):
        ttlo[b] = np.arange(256, dtype=np.uint32) << np.uint32(8 * b)
        tthi[4 + b] = np.arange(256, dtype=np.uint32) << np.uint32(8 * b)
    g, m, a = ch.G_MAX, ch.M_MAX, ch.A_MAX
    flags = np.zeros(ch.N_FLAGS, np.int32)
    flags[1] = 1  # the filter
    flags[ch.F_EXPORT] = 1  # export the entry keys
    flags[ch.F_FOWN:ch.F_EPOST] = -1  # filter operands come in as rows
    # member 0 (slot 0) keeps rows whose operand 0 lies in [0, 2**31]
    flo = np.zeros((m, a, 2), np.uint32)
    fhi = np.zeros((m, a, 2), np.uint32)
    fhi[0, 0] = (1 << 31, 0)
    fcon = np.zeros((m, a), np.int32)
    fcon[0, 0] = 1
    fbit = np.zeros((m, 2), np.uint32)
    fbit[0, 0] = 1
    row0 = np.zeros(rows, np.uint32)
    fvals = ((rng.integers(0, 1 << 32, rows, dtype=np.uint32), row0),) + ((row0, row0),) * (a - 1)
    entry_keys = np.zeros(ecap, np.uint32)
    entry_keys[:n_entries] = keys_host.view(np.uint32)
    args = (
        rng.integers(0, 1 << 32, rows, dtype=np.uint32),  # bits_lo
        np.zeros(rows, np.uint32),  # bits_hi
        rng.integers(1, 2 * n_entries, rows).astype(np.int32).view(np.uint32),
        tkeys, tentry, evlo, evhi, ttlo, tthi, flags,
        evlo, evhi,  # provenance words
        np.zeros((g, 2), np.uint32), np.zeros((g, 2), np.uint32), np.zeros((g, a), np.int32),
        np.zeros((g, a, 2), np.uint32), np.zeros((g, a, 2), np.uint32), ((evlo, evhi),) * a,
        fvals, flo, fhi, fcon, fbit, (entry_keys,) * ch.X_MAX,
    )
    ref = ch.graft_chain_stage(*args)
    shd = ch.graft_chain_stage_sharded(mesh)(*args)
    ok = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in zip(ref, shd)
    )
    return {
        "rows": int(rows),
        "data_shards": d,
        "parity": bool(ok),
        "matched_rows": int(np.asarray(ref[3])[1]),
    }


def db_plane_record(
    mesh,
    *,
    rows: int = 1 << 26,
    n_groups: int = 256,
    chain_rows: Optional[int] = 2048,
) -> Dict:
    """Lower+compile the distributed GraftDB data plane on ``mesh`` and
    return a validated record — proves the paper's engine itself shards
    across the pod (DESIGN.md §4/§14). ``chain_rows=None`` skips the
    executed fused-chain parity block (it RUNS the kernel; the join and
    aggregate only compile)."""
    import jax
    import jax.numpy as jnp

    from ..relational.distributed import make_partitioned_aggregate, make_partitioned_join
    from .hlo_analysis import analyze

    t0 = time.time()
    d = int(mesh.shape["data"])
    rec: Dict = {
        "arch": "graftdb-dataplane",
        "shape": f"join_{rows >> 20 if rows >= 1 << 20 else rows}"
        + ("M" if rows >= 1 << 20 else ""),
        "mesh": _mesh_label(mesh),
        "data_shards": d,
        "rows": int(rows),
        "status": "ok",
        "aggregate": "skipped",
        "chain": "skipped",
    }
    try:
        capacity = max(8, 2 * rows // d // max(d, 1))
        join = make_partitioned_join(
            mesh, build_width=2, probe_width=3, capacity=capacity
        )
        sds = jax.ShapeDtypeStruct
        bk = sds((rows,), jnp.int64)
        bv = sds((rows, 2), jnp.float32)
        pk = sds((rows,), jnp.int64)
        pv = sds((rows, 3), jnp.float32)
        compiled = join.lower(bk, bv, pk, pv).compile()
        st = analyze(compiled.as_text())
        rec["hlo_stats"] = {
            "flops_per_device": float(st.flops),
            "mem_bytes_per_device": float(st.mem_bytes),
            "coll_bytes_per_device": float(sum(st.coll_bytes.values())),
            "coll_count": int(sum(st.coll_count.values())),
            "coll_by_op": {k: int(v) for k, v in st.coll_count.items()},
        }
        agg = make_partitioned_aggregate(mesh, n_groups=n_groups, width=4)
        agg.lower(sds((rows,), jnp.int32), sds((rows, 4), jnp.float32)).compile()
        rec["aggregate"] = "ok"
        if chain_rows is not None:
            rec["chain"] = _chain_parity(mesh, chain_rows)
    except Exception as e:
        rec["status"] = "fail"
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def validate_db_plane_record(rec: Dict) -> Dict:
    """Structural + status validation of a db-plane record; raises
    ValueError with the first problem found, returns the record on
    success (so call sites can chain it)."""
    missing = [f for f in REQUIRED_FIELDS if f not in rec]
    if missing:
        raise ValueError(f"db-plane record missing fields: {missing}")
    if rec["status"] != "ok":
        raise ValueError(
            f"db-plane dry-run failed: {rec.get('error', 'unknown error')}"
        )
    hs = rec["hlo_stats"]
    bad = [f for f in HLO_STAT_FIELDS if not isinstance(hs.get(f), (int, float))]
    if bad:
        raise ValueError(f"db-plane hlo_stats malformed fields: {bad}")
    if rec["aggregate"] != "ok":
        raise ValueError(f"db-plane aggregate compile failed: {rec['aggregate']!r}")
    chain = rec["chain"]
    if chain != "skipped":
        if not isinstance(chain, dict) or not chain.get("parity"):
            raise ValueError(
                f"shard-local fused chain is not bit-identical to the "
                f"unsharded launch: {chain!r}"
            )
        if chain.get("matched_rows", 0) <= 0:
            raise ValueError(
                f"chain parity block matched no rows — vacuous check: {chain!r}"
            )
    if rec["data_shards"] > 1 and hs["coll_count"] <= 0:
        raise ValueError(
            "multi-shard join compiled to zero collectives — the exchange "
            "was elided, the plan is not actually distributed"
        )
    return rec
