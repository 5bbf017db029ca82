"""Distributed relational data plane: shard_map-partitioned operators.

DESIGN.md §4 (GraftDB-on-mesh): base tables are row-partitioned over the
'data' mesh axis; equi-joins repartition both sides by join-key hash with a
fixed-capacity bucketed all_to_all (TPU-native: dense [P, C, W] exchange
tensors, no ragged communication); aggregations combine shard-local segment
sums with an all_to_all by group hash. The control plane (grafting
admission) stays replicated-deterministic on every host — only the data
plane communicates.

These operators are the scale-out twins of the single-worker engine's
morsel pipeline: the engine's shared states partition by key exactly like
`repartition_by_key` (pass ``dest=key_partition(keys, P)`` so the exchange
routes rows to the same shard that owns the state bucket), so a 1000-node
deployment shards every SharedHashBuildState bucket-wise with the same
math. Bucket overflow is never silent: each exchange reports the number of
valid rows that did not fit, and the host-side `exchange_by_key` wrapper
grows capacity (or hard-fails) instead of dropping. Numerical correctness
is validated in tests on the single-device mesh; the production-mesh
lower+compile is part of the dry-run (`launch/db_plane.py`).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

FILL = jnp.int64(-1)

# The dense exchange carries keys as device integers; with jax x64 disabled
# those are int32, so keycodes must fit — same contract as the Pallas probe
# chain (PallasBackend._KEY_LIMIT). Callers with wider keys stay on the host
# data plane.
KEY_LIMIT = 2**31 - 2


class BucketOverflowError(RuntimeError):
    """A bucketed exchange would have dropped rows (capacity too small)."""


def _hash_dest(keys: jnp.ndarray, n: int) -> jnp.ndarray:
    return (keys.astype(jnp.uint32) * jnp.uint32(2654435761) >> jnp.uint32(8)).astype(
        jnp.int32
    ) % n


def repartition_by_key(
    keys: jnp.ndarray,  # [rows_local] int64 (FILL = invalid/padding)
    values: jnp.ndarray,  # [rows_local, W] f32 payload
    axis_name: str,
    n_shards: int,
    capacity: int,
    dest: Optional[jnp.ndarray] = None,
):
    """Inside shard_map: route each local row to shard hash(key)%P via a
    dense [P, C, 1+W] all_to_all. Returns (keys', values', valid',
    n_overflow) with rows now partitioned by key hash.

    ``dest`` overrides the destination shard per row (e.g. the engine's
    splitmix64 ``key_partition`` routing, computed host-side) so exchange
    placement matches shard-local state ownership; invalid (FILL) rows are
    never sent regardless.

    Capacity is a static knob; a destination bucket past capacity does NOT
    silently lose rows — ``n_overflow`` counts every valid row this shard
    failed to place, and callers must grow capacity or fail (see
    `exchange_by_key`)."""
    valid = keys != FILL
    if dest is None:
        dest = _hash_dest(keys, n_shards)
    dest = jnp.where(valid, dest, n_shards)  # invalid -> discard row
    order = jnp.argsort(dest)
    keys_s = keys[order]
    vals_s = values[order]
    dest_s = dest[order]
    # position within destination bucket
    onehot = dest_s[:, None] == jnp.arange(n_shards + 1)[None, :]
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, dest_s[:, None].astype(jnp.int32), axis=1)[:, 0]
    keep = (slot < capacity) & (dest_s < n_shards)
    # valid rows that did not fit their destination bucket: surfaced, never
    # silently dropped (satellite: bucket_overflow_rows)
    n_overflow = jnp.sum((~keep) & (dest_s < n_shards), dtype=jnp.int32)
    safe_dest = jnp.where(keep, dest_s, 0)
    safe_slot = jnp.where(keep, slot, capacity - 1)
    buf_k = jnp.full((n_shards, capacity), FILL)
    buf_v = jnp.zeros((n_shards, capacity, values.shape[1]), values.dtype)
    buf_k = buf_k.at[safe_dest, safe_slot].set(jnp.where(keep, keys_s, FILL), mode="drop")
    buf_v = buf_v.at[safe_dest, safe_slot].set(
        jnp.where(keep[:, None], vals_s, 0.0), mode="drop"
    )
    # exchange: shard p sends buf[q] to shard q
    k_out = jax.lax.all_to_all(buf_k, axis_name, 0, 0, tiled=False)
    v_out = jax.lax.all_to_all(buf_v, axis_name, 0, 0, tiled=False)
    k_flat = k_out.reshape(-1)
    v_flat = v_out.reshape(-1, values.shape[1])
    return k_flat, v_flat, k_flat != FILL, n_overflow


def _local_join(bk, bv, pk, pv):
    """Sort-probe join of local partitions (unique build keys)."""
    order = jnp.argsort(bk)
    sbk = bk[order]
    idx = jnp.searchsorted(sbk, pk)
    idx = jnp.clip(idx, 0, sbk.shape[0] - 1)
    hit = (sbk[idx] == pk) & (pk != FILL)
    bsel = order[idx]
    out_v = jnp.concatenate([pv, bv[bsel]], axis=-1)
    return jnp.where(hit[:, None], out_v, 0.0), hit


def make_partitioned_join(
    mesh: Mesh,
    build_width: int,
    probe_width: int,
    capacity: int,
    axis_name: str = "data",
):
    """jit-able distributed hash join over row-partitioned inputs.

    build_keys/probe_keys: [R] int64 sharded over ``axis_name`` (FILL pads);
    build_vals/probe_vals: [R, W]. Output: joined rows [R_probe', W_p+W_b]
    + hit mask, partitioned by key hash, + the total count of rows that
    overflowed an exchange bucket (psum over the axis — identical on every
    shard; nonzero means the result is incomplete and capacity must grow)."""
    n = mesh.shape[axis_name]
    spec_k = P(axis_name)
    spec_v = P(axis_name, None)

    def local(bk, bv, pk, pv):
        bk2, bv2, _, ob = repartition_by_key(bk, bv, axis_name, n, capacity)
        pk2, pv2, _, op_ = repartition_by_key(pk, pv, axis_name, n, capacity)
        out, hit = _local_join(bk2, bv2, pk2, pv2)
        overflow = jax.lax.psum(ob + op_, axis_name)
        return out, hit, pk2, overflow

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_k, spec_v, spec_k, spec_v),
        out_specs=(spec_v, spec_k, spec_k, P()),
        check_vma=False,
    )
    return jax.jit(fn)


def make_partitioned_exchange(
    mesh: Mesh,
    width: int,
    capacity: int,
    axis_name: str = "data",
):
    """jit-able bucketed all_to_all alone: rows in row-partition order ->
    rows in key-shard order, with per-row ``dest`` routing (replicated in
    row-partition order alongside the rows) and the psum'd overflow count."""
    n = mesh.shape[axis_name]
    spec_k = P(axis_name)
    spec_v = P(axis_name, None)

    def local(keys, vals, dest):
        k2, v2, ok, ov = repartition_by_key(keys, vals, axis_name, n, capacity, dest=dest)
        return k2, v2, ok, jax.lax.psum(ov, axis_name)

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec_k, spec_v, spec_k),
        out_specs=(spec_k, spec_v, spec_k, P()),
        check_vma=False,
    )
    return jax.jit(fn)


@functools.lru_cache(maxsize=32)
def _cached_exchange(mesh: Mesh, width: int, capacity: int, axis_name: str):
    return make_partitioned_exchange(mesh, width, capacity, axis_name)


def exchange_by_key(
    mesh: Mesh,
    keys: np.ndarray,
    values: np.ndarray,
    *,
    capacity: Optional[int] = None,
    dest: Optional[np.ndarray] = None,
    axis_name: str = "data",
    on_overflow: str = "grow",
    max_doublings: int = 6,
) -> Dict:
    """Host-facing bucketed exchange: pad, run the shard_map'd
    repartition, and grow capacity (never drop) on bucket overflow.

    Returns a dict with ``keys``/``values``/``valid`` (device arrays in
    key-shard order, [P*C(')] rows), ``capacity`` actually used,
    ``bucket_overflow_rows`` (total rows that overflowed across all
    attempts — every one was recovered by regrowing, none lost) and
    ``attempts``. ``on_overflow='raise'`` hard-fails with
    BucketOverflowError instead of growing."""
    keys = np.asarray(keys, np.int64)
    if keys.size and np.abs(keys).max() > KEY_LIMIT:
        raise ValueError(
            "device exchange carries int32 keycodes (jax x64 disabled); "
            f"|key| must be <= {KEY_LIMIT} — wider keys stay on the host plane"
        )
    if on_overflow not in ("grow", "raise"):
        raise ValueError(f"on_overflow must be 'grow' or 'raise', got {on_overflow!r}")
    n = int(mesh.shape[axis_name])
    values = np.asarray(values, np.float32)
    if values.ndim == 1:
        values = values[:, None]
    if dest is not None:
        dest = np.asarray(dest, np.int64)
        if dest.shape != keys.shape:
            raise ValueError(f"dest shape {dest.shape} != keys shape {keys.shape}")
        if dest.size and (dest.min() < 0 or dest.max() >= n):
            raise ValueError(f"dest out of range [0, {n}) for the {axis_name} axis")
    k_pad, v_pad, d_pad = pad_partition(keys, values, n, dest=dest)
    per_shard = k_pad.shape[0] // n
    if capacity is None:
        # expected per-destination load + slack; grown below if a skewed
        # key distribution still overflows
        capacity = max(8, 2 * math.ceil(max(1, len(keys)) / (n * n)))
    overflow_total = 0
    attempts = 0
    while True:
        attempts += 1
        fn = _cached_exchange(mesh, values.shape[1], int(capacity), axis_name)
        k2, v2, ok, ov = fn(k_pad, v_pad, d_pad)
        ov = int(ov)
        if ov == 0:
            return {
                "keys": k2,
                "values": v2,
                "valid": ok,
                "capacity": int(capacity),
                "n_shards": n,
                "bucket_overflow_rows": overflow_total,
                "attempts": attempts,
            }
        overflow_total += ov
        if on_overflow == "raise":
            raise BucketOverflowError(
                f"bucketed exchange overflowed {ov} row(s) at capacity {capacity} "
                f"over {n} shard(s); grow capacity or use on_overflow='grow'"
            )
        if attempts > max_doublings:
            raise BucketOverflowError(
                f"bucketed exchange still overflowing after {attempts} attempts "
                f"(capacity {capacity}, {ov} rows over) — key distribution too "
                "skewed for the dense exchange"
            )
        capacity = max(int(capacity) * 2, int(capacity) + ov)


def make_partitioned_aggregate(
    mesh: Mesh,
    n_groups: int,
    width: int,
    axis_name: str = "data",
):
    """Distributed group-by sum: shard-local one-hot segment sums, then
    psum over the data axis (groups replicated; for huge group counts the
    same bucketed all_to_all as the join repartitions by group hash).

    Sentinel rows (gid outside [0, n_groups), e.g. the -1 padding written
    by `pad_groups`) are masked shard-locally and contribute nothing."""
    spec_g = P(axis_name)
    spec_v = P(axis_name, None)

    def local(gids, vals):
        ok = (gids >= 0) & (gids < n_groups)
        onehot = (gids[:, None] == jnp.arange(n_groups)[None, :]).astype(vals.dtype)
        onehot = onehot * ok[:, None].astype(vals.dtype)
        partial = jnp.einsum("rg,rw->gw", onehot, vals)
        return jax.lax.psum(partial, axis_name)

    fn = jax.shard_map(
        local, mesh=mesh, in_specs=(spec_g, spec_v), out_specs=P(None, None), check_vma=False
    )
    return jax.jit(fn)


# -- host-side helpers --------------------------------------------------------


def pad_partition(
    keys: np.ndarray,
    values: np.ndarray,
    n_shards: int,
    dest: Optional[np.ndarray] = None,
):
    """Pad host arrays so rows split evenly across the data axis.

    Padding rows carry the FILL sentinel in ``keys`` — the one invalid
    marker every shard-local consumer masks (the exchange discards them
    before sending, `_local_join` treats them as misses, the aggregate
    masks out-of-range gids), so the round trip is exact for ANY
    ``n_shards``: results over the padded arrays equal results over the
    originals. Returns (keys', values', dest') where dest' pads with 0
    (routing of a FILL row is irrelevant — it is never sent); dest' is a
    valid-everywhere array even when ``dest`` is None (hash routing
    placeholder) so shard_map signatures stay static."""
    rows = len(keys)
    keys = np.asarray(keys, np.int64)
    if rows and np.abs(keys).max() > KEY_LIMIT:
        raise ValueError(
            f"device exchange carries int32 keycodes; |key| must be <= {KEY_LIMIT}"
        )
    per = math.ceil(max(1, rows) / n_shards)
    total = per * n_shards
    k = np.full(total, int(FILL), np.int64)
    v = np.zeros((total, values.shape[1]), values.dtype)
    k[:rows] = keys
    v[:rows] = values
    d = np.zeros(total, np.int64)
    if dest is not None:
        d[:rows] = dest
    else:
        # match the device-side default hash so dest-less callers route the
        # same with or without padding
        kk = np.asarray(keys, np.int64)
        d[:rows] = ((kk.astype(np.uint32) * np.uint32(2654435761)) >> np.uint32(8)).astype(
            np.int64
        ) % n_shards
    return jnp.asarray(k), jnp.asarray(v), jnp.asarray(d)


def pad_groups(gids: np.ndarray, values: np.ndarray, n_shards: int):
    """Pad a group-by input so rows split evenly: padding rows carry gid -1,
    which `make_partitioned_aggregate` masks shard-locally."""
    rows = len(gids)
    per = math.ceil(max(1, rows) / n_shards)
    total = per * n_shards
    g = np.full(total, -1, np.int64)
    v = np.zeros((total, values.shape[1]), values.dtype)
    g[:rows] = gids
    v[:rows] = values
    return jnp.asarray(g), jnp.asarray(v)
