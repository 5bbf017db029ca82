"""Hash-join probe with a fused per-query state lens.

The paper's hot spot (§4.3): probe a shared open-addressing hash-build state
and emit, per probe key, the matching entry index — but only when the entry
is visible to the probing query (visibility bitmask AND query mask), i.e.
the per-query state lens is fused into the probe.

Device form (DESIGN.md §2/§7): the probes are jitted XLA programs over
HBM-resident tables — a bounded linear-probe ``while_loop`` of fully
vectorized gathers+compares (``probe_slots``), no pointer chasing. They are
not Pallas kernels: Mosaic lowers only 2-D gathers, and a kernel's operands
must fit VMEM, while one SF-1 state's table is 4M slots (16 MiB per int32
array). The same programs run on every platform, so the CPU tests exercise
what the chip runs. Each runs under the ``graft_probe`` name scope.

Unique-key tables only (FK-keyed dimension states); the engine routes
multi-match states through the reference path.

``hash_probe_lens_multi64`` is the multi-member variant (DESIGN.md §11/§13):
one launch returns, per probe key, the matched slot AND the matched entry's
packed visibility word — the per-row ownership mask of every probing member
at once. The host translates the word from state-slot space into pipeline
ownership bits (``core.visibility.translate_bits``), so per-morsel device
cost is independent of how many queries share the probe.

``hash_build_insert`` is the batch-insert companion, a Pallas kernel: one
call builds the whole open-addressing table from a key batch (linear-probe
placement, bounded by ``MAX_PROBE``; duplicate keys or over-long clusters
clear the ``ok`` flag so the caller can fall back). The placement loop is
sequential in-kernel, so the backend keeps it opt-in.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import default_interpret

MAX_PROBE = 16
EMPTY = -0x7FFFFFFF
MULT = 2654435761


def _hash(keys: jnp.ndarray, mask) -> jnp.ndarray:
    return (keys.astype(jnp.uint32) * jnp.uint32(MULT)).astype(jnp.int32) & mask


def probe_slots(keys: jnp.ndarray, tkeys: jnp.ndarray) -> jnp.ndarray:
    """Linear-probe scan: per key, the table slot holding it (-1 = absent).

    ``EMPTY`` keys (padding, dead rows) match nothing. The scan stops at an
    empty slot, after ``MAX_PROBE`` slots, or once every key has resolved."""
    cap_mask = jnp.int32(tkeys.shape[0] - 1)
    pos = _hash(keys, cap_mask)
    found0 = jnp.full(keys.shape, -1, jnp.int32)
    done0 = keys == jnp.int32(EMPTY)

    def cond(carry):
        i, _pos, _found, done = carry
        return (i < MAX_PROBE) & jnp.any(~done)

    def body(carry):
        i, pos, found, done = carry
        slot_keys = tkeys[pos]
        hit = (slot_keys == keys) & ~done
        empty = (slot_keys == jnp.int32(EMPTY)) & ~done
        found = jnp.where(hit, pos, found)
        done = done | hit | empty
        pos = (pos + 1) & cap_mask
        return i + 1, pos, found, done

    _, _, found, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), pos, found0, done0)
    )
    return found


@jax.jit
def hash_probe_lens(
    probe_keys: jnp.ndarray,  # [N] int32
    table_keys: jnp.ndarray,  # [T] int32, power-of-two T, EMPTY sentinel
    table_vis: jnp.ndarray,  # [T] uint32 per-slot visibility words
    query_mask: jnp.ndarray,  # [1] uint32
) -> jnp.ndarray:
    """Single-query probe over slot-indexed visibility words: the matched
    table slot per probe key (-1 = no visible match)."""
    with jax.named_scope("graft_probe"):
        slot = probe_slots(probe_keys, table_keys)
        hit = slot >= 0
        vis = (table_vis[jnp.where(hit, slot, 0)] & query_mask[0]) != 0
        return jnp.where(hit & vis, slot, -1)


@jax.jit
def hash_probe_lens64(
    probe_keys: jnp.ndarray,  # [N] int32
    table_keys: jnp.ndarray,  # [T] int32, power-of-two T, EMPTY sentinel
    table_entry: jnp.ndarray,  # [T] int32 slot -> entry index
    evis_lo: jnp.ndarray,  # [E] uint32 entry-indexed visibility low words
    evis_hi: jnp.ndarray,  # [E] uint32 entry-indexed visibility high words
    query_mask: jnp.ndarray,  # [2] uint32 (lo, hi) lens mask
) -> jnp.ndarray:
    """Single-query fused-lens probe over the full 64-slot space
    (DESIGN.md §13): visibility words are entry-indexed uint32 pairs, so
    any slot 0..63 resolves on device and rebuilds leave the mirror
    untouched. Returns the matched table slot per probe key (-1 = no
    visible match)."""
    with jax.named_scope("graft_probe"):
        slot = probe_slots(probe_keys, table_keys)
        hit = slot >= 0
        entry = jnp.where(hit, table_entry[jnp.where(hit, slot, 0)], 0)
        vis = (
            (evis_lo[entry] & query_mask[0]) | (evis_hi[entry] & query_mask[1])
        ) != 0
        return jnp.where(hit & vis, slot, -1)


@jax.jit
def hash_probe_lens_multi64(
    probe_keys: jnp.ndarray,  # [N] int32
    table_keys: jnp.ndarray,  # [T] int32, power-of-two T, EMPTY sentinel
    table_entry: jnp.ndarray,  # [T] int32 slot -> entry index
    evis_lo: jnp.ndarray,  # [E] uint32 entry-indexed visibility low words
    evis_hi: jnp.ndarray,  # [E] uint32 entry-indexed visibility high words
):
    """Multi-member probe returning the full uint64 lens word as (lo, hi)
    uint32 halves (DESIGN.md §13), served for all 64 slots from
    entry-indexed (rebuild-invariant) mirrors. The pair stream is
    pre-visibility and identical to ``probe``."""
    with jax.named_scope("graft_probe"):
        found = probe_slots(probe_keys, table_keys)
        matched = found >= 0
        entry = jnp.where(matched, table_entry[jnp.where(matched, found, 0)], 0)
        wlo = jnp.where(matched, evis_lo[entry], jnp.uint32(0))
        whi = jnp.where(matched, evis_hi[entry], jnp.uint32(0))
        return found, wlo, whi


def _insert_kernel(keys_ref, tkeys_ref, tentry_ref, ok_ref):
    cap = tkeys_ref.shape[0]
    cap_mask = jnp.int32(cap - 1)
    n = keys_ref.shape[0]
    tkeys_ref[...] = jnp.full((cap,), jnp.int32(EMPTY), jnp.int32)
    tentry_ref[...] = jnp.full((cap,), -1, jnp.int32)

    def insert_one(i, ok):
        key = keys_ref[i]
        home = (key.astype(jnp.uint32) * jnp.uint32(MULT)).astype(jnp.int32) & cap_mask

        def step(h, carry):
            pos, state = carry  # state: 0=searching, 1=slot found, 2=duplicate
            slot = (home + h) & cap_mask
            cur = tkeys_ref[slot]
            searching = state == 0
            hit_empty = searching & (cur == jnp.int32(EMPTY))
            hit_dup = searching & (cur == key)
            pos = jnp.where(hit_empty, slot, pos)
            state = jnp.where(hit_empty, 1, jnp.where(hit_dup, 2, state))
            return pos, state

        pos, state = jax.lax.fori_loop(
            0, MAX_PROBE, step, (jnp.int32(0), jnp.int32(0))
        )
        # unconditional read-modify-write keeps the store branch-free
        place = state == 1
        tkeys_ref[pos] = jnp.where(place, key, tkeys_ref[pos])
        tentry_ref[pos] = jnp.where(place, i.astype(jnp.int32), tentry_ref[pos])
        return ok & place.astype(jnp.int32)

    ok_ref[0] = jax.lax.fori_loop(0, n, insert_one, jnp.int32(1))


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def hash_build_insert(
    keys: jnp.ndarray,  # [N] int32, no EMPTY values
    capacity: int,  # power of two, >= 2 * N
    *,
    interpret: Optional[bool] = None,
):
    """Batch-insert ``keys`` into a fresh open-addressing table.

    Returns ``(table_keys, table_entry, ok)``: the slab layout
    ``hash_probe_lens`` consumes (entry i of the batch at its linear-probe
    slot), with ``ok[0] == 0`` when a duplicate key or a probe chain
    longer than ``MAX_PROBE`` makes the table unservable."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return pl.pallas_call(
        _insert_kernel,
        out_shape=[
            jax.ShapeDtypeStruct((capacity,), jnp.int32),
            jax.ShapeDtypeStruct((capacity,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=default_interpret() if interpret is None else interpret,
    )(keys)
