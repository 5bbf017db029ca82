"""Hash-join probe of a shared open-addressing hash-build state (§4.3).

The paper's hot spot: per probe key, the table slot holding it. Device
form (DESIGN.md §2/§7): a bounded linear-probe ``while_loop`` of fully
vectorized gathers+compares over an HBM-resident table (``probe_slots``),
no pointer chasing, inside a jitted XLA program. It is not a Pallas
kernel: Mosaic lowers only 2-D gathers, and a kernel's operands must fit
VMEM, while one SF-1 state's table is 4M slots (16 MiB per int32 array).
The same program runs on every platform, so the CPU tests exercise what
the chip runs.

Unique-key tables only (FK-keyed dimension states); the engine routes
multi-match states through the reference path. The host builds the
table (``api/backends.py`` ``PallasBackend._batch_insert``, the same
hash, ``MULT``, and probe bound, ``MAX_PROBE``).

The engine probes through ``probe_slots`` inside the chain stage program
(``kernels/fused_chain.py`` ``graft_chain_stage``, DESIGN.md §13), which
serves the single-query lens, the multi-member probe (each matched
entry's packed visibility word) and every stage of a fused chain.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

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
