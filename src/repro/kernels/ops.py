"""Public wrappers for the data plane's device programs and kernels.

The probe is a jitted XLA program on every platform. The Pallas kernels
take ``interpret`` from the platform (``default_interpret``): compiled on
the TPU, interpreted elsewhere.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from . import default_interpret
from .flash_attention import flash_attention
from .hash_probe import EMPTY, hash_build_insert, hash_probe_lens
from .linrec import linrec
from .seg_aggregate import seg_aggregate


def build_hash_table(keys: np.ndarray, vis: np.ndarray, load: float = 0.5):
    """Host-side open-addressing build (the engine's build path is
    append-only; the probe kernel consumes this SoA layout). Returns
    (table_keys, table_vis, table_entry_idx)."""
    n = len(keys)
    cap = 1 << max(int(np.ceil(np.log2(max(n / load, 8)))), 3)
    mask = cap - 1
    tk = np.full(cap, int(EMPTY), np.int32)
    tv = np.zeros(cap, np.uint32)
    te = np.full(cap, -1, np.int32)
    pos = (keys.astype(np.uint64) * np.uint64(2654435761)).astype(np.int64) & mask
    for i in range(n):
        p = int(pos[i])
        while tk[p] != int(EMPTY):
            p = (p + 1) & mask
        tk[p] = keys[i]
        tv[p] = vis[i]
        te[p] = i
    return jnp.asarray(tk), jnp.asarray(tv), jnp.asarray(te)


def build_insert(keys, capacity=None, interpret=None):
    """In-kernel batch build of the open-addressing table (the device-side
    counterpart of ``build_hash_table``). Returns (table_keys, table_entry,
    ok) — ``ok[0] == 0`` flags duplicate keys / over-long probe chains."""
    n = len(keys)
    if capacity is None:
        # default to <=25% load: keeps clusters well inside the kernel's
        # bounded probe scan (callers managing their own tables pass cap)
        capacity = 8
        while capacity < 4 * n:
            capacity *= 2
    return hash_build_insert(
        jnp.asarray(keys, jnp.int32), capacity=capacity, interpret=interpret
    )


def probe(probe_keys, table_keys, table_vis, query_mask):
    return hash_probe_lens(
        jnp.asarray(probe_keys, jnp.int32),
        table_keys,
        table_vis,
        jnp.asarray(query_mask, jnp.uint32).reshape(1),
    )


def segmented_sum(codes, values, n_groups, interpret=None):
    return seg_aggregate(
        jnp.asarray(codes, jnp.int32), jnp.asarray(values), n_groups, interpret=interpret
    )


def attention(q, k, v, window=None, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return flash_attention(q, k, v, window=window, interpret=interpret)


def linear_recurrence(a, b, interpret=None):
    interpret = default_interpret() if interpret is None else interpret
    return linrec(a, b, interpret=interpret)
