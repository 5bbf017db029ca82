"""Pallas TPU kernel: segmented aggregation (shared aggregate state update).

Grouped sum over group codes — the data-plane op behind SharedAggregateState
(§4.5). TPU adaptation: the reduction is expressed as a one-hot matmul so it
runs on the MXU: for each VMEM tile of rows, ``onehot(codes)^T @ values``
accumulates into the [G, V] output tile, which is revisited across the
sequential TPU grid (accumulate-in-place pattern).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import default_interpret

BLOCK_N = 512


def _seg_kernel(codes_ref, vals_ref, out_ref, *, n_groups: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    codes = codes_ref[...]
    vals = vals_ref[...]
    onehot = (codes[:, None] == jax.lax.iota(jnp.int32, n_groups)[None, :]).astype(
        vals.dtype
    )
    out_ref[...] += jnp.dot(onehot.T, vals, preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_groups", "interpret"))
def seg_aggregate(
    codes: jnp.ndarray,  # [N] int32 in [0, n_groups)
    values: jnp.ndarray,  # [N, V] float
    n_groups: int,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    n, v = values.shape
    pad = (-n) % BLOCK_N
    codes_p = jnp.pad(codes, (0, pad), constant_values=-1)  # -1 matches no group
    vals_p = jnp.pad(values, ((0, pad), (0, 0)))
    grid = (codes_p.shape[0] // BLOCK_N,)
    out = pl.pallas_call(
        functools.partial(_seg_kernel, n_groups=n_groups),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N,), lambda i: (i,)),
            pl.BlockSpec((BLOCK_N, v), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((n_groups, v), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_groups, v), jnp.float32),
        interpret=default_interpret() if interpret is None else interpret,
    )(codes_p, vals_p)
    return out
