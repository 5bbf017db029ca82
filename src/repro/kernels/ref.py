"""Pure-jnp oracles for the LM stack's Pallas kernels (the correctness
references the shape/dtype sweep tests assert against)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, window=None):
    bh, s, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    scores = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    qpos = jnp.arange(s)[:, None]
    kpos = jnp.arange(s)[None, :]
    ok = qpos >= kpos
    if window is not None:
        ok &= qpos - kpos < window
    scores = jnp.where(ok, scores, -1e30)
    a = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", a.astype(v.dtype), v).astype(q.dtype)


def linrec_ref(a, b):
    def step(h, ab):
        at, bt = ab
        h = at * h + bt
        return h, h

    a32 = a.astype(jnp.float32).swapaxes(0, 1)
    b32 = b.astype(jnp.float32).swapaxes(0, 1)
    h0 = jnp.zeros(a.shape[::2], jnp.float32) if False else jnp.zeros(
        (a.shape[0], a.shape[2]), jnp.float32
    )
    _, hs = jax.lax.scan(step, h0, (a32, b32))
    return hs.swapaxes(0, 1)
