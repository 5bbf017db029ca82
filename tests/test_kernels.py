"""Device programs as the data plane runs them, and the LM kernels' sweeps.

The probe cases drive ``PallasBackend`` — ``graft_chain_stage`` over the
state's device mirrors, the table built by ``_insert_keys`` /
``_batch_insert`` — against ``ReferenceBackend`` on the same
``SharedHashBuildState``. The LM kernels assert_allclose against the
``ref.py`` oracles."""

from collections import Counter

import numpy as np
import pytest
import jax.numpy as jnp

from repro.api.backends import PallasBackend, ReferenceBackend
from repro.core.descriptors import StateSignature
from repro.core.state import SharedHashBuildState
from repro.kernels import default_interpret, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.linrec import linrec

QID, OTHER = 7, 8


def _state():
    sig = StateSignature("hash_build", ("t", ("k",), ("x",)))
    return SharedHashBuildState(1, sig, ("k",), ("x",))


def _insert(state, keys, start, vis):
    """Insert ``keys`` as entries ``start..``, visible through ``vis``."""
    dids = np.arange(start, start + len(keys), dtype=np.int64)
    state.insert_or_mark(
        dids, keys, {"k": keys.astype(float), "x": dids.astype(float)}, vis,
        np.zeros(len(keys), np.uint64),
    )


def _pairs_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("n_keys,n_probe", [(100, 512), (5000, 2048), (20000, 4096)])
@pytest.mark.parametrize("vis_density", [1.0, 0.5])
def test_hash_probe_sweep(n_keys, n_probe, vis_density):
    rng = np.random.default_rng(n_keys + n_probe)
    keys = rng.choice(1 << 20, n_keys, replace=False).astype(np.int64)
    state = _state()
    bit = {q: np.uint64(1) << np.uint64(state.slots.get(q)) for q in (QID, OTHER)}
    vis = np.where(rng.random(n_keys) < vis_density, bit[QID], bit[OTHER]).astype(np.uint64)
    _insert(state, keys, 0, vis)
    pk = np.concatenate(
        [keys[: n_probe // 2], rng.choice(1 << 20, n_probe - n_probe // 2) + (1 << 20)]
    ).astype(np.int64)
    pal, refb = PallasBackend(), ReferenceBackend()
    want = refb.probe_launch(state, pk).collect()
    assert len(want[0]) == min(n_probe // 2, n_keys)
    _pairs_equal(pal.probe_launch(state, pk).collect(), want)
    probe_idx, entry_idx, words = pal.probe_visible_multi_launch(state, pk).collect()
    _pairs_equal((probe_idx, entry_idx), want)
    np.testing.assert_array_equal(words, state.vis.data[entry_idx])
    if vis_density < 1.0:
        visible = state.visible_mask(QID, want[1])
        assert 0 < visible.sum() < len(visible)
        got = pal.probe_visible_launch(state, pk, QID).collect()
        _pairs_equal(got, (want[0][visible], want[1][visible]))
    assert refb.probe_visible_launch(state, pk, QID) is None
    assert pal.fallback_probes == 0 and pal.kernel_probes == 2 + (vis_density < 1.0)


@pytest.mark.parametrize("n_keys", [64, 1000, 5000])
def test_batch_insert_roundtrip(n_keys):
    """Keys inserted through the state in four batches — a fresh table,
    rebuilds as it outgrows its capacity, and appends into it — all probe
    back to their entries after each batch. While the device serves the
    state, its host table holds exactly the inserted keys at their
    entries; a probe cluster longer than ``MAX_PROBE`` makes the table
    decline for good, and the probe is counted as a ``capacity``
    fallback."""
    rng = np.random.default_rng(n_keys)
    keys = rng.choice(1 << 20, n_keys, replace=False).astype(np.int64)
    state, pal = _state(), PallasBackend()
    vis = np.full(n_keys, np.uint64(1) << np.uint64(state.slots.get(QID)))
    cuts = [0, n_keys // 8, n_keys // 2, 3 * n_keys // 4, n_keys]
    for lo, hi in zip(cuts, cuts[1:]):
        _insert(state, keys[lo:hi], lo, vis[lo:hi])
        declined = pal.fallback_probes
        probe_idx, entry_idx = pal.probe_launch(state, keys[:hi]).collect()
        np.testing.assert_array_equal(probe_idx, np.arange(hi))
        np.testing.assert_array_equal(entry_idx, np.arange(hi))
        table = pal._tables[state]
        assert pal.fallback_probes == declined + table.bad
        if table.bad:
            continue
        placed = table.slot_entry >= 0
        assert placed.sum() == hi
        np.testing.assert_array_equal(table.tkeys[placed], keys[table.slot_entry[placed]])
    assert pal.kernel_probes > 0
    assert pal.stats()["fallback_capacity"] == pal.fallback_probes


@pytest.mark.parametrize("where,reason", [("probe", "keyrange"), ("state", "capacity")])
def test_keys_outside_key_limit_decline(where, reason):
    """Keycodes outside ``_KEY_LIMIT`` — in the probe or in the state —
    are declined: the probe runs on the reference path, and the fallback
    is counted under its reason, on the backend and in the engine's
    counters; the lens probe declines outright."""
    big = PallasBackend._KEY_LIMIT + 5
    state_keys = np.array([3, 9, 27] + ([big] if where == "state" else []), np.int64)
    state = _state()
    vis = np.full(len(state_keys), np.uint64(1) << np.uint64(state.slots.get(QID)))
    _insert(state, state_keys, 0, vis)
    pk = np.array([9, big, 4, 3], np.int64)
    pal, counters = PallasBackend(), Counter()
    got = pal.probe_launch(state, pk, counters=counters).collect()
    _pairs_equal(got, ReferenceBackend().probe_launch(state, pk).collect())
    assert pal.probe_visible_launch(state, pk, QID) is None
    assert pal.fallback_probes == 1 and pal.kernel_probes == 0
    assert pal.stats()[f"fallback_{reason}"] == 1
    assert counters == Counter({f"fallback_probes_{reason}": 1})


@pytest.mark.parametrize("n,v,g", [(100, 1, 8), (3000, 8, 64), (10000, 4, 200)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_segment_sum_sweep(n, v, g, dtype):
    """``PallasBackend.segment_sum`` against a plain per-group float64 sum
    and count."""
    rng = np.random.default_rng(n + v + g)
    codes = rng.integers(0, g, n).astype(np.int64)
    vals = rng.normal(size=(n, v)).astype(dtype)
    backend = PallasBackend()
    for j in range(v):
        want = np.zeros(g)
        for c, x in zip(codes, vals[:, j].astype(np.float64)):
            want[c] += x
        np.testing.assert_allclose(backend.segment_sum(codes, vals[:, j], g), want, rtol=1e-12)
    counts = np.array([(codes == k).sum() for k in range(g)], np.float64)
    np.testing.assert_array_equal(backend.segment_sum(codes, None, g), counts)


@pytest.mark.parametrize("bh,s,dh", [(1, 128, 64), (2, 256, 128), (3, 384, 64)])
@pytest.mark.parametrize("window", [None, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(bh, s, dh, window, dtype):
    rng = np.random.default_rng(bh * s + dh)
    q = jnp.asarray(rng.normal(size=(bh, s, dh)), dtype)
    k = jnp.asarray(rng.normal(size=(bh, s, dh)), dtype)
    v = jnp.asarray(rng.normal(size=(bh, s, dh)), dtype)
    got = np.asarray(
        flash_attention(q, k, v, window=window, interpret=default_interpret()), np.float32
    )
    want = np.asarray(ref.flash_attention_ref(q, k, v, window=window), np.float32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("b,s,d", [(1, 256, 128), (2, 512, 256), (3, 1024, 128)])
def test_linrec_sweep(b, s, d):
    rng = np.random.default_rng(b + s + d)
    a = jnp.asarray(rng.uniform(0.7, 0.999, size=(b, s, d)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(b, s, d)) * 0.2, jnp.float32)
    got = np.asarray(linrec(a, bb, interpret=default_interpret()))
    want = np.asarray(ref.linrec_ref(a, bb))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_linrec_matches_rglru_semantics():
    """The kernel computes the same recurrence the RG-LRU layer uses."""
    from repro.models.recurrent import rg_lru

    rng = np.random.default_rng(0)
    p = {
        "w_a": jnp.asarray(rng.normal(size=(128, 128)) * 0.05, jnp.float32),
        "w_x": jnp.asarray(rng.normal(size=(128, 128)) * 0.05, jnp.float32),
        "lam": jnp.asarray(rng.uniform(-4, -2, 128), jnp.float32),
    }
    x = jnp.asarray(rng.normal(size=(2, 256, 128)) * 0.3, jnp.float32)
    from repro.models.recurrent import _rg_lru_gates

    a, b = _rg_lru_gates(p, x)
    h_kernel = np.asarray(linrec(a, b, interpret=default_interpret()))
    h_layer = np.asarray(rg_lru(p, x), np.float32)
    np.testing.assert_allclose(h_kernel, h_layer, rtol=2e-4, atol=2e-4)
