"""Fused morsel stage chain as one device program (DESIGN.md §13).

One launch runs a morsel's entire packed stage chain — hash probe →
lens-word translation → grant-predicate visibility → interval-matrix stage
filter, for every stage in sequence — plus the build-sink word translation,
over device-resident state mirrors. This replaces the per-stage host
round-trips of the member-major pipeline (§11): the host hands the device
the morsel's packed ownership words and per-row probe keys once, and gets
back the final words, per-stage matched entry indices, per-stage
alive/matched counts, per-slot survivor counts, and the sink's
visibility/extent words. Everything that must stay bit-exact in float64
(aggregate accumulation, payload values) is reconstructed host-side from
the returned entry indices; the device only ever computes set membership,
so results are bit-identical to the NumPy member-major path.

Two representation choices make the full 64-slot lens space and float64
predicates device-servable without 64-bit device types (TPUs have neither
int64 nor float64 lanes; the repo never enables jax x64):

* every packed uint64 word — ownership bits, lens words, translation
  tables, sink masks — travels as a (lo, hi) uint32 pair
  (``core.visibility.split_words``), with the byte-table translation done
  as 8 byte-lane gathers ORing into both halves;
* float64 predicate operands (grant bounds, stage-filter bounds, payload
  columns they compare against) are encoded host-side through a *monotone
  total-order* map onto a (hi, lo) uint32 pair (``total_order_u32``), so
  unsigned lexicographic compares on device reproduce IEEE ``>=``/``<=``
  bit-exactly — including -0.0 == 0.0 (canonicalized before encoding) and
  NaN failing every constrained interval (NaN encodes outside the
  ±inf-bounded range on its sign's side).

The launch is shaped by a static, hashable *chain spec* (stage count, key
sourcing, grant/filter arity); the host assembles a flat canonical input
list (``input_kinds`` documents the traversal) and ``chain_launch``
dispatches through a cached jitted XLA program named ``graft_chain``. It is
not a Pallas kernel: its probes and mirror lookups are data-dependent 1-D
gathers into HBM-resident tables, which Mosaic does not lower and which
outgrow VMEM at SF 1 (see ``hash_probe``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .hash_probe import EMPTY, probe_slots

__all__ = [
    "total_order_u32",
    "total_order_bound",
    "input_kinds",
    "chain_launch",
]

_SIGN = np.uint64(0x8000000000000000)


def total_order_u32(vals: np.ndarray):
    """Monotone total-order encoding of float64 onto (hi, lo) uint32 pairs.

    ``a <= b`` (IEEE, finite or infinite) iff ``enc(a) <= enc(b)`` as
    unsigned 64-bit lexicographic pairs. ``-0.0`` is canonicalized to
    ``+0.0`` first so the two zeros encode equal; NaNs land strictly
    outside the [-inf, +inf] band on their sign's side, so every
    constrained interval compare rejects them — exactly IEEE semantics
    for ``(x >= lo) & (x <= hi)``."""
    v = np.ascontiguousarray(np.asarray(vals, dtype=np.float64) + 0.0)
    b = v.view(np.uint64)
    m = np.where((b & _SIGN) != 0, ~b, b | _SIGN)
    hi = (m >> np.uint64(32)).astype(np.uint32)
    lo = (m & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return hi, lo


def total_order_bound(x: float):
    """Scalar :func:`total_order_u32` for predicate bounds."""
    hi, lo = total_order_u32(np.array([x], dtype=np.float64))
    return int(hi[0]), int(lo[0])


# -- chain spec ---------------------------------------------------------------
#
# spec = (stages, sink)
#   stages: tuple of (key_mode, n_grants, grant_attrs, filt)
#     key_mode    -1  => per-row host-encoded int32 keys
#                 s>=0 => keys gathered from an entry-indexed int32 column
#                         mirror through stage s's matched entry index
#     n_grants    number of compiled grant predicates ORed into this
#                 stage's lens resolution (0 = grant-free)
#     grant_attrs union count of bound attrs across this stage's grants
#     filt        None, or (n_members, attr_srcs): an interval stage-filter
#                 matrix over attr_srcs, each -1 (per-row host pair) or an
#                 origin stage index (entry-indexed mirror pair)
#   sink: True when the chain ends in a build sink (emit per-row
#         beneficiary-visibility and extent words from the final bits)


def input_kinds(spec):
    """Canonical flat input traversal for a chain spec.

    Returns a list of ``"row"`` (morsel-length, row-sharded under a mesh)
    / ``"full"`` (replicated: tables, mirrors, parameter matrices)
    markers, in the exact order the host must assemble inputs and the
    chain program consumes them:

    ``bits_lo, bits_hi``, then per stage: key array; ``tkeys, tentry``;
    ``evis_lo, evis_hi``; ``ttab_lo, ttab_hi``; grants block
    (``eem_lo, eem_hi, gbit[G,2], gallow[G,2], gcon[G,A], glo[G,A,2],
    ghi[G,A,2]``, then per grant attr its mirror pair); filter block
    (per attr its value pair, then ``flo[M,A,2], fhi[M,A,2], fcon[M,A],
    fbit[M,2]``); finally the sink's two table pairs."""
    stages, sink = spec
    kinds = ["row", "row"]
    for key_mode, n_grants, grant_attrs, filt in stages:
        kinds.append("row" if key_mode == -1 else "full")
        kinds += ["full"] * 6
        if n_grants:
            kinds += ["full"] * 7
            kinds += ["full"] * (2 * grant_attrs)
        if filt is not None:
            _, srcs = filt
            for src in srcs:
                kinds += ["row", "row"] if src == -1 else ["full", "full"]
            kinds += ["full"] * 4
    if sink:
        kinds += ["full"] * 4
    return kinds


def _ge(xh, xl, bh, bl):
    """(xh, xl) >= (bh, bl), unsigned lexicographic — IEEE >= on
    total-order-encoded float64."""
    return (xh > bh) | ((xh == bh) & (xl >= bl))


def _le(xh, xl, bh, bl):
    return (xh < bh) | ((xh == bh) & (xl <= bl))


def _translate(bl, bh, tlo, thi):
    """8 byte-lane gathers: OR the split translation tables over every
    byte of the (lo, hi) word pair — ``core.visibility.translate_bits``
    on device."""
    olo = jnp.zeros_like(bl)
    ohi = jnp.zeros_like(bh)
    for b in range(4):
        idx = ((bl >> jnp.uint32(8 * b)) & jnp.uint32(0xFF)).astype(jnp.int32)
        olo = olo | tlo[b][idx]
        ohi = ohi | thi[b][idx]
    for b in range(4):
        idx = ((bh >> jnp.uint32(8 * b)) & jnp.uint32(0xFF)).astype(jnp.int32)
        olo = olo | tlo[4 + b][idx]
        ohi = ohi | thi[4 + b][idx]
    return olo, ohi


def _chain_body(spec, arrays):
    """The stage chain over the flat input list of :func:`input_kinds`;
    returns the output tuple :func:`chain_launch` documents."""
    stages, sink = spec
    it = iter(arrays)
    bl = next(it)
    bh = next(it)
    stage_in = []
    for key_mode, n_grants, grant_attrs, filt in stages:
        d = {"key": next(it)}
        d["tkeys"] = next(it)
        d["tentry"] = next(it)
        d["evlo"] = next(it)
        d["evhi"] = next(it)
        d["ttlo"] = next(it)
        d["tthi"] = next(it)
        if n_grants:
            d["eemlo"] = next(it)
            d["eemhi"] = next(it)
            d["gbit"] = next(it)
            d["gallow"] = next(it)
            d["gcon"] = next(it)
            d["glo"] = next(it)
            d["ghi"] = next(it)
            d["gattrs"] = [(next(it), next(it)) for _ in range(grant_attrs)]
        if filt is not None:
            _, srcs = filt
            d["fvals"] = [(next(it), next(it)) for _ in srcs]
            d["flo"] = next(it)
            d["fhi"] = next(it)
            d["fcon"] = next(it)
            d["fbit"] = next(it)
        stage_in.append(d)
    if sink:
        stlo = next(it)
        sthi = next(it)
        selo = next(it)
        sehi = next(it)

    entries = []
    stats = []
    for s, (key_mode, n_grants, grant_attrs, filt) in enumerate(stages):
        d = stage_in[s]
        alive = (bl | bh) != 0
        if key_mode == -1:
            keys = d["key"]
        else:
            e = entries[key_mode]
            ok = e >= 0
            keys = jnp.where(ok, d["key"][jnp.where(ok, e, 0)], jnp.int32(EMPTY))
        keys = jnp.where(alive, keys, jnp.int32(EMPTY))
        found = probe_slots(keys, d["tkeys"])
        matched = found >= 0
        entry = jnp.where(matched, d["tentry"][jnp.where(matched, found, 0)], -1)
        entries.append(entry)
        safe_e = jnp.where(matched, entry, 0)
        # lens gather (entry-indexed: rebuild-invariant) + translation
        vlo = jnp.where(matched, d["evlo"][safe_e], jnp.uint32(0))
        vhi = jnp.where(matched, d["evhi"][safe_e], jnp.uint32(0))
        plo, phi = _translate(vlo, vhi, d["ttlo"], d["tthi"])
        if n_grants:
            # compiled extent-scoped grants: emask ∩ allowed, then the
            # conjunction's interval bounds on total-order-encoded cols
            elo = jnp.where(matched, d["eemlo"][safe_e], jnp.uint32(0))
            ehi = jnp.where(matched, d["eemhi"][safe_e], jnp.uint32(0))
            gvals = [
                (gh[safe_e], gl[safe_e]) for gh, gl in d["gattrs"]
            ]
            for g in range(n_grants):
                gok = ((elo & d["gallow"][g, 0]) | (ehi & d["gallow"][g, 1])) != 0
                for a in range(grant_attrs):
                    xh, xl = gvals[a]
                    inb = _ge(xh, xl, d["glo"][g, a, 0], d["glo"][g, a, 1]) & _le(
                        xh, xl, d["ghi"][g, a, 0], d["ghi"][g, a, 1]
                    )
                    gok = gok & (inb | (d["gcon"][g, a] == 0))
                plo = plo | jnp.where(gok, d["gbit"][g, 0], jnp.uint32(0))
                phi = phi | jnp.where(gok, d["gbit"][g, 1], jnp.uint32(0))
        nbl = bl & jnp.where(matched, plo, jnp.uint32(0))
        nbh = bh & jnp.where(matched, phi, jnp.uint32(0))
        m_post = matched & ((nbl | nbh) != 0)
        bl, bh = nbl, nbh
        if filt is not None:
            n_members, srcs = filt
            vals = []
            for a, src in enumerate(srcs):
                vh, vl = d["fvals"][a]
                if src == -1:
                    vals.append((vh, vl))
                else:
                    e2 = entries[src]
                    s2 = jnp.where(e2 >= 0, e2, 0)
                    vals.append((vh[s2], vl[s2]))
            fblo = jnp.zeros_like(bl)
            fbhi = jnp.zeros_like(bh)
            fmlo = jnp.zeros_like(bl)
            fmhi = jnp.zeros_like(bh)
            for m in range(n_members):
                okm = None
                for a in range(len(srcs)):
                    xh, xl = vals[a]
                    inb = _ge(
                        xh, xl, d["flo"][m, a, 0], d["flo"][m, a, 1]
                    ) & _le(xh, xl, d["fhi"][m, a, 0], d["fhi"][m, a, 1])
                    oka = inb | (d["fcon"][m, a] == 0)
                    okm = oka if okm is None else okm & oka
                fblo = fblo | jnp.where(okm, d["fbit"][m, 0], jnp.uint32(0))
                fbhi = fbhi | jnp.where(okm, d["fbit"][m, 1], jnp.uint32(0))
                fmlo = fmlo | d["fbit"][m, 0]
                fmhi = fmhi | d["fbit"][m, 1]
            bl = bl & (~fmlo | fblo)
            bh = bh & (~fmhi | fbhi)
        stats.append(
            jnp.stack(
                [
                    jnp.sum(alive.astype(jnp.int32)),
                    jnp.sum(matched.astype(jnp.int32)),
                    jnp.sum(m_post.astype(jnp.int32)),
                ]
            )
        )

    slot_counts = jnp.stack(
        [
            jnp.sum(((bl >> jnp.uint32(j)) & jnp.uint32(1)).astype(jnp.int32))
            for j in range(32)
        ]
        + [
            jnp.sum(((bh >> jnp.uint32(j)) & jnp.uint32(1)).astype(jnp.int32))
            for j in range(32)
        ]
    )
    out = [bl, bh, *entries, jnp.stack(stats), slot_counts]
    if sink:
        out += [*_translate(bl, bh, stlo, sthi), *_translate(bl, bh, selo, sehi)]
    return tuple(out)


@functools.lru_cache(maxsize=None)
def _chain_fn(spec):
    def graft_chain(*arrays):
        with jax.named_scope("graft_chain"):
            return _chain_body(spec, arrays)

    return jax.jit(graft_chain)


@functools.lru_cache(maxsize=None)
def _chain_fn_sharded(spec, mesh, axis_name):
    """Shard-local chain launch (DESIGN.md §14): row inputs partitioned
    over the mesh's data axis, state mirrors replicated, one chain program
    per shard inside shard_map. Stats/slot-count outputs are additive over
    row shards and psum'd so every shard (and the host) sees the global
    totals; row outputs stay sharded. Row buffers are donated off-CPU —
    the packed words and keys are dead after the launch, so the device
    reuses their memory for the outputs."""
    from jax.sharding import PartitionSpec

    stages, sink = spec
    n_stages = len(stages)
    kinds = input_kinds(spec)
    row = PartitionSpec(axis_name)
    rep = PartitionSpec()
    stats_i = 2 + n_stages

    def local(*arrays):
        with jax.named_scope("graft_chain"):
            out = list(_chain_body(spec, arrays))
        out[stats_i] = jax.lax.psum(out[stats_i], axis_name)
        out[stats_i + 1] = jax.lax.psum(out[stats_i + 1], axis_name)
        return tuple(out)

    n_out = 2 + n_stages + 2 + (4 if sink else 0)
    out_specs = [row, row] + [row] * n_stages + [rep, rep]
    if sink:
        out_specs += [row] * 4
    assert len(out_specs) == n_out
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(row if k == "row" else rep for k in kinds),
        out_specs=tuple(out_specs),
        check_vma=False,
    )
    donate = ()
    if jax.default_backend() != "cpu":
        # mirror the scatter-path donation gating: CPU jax warns and
        # ignores donation, so only donate on real accelerators
        donate = tuple(i for i, k in enumerate(kinds) if k == "row")
    return jax.jit(fn, donate_argnums=donate)


def chain_launch(spec, arrays, *, mesh=None, axis_name="data"):
    """Dispatch one fused stage-chain launch.

    ``arrays`` must follow :func:`input_kinds`'s traversal, with every
    "row" array padded to a common power-of-two length (dead padding rows
    carry zero ownership words and EMPTY keys, so they contribute to no
    output). Returns the raw output tuple:
    ``(bits_lo, bits_hi, entry_0..entry_{S-1}, stats[S,3], slots[64]``
    ``[, sink_vis_lo, sink_vis_hi, sink_em_lo, sink_em_hi])``.
    ``stats[s]`` is ``(alive_in, matched, matched_visible)`` for stage s.

    With ``mesh`` set, the launch runs shard-locally inside shard_map over
    the mesh's ``axis_name`` axis (§14): row arrays must be divisible by
    the axis size (the power-of-two padding guarantees this for power-of-
    two meshes), row outputs come back in shard order, and stats/slot
    counts are global. A 1-device mesh is bit-identical to the unsharded
    launch."""
    if mesh is None:
        return _chain_fn(spec)(*arrays)
    return _chain_fn_sharded(spec, mesh, axis_name)(*arrays)
