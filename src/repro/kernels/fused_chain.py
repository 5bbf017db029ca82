"""Morsel stage chain on the device (DESIGN.md §13).

The engine runs a morsel's packed stage chain — hash probe → lens-word
translation → grant-predicate visibility → interval-matrix stage filter,
for every stage in sequence — plus the build-sink word translation, over
device-resident state mirrors, as one ``graft_chain_stage`` launch per
stage and a ``graft_chain_sink`` launch, queued back to back on shapes
from a bounded ladder (the end of this module); the mesh check of
``launch/db_plane.py`` runs the stage shard-locally
(``graft_chain_stage_sharded``). This replaces the per-stage host
round-trips of the member-major pipeline (§11): the
host hands the device the morsel's packed ownership words and per-row
probe keys once, and gets
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
  (``core.visibility.split_words``), with the translation done as one
  vector select per slot bit ORing that slot's target into both halves;
* float64 predicate operands (grant bounds, stage-filter bounds, payload
  columns they compare against) are encoded host-side through a *monotone
  total-order* map onto a (hi, lo) uint32 pair (``total_order_u32``), so
  unsigned lexicographic compares on device reproduce IEEE ``>=``/``<=``
  bit-exactly — including -0.0 == 0.0 (canonicalized before encoding) and
  NaN failing every constrained interval (NaN encodes outside the
  ±inf-bounded range on its sign's side).

The programs are jitted XLA programs under the name ``graft_chain``. They
are not Pallas kernels: their probes and mirror lookups are data-dependent
1-D gathers into HBM-resident tables, which Mosaic does not lower and which
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
    "graft_chain_stage",
    "graft_chain_stage_sharded",
    "graft_chain_sink",
    "graft_fill",
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


def _ge(xh, xl, bh, bl):
    """(xh, xl) >= (bh, bl), unsigned lexicographic — IEEE >= on
    total-order-encoded float64."""
    return (xh > bh) | ((xh == bh) & (xl >= bl))


def _le(xh, xl, bh, bl):
    return (xh < bh) | ((xh == bh) & (xl <= bl))


def _translate(bl, bh, tlo, thi):
    """``core.visibility.translate_bits`` on device: OR, over every bit j
    set in the (lo, hi) word pair, of slot j's target word, which the split
    byte tables hold at ``[j // 8, 1 << (j % 8)]``. One select per slot bit
    over the rows, ORed together by a reduction over the 32 bit positions
    of each half, so the translation gathers nothing per row."""
    j = np.arange(64)
    tl, th = tlo[j // 8, 1 << (j % 8)], thi[j // 8, 1 << (j % 8)]
    shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
    on_lo = jnp.uint32(0) - ((bl[None] >> shifts) & jnp.uint32(1))  # slots 0..31
    on_hi = jnp.uint32(0) - ((bh[None] >> shifts) & jnp.uint32(1))  # slots 32..63
    return jax.lax.reduce(
        ((on_lo & tl[:32, None]) | (on_hi & tl[32:, None]),
         (on_lo & th[:32, None]) | (on_hi & th[32:, None])),
        (np.uint32(0), np.uint32(0)),
        lambda x, y: (x[0] | y[0], x[1] | y[1]),
        (0,),
    )


# -- one stage per launch, on a bounded shape ladder ---------------------------
#
# The engine launches the chain one stage at a time (DESIGN.md §13): each
# stage is one ``graft_chain_stage`` program whose static shape is fixed by
# two numbers alone, the morsel's padded row count N and the stage state's
# entry capacity E (its probe table holds 2E slots). Grants, stage-filter
# members and their bound attrs ride in fixed-size blocks (G_MAX, M_MAX,
# A_MAX) that a dynamic flag switches off, and the entry columns a later
# stage reads (payload-origin keys and filter operands) leave the stage as
# X_MAX exported rows. So no member, slot or grant count reaches a shape,
# and a session can compile every program it will launch when it opens.

#: compiled grants one stage launch carries: one per member slot
G_MAX = 64
#: fused stage-filter members one stage launch carries
M_MAX = 64
#: bound attrs a stage's grants, or its filter, may constrain
A_MAX = 4
#: entry columns a stage exports, as rows, to the stages after it
X_MAX = 4
#: the flags vector: has grants, has filter, per grant attr "gathered", per
#: export "on", per filter attr the export slots of its own-stage operand
#: (hi then lo; -1 = the operand comes in as rows), and "entries after the
#: lens" (a row whose words the lens cleared reports entry -1)
N_FLAGS = 2 + A_MAX + X_MAX + 2 * A_MAX + 1
F_GATTR = 2
F_EXPORT = F_GATTR + A_MAX
F_FOWN = F_EXPORT + X_MAX
F_EPOST = F_FOWN + 2 * A_MAX


def _or_over_rows(vals):
    """Bitwise OR over axis 0 of a uint32 matrix."""
    return jax.lax.reduce(vals, np.uint32(0), jax.lax.bitwise_or, (0,))


def _stage_body(bl, bh, keys, tkeys, tentry, evlo, evhi, ttlo, tthi, flags,
                eemlo, eemhi, gbit, gallow, gcon, glo, ghi, gattrs,
                fvals, flo, fhi, fcon, fbit, exports):
    zero = jnp.zeros_like(bl)
    alive = (bl | bh) != 0
    keys = jnp.where(alive, jax.lax.bitcast_convert_type(keys, jnp.int32), jnp.int32(EMPTY))
    found = probe_slots(keys, tkeys)
    matched = found >= 0
    entry = jnp.where(matched, tentry[jnp.where(matched, found, 0)], -1)
    safe_e = jnp.where(matched, entry, 0)
    # lens gather (entry-indexed: rebuild-invariant) + translation
    vlo = jnp.where(matched, evlo[safe_e], jnp.uint32(0))
    vhi = jnp.where(matched, evhi[safe_e], jnp.uint32(0))
    plo, phi = _translate(vlo, vhi, ttlo, tthi)

    def gather_if(on, col):
        return jax.lax.cond(on, lambda: col[safe_e], lambda: zero)

    def with_grants(p):
        # compiled extent-scoped grants, one row of the [G, N] block each:
        # emask ∩ allowed, then the conjunction's interval bounds on
        # total-order-encoded entry columns
        plo, phi = p
        elo = jnp.where(matched, eemlo[safe_e], jnp.uint32(0))
        ehi = jnp.where(matched, eemhi[safe_e], jnp.uint32(0))
        gok = ((elo[None] & gallow[:, 0, None]) | (ehi[None] & gallow[:, 1, None])) != 0
        for a in range(A_MAX):
            on = flags[F_GATTR + a] != 0
            xh, xl = gather_if(on, gattrs[a][0]), gather_if(on, gattrs[a][1])
            inb = _ge(xh[None], xl[None], glo[:, a, 0, None], glo[:, a, 1, None]) & _le(
                xh[None], xl[None], ghi[:, a, 0, None], ghi[:, a, 1, None]
            )
            gok = gok & (inb | (gcon[:, a, None] == 0))
        plo = plo | _or_over_rows(jnp.where(gok, gbit[:, 0, None], jnp.uint32(0)))
        phi = phi | _or_over_rows(jnp.where(gok, gbit[:, 1, None], jnp.uint32(0)))
        return plo, phi

    plo, phi = jax.lax.cond(flags[0] != 0, with_grants, lambda p: p, (plo, phi))
    nbl = bl & jnp.where(matched, plo, jnp.uint32(0))
    nbh = bh & jnp.where(matched, phi, jnp.uint32(0))
    m_post = matched & ((nbl | nbh) != 0)
    xo = [gather_if(flags[F_EXPORT + x] != 0, exports[x]) for x in range(X_MAX)]

    def with_filter(b):
        # interval stage filter, one row of the [M, N] block per member;
        # an operand from this stage's own payload is one of its exports
        bl, bh = b
        xs = jnp.stack(xo)
        okm = None
        for a in range(A_MAX):
            oh, ol = flags[F_FOWN + 2 * a], flags[F_FOWN + 2 * a + 1]
            xh = jnp.where(oh >= 0, xs[jnp.maximum(oh, 0)], fvals[a][0])
            xl = jnp.where(ol >= 0, xs[jnp.maximum(ol, 0)], fvals[a][1])
            inb = _ge(xh[None], xl[None], flo[:, a, 0, None], flo[:, a, 1, None]) & _le(
                xh[None], xl[None], fhi[:, a, 0, None], fhi[:, a, 1, None]
            )
            oka = inb | (fcon[:, a, None] == 0)
            okm = oka if okm is None else okm & oka
        fblo = _or_over_rows(jnp.where(okm, fbit[:, 0, None], jnp.uint32(0)))
        fbhi = _or_over_rows(jnp.where(okm, fbit[:, 1, None], jnp.uint32(0)))
        fmlo = _or_over_rows(fbit[:, 0])
        fmhi = _or_over_rows(fbit[:, 1])
        return bl & (~fmlo | fblo), bh & (~fmhi | fbhi)

    nbl, nbh = jax.lax.cond(flags[1] != 0, with_filter, lambda b: b, (nbl, nbh))
    stats = jnp.stack(
        [
            jnp.sum(alive.astype(jnp.int32)),
            jnp.sum(matched.astype(jnp.int32)),
            jnp.sum(m_post.astype(jnp.int32)),
        ]
    )
    entry = jnp.where((flags[F_EPOST] != 0) & ~m_post, -1, entry)
    return (nbl, nbh, entry, stats, *xo)


@jax.jit
def graft_chain_stage(bl, bh, keys, tkeys, tentry, evlo, evhi, ttlo, tthi, flags,
                      eemlo, eemhi, gbit, gallow, gcon, glo, ghi, gattrs,
                      fvals, flo, fhi, fcon, fbit, exports):
    """One probe stage of a morsel's chain over row length N and a state
    of entry capacity E (probe table 2E slots).

    Rows (length N): ``bl, bh`` the packed ownership words as uint32 halves;
    ``keys`` the probe keys as int32 bit patterns in uint32 (EMPTY = no key);
    ``fvals`` A_MAX (hi, lo) pairs of total-order-encoded filter operands.
    State (length 2E or E): ``tkeys, tentry`` the open-addressing table and
    its slot -> entry map; ``evlo, evhi`` and ``eemlo, eemhi`` the
    entry-indexed visibility and provenance words; ``gattrs`` A_MAX (hi, lo)
    pairs of total-order-encoded entry columns the grants bound;
    ``exports`` X_MAX uint32 entry columns to gather for later stages.
    Parameters: ``ttlo, tthi`` [8, 256] lens translation tables; ``flags``
    int32[N_FLAGS]; grants ``gbit, gallow`` [G, 2], ``gcon`` [G, A],
    ``glo, ghi`` [G, A, 2]; filter ``flo, fhi`` [M, A, 2], ``fcon`` [M, A],
    ``fbit`` [M, 2]. Padding grants allow nothing, padding members carry no
    bit, padding attrs are unconstrained.

    Returns ``(bits_lo, bits_hi, entry, stats[3], export_0..X_MAX-1)``:
    the words after the stage, the matched entry per row (-1 = none; with
    ``flags[F_EPOST]`` also -1 where the lens cleared the row's words),
    ``(alive_in, matched, matched_visible)``, and the exported rows."""
    with jax.named_scope("graft_chain"):
        return _stage_body(bl, bh, keys, tkeys, tentry, evlo, evhi, ttlo, tthi, flags,
                           eemlo, eemhi, gbit, gallow, gcon, glo, ghi, gattrs,
                           fvals, flo, fhi, fcon, fbit, exports)


@functools.lru_cache(maxsize=None)
def graft_chain_stage_sharded(mesh, axis_name: str = "data"):
    """:func:`graft_chain_stage` shard-locally (DESIGN.md §14): the rows
    (``bl, bh, keys, fvals``) partitioned over the mesh's ``axis_name``
    axis, the state and parameters replicated, one stage program per shard
    inside shard_map. ``stats`` is psum'd, so every shard and the host see
    the global counts; row outputs come back in row order. A one-device
    mesh is bit-identical to the unsharded launch."""
    from jax.sharding import PartitionSpec

    row, rep = PartitionSpec(axis_name), PartitionSpec()

    def local(*args):
        with jax.named_scope("graft_chain"):
            bl, bh, entry, stats, *xo = _stage_body(*args)
        return (bl, bh, entry, jax.lax.psum(stats, axis_name), *xo)

    # bl, bh, keys; tkeys .. gattrs; fvals; flo, fhi, fcon, fbit, exports
    in_specs = (row,) * 3 + (rep,) * 15 + (row,) + (rep,) * 5
    out_specs = (row, row, row, rep) + (row,) * X_MAX
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))


@jax.jit
def graft_chain_sink(bl, bh, stlo, sthi, selo, sehi):
    """The end of a build chain: per-slot survivor counts of the final
    words, and their translation into the sink's visibility and extent
    words. Returns ``(vis_lo, vis_hi, em_lo, em_hi, slots[64])``."""
    with jax.named_scope("graft_chain"):
        shifts = jnp.arange(32, dtype=jnp.uint32)[:, None]
        words = jnp.concatenate([bl[None] >> shifts, bh[None] >> shifts])
        slot_counts = jnp.sum((words & jnp.uint32(1)).astype(jnp.int32), axis=1)
        return (*_translate(bl, bh, stlo, sthi), *_translate(bl, bh, selo, sehi), slot_counts)


@functools.lru_cache(maxsize=None)
def graft_fill(length: int, dtype: str):
    """A jitted ``full(length, value)`` of dtype ``dtype`` (a NumPy dtype
    name) with the value as an argument: the device side of a fresh mirror
    or probe table, so nothing of its size crosses from the host."""

    def graft_fill(value):
        return jnp.full((length,), value, dtype)

    return jax.jit(graft_fill)
