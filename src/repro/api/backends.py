"""ExecutionBackend: the data plane behind one Session.

The runtime's per-morsel device work — hash probes against shared build
states (§4.3), a morsel's fused stage chain (§13) and segmented sums into
shared accumulators (§4.5) — goes through one interface, which both
backends implement in full:

* ``ReferenceBackend`` — the NumPy row engine: the state's own
  incremental probe index (``core.state``) and ``np.bincount``
  reductions. The default, and the correctness oracle's path
  (``relational/refexec.py`` semantics). Its launches finish at once, and
  it declines the lens, multi-member and chain launches.
* ``PallasBackend`` — the device data plane. Every probe and every stage
  of a chain is one launch of ``graft_chain_stage``, a build chain's sink
  one of ``graft_chain_sink`` (``kernels/fused_chain.py``, both built on
  ``kernels/hash_probe.probe_slots``); ``graft_fill`` and ``graft_patch``
  make and patch the device mirrors. They are jitted XLA programs that run
  unchanged on every platform. States the programs cannot serve
  (multi-match keys, out-of-range keycodes, over-long probe clusters) fall
  back to the reference probe per call; per-reason fallback counters
  record why.

The Pallas backend keeps a device-resident mirror of every served state's
SoA (DESIGN.md §13): open-addressing keycode table, *entry-indexed* packed
visibility/provenance words as (lo, hi) uint32 pairs, and on demand
total-order-encoded retained columns and int32 key columns. Entry indexing
makes the mirrors rebuild-invariant — growing or rehashing the probe table
never touches them — and the state's mark log patches exactly the re-ORed
entries, so steady-state maintenance is O(appended + marked), not
O(entries). Mirror patches run through donated-buffer jitted scatters when
the platform supports donation (CPU jax warns on donation, so it is gated).
"""

from __future__ import annotations

import functools
import math
import weakref
from typing import Optional, Protocol, runtime_checkable

import numpy as np

from ..core.runtime import Launch, encode_keys
from ..core.state import SharedHashBuildState, _bincount_segment_sum
from ..core.tracing import span, spanned
from ..core.visibility import join_words, split_words, translation_table

#: chain-level / probe-level decline reasons (DESIGN.md §13). ``grants``,
#: ``predicate`` and ``slot_limit`` are chain-plan declines (the staged
#: probes may still run on the device); ``keyrange`` and ``capacity`` are
#: table-level declines that route the probe to the reference path.
FALLBACK_REASONS = ("grants", "slot_limit", "keyrange", "capacity", "predicate")


@runtime_checkable
class ExecutionBackend(Protocol):
    """Everything the runtime calls on a Session's data plane.

    Each probe is split at its device launch (DESIGN.md §11): a
    ``*_launch`` call does the work up to the launch and returns a
    ``core.runtime.Launch``, whose ``collect()`` waits for the outputs and
    finishes the host work; a call served on the host returns a Launch
    with its result in hand. The lens, multi-member and chain launches may
    return None to decline: the runtime then probes pre-visibility
    (``probe_launch``) or runs the staged loop."""

    name: str
    #: whether launches run asynchronously on a device, so that the runner
    #: may hold two units in flight (``Runner._overlaps``)
    launches_on_device: bool

    def probe_launch(
        self, state: SharedHashBuildState, keycodes: np.ndarray, counters=None
    ) -> Launch:
        """All (probe_row_idx, entry_idx) match pairs, pre-visibility.
        ``counters`` is the engine's counter dict, where a backend counts
        its fallbacks by reason."""
        ...

    def probe_visible_launch(
        self, state: SharedHashBuildState, keycodes: np.ndarray, qid
    ) -> Optional[Launch]:
        """The pairs visible to query ``qid`` through its state lens."""
        ...

    def probe_visible_multi_launch(
        self, state: SharedHashBuildState, keycodes: np.ndarray
    ) -> Optional[Launch]:
        """``probe_launch``'s pairs and each matched entry's packed lens
        word: ``(probe_idx, entry_idx, vis_words)``."""
        ...

    def probe_chain_launch(self, cplan, cols, bits, counters=None) -> Optional[Launch]:
        """A morsel's whole stage chain (DESIGN.md §13); the backend
        counts why it declines."""
        ...

    def segment_sum(
        self, gids: np.ndarray, values: Optional[np.ndarray], n_groups: int
    ) -> np.ndarray:
        """Per-group float64 sum of ``values`` (counts when values is None)."""
        ...

    def precompile(self, db, morsel_size: int) -> int:
        """Compile the programs a session on ``db`` launches, when it
        opens; returns how many."""
        ...

    def stats(self) -> dict:
        """Counters, surfaced as ``backend_*`` in ``Session.stats``."""
        ...


class ReferenceBackend:
    """NumPy data plane: the state's own incremental probe index
    (shard-routed under ``n_partitions > 1``, DESIGN.md §9) and the core
    bincount reduction. It launches nothing on a device."""

    name = "reference"
    launches_on_device = False

    def probe_launch(self, state, keycodes, counters=None) -> Launch:
        return Launch(state.probe(keycodes))

    def probe_visible_launch(self, state, keycodes, qid) -> Optional[Launch]:
        return None

    def probe_visible_multi_launch(self, state, keycodes) -> Optional[Launch]:
        return None

    def probe_chain_launch(self, cplan, cols, bits, counters=None) -> Optional[Launch]:
        return None

    def segment_sum(self, gids, values, n_groups):
        return _bincount_segment_sum(gids, values, n_groups)

    def precompile(self, db, morsel_size: int) -> int:
        return 0

    def stats(self) -> dict:
        return {}


@functools.lru_cache(maxsize=None)
def _scatter_set(donate: bool):
    """Jitted mirror patch ``buf.at[idx].set(vals)``; the mirror buffer is
    donated where the platform supports it so steady-state patches update
    device memory in place instead of copying the whole mirror."""
    import jax

    def graft_patch(buf, idx, vals):
        return buf.at[idx].set(vals)

    if donate:
        return jax.jit(graft_patch, donate_argnums=(0,))
    return jax.jit(graft_patch)


def _pow2(n: int) -> int:
    """The next power of two at or above ``n`` (at least 8)."""
    cap = 8
    while cap < n:
        cap *= 2
    return cap


def _pad(a: np.ndarray, length: int, fill=0) -> np.ndarray:
    if len(a) < length:
        a = np.concatenate([a, np.full(length - len(a), fill, dtype=a.dtype)])
    return a


class ShapeLadder:
    """Every static shape the device programs take (DESIGN.md §13).

    Fixed when a session opens, by the engine's morsel size and the
    database's table sizes, never by member, slot or grant counts or by a
    state's growth:

    * ``rows``: launch row lengths, the morsel size and its quarter and
      sixteenth (powers of two); a launch pads its rows to the smallest
      that holds them, and a patch of more rows than the largest goes in
      pieces of it;
    * ``entries``: entry capacities, one per distinct power of two at or
      above a table's row count (at least ``rows[0]``); a state takes the
      smallest that holds its ``max_entries`` bound, its probe table twice
      that many slots.

    A size beyond a ladder takes the next power of two, a program outside
    the ladder that the backend's ``program_keys`` counts. The empty ladder
    (a backend no session opened) does that for every size."""

    def __init__(self, rows=(), entries=()):
        self.rows = tuple(rows)
        self.entries = tuple(entries)

    @classmethod
    def for_database(cls, morsel_size: int, table_rows) -> "ShapeLadder":
        top = _pow2(morsel_size)
        rows = sorted({max(8, top // 16), max(8, top // 4), top})
        entries = sorted({max(rows[0], _pow2(n)) for n in table_rows})
        return cls(rows, entries)

    @staticmethod
    def _fit(n: int, ladder) -> int:
        for v in ladder:
            if v >= n:
                return v
        return _pow2(n)

    def rows_for(self, n: int) -> int:
        return self._fit(n, self.rows)

    def entries_for(self, n: int) -> int:
        return self._fit(n, self.entries)


_EMPTY_U32 = np.uint32(0x80000001)  # hash_probe.EMPTY's int32 bits


class _ProbeTable:
    """Device-resident mirror of one state's SoA (DESIGN.md §13).

    Sized once from the state's entry capacity ``ecap`` (``ShapeLadder``):
    the open-addressing keycode table has ``2 * ecap`` slots, everything
    else — visibility/provenance words, total-order column encodings,
    uint32 key columns — is *entry-indexed* over ``ecap``. Appends patch
    ``[rows:n]`` and the table's newly placed slots; visibility marks patch
    the state's mark-log entries; a mark-log compaction or a ``detach``
    epoch bump forces one full regather."""

    __slots__ = (
        "n",
        "tkeys",
        "slot_entry",
        "jkeys",
        "jentry",
        "bad",
        "ecap",
        "jvlo",
        "jvhi",
        "jelo",
        "jehi",
        "vis_rows",
        "em_rows",
        "vis_stamp",
        "mark_sync",
        "ords",
        "keycols",
        "badkeys",
    )

    def __init__(self):
        self.n = 0  # state entries inserted so far
        self.tkeys: Optional[np.ndarray] = None  # int32 slots (EMPTY sentinel)
        self.slot_entry: Optional[np.ndarray] = None  # int32 slot -> entry index
        self.jkeys = None  # device copy of tkeys, patched slot by slot
        self.jentry = None  # device int32 slot -> entry index
        self.bad = False  # sticky: kernel cannot serve this state's table
        # entry-indexed mirrors over ecap entries
        self.ecap = 0
        self.jvlo = None  # visibility word low halves, uint32[ecap]
        self.jvhi = None
        self.jelo = None  # provenance (emask) halves, built on demand
        self.jehi = None
        self.vis_rows = 0  # entries the vis mirror reflects
        self.em_rows = 0
        self.vis_stamp = None  # (rows_inserted, rows_marked, vis_epoch)
        self.mark_sync = (0, 0)  # (mark_log_epoch, consumed log length)
        self.ords = {}  # attr -> [j_hi, j_lo, rows] total-order encodings
        self.keycols = {}  # attr -> [j_u32, rows] entry-origin key mirrors
        self.badkeys = set()  # attrs whose values left the int32 key range


class PallasBackend:
    """Device data plane.

    Unique-key states probe through one device program,
    ``graft_chain_stage`` (``kernels/fused_chain.py``), over entry-indexed
    device mirrors: a fused stage chain launches it once per stage, and
    each single probe is a one-stage launch of it. Everything the programs
    cannot serve (multi-match keys, out-of-range keycodes, over-long probe
    clusters) falls back to the reference probe, with the decline reason
    counted in ``fallback_reasons``.

    Every program's static shape comes from the session's ``ShapeLadder``,
    and ``precompile`` compiles the whole ladder (or reads it from JAX's
    persistent cache) when a session opens; ``program_keys`` counts the
    distinct program shapes compiled or launched, ``precompiled_programs``
    the ladder's.

    Probe-table maintenance is batch-oriented: new keys insert on the host
    by vectorized per-slot winner election (``_batch_insert``), and only
    the slots they fill cross to the device. Segmented sums stay on the
    host in float64, the reference backend's reduction.
    """

    name = "pallas"
    launches_on_device = True

    # Keycodes must fit int32 and stay clear of the kernel's EMPTY sentinel.
    _KEY_LIMIT = 2**31 - 2

    def __init__(self):
        import jax

        from ..kernels import fused_chain

        self._chain = fused_chain
        self._total_order_u32 = fused_chain.total_order_u32
        # donated in-place mirror patches (CPU jax warns on donation)
        self._donate = jax.default_backend() != "cpu"
        self.ladder = ShapeLadder()
        # program shape keys compiled (the ladder) or launched
        self._programs: set = set()
        self.precompiled_programs = 0
        # Probe tables keyed weakly by the state OBJECT (state_ids are
        # engine-local, so an id key would collide when one backend instance
        # is reused across sessions); released states evict automatically.
        self._tables: "weakref.WeakKeyDictionary[SharedHashBuildState, _ProbeTable]" = (
            weakref.WeakKeyDictionary()
        )
        self._consts = {}  # device constants: padding parameter blocks, tables
        self.kernel_probes = 0
        self.kernel_lens_probes = 0
        self.kernel_multi_probes = 0
        self.fallback_probes = 0
        self.chain_launches = 0
        self.chain_devices = set()  # devices the chain launches ran on
        self.mirror_full_regathers = 0
        self.mirror_patched_rows = 0
        self.fallback_reasons = {r: 0 for r in FALLBACK_REASONS}
        # transfer and padding accounting: bytes of every array handed to
        # or read back from the device, the real and padded rows of every
        # probe and chain launch, and the grant slots stage launches carried
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.device_rows = 0
        self.device_padded_rows = 0
        self.device_launches = 0  # probe and chain launches
        self.grant_rows = 0
        self.grant_padded_rows = 0

    def stats(self) -> dict:
        """Kernel-dispatch counters (surfaced via ``Session.stats``).

        Partitioned states (``n_partitions > 1``) need no special casing
        here: the probe-table mirror is built from the state's global
        keycode SoA, whose entry ids are partition-independent (§9) — each
        (fragment × partition) unit simply lands its own batched kernel
        call, which is the real per-partition work the pool models."""
        out = {
            "kernel_probes": self.kernel_probes,
            "kernel_lens_probes": self.kernel_lens_probes,
            "kernel_multi_probes": self.kernel_multi_probes,
            "fallback_probes": self.fallback_probes,
            "chain_launches": self.chain_launches,
            "mirror_full_regathers": self.mirror_full_regathers,
            "mirror_patched_rows": self.mirror_patched_rows,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "device_rows": self.device_rows,
            "device_padded_rows": self.device_padded_rows,
            "device_launches": self.device_launches,
            "grant_rows": self.grant_rows,
            "grant_padded_rows": self.grant_padded_rows,
            "program_keys": len(self._programs),
            "precompiled_programs": self.precompiled_programs,
        }
        for r in FALLBACK_REASONS:
            out[f"fallback_{r}"] = self.fallback_reasons[r]
        return out

    def mirror_devices(self) -> set:
        """Devices holding the live states' probe-table mirrors."""
        out = set()
        for ent in list(self._tables.values()):
            if ent.jkeys is not None:
                out.update(ent.jkeys.devices())
        return out

    def _note_fallback(self, reason: str, counters=None) -> None:
        """Record one decline by reason, on the backend and (when the
        engine's counter dict is handed in) in the session counters."""
        self.fallback_reasons[reason] += 1
        if counters is not None:
            counters[f"fallback_probes_{reason}"] += 1

    # -- the shape ladder -----------------------------------------------------
    def _program(self, fn, key, *args):
        """Launch ``fn`` and record its program shape ``key``."""
        self._programs.add(key)
        return fn(*args)

    def _ladder_jobs(self):
        """One call per program of the ladder, on zero inputs of its shapes
        (a probe of no live row stops at once): ``(key, thunk)`` pairs."""
        import jax

        ch = self._chain
        u32, i32 = np.uint32, np.int32

        def z(shape, dtype):
            return jax.device_put(np.zeros(shape, dtype))

        jobs = []
        for n_rows in self.ladder.rows:
            for ecap in self.ladder.entries:

                def stage(n_rows=n_rows, ecap=ecap):
                    row, ent = z(n_rows, u32), z(ecap, u32)
                    tab = z(2 * ecap, i32)
                    tt = z((8, 256), u32)
                    return ch.graft_chain_stage(
                        row, row, row, tab, tab, ent, ent, tt, tt, z(ch.N_FLAGS, i32),
                        ent, ent, *self._grant_zero(), ((ent, ent),) * ch.A_MAX,
                        ((row, row),) * ch.A_MAX, *self._filter_zero(), (ent,) * ch.X_MAX,
                    )

                jobs.append((("stage", n_rows, ecap), stage))

            def sink(n_rows=n_rows):
                row, tt = z(n_rows, u32), z((8, 256), u32)
                return ch.graft_chain_sink(row, row, tt, tt, tt, tt)

            jobs.append((("sink", n_rows), sink))
        for ecap in self.ladder.entries:
            for length, dtype in ((ecap, "uint32"), (2 * ecap, "int32")):
                fill = ch.graft_fill(length, dtype)
                jobs.append((("fill", length, dtype),
                             functools.partial(fill, np.dtype(dtype).type(0))))
                for n_rows in self.ladder.rows:

                    def patch(length=length, dtype=dtype, n_rows=n_rows):
                        return _scatter_set(self._donate)(
                            z(length, dtype), z(n_rows, i32), z(n_rows, dtype)
                        )

                    jobs.append((("patch", length, dtype, n_rows), patch))
        return jobs

    @spanned("graftdb.precompile")
    def precompile(self, db, morsel_size: int) -> int:
        """Fix the session's ``ShapeLadder`` from ``db`` and the morsel size
        and compile every program of it — or read it from JAX's persistent
        cache — by calling each once on zeros, several at a time. Returns
        the number of programs."""
        import jax
        from concurrent.futures import ThreadPoolExecutor

        self.ladder = ShapeLadder.for_database(
            morsel_size, [t.nrows for t in db.tables.values()]
        )
        jobs = self._ladder_jobs()
        with ThreadPoolExecutor(max_workers=min(8, len(jobs))) as pool:
            outs = list(pool.map(lambda job: job[1](), jobs))
        jax.block_until_ready(outs)
        self._programs.update(key for key, _ in jobs)
        self.precompiled_programs = len(jobs)
        return len(jobs)

    def _const(self, key, make):
        """A device constant, uploaded once per backend."""
        arr = self._consts.get(key)
        if arr is None:
            arr = self._consts[key] = make()
        return arr

    def _grant_zero(self):
        ch = self._chain
        g, a = ch.G_MAX, ch.A_MAX
        return self._const("grant_zero", lambda: tuple(self._h2d(x) for x in (
            np.zeros((g, 2), np.uint32), np.zeros((g, 2), np.uint32),
            np.zeros((g, a), np.int32), np.zeros((g, a, 2), np.uint32),
            np.zeros((g, a, 2), np.uint32))))

    def _filter_zero(self):
        ch = self._chain
        m, a = ch.M_MAX, ch.A_MAX
        return self._const("filter_zero", lambda: tuple(self._h2d(x) for x in (
            np.zeros((m, a, 2), np.uint32), np.zeros((m, a, 2), np.uint32),
            np.zeros((m, a), np.int32), np.zeros((m, 2), np.uint32))))

    def _tables_dev(self, target: np.ndarray):
        """Split byte-lane translation tables of a slot -> word map."""
        tlo, thi = split_words(translation_table(target).ravel())
        return self._h2d(tlo.reshape(8, 256)), self._h2d(thi.reshape(8, 256))

    def _identity_tables(self):
        """Slot j -> bit j: the stage returns each matched entry's word."""
        target = np.uint64(1) << np.arange(64, dtype=np.uint64)
        return self._const("identity", lambda: self._tables_dev(target))

    def _lens_tables(self, slot: int):
        """Slot ``slot`` -> bit 0: a row survives iff its entry is visible."""
        target = np.zeros(64, dtype=np.uint64)
        target[slot] = 1
        return self._const(("lens", slot), lambda: self._tables_dev(target))

    def _stage(self, ent, rows, bl, bh, keys, tt, flags=None, grants=None, gattrs=(),
               fvals=(), fparams=None, exports=()):
        """One ``graft_chain_stage`` launch over ``ent``'s mirrors; unused
        blocks get padding of the right shapes, switched off by ``flags``."""
        ch = self._chain
        if flags is None:
            flags = self._const("flags_zero", lambda: self._h2d(np.zeros(ch.N_FLAGS, np.int32)))
        eem = (ent.jelo, ent.jehi) if grants is not None else (ent.jvlo, ent.jvhi)
        gparams = grants if grants is not None else self._grant_zero()
        gattrs = tuple(gattrs) + ((ent.jvlo, ent.jvlo),) * (ch.A_MAX - len(gattrs))
        fvals = tuple(fvals) + ((bl, bl),) * (ch.A_MAX - len(fvals))
        fparams = fparams if fparams is not None else self._filter_zero()
        exports = tuple(exports) + (ent.jvlo,) * (ch.X_MAX - len(exports))
        return self._program(
            ch.graft_chain_stage, ("stage", rows, ent.ecap),
            bl, bh, keys, ent.jkeys, ent.jentry, ent.jvlo, ent.jvhi, tt[0], tt[1], flags,
            eem[0], eem[1], *gparams, gattrs, fvals, *fparams, exports,
        )

    # -- host<->device transfers ---------------------------------------------
    def _h2d(self, a):
        """Every host->device copy the backend makes: ``jnp.asarray``,
        counted in ``h2d_bytes`` at the device array's size."""
        import jax.numpy as jnp

        with span("graftdb.h2d"):
            out = jnp.asarray(a)
        self.h2d_bytes += out.nbytes
        return out

    def _read_back(self, outs):
        """Wait for outputs whose copies are queued, then take the copies,
        counted in ``d2h_bytes``. The host's wait for the device and its
        wait for the copies are timed apart."""
        import jax

        with span("graftdb.device_wait"):
            jax.block_until_ready(outs)
        with span("graftdb.d2h"):
            host = [np.asarray(o) for o in outs]
        self.d2h_bytes += sum(h.nbytes for h in host)
        return host

    def _probe_rows(self, keycodes: np.ndarray):
        """A single probe's rows at the ladder's length: the keys as int32
        bits in uint32 (EMPTY padding matches nothing) and all-ones
        ownership words, a device constant per length."""
        n = len(keycodes)
        rows = self.ladder.rows_for(n)
        self.device_rows += n
        self.device_padded_rows += rows
        kc = np.asarray(keycodes, dtype=np.int32).view(np.uint32)
        live = self._const(("ones", rows), lambda: self._h2d(np.full(rows, 0xFFFFFFFF, np.uint32)))
        return rows, self._h2d(_pad(kc, rows, _EMPTY_U32)), live

    # -- probe ---------------------------------------------------------------
    # Each probe and the chain are split at the device launch (DESIGN.md
    # §11): the ``*_launch`` call does everything up to the launch and
    # queues the outputs' copies to the host; the returned ``Launch``'s
    # ``collect()`` reads them back and finishes the host work.
    def _launched(self, name, outs, post) -> Launch:
        """A probe or chain launch of the device outputs ``outs``: each
        copy to the host is queued now, as ``jax.device_get`` does;
        ``collect()`` runs ``post`` on the arrays read back (``_read_back``)."""
        for o in outs:
            o.copy_to_host_async()
        self.device_launches += 1
        return Launch(outs=outs, finish=lambda o: post(self._read_back(o)), name=name)

    def _single_stage(self, state, keycodes, tt, flags=None):
        """A one-stage launch probing ``state`` with ``keycodes``: returns
        the stage's device outputs (``graft_chain_stage``)."""
        ent = self._tables[state]
        self._sync_mirrors(ent, state)
        rows, keys, live = self._probe_rows(keycodes)
        return self._stage(ent, rows, live, live, keys, tt, flags)

    def _lens_flags(self):
        """Flags of a single-query lens probe: entries after the lens."""
        ch = self._chain

        def make():
            fl = np.zeros(ch.N_FLAGS, np.int32)
            fl[ch.F_EPOST] = 1
            return self._h2d(fl)

        return self._const("flags_lens", make)

    @spanned("graftdb.backend.probe")
    def probe_launch(self, state, keycodes, counters=None) -> Launch:
        """All (probe_idx, entry_idx) match pairs, pre-visibility. The
        reference fallbacks run here, and their Launch holds the result."""
        if state.keycode.n == 0 or len(keycodes) == 0:
            return Launch((np.empty(0, dtype=np.int64), np.empty(0, dtype=np.int64)))
        if self._table_for(state) is None:
            self.fallback_probes += 1
            self._note_fallback("capacity", counters)
            return Launch(state.probe(keycodes))
        if keycodes.min() < 0 or keycodes.max() > self._KEY_LIMIT:
            self.fallback_probes += 1
            self._note_fallback("keyrange", counters)
            return Launch(state.probe(keycodes))
        n = len(keycodes)

        def post(host):
            entry = host[0][:n]
            self.kernel_probes += 1
            probe_idx = np.flatnonzero(entry >= 0).astype(np.int64)
            return probe_idx, entry[probe_idx].astype(np.int64)

        outs = self._single_stage(state, keycodes, self._identity_tables())
        return self._launched("graftdb.backend.probe", (outs[2],), post)

    @spanned("graftdb.backend.probe_visible")
    def probe_visible_launch(self, state, keycodes, qid) -> Optional[Launch]:
        """Single-query probe with the state lens fused in the program.

        Collects visibility-filtered (probe_idx, entry_idx) pairs. None
        when the program cannot take over the lens (extent-scoped grants
        need predicate evaluation — unless routed through the chain's
        compiled form; unservable tables fall back entirely). The lens
        words are entry-indexed uint32 pairs, so any slot 0..63 serves
        (DESIGN.md §13)."""
        if state.grants.get(qid):
            return None
        slot = state.slots.peek(qid)
        if slot is None:
            return None
        if state.keycode.n == 0 or len(keycodes) == 0:
            # decline instead of returning the empty pair: keeps the
            # kernel_lens_probes backend attr == engine counter invariant
            return None
        if self._table_for(state) is None or keycodes.min() < 0 or keycodes.max() > self._KEY_LIMIT:
            return None
        n = len(keycodes)

        def post(host):
            entry = host[0][:n]
            self.kernel_probes += 1
            self.kernel_lens_probes += 1
            probe_idx = np.flatnonzero(entry >= 0).astype(np.int64)
            return probe_idx, entry[probe_idx].astype(np.int64)

        outs = self._single_stage(state, keycodes, self._lens_tables(slot), self._lens_flags())
        return self._launched("graftdb.backend.probe_visible", (outs[2],), post)

    @spanned("graftdb.backend.probe_visible_multi")
    def probe_visible_multi_launch(self, state, keycodes) -> Optional[Launch]:
        """Multi-member probe with the packed lens words gathered in the
        program (§11): collects ``(probe_idx, entry_idx, vis_words)`` where
        ``vis_words[i]`` is the matched entry's full uint64 visibility
        word (rejoined from the program's uint32 halves); None when the
        program cannot serve the state. The pair stream is pre-visibility
        and identical to ``probe_launch``'s — ownership filtering happens
        in the runtime's packed translation — so results stay
        bit-identical to the reference path for every member count and
        any slot 0..63."""
        if state.keycode.n == 0 or len(keycodes) == 0:
            return None
        if self._table_for(state) is None or keycodes.min() < 0 or keycodes.max() > self._KEY_LIMIT:
            return None
        n = len(keycodes)

        def post(host):
            entry, wlo, whi = host
            entry = entry[:n]
            self.kernel_probes += 1
            self.kernel_multi_probes += 1
            probe_idx = np.flatnonzero(entry >= 0).astype(np.int64)
            vis_words = join_words(wlo[probe_idx], whi[probe_idx])
            return probe_idx, entry[probe_idx].astype(np.int64), vis_words

        outs = self._single_stage(state, keycodes, self._identity_tables())
        return self._launched("graftdb.backend.probe_visible_multi", outs[2:3] + outs[:2], post)

    # -- fused stage chain (DESIGN.md §13) -----------------------------------
    @staticmethod
    def _chain_layout(stages):
        """Where each stage's inputs come from: the entry columns each
        stage exports as rows (``("key" | "hi" | "lo", attr)``), each
        stage's key source (None = host rows, else (stage, export slot))
        and each filter operand's (None = host rows, else (stage, hi slot,
        lo slot))."""
        exports = [[] for _ in stages]

        def slot(stage, item):
            if item not in exports[stage]:
                exports[stage].append(item)
            return exports[stage].index(item)

        keys, filters = [], []
        for st in stages:
            key = st["key"]
            keys.append(None if key[0] == "host" else (key[1], slot(key[1], ("key", key[2]))))
        for st in stages:
            srcs = []
            for ref in (st["filter"] or {}).get("attrs", ()):
                if ref[0] == "host":
                    srcs.append(None)
                else:
                    srcs.append((ref[1], slot(ref[1], ("hi", ref[2])), slot(ref[1], ("lo", ref[2]))))
            filters.append(srcs)
        return {"exports": exports, "keys": keys, "filters": filters}

    @spanned("graftdb.backend.probe_chain")
    def probe_chain_launch(self, cplan, cols, bits, counters=None) -> Optional[Launch]:
        """A morsel's entire stage chain, one stage program per stage with
        no host round trip between them.

        ``cplan`` is the runtime's compiled chain plan (``Pipeline.
        _build_chain_plan``): per stage the target state, lens translation
        tables, key sourcing, compiled grants and interval filter matrices;
        plus the sink translation tables. A plan that could not compile
        (``cplan["ok"]`` false) is a static decline under its
        ``cplan["reason"]``. ``cols`` are the morsel's source-compacted
        columns, ``bits`` the packed ownership words; source-origin keys
        are encoded from ``cols`` here. Returns None on a decline (reason
        counted), else a Launch collecting a dict with the final packed
        words, per-stage matched entry indices, per-stage (alive, matched,
        matched_visible) stats, and — for build chains — the sink
        visibility/provenance words and per-slot survivor counts. Device
        parameter uploads are cached on the plan (``cplan["_dev"]``), so
        steady-state morsels ship only the row-length arrays."""
        if not cplan["ok"]:
            self._note_fallback(cplan["reason"], counters)
            return None
        ch = self._chain
        stages = cplan["stages"]
        n = len(bits)
        if n == 0:
            return None
        dev = cplan["_dev"]
        layout = dev.get("layout")
        if layout is None:
            layout = dev["layout"] = self._chain_layout(stages)
        if any(len(x) > ch.X_MAX for x in layout["exports"]):
            self._note_fallback("keyrange", counters)
            return None
        for st in stages:
            if len(st["grants"]) > ch.G_MAX:
                self._note_fallback("grants", counters)
                return None
            f = st["filter"]
            if f is not None and (f["n_members"] > ch.M_MAX or len(f["attrs"]) > ch.A_MAX):
                self._note_fallback("predicate", counters)
                return None

        # collect per-state mirror needs across the chain
        needs: dict = {}

        def need(state):
            nd = needs.get(id(state))
            if nd is None:
                nd = needs[id(state)] = {
                    "state": state,
                    "em": False,
                    "ords": set(),
                    "keys": set(),
                }
            return nd

        for si, st in enumerate(stages):
            state = st["state"]
            if state.keycode.n == 0:
                return None  # no rows can survive; the staged path is as cheap
            need(state)
            if st["grants"]:
                nd = need(state)
                nd["em"] = True
                for _, _, bounds in st["grants"]:
                    for a, _, _ in bounds:
                        nd["ords"].add(a)
            for kind, attr in layout["exports"][si]:
                nd = need(state)
                (nd["keys"] if kind == "key" else nd["ords"]).add(attr)
        for st in stages:
            if self._table_for(st["state"]) is None:
                self._note_fallback("capacity", counters)
                return None
        for nd in needs.values():
            state = nd["state"]
            ent = self._tables[state]
            self._sync_mirrors(
                ent,
                state,
                need_em=nd["em"],
                ord_attrs=sorted(nd["ords"]),
                key_attrs=sorted(nd["keys"]),
            )
            for a in nd["keys"]:
                if a in ent.badkeys:
                    self._note_fallback("keyrange", counters)
                    return None
        host_keys = {
            si: encode_keys(cols, st["key"][1])
            for si, st in enumerate(stages)
            if st["key"][0] == "host"
        }
        for kc in host_keys.values():
            if len(kc) and (kc.min() < 0 or kc.max() > self._KEY_LIMIT):
                self._note_fallback("keyrange", counters)
                return None

        rows = self.ladder.rows_for(n)
        self.device_rows += n
        self.device_padded_rows += rows

        def pad_row(a, fill=0):
            return self._h2d(_pad(a, rows, fill))

        blo, bhi = split_words(bits)
        bl, bh = pad_row(blo), pad_row(bhi)
        entries, stats, exported = [], [], []
        for si, st in enumerate(stages):
            ent = self._tables[st["state"]]
            ksrc = layout["keys"][si]
            if ksrc is None:
                kc = host_keys[si].astype(np.int32).view(np.uint32)
                keys = pad_row(kc, _EMPTY_U32)
            else:
                keys = exported[ksrc[0]][ksrc[1]]
            tt = dev.get(("tt", si))
            if tt is None:
                tlo, thi = split_words(st["tables"].ravel())
                tt = dev[("tt", si)] = (
                    self._h2d(tlo.reshape(8, 256)), self._h2d(thi.reshape(8, 256))
                )
            grants, gattrs = None, ()
            if st["grants"]:
                gp = dev.get(("g", si))
                if gp is None:
                    gp = dev[("g", si)] = self._grant_params(st["grants"])
                if gp is False:
                    self._note_fallback("grants", counters)
                    return None
                gattr_names, grants = gp[0], gp[1:]
                gattrs = [(ent.ords[a][0], ent.ords[a][1]) for a in gattr_names]
                self.grant_rows += ch.G_MAX
                self.grant_padded_rows += ch.G_MAX - len(st["grants"])
            f = st["filter"]
            fvals, fparams, fown = [], None, []
            if f is not None and len(f["attrs"]):
                for ref, src in zip(f["attrs"], layout["filters"][si]):
                    if src is None:
                        vh, vl = self._total_order_u32(np.asarray(cols[ref[1]], dtype=np.float64))
                        fvals.append((pad_row(vh), pad_row(vl)))
                        fown.append((-1, -1))
                    elif src[0] == si:
                        fvals.append((bl, bl))
                        fown.append((src[1], src[2]))
                    else:
                        fvals.append((exported[src[0]][src[1]], exported[src[0]][src[2]]))
                        fown.append((-1, -1))
                fparams = dev.get(("f", si))
                if fparams is None:
                    fparams = dev[("f", si)] = self._filter_params(f)
            exports = []
            for kind, attr in layout["exports"][si]:
                if kind == "key":
                    exports.append(ent.keycols[attr][0])
                else:
                    exports.append(ent.ords[attr][0 if kind == "hi" else 1])
            flags = dev.get(("flags", si))
            if flags is None:
                fl = np.zeros(ch.N_FLAGS, np.int32)
                fl[0] = grants is not None
                fl[1] = fparams is not None
                fl[ch.F_GATTR : ch.F_GATTR + len(gattrs)] = 1
                fl[ch.F_EXPORT : ch.F_EXPORT + len(exports)] = 1
                fl[ch.F_FOWN : ch.F_FOWN + 2 * ch.A_MAX] = -1
                for a, (oh, ol) in enumerate(fown):
                    fl[ch.F_FOWN + 2 * a : ch.F_FOWN + 2 * a + 2] = (oh, ol)
                flags = dev[("flags", si)] = self._h2d(fl)
            out = self._stage(ent, rows, bl, bh, keys, tt, flags, grants, gattrs,
                              fvals, fparams, exports)
            bl, bh = out[0], out[1]
            entries.append(out[2])
            stats.append(out[3])
            exported.append(out[4:])
        outs = [bl, bh, *entries, *stats]
        sink = cplan["sink"]
        if sink is not None:
            sp = dev.get("sink")
            if sp is None:
                vt, et = sink
                vlo, vhi = split_words(vt.ravel())
                elo, ehi = split_words(et.ravel())
                sp = dev["sink"] = tuple(self._h2d(x.reshape(8, 256)) for x in (vlo, vhi, elo, ehi))
            outs += list(self._program(ch.graft_chain_sink, ("sink", rows), bl, bh, *sp))
        self.chain_devices.update(bl.devices())
        n_stages = len(stages)

        def post(out):
            res = {
                "bits": join_words(out[0][:n], out[1][:n]),
                "entries": [out[2 + s][:n].astype(np.int64) for s in range(n_stages)],
                "stats": np.stack(out[2 + n_stages : 2 + 2 * n_stages]).astype(np.int64),
            }
            if sink is not None:
                k = 2 + 2 * n_stages
                res["vismask"] = join_words(out[k][:n], out[k + 1][:n])
                res["emask"] = join_words(out[k + 2][:n], out[k + 3][:n])
                res["slots"] = out[k + 4].astype(np.int64)
            self.kernel_probes += 1
            self.chain_launches += 1
            stats = res["stats"]
            for s, st in enumerate(stages):
                if stats[s, 0] == 0:
                    break
                if st["use_post"]:
                    self.kernel_lens_probes += 1
                else:
                    self.kernel_multi_probes += 1
            return res

        return self._launched("graftdb.backend.probe_chain", tuple(outs), post)

    def _grant_params(self, grants):
        """Device parameter block of one stage's compiled grants, padded to
        ``G_MAX`` grants over ``A_MAX`` attrs: the union attr list, per-grant
        split bit/allowed words, and the per-(grant, attr) constrained flags
        + total-order interval bounds (unconstrained cells carry flag 0 and
        the full [-inf, inf] band; padding grants allow nothing). False when
        the grants bound more attrs than the block holds."""
        from ..kernels.fused_chain import total_order_bound

        ch = self._chain
        attrs = []
        for _, _, bounds in grants:
            for a, _, _ in bounds:
                if a not in attrs:
                    attrs.append(a)
        if len(attrs) > ch.A_MAX:
            return False
        n_g, n_a = ch.G_MAX, ch.A_MAX
        gbit = np.zeros((n_g, 2), np.uint32)
        gallow = np.zeros((n_g, 2), np.uint32)
        gcon = np.zeros((n_g, n_a), np.int32)
        glo = np.zeros((n_g, n_a, 2), np.uint32)
        ghi = np.zeros((n_g, n_a, 2), np.uint32)
        glo[:, :, 0], glo[:, :, 1] = total_order_bound(-math.inf)
        ghi[:, :, 0], ghi[:, :, 1] = total_order_bound(math.inf)
        for g, (bitval, allowed, bounds) in enumerate(grants):
            lo, hi = split_words(np.array([bitval], dtype=np.uint64))
            gbit[g] = (lo[0], hi[0])
            lo, hi = split_words(np.array([allowed], dtype=np.uint64))
            gallow[g] = (lo[0], hi[0])
            for a, blo, bhi in bounds:
                j = attrs.index(a)
                gcon[g, j] = 1
                glo[g, j] = total_order_bound(blo)
                ghi[g, j] = total_order_bound(bhi)
        return (tuple(attrs),) + tuple(self._h2d(a) for a in (gbit, gallow, gcon, glo, ghi))

    def _filter_params(self, f):
        """Device matrices of one stage's fused interval filter, padded to
        ``M_MAX`` members over ``A_MAX`` attrs: bounds as total-order uint32
        pairs, constrained flags, split member bits (padding members carry
        no bit, padding attrs no constraint)."""
        ch = self._chain
        n_m, n_a = f["n_members"], len(f["attrs"])
        lh, ll = self._total_order_u32(np.asarray(f["lo"], np.float64).ravel())
        hh, hl = self._total_order_u32(np.asarray(f["hi"], np.float64).ravel())
        flo = np.zeros((ch.M_MAX, ch.A_MAX, 2), np.uint32)
        fhi = np.zeros((ch.M_MAX, ch.A_MAX, 2), np.uint32)
        fcon = np.zeros((ch.M_MAX, ch.A_MAX), np.int32)
        fbit = np.zeros((ch.M_MAX, 2), np.uint32)
        flo[:n_m, :n_a] = np.stack([lh, ll], axis=-1).reshape(n_m, n_a, 2)
        fhi[:n_m, :n_a] = np.stack([hh, hl], axis=-1).reshape(n_m, n_a, 2)
        fcon[:n_m, :n_a] = np.asarray(f["con"], np.int32).reshape(n_m, n_a)
        blo, bhi = split_words(np.asarray(f["bitvals"], np.uint64))
        fbit[:n_m] = np.stack([blo, bhi], axis=-1)
        return tuple(self._h2d(a) for a in (flo, fhi, fcon, fbit))

    # -- entry-indexed device mirrors ----------------------------------------
    def _patch(self, buf, idx, vals):
        """Scatter ``vals`` into the device array ``buf`` at ``idx``, in
        pieces of at most the ladder's longest row length, each padded to
        a ladder length by repeating its first element (duplicate
        same-value writes are benign)."""
        idx = np.asarray(idx, dtype=np.int32)
        vals = np.asarray(vals, dtype=buf.dtype)
        step = self.ladder.rows[-1] if self.ladder.rows else max(len(idx), 1)
        for lo in range(0, len(idx), step):
            ci, cv = idx[lo : lo + step], vals[lo : lo + step]
            cap = self.ladder.rows_for(len(ci))
            ci = _pad(ci, cap, ci[0])
            cv = _pad(cv, cap, cv[0])
            buf = self._program(
                _scatter_set(self._donate), ("patch", buf.shape[0], buf.dtype.name, cap),
                buf, self._h2d(ci), self._h2d(cv),
            )
        return buf

    def _fill(self, length: int, dtype: str, value=0):
        """A device array of ``length`` ``value``s, made on the device."""
        return self._program(
            self._chain.graft_fill(length, dtype), ("fill", length, dtype), np.dtype(dtype).type(value)
        )

    def _mirror(self, vals, length: int):
        """A device array of ``length`` holding ``vals`` at its start and
        zeros after: only ``vals`` cross to the device."""
        vals = np.asarray(vals)
        return self._patch(self._fill(length, vals.dtype.name), np.arange(len(vals)), vals)

    @spanned("graftdb.backend.sync_mirrors")
    def _sync_mirrors(self, ent, state, need_em=False, ord_attrs=(), key_attrs=()):
        """Bring the entry-indexed device mirrors up to the state's SoA.

        Steady state is incremental: appended entries patch ``[rows:n]``,
        visibility/provenance marks patch exactly the state's mark-log
        entry ids. Only a mark-log compaction, a ``detach`` visibility
        epoch bump, or a capacity change trigger a full regather
        (``mirror_full_regathers`` counts them). Total-order column
        encodings and uint32 key-column mirrors are append-only — retained
        column values never change after insert. Key columns whose values
        leave the int32 key range mark ``badkeys`` sticky."""
        n = ent.n
        if ent.jvlo is not None and ent.jvlo.shape[0] != ent.ecap:
            ent.jvlo = ent.jvhi = ent.jelo = ent.jehi = None
            ent.vis_rows = ent.em_rows = 0
            ent.ords = {}
            ent.keycols = {}
        epoch = state.mark_log_epoch
        stamp = (state.rows_inserted, state.rows_marked, state.vis_epoch)
        if ent.jvlo is None:
            lo, hi = split_words(state.vis.data[:n])
            ent.jvlo = self._mirror(lo, ent.ecap)
            ent.jvhi = self._mirror(hi, ent.ecap)
            ent.vis_rows = n
            ent.mark_sync = (epoch, state.mark_log.n)
            ent.vis_stamp = stamp
        elif ent.vis_stamp != stamp:
            se, sp = ent.mark_sync
            if se != epoch or ent.vis_stamp[2] != stamp[2]:
                # mark-log compaction or a detach bit-clear: regather once
                lo, hi = split_words(state.vis.data[:n])
                ent.jvlo = self._mirror(lo, ent.ecap)
                ent.jvhi = self._mirror(hi, ent.ecap)
                ent.vis_rows = n
                if ent.jelo is not None:
                    lo, hi = split_words(state.emask.data[:n])
                    ent.jelo = self._mirror(lo, ent.ecap)
                    ent.jehi = self._mirror(hi, ent.ecap)
                    ent.em_rows = n
                self.mirror_full_regathers += 1
            else:
                ids = state.mark_log.data[sp:]
                if len(ids):
                    ids = np.unique(ids)
                    vm = ids[ids < ent.vis_rows]
                    if len(vm):
                        lo, hi = split_words(state.vis.data[vm])
                        ent.jvlo = self._patch(ent.jvlo, vm, lo)
                        ent.jvhi = self._patch(ent.jvhi, vm, hi)
                        self.mirror_patched_rows += len(vm)
                    if ent.jelo is not None:
                        em = ids[ids < ent.em_rows]
                        if len(em):
                            lo, hi = split_words(state.emask.data[em])
                            ent.jelo = self._patch(ent.jelo, em, lo)
                            ent.jehi = self._patch(ent.jehi, em, hi)
                if ent.vis_rows < n:
                    idx = np.arange(ent.vis_rows, n, dtype=np.int64)
                    lo, hi = split_words(state.vis.data[ent.vis_rows : n])
                    ent.jvlo = self._patch(ent.jvlo, idx, lo)
                    ent.jvhi = self._patch(ent.jvhi, idx, hi)
                    ent.vis_rows = n
                if ent.jelo is not None and ent.em_rows < n:
                    idx = np.arange(ent.em_rows, n, dtype=np.int64)
                    lo, hi = split_words(state.emask.data[ent.em_rows : n])
                    ent.jelo = self._patch(ent.jelo, idx, lo)
                    ent.jehi = self._patch(ent.jehi, idx, hi)
                    ent.em_rows = n
            ent.mark_sync = (epoch, state.mark_log.n)
            ent.vis_stamp = stamp
        if need_em and ent.jelo is None:
            lo, hi = split_words(state.emask.data[:n])
            ent.jelo = self._mirror(lo, ent.ecap)
            ent.jehi = self._mirror(hi, ent.ecap)
            ent.em_rows = n
        for a in ord_attrs:
            rec = ent.ords.get(a)
            if rec is None:
                h, lo = self._total_order_u32(state.cols[a].data[:n])
                ent.ords[a] = [self._mirror(h, ent.ecap), self._mirror(lo, ent.ecap), n]
            elif rec[2] < n:
                h, lo = self._total_order_u32(state.cols[a].data[rec[2] : n])
                idx = np.arange(rec[2], n, dtype=np.int64)
                rec[0] = self._patch(rec[0], idx, h)
                rec[1] = self._patch(rec[1], idx, lo)
                rec[2] = n
        for a in key_attrs:
            if a in ent.badkeys:
                continue
            rec = ent.keycols.get(a)
            start = rec[1] if rec is not None else 0
            if start >= n:
                continue
            vals = state.cols[a].data[start:n]
            with np.errstate(invalid="ignore"):
                # truncate exactly like encode_keys' int64 cast; NaN/inf
                # truncate to INT64_MIN, caught by the range check below
                iv = vals.astype(np.int64)
            if len(iv) and (iv.min() < 0 or iv.max() > self._KEY_LIMIT):
                ent.badkeys.add(a)
                ent.keycols.pop(a, None)
                continue
            u32 = iv.astype(np.int32).view(np.uint32)
            if rec is None:
                ent.keycols[a] = [self._mirror(u32, ent.ecap), n]
            else:
                idx = np.arange(start, n, dtype=np.int64)
                rec[0] = self._patch(rec[0], idx, u32)
                rec[1] = n

    def _table_for(self, state) -> Optional["_ProbeTable"]:
        """Open-addressing probe table over the state's SoA keycodes, cached
        per state and sized once from the state's ``max_entries`` bound
        (``ShapeLadder``): when the state gains entries, only the new keys
        are inserted and only their slots cross to the device. Unservable
        states (duplicate keys, out-of-range keycodes, over-long clusters)
        are marked bad once and fall back to the reference probe forever."""
        n = state.keycode.n
        ent = self._tables.get(state)
        if ent is None:
            ent = _ProbeTable()
            self._tables[state] = ent
        if ent.bad:
            return None
        if ent.n < n:
            self._insert_keys(ent, state.keycode.data, n, state.max_entries or 0)
            if ent.bad:
                return None
        return ent

    @spanned("graftdb.backend.insert_keys")
    def _insert_keys(self, ent: "_ProbeTable", keys, n: int, bound: int = 0) -> None:
        """Insert keys[ent.n:n] into the table. The table is built at the
        state's entry capacity (``bound`` on the ladder) and rebuilt only
        if the state outgrows it. Insertion is one batched winner-election
        pass — never a per-key Python loop.
        Rebuilds reassign table slots but leave the entry-indexed mirrors
        untouched unless the capacity changes (they are keyed by entry id,
        not slot — the §13 incremental-maintenance invariant)."""
        from ..kernels.hash_probe import EMPTY

        new = keys[ent.n : n]
        if len(new) and (new.min() < 0 or new.max() > self._KEY_LIMIT):
            ent.bad = True
            return
        if ent.tkeys is None or n > ent.ecap:
            ent.ecap = self.ladder.entries_for(max(bound, n))
            cap = 2 * ent.ecap
            ent.tkeys = np.full(cap, EMPTY, dtype=np.int32)
            ent.slot_entry = np.full(cap, -1, dtype=np.int32)
            placed = self._batch_insert(ent, keys[:n], 0)
            if placed is None:
                ent.bad = True
                return
            ent.jkeys = self._patch(self._fill(cap, "int32", EMPTY), placed, ent.tkeys[placed])
            ent.jentry = self._patch(self._fill(cap, "int32", 0), placed, ent.slot_entry[placed])
        else:
            placed = self._batch_insert(ent, new, ent.n)
            if placed is None:
                ent.bad = True
                return
            ent.jkeys = self._patch(ent.jkeys, placed, ent.tkeys[placed])
            ent.jentry = self._patch(ent.jentry, placed, ent.slot_entry[placed])
        ent.n = n

    @staticmethod
    def _batch_insert(ent: "_ProbeTable", seg, base: int) -> Optional[np.ndarray]:
        """Vectorized linear-probe insertion of ``seg`` (entry indices
        ``base + i``): each round, every unplaced key inspects its current
        slot; per empty slot the lowest-ranked contender wins, everyone
        else advances. Returns the slots it filled, or None on duplicate
        keys (multi-match state) or a probe chain exceeding the kernel's
        bounded scan."""
        from ..kernels.hash_probe import EMPTY, MAX_PROBE, MULT

        placed = []
        if len(seg) == 0:
            return np.empty(0, dtype=np.int64)
        tkeys, slot_entry = ent.tkeys, ent.slot_entry
        mask = len(tkeys) - 1
        seg32 = np.asarray(seg, dtype=np.int32)
        pos = ((seg.astype(np.uint32) * np.uint32(MULT)).astype(np.int32)) & mask
        hops = np.zeros(len(seg), dtype=np.int64)
        pending = np.arange(len(seg), dtype=np.int64)
        while len(pending):
            p = pos[pending]
            cur = tkeys[p]
            if (cur == seg32[pending]).any():
                return None  # duplicate key: multi-match state
            free = cur == EMPTY
            won = np.zeros(len(pending), dtype=bool)
            if free.any():
                cand = np.flatnonzero(free)
                slots = p[cand]
                so = np.argsort(slots, kind="stable")
                firsts = np.ones(len(so), dtype=bool)
                firsts[1:] = slots[so][1:] != slots[so][:-1]
                winners = cand[so[firsts]]
                wp = p[winners]
                tkeys[wp] = seg32[pending[winners]]
                slot_entry[wp] = base + pending[winners]
                placed.append(wp)
                won[winners] = True
                # a same-batch duplicate that contended for the same slot
                # never revisits it — re-read after the winners' writes so
                # in-batch duplicate keys are caught, not silently placed
                lost = free & ~won
                if lost.any() and (tkeys[p[lost]] == seg32[pending[lost]]).any():
                    return None  # duplicate key within the batch
            rest = ~won
            if not rest.any():
                break
            pr = pending[rest]
            pos[pr] = (p[rest] + 1) & mask
            hops[pr] += 1
            if hops[pr].max() >= MAX_PROBE:
                return None  # cluster exceeds the kernel's bounded probe
            pending = pr
        return np.concatenate(placed)

    # -- segmented aggregation ------------------------------------------------
    def segment_sum(self, gids, values, n_groups):
        return _bincount_segment_sum(gids, values, n_groups)


def resolve_backend(spec) -> ExecutionBackend:
    """Accept a backend name or instance (EngineConfig.backend)."""
    if isinstance(spec, str):
        if spec == "reference":
            return ReferenceBackend()
        if spec == "pallas":
            return PallasBackend()
        raise ValueError(f"unknown backend {spec!r}")
    if not isinstance(spec, ExecutionBackend):
        raise TypeError(f"backend must implement ExecutionBackend, got {spec!r}")
    return spec
