"""Compile rehearsal: the main path's device programs compile for a TPU v5e.

Each test lowers and compiles programs of the data plane — the chain
stage that serves every probe, the build chain's sink, and the stage
shard-locally over a data mesh — for the chips of a described (not
attached) ``v5e:2x2`` topology, at TPC-H SF-1 sizes: a 65,536-row morsel, a
4,194,304-slot probe table (the orders state's ~1.5M keys at the 50% load
factor) and 2,097,152-entry mirrors. Nothing runs; the chip's compiler
refuses here what it would refuse on the chip (an unsupported gather, a
program that does not fit the device's memory).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import fused_chain as ch

ROWS = 65536
SLOTS = 1 << 22
ENTRIES = 1 << 21
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def v5e_2x2():
    """The described chips of a ``v5e:2x2`` host."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # no TPU compiler in this installation
            jax.config.update("jax_enable_compilation_cache", cache_on)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, args):
    compiled = fn.lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert 0 < total < HBM_BYTES, total
    # an XLA program, not a Pallas kernel: no Mosaic custom call
    assert "tpu_custom_call" not in compiled.as_text()
    return compiled


def _probe_args(sh):
    i32, u32 = jnp.int32, jnp.uint32
    return [
        _sds((ROWS,), i32, sh),  # probe keys
        _sds((SLOTS,), i32, sh),  # table keys
        _sds((SLOTS,), i32, sh),  # slot -> entry
        _sds((ENTRIES,), u32, sh),  # visibility lo
        _sds((ENTRIES,), u32, sh),  # visibility hi
    ]


def _stage_args(sh):
    """``graft_chain_stage``'s inputs at the SF-1 sizes, with its grant,
    filter and export blocks."""
    i32, u32 = jnp.int32, jnp.uint32
    keys, tkeys, tentry, evlo, evhi = _probe_args(sh)
    row = _sds((ROWS,), u32, sh)
    tt = _sds((8, 256), u32, sh)
    g, m, a = ch.G_MAX, ch.M_MAX, ch.A_MAX
    return [row, row, _sds((ROWS,), u32, sh), tkeys, tentry, evlo, evhi, tt, tt,
            _sds((ch.N_FLAGS,), i32, sh), evlo, evhi,
            _sds((g, 2), u32, sh), _sds((g, 2), u32, sh), _sds((g, a), i32, sh),
            _sds((g, a, 2), u32, sh), _sds((g, a, 2), u32, sh), ((evlo, evhi),) * a,
            ((row, row),) * a, _sds((m, a, 2), u32, sh), _sds((m, a, 2), u32, sh),
            _sds((m, a), i32, sh), _sds((m, 2), u32, sh), (evlo,) * ch.X_MAX]


def test_single_lens_probe_compiles(one_chip):
    # a single-query lens probe is a one-stage launch of the chain stage
    _compile(ch.graft_chain_stage, _stage_args(one_chip))


def test_multi_member_probe_compiles(one_chip):
    # the multi-member probe is the same stage program; a build chain ends
    # in the sink program, and mirrors are made and patched on the device
    row, tt = _sds((ROWS,), jnp.uint32, one_chip), _sds((8, 256), jnp.uint32, one_chip)
    _compile(ch.graft_chain_sink, [row, row, tt, tt, tt, tt])
    _compile(ch.graft_fill(ENTRIES, "uint32"), [_sds((), jnp.uint32, one_chip)])


def _sharded_stage_args(mesh):
    """``_stage_args`` placed for the shard-local stage: rows split over
    the mesh's data axis, state and parameters replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    row, rep = NamedSharding(mesh, PartitionSpec("data")), NamedSharding(mesh, PartitionSpec())
    args = _stage_args(rep)

    def place(tree, sharding):
        return jax.tree.map(lambda x: _sds(x.shape, x.dtype, sharding), tree)

    for i in (0, 1, 2, 18):  # bl, bh, keys, fvals
        args[i] = place(args[i], row)
    return args


def test_one_stage_chain_with_sink_compiles(v5e_2x2, one_chip):
    # the mesh check's program on a one-chip data mesh, and the sink that
    # ends a build chain
    from jax.sharding import Mesh

    mesh = Mesh(np.array(v5e_2x2[:1]), ("data",))
    _compile(ch.graft_chain_stage_sharded(mesh), _sharded_stage_args(mesh))
    row = _sds((ROWS,), jnp.uint32, one_chip)
    tt = _sds((8, 256), jnp.uint32, one_chip)
    _compile(ch.graft_chain_sink, [row, row, tt, tt, tt, tt])


def test_two_stage_chain_with_grants_and_filter_compiles(v5e_2x2):
    # the stage, with its grant and filter blocks, shard-locally over the
    # four chips of the host: the rows split four ways, the stats psum'd
    from jax.sharding import Mesh

    mesh = Mesh(np.array(v5e_2x2), ("data",))
    assert mesh.shape["data"] == 4
    compiled = _compile(ch.graft_chain_stage_sharded(mesh), _sharded_stage_args(mesh))
    assert "all-reduce" in compiled.as_text()
