"""Compile rehearsal: the main path's device programs compile for a TPU v5e.

Each test lowers and compiles one program of the data plane — the probes
and the fused stage chain — for one chip of a described (not attached)
``v5e:2x2`` topology, at TPC-H SF-1 sizes: a 65,536-row morsel, a
4,194,304-slot probe table (the orders state's ~1.5M keys at the 50% load
factor) and 2,097,152-entry mirrors. Nothing runs; the chip's compiler
refuses here what it would refuse on the chip (an unsupported gather, a
program that does not fit the device's memory).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports every
test file.
"""

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.fused_chain import _chain_fn
from repro.kernels.hash_probe import hash_probe_lens64, hash_probe_lens_multi64

ROWS = 65536
SLOTS = 1 << 22
ENTRIES = 1 << 21
HBM_BYTES = 16 * 10**9  # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

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
        yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)


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


def _chain_args(spec, sh):
    """Shapes of ``input_kinds(spec)``'s traversal at the SF-1 sizes."""
    i32, u32 = jnp.int32, jnp.uint32
    stages, sink = spec
    args = [_sds((ROWS,), u32, sh), _sds((ROWS,), u32, sh)]
    for key_mode, n_grants, grant_attrs, filt in stages:
        args.append(_sds((ROWS if key_mode == -1 else ENTRIES,), i32, sh))
        args += [_sds((SLOTS,), i32, sh), _sds((SLOTS,), i32, sh)]
        args += [_sds((ENTRIES,), u32, sh), _sds((ENTRIES,), u32, sh)]
        args += [_sds((8, 256), u32, sh), _sds((8, 256), u32, sh)]
        if n_grants:
            g, a = n_grants, grant_attrs
            args += [_sds((ENTRIES,), u32, sh), _sds((ENTRIES,), u32, sh)]
            args += [_sds((g, 2), u32, sh), _sds((g, 2), u32, sh)]
            args += [_sds((g, a), i32, sh)]
            args += [_sds((g, a, 2), u32, sh), _sds((g, a, 2), u32, sh)]
            args += [_sds((ENTRIES,), u32, sh)] * (2 * a)
        if filt is not None:
            m, srcs = filt
            for src in srcs:
                args += [_sds((ROWS if src == -1 else ENTRIES,), u32, sh)] * 2
            a = len(srcs)
            args += [_sds((m, a, 2), u32, sh), _sds((m, a, 2), u32, sh)]
            args += [_sds((m, a), i32, sh), _sds((m, 2), u32, sh)]
    if sink:
        args += [_sds((8, 256), u32, sh)] * 4
    return args


def test_single_lens_probe_compiles(one_chip):
    args = _probe_args(one_chip) + [_sds((2,), jnp.uint32, one_chip)]
    _compile(hash_probe_lens64, args)


def test_multi_member_probe_compiles(one_chip):
    _compile(hash_probe_lens_multi64, _probe_args(one_chip))


def test_one_stage_chain_with_sink_compiles(one_chip):
    spec = (((-1, 0, 0, None),), True)
    _compile(_chain_fn(spec), _chain_args(spec, one_chip))


def test_two_stage_chain_with_grants_and_filter_compiles(one_chip):
    # stage 1 takes its keys from stage 0's matched entries, resolves two
    # compiled grants over one attr, and filters three members on a
    # row-sourced and an entry-sourced attr
    spec = (((-1, 0, 0, None), (0, 2, 1, (3, (-1, 0)))), False)
    _compile(_chain_fn(spec), _chain_args(spec, one_chip))
