"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state. The dry-run process sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else (tests, benches) sees the single real device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the model and data plane place arrays through GSPMD
    # sharding constraints, not jax.make_mesh's default explicit-axis typing
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_smoke_mesh():
    """Single-device mesh with the production axis names — lets the same
    sharding rules run in tests on CPU."""
    return _mesh((1, 1), ("data", "model"))


def make_data_mesh(n_data: int):
    """Mesh with ``n_data`` shards on the 'data' axis and the production
    axis names. Used by the engine's mesh execution (P = data-axis size)
    and the host-device dry-runs (--xla_force_host_platform_device_count)."""
    n_data = int(n_data)
    if n_data < 1:
        raise ValueError(f"data-axis size must be >= 1, got {n_data}")
    return _mesh((n_data, 1), ("data", "model"))


def resolve_mesh(spec):
    """Resolve an EngineConfig ``mesh`` spec to a jax Mesh.

    Accepts: a Mesh (must carry a 'data' axis), the string 'smoke'
    (single-device smoke mesh), or an int n (n-way data mesh — requires n
    visible devices, e.g. via XLA_FLAGS=--xla_force_host_platform_device_count)."""
    if spec is None:
        raise ValueError("mesh spec is None — nothing to resolve")
    if isinstance(spec, str):
        if spec == "smoke":
            return make_smoke_mesh()
        raise ValueError(f"unknown mesh spec {spec!r}; expected 'smoke', an int, or a Mesh")
    if isinstance(spec, int):
        return make_data_mesh(spec)
    if "data" not in getattr(spec, "axis_names", ()):
        raise ValueError(
            f"mesh {spec!r} has no 'data' axis — the engine shards state over 'data'"
        )
    return spec


def mesh_data_size(spec) -> int:
    """The data-axis size a mesh spec resolves to, WITHOUT touching jax —
    safe to call from EngineConfig validation before any device init.
    ('smoke' -> 1, int n -> n, Mesh -> mesh.shape['data'].)"""
    if isinstance(spec, str):
        if spec == "smoke":
            return 1
        raise ValueError(f"unknown mesh spec {spec!r}; expected 'smoke', an int, or a Mesh")
    if isinstance(spec, int):
        if spec < 1:
            raise ValueError(f"data-axis size must be >= 1, got {spec}")
        return spec
    shape = getattr(spec, "shape", None)
    if shape is None or "data" not in shape:
        raise ValueError(
            f"mesh {spec!r} has no 'data' axis — the engine shards state over 'data'"
        )
    return int(shape["data"])


def data_axes(mesh) -> tuple:
    """The compound FSDP/data-parallel axis: ('pod','data') on the multi-pod
    mesh, ('data',) on a single pod."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
