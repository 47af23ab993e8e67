"""Mesh construction: the production mesh, the CPU test mesh, and a mesh
over the devices that are really attached.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benchmarks see the real single device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.configs.base import MeshConfig


def _make_mesh(shape, axes, devices=None) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(2, 4), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for CPU sharding tests (8 fake devices)."""
    return _make_mesh(shape, axes)


def make_device_mesh(model: int = 1) -> jax.sharding.Mesh:
    """(data=1, model) mesh over the first ``model`` attached devices:
    ``model=1`` is one chip unsharded, ``model=4`` one v5e host's 2x2."""
    devices = jax.devices()
    if len(devices) < model:
        raise ValueError(f"a model={model} mesh needs {model} devices; "
                         f"{len(devices)} attached")
    return _make_mesh((1, model), ("data", "model"), devices[:model])


def mesh_context(mesh: jax.sharding.Mesh):
    """Activate ``mesh`` for the model's shard_hint constraints."""
    return jax.set_mesh(mesh)


def mesh_config_of(mesh: jax.sharding.Mesh) -> MeshConfig:
    return MeshConfig(multi_pod="pod" in mesh.axis_names)


def batch_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """Mesh axes that shard the batch (pure DP across pods + FSDP data axis)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
