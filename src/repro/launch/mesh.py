"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state.  The production fleet is one TPU v5e pod = 16 x 16 = 256
chips (axes data x model); the multi-pod configuration prepends a pod axis
(2 x 16 x 16 = 512 chips).  The dry-run launcher sets
``--xla_force_host_platform_device_count=512`` BEFORE importing jax.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_dev_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for tests/examples on forced host devices."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def describe(mesh: jax.sharding.Mesh) -> dict:
    return {"axis_names": list(mesh.axis_names),
            "shape": [int(mesh.shape[a]) for a in mesh.axis_names],
            "devices": int(mesh.devices.size)}
