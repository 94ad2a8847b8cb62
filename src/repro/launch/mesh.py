"""Device meshes over the local devices."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the engine's gathers and einsums rely on GSPMD propagation,
    # which explicit-sharding axes (the make_mesh default) would refuse.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over the actually-present local devices (tests, CPU).

    Also the default substrate of the simulation MeshBackend
    (`repro.core.backend`): every local device lands on the 'data' axis,
    which hosts the FL client dimension — force a multi-device CPU mesh
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    n = len(jax.devices())
    data = data or (n // model)
    return _mesh((data, model), ("data", "model"))
