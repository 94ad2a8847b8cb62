"""Production mesh builders.

Single pod: 16 x 16 = 256 chips (TPU v5e pod), axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — the 'pod'
axis hosts cross-silo FL clients for the giant architectures and extends
the client axis for the small ones.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    # Auto axes: the engine's gathers and einsums rely on GSPMD propagation,
    # which explicit-sharding axes (the make_mesh default) would refuse.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over the actually-present local devices (tests, CPU).

    Also the default substrate of the simulation MeshBackend
    (`repro.core.backend`): every local device lands on the 'data' axis,
    which hosts the FL client dimension — force a multi-device CPU mesh
    with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``."""
    n = len(jax.devices())
    data = data or (n // model)
    return _mesh((data, model), ("data", "model"))
