"""Production meshes.

IMPORTANT: functions, not module-level constants — importing this module
must never touch jax device state (the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE any jax
initialization; smoke tests and benches must keep seeing 1 device).
"""
from __future__ import annotations

import jax


def _auto(axes) -> tuple:
    """Auto axes: the model zoo's sharding constraints and einsums place
    arrays by annotation, which Explicit axes (``jax.make_mesh``'s
    default) reject."""
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(model_parallelism: int = 1, axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / elastic restart)."""
    n = len(jax.devices())
    model = min(model_parallelism, n)
    return jax.make_mesh((n // model, model), axes, axis_types=_auto(axes))


def batch_axes(mesh) -> tuple[str, ...]:
    """The axes a batch dimension shards over for this mesh."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)


def all_axes(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
