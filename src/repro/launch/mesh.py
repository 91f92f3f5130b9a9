"""Mesh construction.

A FUNCTION, not a module-level constant: importing this module never
touches jax device state (jax locks the device count on first init, and
only the dry-run process sets --xla_force_host_platform_device_count)."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """A mesh over ``devices``, by default the CPU devices.

    Workload exports compile for the CPU on every host (see
    :func:`repro.core.pipeline.export_workload`), so their meshes are CPU
    meshes; a run on accelerators passes ``devices=jax.devices()``."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=jax.devices("cpu") if devices is None else devices)
