"""Production mesh construction (multi-pod dry-run spec, task brief step 1).

Defined as FUNCTIONS so importing this module never touches jax device state
— jax locks the device count on first backend initialisation, and only
``dryrun.py`` (which sets XLA_FLAGS before any import) should see 512 devices.
"""
from __future__ import annotations

import jax


def make_mesh_compat(shape, axes):
    """`jax.make_mesh` with every axis `Auto` (GSPMD-propagated)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh_compat(shape, axes)


def make_test_mesh(data: int = 2, model: int = 4, pod: int = 0):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count
    set in the test's subprocess environment)."""
    if pod:
        return make_mesh_compat((pod, data, model), ("pod", "data", "model"))
    return make_mesh_compat((data, model), ("data", "model"))
