"""Production meshes (per the assignment).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and only
dryrun.py sets the 512-device XLA flag).
"""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with auto (GSPMD-propagated) axes on every name."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU tests (1x1, same axis names)."""
    return make_mesh((1, 1), ("data", "model"))


def make_host_multi_mesh(shape=(2, 4)):
    """Multi-device host-platform mesh for sharded-ACU tests and the
    ``[sharded]`` benchmark section (same ``(data, model)`` axis names as
    production). Needs ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    (N >= prod(shape)) exported *before* jax initializes; raises otherwise so
    callers fail loudly instead of silently benchmarking a 1-device mesh."""
    import numpy as np
    need = int(np.prod(shape))
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"host mesh {shape} needs {need} devices, found {have}; export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} before "
            f"importing jax")
    return make_mesh(shape, ("data", "model"))
