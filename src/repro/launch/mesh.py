"""Production mesh construction.

Single pod:  (16, 16)    axes ("data", "model")     = 256 chips (v5e pod)
Multi-pod:   (2, 16, 16) axes ("pod", "data", "model") = 512 chips

Defined as a function (never a module-level constant) so importing this
module touches no jax device state -- required because the dry-run driver
must set XLA_FLAGS before the first jax device query.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = len(jax.devices())
    need = 512 if multi_pod else 256
    if ndev < need:
        # scaled-down stand-in for fast local iteration (same axis names);
        # the real dry-run uses xla_force_host_platform_device_count=512.
        if multi_pod:
            shape = (2, 2, ndev // 4)
        else:
            shape = (2, ndev // 2)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_test_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh for unit tests (requires xla_force_host_platform_device_count)."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
