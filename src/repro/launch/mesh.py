"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run entrypoint sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.
"""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (smoke tests, tuner factorization sweeps), with
    ``Auto`` axes: the LM substrate's sharding rules are constraints for
    the partitioner, not explicit array shardings."""
    axes = tuple(axes)
    return jax.make_mesh(
        tuple(shape), axes,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
    )


def mesh_axes_names(mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names)
