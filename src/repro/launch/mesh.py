"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512
before any jax import and then calls these.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes: the drivers place arrays by
    ``PartitionSpec`` and let the partitioner propagate the rest, which
    the default ``Explicit`` axes of ``jax.make_mesh`` refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CPU integration tests (requires
    --xla_force_host_platform_device_count>=8 in the test process)."""
    shape = (2, 2, 2) if multi_pod else (2, 2)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
