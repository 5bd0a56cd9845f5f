"""Production mesh builders.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state; the dry-run sets
``--xla_force_host_platform_device_count=512`` *before* any JAX import.

The model zoo shards by GSPMD propagation (parameter shardings plus
``with_sharding_constraint`` anchors, :mod:`repro.models.sharding`), so its
meshes declare ``Auto`` axes.  ``jax.make_mesh`` defaults to ``Explicit``
axes, under which every op must resolve its own output sharding: the
embedding gather then asks for ``P('data', None, 'data')`` (batch and the
table's FSDP dim both on ``data``) and raises ``DuplicateSpecError``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single-pod mesh, or 2 pods x 16 x 16 = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh for CPU tests/examples (requires enough host devices)."""
    return _auto_mesh((data, model), ("data", "model"))
