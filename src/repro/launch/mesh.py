"""Production mesh construction.

Kept as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialisation, and smoke tests must keep seeing 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_test_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod mesh: 16x16 = 256 chips per pod; 2 pods = 512 chips.

    Axes: ("data", "model") single-pod, ("pod", "data", "model") multi-pod.
    The "pod" axis carries cross-pod data parallelism (with optional int8
    error-feedback gradient compression — optim/compression.py) and is the
    slow-link axis: DCI between pods vs ICI within a pod.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the model places
    arrays through ``NamedSharding``/``with_sharding_constraint``, which
    an ``Explicit`` axis (the make_mesh default) refuses."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(data: int = 2, model: int = 2):
    """Small mesh for subprocess-based distribution tests (8 host devices)."""
    return _auto_mesh((data, model), ("data", "model"))
