"""Production mesh definitions.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — dryrun.py must set XLA_FLAGS before any jax
device-count lock-in.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    """`jax.make_mesh` with Auto axes: the model stack places activations
    with `with_sharding_constraint`, which Explicit axes (make_mesh's
    default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 pod: 16x16 (data, model); two pods add a leading 'pod' axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2, pods: int | None = None):
    """Small mesh for CPU tests (requires xla_force_host_platform_device_count
    >= data*model*(pods or 1))."""
    if pods:
        return _mesh((pods, data, model), ("pod", "data", "model"))
    return _mesh((data, model), ("data", "model"))
