"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; ordinary processes (tests, benches) see 1 device and only ever call
this with meshes that fit.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def use_mesh(mesh):
    """The ambient-mesh context (``jax.set_mesh``)."""
    return jax.set_mesh(mesh)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """A mesh with Auto axes: the SPMD layer leaves propagation to XLA
    (``jax.make_mesh`` alone would make the axes Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

