"""Production mesh construction. A FUNCTION (not a module-level constant) so
importing this module never touches jax device state."""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2,
    data=16, model=16) = 512 chips, the "pod" axis carrying pure DP whose
    gradient all-reduce crosses the inter-pod links."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))
