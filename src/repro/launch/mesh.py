"""Production mesh construction (function, never touches jax at import).

Every mesh uses Auto axis types: the model code relies on GSPMD to
propagate shardings through contractions, which Explicit axes (the
``jax.make_mesh`` default) refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) data x model single pod; (2,16,16) pod x data x model."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
