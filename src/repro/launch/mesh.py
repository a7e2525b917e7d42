"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state. The single-pod mesh is 16x16 = 256 chips
(data, model); the multi-pod mesh adds a leading pod axis: 2x16x16 = 512.
"""
from __future__ import annotations

import jax

from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(shape=None, axes=("data", "model")):
    """Mesh over whatever devices exist (tests / single host)."""
    n = len(jax.devices())
    if shape is None:
        shape = (1, n)
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
