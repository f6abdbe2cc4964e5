"""Production mesh builders.

Functions, not module-level constants — importing this module never
touches jax device state, so tests/benches keep their 1-device world.

Production target: TPU v5e pods. Single pod = 16x16 = 256 chips
("data" x "model"); multi-pod adds a leading "pod" axis (2 x 16 x 16 =
512 chips). The same functions build reduced meshes for CPU tests via
the ``shape`` override.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes=None):
    """Arbitrary mesh (tests / elastic re-meshing)."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
