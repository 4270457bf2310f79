"""The one place meshes are made.

Single pod: (16, 16) ("data", "model") = 256 chips (TPU v5e pod).
Multi-pod:  (2, 16, 16) ("pod", "data", "model") = 512 chips.

Defined as functions so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS *before* any jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape=(2, 2), axes=("data", "model"), *, devices=None):
    """A mesh whose axes are all ``Auto``: GSPMD propagates shardings
    outside ``jax.shard_map``, which is manual over them inside.
    ``jax.make_mesh`` alone would make them ``Explicit``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def data_axes(mesh) -> tuple[str, ...]:
    """Axes the batch is sharded over (pod folds into data-parallelism)."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
