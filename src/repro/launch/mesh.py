"""Production meshes. FUNCTIONS (not module-level constants) so importing
this module never touches jax device state.

Every mesh of the repo is built here with ``Auto`` axes: the models place
activations with ``with_sharding_constraint`` under logical-axis rules
(``distributed.sharding``) and leave the rest to the partitioner, which is
what ``Auto`` means. ``jax.make_mesh`` alone now defaults to ``Explicit``
axes, under which those constraints and the gathers inside ``shard_map``
are refused.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds the 2-pod axis (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small mesh for CPU tests (requires forced host device count)."""
    return make_mesh((n_data, n_model), ("data", "model"))
