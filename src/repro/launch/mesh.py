"""Production mesh construction.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — critical because the dry-run forces 512 host
devices via XLA_FLAGS before any jax import, while tests/benchmarks must see
the real single device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types on every axis.

    jax's default is Explicit axes, under which the sharded index's
    gathers cannot resolve their output sharding; every mesh in this repo
    is built here so that the partitioner (and ``shard_map``) decide."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips) mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over whatever devices exist (CPU tests: 4/8 host devices)."""
    n = len(jax.devices())
    n_data = n_data if n_data is not None else n // n_model
    return make_mesh((n_data, n_model), ("data", "model"))
