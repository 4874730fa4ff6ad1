"""Production meshes (assignment-specified shapes).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must see the real (1-device) topology.
"""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """`jax.make_mesh` with every axis typed Auto (sharding propagated by
    the compiler, as the model code's `with_sharding_constraint`s expect)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    data = data if data is not None else max(1, n // model)
    return make_mesh((data, model), ("data", "model"))
