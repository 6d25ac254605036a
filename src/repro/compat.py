"""Shared spellings of the jax mesh API (jax >= 0.9).

* :func:`shard_map` — ``jax.shard_map`` with the varying-manual-axes
  check off by default (the engine's bodies mix replicated and varying
  values on purpose).
* :func:`make_mesh` — ``jax.make_mesh`` with ``Auto`` axis types, so
  ``jit`` keeps inserting the sharding constraints the callers rely on.
"""

from __future__ import annotations

import jax

__all__ = ["shard_map", "make_mesh"]


def shard_map(fn, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by default."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check,
    )


def make_mesh(shape, axes) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
