"""Public wrapper for the count-min sketch kernel (compressed statistics)."""

from __future__ import annotations

import jax

from repro import kernels as _k
from repro.kernels.sketch_hist.sketch_hist import sketch_hist_pallas


def sketch_hist(ids: jax.Array, weights: jax.Array, multipliers: jax.Array,
                width: int) -> jax.Array:
    """Weighted count-min counters (depth, width) of integer ids."""
    return sketch_hist_pallas(
        ids.reshape(-1), weights.reshape(-1), multipliers, width,
        interpret=_k.interpret(),
    )
