"""Public wrapper for the histogram kernel (OS4M local statistics)."""

from __future__ import annotations

import jax

from repro import kernels as _k
from repro.kernels.histogram.histogram import histogram_pallas


def histogram(ids: jax.Array, weights: jax.Array, num_bins: int) -> jax.Array:
    """Weighted histogram of integer ids; the K^(i) vector of paper eq. 4-1."""
    return histogram_pallas(
        ids.reshape(-1), weights.reshape(-1), num_bins, interpret=_k.interpret()
    )
