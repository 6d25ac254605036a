"""Weighted histogram kernel — the paper's local statistics ``K^(i)`` (§4.1).

Counts (optionally weighted) occurrences of integer ids into ``num_bins``
bins. This is the per-shard half of OS4M's communication mechanism: each
shard computes its own key-distribution vector which is then ``psum``'d
over the mesh (the TaskTracker→JobTracker aggregation tree).

TPU design
----------
The scatter-add a GPU would use has no efficient TPU analogue (no fast
random-access HBM atomics); the TPU-native formulation is a *one-hot
compare + matmul* over VMEM tiles:

* grid = (bin_blocks, token_blocks) — tokens are tiled so the id/weight
  slab fits VMEM; bins are tiled so the one-hot compare matrix
  ``(block_bins, block_tokens)`` stays within a few MB of VMEM.
* Ids and weights are ``(1, N)`` rows (tokens on lanes): a 1-D block
  would put the batch axis in the tiled dims once the kernel is vmapped
  over Reduce slots, which Mosaic refuses.
* Each program builds ``onehot[b, t] = (ids[t] == bin0 + b)`` and
  accumulates ``w @ onehot^T`` — a ``(1, bt) x (bt, bins)`` MXU matmul at
  f32 precision — into its ``(1, block_bins)`` output tile. The
  token-block grid axis is innermost and marked "arbitrary" so the
  accumulation across token blocks is a sequential revisit of the same
  output tile (zeroed on the first visit).

Block sizes default to (512 tokens × 1024 bins): the f32 one-hot is
2 MB, well inside v5e VMEM next to the id/weight slabs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _histogram_kernel(ids_ref, w_ref, out_ref, *, block_bins: int):
    tb = pl.program_id(1)  # token-block index (innermost, sequential)

    @pl.when(tb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    bin0 = pl.program_id(0) * block_bins
    ids = ids_ref[...]  # (1, block_tokens) int32
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (block_bins, ids.shape[1]), 0)
        + bin0 == ids
    ).astype(jnp.float32)  # (block_bins, block_tokens)
    out_ref[...] += jax.lax.dot_general(
        w_ref[...], onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit, static_argnames=("num_bins", "block_tokens", "block_bins", "interpret")
)
def histogram_pallas(
    ids: jax.Array,
    weights: jax.Array,
    num_bins: int,
    *,
    interpret: bool,
    block_tokens: int = 512,
    block_bins: int = 1024,
) -> jax.Array:
    """``out[b] = sum_t weights[t] * (ids[t] == b)`` for b in [0, num_bins).

    ``interpret`` selects the Pallas interpreter (CPU) or Mosaic (TPU);
    the ``ops`` wrapper picks it from the backend.
    """
    (n,) = ids.shape
    # Bin tiles are lane-dense: a multiple of 128 (or the whole padded
    # range when it is smaller than one tile).
    block_bins = min(block_bins, -(-num_bins // 128) * 128)
    # Pad tokens up to a block multiple; padded ids point outside every bin.
    pad = (-n) % block_tokens
    if pad:
        ids = jnp.concatenate([ids, jnp.full((pad,), -1, ids.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])
    nbins_padded = num_bins + (-num_bins) % block_bins

    grid = (nbins_padded // block_bins, ids.shape[0] // block_tokens)
    out = pl.pallas_call(
        functools.partial(_histogram_kernel, block_bins=block_bins),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_tokens), lambda b, t: (0, t)),
            pl.BlockSpec((1, block_tokens), lambda b, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((1, block_bins), lambda b, t: (0, b)),
        out_shape=jax.ShapeDtypeStruct((1, nbins_padded), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(ids.astype(jnp.int32)[None, :], weights.astype(jnp.float32)[None, :])
    return out[0, :num_bins]
