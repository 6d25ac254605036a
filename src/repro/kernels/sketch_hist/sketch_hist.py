"""Count-min sketch kernel — compressed local statistics (ROADMAP item).

The exact `K^(i)` histogram (``kernels/histogram``) scales with the
number of operation clusters ``n``; the sketch replaces it with a
``(depth, width)`` counter grid where ``width`` is a power of two far
below ``n``. Each of the ``depth`` rows hashes every cluster id through
an independent multiply-shift hash ``h_r(x) = (a_r * x) >> (32 -
log2(width))`` (odd multiplier ``a_r``) and accumulates the pair weight
into the hashed bin. Reading the sketch takes the **min over rows** —
every row's cell is the true count plus non-negative collision mass, so
estimates only ever overestimate (the count-min guarantee the planner's
send capacities rely on; see ``core/stats_provider.py``).

TPU design
----------
Same one-hot compare + reduction formulation as the histogram kernel
(no TPU scatter-add), with the hash computed in-register per row:

* grid = (depth, bin_blocks, token_blocks) — rows and bin windows are
  "parallel"; the token-block axis is innermost and "arbitrary" so
  accumulation across token blocks sequentially revisits one output
  tile (zeroed on the first visit).
* Ids and weights are ``(1, N)`` rows (tokens on lanes); the row's odd
  multiplier is read from SMEM. Each program hashes its token slab in
  int32 (wrapping multiply + logical shift — bit-identical to the
  uint32 multiply-shift), builds ``onehot[b, t] = (h_r(ids[t]) == bin0 +
  b)`` and accumulates ``w @ onehot^T`` — a ``(1, bt) x (bt, bins)`` MXU
  matmul — into its ``(1, block_bins)`` output tile.

Default blocks (512 tokens × 1024 bins) keep the f32 one-hot at 2 MB —
comfortably inside v5e VMEM next to the id/weight slabs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sketch_kernel(mult_ref, ids_ref, w_ref, out_ref, *,
                   block_bins: int, shift: int):
    tb = pl.program_id(2)  # token-block index (innermost, sequential)

    @pl.when(tb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]                  # (1, block_tokens) int32
    w = w_ref[...]                      # (1, block_tokens) f32
    mult = mult_ref[pl.program_id(0)]   # this row's odd multiplier (SMEM)
    # Multiply-shift hash: the int32 multiply wraps mod 2^32 exactly like
    # a uint32 one, and the logical right shift keeps the top log2(width)
    # bits — h_r(x) in [0, width).
    hashed = jax.lax.shift_right_logical(ids * mult, shift)
    bin0 = pl.program_id(1) * block_bins
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, (block_bins, ids.shape[1]), 0)
        + bin0 == hashed
    ).astype(jnp.float32)               # (block_bins, block_tokens)
    out_ref[...] += jax.lax.dot_general(
        w, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(
    jax.jit,
    static_argnames=("width", "block_tokens", "block_bins", "interpret"),
)
def sketch_hist_pallas(
    ids: jax.Array,
    weights: jax.Array,
    multipliers: jax.Array,
    width: int,
    *,
    interpret: bool,
    block_tokens: int = 512,
    block_bins: int = 1024,
) -> jax.Array:
    """``out[r, b] = sum_t weights[t] * (h_r(ids[t]) == b)``; (depth, width).

    ``width`` must be a power of two >= 2 (the hash is a top-bits
    extract); ``multipliers`` is the (depth,) uint32 vector of odd
    hash multipliers. ``interpret`` selects the Pallas interpreter (CPU)
    or Mosaic (TPU); the ``ops`` wrapper picks it from the backend.
    """
    (n,) = ids.shape
    (depth,) = multipliers.shape
    if width < 2 or width & (width - 1):
        raise ValueError(f"width must be a power of two >= 2, got {width}")
    shift = 32 - (width.bit_length() - 1)
    block_bins = min(block_bins, width)  # both powers of two: divides evenly
    # Pad tokens up to a block multiple; padded entries carry zero weight
    # (a padded id hashes to SOME bin, the weight keeps it from counting).
    pad = (-n) % block_tokens
    if pad:
        ids = jnp.concatenate([ids, jnp.zeros((pad,), ids.dtype)])
        weights = jnp.concatenate([weights, jnp.zeros((pad,), weights.dtype)])

    grid = (depth, width // block_bins, ids.shape[0] // block_tokens)
    # Rows live on a leading axis of their own: a (1, block_bins) tile of
    # a (depth, width) array breaks the (8, 128) tiling rule for depth < 8.
    out = pl.pallas_call(
        functools.partial(_sketch_kernel, block_bins=block_bins, shift=shift),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_tokens), lambda r, b, t: (0, t)),
            pl.BlockSpec((1, block_tokens), lambda r, b, t: (0, t)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_bins), lambda r, b, t: (r, 0, b)),
        out_shape=jax.ShapeDtypeStruct((depth, 1, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(jax.lax.bitcast_convert_type(multipliers.astype(jnp.uint32), jnp.int32),
      ids.astype(jnp.int32)[None, :], weights.astype(jnp.float32)[None, :])
    return out.reshape(depth, width)
