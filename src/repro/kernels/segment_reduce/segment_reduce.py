"""Sorted segment-sum kernel — the Reduce "run" phase (paper §4.4).

After the shuffle ("copy") and the sort phase, a Reduce slot holds its
pairs ordered by operation-cluster id (the "bucket file" layout). The run
phase aggregates each cluster's values:  ``out[s] = sum_{t: seg[t]==s} v[t]``.

TPU design
----------
On a GPU this is a scatter-add; on TPU we exploit the *sortedness*: a
token block only ever touches the contiguous window of segments
``[seg[t0], seg[t1]]``.

* **Lane-dense operands.** Values enter transposed, ``(V, N)`` with
  tokens on lanes, and the output is ``(V, S)`` with segments on lanes:
  V is small (8 in the word-count job), and a ``(N, V)`` block would pad
  every row to 128 lanes in VMEM. Segment ids are one ``(1, N)`` row.
* **Merge-path grid.** Sortedness makes the touched ``(segment block,
  token block)`` pairs a monotone staircase. The grid walks that
  staircase from ``(0, 0)`` to ``(S_blocks-1, T_blocks-1)``, advancing
  one coordinate per step — ``S_blocks + T_blocks - 1`` steps instead of
  ``S_blocks * T_blocks``. Every output block is visited (zeroed on its
  first visit), every token block once per segment block it overlaps.
  The walk is computed outside the kernel from each token block's first
  and last id and handed over as scalar-prefetch (SMEM) arrays, which
  the index maps read.
* Each step that overlaps builds the one-hot ``P[s, t] = (seg[t] == s0 +
  s)`` and accumulates ``v @ P^T`` — an MXU matmul of shape ``(V, bt) x
  (bt, bs)`` — into its ``(V, bs)`` output tile.

Default tiles: 512 tokens × 512 segments ⇒ one-hot 1 MB + a ``(V, 512)``
value slab, well inside VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _segsum_kernel(sb_ref, tb_ref, lo_ref, hi_ref, seg_ref, val_ref, out_ref,
                   *, block_segs: int):
    i = pl.program_id(0)
    sb = sb_ref[i]
    tb = tb_ref[i]

    @pl.when((i == 0) | (sb != sb_ref[jnp.maximum(i - 1, 0)]))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg0 = sb * block_segs

    @pl.when((hi_ref[tb] >= seg0) & (lo_ref[tb] < seg0 + block_segs))
    def _work():
        seg = seg_ref[...]                               # (1, bt)
        vals = val_ref[...]                              # (V, bt)
        onehot = (
            jax.lax.broadcasted_iota(jnp.int32, (block_segs, seg.shape[1]), 0)
            + seg0 == seg
        ).astype(vals.dtype)                             # (bs, bt)
        out_ref[...] += jax.lax.dot_general(
            vals, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,
        )


def _merge_path(seg_ids: jax.Array, block_tokens: int, block_segs: int,
                num_seg_blocks: int):
    """Per-step (segment block, token block) of the staircase walk.

    Token block ``t`` is walked over segment blocks ``enter(t) ..
    exit(t)`` where ``exit(t)`` is the block of its last id (the last
    token block runs to the end) and ``enter(t) = exit(t - 1)``; step
    ``i`` then lies in token block ``t = max{t : enter(t) + t <= i}`` at
    segment block ``i - t``.
    """
    blocks = seg_ids.reshape(-1, block_tokens)
    lo, hi = blocks[:, 0], blocks[:, -1]
    last = jnp.clip(hi // block_segs, 0, num_seg_blocks - 1)
    last = jax.lax.cummax(last.at[-1].set(num_seg_blocks - 1))
    enter = jnp.concatenate([jnp.zeros((1,), last.dtype), last[:-1]])
    num_tok_blocks = blocks.shape[0]
    start = enter + jnp.arange(num_tok_blocks, dtype=last.dtype)
    steps = jnp.arange(num_seg_blocks + num_tok_blocks - 1, dtype=last.dtype)
    tb = (jnp.searchsorted(start, steps, side="right") - 1).astype(jnp.int32)
    sb = (steps - tb).astype(jnp.int32)
    return sb, tb, lo, hi


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_tokens", "block_segs", "interpret"),
)
def segment_reduce_sorted_pallas(
    values: jax.Array,       # (N, V) — sorted by seg_ids
    seg_ids: jax.Array,      # (N,) int32, non-decreasing
    num_segments: int,
    *,
    interpret: bool,
    block_tokens: int = 512,
    block_segs: int = 512,
) -> jax.Array:
    """``out[s] = Σ_{t: seg_ids[t]==s} values[t]``; ``(num_segments, V)`` f32.

    ``seg_ids`` must be **non-decreasing** (the bucket layout); ids
    outside ``[0, num_segments)`` are padding and contribute nothing.
    ``block_tokens`` is never shrunk to ``N``: the per-block matmul's f32
    association depends on the reduction length, so a fixed block keeps
    outputs invariant to the padded slab length — two callers that feed
    the same valid stream at different slab sizes reduce bit-identically. ``interpret`` selects the Pallas
    interpreter (CPU) or Mosaic (TPU); the ``ops`` wrapper picks it from
    the backend.
    """
    n, v = values.shape
    block_segs = min(block_segs, num_segments)
    pad = (-n) % block_tokens
    if pad:
        values = jnp.concatenate([values, jnp.zeros((pad, v), values.dtype)])
        # Padded ids sit past every real segment (keeps sortedness).
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((pad,), num_segments, seg_ids.dtype)]
        )
    seg_ids = seg_ids.astype(jnp.int32)
    nseg_padded = num_segments + (-num_segments) % block_segs
    num_seg_blocks = nseg_padded // block_segs
    sb, tb, lo, hi = _merge_path(seg_ids, block_tokens, block_segs,
                                 num_seg_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(sb.shape[0],),
        in_specs=[
            pl.BlockSpec((1, block_tokens), lambda i, sb, tb, lo, hi: (0, tb[i])),
            pl.BlockSpec((v, block_tokens), lambda i, sb, tb, lo, hi: (0, tb[i])),
        ],
        out_specs=pl.BlockSpec((v, block_segs),
                               lambda i, sb, tb, lo, hi: (0, sb[i])),
    )
    out = pl.pallas_call(
        functools.partial(_segsum_kernel, block_segs=block_segs),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((v, nseg_padded), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(sb, tb, lo, hi, seg_ids[None, :], values.T)
    return out[:, :num_segments].T
