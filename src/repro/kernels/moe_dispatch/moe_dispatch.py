"""MoE/shuffle dispatch kernel: stable counting-sort ranks + counts.

This is the "copy"-phase address computation shared by the MapReduce
shuffle and the MoE token dispatch: given each token's destination
(Reduce slot, or expert after OS4M placement), compute

  rank[t]   = #{t' < t : dest[t'] == dest[t]}   (stable position in bucket)
  counts[e] = #{t : dest[t] == e}               (the K^(i) statistics)

``rank`` is what makes a fixed-capacity bucket scatter deterministic and
drop-newest under overflow; ``counts`` feeds the OS4M scheduler.

TPU design
----------
The loop-carried dependence (running per-destination offsets) is the part
a GPU handles with atomics; TPU-natively it becomes a *sequential grid
walk with VMEM-resident carry*:

* grid = (token_blocks,) — one sequential axis; scratch ``carry (E,)``
  holds the running per-destination counts across blocks.
* Per block: one-hot (block_tokens, E) on the VPU; an exclusive cumsum
  down the token axis gives within-block ranks; ``rank = within + carry``
  gathered via the same one-hot (a (bt,E)·(E,) contraction, MXU-eligible).
* E is the number of slots/experts (≤ a few hundred) so the carry and
  one-hot tiles are small; block_tokens = 1024 keeps the one-hot ≤ 2 MB
  for E ≤ 512.

The actual scatter into (E, capacity) buckets is done by XLA in ops.py —
a single known-index scatter is already optimal there; the kernel owns the
sequential rank computation that would otherwise serialise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dispatch_kernel(dest_ref, rank_ref, counts_ref, carry_ref, *, num_dests: int,
                     num_blocks: int):
    tb = pl.program_id(0)

    @pl.when(tb == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    dest = dest_ref[...]  # (bt,) int32; invalid marked as >= num_dests or < 0
    bt = dest.shape[0]
    valid = (dest >= 0) & (dest < num_dests)
    onehot = (
        jnp.where(valid, dest, num_dests)[:, None]
        == jax.lax.broadcasted_iota(jnp.int32, (bt, num_dests), 1)
    ).astype(jnp.float32)
    incl = jnp.cumsum(onehot, axis=0)
    excl = incl - onehot                      # exclusive: earlier-in-block count
    within = jnp.sum(excl * onehot, axis=1)   # (bt,)
    base = jnp.sum(onehot * carry_ref[0][None, :], axis=1)
    rank_ref[...] = jnp.where(valid, (within + base).astype(jnp.int32), -1)
    carry_ref[...] = carry_ref[...] + incl[-1][None, :]

    @pl.when(tb == num_blocks - 1)
    def _emit_counts():
        counts_ref[...] = carry_ref[...]


@functools.partial(
    jax.jit, static_argnames=("num_dests", "block_tokens", "interpret")
)
def dispatch_ranks_pallas(
    dest: jax.Array,  # (T,) int32
    num_dests: int,
    *,
    block_tokens: int = 1024,
    interpret: bool,
):
    (t,) = dest.shape
    block_tokens = min(block_tokens, max(t, 1))
    pad = (-t) % block_tokens
    if pad:
        dest = jnp.concatenate([dest, jnp.full((pad,), -1, dest.dtype)])
    num_blocks = dest.shape[0] // block_tokens

    rank, counts = pl.pallas_call(
        functools.partial(
            _dispatch_kernel, num_dests=num_dests, num_blocks=num_blocks
        ),
        grid=(num_blocks,),
        in_specs=[pl.BlockSpec((block_tokens,), lambda i: (i,))],
        out_specs=[
            pl.BlockSpec((block_tokens,), lambda i: (i,)),
            pl.BlockSpec((1, num_dests), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((dest.shape[0],), jnp.int32),
            jax.ShapeDtypeStruct((1, num_dests), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, num_dests), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(dest.astype(jnp.int32))
    return rank[:t], counts[0].astype(jnp.int32)
