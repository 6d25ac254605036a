"""XOR word-combine kernel — the coded-shuffle multicast encoder/decoder.

Coded MapReduce (Li et al., arXiv 1512.01625) replaces unicast shuffle
slabs with multicast packets: a sender XOR-combines the two destination
slabs it holds for a multicast pair, and each receiver XORs the packet
against the slab it can reconstruct from its locally-replicated map data
to recover the slab meant for it. Because ``A ⊕ B ⊕ B = A`` holds on bit
patterns, the decode is *exact* — the engine's bit-identity contract
survives coding by construction.

This kernel is the one compute primitive of that scheme: elementwise XOR
over int32/uint32 *word* views of the payload slabs (float payloads are
bit-cast to words before combining — see ``ops.pack_payload_words``).
Encode and decode are the same operation, so one kernel serves both
sides of the wire.

TPU design
----------
Embarrassingly parallel VPU work: grid over row blocks, each program
XORs one ``(block_rows, words)`` tile resident in VMEM. No reductions,
no cross-block state — ``dimension_semantics=("parallel",)``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _xor_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jax.lax.bitwise_xor(a_ref[...], b_ref[...])


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def xor_words_pallas(
    a: jax.Array,            # (N, W) int32 or uint32 payload words
    b: jax.Array,            # (N, W) same shape/dtype as ``a``
    *,
    block_rows: int = 1024,
    interpret: bool,
) -> jax.Array:
    """Elementwise ``a ^ b`` over word slabs. Returns ``(N, W)`` words.

    Args: ``a``/``b`` must share an integer word dtype (int32 or uint32 —
    the bit-cast views produced by ``ops.pack_payload_words``) and shape.
    ``block_rows`` trades VMEM tile size for grid length;
    ``interpret=True`` runs in interpret mode (CPU tests).
    """
    if a.shape != b.shape or a.dtype != b.dtype:
        raise ValueError(
            f"xor_words needs matching operands, got {a.shape}/{a.dtype} "
            f"vs {b.shape}/{b.dtype}"
        )
    if not jnp.issubdtype(a.dtype, jnp.integer):
        raise ValueError(f"xor_words operates on word views, got {a.dtype}")
    n, w = a.shape
    block_rows = min(block_rows, max(n, 1))
    pad = (-n) % block_rows
    if pad:
        a = jnp.concatenate([a, jnp.zeros((pad, w), a.dtype)])
        b = jnp.concatenate([b, jnp.zeros((pad, w), b.dtype)])
    grid = (a.shape[0] // block_rows,)
    out = pl.pallas_call(
        _xor_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((a.shape[0], w), a.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(a, b)
    return out[:n]
