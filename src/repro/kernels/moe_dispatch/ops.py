"""Public wrappers for the dispatch kernel (MoE / shuffle "copy" phase)."""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import kernels as _k
from repro.kernels.moe_dispatch.moe_dispatch import dispatch_ranks_pallas


def dispatch_ranks(dest: jax.Array, num_dests: int):
    """Stable in-bucket rank per token + per-destination counts."""
    return dispatch_ranks_pallas(dest, num_dests, interpret=_k.interpret())


def dispatch_to_buckets(values: jax.Array, dest: jax.Array, num_dests: int,
                        capacity: int):
    """Scatter (T, V) values into (num_dests, capacity, V) buckets.

    Tokens beyond a bucket's capacity are dropped (drop-newest — the
    deterministic policy the capacity bound of the OS4M schedule implies).
    Returns (buckets, clamped_counts, overflow).
    """
    rank, counts = dispatch_ranks(dest, num_dests)
    ok = (rank >= 0) & (rank < capacity)
    flat = jnp.where(ok, dest * capacity + rank, num_dests * capacity)
    out = (
        jnp.zeros((num_dests * capacity + 1, values.shape[-1]), values.dtype)
        .at[flat]
        .set(jnp.where(ok[:, None], values, 0))[:-1]
        .reshape(num_dests, capacity, values.shape[-1])
    )
    overflow = jnp.sum((rank >= capacity).astype(jnp.int32))
    return out, jnp.minimum(counts, capacity), overflow


def plan_capacity_slabs(capacity: int, num_chunks: int) -> Tuple[Tuple[int, int], ...]:
    """Static (start, size) slabs cutting a bucket's capacity axis into
    pipeline chunks.

    This is the §4.4 chunk planner (``pipeline.plan_chunks``) applied to
    the dispatch bucket layout: before routing runs, every capacity row is
    equally likely to be filled, so the planner sees uniform loads and
    yields contiguous near-equal slabs. Callers all-to-all the slabs one
    at a time, overlapping slab ``i+1``'s "copy" with slab ``i``'s expert
    compute (the MoE analogue of the shuffle→reduce pipeline).
    """
    from repro.core import pipeline as pipe

    if num_chunks <= 1 or capacity <= 1:
        return ((0, capacity),)
    chunks = pipe.plan_chunks([1.0] * capacity, num_chunks, "arrival")
    return tuple((int(c[0]), len(c)) for c in chunks)


def dispatch_to_buckets_chunked(
    values: jax.Array, dest: jax.Array, num_dests: int, capacity: int,
    num_chunks: int,
):
    """Like :func:`dispatch_to_buckets`, pre-split into pipeline slabs.

    Returns ``(slabs, clamped_counts, overflow)`` where ``slabs`` is a
    tuple of ``(num_dests, size_c, V)`` views of the bucket tensor, one per
    chunk of :func:`plan_capacity_slabs` — ready for a chunked all-to-all.
    """
    buckets, counts, overflow = dispatch_to_buckets(
        values, dest, num_dests, capacity
    )
    slabs = tuple(
        buckets[:, s : s + z] for s, z in plan_capacity_slabs(capacity, num_chunks)
    )
    return slabs, counts, overflow
