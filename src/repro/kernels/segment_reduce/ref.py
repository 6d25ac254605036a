"""Pure-jnp oracles for the sorted segment-sum kernel and its gather entry."""

import jax
import jax.numpy as jnp


def segment_reduce_sorted_ref(values, seg_ids, num_segments: int):
    seg = jnp.where(
        (seg_ids >= 0) & (seg_ids < num_segments), seg_ids, num_segments
    )
    return jax.ops.segment_sum(
        values.astype(jnp.float32), seg, num_segments=num_segments + 1
    )[:-1]


def gather_segment_reduce_ref(values, gather_idx, seg_ids, num_segments: int):
    """out[s] = sum over stream rows t with seg_ids[t]==s of values[gather_idx[t]]."""
    rows = jnp.take(values, gather_idx.astype(jnp.int32), axis=0)
    return segment_reduce_sorted_ref(rows, seg_ids, num_segments)
