"""Public wrapper for the sorted segment-sum kernel (Reduce "run" phase).

The engine's chunk reduce gathers the received pairs into rank order in
XLA and hands the sorted stream to this kernel. The gather stays outside
the kernel: an in-kernel gather needs the whole value table in VMEM,
which a real chunk (2^18 rows per slot) does not fit.
"""

from __future__ import annotations

import jax

from repro import kernels as _k
from repro.kernels.segment_reduce.segment_reduce import segment_reduce_sorted_pallas


def segment_reduce_sorted(
    values: jax.Array, seg_ids: jax.Array, num_segments: int
) -> jax.Array:
    """Segment sum over inputs already sorted by ``seg_ids`` (bucket layout)."""
    return segment_reduce_sorted_pallas(
        values, seg_ids, num_segments, interpret=_k.interpret()
    )
