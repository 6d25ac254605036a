"""The shuffle wire at slot counts the other engine tests do not use.

Phase B has one wire: each non-local pair crosses the all-to-all as its
values plus a 4-byte cluster id. Every case here runs a job on the vmap
backend at m in {2, 3, 5, 6, 7} Reduce slots (the rest of the suite runs
4 and 8), for each ``reduce_op``, sequentially and in 2 and 4 pipelined
chunks, and compares the dense outputs exactly with a numpy group-by.
Values are small signed integers in f32, so every summation order is
exact and a ``max`` whose maximum is negative is checked too.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.mapreduce import MapReduceConfig, MapReduceJob

K, V, N = 96, 3, 29


def _batch(m: int, seed: int):
    """``(keys, values, valid)`` shard-major ``(m, K)``: skewed keys, about
    a tenth of the pairs invalid."""
    rng = np.random.default_rng(seed)
    keys = (rng.zipf(1.3, size=(m, K)) % 1009).astype(np.int32)
    values = rng.integers(-6, 7, size=(m, K, V)).astype(np.float32)
    valid = rng.random((m, K)) > 0.1
    return keys, values, valid


def _reference(keys, values, valid, reduce_op: str):
    """Per-cluster numpy group-by of the valid pairs: ``(values, counts)``."""
    cluster = np.abs(keys[valid].astype(np.int64)) % N
    vals = values[valid].astype(np.float64)
    counts = np.bincount(cluster, minlength=N).astype(np.float64)
    out = np.zeros((N, V))
    if reduce_op == "sum":
        np.add.at(out, cluster, vals)
    elif reduce_op == "max":
        out[:] = -np.inf
        np.maximum.at(out, cluster, vals)
        out[counts == 0] = 0.0
    return out, counts


@pytest.mark.parametrize("chunks", [1, 2, 4], ids=["sequential", "2chunks", "4chunks"])
@pytest.mark.parametrize("reduce_op", ["sum", "max", "count"])
@pytest.mark.parametrize("m", [2, 3, 5, 6, 7])
def test_one_wire_matches_reference(m, reduce_op, chunks):
    keys, values, valid = _batch(m, seed=100 * m + chunks)
    job = MapReduceJob(lambda shard: shard, MapReduceConfig(
        num_slots=m, num_clusters=N, reduce_op=reduce_op,
        pipelined=chunks > 1, pipeline_chunks=chunks), backend="vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(values), jnp.asarray(valid)))
    ref_values, ref_counts = _reference(keys, values, valid, reduce_op)
    assert res.overflow == 0
    np.testing.assert_array_equal(res.counts, ref_counts)
    got = np.asarray(res.values, np.float64)
    if reduce_op == "count":
        # One column per value (pipelined) or a single one (sequential),
        # each the cluster's count.
        assert got.shape[0] == N
        np.testing.assert_array_equal(got, np.broadcast_to(ref_counts[:, None], got.shape))
    else:
        np.testing.assert_array_equal(got, ref_values)
