"""What the one shuffle wire carries, counted exactly, and the keyed wire.

* ``shuffle_rows`` and ``shuffle_pairs`` are the valid pairs whose
  assigned slot is not the shard they were mapped on, counted here in
  numpy from the job's own schedule, and ``shuffle_bytes`` is
  rows × (V·itemsize + 4), over the value dtypes and widths a map emits;
* a saved wave plan loads only for the wire that exists;
* the per-key Reduce (``keyed_output``) at slot counts 3 and 5 against a
  numpy group-by by key.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import pipeline
from repro.core.mapreduce import JobResult, MapReduceConfig, MapReduceJob

K, N = 128, 24


def _run(m: int, values_dtype, v: int, chunks: int, seed: int, **options):
    rng = np.random.default_rng(seed)
    keys = (rng.zipf(1.4, size=(m, K)) % 701).astype(np.int32)
    values = rng.integers(-4, 9, size=(m, K, v)).astype(np.float32)
    valid = rng.random((m, K)) > 0.15
    job = MapReduceJob(lambda shard: shard, MapReduceConfig(
        num_slots=m, num_clusters=N, pipelined=chunks > 1,
        pipeline_chunks=chunks, **options), backend="vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(values, values_dtype),
                   jnp.asarray(valid)))
    return (keys, values, valid), res


def _nonlocal_pairs(keys, valid, assignment) -> int:
    """Valid pairs whose cluster's slot is not the shard they sit on."""
    dest = np.asarray(assignment)[np.abs(keys) % N]
    shard = np.arange(keys.shape[0])[:, None]
    return int(np.sum(valid & (dest != shard)))


@pytest.mark.parametrize("v", [1, 5])
@pytest.mark.parametrize("chunks", [1, 4], ids=["sequential", "4chunks"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16],
                         ids=["f32", "bf16", "f16"])
def test_wire_accounting_is_exact(dtype, chunks, v):
    (keys, _, valid), res = _run(4, dtype, v, chunks, seed=7 * v + chunks)
    assert res.overflow == 0
    rows = _nonlocal_pairs(keys, valid, res.schedule.assignment)
    assert rows > 0
    assert res.shuffle_rows == rows
    assert res.shuffle_pairs == rows
    assert res.shuffle_bytes == rows * (v * jnp.dtype(dtype).itemsize + 4)


def test_wire_accounting_fields():
    """The result carries the one wire's three counts, each what the rows
    that crossed the network say."""
    (keys, _, valid), res = _run(8, jnp.float32, 5, 3, seed=3)
    names = {f.name for f in JobResult.__dataclass_fields__.values()}
    assert {"shuffle_bytes", "shuffle_rows", "shuffle_pairs"} <= names
    assert res.shuffle_rows == res.shuffle_pairs == _nonlocal_pairs(
        keys, valid, res.schedule.assignment)
    assert res.shuffle_bytes == res.shuffle_rows * (5 * 4 + 4)


def _plan_json():
    plan = pipeline.plan_waves([3.0, 1.0, 2.0, 5.0], np.array([0, 1, 0, 1]), 2, 2)
    return plan, plan.to_json()


def test_wave_plan_json_without_replication_loads():
    plan, d = _plan_json()
    assert "replication" not in d
    back = pipeline.WavePlan.from_json(d)
    np.testing.assert_array_equal(back.chunk_of_cluster, plan.chunk_of_cluster)
    np.testing.assert_array_equal(back.rank_of_cluster, plan.rank_of_cluster)
    assert back.num_chunks == plan.num_chunks


def test_wave_plan_json_with_replication_one_loads():
    plan, d = _plan_json()
    back = pipeline.WavePlan.from_json(dict(d, replication=1))
    assert back.to_json() == d


@pytest.mark.parametrize("replication", [2, 0])
def test_wave_plan_json_with_other_replication_raises(replication):
    _, d = _plan_json()
    with pytest.raises(ValueError, match="replication"):
        pipeline.WavePlan.from_json(dict(d, replication=replication))


@pytest.mark.parametrize("reduce_op", ["sum", "max", "count"])
@pytest.mark.parametrize("m", [3, 5])
def test_keyed_wire_matches_reference(m, reduce_op):
    (keys, values, valid), res = _run(m, jnp.float32, 2, 3, seed=11 * m,
                                      keyed_output=True, reduce_op=reduce_op)
    assert res.overflow == 0
    names, at = np.unique(keys[valid], return_inverse=True)
    counts = np.bincount(at, minlength=names.size).astype(np.float64)
    if reduce_op == "count":
        ref = counts[:, None]
    elif reduce_op == "sum":
        ref = np.zeros((names.size, 2))
        np.add.at(ref, at, values[valid])
    else:
        ref = np.full((names.size, 2), -np.inf)
        np.maximum.at(ref, at, values[valid])
    order = np.argsort(res.keys)
    np.testing.assert_array_equal(res.keys[order], names)
    np.testing.assert_array_equal(res.counts[order], counts)
    np.testing.assert_array_equal(np.asarray(res.values, np.float64)[order], ref)
