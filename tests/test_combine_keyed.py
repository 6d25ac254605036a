"""The combiner and the per-key Reduce against a plain float64 group-by.

Seeded random pairs on the CPU: 200 hashed 31-bit keys over 16 clusters,
so about twelve keys share each cluster, integer-valued values (float32
sums of integers are exact in any order, so values compare exactly) and
a fifth of the pairs invalid. Every combination of ``combine`` and
``keyed_output`` runs each ``reduce_op`` on the vmap backend and on
shard_map over 8 devices; the shard_map cases run in one child process on
8 virtual CPU devices when this process has fewer:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \\
        python tests/test_combine_keyed.py
"""

from __future__ import annotations

import itertools
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
M, K, N, V, KEYS = 8, 128, 16, 2, 200
CASES = [dict(combine=c, keyed_output=k, reduce_op=op)
         for c, k, op in itertools.product((False, True), (False, True),
                                           ("sum", "max", "count"))]


def _batches():
    """Two batches of ``(keys, values, valid)``, shard-major ``(M, K)``."""
    rng = np.random.default_rng(16)
    universe = rng.choice(1 << 31, size=KEYS, replace=False).astype(np.int32)
    out = []
    for _ in range(2):
        keys = universe[rng.integers(0, KEYS, (M, K))]
        values = rng.integers(-4, 9, (M, K, V)).astype(np.float32)
        out.append((keys, values, rng.random((M, K)) < 0.8))
    return out


def _job(case: dict, backend: str):
    import jax
    from jax.sharding import Mesh

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    mesh = None
    if backend == "shard_map":
        mesh = Mesh(np.asarray(jax.devices()[:M]), ("mr_slots",))
    return MapReduceJob(lambda shard: shard, MapReduceConfig(
        num_slots=M, num_clusters=N, pipeline_chunks=3, reuse=ReusePolicy(),
        **case), backend=backend, mesh=mesh)


def run_case(case: dict, backend: str) -> list:
    """Both batches through one job: per batch keys (or None), values,
    counts and overflow, as lists."""
    import jax.numpy as jnp

    job = _job(case, backend)
    out = []
    for keys, values, valid in _batches():
        res = job.run((jnp.asarray(keys), jnp.asarray(values), jnp.asarray(valid)))
        out.append({"keys": None if res.keys is None else res.keys.tolist(),
                    "values": np.asarray(res.values).tolist(),
                    "counts": np.asarray(res.counts).tolist(),
                    "overflow": int(res.overflow)})
    return out


def reference(keys, values, valid, reduce_op: str, keyed: bool):
    """Plain float64 group-by of the valid pairs, by key or by cluster
    ``|key| mod N``: ``(groups, values (G, V'), counts (G,))``; dense
    groups are 0..N-1, an empty one reading 0."""
    keys, values = keys[valid].astype(np.int64), values[valid].astype(np.float64)
    groups = keys if keyed else np.abs(keys) % N
    names, at = np.unique(groups, return_inverse=True)
    if not keyed:
        names, at = np.arange(N), groups
    counts = np.bincount(at, minlength=names.size).astype(np.float64)
    if reduce_op == "count":
        return names, counts[:, None], counts
    out = np.zeros((names.size, V))
    if reduce_op == "sum":
        np.add.at(out, at, values)
    else:
        out[:] = -np.inf
        np.maximum.at(out, at, values)
        out[counts == 0] = 0.0
    return names, out, counts


def _expect(case: dict, got: list) -> None:
    keyed = case["keyed_output"]
    for batch, res in zip(_batches(), got):
        names, ref_v, ref_c = reference(*batch, case["reduce_op"], keyed)
        values, counts = np.asarray(res["values"]), np.asarray(res["counts"])
        assert res["overflow"] == 0
        if keyed:
            order = np.argsort(res["keys"])
            np.testing.assert_array_equal(np.asarray(res["keys"])[order], names)
            values, counts = values[order], counts[order]
        np.testing.assert_array_equal(counts, ref_c)
        np.testing.assert_array_equal(values, ref_v)


@pytest.fixture(scope="module")
def shard_map_results():
    """Every case on shard_map over 8 devices, in a child process if needed."""
    import jax

    if len(jax.devices()) >= M:
        return [run_case(case, "shard_map") for case in CASES]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, __file__], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("backend", ["vmap", "shard_map"])
@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(
    [c["reduce_op"]] + [k for k in ("combine", "keyed_output") if c[k]]))
def test_engine_matches_a_plain_group_by(case, backend, request):
    if backend == "vmap":
        got = run_case(case, "vmap")
    else:
        got = request.getfixturevalue("shard_map_results")[CASES.index(case)]
    _expect(case, got)


@pytest.mark.parametrize("combine", [False, True])
def test_float_values_within_rounding(combine):
    """Random float values: float32 sums in another order than the
    reference's differ by rounding only. Each output sums at most M * K
    terms of magnitude below 4, so its error is below M * K * 4 * 2^-24 ~
    2.4e-4 in absolute terms; 1e-3 leaves room and is still far below
    one dropped or doubled pair (a term of about 1)."""
    import jax.numpy as jnp

    keys, _, valid = _batches()[0]
    values = np.random.default_rng(5).uniform(-4, 4, (M, K, V)).astype(np.float32)
    job = _job(dict(combine=combine, keyed_output=True, reduce_op="sum"), "vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(values), jnp.asarray(valid)))
    names, ref_v, ref_c = reference(keys, values, valid, "sum", keyed=True)
    order = np.argsort(res.keys)
    np.testing.assert_array_equal(res.keys[order], names)
    np.testing.assert_array_equal(res.counts[order], ref_c)
    np.testing.assert_allclose(res.values[order], ref_v, rtol=0, atol=1e-3)


def test_keys_sharing_a_cluster():
    """Two keys of one cluster: summed into one row per cluster without
    ``keyed_output``, two rows with it."""
    import jax.numpy as jnp

    a, b = 5, 5 + 7 * N                   # |key| mod N == 5 for both
    keys = np.full((M, K), a, np.int32)
    keys[:, ::2] = b
    values = np.ones((M, K, 1), np.float32)
    batch = (jnp.asarray(keys), jnp.asarray(values), jnp.ones((M, K), bool))
    for combine in (False, True):
        dense = _job(dict(combine=combine), "vmap").run(batch)
        assert dense.keys is None and dense.counts[5] == M * K
        assert dense.values[5, 0] == M * K
        keyed = _job(dict(combine=combine, keyed_output=True), "vmap").run(batch)
        rows = dict(zip(keyed.keys.tolist(), keyed.counts.tolist()))
        assert rows == {a: M * K / 2, b: M * K / 2}


@pytest.mark.parametrize("combine", [False, True])
def test_the_largest_key_beside_invalid_pairs(combine):
    """Invalid pairs sort under the largest int32 key; a valid pair with
    that key still comes out as its own row, with only its own pairs."""
    import jax.numpy as jnp

    big = np.iinfo(np.int32).max
    keys = np.where(np.arange(K) % 3 == 0, big, 7).astype(np.int32)
    keys = np.broadcast_to(keys, (M, K)).copy()
    valid = np.broadcast_to(np.arange(K) % 2 == 0, (M, K))
    values = np.full((M, K, 1), 2.0, np.float32)
    for op in ("sum", "max", "count"):
        res = _job(dict(combine=combine, keyed_output=True, reduce_op=op), "vmap").run(
            (jnp.asarray(keys), jnp.asarray(values), jnp.asarray(valid)))
        names, ref_v, ref_c = reference(keys, values, valid, op, keyed=True)
        order = np.argsort(res.keys)
        np.testing.assert_array_equal(res.keys[order], names)
        np.testing.assert_array_equal(res.counts[order], ref_c)
        np.testing.assert_array_equal(res.values[order], ref_v[:, :1] if op != "count"
                                      else ref_v)


def test_combiner_capacity_is_rerun_when_outgrown():
    """C settles on the first batch's largest shard (40 keys: C = 64); a
    batch with 100 keys a shard outgrows it, re-runs its combiner at 128,
    comes out exact and keeps C there."""
    import jax.numpy as jnp

    rng = np.random.default_rng(3)
    job = _job(dict(combine=True, keyed_output=True), "vmap")
    caps = []
    for few in (40, 40, 100, 100):
        keys = np.stack([rng.permutation(K) % few for _ in range(M)]).astype(np.int32)
        res = job.run((jnp.asarray(keys), jnp.ones((M, K, 1), jnp.float32),
                       jnp.ones((M, K), bool)))
        names, counts = np.unique(keys, return_counts=True)
        order = np.argsort(res.keys)
        np.testing.assert_array_equal(res.keys[order], names)
        np.testing.assert_array_equal(res.counts[order], counts)
        np.testing.assert_array_equal(res.values[order, 0], counts)
        assert res.overflow == 0
        caps.append(job._combine_cap)
    assert caps == [64, 64, 128, 128]
    assert job.capacity_fallbacks == 2     # the first batch (C = 1) and the third


UNSUPPORTED = {
    "checkpoint_waves": {"checkpoint_waves": True},
    "measured": {"measure_timings": True, "estimate_speeds": True},
    "use_kernels": {"use_kernels": True},
}


@pytest.mark.parametrize("flag,other", [
    (flag, name) for flag in ("combine", "keyed_output") for name in UNSUPPORTED
] + [("combine", "stream_prefix")])
def test_unsupported_combination_raises(flag, other):
    import jax
    from jax.sharding import Mesh

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob

    slots, backend, mesh = M, "vmap", None
    if other == "measured":  # measured wave clocks exist only on shard_map
        slots, backend = 1, "shard_map"
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("mr_slots",))
    options = UNSUPPORTED.get(other, {"stats": "sketch", "stream_prefix": 0.5})
    cfg = MapReduceConfig(num_slots=slots, num_clusters=N, **{flag: True}, **options)
    with pytest.raises(ValueError, match=other.split("_")[0] if other in (
            "checkpoint_waves", "stream_prefix", "use_kernels") else "not supported"):
        MapReduceJob(lambda s: s, cfg, backend=backend, mesh=mesh)
    MapReduceJob(lambda s: s, MapReduceConfig(num_slots=slots, num_clusters=N,
                                              **options), backend=backend, mesh=mesh)


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    print(json.dumps([run_case(case, "shard_map") for case in CASES]))
