"""The chunked double-buffered shuffle→reduce engine + chunk reduce kernel.

Covers the PR's acceptance surface:
* pipelined phase B == sequential phase B **bit-exactly** on fixed seeds
  (integer-valued f32 inputs make every summation order exact);
* ``plan_chunks`` invariants — every operation exactly once, chunk walk in
  increasing-load order, chunk count bounds;
* the gather + segment-reduce kernel path vs its jnp oracle across dtypes;
* the ``auto`` strategy: picks a candidate, never balances worse than hash,
  and reports per-candidate cost estimates.
"""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import pipeline as pipe
from repro.core import simulator as sim
from repro.core.mapreduce import MapReduceConfig, MapReduceJob
from repro.kernels.segment_reduce.ops import segment_reduce_sorted
from repro.kernels.segment_reduce.ref import gather_segment_reduce_ref
from repro.kernels.moe_dispatch.ops import (dispatch_to_buckets,
                                            dispatch_to_buckets_chunked,
                                            plan_capacity_slabs)


def _identity_map(shard):
    return shard


def _int_job_inputs(rng, m, K, V, key_mod):
    """Integer-valued f32 pairs: bit-exact under any summation order."""
    keys = (rng.zipf(1.3, size=(m, K)) % key_mod).astype(np.int32)
    vals = rng.integers(0, 8, size=(m, K, V)).astype(np.float32)
    valid = rng.random((m, K)) > 0.1
    return keys, vals, valid


# ---------------------------------------------------------------------------
# Pipelined == sequential, bit-exact
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sched", ["hash", "os4m", "auto"])
@pytest.mark.parametrize("chunks", [2, 4, 7])
def test_pipelined_bit_identical_to_sequential(rng, sched, chunks):
    m, K, V, n = 4, 256, 3, 24
    keys, vals, valid = _int_job_inputs(rng, m, K, V, 997)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))
    results = {}
    for pipelined in (True, False):
        job = MapReduceJob(_identity_map, MapReduceConfig(
            num_slots=m, num_clusters=n, scheduler=sched,
            pipelined=pipelined, pipeline_chunks=chunks), backend="vmap")
        results[pipelined] = job.run(batch)
    assert np.array_equal(results[True].values, results[False].values)
    assert np.array_equal(results[True].counts, results[False].counts)
    assert results[True].overflow == 0
    assert results[False].overflow == 0


def test_pipelined_bit_identical_with_kernels(rng):
    """The kernel path must agree bit-for-bit too (f32 accum both)."""
    m, K, V, n = 4, 128, 2, 16
    keys, vals, valid = _int_job_inputs(rng, m, K, V, 509)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))
    outs = []
    for use_kernels in (False, True):
        job = MapReduceJob(_identity_map, MapReduceConfig(
            num_slots=m, num_clusters=n, scheduler="os4m",
            pipelined=True, pipeline_chunks=3, use_kernels=use_kernels),
            backend="vmap")
        outs.append(job.run(batch))
    assert np.array_equal(outs[0].values, outs[1].values)
    assert np.array_equal(outs[0].counts, outs[1].counts)


def test_reduce_op_max_pipelined_matches_sequential(rng):
    m, K, n = 2, 64, 8
    keys = rng.integers(0, 100, (m, K)).astype(np.int32)
    # All-negative values ⇒ every cluster's true max is negative
    # (regression: a maximum() chunk merge clamped negative maxima at the
    # zero-initialised accumulator, returning all zeros).
    vals = rng.integers(-1000, -1, (m, K, 1)).astype(np.float32)
    valid = np.ones((m, K), bool)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))
    res = {}
    for pipelined in (True, False):
        job = MapReduceJob(_identity_map, MapReduceConfig(
            num_slots=m, num_clusters=n, reduce_op="max",
            pipelined=pipelined), backend="vmap")
        res[pipelined] = job.run(batch)
    assert np.array_equal(res[True].values, res[False].values)
    assert res[True].values.min() < 0      # the negative maxima survived


def test_pipelined_preserves_value_dtype(rng):
    """bf16 payloads come back bf16 from both phase-B paths (regression:
    the pipelined accumulator was hardcoded f32)."""
    m, K, n = 4, 128, 12
    keys = rng.integers(0, 300, (m, K)).astype(np.int32)
    vals = jnp.asarray(rng.integers(0, 4, (m, K, 2)), jnp.bfloat16)
    valid = jnp.ones((m, K), bool)
    batch = (jnp.asarray(keys), vals, valid)
    dtypes = {}
    vals_sum = {}
    for pipelined in (True, False):
        job = MapReduceJob(_identity_map, MapReduceConfig(
            num_slots=m, num_clusters=n, pipelined=pipelined),
            backend="vmap")
        res = job.run(batch)
        dtypes[pipelined] = res.values.dtype
        vals_sum[pipelined] = float(np.asarray(res.values, np.float32).sum())
    assert dtypes[True] == dtypes[False]
    assert vals_sum[True] == vals_sum[False] > 0


# ---------------------------------------------------------------------------
# plan_chunks invariants
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("num_chunks", [1, 3, 8])
def test_plan_chunks_partition_and_order(seed, num_chunks):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 60))
    loads = rng.zipf(1.4, n).astype(float)
    chunks = pipe.plan_chunks(loads, num_chunks, "increasing")
    # every operation exactly once
    flat = np.concatenate(chunks)
    assert sorted(flat.tolist()) == list(range(n))
    # chunk count bounds
    assert 1 <= len(chunks) <= min(num_chunks, n)
    # increasing-load order: within each chunk AND across chunk boundaries
    ordered = loads[flat]
    assert (np.diff(ordered) >= -1e-12).all()


def test_plan_waves_clamps_excess_chunks_and_warns_once(monkeypatch):
    monkeypatch.setattr(pipe, "_warned_excess_chunks", False)
    loads = [4.0, 2.0, 1.0]
    assign = np.array([0, 1, 0])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        plan = pipe.plan_waves(loads, assign, 2, num_chunks=10)
    assert plan.num_chunks <= 3              # clamped, no empty waves
    assert (plan.chunk_of_cluster < plan.num_chunks).all()
    assert len(caught) == 1 and "clamping" in str(caught[0].message)
    with warnings.catch_warnings(record=True) as again:
        warnings.simplefilter("always")
        pipe.plan_waves(loads, assign, 2, num_chunks=10)
    assert len(again) == 0                   # warn-once


def test_plan_chunks_balances_load():
    loads = np.ones(64)
    chunks = pipe.plan_chunks(loads, 4, "increasing")
    sizes = [len(c) for c in chunks]
    assert len(chunks) == 4
    assert max(sizes) - min(sizes) <= 1


def test_engine_chunk_walk_is_increasing_load_per_slot(rng):
    """Each Reduce slot's waves see non-decreasing per-wave operation load."""
    m, K, n = 4, 512, 32
    keys, vals, valid = _int_job_inputs(rng, m, K, 2, 2003)
    job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=m, num_clusters=n, scheduler="os4m", pipeline_chunks=4),
        backend="vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid)))
    # Reconstruct the wave plan the engine used.
    key_dist = res.key_distribution
    for d in range(m):
        members = np.nonzero(res.schedule.assignment == d)[0]
        if members.size < 2:
            continue
        waves = pipe.plan_chunks(key_dist[members], 4, "increasing")
        flat = np.concatenate(waves)
        ordered = key_dist[members][flat]
        assert (np.diff(ordered) >= -1e-12).all()


# ---------------------------------------------------------------------------
# Gather + segment-reduce kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("n,s,v", [(64, 16, 4), (500, 37, 8), (1024, 600, 16)])
def test_fused_shuffle_reduce_dtype_sweep(rng, dtype, n, s, v):
    vals = jnp.asarray(rng.standard_normal((n, v)), dtype)
    seg_unsorted = rng.integers(0, s, n).astype(np.int32)
    order = np.argsort(seg_unsorted, kind="stable").astype(np.int32)
    seg_sorted = jnp.asarray(seg_unsorted[order])
    got = segment_reduce_sorted(vals[jnp.asarray(order)], seg_sorted, s)
    ref = gather_segment_reduce_ref(vals, jnp.asarray(order), seg_sorted, s)
    atol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


def test_fused_fallback_matches_kernel(rng):
    n, s, v = 300, 25, 4
    vals = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    seg = np.sort(rng.integers(0, s, n)).astype(np.int32)
    order = jnp.asarray(rng.permutation(n).astype(np.int32))
    # padding rows (seg == s) must be dropped by both paths
    seg[-5:] = s
    a = segment_reduce_sorted(vals[order], jnp.asarray(seg), s)
    b = gather_segment_reduce_ref(vals, order, jnp.asarray(seg), s)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# Auto strategy
# ---------------------------------------------------------------------------


def test_auto_strategy_resolves_and_reports_costs(rng):
    m, K, n = 4, 256, 24
    keys, vals, valid = _int_job_inputs(rng, m, K, 2, 997)
    job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=m, num_clusters=n, scheduler="auto"), backend="vmap")
    res = job.run((jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid)))
    assert res.strategy in ("hash", "lpt", "multifit", "bss")
    assert set(res.strategy_costs) == {"hash", "lpt", "multifit", "bss"}
    # the pick is the argmin of its own cost table
    assert res.strategy_costs[res.strategy] == min(res.strategy_costs.values())
    # and never balances worse than the hash baseline
    hash_job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=m, num_clusters=n, scheduler="hash"), backend="vmap")
    hash_res = hash_job.run(
        (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid)))
    assert res.schedule.balance_ratio <= hash_res.schedule.balance_ratio + 1e-9


def test_pick_strategy_prefers_balance_on_skew():
    rng = np.random.default_rng(0)
    loads = rng.zipf(1.3, 480).clip(1, 20_000).astype(float)
    name, schedule, costs = sim.pick_strategy(loads, 30)
    assert name != "hash"            # skewed: hash pays for its imbalance
    assert schedule.balance_ratio < 1.2
    assert costs["hash"] > costs[name]


def test_estimate_reduce_time_monotone_in_imbalance():
    loads = np.asarray([100.0] * 32)
    from repro.core import scheduler as S
    balanced = S.schedule_lpt(loads, 4)
    skewed = S.Schedule.from_assignment(np.zeros(32, np.int32), loads, 4)
    assert (sim.estimate_reduce_time(loads, skewed)
            > sim.estimate_reduce_time(loads, balanced))


# ---------------------------------------------------------------------------
# shard_map backend (8 virtual devices; CI sets XLA_FLAGS)
# ---------------------------------------------------------------------------


def test_shard_map_repeated_runs_and_match_vmap(rng):
    """The jit cache must serve the shard_map backend across run() calls
    (regression: a cache hit used to skip the arg-flattening step)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from jax.sharding import Mesh

    m, K, n = 8, 64, 12
    keys, vals, valid = _int_job_inputs(rng, m, K, 2, 503)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))
    mesh = Mesh(np.asarray(jax.devices()).reshape(m), ("mr_slots",))
    job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=m, num_clusters=n, pipeline_chunks=3),
        backend="shard_map", mesh=mesh)
    r1 = job.run(batch)
    r2 = job.run(batch)     # cache hit — must not retrace/crash
    assert np.array_equal(r1.values, r2.values)
    vres = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=m, num_clusters=n, pipeline_chunks=3),
        backend="vmap").run(batch)
    assert np.array_equal(np.asarray(vres.values), np.asarray(r1.values))


def test_jit_cache_bounded_across_distributions(rng):
    """Distinct key distributions produce distinct phase-B statics; the
    LRU bound must keep the executable cache finite."""
    job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=4, num_clusters=32, scheduler="bss", pipeline_chunks=4),
        backend="vmap")
    for seed in range(6):
        r = np.random.default_rng(seed)
        keys = (r.zipf(1.3, size=(4, 256)) % 997).astype(np.int32)
        vals = np.ones((4, 256, 2), np.float32)
        ok = np.ones((4, 256), bool)
        job.run((jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(ok)))
    assert len(job._jit_cache) <= job._jit_cache_max


def test_moe_chunked_overflow_parity_binding_capacity(mesh8):
    """When expert capacity binds, chunked dispatch must drop exactly as
    many tokens per expert as single-shot (carry-based global ranks) —
    regression: per-slab ranks let chunking keep a different count."""
    import dataclasses

    from repro.nn import layers as L
    from repro.nn.moe import MoEArgs, init_moe, moe

    base = MoEArgs(num_experts=8, top_k=2, d_model=16, d_ff=32,
                   capacity_factor=1.0, strategy="a2a")
    params, _ = L.split(init_moe(jax.random.PRNGKey(0), base, mesh8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 16)) + 2.0  # skewed
    _, s1 = moe(params, x, args=base, mesh=mesh8)
    _, s4 = moe(params, x,
                args=dataclasses.replace(base, pipeline_chunks=4), mesh=mesh8)
    assert int(s1["overflow"]) > 0          # capacity actually binds
    assert int(s4["overflow"]) == int(s1["overflow"])


def test_moe_chunked_matches_unchunked_default_capacity(mesh8):
    """pipeline_chunks is an overlap-only optimization: at the *default*
    capacity_factor it must neither drop extra tokens nor change outputs
    (regression: per-expert capacity was sized from the slab, not the
    full receive buffer)."""
    import dataclasses

    from repro.nn import layers as L
    from repro.nn.moe import MoEArgs, init_moe, moe

    base = MoEArgs(num_experts=8, top_k=2, d_model=16, d_ff=32,
                   strategy="a2a")   # capacity_factor default
    params, _ = L.split(init_moe(jax.random.PRNGKey(0), base, mesh8))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 16))
    y1, s1 = moe(params, x, args=base, mesh=mesh8)
    y4, s4 = moe(params, x,
                 args=dataclasses.replace(base, pipeline_chunks=4),
                 mesh=mesh8)
    assert int(s4["overflow"]) == int(s1["overflow"])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y4), atol=1e-5)


# ---------------------------------------------------------------------------
# Chunked dispatch helpers (MoE path)
# ---------------------------------------------------------------------------


def test_plan_capacity_slabs_cover_capacity():
    for cap, chunks in [(64, 4), (7, 3), (1, 4), (16, 1), (5, 8)]:
        slabs = plan_capacity_slabs(cap, chunks)
        covered = []
        for s, z in slabs:
            covered.extend(range(s, s + z))
        assert covered == list(range(cap))
        assert len(slabs) <= max(1, min(chunks, cap))


def test_dispatch_chunked_matches_unchunked(rng):
    t, e, cap = 512, 8, 96
    dest = rng.integers(-1, e, t).astype(np.int32)
    vals = rng.standard_normal((t, 4)).astype(np.float32)
    full, counts, ovf = dispatch_to_buckets(
        jnp.asarray(vals), jnp.asarray(dest), e, cap)
    slabs, counts_c, ovf_c = dispatch_to_buckets_chunked(
        jnp.asarray(vals), jnp.asarray(dest), e, cap, 4)
    np.testing.assert_allclose(np.concatenate([np.asarray(s) for s in slabs],
                                              axis=1), np.asarray(full))
    assert np.array_equal(np.asarray(counts), np.asarray(counts_c))
    assert int(ovf) == int(ovf_c)
