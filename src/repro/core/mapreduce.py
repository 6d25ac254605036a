"""A keyed Map/Shuffle/Reduce engine over a JAX mesh with OS4M scheduling.

This is the faithful reproduction substrate: the paper's whole workflow —

    map  →  collect per-key statistics  →  (host) Q||C_max schedule
         →  chunked shuffle ("copy")    →  pipelined segment reduce ("run")
         →  measure per-slot wave timings → update slot-speed estimate

expressed as two jitted phases. Phase boundaries match the paper exactly:
Reduce work begins only after *all* Map operations have finished and the
schedule is known (§4.1 step 6), eliminating Map↔Reduce contention.

The Reduce phase is a **chunked, double-buffered pipeline** (§4.4): the
host groups operation clusters into chunks of roughly equal load in
*increasing-load order* (``pipeline.plan_chunks``), and phase B walks the
chunks with a software-pipelined loop — the all-to-all "copy" of chunk
``i+1`` is issued *before* the segment-reduce "run" of chunk ``i``, so on
real hardware the ICI transfer of the next chunk overlaps the current
chunk's compute (the TPU analogue of Fig 4(b)'s copy/sort/run overlap).
With ``use_kernels=True`` the "sort" and "run" of a chunk are one
gather into rank order plus the ``kernels/segment_reduce`` Pallas kernel.

Schedule selection: ``scheduler`` may name one algorithm (``hash`` | ``lpt``
| ``multifit`` | ``bss`` | ``os4m``) or ``"auto"``, which runs every
candidate on the measured key distribution and keeps the one whose
*estimated* Reduce makespan (``simulator.pick_strategy`` — the same
flow-shop cost model behind the paper's Figs 7–16) is lowest.

Steady-state serving: planning is decoupled from execution. Each ``run()``
produces (or replays) a :class:`repro.core.schedule_cache.CachedSchedule` —
the schedule, the §4.4 wave plan, and the statistics-sized send capacities.
With ``MapReduceConfig(reuse=ReusePolicy(...))`` the job snapshots the plan
and replays it while the measured key distribution stays close (an
on-device drift metric over the per-shard ``K^(i)`` histograms); only a
drifted, aged-out, or overflowed batch pays the host scheduling cost
again. Because the snapshot pins phase B's static shapes, reused batches
always hit the jitted-executable cache — zero retraces after warmup.

Execution backends share one per-shard code path written against named-axis
collectives:

* ``backend="vmap"``      — slots are a leading array axis mapped with
  ``jax.vmap(..., axis_name=AXIS)``; runs on a single CPU device (tests,
  examples).
* ``backend="shard_map"`` — slots are shards of a mesh axis; the same code
  runs under ``jax.shard_map`` with real ``psum`` / ``all_to_all``
  collectives (dry-run, production).

Data model: a Map operation emits up to ``K`` intermediate pairs
``(key_hash:int32, value:(V,)float32, valid:bool)``. Keys are pre-hashed by
the user's map function (text by :func:`repro.data.text.hash_tokens`).

Two options follow Hadoop's job set-up. ``combine`` is its combiner: each
map shard's pairs are reduced by their full key before the statistics, so
OS4M balances, and phase B moves, the combined pairs. ``keyed_output`` is
one Reduce call per key, the paper's operation: the wire carries each
pair's key, each slot reduces by key, and the result is a
``(keys, values, counts)`` table instead of one row per cluster.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
import warnings
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, PartitionSpec as P

from repro import compat
from repro.core import clustering, pipeline as pipe, spans
from repro.core import mesh_timing as mt
from repro.core import schedule_cache as sc
from repro.core import scheduler as sched_lib
from repro.core import slot_speeds as ss
from repro.core import stats_provider as sp

AXIS = "mr_slots"

__all__ = ["MapReduceConfig", "JobResult", "MapReduceJob", "AXIS"]


@dataclasses.dataclass(frozen=True)
class MapReduceConfig:
    """Static configuration of one :class:`MapReduceJob`.

    ``reuse`` switches the job into steady-state mode: plans are cached
    in a :class:`repro.core.schedule_cache.ScheduleCache` and replayed
    until the policy (drift / age / speed drift / overflow) demands a
    replan.

    Heterogeneous slots (Q||C_max): ``speeds`` pins a known per-slot
    relative speed vector; ``estimate_speeds`` instead learns one online
    from phase-B wave timings (:class:`repro.core.slot_speeds.
    SlotSpeedEstimator`, EWMA weight ``speed_ewma``). Speeds only move
    *where* clusters are reduced — outputs are bit-identical under any
    speed vector.

    ``measure_timings`` picks the timing source for the estimator.
    ``None`` (default) resolves automatically: *measured* per-device
    wave timings on the shard_map backend (each slot is a device with
    its own clock), the synthetic work/slowdown model on vmap (one
    device, per-slot clocks don't exist). ``True`` forces the measured
    path (requires shard_map + ``estimate_speeds``); ``False`` disables
    it. Measured mode runs the SAME overlapped double-buffered pipeline
    as the unmeasured path, with per-wave on-device tick stamps
    (``kernels/wave_timer``) read from a tiny ticks buffer after the
    batch — outputs stay bit-identical and the copy/run overlap is
    kept. Platforms without a tick source fall back to wave-fenced
    host timing (see :meth:`MapReduceJob._execute_measured_fenced`).
    """

    num_slots: int                      # m — Reduce slots (= mesh shards)
    num_clusters: int                   # n — operation clusters (§4.3)
    scheduler: str = "os4m"             # hash | lpt | multifit | bss | os4m | auto
    eta: float = 0.002                  # FPTAS precision (paper §5: 0.2%)
    reduce_op: str = "sum"              # sum | max | count
    pipeline_chunks: int = 4            # Reduce pipeline granularity (§4.4)
    pipelined: bool = True              # False = Hadoop-style single-shot phase B
    capacity_send: Optional[int] = None  # per-(shard,dest) send buffer; None = safe bound
    use_kernels: bool = False           # route statistics + chunk reduce via Pallas
    reuse: Optional[sc.ReusePolicy] = None  # schedule-reuse policy; None = replan per run
    speeds: Optional[Tuple[float, ...]] = None  # static per-slot speeds (1.0 = nominal)
    estimate_speeds: bool = False       # learn speeds online from phase-B timings
    speed_ewma: float = 0.4             # estimator smoothing (newest-sample weight)
    measure_timings: Optional[bool] = None  # real per-device wave clocks (shard_map)
    # Elastic mesh: walk phase B wave-by-wave, persisting each completed
    # wave's outputs + the wave cursor to the host
    # (:class:`repro.core.pipeline.WaveCheckpoint`). A slot killed
    # mid-batch (``set_slot_failure(slot, at_wave=w)``) then replays only
    # the waves at/after the cursor onto the surviving mesh — outputs stay
    # bit-identical to an uninterrupted run. Costs the §4.4 copy/run
    # overlap (each wave is fenced to the host), so it is a
    # fault-tolerance mode, not the throughput path. Incompatible with
    # measured timings (which own the fenced program structure).
    checkpoint_waves: bool = False
    # Pluggable statistics layer (docs/STATISTICS.md). "exact" plans from
    # the full (m, n) histogram K^(i) — bit-identical to the pre-provider
    # engine. "sketch" plans from a per-shard count-min sketch
    # (core/stats_provider.py): phase A emits (sketch_depth *
    # sketch_width) counters per shard instead of n, the host plans from
    # overestimate-only estimates, and outputs stay bit-identical to the
    # exact path — capacities only gate buffer sizing, and estimates can
    # only over-provision (the overflow escape hatch covers the one case
    # that can't hold, prefix-committed caps below). Incompatible with
    # checkpoint_waves (recovery rewrites per-cluster histogram columns,
    # which don't exist in a sketch).
    stats: str = "exact"
    sketch_width: int = 1024            # count-min columns (power of two >= 8)
    sketch_depth: int = 4               # count-min hash rows (min over rows)
    # Streaming-prefix planning (sketch only): plan wave 1 from a sketch
    # of the first ``stream_prefix`` fraction of each shard's pairs
    # (scaled up), then refine the remaining waves from the full-batch
    # sketch once the tail lands — the refined plan keeps wave 1's
    # committed membership and capacity (``pipeline.plan_waves``
    # ``pinned_first``), so a wave already in flight is never re-cut. The
    # committed wave-1 cap is an extrapolation and may under-provision;
    # overflow then triggers the exact escape hatch (caps escalate to the
    # safe bound and the batch re-executes — outputs stay exact).
    stream_prefix: Optional[float] = None
    # Hadoop's combiner (``Job.setCombinerClass``, here the job's own
    # ``reduce_op``): after the map, an executable of its own (``combine``)
    # reduces each shard's valid pairs by their full key and compacts them
    # to a static per-shard capacity C; the statistics, the plan and phase B
    # then see the combined pairs, each carrying its count of map pairs. C
    # grows to cover the largest per-shard count seen, and a batch past it
    # re-runs its combiner, so no pair is dropped (MapReduceJob._combine).
    combine: bool = False
    # One Reduce call per key (the paper's operation) instead of per
    # operation cluster: the wire carries each pair's key, each slot
    # reduces what it received by key, and JobResult holds a table of
    # (keys, values, counts), one row per key.
    keyed_output: bool = False


@dataclasses.dataclass
class JobResult:
    """Outputs + provenance of one ``run()`` (fresh plan or cached replay)."""

    values: np.ndarray          # (num_clusters, V) reduced outputs; (R, V) keyed
    counts: np.ndarray          # (num_clusters,) pairs per cluster; (R,) keyed
    schedule: sched_lib.Schedule
    key_distribution: np.ndarray  # K = (k_1..k_n) (cluster loads, §4.1)
    overflow: int               # pairs dropped by capacity clamp (0 in normal runs)
    network_cost: clustering.NetworkCost
    strategy: str = ""          # scheduler actually used ("auto" resolves here)
    strategy_costs: Optional[dict] = None  # auto mode: estimated cost per candidate
    reused: bool = False        # True = phase B replayed a cached schedule
    plan_reason: str = ""       # ReuseDecision.reason ("" when reuse is off)
    drift: Optional[float] = None  # drift metric, when it was computed this run
    replan_benefit: Optional[dict] = None  # cost-gate verdict (auto + cost_gate)
    slot_speeds: Optional[np.ndarray] = None  # speeds the plan was built for
    speed_drift: Optional[float] = None  # slot-speed change vs the cached plan
    # Measured bytes-on-the-wire of phase B's shuffle (None on executors
    # that do not account — the checkpointed walk). Rows are counted on
    # device (psum'd with the outputs); each is one non-local pair, and
    # the host converts rows → bytes with the static wire row size, so
    # the cost model and the replan gate see *measured* shuffle volume,
    # not the modeled one.
    shuffle_bytes: Optional[int] = None   # a2a payload bytes
    shuffle_rows: Optional[int] = None    # wire rows behind those bytes
    shuffle_pairs: Optional[int] = None   # non-local pairs the wire carried
    # keyed_output: the R keys that had a pair, one row of values and
    # counts each, in no particular order (None when rows are clusters).
    keys: Optional[np.ndarray] = None


def _pull(span: str, x) -> np.ndarray:
    """``x`` copied to the host, inside the host span ``span`` with its bytes."""
    with TraceAnnotation(span, bytes=x.nbytes):
        return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# Per-shard phase bodies (named-axis collectives; backend-agnostic).
# ---------------------------------------------------------------------------


def _map_shard(shard_input, map_fn: Callable):
    """The job's Map on one shard: ``(key_hashes int32, values, valid)``."""
    key_hashes, values, valid = map_fn(shard_input)
    return key_hashes.astype(jnp.int32), values, valid


def _phase_a_shard(
    shard_input,
    map_fn: Callable,
    num_clusters: int,
    stats_fn: Callable,
    prefix_fraction: Optional[float] = None,
):
    """Map + local statistics (paper §4.1 steps 1–3).

    Each slot returns its *local* statistics state — the TaskTracker →
    JobTracker report of §4.1. ``stats_fn(cluster_ids, weights)`` is the
    provider's traced collection step (``core/stats_provider``): the
    exact K^(i) histogram, or a count-min counter grid whose size is
    independent of ``num_clusters``.

    ``prefix_fraction`` (streaming ingestion): additionally sketch only
    the first ``ceil(fraction * K)`` pair positions of the shard — the
    pairs that would have "landed first" in a streaming deployment — and
    return ``concat([full_state, prefix_state])``, so wave-1 planning
    can start from the prefix while the tail is conceptually in flight.
    """
    key_hashes, values, valid = _map_shard(shard_input, map_fn)
    cluster_ids = jnp.abs(key_hashes) % num_clusters
    weights = valid.astype(jnp.float32)
    state = stats_fn(cluster_ids, weights)
    if prefix_fraction is not None:
        k = int(cluster_ids.shape[0])
        cut = int(np.ceil(prefix_fraction * k))
        in_prefix = (jnp.arange(k) < cut).astype(jnp.float32)
        prefix_state = stats_fn(cluster_ids, weights * in_prefix)
        state = jnp.concatenate([state, prefix_state])
    return (key_hashes, values, valid), state


def _segmented_scan(segment, values, counts, reduce_op: str):
    """Inclusive scan of ``values`` (``max`` or a sum) and ``counts`` (a sum)
    within runs of equal ``segment``: log2(K) shifted steps (Hillis and
    Steele), each an elementwise pass, so it compiles and runs as a few
    fusions at any K."""
    k, d = segment.shape[0], 1
    while d < k:
        same = jnp.concatenate([jnp.zeros((d,), bool), segment[d:] == segment[:-d]])
        prev = jnp.concatenate([jnp.zeros((d,) + values.shape[1:], values.dtype),
                                values[:-d]])
        folded = jnp.maximum(prev, values) if reduce_op == "max" else prev + values
        values = jnp.where(same[:, None], folded, values)
        counts = jnp.where(same, counts + jnp.concatenate(
            [jnp.zeros((d,), counts.dtype), counts[:-d]]), counts)
        d *= 2
    return values, counts


def _runs_by_key(keys, values, valid, reduce_op: str, counted: bool):
    """Pairs sorted by key, each run of one key reduced into its last pair.

    One sort on a single int32 key carries the value columns and the
    counts (no gather); a segmented scan then folds each run with
    ``reduce_op``. Invalid pairs take the largest key and the fold's
    identity, with count 0, so where a valid key is that largest one they
    share its run and add nothing. ``counted`` says the last value column
    holds each pair's count of map pairs (pairs a combiner made): counts
    then sum that column and the other columns fold with ``max`` for
    ``max``, else with a sum.
    Returns ``(keys, values (K, V), counts (K,), last (K,))``, ``last``
    marking the last pair of each key's run, where the run's result is.
    """
    if counted:
        values, counts = values[:, :-1], values[:, -1].astype(jnp.float32)
        reduce_op = "max" if reduce_op == "max" else "sum"
    else:
        counts = jnp.ones(keys.shape, jnp.float32)
        if reduce_op == "count":
            values = values[:, :0]
    identity = jnp.finfo(values.dtype).min if reduce_op == "max" else 0
    cols = [jnp.where(valid, values[:, j], identity) for j in range(values.shape[-1])]
    # Unstable: the order within a key's run only orders a float fold (no
    # receiver rebuilds this sort), and one key compiles much faster on a
    # TPU than the two a stable sort amounts to.
    keys, counts, *cols = jax.lax.sort(
        (jnp.where(valid, keys, jnp.iinfo(jnp.int32).max),
         jnp.where(valid, counts, 0.0), *cols), num_keys=1, is_stable=False)
    values = jnp.stack(cols, axis=-1) if cols else values[:, :0]
    differs = keys[1:] != keys[:-1]
    segment = jnp.cumsum(jnp.concatenate([jnp.ones((1,), bool), differs]))
    values, counts = _segmented_scan(segment, values, counts, reduce_op)
    if not cols:
        values = counts[:, None].astype(values.dtype)
    last = jnp.concatenate([differs, jnp.ones((1,), bool)]) & (counts > 0)
    return keys, values, counts, last


@jax.named_scope(spans.REDUCE)
def _reduce_by_key(keys, values, valid, reduce_op: str, counted: bool):
    """A slot's per-key Reduce of the pairs it received: a table with one
    row per received pair, ``(keys, values, counts)``, whose rows with a
    count are the keys' results (counts are zero elsewhere)."""
    keys, values, counts, last = _runs_by_key(keys, values, valid, reduce_op,
                                              counted)
    return (keys, jnp.where(last[:, None], values, 0),
            jnp.where(last, counts, 0.0))


def _combine_shard(intermediate, capacity: int, num_clusters: int,
                   stats_fn: Callable, reduce_op: str):
    """Hadoop's combiner on one map shard, then its statistics.

    The shard's valid pairs are reduced by their full key (``reduce_op``)
    and the runs compacted, in key order, to ``capacity`` rows: each row
    a key, its combined values and, as one more value column, its count of
    map pairs. The ``(K,)`` run ends are counted once (a cumulative sum);
    row ``r`` is the ``r``-th end, found by a binary search per row, so
    only ``capacity`` rows are gathered. Returns the combined pairs, the
    statistics over them (the loads phase B moves) and the shard's number
    of keys; past ``capacity`` keys are left out and the caller re-runs.
    """
    keys, values, valid = intermediate
    with jax.named_scope(spans.COMBINE):
        keys, values, counts, last = _runs_by_key(keys, values, valid,
                                                  reduce_op, counted=False)
        ends = jnp.cumsum(last.astype(jnp.int32))
        rows = jnp.arange(1, capacity + 1, dtype=jnp.int32)
        at = jnp.searchsorted(ends, rows, side="left")
        kept = rows <= ends[-1]
        at = jnp.minimum(at, keys.shape[0] - 1)
        combined = (
            jnp.where(kept, keys[at], 0),
            jnp.concatenate([values[at], counts[at][:, None].astype(values.dtype)],
                            axis=-1),
            kept,
        )
    state = stats_fn(jnp.abs(combined[0]) % num_clusters, kept.astype(jnp.float32))
    return combined, state, ends[-1:]


def _counting_sort_to_buckets(
    dest: jnp.ndarray,       # (K,) int32 in [0, m] (m = invalid)
    values: jnp.ndarray,     # (K, V)
    payload: jnp.ndarray,    # (K,) int32 cluster ids
    num_slots: int,
    capacity: int,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bucket pairs by destination slot into fixed-capacity send buffers.

    Returns (bucket_values (m, cap, V), bucket_clusters (m, cap),
    bucket_valid (m, cap), overflow_count). This is the "bucket file per
    operation cluster" layout of §4.4, bounded by the schedule's capacity.
    Mirrors the moe_dispatch kernel's reference semantics. Uniform-capacity
    special case of the ragged sort below.
    """
    caps = np.full(num_slots, capacity, np.int64)
    bv, bc, bm, overflow = _ragged_counting_sort_to_buckets(
        dest, values, payload, caps, num_slots * capacity
    )
    return (
        bv.reshape(num_slots, capacity, values.shape[-1]),
        bc.reshape(num_slots, capacity),
        bm.reshape(num_slots, capacity),
        overflow,
    )


@jax.named_scope(spans.SPILL)
def _ragged_counting_sort_to_buckets(
    group: jnp.ndarray,      # (K,) int32 in [0, G] (G = invalid)
    values: jnp.ndarray,     # (K, V)
    payload: jnp.ndarray,    # (K,) int32 cluster ids
    group_caps: np.ndarray,  # (G,) static per-group capacities
    total: int,              # = group_caps.sum()
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Counting sort into *ragged* fixed-capacity group buffers.

    The pipelined engine's groups are (chunk, dest) pairs with
    statistics-derived (hence unequal) capacities; one spill writes every
    chunk's bucket file, with chunk slabs contiguous in the flat output.
    One stable sort of the group ids carries each pair's index, so group
    g's pairs are a run in stable order, and row ``j`` of g's slab holds
    the ``(j - base[g])``-th pair of that run, where g has that many.
    Each of the ``total`` rows gathers its own pair, so nothing as wide
    as the pairs is gathered or scattered. Returns flat ``(total, V)`` /
    ``(total,)`` buffers + the count of pairs past their group's capacity.
    """
    k = group.shape[0]
    num_groups = group_caps.shape[0]
    base = np.zeros(num_groups, np.int64)
    base[1:] = np.cumsum(group_caps)[:-1]
    base = jnp.asarray(base, jnp.int32)
    caps = jnp.asarray(group_caps, jnp.int32)
    g_sorted, order = jax.lax.sort(
        (group, jnp.arange(k, dtype=jnp.int32)), num_keys=1, is_stable=True
    )
    # g_sorted takes only G + 1 values: G + 1 searches in one compare-and-
    # count pass, where a search per pair loops over all K pairs.
    starts = jnp.searchsorted(
        g_sorted, jnp.arange(num_groups + 1, dtype=g_sorted.dtype),
        side="left", method="compare_all",
    )
    counts = starts[1:] - starts[:-1]
    overflow = jnp.sum(jnp.maximum(counts - caps, 0))
    # Row j's group is the last whose slab starts at or before j (a group
    # with no capacity shares its base with the next one).
    row = jnp.arange(total, dtype=jnp.int32)
    g_of_row = jnp.searchsorted(base, row, side="right", method="compare_all") - 1
    pos = row - base[g_of_row]
    ok = pos < counts[g_of_row]
    src = order[jnp.where(ok, starts[g_of_row] + pos, 0)]
    bucket_values = jnp.where(ok[:, None], values[src], 0)
    bucket_clusters = jnp.where(ok, payload[src], -1)
    return bucket_values, bucket_clusters, ok, overflow


def _segment_reduce(cluster_ids, values, valid, num_clusters: int, reduce_op: str,
                    counted: bool = False):
    """Reduce the "run" phase: aggregate pairs per cluster (jnp path).

    ``counted``: the last value column is each pair's count of map pairs
    (combined pairs, see :func:`_runs_by_key`).
    """
    w = valid.astype(values.dtype)[..., None]
    seg = jnp.where(valid, cluster_ids, num_clusters)
    weights = valid.astype(jnp.float32)
    if counted:
        weights = weights * values[:, -1].astype(jnp.float32)
        values = values[:, :-1]
        reduce_op = "max" if reduce_op == "max" else "sum"
    counts = jax.ops.segment_sum(
        weights, seg, num_segments=num_clusters + 1
    )[:-1]
    if reduce_op == "sum":
        out = jax.ops.segment_sum(values * w, seg, num_segments=num_clusters + 1)[:-1]
    elif reduce_op == "max":
        big_neg = jnp.finfo(values.dtype).min
        masked = jnp.where(valid[:, None], values, big_neg)
        out = jax.ops.segment_max(masked, seg, num_segments=num_clusters + 1)[:-1]
        out = jnp.where(counts[:, None] > 0, out, 0.0)
    elif reduce_op == "count":
        out = jax.ops.segment_sum(w, seg, num_segments=num_clusters + 1)[:-1]
    else:
        raise ValueError(f"unknown reduce_op {reduce_op!r}")
    return out, counts


@jax.named_scope(spans.COPY)
def _copy_chunk(buckets, value_dim: int):
    """The "copy" phase of one chunk: all-to-all every bucket to its slot."""
    bv, bc, bm = buckets
    rv = jax.lax.all_to_all(bv, AXIS, split_axis=0, concat_axis=0, tiled=False)
    rc = jax.lax.all_to_all(bc, AXIS, split_axis=0, concat_axis=0, tiled=False)
    rm = jax.lax.all_to_all(bm, AXIS, split_axis=0, concat_axis=0, tiled=False)
    return rv.reshape(-1, value_dim), rc.reshape(-1), rm.reshape(-1)


@jax.named_scope(spans.REDUCE)
def _reduce_chunk(
    rv, rc, rm,
    rank_of_cluster: jnp.ndarray,
    num_clusters: int,
    reduce_op: str,
    use_kernel: bool,
    counted: bool = False,
):
    """The "sort" + "run" of one received chunk.

    Kernel path: pairs are ordered by pipeline *rank* (increasing cluster
    load, §4.4) — rank is the one key that is monotone along the sorted
    stream — gathered into that order, and segment-reduced by the sorted
    segment-sum kernel; the result is un-permuted back to cluster ids with
    one gather.

    jnp path: ``segment_sum`` needs no sorted stream, and each cluster's
    pairs arrive in the same (src shard, bucket position) relative order on
    every path — sequential and pipelined accumulate bit-identically — so
    the explicit sort is skipped entirely.
    """
    if reduce_op == "sum" and use_kernel:
        from repro.kernels.segment_reduce import ops as segops

        rank = jnp.where(
            rm, rank_of_cluster[jnp.clip(rc, 0, num_clusters - 1)], num_clusters
        )
        order = jnp.argsort(rank, stable=True)
        out_by_rank = segops.segment_reduce_sorted(
            rv[order], rank[order].astype(jnp.int32), num_clusters
        )
        out = out_by_rank[rank_of_cluster]
        seg = jnp.where(rm, rc, num_clusters)
        counts = jax.ops.segment_sum(
            rm.astype(jnp.float32), seg, num_segments=num_clusters + 1
        )[:-1]
        return out, counts
    return _segment_reduce(rc, rv, rm, num_clusters, reduce_op, counted)


@jax.named_scope(spans.REDUCE)
def _sequential_reduce(
    rv, rc, rm,
    rank_of_cluster: jnp.ndarray,
    num_clusters: int,
    reduce_op: str,
    use_kernel: bool,
    counted: bool = False,
):
    """Whole-input "sort"+"run" — Hadoop's Fig 4(a) Reduce on one shard.

    The *entire* received input is merge-sorted before the run phase
    (rank order, stable — each cluster's pairs keep their arrival order,
    so this stays bit-identical to the pipelined path's per-chunk
    reduce). Shared by the sequential branch of :func:`_phase_b_shard`
    and the fenced executors' single-wave run program, and traced
    directly by the contract analyzer (``repro.analysis``).
    """
    if reduce_op == "sum" and use_kernel:
        return _reduce_chunk(
            rv, rc, rm, rank_of_cluster, num_clusters, reduce_op, True
        )
    rank = jnp.where(
        rm, rank_of_cluster[jnp.clip(rc, 0, num_clusters - 1)], num_clusters
    )
    # Identical-sort wire contract: stability explicit, never a default.
    order = jnp.argsort(rank, stable=True)
    return _segment_reduce(
        rc[order], rv[order], rm[order], num_clusters, reduce_op, counted
    )


def _wire_vec(wire_rows):
    """Phase B's wire vector: the rows that crossed the network (each a
    non-local pair), psum'd so the host reads one ``(1,)`` vector on
    either backend."""
    return jax.lax.psum(wire_rows[None], AXIS)[None]


def _fenced_wave_copy(fv, fc, fm, off: int, cap: int, num_slots: int,
                      v_dim: int):
    """The "copy" program of one fenced wave: slice its slab, all-to-all it.

    Module-level (not an executor closure) so the contract analyzer
    traces the *same* per-wave program the measured-fenced and
    checkpointed executors dispatch — not a reconstruction of it.
    """
    size = num_slots * cap
    slab = (fv[off:off + size].reshape(num_slots, cap, v_dim),
            fc[off:off + size].reshape(num_slots, cap),
            fm[off:off + size].reshape(num_slots, cap))
    return _copy_chunk(slab, v_dim)


def _fenced_wave_run(rv, rc, rm, rank_of_cluster, num_clusters: int,
                     reduce_op: str, use_kernel: bool):
    """The "sort"+"run" program of one fenced wave — shard-local reduce."""
    return _reduce_chunk(rv, rc, rm, rank_of_cluster, num_clusters,
                         reduce_op, use_kernel)


def _phase_b_shard(
    intermediate,
    assignment: jnp.ndarray,        # (n_clusters,) int32 — the broadcast schedule S
    rank_of_cluster: jnp.ndarray,   # (n_clusters,) pipeline order rank (§4.4)
    chunk_of_cluster: jnp.ndarray,  # (n_clusters,) chunk id per cluster
    cfg_static: Tuple,
    stamp_through=None,
    keyed: bool = False,
    counted: bool = False,
):
    """Chunked shuffle ("copy") + pipelined reduce ("run") — §4.1 step 6 + §4.4.

    ``pipelined=False`` (or a single chunk) is the Hadoop-style barrier:
    one bulk all-to-all of every pair, then one segment reduce. The
    pipelined path buckets each *chunk* separately and walks them with a
    double-buffered loop — the all-to-all of chunk ``c+1`` is issued before
    the reduce of chunk ``c``, so the next chunk's "copy" is in flight
    (ICI) while the current chunk's "run" occupies the compute units. The
    loop is unrolled (``num_chunks`` is static and small), which hands XLA
    the exact dependence structure: copy(c+1) has no edge from run(c).

    ``stamp_through`` is the measured executor's tick hook
    (``kernels/wave_timer.ops.stamp_through``; see
    :func:`_phase_b_shard_timed`). When set, per-wave boundary stamps are
    threaded through THIS body — one source of truth, so the measured
    path's advertised bit-identity cannot drift out of sync with the
    unmeasured program — and an extra ``(waves, 2, 2)`` uint32 ticks
    output is appended. ``None`` (the default) compiles to the identical
    untimed program.

    ``keyed`` (``MapReduceConfig.keyed_output``): the wire carries each
    pair's key in place of its cluster id, and each slot reduces what it
    received by key (:func:`_reduce_by_key`, chunk by chunk: a key lives in
    one cluster, hence in one chunk). The outputs are then a table per
    slot, ``(keys, values)`` and counts, one row per received pair.
    ``counted``: the pairs are a combiner's, each with its count of map
    pairs as the last value column (``MapReduceConfig.combine``). Neither
    is timed.
    """
    (num_slots, num_clusters, capacity, chunk_caps, reduce_op, pipelined,
     num_chunks, use_kernel) = cfg_static
    key_hashes, values, valid = intermediate
    v_dim = values.shape[-1]
    cluster_ids = jnp.abs(key_hashes) % num_clusters
    timed = stamp_through is not None
    me = jax.lax.axis_index(AXIS)

    payload = key_hashes if keyed else cluster_ids.astype(jnp.int32)
    if not pipelined or num_chunks <= 1:
        dest = jnp.where(valid, assignment[cluster_ids], num_slots).astype(jnp.int32)
        bv, bc, bm, overflow = _counting_sort_to_buckets(
            dest, values, payload, num_slots, capacity
        )
        # Bytes-on-the-wire: every bucketed row except the slot's own
        # diagonal bucket (delivered locally) crosses the network.
        wire_rows = (jnp.sum(bm.astype(jnp.float32))
                     - jnp.sum(bm[me].astype(jnp.float32)))
        rv, rc, rm = _copy_chunk((bv, bc, bm), v_dim)
        if keyed:
            keys, out, counts = _reduce_by_key(rc, rv, rm, reduce_op, counted)
            return ((keys, out), counts, jax.lax.psum(overflow, AXIS)[None],
                    _wire_vec(wire_rows))
        if timed:
            # Start stamp: produces the ids the reduce consumes.
            rc, start = stamp_through(rc)
        out, counts = _sequential_reduce(
            rv, rc, rm, rank_of_cluster, num_clusters, reduce_op, use_kernel,
            counted=counted,
        )
        if timed:
            # End stamp: consumes + re-emits the outputs (bit-identical),
            # so it cannot fire before the reduce nor be deferred past
            # its use.
            out, end = stamp_through(out, counts[0])
            return (out, counts, jax.lax.psum(overflow, AXIS)[None],
                    _wire_vec(wire_rows), jnp.stack([start, end])[None])
        return (out, counts, jax.lax.psum(overflow, AXIS)[None],
                _wire_vec(wire_rows))

    # ---- Write every chunk's bucket file in ONE counting-sort spill
    # ("bucket file per operation cluster", §4.4): groups are (chunk, dest)
    # pairs with statistics-derived capacities, laid out chunk-major so
    # each chunk's send buckets are a contiguous static slab.
    chunk_of_pair = chunk_of_cluster[cluster_ids]
    dest = assignment[cluster_ids]
    group = jnp.where(
        valid, chunk_of_pair * num_slots + dest, num_chunks * num_slots
    ).astype(jnp.int32)
    group_caps = np.repeat(np.asarray(chunk_caps, np.int64), num_slots)
    total = int(group_caps.sum())
    fv, fc, fm, overflow = _ragged_counting_sort_to_buckets(
        group, values, payload, group_caps, total
    )
    send = []
    wire_rows = jnp.zeros((), jnp.float32)
    off = 0
    for c in range(num_chunks):
        size = num_slots * chunk_caps[c]
        slab_m = fm[off:off + size].reshape(num_slots, chunk_caps[c])
        send.append((
            fv[off:off + size].reshape(num_slots, chunk_caps[c], v_dim),
            fc[off:off + size].reshape(num_slots, chunk_caps[c]),
            slab_m,
        ))
        wire_rows = wire_rows + (jnp.sum(slab_m.astype(jnp.float32))
                                 - jnp.sum(slab_m[me].astype(jnp.float32)))
        off += size

    # ---- Double-buffered copy→run walk, in increasing-load chunk order.
    # Accumulator dtype mirrors what the sequential path returns (f32 from
    # the segment-reduce kernel, else the value dtype) so both paths agree.
    acc_dtype = jnp.float32 if (reduce_op == "sum" and use_kernel) else values.dtype
    acc = jnp.zeros((num_clusters, v_dim), acc_dtype)
    cnt = jnp.zeros((num_clusters,), jnp.float32)
    # Timed mode: boundary stamps b_0..b_C, b_c pinned between reduce(c-1)
    # and reduce(c) by true deps — it consumes reduce(c-1)'s outputs
    # (scalar reads) and produces the ids reduce(c) reads. Wave c's stamp
    # pair is (b_c, b_{c+1}); the final boundary passes the last wave's
    # outputs through instead, so it lands after the last reduce.
    boundaries = []
    prev_out = None
    tables = []
    recv = _copy_chunk(send[0], v_dim)
    for c in range(num_chunks):
        rv, rc, rm = recv
        if c + 1 < num_chunks:
            # Issue chunk c+1's all-to-all BEFORE reducing chunk c (no
            # data edge from run(c) — nor, in timed mode, to any stamp).
            recv = _copy_chunk(send[c + 1], v_dim)
        if keyed:
            tables.append(_reduce_by_key(rc, rv, rm, reduce_op, counted))
            continue
        if timed:
            anchors = () if prev_out is None else (prev_out[0][0, 0],
                                                   prev_out[1][0])
            rc, b = stamp_through(rc, *anchors)
            boundaries.append(b)
        out_c, cnt_c = _reduce_chunk(
            rv, rc, rm, rank_of_cluster, num_clusters,
            reduce_op, use_kernel, counted=counted,
        )
        if timed and c + 1 == num_chunks:
            # Final boundary: re-emit the last outputs (bit-identical) so
            # the stamp sits after the reduce and before the merge below.
            out_c, b_last = stamp_through(out_c, cnt_c[0])
            boundaries.append(b_last)
        prev_out = (out_c, cnt_c)
        # Every cluster lives in exactly one chunk, so merging is a
        # *replace* where this chunk saw data — correct for max (a
        # maximum() merge would clamp negative maxima at the zero init)
        # and equivalent to += for sum/count (out_c is 0 elsewhere).
        if reduce_op == "max":
            acc = jnp.where(cnt_c[:, None] > 0, out_c.astype(acc_dtype), acc)
        else:
            acc = acc + out_c.astype(acc_dtype)
        cnt = cnt + cnt_c.astype(jnp.float32)
    if keyed:
        keys, out, counts = (jnp.concatenate(t) for t in zip(*tables))
        return ((keys, out), counts, jax.lax.psum(overflow, AXIS)[None],
                _wire_vec(wire_rows))
    if timed:
        ticks = jnp.stack([
            jnp.stack([boundaries[c], boundaries[c + 1]])
            for c in range(num_chunks)
        ])
        return (acc, cnt, jax.lax.psum(overflow, AXIS)[None],
                _wire_vec(wire_rows), ticks)
    return (acc, cnt, jax.lax.psum(overflow, AXIS)[None],
            _wire_vec(wire_rows))


def _phase_b_shard_timed(
    intermediate,
    assignment: jnp.ndarray,
    rank_of_cluster: jnp.ndarray,
    chunk_of_cluster: jnp.ndarray,
    cfg_static: Tuple,
):
    """:func:`_phase_b_shard` with on-device tick stamps around each reduce.

    A thin binding of the ONE phase-B body to the ``kernels/wave_timer``
    stamp hook — same per-chunk programs, same accumulation order, so
    outputs are **bit-identical** to the untimed program by construction
    (there is no second copy to drift). Ordering is by **true buffer
    dependencies** (``wave_timer.ops.stamp_through``): each boundary
    stamp consumes the previous wave's reduce outputs and *produces* the
    buffer the next wave's reduce reads (its cluster ids — every reduce
    path consumes them — or, at the final boundary, the last wave's
    outputs themselves), so no scheduler can hoist a stamp before its
    wave's data or defer it past the compute it precedes. Consecutive
    waves *share* their boundary stamp (end(c) ≡ start(c+1)), tiling the
    shard's reduce timeline with one counter read per boundary. The next
    chunk's all-to-all keeps NO edge to any stamp — the §4.4 copy/run
    overlap survives measurement, which is the whole point of moving the
    clock onto the device.

    Returns ``(out, counts, overflow, wire, ticks)`` with ``ticks`` shaped
    ``(waves, 2, 2)`` uint32 — (start, end) × (lo, hi) counter words.
    """
    from repro.kernels.wave_timer import ops as wt_ops

    return _phase_b_shard(
        intermediate, assignment, rank_of_cluster, chunk_of_cluster,
        cfg_static, stamp_through=wt_ops.stamp_through,
    )


# ---------------------------------------------------------------------------
# The job orchestrator.
# ---------------------------------------------------------------------------


class MapReduceJob:
    """Two-phase OS4M job. See module docstring.

    ``map_fn(shard_input) -> (key_hashes (K,), values (K, V), valid (K,))``
    must be a pure JAX function with static output shapes.
    """

    def __init__(
        self,
        map_fn: Callable,
        config: MapReduceConfig,
        backend: str = "vmap",
        mesh: Optional[Mesh] = None,
    ):
        self.map_fn = map_fn
        self.cfg = config
        self.backend = backend
        if backend == "shard_map":
            if mesh is None:
                raise ValueError("shard_map backend requires a mesh")
            devices = np.asarray(mesh.devices).reshape(-1)
            if devices.size != config.num_slots:
                raise ValueError(
                    f"mesh has {devices.size} devices but config.num_slots="
                    f"{config.num_slots}"
                )
            # Re-axis the mesh so the engine's named axis is bound.
            self.mesh = Mesh(devices, (AXIS,))
        else:
            self.mesh = None

        cfg = self.cfg
        # Statistics provider (docs/STATISTICS.md): owns phase A's traced
        # collection step and the host-side estimators _plan reads.
        self._stats = sp.make_provider(
            cfg.stats, cfg.num_clusters,
            width=cfg.sketch_width, depth=cfg.sketch_depth,
            use_kernel=cfg.use_kernels,
        )
        if cfg.stream_prefix is not None:
            if cfg.stats != "sketch":
                raise ValueError(
                    "stream_prefix requires stats='sketch' — prefix planning"
                    " extrapolates a sketch, the exact path has no estimate"
                    " to extrapolate"
                )
            if not 0.0 < cfg.stream_prefix <= 1.0:
                raise ValueError(
                    f"stream_prefix must be in (0, 1], got {cfg.stream_prefix}"
                )
        if cfg.stats == "sketch" and cfg.checkpoint_waves:
            raise ValueError(
                "stats='sketch' is incompatible with checkpoint_waves — "
                "wave recovery zeroes completed per-cluster histogram "
                "columns, which a count-min counter grid does not have"
            )
        if cfg.combine:
            # Phase A is the map alone; the statistics count combined pairs.
            self._phase_a = functools.partial(_map_shard, map_fn=self.map_fn)
        else:
            self._phase_a = functools.partial(
                _phase_a_shard,
                map_fn=self.map_fn,
                num_clusters=cfg.num_clusters,
                stats_fn=self._stats.collect,
                prefix_fraction=cfg.stream_prefix,
            )
        # The combiner's per-shard capacity C (see _combine).
        self._combine_cap: Optional[int] = None
        # Overflow escape hatches taken for estimate-committed capacities
        # (prefix-planned wave-1 caps; see _escalate_caps) and for a
        # combiner capacity a shard outgrew (see _combine). Telemetry —
        # distinct from ScheduleCache.capacity_fallbacks, which counts
        # reused-plan overflows.
        self.capacity_fallbacks = 0
        # Jitted executables cached per phase static config: a job object
        # runs many batches (serving, training); re-tracing phase B's
        # unrolled pipeline every run would dwarf the work at small sizes.
        # Keys carry the (quantized) statistics-derived capacities, which
        # still vary batch-to-batch when the schedule shifts — the LRU
        # bound keeps hot keys resident and the dict finite. (Schedule
        # reuse across batches of one workload is the follow-up that makes
        # this hit ~always.)
        self._jit_cache: "collections.OrderedDict" = collections.OrderedDict()
        # Measured mode adds one timed executable per plan shape ("bt"),
        # and its fenced *fallback* splits phase B into per-wave programs
        # (spill + one copy/run pair per chunk) — the cache must hold a
        # whole fenced plan next to the fused executables without
        # thrashing.
        self._jit_cache_max = 48
        # Trace telemetry: +1 every time a new executable is built. Steady-
        # state serving asserts this stays flat after warmup.
        self.jit_misses = 0
        # Batches run so far: the ``batch`` counter of each os4m.batch span.
        self.batches_run = 0
        # Schedule-reuse state (the ROADMAP serving item): holds the live
        # CachedSchedule snapshot + decision counters when cfg.reuse is set.
        # On shard_map the drift check is device-resident: the baseline
        # K^(i) stays sharded on the mesh between batches and the metric
        # is a per-device reduction + pmax (only the scalar crosses).
        self.schedule_cache: Optional[sc.ScheduleCache] = (
            sc.ScheduleCache(cfg.reuse, drift_fn=self._make_sharded_drift())
            if cfg.reuse is not None else None
        )
        # Q||C_max state: static speeds are validated once; the online
        # estimator closes the measure → update → next-plan feedback loop.
        if cfg.speeds is not None:
            sched_lib.normalize_speeds(cfg.speeds, cfg.num_slots)
        self.speed_estimator: Optional[ss.SlotSpeedEstimator] = (
            ss.SlotSpeedEstimator(cfg.num_slots, ewma=cfg.speed_ewma)
            if cfg.estimate_speeds else None
        )
        # Timing source: measured per-device wave clocks on a real mesh,
        # the synthetic model otherwise (see MapReduceConfig docstring).
        measure = cfg.measure_timings
        if measure is None:
            measure = backend == "shard_map" and cfg.estimate_speeds
        elif measure:
            if backend != "shard_map":
                raise ValueError(
                    "measure_timings=True needs backend='shard_map' — per-slot"
                    " clocks do not exist on a single vmap device"
                )
            if not cfg.estimate_speeds:
                raise ValueError(
                    "measure_timings=True without estimate_speeds=True would "
                    "measure timings nothing consumes"
                )
        self._measure_timings = bool(measure)
        # The combiner and the per-key Reduce run on the fused jnp phase-B
        # executor only (the other executors reduce per cluster id).
        if cfg.combine or cfg.keyed_output:
            unsupported = {
                "checkpoint_waves": cfg.checkpoint_waves,
                "measured timings": self._measure_timings,
                "use_kernels": cfg.use_kernels,
                # A prefix of the combined, key-sorted pairs is no sample of
                # the pairs that land first.
                "stream_prefix": cfg.combine and cfg.stream_prefix is not None,
            }
            for name, on in unsupported.items():
                if on:
                    raise ValueError(
                        f"combine and keyed_output run on the fused jnp phase-B"
                        f" executor only; {name} is not supported with them"
                    )
        # Last measured (wire bytes, non-local pairs) — turns the cost
        # model's modeled bytes/pair into a measured rate on the next plan.
        self._last_wire: Optional[Tuple[int, int]] = None
        if cfg.checkpoint_waves and self._measure_timings:
            raise ValueError(
                "checkpoint_waves=True is incompatible with measured timings —"
                " both own the fenced phase-B program structure; set"
                " measure_timings=False (synthetic model) to combine fault"
                " tolerance with speed estimation"
            )
        # Last batch's measured (slots, waves) buffer (None on the
        # synthetic path) — telemetry for benches and tests.
        self.last_wave_timings: Optional[mt.WaveTimings] = None
        # Fault injection (tests, launch/serve --slot-slowdown): per-slot
        # wall-clock multipliers (2.0 = twice as slow). On the vmap
        # backend phase B runs every slot on one device, so per-slot wall
        # time cannot be clocked independently; the timing model below
        # synthesises wave timings as work × slowdown. On a shard_map
        # mesh the measured path clocks each device's wave programs for
        # real, and the injection scales the *measured* seconds instead
        # (a stand-in for genuinely slow hardware). Callers with their
        # own clocks feed ``observe_slot_times`` directly.
        self._slot_slowdown = np.ones(cfg.num_slots)
        # True once observe_slot_times delivered a real measurement; the
        # synthetic model then stays out of the estimator.
        self._external_timings = False
        # Elastic-mesh state: which slots have vanished (speed pinned to
        # exact 0.0 — the dead-slot convention of ``scheduler.
        # normalize_speeds``), and armed mid-batch kills (slot → wave
        # index; fired by the checkpointing executor just before that
        # wave runs). ``on_mesh_change(event_dict)`` is an optional
        # observer hook (serve/engine lane accounting); ``mesh_events``
        # keeps the full join/leave/death log for telemetry either way.
        self._dead_slots = np.zeros(cfg.num_slots, dtype=bool)
        self._kill_at_wave: dict = {}
        self.on_mesh_change: Optional[Callable[[dict], None]] = None
        self.mesh_events: list = []
        # Checkpoint telemetry of the last run() (None before the first
        # checkpointed batch): wave cursor at the last completed
        # checkpoint, how many waves the recovery replayed (0 = clean
        # uninterrupted batch), and the WaveCheckpoint itself.
        self.last_checkpoint_wave: Optional[int] = None
        self.last_replayed_waves: Optional[int] = None
        self.last_checkpoint: Optional[pipe.WaveCheckpoint] = None
        # The recovery plan of the last mid-batch failure (None if the
        # last batch ran clean) — benches assert its schedule assigns
        # zero load to the dead slots.
        self.last_replay_plan: Optional[sc.CachedSchedule] = None

    # -- Q||C_max speed plumbing --------------------------------------------

    def set_slot_slowdown(self, slot: int, factor: float) -> None:
        """Inject a fault: slot ``slot``'s wave wall-clock is multiplied by ``factor``.

        A slowdown factor is a **wall-clock multiplier** — ``2.0`` makes
        the slot read twice as *slow* (half the nominal speed); ``0.5``
        makes it read twice as fast. Affects only the wave timings the
        estimator sees (and hence future plans) — never the computed
        outputs.

        ``factor == 0`` is the elastic-mesh limit: the slot is **dead**
        (vanished, not infinitely slow) and the call routes to
        :meth:`set_slot_failure` — future plans assign it nothing at all.
        """
        if not 0 <= slot < self.cfg.num_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.cfg.num_slots})")
        if factor < 0:
            raise ValueError("slowdown factor must be >= 0 (0 = dead slot)")
        if factor == 0:
            self.set_slot_failure(slot)
            return
        self._slot_slowdown[slot] = factor

    def set_slot_failure(self, slot: int, dead: bool = True,
                         at_wave: Optional[int] = None) -> None:
        """Declare slot ``slot`` dead (or revived) on the elastic mesh.

        ``dead=True`` with no ``at_wave`` takes effect immediately: the
        slot's speed is pinned to exact 0.0 in :meth:`current_speeds`, the
        online estimator masks it out (a dead slot never re-inherits
        work), and the next plan — forced by the schedule cache's
        ``"slot_dead"`` structural check — assigns it nothing.

        ``at_wave=w`` arms a **mid-batch kill** for fault injection
        (``launch/serve.py --kill-at-wave i:w``): the slot dies just
        before phase-B wave ``w`` executes, after waves ``0..w-1``
        checkpointed. Requires ``MapReduceConfig(checkpoint_waves=True)``
        — without wave checkpoints there is no consistent cut to recover
        from.

        ``dead=False`` revives a previously dead slot (a join): speed
        estimate resets to unknown and the next structural check replans.
        """
        if not 0 <= slot < self.cfg.num_slots:
            raise ValueError(f"slot {slot} out of range [0, {self.cfg.num_slots})")
        if at_wave is not None:
            if not dead:
                raise ValueError("at_wave only makes sense with dead=True")
            if not self.cfg.checkpoint_waves:
                raise ValueError(
                    "set_slot_failure(at_wave=...) requires "
                    "MapReduceConfig(checkpoint_waves=True)"
                )
            if at_wave < 0:
                raise ValueError("at_wave must be >= 0")
            self._kill_at_wave[int(slot)] = int(at_wave)
            return
        self._mark_slot_dead(slot, dead)

    def _mark_slot_dead(self, slot: int, dead: bool = True) -> None:
        """Flip one slot's dead bit + estimator mask; emit a mesh event."""
        if bool(self._dead_slots[slot]) == bool(dead):
            return
        self._dead_slots[slot] = dead
        self._kill_at_wave.pop(slot, None)
        if self.speed_estimator is not None:
            self.speed_estimator.set_slot_failure(slot, dead=dead)
        self._emit_mesh_event({
            "event": "slot_dead" if dead else "slot_join",
            "slot": int(slot),
            "num_slots": self.cfg.num_slots,
            "alive": int(self.cfg.num_slots - int(self._dead_slots.sum())),
        })

    def _emit_mesh_event(self, event: dict) -> None:
        """Log a join/leave/death/resize event; notify the observer hook."""
        self.mesh_events.append(event)
        if self.on_mesh_change is not None:
            self.on_mesh_change(event)

    def resize(self, num_slots: int, mesh: Optional[Mesh] = None) -> None:
        """Elastically resize the mesh to ``num_slots`` Reduce slots.

        The cheap path through a membership change: instead of discarding
        the job's warm state, every per-slot structure is re-shaped —

        * a cached plan snapshot is **re-projected** onto the new slot
          count (``CachedSchedule.reproject``: re-bin the per-shard
          ``K^(i)`` baseline + one host re-plan from those warm
          statistics — no cold statistics pass on the next batch);
        * the speed estimator keeps the surviving slots' learned rates
          (``SlotSpeedEstimator.resize``);
        * slowdown/dead-slot vectors are truncated or padded (new slots
          arrive alive and nominal);
        * the jit cache is flushed (phase shapes are keyed on ``m``) and
          the device-resident drift closure is rebuilt on the new mesh.

        ``mesh`` is required on the shard_map backend when growing or
        shrinking the device set (it must hold exactly ``num_slots``
        devices); the vmap backend needs none.
        """
        old_m = self.cfg.num_slots
        if num_slots == old_m:
            return
        if num_slots < 1:
            raise ValueError("num_slots must be >= 1")
        if self.backend == "shard_map":
            if mesh is None:
                raise ValueError(
                    "resize on the shard_map backend needs a mesh with the"
                    " new device count"
                )
            devices = np.asarray(mesh.devices).reshape(-1)
            if devices.size != num_slots:
                raise ValueError(
                    f"mesh has {devices.size} devices but resize asked for"
                    f" {num_slots}"
                )
            self.mesh = Mesh(devices, (AXIS,))

        # Static speeds: keep survivors, pad joiners at nominal.
        new_speeds = None
        if self.cfg.speeds is not None:
            base = list(self.cfg.speeds)[:num_slots]
            base += [1.0] * (num_slots - len(base))
            new_speeds = tuple(base)
        self.cfg = dataclasses.replace(
            self.cfg, num_slots=num_slots, speeds=new_speeds
        )

        # Per-slot state: truncate or pad (new slots alive, nominal).
        keep = min(old_m, num_slots)
        slowdown = np.ones(num_slots)
        slowdown[:keep] = self._slot_slowdown[:keep]
        self._slot_slowdown = slowdown
        dead = np.zeros(num_slots, dtype=bool)
        dead[:keep] = self._dead_slots[:keep]
        self._dead_slots = dead
        self._kill_at_wave = {
            s: w for s, w in self._kill_at_wave.items() if s < num_slots
        }
        if self.speed_estimator is not None:
            self.speed_estimator.resize(num_slots)

        # Every cached executable is shaped on the old m — flush, and
        # rebuild the sharded drift closure against the new mesh.
        self._jit_cache.clear()
        if self.schedule_cache is not None:
            self.schedule_cache.drift_fn = self._make_sharded_drift()
            snap = self.schedule_cache.snapshot
            if snap is not None:
                # Warm resize: re-project the snapshot instead of going
                # cold — one re-plan from the re-binned K^(i) baseline.
                self.schedule_cache.snapshot = snap.reproject(
                    num_slots, self._plan
                )
                self.schedule_cache.reprojections += 1
        self._emit_mesh_event({
            "event": "resize",
            "from": int(old_m),
            "to": int(num_slots),
            "alive": int(num_slots - int(self._dead_slots.sum())),
        })

    @property
    def dead_slots(self) -> np.ndarray:
        """Boolean mask of vanished slots (copy)."""
        return self._dead_slots.copy()

    def current_speeds(self) -> Optional[np.ndarray]:
        """Speed vector the next plan will use (None ≡ all nominal).

        Static ``cfg.speeds`` wins; otherwise the online estimate (None
        until the estimator has seen at least one batch). Dead slots
        overlay an exact 0.0 on either source — with neither source set,
        a mesh with dead slots still returns a concrete vector (nominal
        alive, 0.0 dead) so every planner sees the failure.
        """
        if self.cfg.speeds is not None:
            base = np.asarray(self.cfg.speeds, np.float64)
        elif self.speed_estimator is not None:
            base = self.speed_estimator.speeds()
        else:
            base = None
        if np.any(self._dead_slots):
            if base is None:
                base = np.ones(self.cfg.num_slots, np.float64)
            return np.where(self._dead_slots, 0.0, base)
        return base

    def proc_times_row(self, total_load: float = 1.0) -> np.ndarray:
        """This job's row of the multi-job R-matrix: per-slot time for
        ``total_load`` units of its work.

        ``R[job, slot] = total_load / speed[job, slot]`` from the job's
        *own* :class:`~repro.core.slot_speeds.SlotSpeedEstimator` (each
        job observes its own wave timings — cache residency and kernel
        mix make relative slot speeds job-specific, which is exactly why
        the fleet view is unrelated processors, not uniform machines).
        Dead slots read ``+inf`` — the matrix form of the speed-0
        convention that :func:`repro.core.scheduler.normalize_proc_times`
        expects.
        """
        speeds = self.current_speeds()
        if speeds is None:
            speeds = np.ones(self.cfg.num_slots, np.float64)
        row = np.full(self.cfg.num_slots, np.inf, np.float64)
        alive = speeds > 0.0
        row[alive] = float(total_load) / speeds[alive]
        return row

    def attach_schedule_cache(self, cache: sc.ScheduleCache) -> None:
        """Adopt an externally owned cache (multi-tenant coordination).

        The multi-job coordinator hands each job the
        :class:`~repro.core.schedule_cache.ScheduleCache` it reserved
        under the job's tenant key. The job keeps its backend-resident
        drift reduction: if the tenant cache has no ``drift_fn`` yet it
        inherits this job's sharded one. Requires a reuse policy — a
        cache without one has nothing to decide.
        """
        if cache.drift_fn is None:
            cache.drift_fn = self._make_sharded_drift()
        self.cfg = dataclasses.replace(self.cfg, reuse=cache.policy)
        self.schedule_cache = cache

    def observe_slot_times(self, slot_work, slot_seconds) -> None:
        """Feed measured per-slot phase-B (work, wall seconds) to the estimator.

        The hook for real deployments where each slot is a device with its
        own clock. The first call permanently switches the job to
        external-measurement mode: ``run()`` stops folding in its
        synthetic timing model, so real samples are never diluted by
        all-nominal synthetic ones.
        """
        if self.speed_estimator is not None:
            self._external_timings = True
            self.speed_estimator.update(slot_work, slot_seconds)

    def _observe_wave_timings(self, planned: sc.CachedSchedule,
                              key_dist: np.ndarray) -> None:
        """Synthetic per-slot timing model: seconds = work × slowdown.

        One observation per executed batch — the phase-B wave timings of
        §4.4, with the injected ``_slot_slowdown`` (a wall-clock
        multiplier: 2.0 ⇒ twice as slow) standing in for real straggler
        hardware. The estimator normalises rates, so the nominal unit
        cancels; with no injected fault every slot measures 1.0 and plans
        stay bit-identical to the speed-oblivious ones. Disabled as soon
        as ``observe_slot_times`` has delivered a real measurement.
        """
        if self.speed_estimator is None or self._external_timings:
            return
        m = self.cfg.num_slots
        slot_work = np.bincount(
            planned.schedule.assignment, weights=np.asarray(key_dist),
            minlength=m,
        )[:m]
        slot_seconds = slot_work * self._slot_slowdown
        self.speed_estimator.update(slot_work, slot_seconds)

    def _observe_measured(self, timings: mt.WaveTimings,
                          planned: sc.CachedSchedule) -> None:
        """Feed one batch's *measured* per-device wave clocks to the estimator.

        Wave programs are capacity-shaped — every device reduces the same
        statically padded buffer — so the work unit is the shape work
        (rows processed, identical per slot) and ``work/seconds`` isolates
        per-device speed from per-slot load (see
        :class:`repro.core.mesh_timing.WaveTimings`). Injected slowdowns
        multiply the measured seconds by the factor — the wall-clock a
        genuinely slow device would have reported — so fault injection
        rides the measured path instead of reviving the synthetic model.
        Invalid batches are skipped (``timings.valid``: wrapped tick
        stamps, or fenced-fallback waves that traced/compiled). Routed
        through :meth:`observe_slot_times`, which permanently retires the
        synthetic fallback on first contact.
        """
        if self.speed_estimator is None or not timings.valid:
            return
        m = self.cfg.num_slots
        rows = float(m * planned.capacity if planned.waves.num_chunks <= 1
                     else m * sum(planned.chunk_caps))
        timings.slot_work = np.full(m, rows)
        work, secs = timings.observation(self._slot_slowdown)
        # Zero-second guard (ISSUE 5): an empty/degenerate buffer (e.g.
        # ``WaveTimings.empty(m, 0)``, or sub-tick waves on a coarse
        # counter) carries no speed signal — feeding it would flip the
        # job to external-measurement mode on a vacuous sample and risk
        # inf/NaN rates downstream. Skip it entirely.
        if not bool(np.any((secs > 0) & np.isfinite(secs))):
            return
        self.observe_slot_times(work, secs)

    # -- device-resident drift (shard_map backend) ---------------------------

    def _make_sharded_drift(self):
        """A drift_fn for :class:`~repro.core.schedule_cache.ScheduleCache`.

        shard_map backend only (``None`` elsewhere): the plan-time baseline
        ``K^(i)`` is uploaded ONCE, sharded row-per-device next to the
        fresh phase-A histograms, and the L1/χ² metric runs as a
        shard-local reduction + ``pmax`` — between batches the baseline
        stays resident on the mesh, and only the scalar verdict crosses to
        the host.
        """
        if self.backend != "shard_map" or self.cfg.reuse is None:
            return None
        from jax.sharding import NamedSharding

        mesh = self.mesh
        metric = self.cfg.reuse.metric

        def per_shard(ref, fresh):
            """One device's drift contribution over its own K^(i) row."""
            p = ref / jnp.maximum(ref.sum(-1, keepdims=True), 1e-9)
            q = fresh / jnp.maximum(fresh.sum(-1, keepdims=True), 1e-9)
            if metric == "l1":
                d = 0.5 * jnp.abs(p - q).sum()
            else:
                d = 0.5 * ((p - q) ** 2 / jnp.maximum(p + q, 1e-9)).sum()
            return jax.lax.pmax(d, AXIS)

        fn = jax.jit(compat.shard_map(
            per_shard, mesh=mesh,
            in_specs=(P(AXIS, None), P(AXIS, None)), out_specs=P(),
        ))
        sharding = NamedSharding(mesh, P(AXIS, None))

        def drift(snapshot: sc.CachedSchedule, fresh_hist):
            """Scalar drift of ``fresh_hist`` vs the device-resident baseline."""
            ref = snapshot.hist_device(
                lambda h: jax.device_put(jnp.asarray(h, jnp.float32), sharding)
            )
            return fn(ref, jnp.asarray(fresh_hist, jnp.float32))

        return drift

    def load_snapshot(self, snapshot) -> sc.CachedSchedule:
        """Install a persisted plan so a warm process skips the first replan.

        ``snapshot`` is a :class:`~repro.core.schedule_cache.CachedSchedule`
        or its ``to_json`` dict (e.g. read from ``launch/serve.py
        --schedule-snapshot path.json``). Requires ``cfg.reuse`` — the
        snapshot lands in the schedule cache and the first batch goes
        through the normal drift check instead of the cold replan.
        """
        if self.schedule_cache is None:
            raise ValueError("load_snapshot requires MapReduceConfig(reuse=...)")
        if isinstance(snapshot, dict):
            snapshot = sc.CachedSchedule.from_json(snapshot)
        m, n = self.cfg.num_slots, self.cfg.num_clusters
        if snapshot.schedule.num_slots != m:
            raise ValueError(
                f"snapshot has {snapshot.schedule.num_slots} slots, config {m}"
            )
        if snapshot.schedule.assignment.shape[0] != n:
            raise ValueError(
                f"snapshot covers {snapshot.schedule.assignment.shape[0]} "
                f"clusters, config {n}"
            )
        # Warm-start the estimator with the plan-time speeds: a snapshot
        # built from measured (non-nominal) speeds would otherwise face
        # its first drift check with fresh_speeds=None — conservative
        # ``inf`` — and replan immediately, defeating the warm start.
        if self.speed_estimator is not None \
                and self.speed_estimator.observations == 0:
            self.speed_estimator.seed(snapshot.schedule.slot_speeds)
        self.schedule_cache.store(snapshot)
        return snapshot

    # -- backend plumbing ---------------------------------------------------
    #
    # Array convention: per-shard code sees unbatched arrays. The caller
    # passes inputs with a leading (num_slots,) axis for ``vmap`` or a
    # global leading axis of size num_slots * per_shard for ``shard_map``.

    @staticmethod
    def _to_pspec(tree):
        return jax.tree.map(
            lambda a: P(AXIS) if a == 0 else P(),
            tree,
            is_leaf=lambda x: x is None or isinstance(x, int),
        )

    def _run_sharded(self, fn, in_specs, out_specs, *args, cache_key=None):
        # Callers use the vmap convention (leading (num_slots,) axis);
        # shard_map shards a flat global axis, so merge the first two dims
        # on sharded args (outputs come back in the matching flat layout).
        # This runs on every call — cached executables see the same layout
        # they were traced with.
        def _flatten(spec, a):
            if spec == 0 and hasattr(a, "ndim") and a.ndim >= 2:
                return a.reshape((-1,) + a.shape[2:])
            if isinstance(spec, tuple):
                return tuple(_flatten(s, x) for s, x in zip(spec, a))
            return a

        if self.backend != "vmap":
            args = tuple(_flatten(s, a) for s, a in zip(in_specs, args))

        jitted = self._jit_cache.get(cache_key) if cache_key is not None else None
        if jitted is not None:
            self._jit_cache.move_to_end(cache_key)
            return jitted(*args)
        self.jit_misses += 1
        if self.backend == "vmap":
            jitted = jax.jit(jax.vmap(
                fn, in_axes=in_specs, out_axes=out_specs, axis_name=AXIS
            ))
        else:
            jitted = jax.jit(compat.shard_map(
                fn,
                mesh=self.mesh,
                in_specs=self._to_pspec(in_specs),
                out_specs=self._to_pspec(out_specs),
            ))
        if cache_key is not None:
            self._jit_cache[cache_key] = jitted
            while len(self._jit_cache) > self._jit_cache_max:
                self._jit_cache.popitem(last=False)
        # The first call traces, compiles (or loads from the persistent
        # cache) and dispatches the new executable.
        with TraceAnnotation(spans.JIT_BUILD,
                             key=str(cache_key[0]) if cache_key else ""):
            return jitted(*args)

    # -- measured shuffle-volume accounting ----------------------------------

    def _wire_rate(self) -> float:
        """Measured wire bytes per non-local pair (model default until measured).

        ``shuffle_bytes / shuffle_pairs`` of the last accounted batch: the
        per-pair cost of the wire, which is what the flow-shop cost
        model's copy phase should charge. Falls back to the simulator's
        modeled 64 B/pair.
        """
        if self._last_wire is not None and self._last_wire[1] > 0:
            return max(1e-6, self._last_wire[0] / self._last_wire[1])
        return 64.0

    @staticmethod
    def _wire_accounting(rows: float, values) -> Tuple[int, int, int]:
        """``(shuffle_bytes, shuffle_rows, shuffle_pairs)`` of ``rows``
        measured wire rows: each row is one non-local pair, its ``V``
        values plus a 4-byte cluster id (or key), so bytes = rows ×
        (V·itemsize + 4)."""
        row_bytes = int(values.shape[-1]) * jnp.dtype(values.dtype).itemsize + 4
        n = int(round(rows))
        return n * row_bytes, n, n

    # -- planning (the host "JobTracker" step) -------------------------------

    def _plan(
        self,
        local_hist: np.ndarray,
        key_dist: Optional[np.ndarray],
        k_per_shard: int,
        prev: Optional[sc.CachedSchedule] = None,
        num_chunks: Optional[int] = None,
        assignment_override: Optional[np.ndarray] = None,
        strategy_override: Optional[str] = None,
        pinned_first: Optional[np.ndarray] = None,
        chunk0_cap: Optional[int] = None,
    ) -> sc.CachedSchedule:
        """One host planning step: schedule + §4.4 waves + send capacities.

        Pure host computation from the per-shard statistics; the returned
        :class:`~repro.core.schedule_cache.CachedSchedule` fully determines
        phase B (and its jit-cache key), so it can be replayed across
        batches. ``prev`` is the outgoing snapshot when replanning under a
        reuse policy — capacities take the elementwise max with it (shape
        hysteresis), so repeated replans of one workload converge on a
        single set of buffer shapes and the phase-B jit cache keeps
        hitting even across replans.

        ``local_hist`` is *provider state* (``core/stats_provider``): the
        exact ``(m, n)`` histogram, or ``(m, depth * width)`` count-min
        cells under ``cfg.stats == "sketch"`` — in which case every
        planning input here is O(sketch size), the dense per-shard and
        global estimates are derived on the host (overestimate-only, so
        capacities never silently under-provision), and the passed
        ``key_dist`` is ignored (a sketch's global distribution is an
        estimate, not a column sum — callers may pass ``None``).

        ``num_chunks`` overrides ``cfg.pipeline_chunks`` — the elastic
        recovery path plans only the *remaining* waves after a mid-batch
        failure, so the replayed pipeline is exactly as deep as the work
        left to do.

        The remaining keywords serve streaming-prefix refinement
        (:meth:`_plan_prefixed`): ``assignment_override`` /
        ``strategy_override`` replay a committed cluster → slot
        assignment instead of invoking the scheduler,  ``pinned_first``
        pins the committed wave-1 members to chunk 0, and ``chunk0_cap``
        clamps chunk 0 to the committed capacity — marking the plan
        ``caps_estimated`` (the commitment came from an extrapolated
        prefix and may under-provision; the runner's overflow escape
        hatch restores exactness).
        """
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters
        pipeline_chunks = (num_chunks if num_chunks is not None
                          else cfg.pipeline_chunks)
        speeds = self.current_speeds()
        provider = self._stats
        state = np.asarray(local_hist)
        # f32 integer-exactness guard on the RAW device counters — for
        # exact stats these are the histogram cells themselves; for a
        # sketch they are the count-min cells, whose estimates (mins over
        # rows) are only trustworthy while every cell is still exact. A
        # saturated counter voids the overestimate guarantee, so all
        # bounds fall back to the safe k_per_shard.
        raw_max = float(state.max()) if state.size else 0.0
        hist_exact = raw_max < sp.F32_EXACT_MAX
        if provider.kind == "sketch":
            # No (m, n) densify here: capacities come straight from the
            # cells (provider.send_bound) and only the (n,) global
            # estimate is materialized for the scheduler.
            dense_hist = None
            key_dist = provider.key_dist(state)
        else:
            dense_hist = state
            key_dist = (np.asarray(key_dist) if key_dist is not None
                        else provider.key_dist(state))

        # The JobTracker invokes the scheduling algorithm (§4.1 step 4).
        # "auto" tries every candidate and keeps the one with the lowest
        # estimated Reduce makespan under the flow-shop cost model. Every
        # strategy assigns by earliest finish time under the current
        # per-slot speed estimate (Q||C_max; None ≡ identical slots).
        strategy_costs = None
        with TraceAnnotation(spans.PLAN_ASSIGN):
            if assignment_override is not None:
                # Prefix refinement: the assignment was committed by the
                # wave-1 plan; only waves and capacities are recomputed.
                strategy = strategy_override or cfg.scheduler
                schedule = sched_lib.Schedule.from_assignment(
                    np.asarray(assignment_override, np.int32), key_dist, m,
                    speeds=speeds,
                )
            elif cfg.scheduler == "auto":
                from repro.core import simulator as sim

                strategy, schedule, strategy_costs = sim.pick_strategy(
                    key_dist, m, eta=cfg.eta,
                    pipelined=cfg.pipelined and pipeline_chunks > 1,
                    speeds=speeds,
                    # Measured wire rate (last batch) + per-slot locality: the
                    # model sees what the shuffle actually costs.
                    bytes_per_pair=self._wire_rate(),
                    # The locality-aware wire model wants per-shard (m, n)
                    # counts; a sketch densifies its estimates only for this
                    # one auto-strategy path.
                    local_hist=(provider.to_dense(state) if dense_hist is None
                                else dense_hist),
                )
            else:
                strategy = cfg.scheduler
                scheduler = sched_lib.get_scheduler(cfg.scheduler)
                if cfg.scheduler == "hash":
                    schedule = scheduler(key_dist, m, keys=np.arange(n),
                                         speeds=speeds)
                elif dense_hist is None:
                    # Sketch plans schedule at *bin* granularity: the row-0
                    # cell sums are the exact total mass landing in each bin,
                    # so Q||C_max runs over ``width`` loads instead of ``n``
                    # and the scheduling cost is O(sketch), independent of the
                    # key count. The per-cluster assignment is a gather
                    # through the row-0 hash — clusters sharing a bin travel
                    # together, which is exactly the granularity the
                    # distinct-bin send bound already charges capacities for.
                    cells = state.reshape(m, provider.depth, provider.width)
                    bin_loads = np.asarray(cells[:, 0, :].sum(axis=0),
                                           np.float64)
                    if cfg.scheduler in ("bss", "os4m"):
                        bin_sched = scheduler(bin_loads, m, eta=cfg.eta,
                                              speeds=speeds)
                    else:
                        bin_sched = scheduler(bin_loads, m, speeds=speeds)
                    assignment = bin_sched.assignment[provider.bins()[0]]
                    schedule = sched_lib.Schedule.from_assignment(
                        np.asarray(assignment, np.int32), key_dist, m,
                        speeds=speeds)
                elif cfg.scheduler in ("bss", "os4m"):
                    schedule = scheduler(key_dist, m, eta=cfg.eta, speeds=speeds)
                else:
                    schedule = scheduler(key_dist, m, speeds=speeds)

        # Static capacity for the all-to-all: the per-(shard,dest) worst
        # case from the per-shard statistics — shard i sends dest d exactly
        # the pairs of d's clusters that i holds, and the host has K^(i)
        # (or an overestimate of it) per shard, so every send buffer is
        # statistics-sized. Bounds are quantized (≤12.5% slack) so
        # repeated jobs with similar — not identical — distributions share
        # one jitted phase-B executable instead of retracing per batch.
        # Under a reuse policy the bound gains ``capacity_slack`` headroom
        # first, so sub-threshold drift between replans rarely overflows a
        # replayed plan's buffers.
        capacity = cfg.capacity_send or k_per_shard
        slack = 1.0 + (cfg.reuse.capacity_slack if cfg.reuse is not None else 0.0)

        def _quantize_cap(c: int) -> int:
            """Round up to ~1/8-octave steps: bounded cache-key alphabet."""
            c = max(1, int(c))
            if c <= 8:
                return c
            g = 1 << max(0, (c - 1).bit_length() - 3)
            return -(-c // g) * g

        def _send_bound(members) -> int:
            """max over (shard, dest) of pairs shard sends dest (+ slack)."""
            if not hist_exact:
                return k_per_shard      # saturated f32 counts: safe bound
            if len(members) == 0:
                return 1
            dests = schedule.assignment[members]
            if dense_hist is None:
                # Count-min distinct-bin bound: O(sketch), still >= the
                # true per-(shard, dest) worst case (overestimate-only).
                worst = provider.send_bound(state, dests, members, m)
            else:
                worst = 0.0
                for i in range(m):
                    per_dest = np.bincount(
                        dests, weights=dense_hist[i, members], minlength=m
                    )
                    worst = max(worst, float(per_dest.max()))
            return min(k_per_shard, _quantize_cap(int(np.ceil(worst * slack))))

        all_members = np.arange(n)
        capacity = max(1, int(min(capacity, k_per_shard, _send_bound(all_members))))

        # Pipeline plan (§4.4): per-slot increasing-load waves merged into
        # job-wide chunks, globally ordered by finish time under the slot
        # speeds — see ``pipeline.plan_waves``.
        waves = pipe.plan_waves(
            key_dist, schedule.assignment, m, pipeline_chunks,
            speeds=speeds, pinned_first=pinned_first,
        )
        chunk_caps = [
            int(min(capacity, _send_bound(waves.chunk_members(ci))))
            for ci in range(waves.num_chunks)
        ]
        caps_estimated = False
        if chunk0_cap is not None:
            # Streaming commitment: wave 1's buffer was sized from the
            # prefix extrapolation before the tail landed, so the refined
            # plan must replay it — even if the full statistics now say
            # it is too small (that is what the overflow hatch is for).
            chunk_caps[0] = max(1, int(min(capacity, chunk0_cap)))
            caps_estimated = chunk_caps[0] < _send_bound(
                waves.chunk_members(0))

        # Shape hysteresis: buffer shapes may only grow across replans of
        # one workload (bounded by k_per_shard), so the phase-B jit cache
        # converges instead of ping-ponging between quantization buckets.
        if prev is not None and prev.waves.num_chunks == waves.num_chunks:
            capacity = max(capacity, prev.capacity)
            chunk_caps = [max(a, b) for a, b in zip(chunk_caps, prev.chunk_caps)]

        return sc.CachedSchedule(
            schedule=schedule,
            strategy=strategy,
            strategy_costs=strategy_costs,
            waves=waves,
            capacity=capacity,
            chunk_caps=tuple(int(c) for c in chunk_caps),
            local_hist=state,
            key_dist=np.asarray(key_dist),
            k_per_shard=int(k_per_shard),
            stats_provider=provider.kind,
            stats_params=provider.params(),
            stats_overestimate=not caps_estimated,
            caps_estimated=caps_estimated,
        )

    def _plan_prefixed(
        self,
        state: np.ndarray,
        prefix_state: np.ndarray,
        k_per_shard: int,
        prev: Optional[sc.CachedSchedule] = None,
    ) -> sc.CachedSchedule:
        """Streaming-prefix planning: commit wave 1 early, refine the rest.

        Emulates the streaming deployment where the JobTracker cannot
        wait for every Map to report before the Reduce pipeline starts:

        1. Plan from the *prefix* sketch scaled by ``1 / stream_prefix``
           (the prefix extrapolated to the full batch). This commits the
           cluster → slot assignment, wave 1's membership, and wave 1's
           send capacity — everything a real deployment would have
           dispatched before the tail landed.
        2. Re-plan from the full-batch sketch, replaying the committed
           assignment (``assignment_override``), pinning the committed
           wave-1 members to chunk 0 (``pinned_first``) and clamping
           chunk 0 to the committed capacity (``chunk0_cap``) — only the
           tail waves are re-cut and re-sized from the tighter
           statistics.

        The refined plan is what phase B executes, so prefix-planned and
        full-planned runs produce identical outputs whenever the
        committed wave-1 cap did not under-provision; when it did, the
        overflow hatch (:meth:`_escalate_caps`) restores exactness.
        """
        frac = self.cfg.stream_prefix
        plan1 = self._plan(prefix_state / frac, None, k_per_shard)
        pinned = plan1.waves.chunk_members(0)
        return self._plan(
            state, None, k_per_shard, prev=prev,
            assignment_override=plan1.schedule.assignment,
            strategy_override=plan1.strategy,
            pinned_first=pinned,
            chunk0_cap=plan1.chunk_caps[0],
        )

    def _escalate_caps(self, planned: sc.CachedSchedule) -> sc.CachedSchedule:
        """Exactness escape hatch for estimate-committed capacities.

        A plan whose chunk-0 cap was committed from a prefix estimate
        (``caps_estimated``) can overflow. Capacities only gate buffer
        sizing — assignment, wave membership and reduce order are
        untouched — so the recovery is NOT a replan: the same plan is
        re-issued with every capacity raised to the safe bound
        ``min(capacity_send, k_per_shard)`` (a shard holds at most
        ``k_per_shard`` pairs, so estimate-driven overflow becomes
        impossible and the re-executed batch is bit-identical to what an
        exact-stats plan of the same schedule would produce).
        """
        cfg = self.cfg
        k = int(planned.k_per_shard)
        safe = max(1, int(min(cfg.capacity_send or k, k)))
        return dataclasses.replace(
            planned,
            capacity=safe,
            chunk_caps=tuple(safe for _ in range(planned.waves.num_chunks)),
            stats_overestimate=True,
            caps_estimated=False,
        )

    # -- execution (phase B under one plan) ----------------------------------

    def _execute(self, intermediate, planned: sc.CachedSchedule):
        """Run phase B under one plan (fresh or replayed); device results.

        The jit-cache key is derived from the plan's static shapes alone,
        so replaying a snapshot is guaranteed to hit the cached executable.
        """
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters
        static = (
            m, n, planned.capacity, tuple(planned.chunk_caps), cfg.reduce_op,
            cfg.pipelined, planned.waves.num_chunks, cfg.use_kernels,
        )

        keyed, counted = cfg.keyed_output, cfg.combine

        def phase_b(intermediate, assignment, rank_of_cluster, chunk_of_cluster):
            """Per-shard chunked shuffle + pipelined reduce under ``static``."""
            return _phase_b_shard(
                intermediate, assignment, rank_of_cluster, chunk_of_cluster, static,
                keyed=keyed, counted=counted,
            )

        return self._run_sharded(
            phase_b,
            ((0, 0, 0), None, None, None),
            ((0, 0) if keyed else 0, 0, 0, 0),
            intermediate,
            jnp.asarray(planned.schedule.assignment, jnp.int32),
            jnp.asarray(planned.waves.rank_of_cluster),
            jnp.asarray(planned.waves.chunk_of_cluster),
            cache_key=("b", static),
        )

    def _execute_measured(self, intermediate, planned: sc.CachedSchedule):
        """Overlapped phase B with on-device wave tick stamps (no fencing).

        Runs the SAME double-buffered pipeline as :meth:`_execute` — the
        all-to-all of chunk i+1 issued under the reduce of chunk i — via
        :func:`_phase_b_shard_timed`, which brackets each wave's reduce
        with per-device (start, end) tick stamps from
        ``kernels/wave_timer``. Per-slot wall clocks are read from the
        tiny ``(slots, waves, 2)`` ticks buffer *after* the batch instead
        of host fences, so measured mode keeps the §4.4 copy/run overlap
        and its throughput penalty vs unmeasured drops to stamp overhead.
        Outputs are bit-identical to :meth:`_execute` (same per-chunk
        programs and accumulation order; the pass-through stamps are
        value identities), and — unlike the
        fenced fallback — the stamps execute with the program, after
        compilation, so even a freshly traced batch yields a valid
        measurement.

        Platforms without a tick source (``wave_timer.ops.available()``
        False — no device counter primitive and no CPU callback, as on a
        TPU with jax 0.9) fall back to :meth:`_execute_measured_fenced`,
        the documented host-timed path, and say so with a
        ``RuntimeWarning``.

        Returns ``(out, counts, overflow, timings)`` where ``timings`` is
        the ``(slots, waves)`` :class:`repro.core.mesh_timing.WaveTimings`
        buffer.
        """
        from repro.kernels.wave_timer import ops as wt_ops

        if not wt_ops.available():
            warnings.warn(
                f"no wave-timer tick source on the {jax.default_backend()!r} "
                "backend: measured phase-B timings use the host-fenced executor",
                RuntimeWarning, stacklevel=2,
            )
            return self._execute_measured_fenced(intermediate, planned)
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters
        num_chunks = planned.waves.num_chunks
        static = (
            m, n, planned.capacity, tuple(planned.chunk_caps), cfg.reduce_op,
            cfg.pipelined, num_chunks, cfg.use_kernels,
        )
        num_waves = num_chunks if cfg.pipelined and num_chunks > 1 else 1

        def phase_b_timed(intermediate, assignment, rank_of_cluster,
                          chunk_of_cluster):
            """Per-shard overlapped phase B + wave tick stamps."""
            return _phase_b_shard_timed(
                intermediate, assignment, rank_of_cluster, chunk_of_cluster,
                static,
            )

        out, counts, overflow, wire, words = self._run_sharded(
            phase_b_timed,
            ((0, 0, 0), None, None, None),
            (0, 0, 0, 0, 0),
            intermediate,
            jnp.asarray(planned.schedule.assignment, jnp.int32),
            jnp.asarray(planned.waves.rank_of_cluster),
            jnp.asarray(planned.waves.chunk_of_cluster),
            cache_key=("bt", static),
        )
        raw = np.asarray(jax.device_get(words)).reshape(m, num_waves, 2, 2)
        timings = mt.WaveTimings.from_ticks(
            wt_ops.combine_ticks(raw),
            wt_ops.tick_calibration().seconds_per_tick,
        )
        return out, counts, overflow, wire, timings

    def _execute_measured_fenced(self, intermediate, planned: sc.CachedSchedule):
        """Fenced fallback: per-wave dispatches + host-attributed clocks.

        The documented fallback for platforms where no tick source exists
        (``kernels/wave_timer`` probes a device counter primitive, then a
        CPU callback; see its ``ops.backend``). Same math as
        :meth:`_execute`, different program structure: the single unrolled
        phase-B program is split into a shard-local spill, and per §4.4
        wave one "copy" program (the all-to-all — a collective
        synchronises every device, so its time is not attributed per slot)
        and one "run" program (shard-local segment reduce, NO collectives
        — each device's output shard becomes ready when *that device*
        finishes, polled in completion order by
        :func:`repro.core.mesh_timing.shard_ready_seconds`). Accumulation
        walks the waves in the same order with the same per-chunk reduce,
        so outputs are bit-identical to the overlapped path; the price is
        the lost copy/run overlap — exactly what the tick path exists to
        avoid paying.

        Returns ``(out, counts, overflow, wire, timings)`` like
        :meth:`_execute_measured`.
        """
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters
        num_chunks = planned.waves.num_chunks
        static = (
            m, n, planned.capacity, tuple(planned.chunk_caps), cfg.reduce_op,
            cfg.pipelined, num_chunks, cfg.use_kernels,
        )
        assignment = jnp.asarray(planned.schedule.assignment, jnp.int32)
        rank_of_cluster = jnp.asarray(planned.waves.rank_of_cluster)
        chunk_of_cluster = jnp.asarray(planned.waves.chunk_of_cluster)
        capacity = planned.capacity
        chunk_caps = tuple(planned.chunk_caps)
        reduce_op, use_kernel = cfg.reduce_op, cfg.use_kernels
        pipelined = cfg.pipelined and num_chunks > 1

        def _block_all(arrs):
            for a in arrs:
                a.block_until_ready()

        if not pipelined:
            # Single wave, mirroring _phase_b_shard's sequential branch.
            def bucket_fn(inter, assignment):
                """Shard-local counting sort into per-dest send buckets."""
                key_hashes, values, valid = inter
                # Verbatim the fused path's expression (phase A already
                # emitted int32 hashes) so both executors bucket identically.
                cluster_ids = jnp.abs(key_hashes) % n
                dest = jnp.where(valid, assignment[cluster_ids], m).astype(jnp.int32)
                bv, bc, bm, overflow = _counting_sort_to_buckets(
                    dest, values, cluster_ids.astype(jnp.int32), m, capacity
                )
                me = jax.lax.axis_index(AXIS)
                rows = (jnp.sum(bm.astype(jnp.float32))
                        - jnp.sum(bm[me].astype(jnp.float32)))
                return (bv[None], bc[None], bm[None],
                        jax.lax.psum(overflow, AXIS)[None], _wire_vec(rows))

            def copy_fn(bv, bc, bm):
                """The "copy": all-to-all every bucket to its Reduce slot."""
                rv, rc, rm = _copy_chunk((bv, bc, bm), bv.shape[-1])
                return rv[None], rc[None], rm[None]

            def run_fn(rv, rc, rm, rank_of_cluster):
                """Shard-local "sort"+"run" — the timed, collective-free part."""
                return _sequential_reduce(rv, rc, rm, rank_of_cluster, n,
                                          reduce_op, use_kernel)

            bv, bc, bm, overflow, wire = self._run_sharded(
                bucket_fn, ((0, 0, 0), None), (0, 0, 0, 0, 0),
                intermediate, assignment, cache_key=("m_bucket", static))
            recv = self._run_sharded(
                copy_fn, (0, 0, 0), (0, 0, 0), bv, bc, bm,
                cache_key=("m_copy", static))
            _block_all(recv)
            timings = mt.WaveTimings.empty(m, 1)
            miss0 = self.jit_misses
            t0 = time.perf_counter()
            out, counts = self._run_sharded(
                run_fn, (0, 0, 0, None), (0, 0),
                recv[0], recv[1], recv[2], rank_of_cluster,
                cache_key=("m_run", static))
            timings.record(0, mt.shard_ready_seconds([out, counts], m, t0))
            timings.valid = self.jit_misses == miss0
            return out, counts, overflow, wire, timings

        # Pipelined: one shard-local spill writes every wave's bucket file,
        # then a fenced copy→run walk per wave in the same chunk order.
        group_caps = np.repeat(np.asarray(chunk_caps, np.int64), m)
        total = int(group_caps.sum())

        def spill_fn(inter, assignment, chunk_of_cluster):
            """Shard-local ragged counting sort — all chunk slabs in one spill."""
            key_hashes, values, valid = inter
            cluster_ids = jnp.abs(key_hashes) % n   # fused-path expression
            chunk_of_pair = chunk_of_cluster[cluster_ids]
            dest = assignment[cluster_ids]
            group = jnp.where(
                valid, chunk_of_pair * m + dest, num_chunks * m
            ).astype(jnp.int32)
            fv, fc, fm, overflow = _ragged_counting_sort_to_buckets(
                group, values, cluster_ids.astype(jnp.int32), group_caps, total
            )
            me = jax.lax.axis_index(AXIS)
            rows = jnp.zeros((), jnp.float32)
            off = 0
            for cc in chunk_caps:
                slab_m = fm[off:off + m * cc].reshape(m, cc)
                rows = rows + (jnp.sum(slab_m.astype(jnp.float32))
                               - jnp.sum(slab_m[me].astype(jnp.float32)))
                off += m * cc
            return (fv[None], fc[None], fm[None],
                    jax.lax.psum(overflow, AXIS)[None], _wire_vec(rows))

        fv, fc, fm, overflow, wire = self._run_sharded(
            spill_fn, ((0, 0, 0), None, None), (0, 0, 0, 0, 0),
            intermediate, assignment, chunk_of_cluster,
            cache_key=("m_spill", static))

        v_dim = int(fv.shape[-1])
        acc_dtype = (jnp.float32 if (reduce_op == "sum" and use_kernel)
                     else fv.dtype)
        acc = jnp.zeros((m * n, v_dim), acc_dtype)
        cnt = jnp.zeros((m * n,), jnp.float32)
        timings = mt.WaveTimings.empty(m, num_chunks)
        offsets = np.concatenate([[0], np.cumsum(
            [m * c for c in chunk_caps])]).astype(int)
        for c in range(num_chunks):
            off, cap = int(offsets[c]), chunk_caps[c]

            def copy_fn(fv, fc, fm, _off=off, _cap=cap):
                """The "copy" of wave c: slice its slab, all-to-all it."""
                rv, rc, rm = _fenced_wave_copy(fv, fc, fm, _off, _cap, m,
                                               v_dim)
                return rv[None], rc[None], rm[None]

            def run_fn(rv, rc, rm, rank_of_cluster):
                """The "sort"+"run" of wave c — shard-local, timed per device."""
                return _fenced_wave_run(rv, rc, rm, rank_of_cluster, n,
                                        reduce_op, use_kernel)

            recv = self._run_sharded(
                copy_fn, (0, 0, 0), (0, 0, 0), fv, fc, fm,
                cache_key=("m_wcopy", static, c))
            _block_all(recv)
            miss0 = self.jit_misses
            t0 = time.perf_counter()
            out_c, cnt_c = self._run_sharded(
                run_fn, (0, 0, 0, None), (0, 0),
                recv[0], recv[1], recv[2], rank_of_cluster,
                cache_key=("m_wrun", static, cap))
            timings.record(c, mt.shard_ready_seconds([out_c, cnt_c], m, t0))
            if self.jit_misses != miss0:
                timings.valid = False
            # Same merge as the fused program, elementwise on the global
            # (m·n, v) layout — replace-where-seen for max, += otherwise.
            if reduce_op == "max":
                acc = jnp.where((cnt_c > 0)[:, None], out_c.astype(acc_dtype),
                                acc)
            else:
                acc = acc + out_c.astype(acc_dtype)
            cnt = cnt + cnt_c.astype(jnp.float32)
        return acc, cnt, overflow, wire, timings

    def _mask_completed(self, intermediate, completed: np.ndarray):
        """Invalidate every pair whose cluster already checkpointed.

        Elementwise (no collectives), so one jitted function serves both
        backends and any intermediate layout. The replayed phase B then
        reduces exactly the pairs of the unfinished waves — completed
        clusters contribute nothing twice.
        """
        key_hashes, values, valid = intermediate
        fn = self._jit_cache.get(("mask",))
        if fn is not None:
            return (key_hashes, values, fn(key_hashes, valid,
                                           jnp.asarray(completed)))
        self.jit_misses += 1
        n = self.cfg.num_clusters

        def mask(kh, valid, done):
            """valid &= cluster not yet checkpointed."""
            return valid & ~done[jnp.abs(kh) % n]

        fn = jax.jit(mask)
        self._jit_cache[("mask",)] = fn
        with TraceAnnotation(spans.JIT_BUILD, key="mask"):
            return (key_hashes, values, fn(key_hashes, valid,
                                           jnp.asarray(completed)))

    def _execute_checkpointed(self, intermediate, planned: sc.CachedSchedule,
                              local_k, k_per_shard: int):
        """Phase B with host checkpoints at wave granularity (elastic mesh).

        Walks the §4.4 waves one fenced copy→run pair at a time (same
        per-chunk programs and accumulation structure as :meth:`_execute`,
        so an uninterrupted walk is **bit-identical** to the fused
        pipeline: every cluster lives in exactly one wave and is reduced
        on exactly one slot, and merging its single non-zero contribution
        with exact zeros is order-insensitive). After each wave the merged
        outputs land in a host :class:`repro.core.pipeline.WaveCheckpoint`.

        An armed kill (``set_slot_failure(slot, at_wave=w)``) fires just
        before wave ``w``: the slot is marked dead, the *remaining* load
        (fresh ``K^(i)`` with completed clusters zeroed) is re-planned
        onto the surviving slots with exactly ``num_chunks − w`` chunks,
        completed clusters are masked out of the intermediate pairs, and
        the fused executor replays only that residue — so recovery costs
        ``remaining_waves`` of work, never the whole batch.

        Returns host ``(values (n, v), counts (n,), overflow_total)``.
        """
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters
        num_chunks = planned.waves.num_chunks
        pipelined = cfg.pipelined and num_chunks > 1
        waves_total = num_chunks if pipelined else 1
        ckpt = pipe.WaveCheckpoint(num_chunks=waves_total)
        vals = None
        cnts = None
        overflow_total = 0
        replayed = 0

        def _merge_host(out, counts):
            """Collapse device outputs over slots (each cluster: one slot)."""
            o = np.asarray(jax.device_get(out)).reshape(m, n, -1).sum(axis=0)
            ct = np.asarray(jax.device_get(counts)).reshape(m, n).sum(axis=0)
            return o, ct

        def _absorb(o, ct):
            """Merge one wave into the accumulators (replace for max)."""
            nonlocal vals, cnts
            if vals is None:
                vals = np.zeros_like(o)
                cnts = np.zeros_like(ct)
            if cfg.reduce_op == "max":
                vals = np.where(ct[:, None] > 0, o, vals)
            else:
                vals = vals + o
            cnts = cnts + ct

        def _fire(due):
            """Mark the due slots dead (pops their armed kills)."""
            for s in due:
                self._kill_at_wave.pop(s, None)
                self._mark_slot_dead(s)

        def _replay(cursor: int):
            """Re-plan + re-execute the unfinished waves on the survivors."""
            nonlocal overflow_total, replayed
            completed = (ckpt.completed_clusters
                         if ckpt.completed_clusters is not None
                         else np.zeros(n, dtype=bool))
            hist = np.asarray(jax.device_get(local_k), np.float64).copy()
            hist[:, completed] = 0.0
            key_dist = hist.sum(axis=0)
            remaining = max(1, waves_total - cursor)
            replan = self._plan(hist, key_dist, k_per_shard, prev=None,
                                num_chunks=remaining)
            masked = self._mask_completed(intermediate, completed)
            out, counts, overflow, _wire = self._execute(masked, replan)
            o, ct = _merge_host(out, counts)
            _absorb(o, ct)
            overflow_total += int(
                np.asarray(jax.device_get(overflow)).reshape(-1)[0]
            )
            replayed = (replan.waves.num_chunks
                        if cfg.pipelined and replan.waves.num_chunks > 1 else 1)
            self.last_replay_plan = replan

        def _due(c: int):
            return [s for s, w in self._kill_at_wave.items() if w <= c]

        killed = False
        if not pipelined:
            due = _due(0)
            if due:
                _fire(due)
                _replay(0)
                killed = True
            else:
                out, counts, overflow, _wire = self._execute(
                    intermediate, planned)
                o, ct = _merge_host(out, counts)
                _absorb(o, ct)
                overflow_total += int(
                    np.asarray(jax.device_get(overflow)).reshape(-1)[0]
                )
                ckpt.mark_wave(np.arange(n), {}, n)
        else:
            assignment = jnp.asarray(planned.schedule.assignment, jnp.int32)
            rank_of_cluster = jnp.asarray(planned.waves.rank_of_cluster)
            chunk_of_cluster = jnp.asarray(planned.waves.chunk_of_cluster)
            chunk_caps = tuple(planned.chunk_caps)
            static = (m, n, planned.capacity, chunk_caps, cfg.reduce_op,
                      cfg.pipelined, num_chunks, cfg.use_kernels)
            reduce_op, use_kernel = cfg.reduce_op, cfg.use_kernels
            group_caps = np.repeat(np.asarray(chunk_caps, np.int64), m)
            total = int(group_caps.sum())
            # Keep every intermediate product in the caller-side vmap
            # convention (leading (m,) axis): vmap stacks per-shard
            # outputs itself; shard_map concatenates flat, so each shard
            # re-adds a leading 1 — then re-entry through ``_run_sharded``
            # flattens it back correctly on either backend.
            if self.backend == "vmap":
                lead = lambda a: a          # noqa: E731
            else:
                lead = lambda a: a[None]    # noqa: E731

            def spill_fn(inter, assignment, chunk_of_cluster):
                """Shard-local ragged spill — all wave slabs in one sort."""
                key_hashes, values, valid = inter
                cluster_ids = jnp.abs(key_hashes) % n
                chunk_of_pair = chunk_of_cluster[cluster_ids]
                dest = assignment[cluster_ids]
                group = jnp.where(
                    valid, chunk_of_pair * m + dest, num_chunks * m
                ).astype(jnp.int32)
                fv, fc, fm, overflow = _ragged_counting_sort_to_buckets(
                    group, values, cluster_ids.astype(jnp.int32), group_caps,
                    total,
                )
                return (lead(fv), lead(fc), lead(fm),
                        jax.lax.psum(overflow, AXIS)[None])

            fv, fc, fm, overflow = self._run_sharded(
                spill_fn, ((0, 0, 0), None, None), (0, 0, 0, 0),
                intermediate, assignment, chunk_of_cluster,
                cache_key=("c_spill", static))
            overflow_total += int(
                np.asarray(jax.device_get(overflow)).reshape(-1)[0]
            )
            v_dim = int(fv.shape[-1])
            offsets = np.concatenate([[0], np.cumsum(
                [m * cc for cc in chunk_caps])]).astype(int)
            for c in range(num_chunks):
                due = _due(c)
                if due:
                    _fire(due)
                    _replay(c)
                    killed = True
                    break
                off, cap = int(offsets[c]), chunk_caps[c]

                def copy_fn(fv, fc, fm, _off=off, _cap=cap):
                    """The "copy" of wave c: slice its slab, all-to-all it."""
                    rv, rc, rm = _fenced_wave_copy(fv, fc, fm, _off, _cap, m,
                                                   v_dim)
                    return lead(rv), lead(rc), lead(rm)

                def run_fn(rv, rc, rm, rank_of_cluster):
                    """The "sort"+"run" of wave c — shard-local reduce."""
                    return _fenced_wave_run(rv, rc, rm, rank_of_cluster, n,
                                            reduce_op, use_kernel)

                rv, rc, rm = self._run_sharded(
                    copy_fn, (0, 0, 0), (0, 0, 0), fv, fc, fm,
                    cache_key=("c_wcopy", static, c))
                out_c, cnt_c = self._run_sharded(
                    run_fn, (0, 0, 0, None), (0, 0),
                    rv, rc, rm, rank_of_cluster,
                    cache_key=("c_wrun", static, cap))
                o, ct = _merge_host(out_c, cnt_c)
                _absorb(o, ct)
                members = planned.waves.chunk_members(c)
                ckpt.mark_wave(
                    members, {int(j): o[j] for j in members}, n
                )

        # Kills armed past the last wave fire between batches: the slot is
        # dead for the NEXT plan, nothing of THIS batch needs replay.
        if self._kill_at_wave:
            _fire(list(self._kill_at_wave))

        self.last_checkpoint = ckpt
        self.last_checkpoint_wave = ckpt.wave_cursor
        self.last_replayed_waves = replayed
        return vals, cnts, overflow_total

    def _combine(self, pairs):
        """Hadoop's combiner over the map's pairs: ``(combined, local_k)``.

        Dispatches ``jit_combine`` at the per-shard capacity C and pulls the
        shards' counts of combined pairs, which are exact whatever C is. A
        shard with more than C re-runs the combiner at the next power of
        two at or above the largest count, which holds every shard's pairs,
        so no pair is dropped; C keeps that value. C starts at 1 and only
        grows, so the combiner's and phase B's executables settle within
        the first batches. (K itself is no start: phase B's buffers for K
        combined pairs of two value columns take the whole of a 16 GB chip.)
        """
        k = int(pairs[0].shape[-1])
        cap = self._combine_cap or 1
        with TraceAnnotation(spans.COMBINE) as span:
            combined, local_k, runs = self._run_combine(pairs, cap)
            runs = _pull(spans.STATS_PULL, runs).reshape(-1)
            most = int(runs.max())
            if most > cap:
                self.capacity_fallbacks += 1
                cap = min(k, 1 << (most - 1).bit_length())
                combined, local_k, _ = self._run_combine(pairs, cap)
            span.set_metadata(capacity=cap, combined_pairs=int(runs.sum()))
        self._combine_cap = cap
        return combined, local_k

    def _run_combine(self, pairs, capacity: int):
        """The ``jit_combine`` executable at per-shard ``capacity``."""
        cfg = self.cfg
        # The combined pairs keep the caller-side layout, a leading (m,)
        # axis, on either backend (shard_map concatenates flat, so each
        # shard re-adds a leading 1), as phase B expects of its input.
        lead = (lambda a: a) if self.backend == "vmap" else (lambda a: a[None])

        def combine(pairs):
            """Per-shard combiner and statistics (see _combine_shard)."""
            combined, state, runs = _combine_shard(
                pairs, capacity, cfg.num_clusters, self._stats.collect, cfg.reduce_op)
            return tuple(lead(a) for a in combined), state, runs

        return self._run_sharded(combine, ((0, 0, 0),), ((0, 0, 0), 0, 0), pairs,
                                 cache_key=("combine", capacity))

    def _decide(self, cache: sc.ScheduleCache, local_k):
        """Reuse or replan this batch: the drift check, then the cost gate.

        Returns ``(decision, benefit, local_hist)``; the last two are set
        only when the cost gate ran (it pulls the statistics to the host).
        """
        cfg = self.cfg
        provider = self._stats
        benefit = local_hist = None
        decision = cache.decide(local_k, fresh_speeds=self.current_speeds())
        if (decision.action == "replan" and decision.reason == "drift"
                and cache.policy.cost_gate and cfg.scheduler == "auto"):
            # The distribution drifted — but is a fresh plan actually
            # better than the stale schedule's expected imbalance, net
            # of the scheduler's own cost? (simulator cost model)
            from repro.core import simulator as sim

            local_hist = _pull(spans.STATS_PULL, local_k)
            # The cost model wants dense per-shard counts; under a
            # sketch these are the overestimate-only densifications.
            benefit = sim.estimate_replan_benefit(
                provider.key_dist(local_hist), cache.snapshot.schedule,
                eta=cfg.eta,
                pipelined=cfg.pipelined and cfg.pipeline_chunks > 1,
                speeds=self.current_speeds(),
                # Gate on MEASURED shuffle cost: the wire rate of the
                # last accounted batch and the per-slot locality both
                # shrink the copy term the model weighs replanning by.
                bytes_per_pair=self._wire_rate(),
                local_hist=provider.to_dense(local_hist),
            )
            if benefit["benefit"] <= 0.0:
                # Not worth it: keep the plan, re-anchor the drift
                # baseline so the question isn't re-asked every batch.
                cache.snapshot.refresh_baseline(
                    local_hist, key_dist=provider.key_dist(local_hist))
                decision = sc.ReuseDecision(
                    "reuse", "cost_gate", decision.drift,
                    speed_drift=decision.speed_drift,
                )
        return decision, benefit, local_hist

    def _plan_span(self, key_dist, k_per_shard: int) -> TraceAnnotation:
        """The ``os4m.plan`` span, with the valid and the input pairs."""
        return TraceAnnotation(spans.PLAN, valid_pairs=int(np.sum(key_dist)),
                               input_pairs=self.cfg.num_slots * int(k_per_shard))

    def _bucket_rows(self, planned: sc.CachedSchedule) -> int:
        """Rows one shard's spill writes under ``planned``: ``m`` buckets of
        each chunk's capacity, or of the one capacity when not pipelined."""
        m = self.cfg.num_slots
        if self.cfg.pipelined and planned.waves.num_chunks > 1:
            return m * sum(planned.chunk_caps)
        return m * planned.capacity

    def _phase_b_span(self, planned: sc.CachedSchedule) -> TraceAnnotation:
        """The ``os4m.phase_b`` span, with the rows a shard's spill writes."""
        return TraceAnnotation(spans.PHASE_B,
                               bucket_rows=self._bucket_rows(planned))

    # -- public API ----------------------------------------------------------

    def run(self, inputs) -> JobResult:
        """Execute the full job: phase A → {replay cached | host plan} → phase B.

        Without a reuse policy this is the paper's per-job workflow (host
        schedule every run). With ``cfg.reuse`` set, the per-shard
        histograms feed an on-device drift check first; a reused batch
        skips the statistics pull and the scheduler entirely and replays
        the cached plan, which by construction hits the phase-B jit cache.

        Each step records a host span for a profiler trace
        (:mod:`repro.core.spans`), all inside one ``os4m.batch``.
        """
        self.batches_run += 1
        with TraceAnnotation(spans.BATCH, batch=self.batches_run - 1):
            return self._run_batch(inputs)

    def _run_batch(self, inputs) -> JobResult:
        """The body of :meth:`run`, one batch."""
        cfg = self.cfg
        m, n = cfg.num_slots, cfg.num_clusters

        # ---- Phase A: map + statistics (all Maps finish before any Reduce).
        def phase_a(shard_input):
            """Per-shard map + local K^(i) histogram (phase A body)."""
            return self._phase_a(shard_input)

        if cfg.combine:
            pairs = self._run_sharded(phase_a, (0,), (0, 0, 0), inputs,
                                      cache_key=("a",))
            intermediate, local_k = self._combine(pairs)
        else:
            intermediate, local_k = self._run_sharded(
                phase_a, (0,), ((0, 0, 0), 0), inputs, cache_key=("a",)
            )
        # Per-shard provider state, still on device: (m, S) for vmap, a
        # flat global axis under shard_map — reshape covers both. S is the
        # provider's state size (n exact, depth*width sketch); streaming
        # prefix mode doubles it (columns [0:S) full batch, [S:2S) the
        # prefix sketch — see _phase_a_shard).
        provider = self._stats
        local_k = local_k.reshape(m, -1)
        prefix_k = None
        if cfg.stream_prefix is not None:
            s = provider.state_size
            prefix_k = local_k[:, s:]
            local_k = local_k[:, :s]
        k_per_shard = int(intermediate[0].shape[-1])
        cache = self.schedule_cache

        # ---- Reuse decision (on-device drift; only a scalar reaches host).
        decision = benefit = local_hist = None
        if cache is not None:
            with TraceAnnotation(spans.DECIDE):
                decision, benefit, local_hist = self._decide(cache, local_k)

        # ---- Host plan (cold / drift / max_age) or cached replay.
        if decision is not None and decision.action == "reuse":
            planned = cache.snapshot
            # Fresh measured K for the result (an (S,) pull — the full
            # (m, S) statistics and the scheduler both stay off this path;
            # a cost-gated batch already pulled the statistics, reuse
            # them). Under a sketch the provider turns the pulled global
            # counters into the (n,) overestimate.
            key_dist = provider.key_dist(
                local_hist if local_hist is not None
                else _pull(spans.STATS_PULL, jnp.sum(local_k, axis=0)))
        else:
            local_hist = _pull(spans.STATS_PULL, local_k)
            key_dist = provider.key_dist(local_hist)
            prev = cache.snapshot if cache is not None else None
            if prefix_k is not None:
                prefix_hist = _pull(spans.STATS_PULL, prefix_k)
                with self._plan_span(key_dist, k_per_shard):
                    planned = self._plan_prefixed(
                        local_hist, prefix_hist, k_per_shard, prev=prev)
            else:
                with self._plan_span(key_dist, k_per_shard):
                    planned = self._plan(local_hist, key_dist, k_per_shard,
                                         prev=prev)
            if cache is not None:
                cache.store(planned)

        # Measured mode (shard_map + estimation): the overlapped pipeline
        # with on-device wave tick stamps (host-fenced clocks only as the
        # no-tick-source fallback); otherwise the untimed fused program.
        # Checkpointing mode (elastic mesh) walks the waves fenced, with
        # host checkpoints, and returns host-merged results directly.
        measured = self._measure_timings and self.speed_estimator is not None
        checkpointing = cfg.checkpoint_waves and not measured
        timings: Optional[mt.WaveTimings] = None
        values = counts_np = None
        wire_vec = None

        def execute(planned):
            """Phase B under ``planned``: device results and the overflow count."""
            timings = None
            with self._phase_b_span(planned):
                if measured:
                    out, counts, overflow, wire_vec, timings = (
                        self._execute_measured(intermediate, planned))
                else:
                    out, counts, overflow, wire_vec = self._execute(
                        intermediate, planned)
            overflow_total = int(_pull(spans.OUTPUT_PULL, overflow).reshape(-1)[0])
            return out, counts, wire_vec, timings, overflow_total

        if checkpointing:
            self.last_replay_plan = None
            with self._phase_b_span(planned):
                values, counts_np, overflow_total = self._execute_checkpointed(
                    intermediate, planned, local_k, k_per_shard)
        else:
            out, counts, wire_vec, timings, overflow_total = execute(planned)

        # ---- Capacity fallback: a replayed plan's statistics-sized
        # buffers were too small for this batch (drift under the threshold
        # can still concentrate load). Overflow counting is exact, so
        # replan from the fresh statistics and re-execute — outputs are
        # always the no-drop ones. This doubles as the sketch path's
        # exactness escape hatch: a fresh pure-sketch plan's capacities
        # are overestimate-only, so the re-executed batch cannot
        # estimate-overflow again.
        if decision is not None and decision.action == "reuse" and overflow_total > 0:
            cache.capacity_fallbacks += 1
            local_hist = _pull(spans.STATS_PULL, local_k)
            key_dist = provider.key_dist(local_hist)
            with self._plan_span(key_dist, k_per_shard):
                planned = self._plan(local_hist, key_dist, k_per_shard,
                                     prev=cache.snapshot)
            cache.store(planned)
            decision = sc.ReuseDecision("replan", "overflow", decision.drift,
                                        speed_drift=decision.speed_drift)
            if checkpointing:
                # Mid-batch kills already fired during the first walk, so
                # this re-execution is a clean checkpointed pass.
                with self._phase_b_span(planned):
                    values, counts_np, overflow_total = self._execute_checkpointed(
                        intermediate, planned, local_k, k_per_shard)
            else:
                out, counts, wire_vec, timings, overflow_total = execute(planned)

        # ---- Estimate-commitment fallback (streaming prefix): wave 1's
        # committed cap under-provisioned this batch. Not a replan — the
        # schedule and wave membership are kept (capacities only gate
        # buffer sizing), every cap escalates to the safe bound, and the
        # batch re-executes drop-free (see _escalate_caps).
        if planned.caps_estimated and overflow_total > 0:
            self.capacity_fallbacks += 1
            with self._plan_span(key_dist, k_per_shard):
                planned = self._escalate_caps(planned)
            if cache is not None:
                cache.store(planned)
            out, counts, wire_vec, timings, overflow_total = execute(planned)

        keys = None
        if not checkpointing:
            pulled = sum(x.nbytes for x in jax.tree.leaves((out, counts, wire_vec)))
            with TraceAnnotation(spans.OUTPUT_PULL, bytes=pulled):
                out, counts, wire_vec = jax.device_get((out, counts, wire_vec))
        with TraceAnnotation(spans.MERGE):
            if cache is not None:
                cache.record(decision)

            # ---- Close the Q||C_max feedback loop: this batch's phase-B wave
            # timings (measured per-device clocks on a shard_map mesh,
            # synthetic on the single-device vmap backend) update the speed
            # estimate the *next* plan will schedule under.
            self.last_wave_timings = timings
            if timings is not None:
                self._observe_measured(timings, planned)
            else:
                self._observe_wave_timings(planned, key_dist)

            # Each cluster is reduced on exactly one slot; merge = sum over
            # slots (the checkpointed executor already merged wave-by-wave).
            if cfg.keyed_output:
                # Each key is reduced on one slot: its rows are those with
                # a count, in slot order.
                counts_np = np.asarray(counts).reshape(-1)
                has = counts_np > 0
                keys = np.asarray(out[0]).reshape(-1)[has]
                values = np.asarray(out[1]).reshape(counts_np.size, -1)[has]
                counts_np = counts_np[has]
            elif not checkpointing:
                values = np.asarray(out).reshape(m, n, -1).sum(axis=0)
                counts_np = np.asarray(counts).reshape(m, n).sum(axis=0)

            # ---- Measured shuffle volume: device row counters → bytes with
            # static row sizes. Feeds the result AND the next plan's cost
            # model (``_wire_rate``), so the simulator charges the copy phase
            # what the wire actually cost, not the modeled 64 B/pair.
            shuffle_bytes = shuffle_rows = shuffle_pairs = None
            if wire_vec is not None:
                rows = float(np.asarray(wire_vec, np.float64).reshape(-1)[0])
                shuffle_bytes, shuffle_rows, shuffle_pairs = (
                    self._wire_accounting(rows, intermediate[1]))
                self._last_wire = (shuffle_bytes, shuffle_pairs)

            # One Map operation per shard (paper footnote 1: Map task == operation).
            net = clustering.network_cost_bytes(
                num_map_ops=m, num_clusters=n, num_tasktrackers=m, num_reduce_tasks=m
            )
            return JobResult(
                values=values,
                counts=counts_np,
                schedule=planned.schedule,
                key_distribution=key_dist,
                overflow=overflow_total,
                network_cost=net,
                strategy=planned.strategy,
                strategy_costs=planned.strategy_costs,
                reused=bool(decision is not None and decision.action == "reuse"),
                plan_reason=decision.reason if decision is not None else "",
                drift=decision.drift if decision is not None else None,
                replan_benefit=benefit,
                slot_speeds=planned.schedule.slot_speeds,
                speed_drift=(decision.speed_drift if decision is not None else None),
                shuffle_bytes=shuffle_bytes,
                shuffle_rows=shuffle_rows,
                shuffle_pairs=shuffle_pairs,
                keys=keys,
            )
