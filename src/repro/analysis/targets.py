"""Real analyzer targets: traced phase-B programs and host plan objects.

The analyzer never checks toy stand-ins — these builders trace the
engine's *actual* per-shard phase-B bodies (`repro.core.mapreduce.
_phase_b_shard` and friends) in every execution variant the repo ships:

* sequential (Hadoop-style single-shot) and pipelined (§4.4 chunk walk);
* the Pallas fused-kernel path (``use_kernels=True``);
* the measured path (wave-timer stamps threaded through the same body,
  callback backend) in both sequential and pipelined form;
* the fenced per-wave copy/run programs the measured-fallback and
  checkpointed executors dispatch (module-level bodies in
  ``core.mapreduce``, traced verbatim);
* a whole shard_map-wrapped phase B when the host exposes enough
  devices (the exact program the shard_map backend jits — the vmap
  backend maps the identical per-shard body, which the other targets
  trace directly).

Tracing uses :func:`repro.analysis.jaxpr_graph.trace_sharded` — the
named-axis environment keeps ``all_to_all``/``psum`` first-class, so the
dependency structure the checkers certify is the one XLA schedules.

Plan targets come from the same host planner the job runs
(:meth:`MapReduceJob._plan`) on synthetic-but-realistic statistics,
including a straggler (Q||C_max) plan, a dead-slot plan, and the
sketch-statistics plans (pure count-min and the
streaming-prefix two-step via :meth:`MapReduceJob._plan_prefixed`) whose
snapshots exercise the analyzer's overestimate-aware capacity rules.
A phase-A sketch target traces the provider collection step
(``_phase_a_shard`` with ``SketchStats.collect``) — it carries no
collectives, callbacks, or wire sorts, and the checkers certify that.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro import compat
from repro.analysis import jaxpr_graph as jg
from repro.core import mapreduce as mr
from repro.core import schedule_cache as sc

# One small-but-structured geometry shared by every traced variant:
# m slots, n operation clusters, k pairs per shard, v-dim values,
# C pipeline chunks with per-chunk send caps.
M, N_CLUSTERS, K_PAIRS, V_DIM, CHUNKS = 4, 8, 32, 3, 4
CHUNK_CAPS: Tuple[int, ...] = (16, 16, 16, 16)
CAPACITY = 32


@dataclasses.dataclass
class TracedTarget:
    """One traced phase-B program + the flags the checkers dispatch on."""

    name: str
    graph: jg.EqnGraph
    timed: bool = False
    pipelined: bool = False


def _shard_args():
    """ShapeDtypeStruct arguments of one per-shard phase-B call."""
    inter = (
        jax.ShapeDtypeStruct((K_PAIRS,), jnp.int32),
        jax.ShapeDtypeStruct((K_PAIRS, V_DIM), jnp.float32),
        jax.ShapeDtypeStruct((K_PAIRS,), jnp.bool_),
    )
    vec = jax.ShapeDtypeStruct((N_CLUSTERS,), jnp.int32)
    return inter, vec, vec, vec


def _static(pipelined: bool, use_kernel: bool = False) -> Tuple:
    """The engine's ``cfg_static`` tuple for one variant."""
    chunks = CHUNKS if pipelined else 1
    caps = CHUNK_CAPS if pipelined else (CAPACITY,)
    return (M, N_CLUSTERS, CAPACITY, caps, "sum", pipelined, chunks,
            use_kernel)


def _trace_phase_b(static, timed: bool) -> jg.EqnGraph:
    args = _shard_args()

    if timed:
        from repro.kernels.wave_timer import ops as wt_ops

        def body(inter, a, r, c):
            return mr._phase_b_shard_timed(inter, a, r, c, static)

        # Pin the CPU callback backend so the traced stamps are the
        # io_callback path the analyzer's stamp rules certify.
        with wt_ops.force_backend("callback"):
            closed = jg.trace_sharded(body, args, mr.AXIS, M)
    else:
        def body(inter, a, r, c):
            return mr._phase_b_shard(inter, a, r, c, static)

        closed = jg.trace_sharded(body, args, mr.AXIS, M)
    return jg.EqnGraph(closed)


def _trace_fenced_wave() -> List[TracedTarget]:
    """The checkpointed/measured-fallback per-wave copy + run programs."""
    total = M * sum(CHUNK_CAPS)
    fv = jax.ShapeDtypeStruct((total, V_DIM), jnp.float32)
    fc = jax.ShapeDtypeStruct((total,), jnp.int32)
    fm = jax.ShapeDtypeStruct((total,), jnp.bool_)
    cap = CHUNK_CAPS[1]
    off = M * CHUNK_CAPS[0]

    def copy_body(fv, fc, fm):
        return mr._fenced_wave_copy(fv, fc, fm, off, cap, M, V_DIM)

    rv = jax.ShapeDtypeStruct((M * cap, V_DIM), jnp.float32)
    rc = jax.ShapeDtypeStruct((M * cap,), jnp.int32)
    rm = jax.ShapeDtypeStruct((M * cap,), jnp.bool_)
    rank = jax.ShapeDtypeStruct((N_CLUSTERS,), jnp.int32)

    def run_body(rv, rc, rm, rank):
        return mr._fenced_wave_run(rv, rc, rm, rank, N_CLUSTERS, "sum", False)

    copy_g = jg.EqnGraph(jg.trace_sharded(copy_body, (fv, fc, fm), mr.AXIS, M))
    run_g = jg.EqnGraph(jg.trace_sharded(run_body, (rv, rc, rm, rank),
                                         mr.AXIS, M))
    return [
        TracedTarget("checkpointed-wave-copy", copy_g, pipelined=True),
        TracedTarget("checkpointed-wave-run", run_g, pipelined=True),
    ]


def _trace_shard_map() -> Optional[TracedTarget]:
    """Whole shard_map-wrapped phase B (needs >= M devices on the host)."""
    if len(jax.devices()) < M:
        return None
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:M]), (mr.AXIS,))
    static = _static(pipelined=True)

    def body(inter, a, r, c):
        return mr._phase_b_shard(inter, a, r, c, static)

    sharded = compat.shard_map(
        body, mesh=mesh,
        in_specs=((P(mr.AXIS), P(mr.AXIS), P(mr.AXIS)), P(), P(), P()),
        out_specs=(P(mr.AXIS), P(mr.AXIS), P(mr.AXIS), P(mr.AXIS)),
    )
    inter = (
        jax.ShapeDtypeStruct((M * K_PAIRS,), jnp.int32),
        jax.ShapeDtypeStruct((M * K_PAIRS, V_DIM), jnp.float32),
        jax.ShapeDtypeStruct((M * K_PAIRS,), jnp.bool_),
    )
    vec = jax.ShapeDtypeStruct((N_CLUSTERS,), jnp.int32)
    closed = jax.make_jaxpr(sharded)(inter, vec, vec, vec)
    return TracedTarget("shard_map-pipelined", jg.EqnGraph(closed),
                        pipelined=True)


def _trace_phase_a_sketch() -> TracedTarget:
    """Phase A with the sketch provider: map + count-min collection.

    The traced program is the real ``_phase_a_shard`` body under
    ``SketchStats.collect`` (jnp fallback — the kernel path is certified
    by its own ref-oracle test). No all_to_all, no callbacks, no wire
    sorts: the overlap and determinism checkers verify that emptiness.
    """
    from repro.core import stats_provider as sp

    provider = sp.SketchStats(N_CLUSTERS, width=64, depth=3)

    def body(shard_input):
        return mr._phase_a_shard(
            shard_input, map_fn=lambda s: s, num_clusters=N_CLUSTERS,
            stats_fn=provider.collect)

    args = ((
        jax.ShapeDtypeStruct((K_PAIRS,), jnp.int32),
        jax.ShapeDtypeStruct((K_PAIRS, V_DIM), jnp.float32),
        jax.ShapeDtypeStruct((K_PAIRS,), jnp.bool_),
    ),)
    closed = jg.trace_sharded(body, args, mr.AXIS, M)
    return TracedTarget("phase-a-sketch", jg.EqnGraph(closed))


def phase_b_targets() -> List[TracedTarget]:
    """Every real phase-B variant, traced and graphed."""
    targets = [
        TracedTarget("sequential",
                     _trace_phase_b(_static(False), timed=False)),
        TracedTarget("pipelined",
                     _trace_phase_b(_static(True), timed=False),
                     pipelined=True),
        TracedTarget("pipelined-kernels",
                     _trace_phase_b(_static(True, use_kernel=True),
                                    timed=False),
                     pipelined=True),
        TracedTarget("timed-sequential",
                     _trace_phase_b(_static(False), timed=True), timed=True),
        TracedTarget("timed-pipelined",
                     _trace_phase_b(_static(True), timed=True),
                     timed=True, pipelined=True),
    ]
    targets.extend(_trace_fenced_wave())
    targets.append(_trace_phase_a_sketch())
    sm = _trace_shard_map()
    if sm is not None:
        targets.append(sm)
    return targets


# ---------------------------------------------------------------------------
# Plan targets (host objects, produced by the job's real planner).
# ---------------------------------------------------------------------------


def _plan_for(cfg: mr.MapReduceConfig, seed: int) -> sc.CachedSchedule:
    job = mr.MapReduceJob(lambda s: s, cfg)
    rng = np.random.default_rng(seed)
    hist = rng.integers(1, 64, size=(cfg.num_slots, cfg.num_clusters))
    hist = hist.astype(np.float64)
    k_per_shard = int(np.ceil(hist.sum(axis=1).max()))
    if cfg.stats == "sketch":
        # The planner consumes provider state — sketch the synthetic
        # histogram (count-min is linear, so from_dense == collect).
        state = job._stats.from_dense(hist)
        if cfg.stream_prefix is not None:
            # Prefix state: a thinner sample of the same distribution,
            # as the first stream_prefix of pairs would produce.
            noise = rng.uniform(0.5, 1.5, size=hist.shape)
            prefix = np.floor(hist * cfg.stream_prefix * noise)
            return job._plan_prefixed(
                state, job._stats.from_dense(prefix), k_per_shard)
        return job._plan(state, None, k_per_shard)
    return job._plan(hist, hist.sum(axis=0), k_per_shard)


def plan_targets() -> List[Tuple[str, sc.CachedSchedule]]:
    """Real planner outputs across scheduler / speed / statistics variants."""
    out: List[Tuple[str, sc.CachedSchedule]] = []
    out.append(("lpt-uniform", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="lpt"),
        seed=0)))
    out.append(("os4m-pipelined", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=12, scheduler="os4m",
                           pipeline_chunks=3), seed=1)))
    out.append(("lpt-straggler", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="lpt",
                           speeds=(1.0, 0.5, 1.0, 2.0)), seed=2)))
    out.append(("lpt-dead-slot", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="lpt",
                           speeds=(1.0, 1.0, 0.0, 1.0)), seed=3)))
    out.append(("sketch-os4m", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="os4m",
                           stats="sketch", sketch_width=64, sketch_depth=3),
        seed=5)))
    out.append(("sketch-lpt", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="lpt",
                           stats="sketch", sketch_width=32, sketch_depth=4),
        seed=6)))
    out.append(("sketch-prefix", _plan_for(
        mr.MapReduceConfig(num_slots=4, num_clusters=16, scheduler="lpt",
                           stats="sketch", sketch_width=64, sketch_depth=3,
                           stream_prefix=0.25), seed=7)))
    return out
