"""Run every benchmark; print ``name,key,value`` CSV.

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig14]
       PYTHONPATH=src python -m benchmarks.run --smoke [--out BENCH_schedulers.json]
       PYTHONPATH=src python -m benchmarks.run --smoke-reuse [--out BENCH_schedule_reuse.json]
       PYTHONPATH=src python -m benchmarks.run --smoke-straggler [--out BENCH_stragglers.json]

``--smoke`` is the CI perf-trajectory gate: a small fixed-seed config that
measures (a) the makespan ratio max/ideal of every scheduling strategy and
(b) wall time of the pipelined vs sequential shuffle→reduce engine, and
writes the results to a JSON file benchers can diff across commits.

``--smoke-reuse`` measures the schedule-reuse steady state: one reused-plan
job vs an always-replan job over a stationary batch stream, then under an
injected distribution shift — replan rate, per-batch wall time, stale-vs-
replanned imbalance, and bit-identity of every output.

``--smoke-straggler`` measures the Q||C_max payoff: with one Reduce slot
running at 0.5x (a 2x-slow straggler) on zipf keys, how much estimated
Reduce makespan does speed-*aware* scheduling cut vs speed-*oblivious*
schedules of the same strategy, and does a job detect a mid-run slowdown
online (replan count) while keeping outputs bit-identical.

``--smoke-straggler --measured`` runs the online half on an
8-virtual-device shard_map mesh with **measured** per-device phase-B wave
clocks driving the estimator (the synthetic timing model never runs —
``--slot-slowdown``-style injection scales the measured seconds instead,
standing in for genuinely slow hardware). Same gates; writes
``BENCH_stragglers_measured.json``, and additionally runs the
**overlap-recovery** bench (``BENCH_overlap_measured.json``): the
tick-instrumented measured executor runs the same overlapped pipeline
as unmeasured phase B, so its wall clock must stay within
``OVERLAP_THRESHOLD``× of unmeasured mode — the fenced host-timed
fallback is recorded for context. Needs >= 8 devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

``--smoke-sketch`` measures the pluggable-statistics plan path
(docs/STATISTICS.md): wall time of phase A statistics + host pull +
``_plan`` with exact histograms vs a count-min sketch at a large cluster
count (the sketch pulls O(depth × width) cells instead of O(n) columns),
the overflow-replan (escape hatch) rate on a benign and on an engineered
adversarial streaming-prefix workload, and bit-identity of sketch and
prefix outputs against exact statistics; writes ``BENCH_sketch.json``
for the ``sketch`` gate.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

# Overlap-recovery gate (``--smoke-straggler --measured``): measured-mode
# phase B may cost at most this factor of unmeasured phase B (medians),
# plus an absolute allowance. On CI containers the tick source is the
# CPU *callback* fallback, which pays ~0.5-1.5 ms of host-callback (GIL)
# latency per wave-boundary stamp — slots × (waves+1) ≈ 32 stamps/batch
# here — on 2-core runners; a real device counter pays none of it. The
# absolute slack covers that tax (and the wild phase-B median swings of
# a 2-core box, where 8 virtual devices timeshare the pool); on
# many-core hardware the *ratio* is the meaningful signal. The fenced
# executor's full dispatch+fence per wave is reported alongside for
# context.
OVERLAP_THRESHOLD = 1.6
OVERLAP_ABS_SLACK_S = 0.05


def bench_smoke(out_path: str) -> dict:
    """Fixed-seed scheduler + engine smoke; writes ``out_path`` JSON."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core import scheduler as S
    from repro.core import simulator as sim
    from repro.core.mapreduce import MapReduceConfig, MapReduceJob

    rng = np.random.default_rng(0)

    # --- (a) schedule quality: max/ideal per strategy on a skewed K.
    loads = rng.zipf(1.3, 480).clip(1, 20_000).astype(float)
    m = 30
    schedulers = {}
    for name in S.AUTO_CANDIDATES:
        fn = S.get_scheduler(name)
        t0 = time.perf_counter()
        sched = fn(loads, m, keys=np.arange(loads.size)) if name == "hash" \
            else fn(loads, m)
        schedulers[name] = {
            "balance_ratio": float(sched.balance_ratio),
            "host_seconds": time.perf_counter() - t0,
        }
    auto_choice, _, auto_costs = sim.pick_strategy(loads, m)

    # --- (b) engine wall time: pipelined vs sequential phase B on the
    # same job (vmap backend; integer-valued floats so the comparison is
    # bit-exact). First call per config includes compilation; measure the
    # steady state with a warmup run.
    slots, K, n = 4, 16384, 96
    keys = (rng.zipf(1.25, size=(slots, K)) % 4099).astype(np.int32)
    vals = np.ones((slots, K, 8), np.float32)
    valid = np.ones((slots, K), bool)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))

    def make_job(pipelined: bool):
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="bss",
                            pipelined=pipelined, pipeline_chunks=4),
            backend="vmap")

    jobs = {False: make_job(False), True: make_job(True)}
    results = {p: jobs[p].run(batch) for p in jobs}   # warmup (compile)
    walls = {False: [], True: []}
    for _ in range(12):                # interleaved to de-bias load drift
        for p in (False, True):
            t0 = time.perf_counter()
            results[p] = jobs[p].run(batch)
            walls[p].append(time.perf_counter() - t0)
    t_seq = statistics.median(walls[False])
    t_pipe = statistics.median(walls[True])
    res_seq, res_pipe = results[False], results[True]

    report = {
        "config": {"loads": "zipf(1.3) n=480 m=30",
                   "engine": f"slots={slots} K={K} clusters={n} chunks=4"},
        "schedulers": schedulers,
        "auto_choice": auto_choice,
        "auto_costs": {k: float(v) for k, v in auto_costs.items()},
        "engine": {
            "sequential_seconds": t_seq,
            "pipelined_seconds": t_pipe,
            "speedup": t_seq / max(t_pipe, 1e-12),
            "bit_identical": bool(
                np.array_equal(res_seq.values, res_pipe.values)
                and np.array_equal(res_seq.counts, res_pipe.counts)),
        },
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_sketch(out_path: str) -> dict:
    """Sketch-vs-exact plan path + escape-hatch rate; writes JSON.

    Fixed seeds, vmap backend. Three measurements:

    * **plan path** — at a large cluster count, median wall time of
      phase A statistics → host pull → ``_plan`` with exact per-cluster
      histograms vs a count-min sketch. The sketch's device→host pull
      and planner input are O(depth × width) regardless of n.
    * **escape-hatch rate** — a benign zipf stream planned from a 25%
      prefix must never trip the overflow hatch; an adversarial stream
      whose hot cluster is absent from the prefix must trip it exactly
      once per batch and still finish with zero overflow.
    * **bit-identity** — sketch and prefix-planned outputs equal the
      exact-statistics outputs on every batch above.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob

    # --- (a) plan path at large n: stats + pull + host plan.
    slots, K, n = 8, 8192, 1 << 17
    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.2, size=(slots, K)) % n).astype(np.int32)
    vals = np.ones((slots, K, 1), np.float32)
    valid = np.ones((slots, K), bool)
    batch = (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))

    def make_job(**kw):
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="lpt",
                            **kw),
            backend="vmap")

    jobs = {"exact": make_job(),
            "sketch": make_job(stats="sketch", sketch_width=1024,
                               sketch_depth=4)}

    def plan_path(job):
        inter, local_k = job._run_sharded(
            lambda s: job._phase_a(s), (0,), ((0, 0, 0), 0), batch,
            cache_key=("a",))
        state = np.asarray(jax.device_get(local_k.reshape(slots, -1)))
        return state, job._plan(state, None, int(inter[0].shape[-1]))

    states, plans = {}, {}
    for name, job in jobs.items():            # warmup (compile)
        states[name], plans[name] = plan_path(job)
    walls = {name: [] for name in jobs}
    for _ in range(9):                 # interleaved to de-bias load drift
        for name, job in jobs.items():
            t0 = time.perf_counter()
            plan_path(job)
            walls[name].append(time.perf_counter() - t0)
    med = {name: statistics.median(w) for name, w in walls.items()}

    # --- (b) + (c): hatch rate and bit-identity on streaming batches.
    slots_b, K_b, n_b, cut = 4, 1024, 64, 1024 // 4

    def stream_batch(seed: int, adversarial: bool):
        brng = np.random.default_rng(seed)
        kk = np.empty((slots_b, K_b), np.int32)
        if adversarial:
            # hot cluster 3 appears only after the planning prefix
            choices = np.array([c for c in range(n_b) if c != 3], np.int32)
            kk[:, :cut] = brng.choice(choices, size=(slots_b, cut))
            kk[:, cut:] = 3
        else:
            kk[:] = (brng.zipf(1.3, size=(slots_b, K_b)) % n_b)
        vv = brng.random((slots_b, K_b, 2)).astype(np.float32)
        return (jnp.asarray(kk), jnp.asarray(vv),
                jnp.ones((slots_b, K_b), bool))

    def make_stream_job(**kw):
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=slots_b, num_clusters=n_b,
                            scheduler="lpt", **kw),
            backend="vmap")

    scenarios = {}
    bit_identical = True
    for scen, adversarial in (("benign", False), ("adversarial", True)):
        batches = [stream_batch(10 * i + int(adversarial), adversarial)
                   for i in range(4)]
        exact_job = make_stream_job()
        prefix_job = make_stream_job(stats="sketch", sketch_width=128,
                                     sketch_depth=4, stream_prefix=0.25)
        overflow_free = True
        for b in batches:
            r_exact = exact_job.run(b)
            r_prefix = prefix_job.run(b)
            bit_identical &= bool(
                np.array_equal(np.asarray(r_exact.values),
                               np.asarray(r_prefix.values))
                and np.array_equal(np.asarray(r_exact.counts),
                                   np.asarray(r_prefix.counts)))
            overflow_free &= (int(r_prefix.overflow) == 0)
        scenarios[scen] = {
            "batches": len(batches),
            "overflow_replans": int(prefix_job.capacity_fallbacks),
            "replan_rate": prefix_job.capacity_fallbacks / len(batches),
            "overflow_free": overflow_free,
        }

    report = {
        "config": {
            "plan_path": f"slots={slots} K={K} clusters={n} lpt "
                         f"sketch=1024x4 backend=vmap",
            "stream": f"slots={slots_b} K={K_b} clusters={n_b} lpt "
                      f"sketch=128x4 stream_prefix=0.25",
        },
        "plan_path": {
            "exact_seconds": med["exact"],
            "sketch_seconds": med["sketch"],
            "speedup": med["exact"] / max(med["sketch"], 1e-12),
            "exact_pull_floats": int(states["exact"].size),
            "sketch_pull_floats": int(states["sketch"].size),
        },
        "scenarios": scenarios,
        "bit_identical": bit_identical,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_schedule_reuse(out_path: str) -> dict:
    """Schedule-reuse steady state vs always-replan; writes ``out_path`` JSON.

    Fixed seeds. A stationary phase (10 batches, one zipf law, fresh draws)
    followed by a drifted phase (4 batches, shifted zipf exponent). The
    reuse job should plan exactly once in the stationary phase, replan on
    the shift, and every output must stay bit-identical to the
    always-replan baseline job run on the same batches.
    """
    import numpy as np
    import jax.numpy as jnp

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    slots, K, n = 4, 16384, 96
    stationary, drifted = 10, 4

    def make_batch(seed: int, alpha: float):
        rng = np.random.default_rng(seed)
        keys = (rng.zipf(alpha, size=(slots, K)) % 4099).astype(np.int32)
        vals = np.ones((slots, K, 8), np.float32)
        valid = np.ones((slots, K), bool)
        return (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))

    batches = [make_batch(i, 1.25) for i in range(stationary)]
    batches += [make_batch(100 + i, 1.5) for i in range(drifted)]

    def make_job(reuse):
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="auto",
                            pipeline_chunks=4,
                            reuse=ReusePolicy(max_drift=0.15) if reuse else None),
            backend="vmap")

    reuse_job, base_job = make_job(True), make_job(False)

    rows = []
    bit_identical = True
    stale_ratio_at_shift = None
    for i, batch in enumerate(batches):
        if i == stationary:
            # Imbalance a *stale* schedule would suffer on the drifted
            # distribution: evaluate the cached assignment against the
            # fresh key histogram before either job replans.
            snap = reuse_job.schedule_cache.snapshot
            fresh_k = np.asarray(
                np.bincount(np.abs(np.asarray(batch[0]).reshape(-1)) % n,
                            minlength=n), float)
            loads = np.bincount(snap.schedule.assignment, weights=fresh_k,
                                minlength=slots)
            stale_ratio_at_shift = float(loads.max() / (fresh_k.sum() / slots))
        t0 = time.perf_counter()
        r = reuse_job.run(batch)
        t_reuse = time.perf_counter() - t0
        t0 = time.perf_counter()
        b = base_job.run(batch)
        t_base = time.perf_counter() - t0
        bit_identical &= bool(np.array_equal(r.values, b.values)
                              and np.array_equal(r.counts, b.counts))
        rows.append({
            "batch": i, "reused": r.reused, "reason": r.plan_reason,
            "drift": r.drift, "reuse_seconds": t_reuse,
            "replan_seconds": t_base,
            "balance_ratio": float(r.schedule.balance_ratio),
        })

    cache = reuse_job.schedule_cache.stats()
    # Steady state excludes the warmup (compile) batch on both sides.
    steady = [r["reuse_seconds"] for r in rows[1:stationary] if r["reused"]]
    base_steady = [r["replan_seconds"] for r in rows[1:stationary]]
    first_drift = rows[stationary]
    report = {
        "config": {
            "engine": f"slots={slots} K={K} clusters={n} chunks=4 scheduler=auto",
            "policy": "ReusePolicy(max_drift=0.15)",
            "phases": f"{stationary} stationary (zipf 1.25) + {drifted} drifted (zipf 1.5)",
        },
        "replan_rate": cache["replan_rate"],
        "stationary_replans": sum(not r["reused"] for r in rows[:stationary]),
        "drift_replans": sum(not r["reused"] for r in rows[stationary:]),
        "steady_state_seconds": statistics.median(steady) if steady else None,
        "always_replan_seconds": statistics.median(base_steady),
        "speedup": (statistics.median(base_steady) / max(statistics.median(steady), 1e-12)
                    if steady else None),
        "jit_misses": {"reuse_job": reuse_job.jit_misses,
                       "always_replan_job": base_job.jit_misses},
        "drift_at_shift": first_drift["drift"],
        "stale_balance_ratio_at_shift": stale_ratio_at_shift,
        "replanned_balance_ratio_at_shift": first_drift["balance_ratio"],
        "bit_identical": bit_identical,
        "batches": rows,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_straggler(out_path: str, measured: bool = False) -> dict:
    """Speed-aware vs speed-oblivious under a 2x-slow slot; writes JSON.

    Fixed seeds. Part (a): schedule quality — zipf cluster loads, one slot
    at 0.5x relative speed; each strategy plans once ignoring speeds
    (P||C_max, the pre-refactor behaviour) and once with the true speed
    vector (Q||C_max), and both schedules are priced by the simulator's
    flow-shop model *under the true speeds*. Part (b): the online loop —
    a reuse-policy job with speed estimation serves a stationary stream,
    slot 1 turns 2x slow mid-run (``set_slot_slowdown(1, 2.0)`` — the
    factor is a wall-clock multiplier); the job must detect it from wave
    timings, replan (``speed_drift``), and keep every output bit-identical
    to a speed-oblivious job on the same batches.

    ``measured=True`` runs part (b) on an 8-virtual-device shard_map mesh
    with measured per-device wave clocks feeding the estimator (the
    synthetic model never runs; the slowdown is injected into the
    *measured* seconds).
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core import scheduler as S
    from repro.core import simulator as sim
    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    rng = np.random.default_rng(0)

    # --- (a) estimated Reduce makespan, oblivious vs aware, one 2x-slow slot.
    loads = rng.zipf(1.3, 480).clip(1, 20_000).astype(float)
    m = 8
    speeds = np.ones(m)
    speeds[3] = 0.5
    strategies = {}
    for name in ("lpt", "multifit", "bss"):
        fn = S.get_scheduler(name)
        oblivious = fn(loads, m)                 # plans blind to the straggler
        aware = fn(loads, m, speeds=speeds)      # plans around it
        t_obl = sim.estimate_reduce_time(loads, oblivious, speeds=speeds)
        t_aware = sim.estimate_reduce_time(loads, aware, speeds=speeds)
        strategies[name] = {
            "oblivious_makespan_s": float(t_obl),
            "aware_makespan_s": float(t_aware),
            "makespan_cut": float(1.0 - t_aware / t_obl),
            "aware_finish_ratio": float(aware.finish_ratio),
        }
    hash_sched = S.schedule_hash(loads, m, keys=np.arange(loads.size),
                                 speeds=speeds)
    hash_makespan = sim.estimate_reduce_time(loads, hash_sched, speeds=speeds)

    # --- (b) mid-run slowdown: online detection, replans, bit-identity.
    if measured:
        slots, K, n = 8, 4096, 96
        total_batches, slow_at = 10, 3
        if len(jax.devices()) < slots:
            sys.exit(f"--measured needs >= {slots} devices (set XLA_FLAGS="
                     f"--xla_force_host_platform_device_count={slots})")
        from jax.sharding import Mesh

        mesh = Mesh(np.asarray(jax.devices()[:slots]), ("mr_slots",))
        backend = "shard_map"
    else:
        slots, K, n = 4, 8192, 96
        total_batches, slow_at = 8, 3
        mesh, backend = None, "vmap"

    def make_batch(seed: int):
        brng = np.random.default_rng(seed)
        keys = (brng.zipf(1.25, size=(slots, K)) % 4099).astype(np.int32)
        vals = np.ones((slots, K, 8), np.float32)
        valid = np.ones((slots, K), bool)
        return (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))

    batches = [make_batch(i) for i in range(total_batches)]
    aware_job = MapReduceJob(
        lambda s: s,
        MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="bss",
                        estimate_speeds=True,
                        reuse=ReusePolicy(max_drift=0.15,
                                          max_speed_drift=0.25)),
        backend=backend, mesh=mesh)
    oblivious_job = MapReduceJob(
        lambda s: s,
        MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="bss"),
        backend="vmap")

    rows = []
    bit_identical = True
    measured_batches = 0
    for i, batch in enumerate(batches):
        if i == slow_at:
            aware_job.set_slot_slowdown(1, 2.0)   # 2x wall-clock = 0.5x speed
        r = aware_job.run(batch)
        b = oblivious_job.run(batch)
        bit_identical &= bool(np.array_equal(np.asarray(r.values),
                                             np.asarray(b.values))
                              and np.array_equal(np.asarray(r.counts),
                                                 np.asarray(b.counts)))
        t = aware_job.last_wave_timings
        if t is not None and t.valid:
            measured_batches += 1
        rows.append({
            "batch": i, "reused": r.reused, "reason": r.plan_reason,
            "speed_drift": (None if r.speed_drift is None
                            else min(float(r.speed_drift), 1e9)),
            "slot_speeds": [round(float(s), 4) for s in r.slot_speeds],
            "wave_seconds": (None if t is None else
                             [round(float(s), 5) for s in t.slot_seconds()]),
        })
    cache = aware_job.schedule_cache.stats()

    report = {
        "config": {
            "schedule": f"zipf(1.3) n=480 m={m}, slot 3 at 0.5x speed",
            "engine": (f"slots={slots} K={K} clusters={n} bss "
                       f"backend={backend}, slot 1 -> 2x slowdown at batch "
                       f"{slow_at}"),
        },
        "timing_source": ("measured per-device wave clocks" if measured
                          else "synthetic work/slowdown model"),
        "measured_batches": measured_batches,
        "strategies": strategies,
        "hash_makespan_s": float(hash_makespan),
        "min_makespan_cut": min(s["makespan_cut"] for s in strategies.values()),
        "speed_replans": cache["speed_replans"],
        "replans": cache["replans"],
        "estimated_final_speeds": [
            round(float(s), 4) for s in aware_job.speed_estimator.speeds()
        ],
        "bit_identical": bit_identical,
        "batches": rows,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_overlap_measured(out_path: str) -> dict:
    """Overlap recovery of tick-instrumented measured mode; writes JSON.

    One plan is built once (phase A + host schedule, off the clock), then
    three phase-B executors replay it on the same intermediate data:

    * ``unmeasured``       — the fused overlapped pipeline (``_execute``);
    * ``measured_ticks``   — the SAME overlapped pipeline with on-device
      wave tick stamps + host readback of the tiny ticks buffer
      (``_execute_measured``), the ISSUE 5 tentpole path;
    * ``measured_fenced``  — the host-fenced fallback
      (``_execute_measured_fenced``), one dispatch + fence per wave —
      recorded for context, not gated (it is exactly the overlap loss
      the tick path exists to avoid).

    Gate: median ``measured_ticks`` wall ≤ ``OVERLAP_THRESHOLD`` ×
    median ``unmeasured`` + ``OVERLAP_ABS_SLACK_S``.
    """
    import numpy as np
    import jax
    import jax.numpy as jnp

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob

    slots, K, n, chunks = 8, 4096, 96, 4
    if len(jax.devices()) < slots:
        sys.exit(f"overlap bench needs >= {slots} devices (set XLA_FLAGS="
                 f"--xla_force_host_platform_device_count={slots})")
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:slots]), ("mr_slots",))
    job = MapReduceJob(
        lambda s: s,
        MapReduceConfig(num_slots=slots, num_clusters=n, scheduler="bss",
                        pipeline_chunks=chunks, estimate_speeds=True),
        backend="shard_map", mesh=mesh)

    rng = np.random.default_rng(0)
    keys = (rng.zipf(1.25, size=(slots, K)) % 4099).astype(np.int32)
    batch = (jnp.asarray(keys),
             jnp.asarray(np.ones((slots, K, 8), np.float32)),
             jnp.asarray(np.ones((slots, K), bool)))

    # Phase A + one host plan, shared by every executor (off the clock).
    inter, local_k = job._run_sharded(
        lambda s: job._phase_a(s), (0,), ((0, 0, 0), 0), batch,
        cache_key=("a",))
    local_hist = np.asarray(jax.device_get(local_k.reshape(slots, n)))
    planned = job._plan(local_hist, local_hist.sum(axis=0),
                        int(inter[0].shape[-1]))

    execs = {
        "unmeasured": lambda: job._execute(inter, planned),
        "measured_ticks": lambda: job._execute_measured(inter, planned),
        "measured_fenced": lambda: job._execute_measured_fenced(inter, planned),
    }
    for fn in execs.values():                  # warmup (compile)
        jax.block_until_ready(fn()[:3])
    walls = {name: [] for name in execs}
    for _ in range(13):                        # interleaved to de-bias drift
        for name, fn in execs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn()[:3])
            walls[name].append(time.perf_counter() - t0)
    med = {name: statistics.median(w) for name, w in walls.items()}
    ratio = med["measured_ticks"] / max(med["unmeasured"], 1e-12)
    report = {
        "config": f"slots={slots} K={K} clusters={n} chunks={chunks} "
                  f"backend=shard_map",
        "phase_b_seconds": med,
        "measured_over_unmeasured": ratio,
        "fenced_over_unmeasured":
            med["measured_fenced"] / max(med["unmeasured"], 1e-12),
        "threshold": OVERLAP_THRESHOLD,
        "abs_slack_seconds": OVERLAP_ABS_SLACK_S,
        "overlap_recovered": bool(
            med["measured_ticks"]
            <= OVERLAP_THRESHOLD * med["unmeasured"] + OVERLAP_ABS_SLACK_S),
        "walls": walls,
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_elastic(out_path: str) -> dict:
    """Elastic-mesh fault injection sweep; writes ``out_path`` JSON.

    Fixed seeds, vmap backend. Four scenarios against one uninterrupted
    8-slot baseline:

    * ``dead_at_start`` — slot 5 declared dead before the batch
      (``set_slot_slowdown(5, 0)``): outputs bit-identical, the plan
      assigns the dead slot zero load.
    * ``die_mid_wave`` — slot 3 killed just before wave 2 of 4
      (``set_slot_failure(3, at_wave=2)`` under ``checkpoint_waves``):
      outputs bit-identical, only the unfinished waves replay
      (``replayed ≤ waves − checkpoint``), and the recovery plan assigns
      the dead slot nothing.
    * ``resize_8to6`` / ``resize_6to8`` — a warm reuse-policy job is
      resized; the cached snapshot is re-projected (re-binned ``K^(i)``
      + one host re-plan), so the next batch replays it instead of going
      cold (``plan_reason`` must not be ``"cold"``), and outputs match a
      dedicated fixed-size job.
    """
    import numpy as np
    import jax.numpy as jnp

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    slots, K, n, chunks = 8, 4096, 96, 4

    def make_batch(num_slots: int, seed: int = 0):
        brng = np.random.default_rng(seed)
        keys = (brng.zipf(1.25, size=(num_slots, K)) % 4099).astype(np.int32)
        vals = np.ones((num_slots, K, 8), np.float32)
        valid = np.ones((num_slots, K), bool)
        return (jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(valid))

    def make_job(num_slots: int, checkpoint: bool = False, reuse=None):
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=num_slots, num_clusters=n,
                            scheduler="bss", pipeline_chunks=chunks,
                            checkpoint_waves=checkpoint, reuse=reuse),
            backend="vmap")

    batch8 = make_batch(slots)
    batch6 = make_batch(6)
    base8 = make_job(slots).run(batch8)
    base6 = make_job(6).run(batch6)

    def identical(a, b):
        return bool(np.array_equal(a.values, b.values)
                    and np.array_equal(a.counts, b.counts))

    # --- dead at start: plan around the corpse, outputs unchanged.
    dead_job = make_job(slots, checkpoint=True)
    dead_job.set_slot_slowdown(5, 0.0)            # 0 = dead, not slow
    r_dead = dead_job.run(batch8)
    dead_start = {
        "bit_identical": identical(base8, r_dead),
        "dead_slot_load": float(r_dead.schedule.slot_loads[5]),
        "events": list(dead_job.mesh_events),
    }

    # --- die mid-wave: checkpoint + bounded replay onto the survivors.
    kill_job = make_job(slots, checkpoint=True)
    kill_at = 2
    kill_job.set_slot_failure(3, at_wave=kill_at)
    r_kill = kill_job.run(batch8)
    num_waves = int(kill_job.last_checkpoint.num_chunks)
    replay_plan = kill_job.last_replay_plan
    mid_kill = {
        "bit_identical": identical(base8, r_kill),
        "num_waves": num_waves,
        "checkpoint_wave": int(kill_job.last_checkpoint_wave),
        "replayed_waves": int(kill_job.last_replayed_waves),
        "replay_bound_ok": bool(
            kill_job.last_replayed_waves
            <= num_waves - kill_job.last_checkpoint_wave),
        "replay_dead_slot_load": (
            None if replay_plan is None
            else float(replay_plan.schedule.slot_loads[3])),
        "events": list(kill_job.mesh_events),
    }

    # --- warm resizes: the snapshot re-projects instead of going cold.
    policy = ReusePolicy(max_drift=0.35, revalidate_every=1)
    elastic_job = make_job(slots, reuse=policy)
    elastic_job.run(batch8)                       # cold plan
    r_warm = elastic_job.run(batch8)              # warm reuse
    elastic_job.resize(6)
    r_6 = elastic_job.run(batch6)
    elastic_job.resize(8)
    r_8 = elastic_job.run(batch8)
    resizes = {
        "warm_reason": r_warm.plan_reason,
        "after_8to6_reason": r_6.plan_reason,
        "after_6to8_reason": r_8.plan_reason,
        "no_cold_after_resize": bool(r_6.plan_reason != "cold"
                                     and r_8.plan_reason != "cold"),
        "reprojections": int(elastic_job.schedule_cache.reprojections),
        "outputs_6_match": bool(np.allclose(r_6.values, base6.values)
                                and np.array_equal(r_6.counts, base6.counts)),
        "outputs_8_bit_identical": identical(base8, r_8),
        "events": list(elastic_job.mesh_events),
    }

    report = {
        "config": f"slots={slots} K={K} clusters={n} chunks={chunks} "
                  f"backend=vmap scheduler=bss",
        "dead_at_start": dead_start,
        "die_mid_wave": mid_kill,
        "resizes": resizes,
        "bit_identical": bool(dead_start["bit_identical"]
                              and mid_kill["bit_identical"]
                              and resizes["outputs_8_bit_identical"]),
        "dead_load_total": float(
            dead_start["dead_slot_load"]
            + (mid_kill["replay_dead_slot_load"] or 0.0)),
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def bench_multijob(out_path: str) -> dict:
    """Multi-job ΣwᵢCᵢ bench: WSPT admission vs FIFO on a skewed 2-job mix.

    Fixed seeds, vmap backend, 8 slots. Job ``bulk`` holds 6 pending
    batches at weight 1; job ``urgent`` holds 1 batch of the same shape
    at weight 4 — the classic case where FIFO (bulk arrived first) is
    maximally wrong and Smith's rule is exactly optimal. Both
    coordinators run the identical workload end to end after an untimed
    warm-up batch per job (excludes jit compile *and* the cold plan from
    the measured completions). Reported:

    * ``improvement`` — 1 − ΣwC(wspt) / ΣwC(fifo), gated ≥ 20%;
    * ``bit_identical`` — every coordinator-run batch output equals the
      same batch run on a solo job (scheduling moves *where* work runs,
      never what it computes), gated;
    * ``cache.collisions`` — tenant pairs sharing snapshot state, gated
      == 0 (multi-tenant isolation is measured, not assumed);
    * ``coschedule_overlap`` — cross-job fraction of the merged §4.4
      wave issue order (telemetry).
    """
    import numpy as np
    import jax.numpy as jnp

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.multi_job import MultiJobCoordinator
    from repro.core.schedule_cache import ReusePolicy

    slots, K, n, chunks = 8, 1024, 64, 4
    BULK_BATCHES, URGENT_BATCHES = 6, 1
    W_BULK, W_URGENT = 1.0, 4.0

    def make_batch(seed: int):
        brng = np.random.default_rng(seed)
        keys = (brng.zipf(1.25, size=(slots, K)) % 997).astype(np.int32)
        vals = np.ones((slots, K, 8), np.float32)
        return (jnp.asarray(keys), jnp.asarray(vals),
                jnp.ones((slots, K), bool))

    def make_job():
        return MapReduceJob(
            lambda s: s,
            MapReduceConfig(num_slots=slots, num_clusters=n,
                            scheduler="bss", pipeline_chunks=chunks,
                            reuse=ReusePolicy(max_drift=0.5)),
            backend="vmap")

    bulk_batches = [make_batch(s) for s in range(BULK_BATCHES)]
    urgent_batches = [make_batch(100 + s) for s in range(URGENT_BATCHES)]
    warm_batch = make_batch(999)

    # Solo references for the bit-identity check (same warm-up sequence).
    solo = {}
    for name, batches in (("bulk", bulk_batches), ("urgent", urgent_batches)):
        job = make_job()
        job.run(warm_batch)
        solo[name] = [job.run(b) for b in batches]

    def run_order(order: str) -> dict:
        co = MultiJobCoordinator(num_slots=slots)
        for name, weight in (("bulk", W_BULK), ("urgent", W_URGENT)):
            handle = co.add_job(name, make_job(), weight=weight)
            handle.job.run(warm_batch)   # untimed: compile + cold plan
        for b in bulk_batches:
            co.submit("bulk", b)
        for b in urgent_batches:
            co.submit("urgent", b)
        out = co.run_queue(order=order)
        out["results"] = {name: co[name].results
                          for name in ("bulk", "urgent")}
        return out

    fifo = run_order("fifo")
    wspt = run_order("wspt")

    identical = True
    for name in ("bulk", "urgent"):
        for run in (fifo, wspt):
            for ref, got in zip(solo[name], run["results"][name]):
                identical = identical and bool(
                    np.array_equal(ref.values, got.values)
                    and np.array_equal(ref.counts, got.counts))

    wc_fifo = fifo["weighted_completion"]
    wc_wspt = wspt["weighted_completion"]
    report = {
        "config": f"slots={slots} K={K} clusters={n} chunks={chunks} "
                  f"backend=vmap scheduler=bss "
                  f"bulk={BULK_BATCHES}x@w{W_BULK:g} "
                  f"urgent={URGENT_BATCHES}x@w{W_URGENT:g}",
        "fifo": {"order": fifo["order"],
                 "completions_s": fifo["completions"],
                 "weighted_completion_s": wc_fifo},
        "wspt": {"order": wspt["order"],
                 "completions_s": wspt["completions"],
                 "weighted_completion_s": wc_wspt},
        "improvement": 1.0 - wc_wspt / wc_fifo if wc_fifo > 0 else 0.0,
        "bit_identical": identical,
        "coschedule_overlap": wspt["coschedule_overlap"],
        "cache": {k: v for k, v in wspt["cache"].items()
                  if k != "per_tenant"},
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="run the CI bench-smoke and write --out JSON")
    ap.add_argument("--smoke-reuse", action="store_true",
                    help="run the schedule-reuse bench and write --out JSON")
    ap.add_argument("--smoke-straggler", action="store_true",
                    help="run the Q||C_max straggler bench and write --out JSON")
    ap.add_argument("--measured", action="store_true",
                    help="with --smoke-straggler: shard_map mesh + measured "
                         "per-device wave timings (needs >= 8 devices)")
    ap.add_argument("--smoke-elastic", action="store_true",
                    help="run the elastic-mesh fault-injection bench and "
                         "write --out JSON")
    ap.add_argument("--smoke-multijob", action="store_true",
                    help="run the multi-job ΣwC admission bench and "
                         "write --out JSON")
    ap.add_argument("--smoke-sketch", action="store_true",
                    help="run the sketch-statistics plan-path bench and "
                         "write --out JSON")
    ap.add_argument("--out", default="BENCH_schedulers.json")
    args = ap.parse_args()

    if args.smoke_sketch:
        sys.path.insert(0, "src")
        out = args.out if args.out != "BENCH_schedulers.json" \
            else "BENCH_sketch.json"
        report = bench_sketch(out)
        pp = report["plan_path"]
        print(f"plan path: exact={pp['exact_seconds'] * 1e3:.1f}ms "
              f"sketch={pp['sketch_seconds'] * 1e3:.1f}ms "
              f"speedup={pp['speedup']:.2f}x "
              f"(pull {pp['exact_pull_floats']} -> "
              f"{pp['sketch_pull_floats']} floats)")
        for scen, row in report["scenarios"].items():
            print(f"{scen}: overflow_replans={row['overflow_replans']}"
                  f"/{row['batches']} overflow_free={row['overflow_free']}")
        print(f"bit_identical={report['bit_identical']}")
        # thresholds live in benchmarks/check.py (--gate sketch); keep
        # the runner's own exit status honest for local use too
        if not report["bit_identical"]:
            sys.exit("FAIL: sketch/prefix outputs diverged from exact")
        if report["scenarios"]["benign"]["overflow_replans"] != 0:
            sys.exit("FAIL: benign stream tripped the overflow hatch")
        if report["scenarios"]["adversarial"]["overflow_replans"] < 1:
            sys.exit("FAIL: adversarial stream never exercised the hatch")
        return

    if args.smoke_multijob:
        sys.path.insert(0, "src")
        out = args.out if args.out != "BENCH_schedulers.json" \
            else "BENCH_multijob.json"
        report = bench_multijob(out)
        print(f"fifo:  order={report['fifo']['order']} "
              f"ΣwC={report['fifo']['weighted_completion_s']:.3f}s")
        print(f"wspt:  order={report['wspt']['order']} "
              f"ΣwC={report['wspt']['weighted_completion_s']:.3f}s")
        print(f"improvement={report['improvement'] * 100:.1f}% "
              f"bit_identical={report['bit_identical']} "
              f"collisions={report['cache']['collisions']} "
              f"overlap={report['coschedule_overlap']:.2f}")
        # thresholds live in benchmarks/check.py (--gate multijob); keep
        # the runner's own exit status honest for local use too
        if not report["bit_identical"]:
            sys.exit("FAIL: a coordinator-run batch diverged from its "
                     "solo-job output")
        if report["improvement"] < 0.20:
            sys.exit("FAIL: WSPT admission improved ΣwC by only "
                     f"{report['improvement'] * 100:.1f}% (< 20%)")
        if report["cache"]["collisions"] != 0:
            sys.exit("FAIL: tenant schedule caches shared snapshot state")
        return

    if args.smoke_elastic:
        sys.path.insert(0, "src")
        out = args.out if args.out != "BENCH_schedulers.json" \
            else "BENCH_elastic.json"
        report = bench_elastic(out)
        mk = report["die_mid_wave"]
        rs = report["resizes"]
        print(f"dead_at_start: bit_identical="
              f"{report['dead_at_start']['bit_identical']} "
              f"dead_slot_load={report['dead_at_start']['dead_slot_load']}")
        print(f"die_mid_wave: bit_identical={mk['bit_identical']} "
              f"ckpt={mk['checkpoint_wave']}/{mk['num_waves']} "
              f"replayed={mk['replayed_waves']} "
              f"replay_dead_load={mk['replay_dead_slot_load']}")
        print(f"resizes: 8to6={rs['after_8to6_reason']} "
              f"6to8={rs['after_6to8_reason']} "
              f"reprojections={rs['reprojections']} "
              f"6_match={rs['outputs_6_match']} "
              f"8_identical={rs['outputs_8_bit_identical']}")
        # thresholds live in benchmarks/check.py (--gate elastic); keep
        # the runner's own exit status honest for local use too
        if not report["bit_identical"]:
            sys.exit("FAIL: a fault scenario diverged from the "
                     "uninterrupted baseline")
        if report["dead_load_total"] != 0.0:
            sys.exit("FAIL: a plan assigned work to a dead slot")
        return

    if args.smoke_straggler:
        sys.path.insert(0, "src")
        out = args.out if args.out != "BENCH_schedulers.json" \
            else ("BENCH_stragglers_measured.json" if args.measured
                  else "BENCH_stragglers.json")
        report = bench_straggler(out, measured=args.measured)
        print(f"timing source: {report['timing_source']} "
              f"({report['measured_batches']} measured batches)")
        for name, row in report["strategies"].items():
            print(f"{name}: oblivious={row['oblivious_makespan_s']:.1f}s "
                  f"aware={row['aware_makespan_s']:.1f}s "
                  f"cut={row['makespan_cut'] * 100:.1f}% "
                  f"finish_ratio={row['aware_finish_ratio']:.3f}")
        print(f"hash baseline: {report['hash_makespan_s']:.1f}s")
        print(f"mid-run slowdown: {report['speed_replans']} speed replans, "
              f"estimated speeds {report['estimated_final_speeds']}, "
              f"bit_identical={report['bit_identical']}")
        if not report["bit_identical"]:
            sys.exit("FAIL: speed-aware outputs diverged from speed-oblivious")
        if report["min_makespan_cut"] < 0.25:
            sys.exit("FAIL: speed-aware scheduling cut makespan by only "
                     f"{report['min_makespan_cut'] * 100:.1f}% (< 25%)")
        if report["speed_replans"] < 1:
            sys.exit("FAIL: mid-run slowdown did not trigger a speed replan")
        if args.measured and report["measured_batches"] < 1:
            sys.exit("FAIL: no batch delivered valid measured timings")
        if args.measured:
            ov = bench_overlap_measured("BENCH_overlap_measured.json")
            med = ov["phase_b_seconds"]
            print(f"overlap: unmeasured={med['unmeasured'] * 1e3:.1f}ms "
                  f"ticks={med['measured_ticks'] * 1e3:.1f}ms "
                  f"(x{ov['measured_over_unmeasured']:.2f}) "
                  f"fenced={med['measured_fenced'] * 1e3:.1f}ms "
                  f"(x{ov['fenced_over_unmeasured']:.2f})")
            if not ov["overlap_recovered"]:
                sys.exit("FAIL: measured-mode phase B lost the overlap "
                         f"(x{ov['measured_over_unmeasured']:.2f} > "
                         f"{OVERLAP_THRESHOLD} of unmeasured + "
                         f"{OVERLAP_ABS_SLACK_S * 1e3:.0f}ms)")
        return

    if args.smoke_reuse:
        sys.path.insert(0, "src")
        out = args.out if args.out != "BENCH_schedulers.json" \
            else "BENCH_schedule_reuse.json"
        report = bench_schedule_reuse(out)
        print(f"replan_rate={report['replan_rate']:.3f} "
              f"(stationary replans={report['stationary_replans']}, "
              f"drift replans={report['drift_replans']})")
        if report["steady_state_seconds"] is not None:
            print(f"steady_state={report['steady_state_seconds'] * 1e3:.1f} ms/batch "
                  f"always_replan={report['always_replan_seconds'] * 1e3:.1f} ms/batch "
                  f"speedup={report['speedup']:.2f}x")
        print(f"imbalance at shift: stale="
              f"{report['stale_balance_ratio_at_shift']:.3f} "
              f"replanned={report['replanned_balance_ratio_at_shift']:.3f}")
        print(f"bit_identical={report['bit_identical']}")
        if not report["bit_identical"]:
            sys.exit("FAIL: reused-schedule outputs diverged from always-replan")
        if report["stationary_replans"] != 1:
            sys.exit("FAIL: stationary phase should plan exactly once, got "
                     f"{report['stationary_replans']}")
        return

    if args.smoke:
        sys.path.insert(0, "src")
        report = bench_smoke(args.out)
        eng = report["engine"]
        print(f"auto_choice={report['auto_choice']}")
        for name, row in report["schedulers"].items():
            print(f"{name}: balance_ratio={row['balance_ratio']:.4f}")
        print(f"engine: sequential={eng['sequential_seconds']:.3f}s "
              f"pipelined={eng['pipelined_seconds']:.3f}s "
              f"bit_identical={eng['bit_identical']}")
        if not eng["bit_identical"]:
            sys.exit("FAIL: pipelined engine diverged from sequential")
        return

    sys.path.insert(0, "src")
    from benchmarks.beyond import ALL_BEYOND
    from benchmarks.figures import ALL_FIGURES
    from benchmarks.roofline import summary_rows

    benches = ALL_FIGURES + ALL_BEYOND + [summary_rows]
    print("name,key,value")
    t_start = time.time()
    for fn in benches:
        if args.only and args.only not in fn.__name__:
            continue
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{fn.__name__},ERROR,{type(e).__name__}:{e}",
                  file=sys.stderr)
            raise
        for name, key, value in rows:
            print(f"{name},{key},{value:.6g}")
        print(f"# {fn.__name__}: {time.time() - t0:.1f}s", file=sys.stderr)
    print(f"# total: {time.time() - t_start:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
