"""Run one benchmark cell on the chips of this machine and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json`` (see ``bench/layout.py``). One run:

1. set-up: JAX on the TPU with the persistent compilation cache in
   ``<checkout>/.jax_cache``; the traffic's pool of batches drawn from
   ``--seed`` and put on the device(s); one ``MapReduceJob``; the warm-up
   batches, which compile and make the cold plan;
2. the window: ``MapReduceJob.run`` on pool batches in the mix's order,
   one batch in flight, until ``--seconds`` have passed, finishing the
   batch in flight. Outputs stay on the host. With ``--trace 1`` the
   window runs under the profiler and per-layer metrics are read from the
   trace (``bench/metrics/<metric>.py``);
3. after the window: peak device memory, the program's state freed, then
   every batch of the window compared with the job's plain reference,
   row by row named by key (``bench/checks.py``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit,
which also end standard error. With no TPU, or fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))

import checks  # noqa: E402
import floors  # noqa: E402
import layout  # noqa: E402
import trace_reduce  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileEvents:
    """Counts compilations and persistent-cache lookups while ``armed``."""

    def __init__(self, jax):
        self.armed = False
        self.counts = {"backend_compiles": 0, "cache_hits": 0, "cache_misses": 0}
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw):
        if self.armed and event.startswith("/jax/compilation_cache/cache_"):
            self.counts[event.rsplit("/", 1)[1]] += 1

    def _duration(self, event: str, _secs: float, **_kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.counts["backend_compiles"] += 1


def nearest_rank(values, q: float) -> float:
    """The ``q`` quantile by nearest rank."""
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def device_info(jax, used) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    d0 = jax.devices()[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": int(peak)}


def pool_sharding(jax, np, chips: int):
    """Where pool batches live: the one chip, or split over a mesh axis
    ``slots`` with one map shard per chip."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    used = jax.devices()[:chips]
    if chips == 1:
        return SingleDeviceSharding(used[0]), None, used
    mesh = Mesh(np.asarray(used), ("slots",))
    return NamedSharding(mesh, P("slots")), mesh, used


def make_job(cell, job_module, mesh):
    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    engine = dict(cell.config["engine"])
    reuse = engine.pop("reuse", None)
    config = MapReduceConfig(**engine,
                             reuse=None if reuse is None else ReusePolicy(**reuse))
    return MapReduceJob(job_module.map_fn, config, backend=cell.config["backend"],
                        mesh=mesh)


def run_cell(args, cell=None, *, require_tpu: bool = True, peaks=None) -> dict:
    """One run of ``args.workload``; returns the result object.

    Tests pass a small ``cell`` of their own, ``require_tpu=False`` and the
    ``peaks`` to use; a benchmark run passes neither.
    """
    cell = cell or layout.load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    import numpy as np
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: cell {cell.name} needs {cell.chips} chips, "
                         f"JAX sees {len(devices)}")
    peaks = peaks or layout.peaks(devices[0].device_kind)
    from repro.launch import compile_cache

    compile_cache.enable()  # takes JAX_COMPILATION_CACHE_DIR, set above
    # Cache every program, the quick ones too, so a run after the first
    # compiles nothing and its set-up is steady.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compile_events = CompileEvents(jax)
    compile_events.armed = True

    cfg = cell.config
    job_module = layout.job_module(cell)
    traffic = layout.traffic_module(cell)
    m, k = int(cfg["engine"]["num_slots"]), int(cfg["rows_per_shard"])
    if m * k != int(cfg["job"]["rows_per_batch"]):
        raise ValueError(f"{m} shards x {k} rows != rows_per_batch {cfg['job']['rows_per_batch']}")

    t = time.perf_counter()
    sharding, mesh, used = pool_sharding(jax, np, cell.chips)
    pool = traffic.make_pool(cell.traffic, job_module, cfg["job"], args.seed, (m, k), sharding)
    jax.block_until_ready(pool)
    spans = {d.id for b in pool for a in b.values() for d in a.sharding.device_set}
    if spans != {d.id for d in used}:
        raise RuntimeError(f"pool batches span devices {sorted(spans)}, not {cell.chips}")
    t_data = time.perf_counter() - t

    job = make_job(cell, job_module, mesh)
    warmup = int(cell.traffic["warmup_batches"])
    t = time.perf_counter()
    for i in range(warmup):
        job.run(pool[traffic.pool_index(cell.traffic, i)])
    t_warm = time.perf_counter() - t
    log(f"set-up: data {t_data:.6f} s, warm-up {warmup} batches "
        f"{t_warm:.6f} s, jit misses {job.jit_misses}; in set-up {compile_events.counts}")
    compile_events.counts = dict.fromkeys(compile_events.counts, 0)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        profiling = jax.profiler.trace(trace_dir, profiler_options=opts)
    else:
        profiling = contextlib.nullcontext()

    records = []
    with profiling:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            t_w0 = time.perf_counter()
            setup_s = t_w0 - T0
            i = warmup
            while not records or time.perf_counter() - t_w0 < args.seconds:
                p = traffic.pool_index(cell.traffic, i)
                with jax.profiler.TraceAnnotation("bench.batch"):
                    t = time.perf_counter()
                    res = job.run(pool[p])
                    wall = time.perf_counter() - t
                records.append({"pool": p, "wall_s": wall, "reused": res.reused,
                                "reason": res.plan_reason, "overflow": res.overflow,
                                "result": res})
                i += 1
            t_w1 = time.perf_counter()
    compile_events.armed = False
    window_s = t_w1 - t_w0
    reused = sum(r["reused"] for r in records)
    walls = [r["wall_s"] for r in records]
    slowest = sorted(range(len(walls)), key=walls.__getitem__)[-3:][::-1]
    log(f"window: {len(records)} batches in {window_s:.6f} s, {reused} reused the plan; "
        f"in the window {compile_events.counts}; batch walls: median "
        f"{sorted(walls)[len(walls) // 2]:.6f} s, slowest "
        f"{[(i, round(walls[i], 6)) for i in slowest]}, outside batches "
        f"{window_s - sum(walls):.6f} s")

    device = device_info(jax, used)
    served = sorted({r["pool"] for r in records})
    host_pool = dict(zip(served, jax.device_get([pool[p] for p in served])))
    pairs = {p: int(job_module.valid(b).sum()) for p, b in host_pool.items()}
    del job, pool, res
    gc.collect()

    result = {"correct": False, "attempted": len(records), "failed": 0, "metrics": {}}
    if args.trace:
        run = trace_run(args, cell, job_module, host_pool, records, trace_dir, used, peaks)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.busy_s
        device["window_s"] = run.window_s
        for spec in cell.per_layer:
            value = layout.metric_module(spec["name"]).read(run)
            if value is not None:
                result["metrics"][spec["name"]] = {"value": value, "unit": spec["unit"]}
        result["breakdown"] = trace_reduce.breakdown(run.trace)
        log(f"phase-B floor bound: {run.floor_bound}")
    else:
        e2e = {
            "pairs_per_s": sum(pairs[r["pool"]] for r in records) / window_s,
            "batch_p95_s": nearest_rank(walls, 0.95),
            "setup_s": setup_s,
        }
        for spec in cell.end_to_end:
            result["metrics"][spec["name"]] = {"value": e2e[spec["name"]], "unit": spec["unit"]}
    result["device"] = device

    verdict = check_outputs(cell, job_module, host_pool, records)
    result["correct"] = verdict.correct
    result["attempted"] = verdict.attempted
    result["failed"] = verdict.failed
    result["checks"] = verdict.as_dict()
    for line in verdict.lines():
        log(line)
    if args.trace and args.trace_out:
        kept = dict(result, floors=run.floors)
        (Path(args.trace_out) / f"{cell.name}.result.json").write_text(json.dumps(kept))
    return result


def check_outputs(cell, job_module, host_pool, records) -> checks.Verdict:
    """Every batch of the window against the plain reference of its input,
    row by row named by key."""
    n = int(cell.config["engine"]["num_clusters"])
    verdict = checks.Verdict(checks.limits_of(cell.config))
    refs = {}
    for r in records:
        if r["pool"] not in refs:
            refs[r["pool"]] = checks.rows(job_module.reference(host_pool[r["pool"]], n))
        out = checks.rows(checks.program_out(job_module, r["result"], n))
        verdict.add(checks.compare(out, refs[r["pool"]], r["overflow"]))
    return verdict


def trace_run(args, cell, job_module, host_pool, records, trace_dir, used, peaks):
    """The window's trace, reduced, with the run's record and phase-B floors."""
    path = trace_reduce.find_xplane(trace_dir)
    if args.trace_out:
        Path(args.trace_out).mkdir(parents=True, exist_ok=True)
        shutil.copy(path, Path(args.trace_out) / f"{cell.name}.xplane.pb")
    trace = trace_reduce.load(path, devices=[d.id for d in used])
    cfg = cell.config
    floor_of = {}
    for r in records:
        if r["pool"] not in floor_of:
            batch = host_pool[r["pool"]]
            floor_of[r["pool"]] = floors.phase_b_floor(
                job_module.group_ids(batch), job_module.valid(batch),
                num_shards=int(cfg["engine"]["num_slots"]),
                num_groups=int(cfg["engine"]["num_clusters"]),
                value_dim=job_module.VALUE_DIM,
                num_reducers=int(cfg["engine"]["num_slots"]),
                chips=cell.chips, peaks=peaks)
    batches = [{k: r[k] for k in ("pool", "wall_s", "reused", "reason")} for r in records]
    return trace_reduce.TracedRun(trace, batches, [floor_of[r["pool"]] for r in records])


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-out", default=None,
                    help="with --trace 1, also keep the window's .xplane.pb in this directory")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args)
    except SystemExit as e:
        log(str(e))
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
