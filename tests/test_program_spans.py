"""The engine's host spans and device scopes (``repro.core.spans``) in a trace.

A tiny job runs three batches under ``jax.profiler.trace`` on the CPU: a
cold replan, the same inputs again (a reuse), and a drifted batch (a
replan). The trace is read back with ``jax.profiler.ProfileData``. The
shard_map case needs 4 devices; with fewer in this process it runs in a
child process on 4 virtual CPU devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python tests/test_program_spans.py shard_map
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import tempfile
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
M, K, N = 4, 1024, 32


def _identity_map(shard):
    return shard


def _batch(seed: int, alpha: float):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    keys = (rng.zipf(alpha, size=(M, K)) % 997).astype(np.int32)
    vals = rng.integers(0, 8, size=(M, K, 2)).astype(np.float32)
    return (jnp.asarray(keys), jnp.asarray(vals), jnp.ones((M, K), bool))


def _job(backend: str):
    import jax
    from jax.sharding import Mesh

    from repro.core.mapreduce import MapReduceConfig, MapReduceJob
    from repro.core.schedule_cache import ReusePolicy

    mesh = None
    if backend == "shard_map":
        mesh = Mesh(np.asarray(jax.devices()[:M]), ("mr_slots",))
    return MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=M, num_clusters=N, pipeline_chunks=3,
        reuse=ReusePolicy(max_drift=0.2)), backend=backend, mesh=mesh)


def record(backend: str, log_dir: str) -> dict:
    """Three batches of a fresh job under the profiler: the ``os4m.*`` host
    events, each batch's ``reused`` flag, and the job's jit misses."""
    import jax
    from jax.profiler import ProfileData

    job = _job(backend)
    a, drifted = _batch(0, 1.3), _batch(1, 3.0)
    with jax.profiler.trace(log_dir):
        reused = [job.run(b).reused for b in (a, a, drifted)]
    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("os4m."):
                    events.append([e.name, e.start_ns, e.start_ns + e.duration_ns,
                                   dict(e.stats)])
    events.sort(key=lambda e: (e[1], -e[2]))
    return {"events": events, "reused": reused, "jit_misses": job.jit_misses}


def _recorded(backend: str) -> dict:
    import jax

    if backend == "vmap" or len(jax.devices()) >= M:
        with tempfile.TemporaryDirectory() as d:
            return record(backend, d)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, __file__, backend], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module", params=["vmap", "shard_map"])
def traced(request):
    return _recorded(request.param)


def _batches(rec):
    return [e for e in rec["events"] if e[0] == "os4m.batch"]


def _inside(rec, batch):
    """The spans nested in one ``os4m.batch`` span, in start order."""
    _, s, e, _ = batch
    return [x for x in rec["events"] if x is not batch and s <= x[1] and x[2] <= e]


def test_one_batch_span_per_run(traced):
    batches = _batches(traced)
    assert len(batches) == 3
    assert [b[3]["batch"] for b in batches] == [0, 1, 2]
    assert traced["reused"] == [False, True, False]
    # every os4m span of the trace lies in one of the batches
    assert all(any(b[1] <= e[1] and e[2] <= b[2] for b in batches)
               for e in traced["events"])


@pytest.mark.parametrize("which", [0, 2])
def test_replanned_batch_nests_every_step(traced, which):
    batch = _batches(traced)[which]
    inner = _inside(traced, batch)
    names = [e[0] for e in inner]
    order = ["os4m.decide", "os4m.stats_pull", "os4m.plan", "os4m.phase_b",
             "os4m.output_pull", "os4m.merge"]
    firsts = [names.index(n) for n in order]
    assert firsts == sorted(firsts), names
    plan = inner[names.index("os4m.plan")]
    assign = [e for e in inner if e[0] == "os4m.plan.assign"]
    assert len(assign) == 1 and plan[1] <= assign[0][1] and assign[0][2] <= plan[2]
    assert plan[3] == {"valid_pairs": M * K, "input_pairs": M * K}
    pulls = [e for e in inner if e[0] in ("os4m.stats_pull", "os4m.output_pull")]
    assert all(e[3]["bytes"] > 0 for e in pulls)
    # the statistics pull is the (m, n) float32 histogram
    assert inner[names.index("os4m.stats_pull")][3]["bytes"] == M * N * 4


def test_reused_batch_has_no_plan(traced):
    names = [e[0] for e in _inside(traced, _batches(traced)[1])]
    assert "os4m.plan" not in names and "os4m.plan.assign" not in names
    assert {"os4m.decide", "os4m.stats_pull", "os4m.phase_b", "os4m.output_pull",
            "os4m.merge"} <= set(names)


def test_jit_builds_match_jit_misses(traced):
    builds = [e for e in traced["events"] if e[0] == "os4m.jit_build"]
    assert len(builds) == traced["jit_misses"] >= 2
    keys = {e[3]["key"] for e in builds}
    assert {"a", "b"} <= keys
    # phase A builds directly under os4m.batch, phase B under os4m.phase_b
    first = _inside(traced, _batches(traced)[0])
    b_build = next(e for e in first if e[0] == "os4m.jit_build" and e[3]["key"] == "b")
    assert any(p[0] == "os4m.phase_b" and p[1] <= b_build[1] and b_build[2] <= p[2]
               for p in first)


@pytest.mark.parametrize("pipelined", [True, False])
def test_phase_b_hlo_carries_the_scopes(pipelined):
    import jax.numpy as jnp

    from repro.core import spans
    from repro.core.mapreduce import MapReduceConfig, MapReduceJob

    job = MapReduceJob(_identity_map, MapReduceConfig(
        num_slots=M, num_clusters=N, pipeline_chunks=3, pipelined=pipelined),
        backend="vmap")
    batch = _batch(0, 1.3)
    job.run(batch)
    intermediate, _ = job._jit_cache[("a",)](batch)
    phase_b = next(fn for k, fn in job._jit_cache.items() if k[0] == "b")
    per_cluster = jnp.zeros(N, jnp.int32)  # only shapes reach the compiler
    text = phase_b.lower(intermediate, per_cluster, per_cluster,
                         per_cluster).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    for scope in spans.DEVICE_SCOPES:
        rx = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        assert any(rx.search(n) for n in op_names), scope


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT / "src"))
    with tempfile.TemporaryDirectory() as d:
        print(json.dumps(record(sys.argv[1], d)))
