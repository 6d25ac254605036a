"""The HiBench WordCount job kind: its text, reference, control, outputs and metrics.

Run on the CPU at a small size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_hibench_wordcount.py
"""

from __future__ import annotations

import collections
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import checks  # noqa: E402
import layout  # noqa: E402
import trace_reduce as tr  # noqa: E402

CELL = "wordcount-uniform.1chip"


def small_cell(shard_bytes: int = 1 << 14) -> layout.Cell:
    """The cell at ``shard_bytes`` a map task, 3 pool batches."""
    cell = layout.load_cell(CELL)
    shards = int(cell.config["engine"]["num_slots"])
    cell.config["job"]["rows_per_shard"] = cell.config["rows_per_shard"] = shard_bytes
    cell.config["job"]["rows_per_batch"] = shards * shard_bytes
    cell.traffic["pool_batches"] = 3
    cell.traffic["warmup_batches"] = 3
    return cell


def host_pool(cell: layout.Cell, seed: int) -> list:
    import jax
    from jax.sharding import SingleDeviceSharding

    m, k = cell.config["engine"]["num_slots"], cell.config["rows_per_shard"]
    pool = layout.traffic_module(cell).make_pool(
        cell.traffic, layout.job_module(cell), cell.config["job"], seed, (m, k),
        SingleDeviceSharding(jax.devices()[0]))
    return jax.device_get(pool)


@pytest.fixture(scope="module")
def pool():
    return host_pool(small_cell(), 2**31 + 41)


def test_text_is_the_configured_deployment(pool):
    cell = small_cell()
    job, cfg = layout.job_module(cell), cell.config["job"]
    words = [w for b in pool for s in b["text"] for w in s.tobytes().split()]
    vocab = set(words)
    assert len(vocab) == cfg["words"]
    assert all(w.isalpha() and w.islower() and 3 <= len(w) <= 18 for w in vocab)
    assert len({job.fnv1a31(w) for w in vocab}) == cfg["words"]
    lengths, per_shard = job._layout(cfg)
    for b in pool:
        for shard in b["text"]:
            raw = shard.tobytes()
            records = raw.rstrip(b" ").split(b"\n")[:-1]
            assert raw.rstrip(b" ").endswith(b"\n") and not raw.startswith(b" ")
            assert len(raw.split()) == per_shard
            assert all(len(r.split()) <= cfg["max_record_words"] for r in records)
            assert all(len(r.split()) >= cfg["min_record_words"] for r in records[:-1])


def test_every_seed_does_the_same_work(pool):
    cell = small_cell()
    other = host_pool(cell, 2**31 + 42)
    job = layout.job_module(cell)
    for a, b in zip(pool, other):
        assert not np.array_equal(a["text"], b["text"])
        assert (job.valid(a).sum(axis=1) == job.valid(b).sum(axis=1)).all()
        letters = [(x["text"] >= ord("a")).sum(axis=1) for x in (a, b)]
        assert (letters[0] == letters[1]).all()
        for sa, sb in zip(a["text"], b["text"]):
            ca = collections.Counter(sa.tobytes().split())
            cb = collections.Counter(sb.tobytes().split())
            assert sorted(ca.values()) == sorted(cb.values())


def test_reference_is_a_counter(pool):
    job = layout.job_module(small_cell())
    batch = pool[1]
    keys, values, counts = job.reference(batch, 1024)
    plain = collections.Counter(w for s in batch["text"] for w in bytes(s).split())
    assert {int(k): c for k, c in zip(keys, counts)} == {
        job.fnv1a31(w): float(c) for w, c in plain.items()}
    np.testing.assert_array_equal(values[:, 0], counts)
    assert counts.sum() == job.valid(batch).sum()
    ids, ok = job.group_ids(batch), job.valid(batch)
    assert set(np.unique(ids[ok]).tolist()) == set(keys.tolist())


def test_control_fails_the_limits():
    """At 2^19 bytes a map task a word's count passes 256, where a bfloat16
    running sum stops."""
    cell = small_cell(1 << 19)
    job = layout.job_module(cell)
    verdict = checks.Verdict(checks.limits_of(cell.config))
    for batch in host_pool(cell, 2**31 + 43)[:2]:
        ref = job.reference(batch, 1024)
        verdict.add(checks.compare(job.control(batch, 1024), ref, 0))
        assert checks.compare(ref, ref, 0)["value_rel_err"] == 0
    assert not verdict.correct
    assert verdict.worst["value_rel_err"] > 0.1
    assert verdict.worst["count_mismatch"] == 0


def test_outputs_give_the_keyed_triple():
    job = layout.job_module(small_cell())
    result = types.SimpleNamespace(keys=np.array([7, 3]), values=np.array([[2.0], [5.0]]),
                                   counts=np.array([2.0, 5.0]))
    keys, values, counts = checks.program_out(job, result, 1024)
    assert keys.tolist() == [7, 3] and values[:, 0].tolist() == [2.0, 5.0]
    assert counts.tolist() == [2.0, 5.0]


def run_small(seed: int, seconds: float = 1.0) -> dict:
    import run

    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds, trace=0,
                                 trace_out=None)
    return run.run_cell(args, small_cell(), require_tpu=False,
                        peaks=layout.peaks("TPU v5 lite"))


def test_small_cell_is_correct():
    result = run_small(2**31 + 47)
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert all(result["checks"][k]["value"] == 0 for k in checks.NAMES)
    assert set(result["metrics"]) == {"pairs_per_s", "batch_p95_s", "setup_s"}


def test_small_cell_with_words_merged_per_cluster_is_not_correct(monkeypatch):
    """The check reads the per-word table: an engine that sums the words of
    a cluster into one row fails it."""
    import run

    make_job = run.make_job

    def per_cluster(cell, job_module, mesh):
        job = make_job(cell, job_module, mesh)
        orig = job.run

        def merged(inputs):
            res = orig(inputs)
            cluster = np.abs(res.keys) % 4
            res.keys = np.unique(cluster)
            res.values = np.bincount(cluster, res.values[:, 0])[res.keys][:, None]
            res.counts = np.bincount(cluster, res.counts)[res.keys]
            return res
        job.run = merged
        return job
    monkeypatch.setattr(run, "make_job", per_cluster)
    result = run_small(2**31 + 48)
    assert not result["correct"]
    assert result["checks"]["count_mismatch"]["value"] > 0


def _traced(modules, floors):
    """One chip, two batches, ``modules`` as ``XLA Modules`` events (ns)."""
    trace = tr.Trace([tr.Device(0, modules, [])], [(tr.WINDOW_SPAN, 0, 10_000_000)],
                     (0, 10_000_000))
    return tr.TracedRun(trace, [{"reused": True}, {"reused": True}], floors)


def test_metric_readers_on_a_synthetic_trace():
    floors = [{"hbm_s": 1e-5, "ici_s": 0.0, "floor_s": 1e-5, "bound": "hbm"}] * 2
    run = _traced([("jit_phase_a(1)", 0, 1_000_000), ("jit_combine(2)", 1_000_000, 3_000_000),
                   ("jit_combine(2)", 5_000_000, 7_000_000),
                   ("jit_phase_b(3)", 7_000_000, 7_500_000)], floors)
    ms = layout.metric_module("combine_ms").read(run)
    roof = layout.metric_module("combine_roofline").read(run)
    assert ms == pytest.approx(2.0)           # 4 ms of jit_combine over 2 batches
    assert roof == pytest.approx(100 * 2e-5 / 4e-3)
    no_combiner = _traced([("jit_phase_a(1)", 0, 1_000_000)], floors)
    assert layout.metric_module("combine_ms").read(no_combiner) is None
    assert layout.metric_module("combine_roofline").read(no_combiner) is None
