"""The check that decides ``correct``, its plain reference and its control.

Run on the CPU at a tiny size:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each fault test drives a whole run of a cell (``run.run_cell``), with the
harness's look for a chip skipped and the engine broken underneath, and
sees ``correct`` come out false.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import types
from datetime import date, timedelta
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import checks  # noqa: E402
import layout  # noqa: E402

ROWS = 1 << 16
CELL = "q15-skew.1chip"


def tiny_cell(name: str = CELL, chips: int = 1) -> layout.Cell:
    """The cell at ``ROWS`` rows a batch; with ``chips=4``, one reducer per
    chip over a 4-device mesh (shard_map), 4 map shards of the same rows."""
    cell = layout.load_cell(name)
    if chips == 4:
        cell.chips = cell.config["chips"] = 4
        cell.config["backend"] = "shard_map"
        cell.config["engine"]["num_slots"] = 4
    cell.config["job"]["rows_per_batch"] = ROWS
    cell.config["job"]["block_rows"] = ROWS // 8
    cell.config["rows_per_shard"] = ROWS // int(cell.config["engine"]["num_slots"])
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


def run_tiny(seed: int = 2**31 + 17, seconds: float = 0.5, chips: int = 1) -> dict:
    import run

    args = types.SimpleNamespace(workload=CELL, seed=seed, seconds=seconds, trace=0,
                                 trace_out=None)
    return run.run_cell(args, tiny_cell(chips=chips), require_tpu=False,
                        peaks=layout.peaks("TPU v5 lite"))


def compare_dense(values, counts, overflow, ref_values, ref_counts):
    return checks.compare(checks.rows((values, counts)),
                          checks.rows((ref_values, ref_counts)), overflow)


def compare_by_position(values, counts, overflow, ref_values, ref_counts):
    """The comparison as it was before rows were named by key: by position."""
    values = np.asarray(values, np.float64).reshape(ref_values.shape)
    counts = np.asarray(counts, np.float64).reshape(ref_counts.shape)
    gap = np.abs(values - ref_values) / np.maximum(np.abs(ref_values), 1.0)
    rel = float(np.max(gap)) if gap.size else 0.0
    return {"value_rel_err": rel if np.isfinite(rel) else float("inf"),
            "count_mismatch": float(np.count_nonzero(counts != ref_counts)),
            "overflow": float(overflow)}


def test_reference_matches_a_loop():
    cell = tiny_cell()
    job = layout.job_module(cell)
    batch = host_pool(cell, 3)[0]
    values, counts = job.reference(batch, 16384)
    loop_v, loop_c = np.zeros(16384), np.zeros(16384)
    kept = 0
    for key, price, disc, ship in zip(*(batch[c].reshape(-1).tolist() for c in job.COLUMNS)):
        if date(1996, 1, 1) <= date(1992, 1, 1) + timedelta(days=ship) < date(1996, 4, 1):
            loop_v[key] += price * (1.0 - disc)
            loop_c[key] += 1
            kept += 1
    np.testing.assert_allclose(values[:, 0], loop_v, rtol=1e-12, atol=1e-9)
    np.testing.assert_array_equal(counts, loop_c)
    assert counts[0] == 0 and counts[1:10001].sum() == kept
    assert 0.03 * ROWS < kept < 0.045 * ROWS  # Q15's quarter: 91 of 2406 ship days


def test_ship_dates_follow_the_generator():
    ship = np.concatenate([b["l_shipdate"].reshape(-1) for b in host_pool(tiny_cell(), 7)])
    assert ship.min() >= 1 and ship.max() <= 2405 + 121
    inner = np.bincount(ship, minlength=2527)[121:2406]  # flat where every lag fits
    assert abs(inner.mean() - ship.size / 2406) < 0.02 * ship.size / 2406


def test_same_seed_same_rows_on_one_and_four_chips():
    one = host_pool(tiny_cell(), 2**31 + 99)
    four = host_pool(tiny_cell(chips=4), 2**31 + 99)
    again = host_pool(tiny_cell(), 2**31 + 99)
    other = host_pool(tiny_cell(), 2**31 + 100)
    job = layout.job_module(tiny_cell())
    for c in job.COLUMNS:
        np.testing.assert_array_equal(one[1][c].reshape(-1), four[1][c].reshape(-1))
        np.testing.assert_array_equal(one[1][c], again[1][c])
    assert not np.array_equal(one[1]["l_suppkey"], other[1]["l_suppkey"])
    for a, b in zip(one, other):  # every seed: the same valid counts per shard, relabelled
        for ka, kb, va, vb in zip(a["l_suppkey"], b["l_suppkey"], job.valid(a), job.valid(b)):
            np.testing.assert_array_equal(np.sort(np.bincount(ka[va], minlength=10001)),
                                          np.sort(np.bincount(kb[vb], minlength=10001)))


@pytest.mark.parametrize("chips", [1, 4])
def test_control_is_rejected(chips):
    cell = tiny_cell(chips=chips)
    job = layout.job_module(cell)
    verdict = checks.Verdict(checks.limits_of(cell.config))
    for batch in host_pool(cell, 11):
        verdict.add(compare_dense(*job.control(batch, 16384), 0,
                                  *job.reference(batch, 16384)))
    assert not verdict.correct
    assert verdict.worst["value_rel_err"] > 3 * verdict.limits["value_rel_err"]


@pytest.mark.parametrize("fault", ["one_value", "one_count", "empty_group", "nan", "overflow"])
def test_one_wrong_output_is_caught(fault):
    cell = tiny_cell()
    job = layout.job_module(cell)
    batch = host_pool(cell, 5)[0]
    ref_v, ref_c = job.reference(batch, 16384)
    values, counts, overflow = ref_v.astype(np.float32), ref_c.astype(np.float32), 0
    hot = int(np.argmax(ref_c))
    if fault == "one_value":
        values[hot] *= 1.001
    elif fault == "one_count":
        counts[hot] += 1
    elif fault == "empty_group":
        values[0] = 1.0
    elif fault == "nan":
        values[hot] = np.nan
    else:
        overflow = 1
    verdict = checks.Verdict(checks.limits_of(cell.config))
    numbers = compare_dense(values, counts, overflow, ref_v, ref_c)
    assert numbers == compare_by_position(values, counts, overflow, ref_v, ref_c)
    assert not verdict.add(numbers)
    assert verdict.failed == 1 and not verdict.correct


def test_clean_run_is_correct():
    result = run_tiny()
    assert result["correct"], result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"pairs_per_s", "batch_p95_s", "setup_s"}


def _patch(monkeypatch, fault: str):
    import jax
    import jax.numpy as jnp

    from repro.core import mapreduce as mr

    if fault == "half_batch_dropped":
        orig = mr._phase_a_shard

        def phase_a(shard, map_fn, **kw):
            def half(x):
                key, value, valid = map_fn(x)
                return key, value, valid & (jnp.arange(valid.shape[-1]) % 2 == 0)
            return orig(shard, half, **kw)
        monkeypatch.setattr(mr, "_phase_a_shard", phase_a)
    elif fault == "exchange_left_out":
        def copy_chunk(buckets, value_dim):
            bv, bc, bm = buckets
            own = jnp.arange(bm.shape[0]) == jax.lax.axis_index(mr.AXIS)
            return ((bv * own[:, None, None]).reshape(-1, value_dim), bc.reshape(-1),
                    (bm & own[:, None]).reshape(-1))
        monkeypatch.setattr(mr, "_copy_chunk", copy_chunk)
    elif fault == "answer_altered":
        orig = mr._reduce_chunk

        def reduce_chunk(*a, **kw):
            out, cnt = orig(*a, **kw)
            largest = jnp.argmax(jnp.abs(out).reshape(out.shape[0], -1).sum(-1))
            return out.at[largest].multiply(1.01), cnt
        monkeypatch.setattr(mr, "_reduce_chunk", reduce_chunk)
    elif fault == "stale_outputs":
        orig = mr.MapReduceJob.run
        last = {}

        def run_stale(self, inputs):
            res = orig(self, inputs)
            stale = last.get(id(self), res)
            last[id(self)] = res
            return stale
        monkeypatch.setattr(mr.MapReduceJob, "run", run_stale)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault", ["half_batch_dropped", "exchange_left_out",
                                   "answer_altered", "stale_outputs"])
def test_broken_engine_is_not_correct(monkeypatch, fault):
    _patch(monkeypatch, fault)
    result = run_tiny(seconds=1.0)
    assert result["attempted"] >= 1
    assert not result["correct"], result["checks"]
    assert result["failed"] >= 1


_FOUR_CHIP = """
import json, sys, types
sys.path.insert(0, {tests!r})
import test_checks as t
import pytest
mp = pytest.MonkeyPatch()
if {fault!r}:
    t._patch(mp, {fault!r})
r = t.run_tiny(seconds=1.0, chips=4)
print(json.dumps({{"correct": r["correct"], "attempted": r["attempted"],
                  "count": r["device"]["count"]}}))
"""


@pytest.mark.parametrize("fault", ["", "exchange_left_out", "answer_altered"])
def test_four_chip_cell_on_virtual_devices(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _FOUR_CHIP.format(tests=str(Path(__file__).parent), fault=fault)
    out = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["count"] == 4 and result["attempted"] >= 1
    assert result["correct"] == (fault == "")


# ---------------------------------------------------------------------------
# Rows named by key.
# ---------------------------------------------------------------------------


def _keyed_reference():
    keys = np.array([3, 17, 40, 1 << 30, (1 << 31) - 1])
    counts = np.array([5.0, 1.0, 7.0, 2.0, 9.0])
    return keys, counts[:, None].copy(), counts


@pytest.mark.parametrize("fault", ["merged", "dropped", "duplicated", "extra", "renamed"])
def test_keyed_fault_fails_count_mismatch(fault):
    keys, values, counts = _keyed_reference()
    if fault == "merged":      # keys 17 and 40 summed into one row named 17
        k, v, c = keys[[0, 1, 3, 4]], values[[0, 1, 3, 4]], counts[[0, 1, 3, 4]]
        v[1] += values[2]
        c[1] += counts[2]
    elif fault == "dropped":
        k, v, c = keys[1:], values[1:], counts[1:]
    elif fault == "duplicated":  # one key split over two rows, the sums still right
        k = np.append(keys, keys[2])
        v = np.concatenate([values, [[0.0]]])
        c = np.append(counts, 0.0)
    elif fault == "extra":
        k, v, c = np.append(keys, 99), np.concatenate([values, [[0.0]]]), np.append(counts, 0.0)
    else:
        k, v, c = keys.copy(), values, counts
        k[0] = 4
    numbers = checks.compare((k, v, c), (keys, values, counts), 0)
    assert numbers["count_mismatch"] >= 1, numbers
    verdict = checks.Verdict({"value_rel_err": 0.0, "count_mismatch": 0.0, "overflow": 0.0})
    assert not verdict.add(numbers)


def test_keyed_rows_in_any_order_read_as_their_dense_table():
    rng = np.random.default_rng(3)
    n = 64
    ref_c = rng.integers(0, 5, n).astype(np.float64)
    ref_v = (ref_c * 2.5)[:, None]
    v, c = ref_v.astype(np.float32), ref_c.astype(np.float32)
    v[7] *= 1.0 + 2e-6
    c[9] += 1
    dense = compare_dense(v, c, 0, ref_v, ref_c)
    assert dense == compare_by_position(v, c, 0, ref_v, ref_c)
    order, ref_order = rng.permutation(n), rng.permutation(n)
    keyed = checks.compare((order, v[order], c[order]),
                           (ref_order, ref_v[ref_order], ref_c[ref_order]), 0)
    assert keyed == dense
    assert dense["count_mismatch"] == 1 and dense["value_rel_err"] > 0


@pytest.mark.parametrize("chips", [1, 4])
def test_dense_check_numbers_are_those_by_position(chips):
    """The Q15 cells are read densely: their check numbers stay bit for bit
    what the comparison by position gave, on sound and on control outputs."""
    cell = tiny_cell(chips=chips)
    job = layout.job_module(cell)
    for batch in host_pool(cell, 2**31 + 23):
        ref = job.reference(batch, 16384)
        for out in (ref, job.control(batch, 16384)):
            values, counts = (np.asarray(a, np.float32) for a in out)
            assert (compare_dense(values, counts, 0, *ref)
                    == compare_by_position(values, counts, 0, *ref))


def keyed(job):
    """Q15's job kind with its rows named by key, as a kind whose engine
    reduces per key would name them: the program's rows are the clusters
    that reduced a pair, the reference's the suppliers with a pair."""
    def outputs(result, num_groups):
        keys = np.flatnonzero(result.counts)
        return keys, result.values[keys], result.counts[keys]

    def reference(batch, num_groups):
        values, counts = job.reference(batch, num_groups)
        keys = np.flatnonzero(counts)
        return keys, values[keys], counts[keys]

    kind = {k: getattr(job, k) for k in dir(job) if not k.startswith("_")}
    return types.SimpleNamespace(**dict(kind, outputs=outputs, reference=reference))


@pytest.mark.parametrize("fault", ["", "keys_merged", "half_batch_dropped"])
def test_keyed_job_kind_through_a_whole_run(monkeypatch, fault):
    """A job kind with ``outputs`` is read by key in a run: sound outputs
    are correct, and suppliers merged in pairs by the fold into clusters
    fail ``count_mismatch``."""
    dense = layout.job_module
    monkeypatch.setattr(layout, "job_module", lambda cell: keyed(dense(cell)))
    if fault == "keys_merged":
        from repro.core import mapreduce as mr

        orig = mr._phase_a_shard

        def phase_a(shard, map_fn, **kw):
            def merged(x):
                key, value, valid = map_fn(x)
                return key - (key % 2), value, valid
            return orig(shard, merged, **kw)
        monkeypatch.setattr(mr, "_phase_a_shard", phase_a)
    elif fault:
        _patch(monkeypatch, fault)
    result = run_tiny(seconds=1.0)
    assert result["attempted"] >= 1
    assert result["correct"] == (fault == ""), result["checks"]
    if fault == "keys_merged":
        assert result["checks"]["count_mismatch"]["value"] >= 10
