"""The reduction from a profiler trace to per-layer metrics.

Checked against a trace recorded on a TPU v5e chip by

    python3 bench/run.py --workload q15-skew.1chip --seed <n> --seconds 5 \
        --trace 1 --trace-out <dir>

kept gzipped in ``bench/testdata`` with the result line each run printed,
and against small hand-made intervals.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import floors  # noqa: E402
import layout  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = BENCH / "testdata"
RECORDED_CELLS = ("q15-skew.1chip",)


def test_union_and_gaps():
    ivs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert tr.union_ns(ivs) == 25
    assert tr.idle_gaps(ivs, (0, 40)) == [(15, 20), (30, 40)]
    assert tr.union_ns(tr.clip(ivs, (8, 24))) == 11


def test_host_labels():
    spans = tr.HostSpans([(tr.WINDOW_SPAN, 0, 100), ("bench.batch", 10, 50),
                          ("PjitFunction(phase_b)", 20, 30), ("bench.batch", 60, 90)])
    assert spans.label(25) == "bench.batch > PjitFunction(phase_b)"
    assert spans.label(40) == "bench.batch"
    assert spans.label(55) == "(no host span)"


def test_unmatched_name_is_an_error():
    with pytest.raises(tr.TraceError, match="no XLA Ops event matches"):
        tr.matching([("fusion.1", 0, 1)], r"all-to-all", "XLA Ops")


def test_floors_one_and_four_chips():
    peaks = layout.peaks("TPU v5 lite")
    groups = np.array([[1, 1, 2, 3, 0], [1, 2, 2, 3, 1]])
    valid = np.array([[1, 1, 1, 1, 0], [1, 1, 1, 1, 0]], bool)  # the last rows are filtered out
    one = floors.phase_b_floor(groups, valid, num_shards=2, num_groups=4, value_dim=1,
                               num_reducers=2, chips=1, peaks=peaks)
    assert one["bound"] == "hbm" and one["ici_s"] == 0.0
    assert one["hbm_s"] == pytest.approx((8 * 8 + 2 * 4 * 4) / 819e9)
    two = floors.phase_b_floor(groups, valid, num_shards=2, num_groups=4, value_dim=1,
                               num_reducers=2, chips=2, peaks=peaks)
    # group 1: 2 + 1 rows, 1 must move; group 2: 1 + 2, 1 moves; group 3: 1 + 1, 1 moves
    assert two["ici_s"] == pytest.approx(3 * 8 / (2 * 200e9))


def _leaving_by_group_id(groups, valid, num_shards, num_groups, chips):
    """The pairs that must leave their chip, counted over the job's group ids
    as they come, one bin a (chip, id): how the floor was counted before it
    took dense ranks."""
    groups = np.asarray(groups).reshape(num_shards, -1)
    chip_of_shard = np.arange(num_shards) * chips // num_shards
    flat = chip_of_shard[:, None] * num_groups + groups
    per_chip = np.bincount(flat[valid], minlength=chips * num_groups)
    per_chip = per_chip.reshape(chips, num_groups)
    return int(per_chip.sum() - per_chip.max(axis=0).sum())


@pytest.mark.parametrize("cell_name", ["q15-skew.1chip", "q15-skew.4chip"])
def test_q15_floors_are_those_by_group_id(cell_name):
    """Dense ranks leave both Q15 cells' floors bit for bit as they were."""
    import test_checks

    cell = layout.load_cell(cell_name)
    chips, slots = cell.chips, int(cell.config["engine"]["num_slots"])
    tiny = test_checks.tiny_cell(cell_name, chips=chips)
    job = layout.job_module(tiny)
    peaks = layout.peaks("TPU v5 lite")
    for batch in test_checks.host_pool(tiny, 2**31 + 61):
        groups, valid = job.group_ids(batch), job.valid(batch)
        got = floors.phase_b_floor(groups, valid, num_shards=slots, num_groups=16384,
                                   value_dim=1, num_reducers=slots, chips=chips, peaks=peaks)
        leaving = _leaving_by_group_id(groups, valid, slots, 16384, chips)
        assert (leaving > 0) == (chips > 1)
        ici_s = leaving * floors.pair_bytes(1) / (chips * peaks["ici_bytes_per_s"])
        assert got["ici_s"] == ici_s
        if chips > 1:
            assert got["bound"] == ("ici" if ici_s > got["hbm_s"] else "hbm")


def test_floors_take_hashed_31_bit_group_ids():
    """Hashed keys: the ICI count allocates bins for the groups present, not
    for every 31-bit id (4 chips x 2^31 bins would be 64 GiB)."""
    import tracemalloc

    rng = np.random.default_rng(8)
    words = rng.integers(0, 1 << 31, 5000)
    groups = words[rng.integers(0, 5000, (4, 1 << 14))]
    valid = rng.random(groups.shape) < 0.9
    tracemalloc.start()
    try:
        got = floors.phase_b_floor(groups, valid, num_shards=4, num_groups=5000, value_dim=1,
                                   num_reducers=4, chips=4, peaks=layout.peaks("TPU v5 lite"))
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak < 16 << 20
    per_group = {}
    for chip in range(4):
        for g in groups[chip][valid[chip]].tolist():
            per_group.setdefault(g, [0] * 4)[chip] += 1
    leaving = sum(sum(c) - max(c) for c in per_group.values())
    assert got["ici_s"] == leaving * floors.pair_bytes(1) / (4 * 200e9)


@pytest.fixture(scope="module", params=RECORDED_CELLS)
def recorded(request):
    trace = tr.load(DATA / f"{request.param}.xplane.pb.gz")
    return trace, json.loads((DATA / f"{request.param}.result.json").read_text())


def test_recorded_trace_has_the_device_and_window(recorded):
    trace, result = recorded
    assert len(trace.devices) == result["device"]["count"]
    assert trace.window_ns * 1e-9 == pytest.approx(result["device"]["window_s"], rel=1e-9)
    batches = [e for e in trace.host if e[0] == "bench.batch"]
    assert len(batches) == result["attempted"]
    busy = tr.busy_ns(trace)
    assert all(0 < b < trace.window_ns for b in busy)
    assert sum(busy) / len(busy) * 1e-9 == pytest.approx(result["device"]["busy_s"], rel=1e-9)


def test_recorded_trace_gives_the_metrics_the_run_printed(recorded):
    trace, result = recorded
    batches = [{"reused": True} for _ in range(result["attempted"])]
    run = tr.TracedRun(trace, batches, result["floors"])
    for name, metric in result["metrics"].items():
        if name == "replan_share":
            continue
        value = layout.metric_module(name).read(run)
        assert value == pytest.approx(metric["value"], rel=1e-9), name
    assert 0 < run.trace_module_ns(r"^jit_phase_b\b")[0] < trace.window_ns
    assert 0 < result["metrics"]["phase_b_roofline"]["value"] <= 100


def test_recorded_trace_breakdown(recorded):
    trace, result = recorded
    bd = tr.breakdown(trace)
    assert 1 <= len(bd["device_ops"]) <= 10 and 1 <= len(bd["idle_gaps"]) <= 10
    recorded_s = [v for _, v in result["breakdown"]["device_ops"]]
    assert [v for _, v in bd["device_ops"]] == pytest.approx(recorded_s, rel=1e-9)
    assert bd["device_ops"][0][0] == "jit_phase_b/while.10"
    assert all("/" in name for name, _ in bd["device_ops"])
    assert bd["idle_gaps"] == result["breakdown"]["idle_gaps"]


def test_recorded_trace_missing_module_is_an_error():
    trace = tr.load(DATA / "q15-skew.1chip.xplane.pb.gz")
    with pytest.raises(tr.TraceError, match="jit_phase_z"):
        tr.module_ns(trace, r"^jit_phase_z\b")
    with pytest.raises(tr.TraceError, match="no XLA Ops event matches"):
        tr.matching(trace.devices[0].ops, r"^all[-_]to[-_]all", "XLA Ops")
