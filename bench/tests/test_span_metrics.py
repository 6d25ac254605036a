"""The per-layer metrics that read the engine's host spans, and the span report.

Checked against small hand-made traces, against the trace kept from before
the engine recorded spans (every reader gives ``None`` there), and against
traces recorded on TPU v5e chips by

    python3 bench/run.py --workload <cell> --seed <n> --seconds 5 \\
        --trace 1 --trace-out <dir>

kept gzipped in ``bench/testdata`` with the result line each run printed.
"""

from __future__ import annotations

import dataclasses
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH / "tools"))

import layout  # noqa: E402
import program_spans as ps  # noqa: E402
import span_report  # noqa: E402
import trace_reduce as tr  # noqa: E402

DATA = BENCH / "testdata"
SPAN_METRICS = ("host_plan_ms", "drift_check_ms", "host_pull_ms", "host_merge_ms")
# cell -> the stem of its recorded trace with spans
RECORDED = {"q15-skew.1chip": "q15-skew.1chip.spans", "q15-skew.4chip": "q15-skew.4chip"}


def _read(name, run):
    return layout.metric_module(name).read(run)


def test_disjoint_and_overlap():
    assert ps.disjoint([("a", 5, 8), ("b", 0, 2), ("c", 1, 3), ("d", 8, 9)]) == [(0, 3), (5, 9)]
    assert ps.overlap_ns([(0, 3), (5, 9)], [(2, 6), (8, 20)]) == 1 + 1 + 1
    assert ps.overlap_ns([], [(0, 1)]) == 0


def _hand_made(batches=({"reused": False}, {"reused": True}), drop=None):
    """Two batches of 100 ns on one chip busy 20-40 and 120-140; times in ns."""
    host = [(tr.WINDOW_SPAN, 0, 200)]
    for b, t in ((0, 0), (1, 100)):
        host += [("bench.batch", t, t + 100), (ps.BATCH, t + 1, t + 99),
                 (ps.DECIDE, t + 2, t + 44),           # waits on phase A (20-40)
                 (ps.STATS_PULL, t + 44, t + 46),
                 ("os4m.phase_b", t + 70, t + 72),
                 (ps.OUTPUT_PULL, t + 72, t + 90)]      # no device op: all idle
        if b == 0:
            host += [(ps.PLAN, t + 46, t + 70), ("os4m.plan.assign", t + 50, t + 60)]
        host.append((ps.MERGE, t + 90, t + 98))
    host = [iv for iv in host if iv != drop]
    ops = [("fusion", 20, 40), ("fusion", 120, 140)]
    trace = tr.Trace([tr.Device(0, [], ops)], sorted(host, key=lambda e: (e[1], -e[2])),
                     (0, 200))
    return tr.TracedRun(trace, list(batches), [])


def test_hand_made_readings():
    run = _hand_made()
    ns = 1e-6 / 2  # ns per batch, in ms
    assert _read("host_plan_ms", run) == pytest.approx(24 * ns)
    assert _read("host_merge_ms", run) == pytest.approx(2 * 8 * ns)
    # decide t+2..t+44 with the chip busy t+20..t+40: 18 + 4 idle a batch
    assert _read("drift_check_ms", run) == pytest.approx(2 * 22 * ns)
    # stats pull 2 + output pull 18 per batch, all idle
    assert _read("host_pull_ms", run) == pytest.approx(2 * 20 * ns)


def test_no_batch_span_is_no_reading():
    run = _hand_made()
    run.trace.host = [iv for iv in run.trace.host if not iv[0].startswith("os4m.")]
    assert ps.read(run) is None
    assert all(_read(name, run) is None for name in SPAN_METRICS)


def test_batch_count_mismatch_raises():
    run = _hand_made(batches=({"reused": False},) * 3)
    with pytest.raises(tr.TraceError, match="3 batches"):
        ps.read(run)


def test_replanned_batch_without_plan_raises():
    with pytest.raises(tr.TraceError, match="batch 1 .* no 'os4m.plan'"):
        ps.read(_hand_made(batches=({"reused": False}, {"reused": False})))
    with pytest.raises(tr.TraceError, match="batch 0"):
        ps.read(_hand_made(drop=(ps.PLAN, 46, 70)))


def test_trace_without_spans_gives_no_readings():
    """The trace kept from the engine before it recorded spans."""
    trace = tr.load(DATA / "q15-skew.1chip.xplane.pb.gz")
    result = json.loads((DATA / "q15-skew.1chip.result.json").read_text())
    run = tr.TracedRun(trace, [{"reused": False}] * result["attempted"], result["floors"])
    for name in SPAN_METRICS:
        assert _read(name, run) is None, name


@pytest.fixture(scope="module", params=sorted(RECORDED))
def recorded(request):
    stem = RECORDED[request.param]
    path = DATA / f"{stem}.xplane.pb.gz"
    result = json.loads((DATA / f"{stem}.result.json").read_text())
    return request.param, path, tr.load(path), result


def _run(trace, result, reused=True):
    return tr.TracedRun(trace, [{"reused": reused}] * result["attempted"], result["floors"])


def test_recorded_trace_gives_the_metrics_the_run_printed(recorded):
    cell, _, trace, result = recorded
    run = _run(trace, result)
    for spec in layout.load_cell(cell).per_layer:
        assert spec["name"] in result["metrics"], spec["name"]
        value = _read(spec["name"], run)
        if spec["name"] == "replan_share":
            continue  # a count of the run's records, not of the trace
        assert value == pytest.approx(result["metrics"][spec["name"]]["value"], rel=1e-9)
    for name in SPAN_METRICS:
        assert 0 <= result["metrics"][name]["value"] < 1e3 * result["device"]["window_s"]
    assert tr.breakdown(trace) == result["breakdown"]


def test_recorded_trace_has_one_plan_per_batch_and_raises_without_one(recorded):
    _, _, trace, result = recorded
    plans = [iv for iv in trace.host if iv[0] == ps.PLAN]
    assert len(plans) == result["attempted"]  # every batch of these cells replans
    assert ps.read(_run(trace, result, reused=False)) is not None
    dropped = dataclasses.replace(
        trace, host=[iv for iv in trace.host if iv is not plans[len(plans) // 2]])
    with pytest.raises(tr.TraceError, match="no 'os4m.plan'"):
        ps.read(_run(dropped, result, reused=False))


def test_recorded_idle_is_under_named_spans(recorded):
    """The spans and the device ops share one clock: the four readings hold
    most of the device's idle time, and almost no idle gap is labelled by
    a host span that is not one of the engine's steps."""
    _, _, trace, result = recorded
    idle_ms = (result["device"]["window_s"] - result["device"]["busy_s"]) * 1e3
    per_batch = idle_ms / result["attempted"]
    assert sum(result["metrics"][n]["value"] for n in SPAN_METRICS) >= 0.85 * per_batch
    gaps = tr.breakdown(trace, top=10**6)["idle_gaps"]
    unnamed = sum(s for label, s in gaps
                  if not any(p.startswith("os4m.") and p != ps.BATCH
                             for p in label.split(" > ")))
    assert unnamed <= 0.10 * sum(s for _, s in gaps)


def test_span_report_reads_spans_counters_and_scopes(recorded):
    _, path, trace, result = recorded
    rep = span_report.report(path)
    assert len(rep["batches"]) == result["attempted"] and rep["chips"]
    for bt in rep["batches"]:
        names = [s["name"] for s in bt["spans"]]
        assert names[0] == ps.BATCH and ps.PLAN in names and ps.MERGE in names
        plan = bt["spans"][names.index(ps.PLAN)]
        assert 0 < plan["counters"]["valid_pairs"] < plan["counters"]["input_pairs"]
        assert all(s["counters"]["bytes"] > 0 for s in bt["spans"]
                   if s["name"] in (ps.STATS_PULL, ps.OUTPUT_PULL))
        assert all(s["idle_ms"] <= s["host_ms"] + 1e-9 for s in bt["spans"])
        for scopes in bt["scopes"].values():
            assert all(scopes[s] > 0 for s in span_report.SCOPES), scopes
    assert rep["reduce_max_over_mean"] >= 1.0


def test_span_report_refuses_a_trace_without_spans():
    with pytest.raises(tr.TraceError, match="os4m.batch"):
        span_report.report(DATA / "q15-skew.1chip.xplane.pb.gz")
