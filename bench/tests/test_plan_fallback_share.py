"""``plan_fallback_share``: the share of fresh plans that ran OS4M's BSS DP,
read from small hand-made traces."""

from __future__ import annotations

import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import layout  # noqa: E402
import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

PLAN_BSS = "os4m.plan.bss"


def _read(run):
    return layout.metric_module("plan_fallback_share").read(run)


def _run(plans):
    """One batch of 100 ns per entry of ``plans``: None reuses the plan,
    False plans with LPT certified, True plans with the BSS DP."""
    host = [(tr.WINDOW_SPAN, 0, 100 * len(plans))]
    for i, dp in enumerate(plans):
        t = 100 * i
        host.append((ps.BATCH, t + 1, t + 99))
        if dp is not None:
            host += [(ps.PLAN, t + 10, t + 60), ("os4m.plan.assign", t + 20, t + 50)]
        if dp:
            host.append((PLAN_BSS, t + 30, t + 45))
    trace = tr.Trace([tr.Device(0, [], [("fusion", 60, 80)])],
                     sorted(host, key=lambda e: (e[1], -e[2])), (0, 100 * len(plans)))
    return tr.TracedRun(trace, [{"reused": dp is None} for dp in plans], [])


@pytest.fixture
def engine_names_span(monkeypatch):
    """The engine's span list as a run that loaded it holds it."""
    monkeypatch.setitem(sys.modules, "repro.core.spans",
                        types.SimpleNamespace(PLAN="os4m.plan", PLAN_BSS=PLAN_BSS))


@pytest.mark.parametrize("plans, share", [
    ((True, False, None, False), 100.0 / 3),
    ((False, False), 0.0),
    ((True, True), 100.0),
])
def test_share_of_plans_that_ran_the_dp(engine_names_span, plans, share):
    assert _read(_run(plans)) == pytest.approx(share)


def test_no_fresh_plan_is_no_reading(engine_names_span):
    assert _read(_run((None, None))) is None


def test_no_spans_is_no_reading(engine_names_span):
    run = _run((True, False))
    run.trace.host = [iv for iv in run.trace.host if not iv[0].startswith("os4m.")]
    assert _read(run) is None


def test_engine_without_the_span_is_no_reading(monkeypatch):
    """An engine whose ``"os4m"`` always runs the DP names no such span:
    its trace would read 0%, which is no reading of it."""
    monkeypatch.setitem(sys.modules, "repro.core.spans",
                        types.SimpleNamespace(PLAN="os4m.plan"))
    assert _read(_run((False, False))) is None
    monkeypatch.delitem(sys.modules, "repro.core.spans")
    assert _read(_run((False, False))) is None


def test_reads_the_real_span_list():
    sys.path.insert(0, str(BENCH.parent / "src"))
    from repro.core import spans

    assert spans.PLAN_BSS == PLAN_BSS
    assert _read(_run((True, False))) == pytest.approx(50.0)
