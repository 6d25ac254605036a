"""Tests for the static contract analyzer (src/repro/analysis).

Three layers, mirroring the analyzer's own proof obligations:

* **Real targets are green** — every traced phase-B variant the repo
  ships (vmap + shard_map, kernel path, measured stamps, fenced waves)
  and every real planner snapshot must produce zero findings: the
  analyzer certifies the shipped engine, it does not cry wolf.
* **Mutations are caught** — each seeded violation must be caught by the
  *intended* checker with the *intended* rule and non-empty evidence
  (an analyzer that has never failed anything proves nothing).
* **Properties** — the plan validator accepts whatever the real planner
  emits across random histograms and speed vectors (including dead
  slots).
"""

import io

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax

from repro.analysis import (
    conventions,
    determinism,
    mutations,
    overlap,
    plan_checks,
    allowlist,
)
from repro.analysis import jaxpr_graph as jg
from repro.analysis import targets as tgt
from repro.analysis.__main__ import run as run_analysis
from repro.analysis.report import CHECKER_BITS, Finding, Report
from repro.core import mapreduce as mr


@pytest.fixture(scope="module")
def phase_b():
    return tgt.phase_b_targets()


@pytest.fixture(scope="module")
def plans():
    return tgt.plan_targets()


# ---------------------------------------------------------------------------
# Real targets are green
# ---------------------------------------------------------------------------


class TestRealTargetsGreen:
    def test_variant_coverage(self, phase_b):
        names = {t.name for t in phase_b}
        expected = {
            "sequential", "pipelined", "pipelined-kernels",
            "timed-sequential", "timed-pipelined",
            "checkpointed-wave-copy", "checkpointed-wave-run",
        }
        assert expected <= names
        if len(jax.devices()) >= tgt.M:
            assert "shard_map-pipelined" in names

    def test_overlap_clean(self, phase_b):
        findings = overlap.check_overlap(phase_b)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_determinism_clean(self, phase_b):
        findings = determinism.check_determinism(phase_b)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_plans_clean(self, plans):
        names = {name for name, _ in plans}
        assert {"lpt-uniform", "os4m-pipelined", "lpt-straggler",
                "lpt-dead-slot"} <= names
        findings = plan_checks.check_plans(plans)
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_conventions_clean(self):
        findings = conventions.lint_tree(conventions.default_root())
        assert findings == [], "\n".join(f.render() for f in findings)

    def test_timed_targets_contain_stamps(self, phase_b):
        timed = [t for t in phase_b if t.timed]
        assert timed, "no timed variants traced"
        for t in timed:
            assert t.graph.by_prim("io_callback"), t.name



# ---------------------------------------------------------------------------
# Mutation suite: every seeded violation caught, with evidence
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case", mutations._CASES, ids=[c[0] for c in mutations._CASES])
def test_mutation_caught_by_intended_checker(case):
    name, checker, rule, fn = case
    findings = fn()
    hits = [f for f in findings if f.checker == checker and f.rule == rule]
    assert hits, (f"{name}: expected [{checker}:{rule}], got "
                  + ("; ".join(f"[{f.checker}:{f.rule}]" for f in findings)
                     or "nothing"))
    for f in hits:
        assert len(f.evidence) > 0, f"{name}: finding carries no evidence"
        assert f.render().count("\n") >= 1, "evidence must render as lines"


def test_self_test_harness_roll_up():
    results = mutations.run_self_tests()
    assert mutations.self_tests_ok(results)
    assert len(results) == len(mutations._CASES)
    checkers = {r.checker for r in results}
    assert checkers == {"overlap", "determinism", "plan", "conventions"}


# ---------------------------------------------------------------------------
# Graph + report unit behaviour
# ---------------------------------------------------------------------------


class TestEqnGraph:
    def test_sorts_found_inside_pjit(self):
        """jnp.argsort lowers into a jit sub-jaxpr; the flattened graph
        must still expose the sort equation (and its stability flag)."""
        import jax.numpy as jnp

        def body(x):
            return x[jnp.argsort(x[:, 0], stable=True)]

        closed = jg.trace_sharded(
            body, (jax.ShapeDtypeStruct((4, 8), jnp.float32),), mr.AXIS, 4)
        g = jg.EqnGraph(closed)
        sorts = g.by_prim("sort")
        assert sorts and sorts[0].eqn.params["is_stable"] is True
        assert not any(n.prim in jg._INLINE_PRIMS for n in g.nodes)

    def test_nested_call_primitive_is_inlined(self):
        """Pins the installed jax's nested-call primitive name: if jax
        renames it again, the graph would silently stop seeing sorts."""
        import jax.numpy as jnp

        closed = jax.make_jaxpr(jnp.argsort)(jnp.arange(4))
        (eqn,) = closed.jaxpr.eqns
        assert eqn.primitive.name == "jit"
        assert eqn.primitive.name in jg._INLINE_PRIMS

    def test_path_evidence_is_readable(self):
        import jax.numpy as jnp
        from jax import lax

        def body(x):
            a = lax.all_to_all(x, mr.AXIS, 0, 0)
            b = lax.all_to_all(a * 2.0, mr.AXIS, 0, 0)
            return b

        g = jg.EqnGraph(jg.trace_sharded(
            body, (jax.ShapeDtypeStruct((4, 8), jnp.float32),), mr.AXIS, 4))
        a2a = [n.id for n in g.by_prim("all_to_all")]
        chain = g.find_path(a2a[0], a2a[1])
        assert chain[0] == a2a[0] and chain[-1] == a2a[1]
        lines = g.describe_path(chain)
        assert len(lines) == len(chain)
        assert "all_to_all" in lines[0] and "all_to_all" in lines[-1]

    def test_resolve_callback_unwraps_registered_body(self):
        from repro.kernels.wave_timer import ops as wt_ops

        qual = allowlist.qualname_of(wt_ops._host_stamp)
        assert allowlist.is_allowed(qual)
        assert qual.endswith("._host_stamp")

    def test_wave_timer_bodies_registered(self):
        names = allowlist.allowed_names()
        assert any(n.endswith("._host_stamp") for n in names)
        assert any(n.endswith("._host_stamp_through") for n in names)
        assert any(n.endswith("._host_ticks") for n in names)


class TestReport:
    def test_exit_code_is_bitmask(self):
        r = Report()
        r.extend("overlap", [Finding("overlap", "r", "t", "s", ["e"])])
        r.extend("plan", [Finding("plan", "r", "t", "s", ["e"])])
        r.extend("determinism", [])
        assert r.exit_code() == CHECKER_BITS["overlap"] | CHECKER_BITS["plan"]
        assert not r.ok

    def test_unknown_checker_rejected(self):
        with pytest.raises(ValueError):
            Finding("typo", "r", "t", "s")

    def test_render_names_failures(self):
        r = Report()
        r.extend("overlap", [])
        r.extend("plan", [Finding("plan", "dead-slot-loaded", "t", "s", ["e"])])
        text = r.render()
        assert "overlap" in text and "ok" in text
        assert "[plan:dead-slot-loaded]" in text


# ---------------------------------------------------------------------------
# Plan-validator properties (real planner across random inputs)
# ---------------------------------------------------------------------------


def _snapshot(m, n, seed, speeds=None, chunks=1):
    cfg = mr.MapReduceConfig(
        num_slots=m, num_clusters=n, scheduler="lpt",
        pipeline_chunks=chunks, speeds=speeds)
    job = mr.MapReduceJob(lambda s: s, cfg)
    rng = np.random.default_rng(seed)
    hist = rng.integers(1, 64, size=(m, n)).astype(np.float64)
    k = int(np.ceil(hist.sum(axis=1).max()))
    return job._plan(hist, hist.sum(axis=0), k)


class TestPlanProperties:
    @given(st.integers(2, 6), st.integers(6, 20), st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_random_plans_validate_clean(self, m, n, seed):
        snap = _snapshot(m, n, seed, chunks=min(3, n))
        assert plan_checks.validate_snapshot(snap, "prop") == []

    @given(st.integers(3, 6), st.integers(8, 20), st.integers(0, 10_000))
    @settings(max_examples=10, deadline=None)
    def test_dead_slot_plans_validate_clean(self, m, n, seed):
        """A dead slot (speed 0.0) must end up with exactly zero work —
        and the validator must agree that it did."""
        speeds = [1.0] * m
        speeds[seed % m] = 0.0
        snap = _snapshot(m, n, seed, speeds=tuple(speeds))
        assert plan_checks.validate_snapshot(snap, "prop-dead") == []
        dead = seed % m
        assert not np.any(np.asarray(snap.schedule.assignment) == dead)


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------


class TestCLI:
    def test_run_plan_checker_exits_zero(self):
        out = io.StringIO()
        assert run_analysis(check="plan", out=out) == 0
        text = out.getvalue()
        assert "plan" in text and "ok" in text

    def test_run_rejects_unknown_checker(self):
        with pytest.raises(ValueError):
            run_analysis(check="nonsense")

    def test_main_exits_with_bitmask_zero(self):
        from repro.analysis.__main__ import main

        with pytest.raises(SystemExit) as ei:
            main(["--check", "plan"])
        assert ei.value.code == 0
