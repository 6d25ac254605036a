"""Mutation self-tests: seeded violations every checker must catch.

A static analyzer that has never seen a violation is indistinguishable
from one that checks nothing. Each case here *constructs* a known-bad
program / plan / source file — the exact bug class a checker claims to
certify against — runs only the analyzer (never the mutant), and demands
a finding from the intended checker, with the intended rule, carrying
non-empty evidence:

* a phase-B body whose second all-to-all consumes the first's output
  (the §4.4 overlap killer);
* a wave-timer stamp whose pass-through buffer is dropped, and one with
  no all-to-all anchor;
* an unstable sort ordering all-to-all output (wire contract);
* an unregistered host callback;
* a kernel builder whose block size derives from the slab length
  (PR 8 bug class);
* plans with a duplicated rank, an out-of-range chunk id, a
  double-placed cluster, a loaded dead slot, undersized chunk caps
  (exact *and* sketch-planned — the latter exercises the count-min
  estimate floor), a sketch snapshot stripped of both the
  overestimate-only claim and the escape hatch, and a lossy JSON
  snapshot;
* source files with a jitted ``time.time()``, a default-stability wire
  sort, and an unmarked callback call site.

``run_self_tests()`` is wired into ``--self-test`` and the CI gate: a
checker that goes blind fails the build, not just the review.
"""

from __future__ import annotations

import dataclasses
import pathlib
import tempfile
import textwrap
from typing import Callable, List, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback

from repro.analysis import conventions, determinism, overlap, plan_checks
from repro.analysis import jaxpr_graph as jg
from repro.analysis.report import Finding
from repro.core import mapreduce as mr

_M = 4  # mutant mesh size


@dataclasses.dataclass
class SelfTestResult:
    """One mutation case: did the intended checker catch it with evidence?"""

    name: str
    checker: str
    rule: str
    caught: bool
    findings: List[Finding]

    def render(self) -> str:
        mark = "caught" if self.caught else "MISSED"
        return f"{mark:7s} {self.name} -> [{self.checker}:{self.rule}]"


def _fake_target(name: str, body, args, timed=False):
    """Trace a mutant per-shard body into a TracedTarget-shaped object."""
    from repro.analysis.targets import TracedTarget

    closed = jg.trace_sharded(body, args, mr.AXIS, _M)
    return TracedTarget(name, jg.EqnGraph(closed), timed=timed)


def _x44():
    return (jax.ShapeDtypeStruct((_M, 8), jnp.float32),)


# --------------------------------------------------------------------------
# Jaxpr mutants
# --------------------------------------------------------------------------


def _mutant_a2a_chain():
    """Second all-to-all data-depends on the first: overlap is impossible."""

    def body(x):
        a = lax.all_to_all(x, mr.AXIS, 0, 0)
        b = lax.all_to_all(x + jnp.sum(a) * 0, mr.AXIS, 0, 0)
        return a + b

    t = _fake_target("mutant-a2a-chain", body, _x44())
    return overlap.check_overlap([t])


def _mutant_stamp_dropped():
    """Stamp's pass-through buffer discarded: downstream reads the original."""
    from repro.kernels.wave_timer import ops as wt_ops

    def body(x):
        y = lax.all_to_all(x, mr.AXIS, 0, 0)
        passed, ticks = wt_ops.stamp_through(y)
        out = jnp.sum(y)          # BUG: consumes y, not passed
        return out, out * 0, ticks

    with wt_ops.force_backend("callback"):
        t = _fake_target("mutant-stamp-dropped", body, _x44(), timed=True)
    return overlap.check_overlap([t])


def _mutant_stamp_unanchored():
    """Stamp with no all-to-all ancestor: can fire before its wave exists."""
    from repro.kernels.wave_timer import ops as wt_ops

    def body(x):
        passed, ticks = wt_ops.stamp_through(x)   # BUG: pre-wave stamp
        y = lax.all_to_all(passed, mr.AXIS, 0, 0)
        out = jnp.sum(y)
        return y, out, ticks

    with wt_ops.force_backend("callback"):
        t = _fake_target("mutant-stamp-unanchored", body, _x44(), timed=True)
    return overlap.check_overlap([t])


def _mutant_unstable_sort():
    """stable=False on a sort ordering received (post-all-to-all) records."""

    def body(x):
        a = lax.all_to_all(x, mr.AXIS, 0, 0)
        order = jnp.argsort(a[:, 0], stable=False)   # BUG: ties reorder
        return a[order]

    t = _fake_target("mutant-unstable-sort", body, _x44())
    return determinism.check_determinism([t])


def _rogue_clock(x):
    """An UNREGISTERED host callback body (intentionally not allowlisted)."""
    return np.asarray(x)


def _mutant_rogue_callback():
    """io_callback to a body missing from the allowlist registry."""

    def body(x):
        shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return io_callback(_rogue_clock, shape, x)

    t = _fake_target("mutant-rogue-callback", body, _x44())
    return determinism.check_determinism([t])


def _mutant_slab_blocking():
    """Kernel builder whose block size tracks the slab length (PR 8 bug)."""
    from repro.kernels.segment_reduce.segment_reduce import (
        segment_reduce_sorted_pallas,
    )

    def build(n: int):
        def body(values, gather_idx, seg_ids):
            return segment_reduce_sorted_pallas(
                values[gather_idx], seg_ids, num_segments=8,
                block_tokens=max(8, n),          # BUG: length-derived block
                interpret=True)

        return jax.make_jaxpr(body)(
            jax.ShapeDtypeStruct((n, 3), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
            jax.ShapeDtypeStruct((n,), jnp.int32),
        )

    return determinism.check_slab_invariance(build)


# --------------------------------------------------------------------------
# Plan mutants
# --------------------------------------------------------------------------


def _mutant_rank_duplicate():
    from repro.core.pipeline import WavePlan

    plan = WavePlan(
        rank_of_cluster=np.array([0, 1, 1, 3], np.int32),   # BUG: rank 1 twice
        chunk_of_cluster=np.array([0, 0, 1, 1], np.int32),
        num_chunks=2)
    return plan_checks.validate_wave_plan(plan, 4, "mutant-rank-duplicate")


def _mutant_chunk_out_of_range():
    from repro.core.pipeline import WavePlan

    plan = WavePlan(
        rank_of_cluster=np.arange(4, dtype=np.int32),
        chunk_of_cluster=np.array([0, 1, 2, 1], np.int32),  # BUG: chunk 2 of 2
        num_chunks=2)
    return plan_checks.validate_wave_plan(plan, 4, "mutant-chunk-range")


def _mutant_double_placed():
    # BUG: cluster 2 rides in both waves, cluster 3 in none.
    return plan_checks.validate_membership(
        [[0, 2], [1, 2]], 4, "mutant-double-placed")


def _mutant_dead_slot_loaded():
    from repro.core.scheduler import Schedule

    sched = Schedule(                       # BUG: slot 2 is dead but loaded
        assignment=np.array([0, 1, 2, 3, 2], np.int32),
        num_slots=4, slot_speeds=(1.0, 1.0, 0.0, 1.0))
    return plan_checks.validate_schedule(sched, "mutant-dead-slot")


def _real_snapshot():
    from repro.analysis.targets import plan_targets

    return plan_targets()[0][1]


def _mutant_chunk_cap_undersized():
    snap = _real_snapshot()
    starved = dataclasses.replace(          # BUG: caps far below statistics
        snap, chunk_caps=tuple(1 for _ in snap.chunk_caps))
    return plan_checks.validate_snapshot(starved, "mutant-cap-undersized")


def _sketch_snapshot():
    from repro.analysis.targets import plan_targets

    for _name, snap in plan_targets():
        if snap.stats_provider == "sketch" and not snap.caps_estimated:
            return snap
    raise RuntimeError("no sketch plan target without estimated caps")


def _mutant_sketch_cap_undersized():
    snap = _sketch_snapshot()
    starved = dataclasses.replace(          # BUG: caps below the estimates
        snap, chunk_caps=tuple(1 for _ in snap.chunk_caps))
    return plan_checks.validate_snapshot(starved, "mutant-sketch-cap")


def _mutant_sketch_unguarded():
    snap = _sketch_snapshot()
    bare = dataclasses.replace(             # BUG: no guarantee, no hatch
        snap, stats_overestimate=False, caps_estimated=False)
    return plan_checks.validate_snapshot(bare, "mutant-sketch-unguarded")


def _mutant_lossy_snapshot():
    from repro.core.schedule_cache import CachedSchedule

    class _Lossy(CachedSchedule):
        def to_json(self):
            d = super().to_json()
            d.pop("slot_speeds")            # BUG: drops the Q||C_max speeds
            return d

    snap = _real_snapshot()
    lossy = _Lossy(**{f.name: getattr(snap, f.name)
                      for f in dataclasses.fields(snap)})
    return plan_checks.validate_roundtrip(lossy, "mutant-lossy-snapshot")


# --------------------------------------------------------------------------
# Source (AST) mutants
# --------------------------------------------------------------------------

_SRC_JIT_TIME = """
    import time
    import jax

    @jax.jit
    def scaled(x):
        return x * time.time()      # BUG: trace-time clock
"""

_SRC_WIRE_SORT = """
    import jax.numpy as jnp

    def encode(slab):
        return slab[jnp.argsort(slab[:, 0])]    # BUG: stability implicit
"""

_SRC_UNMARKED_CB = """
    import jax
    from jax.experimental import io_callback

    def _peek(x):
        return x

    def traced(x):
        shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return io_callback(_peek, shape, x)     # BUG: no marker comment
"""


def _lint_snippet(relpath: str, source: str):
    with tempfile.TemporaryDirectory() as tmp:
        path = pathlib.Path(tmp) / relpath
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(source))
        return conventions.lint_paths([path])


def _mutant_src_jit_time():
    return _lint_snippet("engine.py", _SRC_JIT_TIME)


def _mutant_src_wire_sort():
    return _lint_snippet("core/mapreduce.py", _SRC_WIRE_SORT)


def _mutant_src_unmarked_cb():
    return _lint_snippet("timers.py", _SRC_UNMARKED_CB)


# --------------------------------------------------------------------------
# Harness
# --------------------------------------------------------------------------

_CASES: Sequence = (
    ("a2a-dependency-chain", "overlap", "a2a-depends-on-a2a",
     _mutant_a2a_chain),
    ("stamp-pass-through-dropped", "overlap", "stamp-pass-through-dropped",
     _mutant_stamp_dropped),
    ("stamp-unanchored", "overlap", "stamp-unanchored",
     _mutant_stamp_unanchored),
    ("unstable-wire-sort", "determinism", "unstable-wire-sort",
     _mutant_unstable_sort),
    ("rogue-host-callback", "determinism", "undeclared-host-callback",
     _mutant_rogue_callback),
    ("slab-derived-blocking", "determinism", "slab-dependent-blocking",
     _mutant_slab_blocking),
    ("rank-duplicate", "plan", "rank-not-permutation",
     _mutant_rank_duplicate),
    ("chunk-out-of-range", "plan", "chunk-id-out-of-range",
     _mutant_chunk_out_of_range),
    ("cluster-double-placed", "plan", "cluster-not-placed-once",
     _mutant_double_placed),
    ("dead-slot-loaded", "plan", "dead-slot-loaded",
     _mutant_dead_slot_loaded),
    ("chunk-cap-undersized", "plan", "chunk-cap-undersized",
     _mutant_chunk_cap_undersized),
    ("sketch-cap-undersized", "plan", "chunk-cap-undersized",
     _mutant_sketch_cap_undersized),
    ("sketch-caps-unguarded", "plan", "sketch-caps-unguarded",
     _mutant_sketch_unguarded),
    ("lossy-snapshot", "plan", "snapshot-not-roundtrip",
     _mutant_lossy_snapshot),
    ("jitted-time-call", "conventions", "jit-rng-time",
     _mutant_src_jit_time),
    ("implicit-wire-sort", "conventions", "wire-sort-stability",
     _mutant_src_wire_sort),
    ("unmarked-callback", "conventions", "callback-marker",
     _mutant_src_unmarked_cb),
)


def run_self_tests(
        cases: Sequence = _CASES,
        progress: Callable[[str], None] = lambda _line: None,
) -> List[SelfTestResult]:
    """Run every mutation case; a case passes only with the intended
    checker + rule and non-empty evidence."""
    results: List[SelfTestResult] = []
    for name, checker, rule, fn in cases:
        findings = fn()
        caught = any(
            f.checker == checker and f.rule == rule and len(f.evidence) > 0
            for f in findings)
        r = SelfTestResult(name, checker, rule, caught, list(findings))
        progress(r.render())
        results.append(r)
    return results


def self_tests_ok(results: Sequence[SelfTestResult]) -> bool:
    """True when every mutation was caught by its intended checker."""
    return all(r.caught for r in results)
