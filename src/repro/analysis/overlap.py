"""Overlap certifier: the §4.4 copy/run contract + honest wave stamps.

Two rules over each traced phase-B graph:

**a2a-depends-on-a2a** — the pipelined engine's whole speedup is that
the all-to-all "copy" of chunk ``c+1`` is in flight while the "run" of
chunk ``c`` computes. That overlap exists iff XLA is *free* to schedule
them concurrently, i.e. iff no all-to-all equation transitively consumes
another all-to-all's output: every reduce of chunk ``c`` depends on
chunk ``c``'s all-to-all, so a ``reduce(c) → copy(c+1)`` edge would show
up as exactly such a path. On violation the finding's evidence is the
offending dependency chain, one equation per line.

**stamp-unanchored / stamp-pass-through-dropped** — a wave-timer stamp is
only honest if true buffer dependencies pin it on both sides (PR 5's
lesson: ``optimization_barrier`` and value-anchored pure callbacks do
not constrain XLA:CPU's latest-possible scheduler). Statically: every
stamp callback must (a) have an all-to-all among its ancestors — it
cannot fire before its wave's data exists — and (b) have its
*pass-through* output (output slot 0) on a path to the program's primary
outputs — the scheduler cannot defer it past the compute it precedes,
and the engine actually consumed the passed buffer rather than the
original (the "dropped stamp dependency" mutation).
"""

from __future__ import annotations

from typing import List, Sequence

from repro.analysis.jaxpr_graph import EqnGraph
from repro.analysis.report import Finding

_STAMP_PRIMS = ("io_callback", "pure_callback")


def check_overlap(targets: Sequence) -> List[Finding]:
    """Run both overlap rules over every traced target."""
    findings: List[Finding] = []
    for t in targets:
        findings.extend(_check_a2a_independence(t.name, t.graph))
        if t.timed:
            findings.extend(_check_stamps(t.name, t.graph))
    return findings


def _check_a2a_independence(name: str, g: EqnGraph) -> List[Finding]:
    findings: List[Finding] = []
    a2a_ids = [n.id for n in g.by_prim("all_to_all")]
    a2a_set = set(a2a_ids)
    for src in a2a_ids:
        hit = g.reachable_from([src]) & a2a_set
        if not hit:
            continue
        dst = min(hit)
        chain = g.find_path(src, dst)
        findings.append(Finding(
            checker="overlap",
            rule="a2a-depends-on-a2a",
            target=name,
            summary=(
                "an all_to_all transitively consumes another all_to_all's "
                "output — the next chunk's copy is serialized behind this "
                "chunk's pipeline (§4.4 overlap broken)"),
            evidence=g.describe_path(chain),
        ))
    return findings


def _check_stamps(name: str, g: EqnGraph) -> List[Finding]:
    findings: List[Finding] = []
    a2a_ids = {n.id for n in g.by_prim("all_to_all")}
    # Primary outputs = the reduce values + counts (slots 0 and 1); the
    # ticks output must NOT be what keeps a stamp alive.
    primary = g.output_producer_ids([0, 1])
    for s in (n for n in g.nodes if n.prim in _STAMP_PRIMS):
        ancestors = g.ancestors_of(s.id)
        if not (ancestors & a2a_ids):
            findings.append(Finding(
                checker="overlap",
                rule="stamp-unanchored",
                target=name,
                summary=(
                    "a wave-timer stamp has no all_to_all among its "
                    "ancestors — it can fire before its wave's data "
                    "exists"),
                evidence=[s.describe(),
                          "ancestor set contains no all_to_all equation"],
            ))
        # Pass-through pinning: output slot 0 (the passed primary buffer)
        # must feed the downstream compute — directly a primary output,
        # or on a path to one of its producers.
        direct = any(out is not None and out[0] == s.id and out[1] == 0
                     for out in (g.outputs[i] for i in (0, 1)
                                 if i < len(g.outputs)))
        consumers = g.consumers_of_output(s.id, 0)
        reach = set(consumers) | g.reachable_from(list(consumers))
        if not direct and not (reach & primary):
            findings.append(Finding(
                checker="overlap",
                rule="stamp-pass-through-dropped",
                target=name,
                summary=(
                    "a wave-timer stamp's pass-through output never "
                    "reaches the primary outputs — downstream compute "
                    "consumed the original buffer, so the scheduler may "
                    "defer the stamp past the wave it should precede"),
                evidence=[s.describe(),
                          f"pass-through consumers: "
                          f"{[g.nodes[c].describe() for c in consumers] or 'none'}",
                          "none of them reach output 0/1 producers"],
            ))
    return findings
