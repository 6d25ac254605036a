"""Determinism linter: declared callbacks, stable wires, fixed blocking.

Three rules, each pinned to a bug class this repo has actually shipped
or explicitly designed against:

**undeclared-host-callback (D1)** — host callbacks are the one escape
hatch from jit purity (wall clocks, RNG, file IO all fit through it), so
every ``io_callback`` / ``pure_callback`` equation in a traced phase-B
program must resolve to a body registered in
:mod:`repro.analysis.allowlist`. Today that registry holds exactly the
two wave-timer stamp bodies.

**unstable-wire-sort (D2)** — the sequential, pipelined and fenced
executors are bit-identical only because each cluster's pairs reach its
reduce in the same (src shard, bucket position) order on every path,
and ties are common (the spill key is a (chunk, dest) group id). Any
``sort`` equation with ``is_stable=False`` that is entangled with the
wire — an ``all_to_all`` among its ancestors or its consumers — makes
that order permutation-dependent and is flagged with the connecting
path.

**slab-dependent-blocking (D3)** — the PR 8 bug class: a Pallas grid or
block shape derived from the data-dependent slab length recompiles per
length *and* changes the reduction tree shape, so the same records can
sum to different floats depending on how full the slab is.
:func:`check_slab_invariance` traces the gather + segment-reduce kernel
builder at two slab lengths and requires the operand shapes of every
``pallas_call`` to be identical — with the fixed ``block_tokens`` both
pad to the same block; a length-derived block leaks the length into the
operands.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

from repro.analysis import allowlist
from repro.analysis.jaxpr_graph import EqnGraph, iter_eqns_recursive, resolve_callback
from repro.analysis.report import Finding

_CALLBACK_PRIMS = ("io_callback", "pure_callback")

# Two probe lengths, both under the kernel's fixed 512-token block, so a
# correctly-padded kernel produces identical operand shapes for both.
_SLAB_LENGTHS = (96, 160)


def check_determinism(targets: Sequence,
                      extra_allowed: Sequence[str] = (),
                      slab_build: Optional[Callable] = None) -> List[Finding]:
    """Run D1 + D2 over every traced target, then D3 on the kernel builder."""
    findings: List[Finding] = []
    for t in targets:
        findings.extend(_check_callbacks(t.name, t.graph, extra_allowed))
        findings.extend(_check_wire_sorts(t.name, t.graph))
    findings.extend(check_slab_invariance(slab_build))
    return findings


def _check_callbacks(name: str, g: EqnGraph,
                     extra_allowed: Sequence[str]) -> List[Finding]:
    findings: List[Finding] = []
    for n in g.nodes:
        if n.prim not in _CALLBACK_PRIMS:
            continue
        qual = resolve_callback(n.eqn.params.get("callback"))
        if allowlist.is_allowed(qual) or qual in extra_allowed:
            continue
        findings.append(Finding(
            checker="determinism",
            rule="undeclared-host-callback",
            target=name,
            summary=(
                f"host callback {qual!r} is not in the analyzer allowlist "
                "— undeclared host effects (clocks, RNG, IO) break "
                "replayability of a traced program"),
            evidence=[n.describe(),
                      f"allowed: {sorted(allowlist.allowed_names()) or 'none'}"],
        ))
    return findings


def _check_wire_sorts(name: str, g: EqnGraph) -> List[Finding]:
    findings: List[Finding] = []
    a2a_ids = {n.id for n in g.by_prim("all_to_all")}
    for n in g.by_prim("sort"):
        if n.eqn.params.get("is_stable", True):
            continue
        # Entangled with the wire = an all_to_all upstream (the sort
        # orders received records) or downstream (the sort shapes what
        # gets sent).
        up = g.ancestors_of(n.id) & a2a_ids
        down = g.reachable_from([n.id]) & a2a_ids
        if not (up or down):
            continue
        if up:
            chain = g.find_path(min(up), n.id)
        else:
            chain = g.find_path(n.id, min(down))
        findings.append(Finding(
            checker="determinism",
            rule="unstable-wire-sort",
            target=name,
            summary=(
                "an unstable sort is entangled with the shuffle wire — "
                "ties reorder freely, so a cluster's pairs can reach its "
                "reduce in a different order on each executor"),
            evidence=g.describe_path(chain),
        ))
    return findings


def _default_slab_build(n: int):
    """Trace the gather + sorted segment-reduce kernel at slab length ``n``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.segment_reduce.segment_reduce import (
        segment_reduce_sorted_pallas,
    )

    def body(values, gather_idx, seg_ids):
        return segment_reduce_sorted_pallas(
            values[gather_idx], seg_ids, num_segments=8, interpret=True)

    return jax.make_jaxpr(body)(
        jax.ShapeDtypeStruct((n, 3), jnp.float32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
    )


def _pallas_operand_shapes(closed) -> List[tuple]:
    """Sorted operand shapes of every pallas_call in a traced program.

    The kernel's operands are the token-indexed slabs (gathered values,
    segment ids) and the per-block walk derived from them; with a fixed
    ``block_tokens`` they are padded to the block and their shapes do not
    depend on the slab length.
    """
    shapes = []
    for eqn, _path in iter_eqns_recursive(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        for v in eqn.invars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                shapes.append(tuple(shape))
    return sorted(shapes)


def check_slab_invariance(build: Optional[Callable] = None) -> List[Finding]:
    """D3: kernel blocking must not depend on the data-dependent slab length.

    ``build(n)`` must return the traced (ClosedJaxpr) kernel program for
    slab length ``n``; defaults to the repo's gather + segment-reduce
    builder. Traces at two lengths below one block and compares the
    operand shapes of every ``pallas_call``.
    """
    build = build or _default_slab_build
    n_a, n_b = _SLAB_LENGTHS
    shapes_a = _pallas_operand_shapes(build(n_a))
    shapes_b = _pallas_operand_shapes(build(n_b))
    if shapes_a == shapes_b:
        return []
    return [Finding(
        checker="determinism",
        rule="slab-dependent-blocking",
        target="gather_segment_reduce",
        summary=(
            "pallas_call operand shapes change with the slab length — "
            "blocking derives from data-dependent length, so the "
            "reduction tree (and its float rounding) varies per slab "
            "(PR 8 bug class)"),
        evidence=[
            f"slab length {n_a}: operands {shapes_a}",
            f"slab length {n_b}: operands {shapes_b}",
            "a fixed block_tokens pads both lengths to identical blocks",
        ],
    )]
