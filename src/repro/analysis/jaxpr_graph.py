"""Jaxpr tracing + a flattened equation-level dependency DAG.

The contract checkers reason about *traced programs*, not running ones:
:func:`trace_sharded` traces a per-shard phase-B body under the engine's
named axis (``jax.make_jaxpr`` with an ``axis_env`` binding it — the
collectives ``all_to_all`` / ``psum`` / ``axis_index`` stay first-class
equations instead of being rewritten by a transform), and
:class:`EqnGraph` turns the result into one flat producer→consumer DAG.

Flattening matters: ``jnp.argsort`` and friends lower into ``jit``
*sub-jaxprs*, so a top-level walk never sees a ``sort`` equation. The
graph builder therefore **inlines** call-like equations (``jit``,
``closed_call``, ``custom_jvp_call``/``custom_vjp_call``, ``remat2``,
``shard_map``), threading producers through the call boundary, and keeps
everything else (``pallas_call``, control flow) as one opaque node whose
outputs depend on all of its inputs — conservative in exactly the safe
direction for dependence questions.

Edges are recorded per *output slot* (``(producer id, out index)``), so a
checker can ask "who consumes output 0 of this equation" — the question
the wave-timer pass-through check needs — not just "who depends on it".
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
from jax.extend import core as jcore

# Call-like primitives whose sub-jaxpr is semantically inline code.
_INLINE_PRIMS = {
    "jit", "closed_call", "remat2", "custom_jvp_call", "custom_vjp_call",
    "shard_map",
}


def trace_sharded(fn, args, axis_name: str, axis_size: int):
    """``jax.make_jaxpr`` of a per-shard body that uses a named mesh axis.

    Binds ``axis_name`` with ``axis_size`` in the trace-time axis
    environment, so a body containing ``all_to_all`` / ``psum`` /
    ``axis_index`` over the engine mesh axis traces *as written* — the
    same program every shard runs under ``vmap(axis_name=...)`` or
    ``shard_map`` — without standing up devices or letting a transform's
    batching rule rewrite the collectives.
    """
    return jax.make_jaxpr(fn, axis_env=[(axis_name, axis_size)])(*args)


def _sub_jaxpr(params) -> Optional[jcore.Jaxpr]:
    """The single inline sub-jaxpr of a call-like eqn (None when absent)."""
    for key in ("jaxpr", "call_jaxpr"):
        v = params.get(key)
        if isinstance(v, jcore.ClosedJaxpr):
            return v.jaxpr
        if isinstance(v, jcore.Jaxpr):
            return v
    return None


def iter_eqns_recursive(jaxpr: jcore.Jaxpr, path: Tuple[str, ...] = ()):
    """Yield ``(eqn, path)`` for every equation at any nesting depth.

    Unlike the graph (which inlines only call-like prims), this walks
    *every* sub-jaxpr it can find in the params — including control-flow
    branches and scan bodies — so scans for forbidden primitives
    (unstable sorts, rogue callbacks) cannot be hidden by nesting.
    """
    for eqn in jaxpr.eqns:
        yield eqn, path
        for v in eqn.params.values():
            for sub in _jaxprs_in(v):
                name = eqn.params.get("name", eqn.primitive.name)
                yield from iter_eqns_recursive(sub, path + (str(name),))


def _jaxprs_in(value):
    """All jaxprs contained in one params value (handles tuples/lists)."""
    if isinstance(value, jcore.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jcore.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _jaxprs_in(v)


@dataclasses.dataclass
class Node:
    """One opaque equation in the flattened DAG."""

    id: int
    prim: str
    eqn: jcore.JaxprEqn
    path: Tuple[str, ...]                       # enclosing inlined calls
    preds: Set[Tuple[int, int]] = dataclasses.field(default_factory=set)

    def describe(self) -> str:
        """One readable line: id, primitive, context, salient params."""
        bits = []
        p = self.eqn.params
        if self.prim == "all_to_all":
            bits.append(f"axis={p.get('axis_name')}")
        if self.prim == "sort":
            bits.append(f"is_stable={p.get('is_stable')}")
        if self.prim in ("io_callback", "pure_callback"):
            bits.append(f"callback={resolve_callback(p.get('callback'))}")
        where = "/".join(self.path) if self.path else "top"
        extra = f" {' '.join(bits)}" if bits else ""
        return f"#{self.id} {self.prim}{extra} (in {where})"


class EqnGraph:
    """Flattened producer→consumer DAG over one traced program."""

    def __init__(self, closed: jcore.ClosedJaxpr):
        self.nodes: List[Node] = []
        # succ[(producer id, out idx)] -> consumer node ids
        self._succ_by_out: Dict[Tuple[int, int], Set[int]] = {}
        self._succ: Dict[int, Set[int]] = {}
        jaxpr = closed.jaxpr
        env: Dict[jcore.Var, Optional[Tuple[int, int]]] = {}
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            env[v] = None                       # graph sources
        out_env = self._build(jaxpr, env, path=())
        # Producers of the program's outputs, one (node, out idx) or None
        # (a literal / passed-through input) per top-level outvar.
        self.outputs: List[Optional[Tuple[int, int]]] = [
            out_env.get(v) if isinstance(v, jcore.Var) else None
            for v in jaxpr.outvars
        ]

    # -- construction -------------------------------------------------------

    def _build(self, jaxpr, env, path):
        for eqn in jaxpr.eqns:
            in_prods = [
                env.get(v) if isinstance(v, jcore.Var) else None
                for v in eqn.invars
            ]
            sub = _sub_jaxpr(eqn.params) if eqn.primitive.name in _INLINE_PRIMS else None
            if sub is not None and len(sub.invars) == len(eqn.invars):
                sub_env: Dict[jcore.Var, Optional[Tuple[int, int]]] = {}
                for cv in sub.constvars:
                    sub_env[cv] = None
                for sv, prod in zip(sub.invars, in_prods):
                    sub_env[sv] = prod
                name = str(eqn.params.get("name", eqn.primitive.name))
                sub_out = self._build(sub, sub_env, path + (name,))
                for ov, sv in zip(eqn.outvars, sub.outvars):
                    prod = sub_out.get(sv) if isinstance(sv, jcore.Var) else None
                    env[ov] = prod
                continue
            node = Node(id=len(self.nodes), prim=eqn.primitive.name,
                        eqn=eqn, path=path)
            self.nodes.append(node)
            for prod in in_prods:
                if prod is not None:
                    node.preds.add(prod)
                    self._succ_by_out.setdefault(prod, set()).add(node.id)
                    self._succ.setdefault(prod[0], set()).add(node.id)
            for i, ov in enumerate(eqn.outvars):
                env[ov] = (node.id, i)
        return env

    # -- queries ------------------------------------------------------------

    def by_prim(self, name: str) -> List[Node]:
        """All nodes of one primitive, in program order."""
        return [n for n in self.nodes if n.prim == name]

    def successors(self, node_id: int) -> Set[int]:
        """Direct consumers of any output of ``node_id``."""
        return self._succ.get(node_id, set())

    def consumers_of_output(self, node_id: int, out_idx: int) -> Set[int]:
        """Direct consumers of one specific output slot."""
        return self._succ_by_out.get((node_id, out_idx), set())

    def reachable_from(self, starts: Sequence[int]) -> Set[int]:
        """Transitive consumers of the given nodes (the nodes excluded)."""
        seen: Set[int] = set()
        frontier = list(starts)
        while frontier:
            nid = frontier.pop()
            for s in self._succ.get(nid, ()):  # noqa: B905
                if s not in seen:
                    seen.add(s)
                    frontier.append(s)
        return seen

    def ancestors_of(self, node_id: int) -> Set[int]:
        """Transitive producers feeding ``node_id`` (itself excluded)."""
        seen: Set[int] = set()
        frontier = [node_id]
        while frontier:
            nid = frontier.pop()
            for (p, _idx) in self.nodes[nid].preds:
                if p not in seen:
                    seen.add(p)
                    frontier.append(p)
        return seen

    def find_path(self, src: int, dst: int) -> List[int]:
        """One shortest dependency chain src → … → dst (BFS), [] if none."""
        if src == dst:
            return [src]
        parent: Dict[int, int] = {}
        frontier = [src]
        while frontier:
            nxt: List[int] = []
            for nid in frontier:
                for s in self._succ.get(nid, ()):
                    if s in parent:
                        continue
                    parent[s] = nid
                    if s == dst:
                        chain = [dst]
                        while chain[-1] != src:
                            chain.append(parent[chain[-1]])
                        return list(reversed(chain))
                    nxt.append(s)
            frontier = nxt
        return []

    def describe_path(self, chain: Sequence[int]) -> List[str]:
        """Render a node chain as readable evidence lines."""
        out = []
        for i, nid in enumerate(chain):
            arrow = "    " if i == 0 else " -> "
            out.append(f"{arrow}{self.nodes[nid].describe()}")
        return out

    def output_producer_ids(self, out_indices: Sequence[int]) -> Set[int]:
        """Node ids producing the given top-level output slots."""
        ids = set()
        for i in out_indices:
            if i < len(self.outputs) and self.outputs[i] is not None:
                ids.add(self.outputs[i][0])
        return ids


def resolve_callback(cb) -> str:
    """Fully-qualified name of an io/pure_callback's host function.

    Unwraps ``functools.partial`` layers and jax's internal
    ``_FlatCallback`` wrapper (attribute ``callback_func``) down to the
    user function, returning ``module.qualname`` — the key the
    :mod:`repro.analysis.allowlist` registry stores.
    """
    import functools

    seen = 0
    while seen < 10:
        seen += 1
        if isinstance(cb, functools.partial):
            cb = cb.func
            continue
        inner = getattr(cb, "callback_func", None) or getattr(cb, "func", None)
        if inner is not None and inner is not cb:
            cb = inner
            continue
        break
    mod = getattr(cb, "__module__", "?")
    qual = getattr(cb, "__qualname__", repr(cb))
    return f"{mod}.{qual}"
