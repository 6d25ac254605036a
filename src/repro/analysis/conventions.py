"""AST convention lint over ``src/repro`` — the source-level contract layer.

The jaxpr checkers certify traced programs; this layer catches the same
bug classes at the source level, where they are cheaper to localise and
where untraced code paths (host planners, helpers) also live:

* **jit-rng-time (C1)** — no ``time.*`` / ``random.*`` / ``np.random.*``
  calls inside a function that gets jit-traced (passed to ``jax.jit`` /
  ``vmap`` / ``make_jaxpr`` / ``shard_map`` / ``pallas_call`` or
  decorated with one). A Python clock or RNG in a traced body runs
  *once, at trace time* — it bakes one arbitrary value into the compiled
  program, silently. Host callback bodies are exempt (they run on the
  host every call, which is the point).
* **wire-sort-stability (C2)** — in the wire-shaping module
  (``core/mapreduce.py``), every ``argsort`` / ``lax.sort`` /
  ``sort_key_val`` call must spell its stability (``stable=`` /
  ``is_stable=`` / numpy's ``kind=``). Each cluster's pairs reach the
  reduce in (src shard, bucket position) order on every executor only
  because the spill's sort is stable; that must be visible in the
  source, not inherited from a default that jax has changed before.
* **callback-marker (C3)** — every ``io_callback`` / ``pure_callback``
  call site carries an ``# analysis: allow-callback`` marker on the
  call (or the line above). The marker is the source-level half of the
  :mod:`repro.analysis.allowlist` declaration: greppable, reviewed in
  diffs, and checked here so it cannot rot.

The ``analysis`` package itself is excluded from tree scans: its
mutation fixtures intentionally embed violations.
"""

from __future__ import annotations

import ast
import pathlib
from typing import Iterable, List, Optional, Set

from repro.analysis.report import Finding

# Callee names whose first positional argument becomes traced code.
_TRACE_WRAPPERS = {
    "jit", "vmap", "pmap", "make_jaxpr", "shard_map", "pallas_call",
    "checkpoint", "remat", "grad", "value_and_grad",
}
_CALLBACK_NAMES = {"io_callback", "pure_callback"}
_SORT_ATTRS = {"argsort", "sort_key_val"}
_STABILITY_KWARGS = {"stable", "is_stable", "kind"}
_MARKER = "# analysis: allow-callback"

# Files whose sorts shape the shuffle wire (C2 scope).
_WIRE_PARTS = ("core/mapreduce.py",)


def _final_attr(func: ast.expr) -> Optional[str]:
    """The last dotted component of a callee (``jax.lax.sort`` → ``sort``)."""
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return None


def _dotted(func: ast.expr) -> str:
    """Best-effort dotted name of a callee expression."""
    parts: List[str] = []
    node = func
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _ModuleLint:
    """One parsed module + the alias/def maps the three rules need."""

    def __init__(self, path: pathlib.Path):
        self.path = path
        self.source = path.read_text()
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=str(path))
        # Aliases of the host-effect modules in this file.
        self.time_aliases: Set[str] = set()
        self.random_aliases: Set[str] = set()
        self.numpy_aliases: Set[str] = set()
        # Names imported *from* time/random (from time import perf_counter).
        self.host_fn_names: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    alias = a.asname or a.name.split(".")[0]
                    if a.name == "time":
                        self.time_aliases.add(alias)
                    elif a.name == "random":
                        self.random_aliases.add(alias)
                    elif a.name.split(".")[0] == "numpy":
                        self.numpy_aliases.add(alias)
            elif isinstance(node, ast.ImportFrom):
                if node.module in ("time", "random"):
                    for a in node.names:
                        self.host_fn_names.add(a.asname or a.name)
        # Module/class-level function defs by bare name.
        self.defs = {
            n.name: n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }

    # -- traced-root discovery ---------------------------------------------

    def traced_roots(self) -> Set[str]:
        """Names of functions that end up inside a jax trace."""
        roots: Set[str] = set()
        host: Set[str] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                attr = _final_attr(node.func)
                if attr in _TRACE_WRAPPERS and node.args:
                    first = node.args[0]
                    if isinstance(first, ast.Name):
                        roots.add(first.id)
                if attr in _CALLBACK_NAMES and node.args:
                    first = node.args[0]
                    if isinstance(first, ast.Name):
                        host.add(first.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    d = dec.func if isinstance(dec, ast.Call) else dec
                    if _final_attr(d) in _TRACE_WRAPPERS:
                        roots.add(node.name)
                    # functools.partial(jax.jit, ...) decorators
                    if isinstance(dec, ast.Call) and \
                            _final_attr(dec.func) == "partial" and dec.args and \
                            _final_attr(dec.args[0]) in _TRACE_WRAPPERS:
                        roots.add(node.name)
        roots -= host
        # Transitive closure over bare-name calls to module-local defs.
        frontier = [r for r in roots if r in self.defs]
        seen = set(frontier)
        while frontier:
            fn = self.defs[frontier.pop()]
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                    callee = node.func.id
                    if callee in self.defs and callee not in seen \
                            and callee not in host:
                        seen.add(callee)
                        frontier.append(callee)
        return seen

    def _is_host_effect_call(self, node: ast.Call) -> Optional[str]:
        """Dotted name when ``node`` calls a Python clock/RNG, else None."""
        dotted = _dotted(node.func)
        head = dotted.split(".")[0] if dotted else ""
        if head in self.time_aliases or head in self.random_aliases:
            return dotted
        if head in self.numpy_aliases and ".random." in f".{dotted}.":
            return dotted
        if isinstance(node.func, ast.Name) and \
                node.func.id in self.host_fn_names:
            return node.func.id
        return None

    def _excerpt(self, node: ast.AST) -> str:
        line = self.lines[node.lineno - 1].strip()
        return f"{self.path}:{node.lineno}: {line}"

    # -- the three rules ----------------------------------------------------

    def check_jit_host_effects(self) -> List[Finding]:
        findings: List[Finding] = []
        for name in sorted(self.traced_roots()):
            fn = self.defs.get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    dotted = self._is_host_effect_call(node)
                    if dotted:
                        findings.append(Finding(
                            checker="conventions",
                            rule="jit-rng-time",
                            target=str(self.path),
                            summary=(
                                f"traced function {name!r} calls "
                                f"{dotted}() — it runs once at trace "
                                "time and bakes one value into the "
                                "compiled program"),
                            evidence=[self._excerpt(node)],
                        ))
        return findings

    def check_wire_sorts(self) -> List[Finding]:
        posix = self.path.as_posix()
        if not any(part in posix for part in _WIRE_PARTS):
            return []
        findings: List[Finding] = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            attr = _final_attr(node.func)
            dotted = _dotted(node.func)
            is_sort = attr in _SORT_ATTRS or (
                attr == "sort" and dotted.split(".")[0] in
                ("lax", "jax", "jnp", "np", "numpy"))
            if not is_sort:
                continue
            kwargs = {kw.arg for kw in node.keywords}
            if kwargs & _STABILITY_KWARGS:
                continue
            findings.append(Finding(
                checker="conventions",
                rule="wire-sort-stability",
                target=str(self.path),
                summary=(
                    f"{dotted or attr}() in a wire-shaping module "
                    "without an explicit stability argument — the "
                    "identical-sort contract must be spelled out, not "
                    "inherited from a default"),
                evidence=[self._excerpt(node)],
            ))
        return findings

    def check_callback_markers(self) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            if _final_attr(node.func) not in _CALLBACK_NAMES:
                continue
            start = max(0, node.lineno - 2)          # line above the call
            end = getattr(node, "end_lineno", node.lineno)
            span = self.lines[start:end]
            if any(_MARKER in line for line in span):
                continue
            findings.append(Finding(
                checker="conventions",
                rule="callback-marker",
                target=str(self.path),
                summary=(
                    "host-callback call site without an '# analysis: "
                    "allow-callback' marker — callbacks must be declared "
                    "where they are called, not discovered by the linter"),
                evidence=[self._excerpt(node)],
            ))
        return findings


def lint_paths(paths: Iterable[pathlib.Path]) -> List[Finding]:
    """Run all three convention rules over the given Python files."""
    findings: List[Finding] = []
    for p in paths:
        lint = _ModuleLint(pathlib.Path(p))
        findings.extend(lint.check_jit_host_effects())
        findings.extend(lint.check_wire_sorts())
        findings.extend(lint.check_callback_markers())
    return findings


def lint_tree(root) -> List[Finding]:
    """Lint every ``.py`` under ``root``, excluding the analysis package."""
    root = pathlib.Path(root)
    paths = sorted(
        p for p in root.rglob("*.py")
        if "analysis" not in p.parts
    )
    return lint_paths(paths)


def default_root() -> pathlib.Path:
    """The installed ``repro`` package directory (what ``--check`` lints)."""
    import repro

    if getattr(repro, "__file__", None):
        return pathlib.Path(repro.__file__).parent
    return pathlib.Path(next(iter(repro.__path__)))   # namespace package
