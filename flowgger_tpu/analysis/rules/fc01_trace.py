"""FC01 — trace-safety of jit entry points.

A jitted function is traced once per input signature; anything
impure that runs during tracing is baked in (wall clocks, RNG draws) or
forces a host round-trip (``.item()``, ``.tolist()``), and a Python
branch on a *traced* value either crashes or — worse — silently
retraces per value, which is exactly the recompile cliff that drops the
decode path off the >=50M lines/sec target (cf. simdjson's branch-free
hot-path discipline).

The rule finds jit roots in a module (``@jax.jit`` /
``@partial(jax.jit, static_argnames=...)`` decorators, ``f =
jax.jit(g)`` assignments), computes the module-local call-graph closure
under them, and flags:

- wall-clock reads (``time.time/monotonic/perf_counter/...``) and
  ``time.sleep``;
- Python/numpy RNG (``random.*``, ``np.random.*``) — device RNG via
  ``jax.random`` keys is fine;
- host synchronization: ``.item()``, ``.tolist()``,
  ``.block_until_ready()``;
- I/O: ``open()``, ``print()``, ``input()``;
- tracer-dependent branching: an ``if``/``while``/``assert`` in a jit
  root whose test reads a parameter not listed in ``static_argnames``
  (``x.shape``/``x.ndim``/``x.dtype``, ``len(x)``, ``x is None`` and
  ``isinstance`` checks are static and exempt).

Reachability is module-local by construction: kernels in this tree are
self-contained per module (device_*/encode_* import only jnp/lax), so
cross-module reachability would add noise, not coverage.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import Finding, Module, Project, Rule, dotted_name, register

_CLOCK_CALLS = {
    "time.time", "time.monotonic", "time.perf_counter", "time.time_ns",
    "time.monotonic_ns", "time.perf_counter_ns", "time.process_time",
    "time.sleep", "datetime.datetime.now", "datetime.datetime.utcnow",
}
_RNG_PREFIXES = ("random.", "np.random.", "numpy.random.")
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
_IO_CALLS = {"open", "print", "input"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}


def _jit_target(call: ast.Call) -> bool:
    """Is this call expression ``jax.jit(...)`` / ``jit(...)`` or a
    ``partial(jax.jit, ...)`` wrapping?"""
    name = dotted_name(call.func)
    if name in ("jax.jit", "jit"):
        return True
    if name in ("partial", "functools.partial") and call.args:
        inner = dotted_name(call.args[0])
        return inner in ("jax.jit", "jit")
    return False


def _static_argnames(call: ast.Call) -> Set[str]:
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value, str):
                    names.add(el.value)
    return names


class _ModuleIndex:
    """Module-level functions, jit roots, and the call-graph closure."""

    def __init__(self, tree: ast.Module):
        self.functions: Dict[str, ast.FunctionDef] = {}
        self.roots: Dict[str, Set[str]] = {}  # func name -> static args
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.functions[node.name] = node
                for deco in node.decorator_list:
                    if isinstance(deco, ast.Call) and _jit_target(deco):
                        self.roots[node.name] = _static_argnames(deco)
                    elif dotted_name(deco) in ("jax.jit", "jit"):
                        self.roots[node.name] = set()
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in ("jax.jit", "jit") and node.args:
                target = dotted_name(node.args[0])
                if target in self.functions:
                    self.roots.setdefault(target, _static_argnames(node))

    def reachable(self) -> Dict[str, Tuple[str, Optional[Set[str]]]]:
        """name -> (root it is reachable from, static args if it IS a
        root).  BFS over module-local ``Name`` references (covers plain
        calls and functions passed to ``lax.scan``/``while_loop``)."""
        out: Dict[str, Tuple[str, Optional[Set[str]]]] = {}
        queue = [(name, name) for name in self.roots]
        while queue:
            name, root = queue.pop()
            if name in out:
                continue
            out[name] = (root, self.roots.get(name))
            fn = self.functions.get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id in self.functions
                        and node.id not in out):
                    queue.append((node.id, root))
        return out


def _param_names(fn: ast.FunctionDef) -> Set[str]:
    args = fn.args
    names = {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}
    if args.vararg:
        names.add(args.vararg.arg)
    if args.kwarg:
        names.add(args.kwarg.arg)
    return names


def _traced_names_in_test(test: ast.AST, traced: Set[str]) -> Set[str]:
    """Parameter names the test actually *reads as values* — skipping
    static accessors (``.shape``/``.ndim``/``.dtype``/``len``),
    identity-vs-None checks, and ``isinstance``."""
    if isinstance(test, ast.Compare) and all(
            isinstance(op, (ast.Is, ast.IsNot)) for op in test.ops):
        return set()
    hits: Set[str] = set()
    skip: Set[int] = set()
    for node in ast.walk(test):
        if id(node) in skip:
            continue
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            for sub in ast.walk(node.value):
                skip.add(id(sub))
        elif isinstance(node, ast.Call):
            callee = dotted_name(node.func)
            if callee in ("len", "isinstance", "getattr", "hasattr"):
                for sub in ast.walk(node):
                    skip.add(id(sub))
    for node in ast.walk(test):
        if (id(node) not in skip and isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load) and node.id in traced):
            hits.add(node.id)
    return hits


@register
class TraceSafety(Rule):
    id = "FC01"
    title = "trace-safety of jit entry points"

    def check(self, module: Module, project: Project) -> Iterable[Finding]:
        index = _ModuleIndex(module.tree)
        if not index.roots:
            return []
        findings: List[Finding] = []

        def flag(node: ast.AST, root: str, what: str) -> None:
            findings.append(Finding(
                self.id, module.rel, node.lineno, node.col_offset,
                f"{what} inside code reachable from jit entry point "
                f"'{root}'"))

        for name, (root, statics) in index.reachable().items():
            fn = index.functions.get(name)
            if fn is None:
                continue
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    callee = dotted_name(node.func)
                    if callee in _CLOCK_CALLS:
                        flag(node, root, f"wall-clock call {callee}()")
                    elif callee and callee.startswith(_RNG_PREFIXES):
                        flag(node, root, f"host RNG call {callee}()")
                    elif callee in _IO_CALLS:
                        flag(node, root, f"I/O call {callee}()")
                    elif (isinstance(node.func, ast.Attribute)
                          and node.func.attr in _SYNC_METHODS
                          and not node.args):
                        flag(node, root,
                             f"host sync .{node.func.attr}()")
            if statics is None:
                continue  # helper: branch tests use its own locals
            traced = _param_names(fn) - statics
            for node in ast.walk(fn):
                if isinstance(node, (ast.If, ast.While)):
                    test = node.test
                elif isinstance(node, ast.Assert):
                    test = node.test
                elif isinstance(node, ast.IfExp):
                    test = node.test
                else:
                    continue
                hit = _traced_names_in_test(test, traced)
                if hit:
                    kind = type(node).__name__.lower()
                    findings.append(Finding(
                        self.id, module.rel, node.lineno, node.col_offset,
                        f"Python {kind} on traced value(s) "
                        f"{', '.join(sorted(hit))} in jit entry point "
                        f"'{name}' (make it static_argnames or use "
                        f"jnp.where/lax.cond)"))
        return findings
