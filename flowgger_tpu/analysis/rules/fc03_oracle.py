"""FC03 — the byte-identity contract of device/columnar encode routes.

Every accelerated route in this tree is only allowed to exist because a
scalar oracle produces the *same bytes* at lower throughput (BASELINE.json
names the format surface; the breaker and every degradation path rely on
the swap being invisible).  That contract has two halves, and both must
be declared where the kernel lives so the checker — and the next reader
— can verify them:

- ``SCALAR_ORACLE = "flowgger_tpu.encoders.gelf:GelfEncoder"`` — the
  scalar counterpart this module must stay byte-identical to.  The
  module path must exist in the tree and export the named attribute.
- ``DIFF_TEST = "tests/test_x.py::test_fn"`` (a string or tuple of
  strings) — the differential test(s) that enforce the contract.  The
  file must exist and define the named test function.

Applies to ``tpu/device_*.py``, ``tpu/encode_*_block.py``,
``tpu/fused_*.py`` (the fused decode→encode route tier carries the
same byte-identity obligation as the split kernels it composes),
``tpu/aot.py`` (an AOT-loaded exported program replaces a jit compile
at dispatch — the swap must be byte-invisible, so the loader carries
the contract too), and ``tpu/framing.py`` (device-resident framing
replaces the host splitters — its oracle is the host split/scan
itself).  ``device_common.py`` is shared kernel
infrastructure (segment engine, compile watchdog) with no route of
its own and is exempt.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import Iterable, List, Optional, Tuple

from ..core import Finding, Module, Project, Rule, register

_PATTERNS = ("*tpu/device_*.py", "*tpu/encode_*_block.py",
             "*tpu/fused_*.py", "*tpu/aot.py", "*tpu/framing.py",
             "tpu/device_*.py", "tpu/encode_*_block.py",
             "tpu/fused_*.py", "tpu/aot.py", "tpu/framing.py")
_EXEMPT_BASENAMES = {"device_common.py"}


def _in_scope(rel: str) -> bool:
    base = rel.rsplit("/", 1)[-1]
    if base in _EXEMPT_BASENAMES:
        return False
    return any(fnmatch.fnmatch(rel, pat) for pat in _PATTERNS)


def _module_const(tree: ast.Module, name: str) -> Optional[ast.AST]:
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if isinstance(target, ast.Name) and target.id == name:
                    return node.value
        elif isinstance(node, ast.AnnAssign):
            if (isinstance(node.target, ast.Name)
                    and node.target.id == name):
                return node.value
    return None


def _str_values(node: Optional[ast.AST]) -> List[str]:
    if node is None:
        return []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if isinstance(el, ast.Constant) and isinstance(el.value, str):
                out.append(el.value)
        return out
    return []


def _defines(tree: ast.Module, attr: str) -> bool:
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)) and node.name == attr:
            return True
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if isinstance(target, ast.Name) and target.id == attr:
                    return True
    return False


@register
class ByteIdentityContract(Rule):
    id = "FC03"
    title = "byte-identity contract (scalar oracle + differential test)"

    def check_project(self, project: Project) -> Iterable[Finding]:
        findings: List[Finding] = []
        for module in project.modules:
            if _in_scope(module.rel):
                findings.extend(self._check_module(module, project))
        return findings

    def _check_module(self, module: Module,
                      project: Project) -> List[Finding]:
        findings: List[Finding] = []

        def flag(message: str, line: int = 1) -> None:
            findings.append(Finding(self.id, module.rel, line, 0, message))

        oracle = _module_const(module.tree, "SCALAR_ORACLE")
        oracle_strs = _str_values(oracle)
        if not oracle_strs:
            flag("device/block-encode module does not register its "
                 "scalar oracle (add SCALAR_ORACLE = "
                 '"pkg.module:Attr")')
        else:
            self._check_oracle(oracle_strs[0], module, project, flag)

        tests = _str_values(_module_const(module.tree, "DIFF_TEST"))
        if not tests:
            flag("device/block-encode module does not register a "
                 "differential test (add DIFF_TEST = "
                 '"tests/test_x.py::test_fn")')
        for ref in tests:
            self._check_test_ref(ref, project, flag)
        return findings

    def _check_oracle(self, spec: str, module: Module, project: Project,
                      flag) -> None:
        mod_path, _, attr = spec.partition(":")
        rel = mod_path.replace(".", "/") + ".py"
        if not project.exists(rel):
            flag(f"SCALAR_ORACLE module '{mod_path}' does not resolve to "
                 f"a file in the tree ({rel})")
            return
        if attr:
            tree = project.parse(rel)
            if tree is not None and not _defines(tree, attr):
                flag(f"SCALAR_ORACLE '{spec}': module '{mod_path}' does "
                     f"not define '{attr}'")

    def _check_test_ref(self, ref: str, project: Project, flag) -> None:
        path, _, func = ref.partition("::")
        if not project.exists(path):
            flag(f"DIFF_TEST '{ref}': test file '{path}' does not exist")
            return
        if not func:
            flag(f"DIFF_TEST '{ref}' must name a test function "
                 f"(file.py::test_fn)")
            return
        tree = project.parse(path)
        if tree is None:
            flag(f"DIFF_TEST '{ref}': test file '{path}' is unparseable")
            return
        names = {n.name for n in ast.walk(tree)
                 if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if func not in names:
            flag(f"DIFF_TEST '{ref}': '{path}' does not define "
                 f"'{func}'")
