"""TPU1xx: host-sync discipline.

Every device->host sync stalls the dispatch pipeline, and an implicit
one is invisible in a CPU run: it costs nothing there and shows only on
the accelerator. The contract this pass
enforces: device data crosses to the host ONLY through an explicit
``jax.device_get`` at an allowlisted staging/collect site.

- TPU101 ``np.asarray``/``np.array`` on anything that could be a device
  array (the numpy coercion of a jax array is a silent blocking
  transfer). Literal/host-constructor arguments are exempt; a direct
  ``np.asarray(jax.device_get(x))`` is exempt (the sync is explicit).
- TPU102 ``.item()`` — one scalar, one full RTT.
- TPU103 ``block_until_ready`` — a barrier; legitimate only in
  benchmark/measurement code.
- TPU104 implicit ``__bool__`` on a value assigned from a ``jnp.*``
  call (``if jnp.any(...)``, ``while not done`` over a device flag):
  the truth test syncs without any visible transfer call.
"""
from __future__ import annotations

import ast
from typing import List

from spark_rapids_tpu.analysis import astutil
from spark_rapids_tpu.analysis.diagnostics import Finding

_NP_COERCE = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}

#: jnp functions that return host metadata (python bools), not device
#: arrays — their truth test is free
_JNP_METADATA = {"jnp.issubdtype", "jnp.isdtype",
                 "jax.numpy.issubdtype", "jax.numpy.isdtype"}

#: argument node types that are host data by construction
_HOST_LITERALS = (ast.List, ast.Tuple, ast.Constant, ast.ListComp,
                  ast.GeneratorExp, ast.Dict, ast.Set)


def _arg_is_explicit_host(arg: ast.AST) -> bool:
    if isinstance(arg, _HOST_LITERALS):
        return True
    if isinstance(arg, ast.Call):
        name = astutil.call_name(arg) or ""
        if name.endswith("device_get"):
            return True  # explicit sync: the point of the rule
        # any other call: numpy/host helpers dominate; a jnp.* result
        # fed straight to np.asarray is still flagged
        return not (name.startswith("jnp.") or
                    name.startswith("jax.numpy"))
    return False


def run(root: str) -> List[Finding]:
    findings: List[Finding] = []

    for rel, tree, _src in astutil.iter_modules(root):

        class V(astutil.QualnameVisitor):
            def __init__(self):
                super().__init__()
                # names assigned from jnp.* calls in the current scope
                self._device_names: List[set] = [set()]

            def _push(self, node):
                self._device_names.append(set())
                super()._push(node)
                self._device_names.pop()

            def _emit(self, code, node, msg):
                findings.append(Finding(
                    code=code, path=rel, line=node.lineno,
                    qualname=self.qualname, message=msg))

            def visit_Assign(self, node):
                if isinstance(node.value, ast.Call):
                    name = astutil.call_name(node.value) or ""
                    if name.startswith("jnp.") or \
                            name.startswith("jax.numpy"):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                self._device_names[-1].add(t.id)
                self.generic_visit(node)

            def visit_Call(self, node):
                name = astutil.call_name(node)
                if name in _NP_COERCE and node.args and \
                        not _arg_is_explicit_host(node.args[0]):
                    self._emit(
                        "TPU101", node,
                        f"{name}(...) may coerce a device array to "
                        f"host without an explicit jax.device_get")
                elif name and name.endswith(".item") and not node.args:
                    self._emit(
                        "TPU102", node,
                        ".item() pulls one scalar at a full dispatch "
                        "RTT; batch into one device_get")
                elif name and name.endswith("block_until_ready"):
                    self._emit(
                        "TPU103", node,
                        "block_until_ready barrier outside "
                        "benchmark/measurement code")
                self.generic_visit(node)

            def _check_truth(self, test):
                node = test
                if isinstance(node, ast.UnaryOp) and \
                        isinstance(node.op, ast.Not):
                    node = node.operand
                if isinstance(node, ast.Name) and any(
                        node.id in s for s in self._device_names):
                    self._emit(
                        "TPU104", test,
                        f"truth test on {node.id!r} (assigned from a "
                        f"jnp.* call) forces an implicit sync")
                elif isinstance(node, ast.Call):
                    name = astutil.call_name(node) or ""
                    if (name.startswith("jnp.") or
                            name.startswith("jax.numpy")) and \
                            name not in _JNP_METADATA:
                        self._emit(
                            "TPU104", test,
                            f"truth test on {name}(...) result forces "
                            f"an implicit sync")

            def visit_If(self, node):
                self._check_truth(node.test)
                self.generic_visit(node)

            def visit_While(self, node):
                self._check_truth(node.test)
                self.generic_visit(node)

            def visit_Assert(self, node):
                self._check_truth(node.test)
                self.generic_visit(node)

        V().visit(tree)
    return findings
