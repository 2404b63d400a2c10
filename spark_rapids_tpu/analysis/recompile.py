"""TPU2xx: recompile hazards.

One compile per (program, bucket shape) is the serving layer's core
contract (PR 7): a jit entry point whose operand shapes bypass the
``ops/buckets`` capacity ladder compiles per DISTINCT RAW SIZE — the
exact bug class that made a lazily-compiled 2-way coalesced program a
0.4 s p99 outlier. The chip's compiler takes seconds to minutes per
program, so the hazards are flagged statically:

- TPU201 ``jax.jit`` called inside a function body: the returned
  callable's trace cache dies with it, so every invocation re-traces
  (and usually re-compiles). Module-level jits — including the
  memoized-global idiom ``execs/interop.py`` uses — are the fix.
- TPU202 array constructor (``jnp.zeros``/``ones``/``full``/``empty``)
  whose shape derives from ``len(...)`` or ``.shape`` in a function
  that never quantizes through ``bucket_capacity``: raw data-dependent
  shapes mint unbounded signatures.
- TPU203 ``jnp.asarray``/``jnp.array`` of a bare numeric literal with
  no ``dtype``: weak-type promotion makes the operand's signature
  depend on surrounding arithmetic, so structurally identical programs
  stop sharing executables (x64 drift doubles the damage).
- TPU204 any ``pallas_call``: the package has one kernel layer, the
  jitted jnp programs of ``ops/``, which is what the chip ran. The
  hand-written Pallas layer only ever ran through the interpreter on a
  CPU, the v5e's Mosaic lowering refused its kernels (PR 23), and it
  was deleted (PR 29). A kernel that comes back arrives with a
  compile case for the chip and a benchmark cell it wins.
"""
from __future__ import annotations

import ast
from typing import List, Set

from spark_rapids_tpu.analysis import astutil
from spark_rapids_tpu.analysis.diagnostics import Finding

_CONSTRUCTORS = {"jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty",
                 "jax.numpy.zeros", "jax.numpy.ones", "jax.numpy.full",
                 "jax.numpy.empty"}
_LITERAL_WRAP = {"jnp.asarray", "jnp.array",
                 "jax.numpy.asarray", "jax.numpy.array"}

def _decorator_nodes(tree: ast.Module) -> Set[int]:
    """ids of every node inside a decorator list: ``@partial(jax.jit,
    ...)`` is the SANCTIONED module-level idiom, not a TPU201."""
    out: Set[int] = set()
    for node in ast.walk(tree):
        for dec in getattr(node, "decorator_list", ()) or ():
            for sub in ast.walk(dec):
                out.add(id(sub))
    return out


def _shape_is_data_dependent(call: ast.Call) -> bool:
    """Does the constructor's shape argument derive from len()/.shape?"""
    if not call.args:
        return False
    for node in ast.walk(call.args[0]):
        if isinstance(node, ast.Call) and \
                astutil.call_name(node) == "len":
            return True
        if isinstance(node, ast.Attribute) and node.attr == "shape":
            return True
    return False


def run(root: str) -> List[Finding]:
    findings: List[Finding] = []

    for rel, tree, _src in astutil.iter_modules(root):
        in_decorator = _decorator_nodes(tree)
        functions = astutil.collect_functions(tree)
        # functions that (transitively locally) reach bucket_capacity
        quantizers = {
            qn for qn, fn in functions.items()
            if any(c.split(".")[-1] == "bucket_capacity"
                   for c in astutil.local_calls(fn))}

        class V(astutil.QualnameVisitor):
            def _emit(self, code, node, msg):
                findings.append(Finding(
                    code=code, path=rel, line=node.lineno,
                    qualname=self.qualname, message=msg))

            def visit_Call(self, node):
                name = astutil.call_name(node)
                if name in ("jax.jit", "jit") and self.qualname and \
                        id(node) not in in_decorator:
                    self._emit(
                        "TPU201", node,
                        "jax.jit inside a function body re-traces per "
                        "call; hoist to module level (see "
                        "execs/interop.py's memoized-global idiom)")
                elif name in _CONSTRUCTORS and \
                        _shape_is_data_dependent(node) and \
                        self.qualname not in quantizers:
                    self._emit(
                        "TPU202", node,
                        f"{name} shape derives from len()/.shape in a "
                        f"function that never calls bucket_capacity — "
                        f"raw sizes mint one executable per distinct "
                        f"length")
                elif name in _LITERAL_WRAP and node.args and \
                        isinstance(node.args[0], ast.Constant) and \
                        isinstance(node.args[0].value, (int, float)) and \
                        len(node.args) < 2 and \
                        not any(kw.arg == "dtype"
                                for kw in node.keywords):
                    self._emit(
                        "TPU203", node,
                        f"{name}({node.args[0].value!r}) without dtype "
                        f"is weakly typed; the promoted signature "
                        f"drifts with surrounding arithmetic")
                elif name and (name == "pallas_call" or
                               name.endswith(".pallas_call")):
                    self._emit(
                        "TPU204", node,
                        f"{name}: the package keeps no Pallas kernel — "
                        f"none has compiled for the v5e; bring one "
                        f"back with a chip compile case and a "
                        f"benchmark cell it wins")
                self.generic_visit(node)

        V().visit(tree)
    return findings
