"""API-drift validation (the reference's api_validation module,
ApiValidation.scala:24-60: reflection-diff Gpu exec signatures against
Spark's). Here the invariants are internal: every plan node must be
covered by BOTH engines, and every registered expression must evaluate
on BOTH engines — so the accelerated path and the oracle can never drift
structurally."""
import inspect

import pytest

from spark_rapids_tpu.cpu import engine as cpu_engine
from spark_rapids_tpu.cpu import evaluator as cpu_eval
from spark_rapids_tpu.expressions.base import Expression
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.plan import overrides


def _all_plan_nodes():
    out = [klass for _, klass in inspect.getmembers(pn, inspect.isclass)
           if issubclass(klass, pn.PlanNode) and klass is not pn.PlanNode]
    from spark_rapids_tpu.execs.python_exec import MapInPandasNode
    from spark_rapids_tpu.io.write import WriteFilesNode

    out += [MapInPandasNode, WriteFilesNode]
    return out


def test_every_plan_node_has_planner_rule():
    missing = [k.__name__ for k in _all_plan_nodes()
               if k not in overrides._NODE_RULES]
    assert not missing, (
        f"plan nodes without a TpuOverrides rule: {missing} — add a "
        "NodeRule (or an explicit fallback decision) for each")


def test_every_plan_node_has_cpu_engine_impl():
    missing = [k.__name__ for k in _all_plan_nodes()
               if k not in cpu_engine._NODES]
    assert not missing, (
        f"plan nodes the CPU oracle cannot execute: {missing}")


def _registered_expressions():
    return [k for k in overrides._EXPR_RULES
            if issubclass(k, Expression)]


def test_every_registered_expression_evaluates_on_cpu():
    from spark_rapids_tpu.expressions.aggregates import AggregateFunction

    missing = []
    for klass in _registered_expressions():
        if issubclass(klass, AggregateFunction):
            continue  # evaluated through the aggregate exec, not eval_expr
        if klass in cpu_eval._DISPATCH:
            continue
        if any(issubclass(klass, k) for k in cpu_eval._DISPATCH):
            continue
        if hasattr(klass, "eval_cpu"):
            continue
        missing.append(klass.__name__)
    assert not missing, (
        f"registered expressions the CPU oracle cannot evaluate: "
        f"{missing}")


def test_every_registered_expression_has_device_eval():
    from spark_rapids_tpu.expressions.aggregates import AggregateFunction

    missing = []
    for klass in _registered_expressions():
        if issubclass(klass, AggregateFunction):
            continue
        if "eval" not in {m for k in klass.__mro__ if k is not Expression
                          for m in vars(k)}:
            missing.append(klass.__name__)
    assert not missing, (
        f"registered expressions without a device eval: {missing}")


def test_aggregate_functions_declare_partial_contract():
    """Partial/final split requires coherent update/merge halves
    (CudfAggregate pairs, AggregateFunctions.scala:531)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.expressions import aggregates as A
    from spark_rapids_tpu.expressions.base import BoundReference

    child = BoundReference(0, dt.FLOAT64)
    for klass in (A.Sum, A.Min, A.Max, A.Count, A.Average, A.First,
                  A.Last):
        inst = klass(child)
        assert inst.partial_types(), klass.__name__
        assert inst.update_ops(), klass.__name__
        assert inst.merge_ops(), klass.__name__
        assert len(inst.update_ops()) == len(inst.partial_types())


def test_every_registered_knob_is_read_somewhere():
    """A registered key that no line of the package reads is documented
    as if it did something (PR 29 took seven such away). Read means:
    its constant is named anywhere but where it is defined, or its key
    text stands in a module other than config.py."""
    import os
    import re

    from spark_rapids_tpu import config

    pkg = os.path.dirname(os.path.abspath(config.__file__))
    conf_py = os.path.join(pkg, "config.py")
    own, rest = "", []
    for d, _dirs, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(d, f)) as fh:
                    if os.path.join(d, f) == conf_py:
                        own = fh.read()
                    else:
                        rest.append(fh.read())
    src = "\n".join(rest)
    names = {id(v): k for k, v in vars(config).items()
             if isinstance(v, config.ConfEntry)}
    # config.py's own constants; the per-expression and per-exec keys
    # that plan/overrides registers are read by the rule that made them
    assert len(names) <= 139       # PR 29's count; a new knob argues here
    unread = []
    for e in config.registered_entries():
        name = names.get(id(e))
        if name and e.key not in src and \
                len(re.findall(rf"\b{name}\b", own + src)) < 2:
            unread.append(e.key)
    assert not unread, f"registered but never read: {unread}"


# ---------------------------------------------------------------------------
# Shim loader (SURVEY.md §2.13: ShimLoader + SparkShimServiceProvider)


def test_shim_provider_version_probe():
    from spark_rapids_tpu import shims

    import jax

    # one provider is left: the installed jax's (range match, bounds)
    assert shims.PROVIDERS == [shims.ModernJaxShimProvider]
    assert shims.ModernJaxShimProvider.matches(jax.__version__)
    assert shims.ModernJaxShimProvider.matches("0.9.0")
    assert shims.ModernJaxShimProvider.matches("1.2.3")
    assert not shims.ModernJaxShimProvider.matches("0.4.30")
    assert not shims.ModernJaxShimProvider.matches("0.6.0")


def test_shim_loader_resolves_and_caches():
    import jax

    from spark_rapids_tpu import shims

    s1 = shims.get_shims()
    assert s1 is shims.get_shims()
    # the resolved shard_map is the one the running jax serves
    assert s1.shard_map() is not None
    assert shims._resolve(jax.__version__) is not s1  # fresh build


def test_shim_unsupported_version_raises():
    import pytest

    from spark_rapids_tpu import shims

    with pytest.raises(RuntimeError, match="shim provider"):
        shims._resolve("0.3.25")


def test_shim_provider_override(monkeypatch):
    from spark_rapids_tpu import shims

    monkeypatch.setenv(
        shims.OVERRIDE_ENV,
        "spark_rapids_tpu.shims.ModernJaxShimProvider")
    resolved = shims._resolve("0.3.25")  # probe would fail; override wins
    assert type(resolved).__name__ == "_ModernJaxShims"
