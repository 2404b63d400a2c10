"""Column pruning below joins (``plan/optimizer.prune_join_inputs``): a join
input is narrowed to the columns its join, its keys, its condition and the
plan above read.

- The plans of the benchmark's statements: Q13's left join reads one
  customer column and two of orders, Q3's lower join three of lineitem;
  q1 and q6 have no join and come out of ``optimize()`` as before; no
  statement's result schema moves.
- Answers against the ``cpu/`` engine, which runs the plan as written: every
  join kind with and without a residual condition, a self-join, a shared
  join result read twice for different columns, a join under a window, and
  a projection holding ``rand()``, which keeps its join whole.
- The counter ``plan.prune.columns``: the columns taken off join inputs, once
  a query, with the recorder off.
"""
import os

import numpy as np
import pytest

from compare import assert_frames_equal
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.cpu.engine import execute_cpu
from spark_rapids_tpu.expressions import (Alias, BoundReference, GreaterThan,
                                          Literal)
from spark_rapids_tpu.expressions.aggregates import Count
from spark_rapids_tpu.expressions.arithmetic import Add
from spark_rapids_tpu.expressions.nondeterministic import Rand
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.plan import optimizer
from spark_rapids_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = os.path.join(ROOT, "benchmark", "queries")
STATEMENTS = sorted(f[:-4] for f in os.listdir(QUERIES) if f.endswith(".sql"))
SF = 0.02


def _sql(name: str) -> str:
    with open(os.path.join(QUERIES, name + ".sql")) as f:
        return f.read()


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """The benchmark's tables at sf 0.02, each cached whole under a view."""
    from benchmark.datagen import tpch_weighted

    d = tmp_path_factory.mktemp("pruning")
    tables = ("lineitem", "orders", "customer")
    tpch_weighted.write_tables(str(d), SF, 35, tables)
    s = Session()
    for t in tables:
        df = s.read.parquet(str(d / t)).cache()
        df.create_or_replace_temp_view(t)
        df.count()
    yield s
    s.stop()


def _joins(plan):
    return [n for n in pn.walk(plan) if isinstance(n, pn.JoinNode)]


def _widths(join):
    return [len(c.output_schema()) for c in join.children]


#: the optimised plans of the statements without a join, as the optimizer
#: gave them before the pass existed
NO_JOIN_TREES = {
    "q1": "Sort[[SortKeySpec(ordinal=0, ascending=True, nulls_first=True), "
          "SortKeySpec(ordinal=1, ascending=True, nulls_first=True)], "
          "global=True]\n"
          "  Project[l_returnflag, l_linestatus, sum_qty, sum_base_price, "
          "sum_disc_price, sum_charge, avg_qty, avg_price, avg_disc, "
          "count_order]\n"
          "    Aggregate[complete, keys=['l_returnflag', 'l_linestatus'], "
          "aggs=['_a0', '_a1', '_a2', '_a3', '_a4', '_a5', '_a6', '_a7']]\n"
          "      Filter[LessThanOrEqual(input[10:date], lit(10471))]\n"
          "        Cache[materialized]\n"
          "          Scan[ParquetSource]",
    "q6": "Project[revenue]\n"
          "  Aggregate[complete, keys=[], aggs=['_a0']]\n"
          "    Filter[And(And(And(GreaterThanOrEqual(input[10:date], "
          "lit(8766)), LessThan(input[10:date], lit(9131))), "
          "And(GreaterThanOrEqual(input[6:double], lit(0.05)), "
          "LessThanOrEqual(input[6:double], lit(0.07)))), "
          "LessThan(input[4:double], lit(24)))]\n"
          "      Cache[materialized]\n"
          "        Scan[ParquetSource]",
}


@pytest.mark.parametrize("stmt", STATEMENTS)
def test_benchmark_statement_plans(tpch, stmt):
    plan = tpch.sql(_sql(stmt))._plan
    out = optimizer.optimize(plan)
    before, after = plan.output_schema(), out.output_schema()
    assert (list(after.names), list(after.types)) == \
        (list(before.names), list(before.types))
    joins = _joins(out)
    if stmt in NO_JOIN_TREES:
        assert not joins
        assert out.tree_string() == NO_JOIN_TREES[stmt]
        assert optimizer.prune_join_inputs(out) == (out, 0)
    elif stmt == "q13":
        # customer 8 -> c_custkey; orders 9 -> o_orderkey, o_custkey
        (join,) = joins
        assert join.kind == "left" and _widths(join) == [1, 2]
        assert [list(c.output_schema().names) for c in join.children] == \
            [["c_custkey"], ["o_orderkey", "o_custkey"]]
    elif stmt == "q3":
        # the lower join reads l_orderkey, l_extendedprice, l_discount
        _upper, lower = joins
        assert list(lower.children[0].output_schema().names) == \
            ["l_orderkey", "l_extendedprice", "l_discount"]
        assert _widths(lower) == [3, 4]
        for j in joins:
            assert sum(_widths(j)) <= 7, j.tree_string()
    else:
        raise AssertionError(f"no plan check for the statement {stmt}")


@pytest.mark.parametrize("stmt,removed", [("q13", 14), ("q6", 0)])
def test_prune_counter_a_query(tpch, stmt, removed):
    df = tpch.sql(_sql(stmt))
    before = tracing.counters()
    df.collect()
    assert tracing.counters_delta(before).get("plan.prune.columns", 0) == \
        removed


# -- answers against the CPU engine over small tables ---------------------

LW = 4       # left: k, a, s, d; right: k, x, t, y


def _tables():
    rng = np.random.default_rng(35)
    n_l, n_r = 60, 45
    words = np.array(["ab", "cd", "ef", None], dtype=object)
    left = pn.ScanNode(pn.InMemorySource(
        {"k": rng.integers(0, 10, n_l).astype(np.int64),
         "a": rng.integers(0, 20, n_l).astype(np.int64),
         "s": words[rng.integers(0, 4, n_l)],
         "d": rng.normal(size=n_l)},
        validity={"k": rng.random(n_l) > 0.1}))
    right = pn.ScanNode(pn.InMemorySource(
        {"k": rng.integers(0, 10, n_r).astype(np.int64),
         "x": rng.integers(0, 20, n_r).astype(np.int64),
         "t": words[rng.integers(0, 4, n_r)],
         "y": rng.normal(size=n_r)},
        validity={"k": rng.random(n_r) > 0.1,
                  "x": rng.random(n_r) > 0.1}))
    return left, right


def _ref(i, t=dt.INT64):
    return BoundReference(i, t)


def _project(child, picks):
    """A projection of (ordinal, dtype, name) picks over ``child``."""
    return pn.ProjectNode([Alias(_ref(o, t), n) for o, t, n in picks], child,
                          names=[n for _, _, n in picks])


def _kind_plan(kind, cond):
    left, right = _tables()
    # a residual that names both sides stays on the join
    c = GreaterThan(_ref(1), _ref(LW + 1)) if cond else None
    join = pn.JoinNode(kind, left, right, [0], [0], condition=c)
    picks = [(1, dt.INT64, "a"), (2, dt.STRING, "s")]
    semi = kind in ("left_semi", "left_anti")
    if not semi:
        picks.append((LW + 3, dt.FLOAT64, "y"))
    # left keeps k, a, s; right keeps k, x where the residual reads it,
    # and y where the join outputs it
    return _project(join, picks), 1 + LW - 1 - bool(cond) - (not semi)


def _self_join():
    left, _r = _tables()
    join = pn.JoinNode("inner", left, left, [0], [0])
    return _project(join, [(1, dt.INT64, "a1"), (LW + 3, dt.FLOAT64, "d2")]), \
        4


def _shared_join_read_twice():
    """WITH t AS (left JOIN right) SELECT t1.a, t2.y FROM t t1 JOIN t t2:
    the join t is one node under both inputs and keeps every column."""
    left, right = _tables()
    t = pn.JoinNode("inner", left, right, [0], [0])
    w = 2 * LW
    top = pn.JoinNode("inner", t, t, [0], [0])
    return _project(top, [(1, dt.INT64, "a"),
                          (w + LW + 3, dt.FLOAT64, "y")]), 2 * (w - 2)


def _join_under_window():
    left, right = _tables()
    join = pn.JoinNode("inner", left, right, [0], [0])
    win = pn.WindowNode([0], [SortKeySpec.spark_default(1),
                              SortKeySpec.spark_default(LW + 1)],
                        [pn.WindowCall("row_number", "rn")], join)
    return _project(win, [(1, dt.INT64, "a"), (2 * LW, dt.INT32, "rn")]), 0


def _rand_projection():
    left, right = _tables()
    join = pn.JoinNode("inner", left, right, [0], [0])
    proj = pn.ProjectNode([Alias(_ref(1), "a"), Alias(Rand(3), "r")], join,
                          names=["a", "r"])
    return pn.ProjectNode([Alias(_ref(0), "a"),
                           Alias(GreaterThan(Add(_ref(1, dt.FLOAT64),
                                                 Literal(1.0)),
                                             Literal(0.5)), "ok")],
                          proj, names=["a", "ok"]), 0


def _count_over_cross_join():
    """count(*) over a cross join of a filtered join and a table: nothing
    above reads a column, so each side keeps one."""
    left, right = _tables()
    inner = pn.JoinNode("inner", left, right, [0], [0])
    f = pn.FilterNode(GreaterThan(_ref(LW + 1), Literal(5)), inner)
    cross = pn.JoinNode("cross", f, _tables()[1], [], [])
    # the inner join keeps k of left, k and x of right; the filtered
    # join's output keeps k, the table its first column
    return pn.AggregateNode([], [pn.AggCall(Count(), "n")], cross), \
        (LW - 1) + (LW - 2) + 2 + (LW - 1)


CASES = {f"{kind}{'-cond' if cond else ''}":
         (lambda kind=kind, cond=cond: _kind_plan(kind, cond))
         for kind in ("inner", "left", "right", "full", "left_semi",
                      "left_anti")
         for cond in (False, True)}
CASES.update({"self_join": _self_join,
              "shared_join_read_twice": _shared_join_read_twice,
              "join_under_window": _join_under_window,
              "rand_projection": _rand_projection,
              "count_over_cross_join": _count_over_cross_join})


@pytest.fixture(scope="module")
def session():
    s = Session()
    yield s
    s.stop()


@pytest.mark.parametrize("case", list(CASES))
def test_answers_equal_cpu_engine(session, case):
    plan, removed = CASES[case]()
    pruned, n = optimizer.prune_join_inputs(plan)
    assert n == removed, pruned.tree_string()
    assert (list(pruned.output_schema().names),
            list(pruned.output_schema().types)) == \
        (list(plan.output_schema().names), list(plan.output_schema().types))
    if case == "shared_join_read_twice":
        # one shared join node under both inputs of the top join
        ids = {id(j) for j in _joins(pruned)}
        assert len(_joins(pruned)) == 3 and len(ids) == 2
    want = execute_cpu(plan).to_pandas()
    assert_frames_equal(want, execute_cpu(pruned).to_pandas())
    assert_frames_equal(want, DataFrame(plan, session).collect())
