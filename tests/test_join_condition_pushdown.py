"""A join's own ON conjuncts below it (``plan/optimizer.push_join_conditions``):
one differential a (join kind, conjunct shape) against the CPU engine,
which evaluates the condition over every candidate pair as written, and
a check of the plan ``explain()`` reports.

Where the rule may move a one-sided conjunct (the table in the rule's
comment), the plan shows a ``Filter`` under the join and the join has no
``cond``; where it may not, the join keeps its condition and a non-inner
join still falls back. Both join sides hold NULL keys in every case.
"""
import numpy as np
import pytest

from compare import assert_frames_equal
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.api.dataframe import DataFrame
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.cpu.engine import execute_cpu
from spark_rapids_tpu.expressions import (And, BoundReference, GreaterThan,
                                          LessThan, Literal, Or)
from spark_rapids_tpu.expressions.nondeterministic import Rand
from spark_rapids_tpu.plan import nodes as pn

#: kind -> (a left-only conjunct moves to the left input, a right-only
#: conjunct moves to the right input)
PUSHABLE = {"inner": (True, True), "cross": (True, True),
            "left": (False, True), "right": (True, False),
            "left_semi": (True, True), "left_anti": (False, True),
            "full": (False, False)}
LEFT_W = 2      # left: k, a; right: k, x


def _ref(i):
    return BoundReference(i, dt.INT64)


#: conjunct shape -> (condition over left then right columns, the side it
#: names: "l", "r" or None for both or a nondeterministic one)
SHAPES = {
    "left_only": (lambda: GreaterThan(_ref(1), Literal(3)), "l"),
    "right_only": (lambda: LessThan(_ref(LEFT_W + 1), Literal(6)), "r"),
    "both_sided": (lambda: GreaterThan(_ref(1), _ref(LEFT_W + 1)), None),
    # rand() < 0 never holds, so the value is x > 2's whatever the row
    # order, but the conjunct is nondeterministic and must stay
    "nondeterministic": (lambda: Or(GreaterThan(_ref(LEFT_W + 1),
                                                Literal(2)),
                                    LessThan(Rand(7), Literal(0.0))), None),
    # a right-only conjunct no right row passes: the build is emptied
    "empties_build": (lambda: GreaterThan(_ref(LEFT_W + 1), Literal(1000)),
                      "r"),
}


def _tables():
    rng = np.random.default_rng(34)
    n_l, n_r = 40, 30
    left = pn.InMemorySource(
        {"k": rng.integers(0, 8, n_l).astype(np.int64),
         "a": rng.integers(0, 10, n_l).astype(np.int64)},
        validity={"k": rng.random(n_l) > 0.15})
    right = pn.InMemorySource(
        {"k": rng.integers(0, 8, n_r).astype(np.int64),
         "x": rng.integers(0, 10, n_r).astype(np.int64)},
        validity={"k": rng.random(n_r) > 0.15, "x": rng.random(n_r) > 0.1})
    return pn.ScanNode(left), pn.ScanNode(right)


@pytest.fixture(scope="module")
def session():
    # cross joins are off by default (their OOM risk); the case needs one
    s = Session({"rapids.tpu.sql.exec.BroadcastNestedLoopJoinExec": True})
    yield s
    s.stop()


def _join_and_children(text: str):
    """The join's line of an explain() and the lines of its two inputs.
    An input the planner narrowed to the columns read above the join
    (``plan/optimizer.prune_join_inputs``: a semi or anti join reads only
    its right key) is a ``Project`` over the input: its line is looked
    through, to the line below it."""
    lines = text.splitlines()
    at = next(i for i, ln in enumerate(lines) if "Join[" in ln)
    depth = len(lines[at]) - len(lines[at].lstrip())
    kids = [i for i in range(at + 1, len(lines))
            if len(lines[i]) - len(lines[i].lstrip()) == depth + 2]
    kids = [lines[i + 1] if lines[i].strip().startswith("* Project[")
            else lines[i] for i in kids]
    return lines[at], kids


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("kind", list(PUSHABLE))
def test_on_conjunct_pushdown_matches_cpu_engine(session, kind, shape):
    make, side = SHAPES[shape]
    left, right = _tables()
    keys = ([], []) if kind == "cross" else ([0], [0])
    plan = pn.JoinNode(kind, left, right, *keys, condition=make())
    df = DataFrame(plan, session)

    join, kids = _join_and_children(df.explain())
    pushed = side is not None and PUSHABLE[kind][side == "r"]
    if pushed:
        assert "cond" not in join, join
        under = kids[0] if side == "l" else kids[1]
        assert under.strip().startswith("* Filter["), kids
        assert not join.lstrip().startswith("!"), join
    else:
        assert "cond" in join, join
        assert not any(k.strip().startswith(("* Filter", "! Filter"))
                       for k in kids), kids
        if kind not in ("inner", "cross"):
            assert join.lstrip().startswith("!") and \
                "GpuHashJoin.scala:285-291" in join, join

    want = execute_cpu(plan).to_pandas()
    got = df.collect()
    assert_frames_equal(want, got)
    if shape == "empties_build" and kind in ("inner", "left_semi"):
        assert len(got) == 0


def test_a_join_with_nothing_to_push_is_the_same_node():
    from spark_rapids_tpu.plan.optimizer import push_join_conditions

    left, right = _tables()
    cond = And(GreaterThan(_ref(1), _ref(LEFT_W + 1)),
               GreaterThan(_ref(1), Literal(3)))
    plan = pn.JoinNode("full", left, right, [0], [0], condition=cond)
    assert push_join_conditions(plan) is plan
    inner = pn.JoinNode("inner", left, right, [0], [0], condition=cond)
    out = push_join_conditions(inner)
    # the both-sided conjunct stays, the left-only one moves
    assert out.condition is not None and \
        isinstance(out.children[0], pn.FilterNode)
