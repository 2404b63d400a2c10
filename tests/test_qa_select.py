"""Generated SELECT matrix over fuzzed data — the reference's
qa_nightly_select_test.py role: a wide sweep of (expression x input type)
combinations, every one checked against the CPU oracle with special
values (NaN/Inf/-0.0/boundaries/NULLs) in play."""
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.expressions import arithmetic as ar
from spark_rapids_tpu.expressions import conditional as cond
from spark_rapids_tpu.expressions import math as mth
from spark_rapids_tpu.expressions import predicates as pr
from spark_rapids_tpu.expressions import strings as st
from spark_rapids_tpu.expressions import datetime as dte
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Literal)
from spark_rapids_tpu.expressions.cast import Cast
from spark_rapids_tpu.plan import nodes as pn

from tests import data_gen as dg
from tests.compare import assert_cpu_and_tpu_equal

CONF = RapidsConf({
    "rapids.tpu.sql.test.enabled": True,
    "rapids.tpu.sql.incompatibleOps.enabled": True,
})


def _project(exprs, scan):
    return pn.ProjectNode(
        [Alias(e, f"o{i}") for i, e in enumerate(exprs)], scan)


def ref(i, t):
    return BoundReference(i, t)


# ---------------------------------------------------------------------------
# binary arithmetic x numeric type matrix
# ---------------------------------------------------------------------------

_ARITH = [ar.Add, ar.Subtract, ar.Multiply, ar.Divide, ar.Remainder,
          ar.Pmod]


@pytest.mark.parametrize("op", _ARITH, ids=lambda o: o.__name__)
@pytest.mark.parametrize("gen", dg.NUMERIC_GENS,
                         ids=lambda g: g.dtype.name)
def test_binary_arith_matrix(op, gen, subtests=None):
    scan = dg.gen_scan({"a": gen, "b": type(gen)()}, n=150,
                       seed=hash((op.__name__, gen.dtype.name)) % 10_000)
    a, b = ref(0, gen.dtype), ref(1, gen.dtype)
    exprs = [op(a, b), op(a, Literal(3)), op(Literal(7), b)]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF,
                             approx_float=1e-6)


@pytest.mark.parametrize("op", [pr.EqualTo, pr.LessThan,
                                pr.GreaterThanOrEqual,
                                pr.EqualNullSafe],
                         ids=lambda o: o.__name__)
@pytest.mark.parametrize("gen", [dg.IntegerGen(), dg.DoubleGen(),
                                 dg.StringGen(), dg.DateGen()],
                         ids=lambda g: g.dtype.name)
def test_comparison_matrix(op, gen):
    scan = dg.gen_scan({"a": gen, "b": type(gen)()}, n=150, seed=5)
    exprs = [op(ref(0, gen.dtype), ref(1, gen.dtype))]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


@pytest.mark.parametrize("op", [mth.Sqrt, mth.Exp, mth.Log, mth.Sin,
                                mth.Cos, mth.Tanh, mth.Floor, mth.Ceil,
                                mth.Rint, mth.Asinh, mth.Acosh,
                                mth.Atanh, mth.Cot],
                         ids=lambda o: o.__name__)
def test_unary_math_matrix(op):
    scan = dg.gen_scan({"a": dg.DoubleGen()}, n=200, seed=6)
    assert_cpu_and_tpu_equal(
        _project([op(ref(0, dt.FLOAT64))], scan), conf=CONF,
        approx_float=1e-6)


@pytest.mark.parametrize("op", [st.Upper, st.Lower, st.Length,
                                st.StringTrim, st.Reverse, st.InitCap],
                         ids=lambda o: o.__name__)
def test_unary_string_matrix(op):
    scan = dg.gen_scan({"s": dg.StringGen()}, n=150, seed=7)
    assert_cpu_and_tpu_equal(
        _project([op(ref(0, dt.STRING))], scan), conf=CONF)


@pytest.mark.parametrize("op", [dte.Year, dte.Month, dte.DayOfMonth,
                                dte.DayOfWeek, dte.DayOfYear,
                                dte.Quarter, dte.LastDay],
                         ids=lambda o: o.__name__)
def test_date_field_matrix(op):
    scan = dg.gen_scan({"d": dg.DateGen()}, n=150, seed=8)
    assert_cpu_and_tpu_equal(
        _project([op(ref(0, dt.DATE))], scan), conf=CONF)


_CAST_PAIRS = [
    (dg.IntegerGen(), dt.INT64), (dg.IntegerGen(), dt.FLOAT64),
    (dg.IntegerGen(), dt.STRING), (dg.LongGen(), dt.INT32),
    (dg.DoubleGen(), dt.INT64), (dg.DoubleGen(), dt.FLOAT32),
    (dg.BooleanGen(), dt.INT32), (dg.ByteGen(), dt.INT16),
    (dg.SmallIntGen(), dt.STRING), (dg.DateGen(), dt.TIMESTAMP),
    (dg.TimestampGen(), dt.DATE),
]


@pytest.mark.parametrize("gen,to", _CAST_PAIRS,
                         ids=lambda p: getattr(p, "name", str(p)))
def test_cast_matrix(gen, to):
    scan = dg.gen_scan({"a": gen}, n=150, seed=9)
    assert_cpu_and_tpu_equal(
        _project([Cast(ref(0, gen.dtype), to)], scan), conf=CONF)


def test_conditional_over_fuzz():
    scan = dg.gen_scan({"a": dg.IntegerGen(), "b": dg.IntegerGen(),
                        "p": dg.BooleanGen()}, n=200, seed=10)
    a, b, p = ref(0, dt.INT32), ref(1, dt.INT32), ref(2, dt.BOOLEAN)
    exprs = [
        cond.If(p, a, b),
        cond.Coalesce([a, b, Literal(0, dt.INT32)]),
        cond.CaseWhen([(pr.GreaterThan(a, b), a),
                       (pr.IsNull(a), Literal(-1, dt.INT32))], b),
    ]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_aggregate_over_fuzz():
    from spark_rapids_tpu.expressions import aggregates as A

    scan = dg.gen_scan({"k": dg.SmallIntGen(), "v": dg.DoubleGen(),
                        "i": dg.IntegerGen()}, n=300, seed=11)
    agg = pn.AggregateNode(
        [ref(0, dt.INT64)],
        [pn.AggCall(A.Sum(ref(1, dt.FLOAT64)), "sv"),
         pn.AggCall(A.Min(ref(2, dt.INT32)), "mn"),
         pn.AggCall(A.Max(ref(1, dt.FLOAT64)), "mx"),
         pn.AggCall(A.Count(ref(1, dt.FLOAT64)), "cv"),
         pn.AggCall(A.Average(ref(2, dt.INT32)), "av")],
        scan, grouping_names=["k"])
    assert_cpu_and_tpu_equal(agg, conf=CONF, approx_float=1e-6)


def test_sort_over_fuzz_with_specials():
    """NaN/-0.0/NULL ordering under Spark total order."""
    scan = dg.gen_scan({"a": dg.DoubleGen(nullable=0.2),
                        "b": dg.IntegerGen()}, n=250, seed=12)
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    plan = pn.SortNode([SortKeySpec.spark_default(0),
                        SortKeySpec.spark_default(1, ascending=False)],
                       scan)
    assert_cpu_and_tpu_equal(plan, conf=CONF, sort=False)


def test_join_over_fuzz():
    left = dg.gen_scan({"k": dg.SmallIntGen(), "v": dg.DoubleGen()},
                       n=200, seed=13)
    right = dg.gen_scan({"k2": dg.SmallIntGen(), "w": dg.StringGen()},
                        n=150, seed=14)
    for kind in ("inner", "left", "left_semi", "left_anti"):
        plan = pn.JoinNode(kind, left, right, [0], [0])
        assert_cpu_and_tpu_equal(plan, conf=CONF, approx_float=1e-6)


@pytest.mark.parametrize("op_name", ["BitwiseAnd", "BitwiseOr",
                                     "BitwiseXor"])
@pytest.mark.parametrize("gen", [dg.IntegerGen(), dg.LongGen(),
                                 dg.ShortGen()],
                         ids=lambda g: g.dtype.name)
def test_bitwise_binary_matrix(op_name, gen):
    from spark_rapids_tpu.expressions import bitwise as bw

    op = getattr(bw, op_name)
    scan = dg.gen_scan({"a": gen, "b": type(gen)()}, n=150, seed=21)
    exprs = [op(ref(0, gen.dtype), ref(1, gen.dtype)),
             bw.BitwiseNot(ref(0, gen.dtype))]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


@pytest.mark.parametrize("op_name", ["ShiftLeft", "ShiftRight",
                                     "ShiftRightUnsigned"])
@pytest.mark.parametrize("gen", [dg.IntegerGen(), dg.LongGen()],
                         ids=lambda g: g.dtype.name)
def test_shift_matrix(op_name, gen):
    from spark_rapids_tpu.expressions import bitwise as bw
    from spark_rapids_tpu.expressions.base import Literal

    op = getattr(bw, op_name)
    scan = dg.gen_scan({"a": gen, "s": dg.IntegerGen()}, n=150, seed=22)
    # fuzzed shift amounts exercise the Java width mask (s & 31/63)
    exprs = [op(ref(0, gen.dtype), ref(1, dt.INT32)),
             op(ref(0, gen.dtype), Literal(3, dt.INT32)),
             op(ref(0, gen.dtype), Literal(0, dt.INT32)),
             op(ref(0, gen.dtype), Literal(65, dt.INT32))]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_string_binary_matrix():
    scan = dg.gen_scan({"s": dg.StringGen(), "t": dg.StringGen()},
                       n=150, seed=30)
    s = ref(0, dt.STRING)
    exprs = [
        st.Substring(s, 2, 3),
        st.Substring(s, -3, None),
        st.StringReplace(s, "a", "ZZ"),
        st.StringRepeat(s, 2),
        st.StringLPad(s, 6, "*"),
        st.StringRPad(s, 6, "*"),
        st.StartsWith(s, "a"),
        st.EndsWith(s, "z"),
        st.Contains(s, "X"),
        st.Like(s, "a%b_"),
        st.StringLocate("b", s),
        st.ConcatStrings([s, ref(1, dt.STRING)]),
    ]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_in_and_null_predicates():
    scan = dg.gen_scan({"a": dg.IntegerGen(nullable=0.2),
                        "s": dg.StringGen(nullable=0.2),
                        "f": dg.DoubleGen(nullable=0.2)}, n=200,
                       seed=31)
    exprs = [
        pr.In(ref(0, dt.INT32), [Literal(v, dt.INT32)
                                 for v in (0, 7, -12, 2**31 - 1)]),
        pr.In(ref(1, dt.STRING), [Literal(v) for v in ("ab", "", "X z")]),
        pr.IsNull(ref(0, dt.INT32)),
        pr.IsNotNull(ref(1, dt.STRING)),
        pr.IsNaN(ref(2, dt.FLOAT64)),
        pr.AtLeastNNonNulls(2, [ref(0, dt.INT32), ref(1, dt.STRING),
                                ref(2, dt.FLOAT64)]),
    ]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_datetime_arithmetic_matrix():
    scan = dg.gen_scan({"d": dg.DateGen(), "d2": dg.DateGen(),
                        "ts": dg.TimestampGen(),
                        "n": dg.SmallIntGen()}, n=150, seed=32)
    exprs = [
        dte.DateAdd(ref(0, dt.DATE), Cast(ref(3, dt.INT64), dt.INT32)),
        dte.DateSub(ref(0, dt.DATE), Literal(30, dt.INT32)),
        dte.DateDiff(ref(0, dt.DATE), ref(1, dt.DATE)),
        dte.Hour(ref(2, dt.TIMESTAMP)),
        dte.Minute(ref(2, dt.TIMESTAMP)),
        dte.Second(ref(2, dt.TIMESTAMP)),
        dte.Year(Cast(ref(2, dt.TIMESTAMP), dt.DATE)),
    ]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


# ---------------------------------------------------------------------------
# round-2 expression additions: two-arg log, weekday/time math, string
# index/replace, normalization wrappers
# ---------------------------------------------------------------------------


def test_logarithm_matrix():
    scan = dg.gen_scan({"a": dg.DoubleGen(), "b": dg.DoubleGen()},
                       n=200, seed=31)
    assert_cpu_and_tpu_equal(
        _project([mth.Logarithm(ref(0, dt.FLOAT64),
                                ref(1, dt.FLOAT64))], scan),
        conf=CONF, approx_float=1e-6)


def test_weekday_timeadd_tounix_matrix():
    scan = dg.gen_scan({"d": dg.DateGen(), "t": dg.TimestampGen()},
                       n=200, seed=32)
    exprs = [dte.WeekDay(ref(0, dt.DATE)),
             dte.ToUnixTimestamp(ref(1, dt.TIMESTAMP)),
             dte.TimeAdd(ref(1, dt.TIMESTAMP),
                         Literal(3_600_000_000, dt.INT64))]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_substring_index_regexp_replace_matrix():
    scan = dg.gen_scan({"s": dg.StringGen()}, n=200, seed=33)
    exprs = [st.SubstringIndex(ref(0, dt.STRING), "a", 1),
             st.SubstringIndex(ref(0, dt.STRING), "b", -2),
             st.RegExpReplace(ref(0, dt.STRING), "a", "_")]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)


def test_normalize_wrappers_matrix():
    from spark_rapids_tpu.expressions.constraints import (
        KnownFloatingPointNormalized, NormalizeNaNAndZero)

    scan = dg.gen_scan({"a": dg.DoubleGen()}, n=200, seed=34)
    exprs = [KnownFloatingPointNormalized(
        NormalizeNaNAndZero(ref(0, dt.FLOAT64)))]
    assert_cpu_and_tpu_equal(_project(exprs, scan), conf=CONF)
