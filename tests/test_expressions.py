"""Expression layer tests vs Spark SQL semantics (nulls, 3VL, div-by-zero,
java remainder, date math) — pandas/python is the oracle where applicable."""
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, Scalar, StringColumn
from spark_rapids_tpu.expressions import (
    Abs, Add, Alias, And, BoundReference, CaseWhen, Cast, Coalesce,
    CompiledFilter, CompiledProjection, Divide, EqualNullSafe, EqualTo,
    GreaterThan, If, In, IntegralDivide, IsNaN, IsNotNull, IsNull,
    LessThan, Literal, Multiply, NaNvl, Not, Or, Remainder, Subtract,
)
from spark_rapids_tpu.expressions import datetime as dtexpr
from spark_rapids_tpu.expressions import math as mexpr
from spark_rapids_tpu.expressions import strings as sexpr
from spark_rapids_tpu.expressions.base import EvalContext, broadcast


def make_batch(*cols_spec):
    cols = []
    for spec in cols_spec:
        if isinstance(spec, tuple):
            vals, validity = spec
        else:
            vals, validity = spec, None
        if isinstance(vals, list) and any(
                isinstance(v, str) or v is None for v in vals):
            cols.append(StringColumn.from_strings(vals))
        else:
            cols.append(Column.from_numpy(np.asarray(vals),
                                          validity=validity))
    n = len(cols_spec[0][0] if isinstance(cols_spec[0], tuple)
            else cols_spec[0])
    return ColumnarBatch(cols, n)


def run_project(exprs, batch):
    return CompiledProjection(exprs)(batch)


def col_out(batch, i=0):
    n = batch.realized_num_rows()
    return batch.columns[i].to_numpy(n)


def ref(i, t, nullable=True):
    return BoundReference(i, t, nullable)


def test_fused_arithmetic_pipeline():
    b = make_batch(np.array([1.0, 2.0, 3.0]),
                   np.array([10.0, 20.0, 30.0]))
    e = Add(Multiply(ref(0, dt.FLOAT64), Literal(2.0)), ref(1, dt.FLOAT64))
    proj = CompiledProjection([e])
    assert proj.fused
    out = proj(b)
    vals, _ = col_out(out)
    np.testing.assert_allclose(vals, [12.0, 24.0, 36.0])


def test_null_propagation_binary():
    b = make_batch((np.array([1.0, 2.0]), np.array([True, False])))
    out = run_project([Add(ref(0, dt.FLOAT64), Literal(1.0))], b)
    vals, v = col_out(out)
    assert vals[0] == 2.0
    assert v is not None and not v[1]


def test_divide_by_zero_is_null():
    b = make_batch(np.array([4.0, 9.0]), np.array([2.0, 0.0]))
    out = run_project([Divide(ref(0, dt.FLOAT64), ref(1, dt.FLOAT64))], b)
    vals, v = col_out(out)
    assert vals[0] == 2.0
    assert v is not None and not v[1]


def test_integral_divide_truncates_toward_zero():
    b = make_batch(np.array([-7, 7, -7], dtype=np.int64),
                   np.array([2, 2, -2], dtype=np.int64))
    out = run_project([IntegralDivide(ref(0, dt.INT64), ref(1, dt.INT64))], b)
    vals, _ = col_out(out)
    np.testing.assert_array_equal(vals, [-3, 3, 3])  # java semantics


def test_remainder_java_sign():
    b = make_batch(np.array([-7, 7], dtype=np.int64),
                   np.array([3, -3], dtype=np.int64))
    out = run_project([Remainder(ref(0, dt.INT64), ref(1, dt.INT64))], b)
    vals, _ = col_out(out)
    np.testing.assert_array_equal(vals, [-1, 1])  # sign of dividend


def test_and_or_three_valued_logic():
    t = np.array([True, False, True, False])
    validity = np.array([True, True, False, False])
    b = make_batch((t, validity), np.array([True, True, True, True]))
    # false AND null = false; null AND true = null
    out = run_project([And(ref(0, dt.BOOLEAN), ref(1, dt.BOOLEAN))], b)
    vals, v = col_out(out)
    assert vals[0] and not vals[1]
    assert v is not None
    assert v[1]  # false AND true = false, valid
    assert not v[2] and not v[3]  # null AND true = null
    b2 = make_batch((t, validity),
                    np.array([False, False, False, False]))
    out2 = run_project([And(ref(0, dt.BOOLEAN), ref(1, dt.BOOLEAN))], b2)
    _, v2 = col_out(out2)
    assert v2 is None or v2.all()  # x AND false = false (never null)


def test_comparisons_and_filter():
    b = make_batch(np.array([1, 5, 3, 8], dtype=np.int64))
    f = CompiledFilter(GreaterThan(ref(0, dt.INT64), Literal(3)))
    assert f.fused
    out = f(b)
    vals, _ = col_out(out)
    np.testing.assert_array_equal(sorted(vals.tolist()), [5, 8])


def test_is_null_not_null():
    b = make_batch((np.array([1, 2], dtype=np.int64),
                    np.array([True, False])))
    out = run_project([IsNull(ref(0, dt.INT64)),
                       IsNotNull(ref(0, dt.INT64))], b)
    nv, _ = col_out(out, 0)
    nn, _ = col_out(out, 1)
    np.testing.assert_array_equal(nv, [False, True])
    np.testing.assert_array_equal(nn, [True, False])


def test_case_when_with_null_predicate():
    pred_data = np.array([True, False, True])
    pred_valid = np.array([True, True, False])
    b = make_batch((pred_data, pred_valid),
                   np.array([10, 20, 30], dtype=np.int64))
    e = CaseWhen([(ref(0, dt.BOOLEAN), ref(1, dt.INT64))],
                 Literal(-1, dt.INT64))
    out = run_project([e], b)
    vals, v = col_out(out)
    np.testing.assert_array_equal(vals, [10, -1, -1])  # null pred -> else


def test_coalesce():
    b = make_batch((np.array([1, 0], dtype=np.int64),
                    np.array([True, False])),
                   (np.array([5, 7], dtype=np.int64), None))
    out = run_project([Coalesce([ref(0, dt.INT64), ref(1, dt.INT64)])], b)
    vals, v = col_out(out)
    np.testing.assert_array_equal(vals, [1, 7])
    assert v is None or v.all()


def test_nanvl_null_left_stays_null():
    vals = np.array([np.nan, 2.0, 1.0])
    validity = np.array([True, True, False])
    b = make_batch((vals, validity))
    out = run_project([NaNvl(ref(0, dt.FLOAT64), Literal(9.0))], b)
    v, valid = col_out(out)
    assert v[0] == 9.0 and v[1] == 2.0
    assert valid is not None and not valid[2]  # NULL stays NULL


def test_in_with_null_list():
    b = make_batch(np.array([1, 2, 3], dtype=np.int64))
    out = run_project([In(ref(0, dt.INT64), [1, None])], b)
    vals, v = col_out(out)
    assert vals[0]
    assert v is not None and not v[1] and not v[2]  # no-match + null -> null


def test_cast_float_to_int_java_semantics():
    b = make_batch(np.array([1.9, -1.9, np.nan, 1e300]))
    out = run_project([Cast(ref(0, dt.FLOAT64), dt.INT32)], b)
    vals, _ = col_out(out)
    np.testing.assert_array_equal(
        vals, [1, -1, 0, np.iinfo(np.int32).max])


def test_cast_string_to_int_invalid_is_null():
    b = make_batch(["12", "x", " 7 ", "9223372036854775808"])
    out = run_project([Cast(ref(0, dt.STRING), dt.INT64)], b)
    vals, v = col_out(out)
    assert vals[0] == 12 and vals[2] == 7
    assert v is not None and not v[1] and not v[3]


def test_cast_int_to_string():
    b = make_batch(np.array([1, -5], dtype=np.int64))
    out = run_project([Cast(ref(0, dt.INT64), dt.STRING)], b)
    vals, _ = col_out(out)
    assert list(vals) == ["1", "-5"]


def test_date_extracts():
    # 2020-02-29 = 18321 days since epoch
    b = make_batch(np.array([18321, 0], dtype=np.int32))
    b.columns[0].dtype = dt.DATE
    out = run_project([dtexpr.Year(ref(0, dt.DATE)),
                       dtexpr.Month(ref(0, dt.DATE)),
                       dtexpr.DayOfMonth(ref(0, dt.DATE)),
                       dtexpr.DayOfWeek(ref(0, dt.DATE)),
                       dtexpr.LastDay(ref(0, dt.DATE))], b)
    assert col_out(out, 0)[0].tolist() == [2020, 1970]
    assert col_out(out, 1)[0].tolist() == [2, 1]
    assert col_out(out, 2)[0].tolist() == [29, 1]
    # 2020-02-29 was a Saturday (7); 1970-01-01 Thursday (5)
    assert col_out(out, 3)[0].tolist() == [7, 5]
    # last day of feb 2020 = 2020-02-29 = 18321
    assert col_out(out, 4)[0].tolist()[0] == 18321


def test_timestamp_fields():
    us = (13 * 3600 + 45 * 60 + 7) * 1_000_000
    b = make_batch(np.array([us], dtype=np.int64))
    b.columns[0].dtype = dt.TIMESTAMP
    out = run_project([dtexpr.Hour(ref(0, dt.TIMESTAMP)),
                       dtexpr.Minute(ref(0, dt.TIMESTAMP)),
                       dtexpr.Second(ref(0, dt.TIMESTAMP))], b)
    assert col_out(out, 0)[0][0] == 13
    assert col_out(out, 1)[0][0] == 45
    assert col_out(out, 2)[0][0] == 7


def test_string_upper_length_substring():
    b = make_batch(["hello", "World", None])
    out = run_project([sexpr.Upper(ref(0, dt.STRING)),
                       sexpr.Length(ref(0, dt.STRING)),
                       sexpr.Substring(ref(0, dt.STRING), 2, 3)], b)
    up, upv = col_out(out, 0)
    assert list(up) == ["HELLO", "WORLD", None]
    ln, lnv = col_out(out, 1)
    assert ln[0] == 5 and ln[1] == 5 and lnv is not None and not lnv[2]
    sub, _ = col_out(out, 2)
    assert list(sub)[:2] == ["ell", "orl"]


def test_string_predicates_and_like():
    b = make_batch(["apple pie", "banana", "apricot"])
    out = run_project([
        sexpr.StartsWith(ref(0, dt.STRING), "ap"),
        sexpr.Contains(ref(0, dt.STRING), "an"),
        sexpr.Like(ref(0, dt.STRING), "a%t"),
    ], b)
    assert col_out(out, 0)[0].tolist() == [True, False, True]
    assert col_out(out, 1)[0].tolist() == [False, True, False]
    assert col_out(out, 2)[0].tolist() == [False, False, True]


def test_string_comparison_with_scalar_between_codes():
    b = make_batch(["apple", "fig", "zebra"])
    # "cat" is not in the dictionary: between "apple" and "fig"
    out = run_project([LessThan(ref(0, dt.STRING), Literal("cat"))], b)
    vals, _ = col_out(out)
    assert vals.tolist() == [True, False, False]


def test_string_eq_null_scalar_is_null():
    b = make_batch(["None", "x"])
    out = run_project([EqualTo(ref(0, dt.STRING),
                               Literal(None, dt.STRING))], b)
    _, v = col_out(out)
    assert v is not None and not v.any()


def test_string_column_comparison():
    b = make_batch(["b", "a", "c"], ["b", "b", "a"])
    out = run_project([EqualTo(ref(0, dt.STRING), ref(1, dt.STRING)),
                       GreaterThan(ref(0, dt.STRING), ref(1, dt.STRING))], b)
    assert col_out(out, 0)[0].tolist() == [True, False, False]
    assert col_out(out, 1)[0].tolist() == [False, False, True]


def test_concat_strings():
    b = make_batch(["a", None], ["x", "y"])
    out = run_project([sexpr.ConcatStrings(
        [ref(0, dt.STRING), Literal("-"), ref(1, dt.STRING)])], b)
    vals, v = col_out(out)
    assert vals[0] == "a-x"
    assert v is not None and not v[1]


def test_equal_null_safe():
    a = np.array([1, 2, 0], dtype=np.int64)
    av = np.array([True, True, False])
    bvals = np.array([1, 0, 0], dtype=np.int64)
    bv = np.array([True, False, False])
    b = make_batch((a, av), (bvals, bv))
    out = run_project([EqualNullSafe(ref(0, dt.INT64), ref(1, dt.INT64))], b)
    vals, v = col_out(out)
    assert v is None or v.all()
    np.testing.assert_array_equal(vals, [True, False, True])


def test_math_floor_ceil():
    b = make_batch(np.array([1.5, -1.5]))
    out = run_project([mexpr.Floor(ref(0, dt.FLOAT64)),
                       mexpr.Ceil(ref(0, dt.FLOAT64))], b)
    np.testing.assert_array_equal(col_out(out, 0)[0], [1, -2])
    np.testing.assert_array_equal(col_out(out, 1)[0], [2, -1])


def test_if_with_strings():
    pred = np.array([True, False])
    b = make_batch(pred, ["yes", "yes2"], ["no", "no2"])
    e = If(ref(0, dt.BOOLEAN), ref(1, dt.STRING), ref(2, dt.STRING))
    out = run_project([e], b)
    vals, _ = col_out(out)
    assert list(vals) == ["yes", "no2"]


def test_substring_negative_pos_past_start():
    # Spark: substring('abc', -5, 2) = '' (start+len still left of string)
    b = make_batch(["abc", "abcdef"])
    out = run_project([sexpr.Substring(ref(0, dt.STRING), -5, 2),
                       sexpr.Substring(ref(0, dt.STRING), -2, 5)], b)
    v0, _ = col_out(out, 0)
    v1, _ = col_out(out, 1)
    assert list(v0) == ["", "bc"]
    assert list(v1) == ["bc", "ef"]


_WORDS = ["", "a", "apple", "APPLESAUCE", "banana split", "a%b_c",
          "100%", "under_score", "the quick brown fox", "x", "ab" * 40,
          "café", "naïve", "日本語", "plain"]


def _word_rows(seed, n=64):
    """The words drawn with repeats, a fifth of the rows NULL."""
    r = np.random.default_rng(seed)
    rows = [_WORDS[i] for i in r.integers(0, len(_WORDS), n)]
    return [None if dead else w
            for w, dead in zip(rows, r.random(n) < 0.2)]


def _like(s, pat, esc="\\"):
    """SQL LIKE by recursion: % any run, _ one character."""
    if not pat:
        return not s
    if pat[0] == esc and len(pat) > 1:
        return s[:1] == pat[1] and _like(s[1:], pat[2:], esc)
    if pat[0] == "%":
        return any(_like(s[i:], pat[1:], esc) for i in range(len(s) + 1))
    return bool(s) and pat[0] in ("_", s[0]) and _like(s[1:], pat[1:], esc)


@pytest.mark.parametrize("make, want", [
    (lambda c: sexpr.Like(c, "%apple%"), lambda s: _like(s, "%apple%")),
    (lambda c: sexpr.Like(c, "a%b\\_c"), lambda s: _like(s, "a%b\\_c")),
    (lambda c: sexpr.Like(c, "100\\%"), lambda s: s == "100%"),
    (lambda c: sexpr.Like(c, "_pple"), lambda s: _like(s, "_pple")),
    (lambda c: sexpr.Like(c, "%quick%fox"),
     lambda s: _like(s, "%quick%fox")),
    (lambda c: sexpr.Like(c, "pl_in"), lambda s: s == "plain"),
    (lambda c: sexpr.Like(c, "caf_"), lambda s: s == "café"),
    (lambda c: sexpr.Like(c, "__語"), lambda s: s == "日本語"),
    (lambda c: sexpr.Contains(c, "an"), lambda s: "an" in s),
    (lambda c: sexpr.Contains(c, "ï"), lambda s: "ï" in s),
    (lambda c: sexpr.StartsWith(c, "a"), lambda s: s.startswith("a")),
    (lambda c: sexpr.EndsWith(c, "x"), lambda s: s.endswith("x")),
], ids=["like-infix", "like-escaped-underscore", "like-escaped-percent",
        "like-one-char", "like-two-runs", "like-one-char-ascii",
        "like-one-char-is-a-character-not-a-byte", "like-cjk",
        "contains", "contains-non-ascii", "startswith", "endswith"])
def test_string_predicates_differential(make, want):
    """LIKE / contains / startswith / endswith through the dictionary
    (one evaluation a distinct string, a gather of the codes) == plain
    Python applied row by row, over escapes, wildcards, non-ASCII
    strings and NULLs."""
    rows = _word_rows(3)
    out = run_project([make(ref(0, dt.STRING))], make_batch(rows))
    got, valid = col_out(out)
    for i, s in enumerate(rows):
        if s is None:
            assert valid is not None and not valid[i], i
        else:
            assert (valid is None or valid[i]) and \
                bool(got[i]) == bool(want(s)), (i, s)


@pytest.mark.parametrize("pos, length",
                         [(1, 3), (2, 100), (-3, 2), (0, 2), (5, 0),
                          (2, None)])
def test_substring_differential(pos, length):
    """substring through the dictionary rebuild == Spark's rule applied
    row by row; positions count characters, not bytes."""
    def want(s):
        start = pos - 1 if pos > 0 else len(s) + pos if pos < 0 else 0
        end = len(s) if length is None else start + length
        return s[max(start, 0):max(end, 0)]

    rows = _word_rows(17)
    out = run_project([sexpr.Substring(ref(0, dt.STRING), pos, length)],
                      make_batch(rows))
    got, _ = col_out(out)
    assert list(got) == [None if s is None else want(s) for s in rows]


def test_string_scalar_scalar_comparison():
    from spark_rapids_tpu.expressions import predicates as pexpr
    from spark_rapids_tpu.expressions.base import Literal
    b = make_batch(["x"])
    out = run_project([
        pexpr.EqualTo(Literal("a", dt.STRING), Literal("a", dt.STRING)),
        pexpr.LessThan(Literal("a", dt.STRING), Literal("b", dt.STRING)),
        pexpr.EqualNullSafe(Literal("a", dt.STRING), Literal("b", dt.STRING)),
    ], b)
    assert col_out(out, 0)[0][0]
    assert col_out(out, 1)[0][0]
    assert not col_out(out, 2)[0][0]


def test_inverse_hyperbolic_and_cot():
    vals = np.array([0.3, 1.5, 2.0, -0.4])
    b = make_batch(vals)
    out = run_project(
        [mexpr.Asinh(ref(0, dt.FLOAT64)), mexpr.Acosh(ref(0, dt.FLOAT64)),
         mexpr.Atanh(ref(0, dt.FLOAT64)), mexpr.Cot(ref(0, dt.FLOAT64))],
        b)
    with np.errstate(all="ignore"):
        np.testing.assert_allclose(col_out(out, 0)[0], np.arcsinh(vals))
        np.testing.assert_allclose(col_out(out, 1)[0], np.arccosh(vals))
        np.testing.assert_allclose(col_out(out, 2)[0], np.arctanh(vals))
        np.testing.assert_allclose(col_out(out, 3)[0], 1.0 / np.tan(vals))


def test_logarithm_two_arg():
    b = make_batch(np.array([2.0, 10.0, 3.0]),
                   np.array([8.0, 1000.0, 81.0]))
    out = run_project(
        [mexpr.Logarithm(ref(0, dt.FLOAT64), ref(1, dt.FLOAT64))], b)
    np.testing.assert_allclose(col_out(out)[0], [3.0, 3.0, 4.0],
                               rtol=1e-12)


def test_weekday_vs_dayofweek():
    import jax.numpy as jnp

    # 1970-01-01 (epoch day 0) was a Thursday
    days = jnp.asarray(np.array([0, 1, 2, 3, 4], dtype=np.int32))
    b = ColumnarBatch([Column(dt.DATE, days, None)], 5)
    out = run_project([dtexpr.WeekDay(ref(0, dt.DATE)),
                       dtexpr.DayOfWeek(ref(0, dt.DATE))], b)
    assert list(col_out(out, 0)[0]) == [3, 4, 5, 6, 0]   # Thu=3 Mon-based
    assert list(col_out(out, 1)[0]) == [5, 6, 7, 1, 2]   # Thu=5 Sun-based


def test_time_add_and_to_unix_timestamp():
    import jax.numpy as jnp

    ts = jnp.asarray(np.array([86_400_000_000, 1_000_000],
                              dtype=np.int64))
    b = ColumnarBatch([Column(dt.TIMESTAMP, ts, None)], 2)
    out = run_project(
        [dtexpr.TimeAdd(ref(0, dt.TIMESTAMP),
                        Literal(3_600_000_000, dt.INT64)),
         dtexpr.ToUnixTimestamp(ref(0, dt.TIMESTAMP))], b)
    assert list(col_out(out, 0)[0]) == [90_000_000_000, 3_601_000_000]
    assert list(col_out(out, 1)[0]) == [86_400, 1]


def test_substring_index():
    b = make_batch(["www.apache.org", "a.b", "noseparator", None])
    out = run_project(
        [sexpr.SubstringIndex(ref(0, dt.STRING), ".", 2),
         sexpr.SubstringIndex(ref(0, dt.STRING), ".", -1)], b)
    got2, _ = col_out(out, 0)
    got_1, _ = col_out(out, 1)
    # 'a.b' has one delimiter, so count=2 keeps the whole string (Spark)
    assert list(got2) == ["www.apache", "a.b", "noseparator", None]
    assert list(got_1) == ["org", "b", "noseparator", None]


def test_regexp_replace_simple_pattern():
    b = make_batch(["hello world", "nothing", None])
    out = run_project(
        [sexpr.RegExpReplace(ref(0, dt.STRING), "o", "0")], b)
    got, _ = col_out(out)
    assert list(got) == ["hell0 w0rld", "n0thing", None]


def test_regexp_replace_regex_pattern_falls_back():
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.execs.basic import CpuFallbackExec
    from spark_rapids_tpu.plan import nodes as pn
    from spark_rapids_tpu.plan.overrides import apply_overrides

    plan = pn.ProjectNode(
        [Alias(sexpr.RegExpReplace(ref(0, dt.STRING), "o+", "0"), "r")],
        pn.ScanNode(pn.InMemorySource(
            {"s": np.array(["foo", "oo"], dtype=object)})))
    exec_ = apply_overrides(plan, RapidsConf())
    assert isinstance(exec_, CpuFallbackExec)
    assert any("regex-free" in r for r in exec_.reasons)
    # the oracle-side fallback runs the real regex
    from spark_rapids_tpu.execs.base import collect

    got = collect(exec_)
    assert list(got["r"]) == ["f0", "0"]


def test_normalize_nan_and_zero():
    from spark_rapids_tpu.expressions.constraints import (
        KnownFloatingPointNormalized, NormalizeNaNAndZero)

    vals = np.array([-0.0, 0.0, np.nan, 1.5])
    b = make_batch(vals)
    out = run_project(
        [KnownFloatingPointNormalized(
            NormalizeNaNAndZero(ref(0, dt.FLOAT64)))], b)
    got, _ = col_out(out)
    assert not np.signbit(got[0])  # -0.0 normalized
    assert np.isnan(got[2]) and got[3] == 1.5


def test_fused_kernel_reuse_across_instances():
    """Structurally identical projections/filters share ONE jitted fn
    (fresh per-query plans must not re-trace); different types or
    literals must NOT collide."""
    from spark_rapids_tpu.expressions.compiler import (CompiledFilter,
                                                       CompiledProjection)

    def proj(lit):
        return CompiledProjection(
            [Add(Multiply(ref(0, dt.FLOAT64), Literal(lit)),
                 ref(1, dt.FLOAT64))])

    p1, p2 = proj(2.0), proj(2.0)
    assert p1.fused and p1._jit is p2._jit
    p3 = proj(3.0)
    assert p3._jit is not p1._jit
    # same ordinal, different declared type -> different kernels
    pa = CompiledProjection([Add(ref(0, dt.INT64), Literal(1))])
    pb = CompiledProjection([Add(ref(0, dt.INT32), Literal(1))])
    assert pa._jit is not pb._jit

    f1 = CompiledFilter(GreaterThan(ref(0, dt.FLOAT64), Literal(0.5)))
    f2 = CompiledFilter(GreaterThan(ref(0, dt.FLOAT64), Literal(0.5)))
    assert f1.fused and f1._mask is f2._mask

    # correctness through the shared kernel
    b = make_batch(np.array([1.0, 2.0]), np.array([10.0, 20.0]))
    np.testing.assert_allclose(col_out(p2(b))[0], [12.0, 24.0])
