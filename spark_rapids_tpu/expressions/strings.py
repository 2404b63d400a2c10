"""String expressions (reference .../stringFunctions.scala, 862 LoC:
substr/pad/split/locate/replace/trim/starts/ends/contains/like/concat/
upper/lower/length).

TPU-native strategy: strings are dictionary-encoded (sorted dict host-side,
int32 codes on device). Every string function factors as

    per-dictionary-entry host transform  (once per UNIQUE value)
  + device gather by code               (once per row)

so row-scale work stays on device and host work is O(cardinality). This is
the honest TPU answer to cuDF's native string kernels (SURVEY.md §7
"Strings" flags them as the biggest compat risk): semantics first, with the
host transform amortized across batches by dictionary caching.

These nodes are ``device_only = False`` — the planner keeps them out of
fused jit regions (they still do their row-scale gathers on device).

LIKE patterns support %, _ with regex translation; the reference similarly
gates regexp to trivially-convertible patterns (GpuOverrides.scala:343-351).
"""
from __future__ import annotations

import re
from typing import Callable, List, Optional

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Scalar, StringColumn
from spark_rapids_tpu.expressions.base import ColV, EvalContext, EvalValue, \
    Expression


def _dict_map_str(v: ColV, fn: Callable[[str], str]) -> ColV:
    """str->str via dictionary rebuild + device remap."""
    assert v.scol is not None
    dic = v.scol.dictionary
    if len(dic) == 0:
        return v
    transformed = np.array([fn(str(s)) for s in dic], dtype=object)
    new_dict, inv = np.unique(transformed.astype(str), return_inverse=True)
    remap = jnp.asarray(inv.astype(np.int32))
    codes = jnp.take(remap, v.data, mode="clip")
    sc = StringColumn(codes, new_dict.astype(object), v.validity)
    return ColV(dt.STRING, codes, v.validity, sc)


def _dict_map_val(v: ColV, fn: Callable[[str], object],
                  out_dtype: dt.DType) -> ColV:
    """str->numeric/bool via per-entry table + device gather."""
    assert v.scol is not None
    dic = v.scol.dictionary
    table = np.array([fn(str(s)) for s in dic] if len(dic) else [0],
                     dtype=out_dtype.np_dtype)
    data = jnp.take(jnp.asarray(table), v.data, mode="clip")
    return ColV(out_dtype, data, v.validity)


def _eval_str_unary(expr: Expression, ctx: EvalContext, fn_str,
                    out_dtype: dt.DType) -> EvalValue:
    v = expr.children[0].eval(ctx)
    if isinstance(v, Scalar):
        if v.is_null:
            return Scalar(out_dtype, None)
        return Scalar(out_dtype, fn_str(str(v.value)))
    if out_dtype is dt.STRING:
        return _dict_map_str(v, fn_str)
    return _dict_map_val(v, fn_str, out_dtype)


class _StrUnary(Expression):
    out_type = dt.STRING

    def __init__(self, child: Expression):
        super().__init__([child])

    @property
    def dtype(self):
        return type(self).out_type

    @property
    def device_only(self):
        return False

    def fn(self, s: str):  # pragma: no cover - abstract
        raise NotImplementedError

    def eval(self, ctx):
        return _eval_str_unary(self, ctx, self.fn, self.dtype)


class Upper(_StrUnary):
    """Flagged incompat in the reference for non-ASCII unicode corner cases
    (GpuOverrides.scala:337-340); python .upper() is unicode-correct."""

    def fn(self, s):
        return s.upper()


class Lower(_StrUnary):
    def fn(self, s):
        return s.lower()


class Length(_StrUnary):
    out_type = dt.INT32

    def fn(self, s):
        return len(s)


class StringTrim(_StrUnary):
    def fn(self, s):
        return s.strip()


class StringTrimLeft(_StrUnary):
    def fn(self, s):
        return s.lstrip()


class StringTrimRight(_StrUnary):
    def fn(self, s):
        return s.rstrip()


class InitCap(_StrUnary):
    def fn(self, s):
        return " ".join(w.capitalize() for w in s.split(" "))


class Reverse(_StrUnary):
    def fn(self, s):
        return s[::-1]


class Substring(Expression):
    """substring(str, pos, len) with Spark 1-based/negative pos semantics.
    pos/len must be literals (the planner falls back otherwise — matching
    the reference's lit-only GpuSubstring, GpuOverrides.scala:398-421)."""

    def __init__(self, child: Expression, pos: int, length: Optional[int]):
        super().__init__([child])
        self.pos = pos
        self.length = length

    @property
    def dtype(self):
        return dt.STRING

    @property
    def device_only(self):
        return False

    def fn(self, s: str) -> str:
        pos, ln = self.pos, self.length
        if pos > 0:
            start = pos - 1
        elif pos < 0:
            start = len(s) + pos
        else:
            start = 0
        # clamp only after end is derived from the unclamped start, so
        # substring('abc', -5, 2) = '' (Spark UTF8String.substringSQL), not 'ab'
        end = len(s) if ln is None else start + ln
        return s[max(start, 0):max(end, 0)]

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        if isinstance(v, Scalar):
            if v.is_null:
                return Scalar(dt.STRING, None)
            return Scalar(dt.STRING, self.fn(str(v.value)))
        return _dict_map_str(v, self.fn)


class StringReplace(Expression):
    def __init__(self, child: Expression, search: str, replace: str):
        super().__init__([child])
        self.search = search
        self.replace = replace

    @property
    def dtype(self):
        return dt.STRING

    @property
    def device_only(self):
        return False

    def eval(self, ctx):
        return _eval_str_unary(
            self, ctx, lambda s: s.replace(self.search, self.replace),
            dt.STRING)


class SubstringIndex(_StrUnary):
    """substring_index(str, delim, count): count>0 keeps everything
    before the count-th delimiter from the left, count<0 everything after
    the |count|-th from the right, 0 -> empty (Spark semantics)."""

    def __init__(self, child: Expression, delim: str, count: int):
        super().__init__(child)
        self.delim = delim
        self.count = count

    def fn(self, s):
        if self.count == 0 or not self.delim:
            return ""
        parts = s.split(self.delim)
        if self.count > 0:
            return self.delim.join(parts[:self.count])
        return self.delim.join(parts[self.count:])


_REGEX_METACHARS = set("\\^$.|?*+()[]{}")


class RegExpReplace(_StrUnary):
    """regexp_replace limited to regex-free search patterns — exactly the
    reference's constraint (GpuOverrides.scala:343-351
    isSupportedStringReplacePattern gates GpuRegExpReplace on patterns
    with no regex metacharacters); anything else falls back to the CPU
    engine, whose oracle implementation runs the full regex."""

    def __init__(self, child: Expression, pattern: str, replacement: str):
        super().__init__(child)
        self.pattern = pattern
        self.replacement = replacement

    def fn(self, s):
        return s.replace(self.pattern, self.replacement)

    def tag_self(self, meta, conf):
        if not self.pattern or \
                any(c in _REGEX_METACHARS for c in self.pattern):
            meta.will_not_work(
                "regexp_replace on the TPU requires a non-empty, "
                "regex-free pattern (GpuOverrides.scala:343-351)")
        if "\\" in self.replacement or "$" in self.replacement:
            meta.will_not_work(
                "regexp_replace replacement must not contain "
                "backreferences (GpuOverrides.scala:423-438)")


class StringRepeat(Expression):
    def __init__(self, child: Expression, times: int):
        super().__init__([child])
        self.times = times

    @property
    def dtype(self):
        return dt.STRING

    @property
    def device_only(self):
        return False

    def eval(self, ctx):
        return _eval_str_unary(self, ctx, lambda s: s * max(self.times, 0),
                               dt.STRING)


class _Pad(Expression):
    left = True

    def __init__(self, child: Expression, width: int, pad: str = " "):
        super().__init__([child])
        self.width = width
        self.pad = pad

    @property
    def dtype(self):
        return dt.STRING

    @property
    def device_only(self):
        return False

    def fn(self, s: str) -> str:
        w, p = self.width, self.pad
        if len(s) >= w:
            return s[:w]
        if not p:
            return s
        fill = (p * w)[: w - len(s)]
        return fill + s if type(self).left else s + fill

    def eval(self, ctx):
        return _eval_str_unary(self, ctx, self.fn, dt.STRING)


class StringLPad(_Pad):
    left = True


class StringRPad(_Pad):
    left = False


class _StrPredicate(Expression):
    """starts_with/ends_with/contains vs a literal needle."""

    def __init__(self, child: Expression, needle: str):
        super().__init__([child])
        self.needle = needle

    @property
    def dtype(self):
        return dt.BOOLEAN

    @property
    def device_only(self):
        return False

    def test(self, s: str) -> bool:  # pragma: no cover - abstract
        raise NotImplementedError

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        if isinstance(v, Scalar):
            if v.is_null:
                return Scalar(dt.BOOLEAN, None)
            return Scalar(dt.BOOLEAN, self.test(str(v.value)))
        return _dict_map_val(v, self.test, dt.BOOLEAN)


class StartsWith(_StrPredicate):
    def test(self, s):
        return s.startswith(self.needle)


class EndsWith(_StrPredicate):
    def test(self, s):
        return s.endswith(self.needle)


class Contains(_StrPredicate):
    def test(self, s):
        return self.needle in s


class Like(_StrPredicate):
    """SQL LIKE: % any-seq, _ any-char, escape supported."""

    def __init__(self, child: Expression, pattern: str, escape: str = "\\"):
        super().__init__(child, pattern)
        self.pattern = pattern
        self.escape = escape
        regex = []
        i = 0
        while i < len(pattern):
            ch = pattern[i]
            if ch == escape and i + 1 < len(pattern):
                regex.append(re.escape(pattern[i + 1]))
                i += 2
                continue
            if ch == "%":
                regex.append(".*")
            elif ch == "_":
                regex.append(".")
            else:
                regex.append(re.escape(ch))
            i += 1
        self._re = re.compile("(?s)^" + "".join(regex) + "$")

    def test(self, s):
        return self._re.match(s) is not None


class StringLocate(Expression):
    """locate(needle, str, start=1): 1-based position, 0 if absent."""

    def __init__(self, needle: str, child: Expression, start: int = 1):
        super().__init__([child])
        self.needle = needle
        self.start = start

    @property
    def dtype(self):
        return dt.INT32

    @property
    def device_only(self):
        return False

    def eval(self, ctx):
        def f(s: str) -> int:
            return s.find(self.needle, max(self.start - 1, 0)) + 1

        return _eval_str_unary(self, ctx, f, dt.INT32)


class ConcatStrings(Expression):
    """concat of N string columns. Multi-column dictionary products can
    explode, so this materializes rows host-side — correct first; planner
    marks it high-cost. Null if any input null (Spark concat)."""

    def __init__(self, children: List[Expression]):
        super().__init__(children)

    @property
    def dtype(self):
        return dt.STRING

    @property
    def device_only(self):
        return False

    def eval(self, ctx):
        import jax

        parts = []
        validity = None
        for c in self.children:
            v = c.eval(ctx)
            if isinstance(v, Scalar):
                if v.is_null:
                    return Scalar(dt.STRING, None)
                parts.append([str(v.value)])
                continue
            scol = v.scol
            assert scol is not None
            codes = np.asarray(jax.device_get(v.data))
            dic = scol.dictionary
            vals = dic[np.clip(codes, 0, max(len(dic) - 1, 0))] \
                if len(dic) else np.full(len(codes), "", dtype=object)
            parts.append(vals)
            if v.validity is not None:
                vv = v.validity
                validity = vv if validity is None else (validity & vv)
        cap = ctx.capacity
        out = []
        for i in range(cap):
            out.append("".join(
                str(p[i] if len(p) > 1 else p[0]) for p in parts))
        sc = StringColumn.from_strings(out, capacity=cap)
        return ColV(dt.STRING, sc.data, validity, sc)


#: Spark's Concat over string children — same node (the reference
#: registers Concat, GpuOverrides.scala registry)
Concat = ConcatStrings
