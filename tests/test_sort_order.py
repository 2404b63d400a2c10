"""One way to order rows: ``ops/sortkeys.stable_order`` and its callers.

Differential: the only path against numpy's stable ``lexsort`` / Python's
stable ``sorted`` at every call site (ORDER BY batch, permutation,
partition split, compaction, group-by), over int64 keys beyond 2^32 and
negative, duplicate keys, NULLS FIRST and LAST, NaN and -0.0, zero live
rows and capacity 1. Structural: no ``lax.sort`` in the package carries a
column: read off the traced programs of TPC-H Q3's schemas at two
capacities and off the sources.
"""
from __future__ import annotations

import datetime
import os
import re

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops import groupby as gb
from spark_rapids_tpu.ops import partition as part
from spark_rapids_tpu.ops import sort as osort
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.ops.sortkeys import SortKeySpec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _get(x):
    return np.asarray(jax.device_get(x))


# ---------------------------------------------------------------------------
# stable_order itself against numpy's stable lexsort
# ---------------------------------------------------------------------------

def _lanes(case: str):
    """(lanes most significant first, bits)."""
    r = np.random.default_rng(17)
    n = 257
    if case == "int64_beyond_2_32_and_negative":
        k = r.integers(-(1 << 62), 1 << 62, n)
        k[:40] = r.integers(-3, 3, 40) * ((1 << 32) + 1)
        k[40:60] = [np.iinfo(np.int64).min, np.iinfo(np.int64).max] * 10
        return [k.astype(np.int64)], None
    if case == "int64_low_word_order":
        # equal high words: the low word must compare unsigned
        k = (5 << 32) + r.integers(0, 1 << 32, n)
        k[:8] = (5 << 32) + np.array([0, 1, (1 << 31) - 1, 1 << 31,
                                      (1 << 31) + 1, (1 << 32) - 1, 7, 7])
        return [k.astype(np.int64)], None
    if case == "uint64":
        return [r.integers(0, 1 << 63, n).astype(np.uint64) * 2 + 1], None
    if case == "duplicates_are_stable":
        return [r.integers(0, 3, n).astype(np.int32)], None
    if case == "promised_bits_one_lane":
        return [r.integers(0, 17, n).astype(np.int32)], [5]
    if case == "promised_bits_do_not_fit_with_index":
        return [r.integers(0, 1 << 30, n).astype(np.int32)], [30]
    if case == "bool_mask":
        return [r.random(n) > 0.5], None
    if case == "bool_int64_int32_float_bool":
        f = r.random(n)
        f[::7] = np.nan
        f[::11] = np.inf
        return [r.random(n) > 0.9, r.integers(-2, 2, n) * (1 << 40),
                r.integers(0, 3, n).astype(np.int32), f,
                r.random(n) > 0.5], [None, None, 2, None, None]
    if case == "many_flags_pack_past_32_bits":
        return [r.integers(0, 1 << 12, n).astype(np.int32)
                for _ in range(4)], [12] * 4
    if case == "int8_int16":
        return [r.integers(-128, 128, n).astype(np.int8),
                r.integers(-300, 300, n).astype(np.int16)], None
    if case == "capacity_1":
        return [np.array([7], np.int64), np.array([True])], None
    if case == "all_equal":
        return [np.zeros(n, np.int64)], None
    raise AssertionError(case)


@pytest.mark.parametrize("case", [
    "int64_beyond_2_32_and_negative", "int64_low_word_order", "uint64",
    "duplicates_are_stable", "promised_bits_one_lane",
    "promised_bits_do_not_fit_with_index", "bool_mask",
    "bool_int64_int32_float_bool", "many_flags_pack_past_32_bits",
    "int8_int16", "capacity_1", "all_equal"])
def test_stable_order_matches_numpy_lexsort(case):
    lanes, bits = _lanes(case)
    order, sorted_lanes = jax.jit(
        lambda ls: sortkeys.stable_order(ls, bits))(
            [jnp.asarray(x) for x in lanes])
    want = np.lexsort(tuple(reversed(lanes)))   # stable, last key primary
    assert _get(order).dtype == np.int32
    np.testing.assert_array_equal(_get(order), want)
    for got, lane in zip(sorted_lanes, lanes):
        assert _get(got).dtype == lane.dtype
        np.testing.assert_array_equal(_get(got), lane[want])


@pytest.mark.parametrize("mask", [
    np.random.default_rng(11).random(257) > 0.5, np.ones(64, bool),
    np.zeros(64, bool), np.array([True]), np.array([False])],
    ids=["random257", "all_true", "all_false", "one_true", "one_false"])
def test_partition_order_matches_stable_argsort(mask):
    """Compaction: ``mask``-true rows first, both halves in row order."""
    order, _ = sortkeys.stable_order([~jnp.asarray(mask)])
    np.testing.assert_array_equal(
        _get(order), np.argsort(~mask, kind="stable"))


@pytest.mark.parametrize("n_cols", [1, 3, 32, 33, 70])
def test_take_rows_moves_validities_as_bits(n_cols):
    """Validities travel 32 to a word; columns without one stay None."""
    r = np.random.default_rng(n_cols)
    n = 97
    order = r.permutation(n).astype(np.int32)
    datas = [r.integers(-9, 9, n).astype(np.int64) for _ in range(n_cols)]
    valids = [None if c % 5 == 4 else r.random(n) > 0.4
              for c in range(n_cols)]
    out_d, out_v = jax.jit(sortkeys.take_rows)(
        jnp.asarray(order), [jnp.asarray(d) for d in datas],
        [None if v is None else jnp.asarray(v) for v in valids])
    for d, v, got_d, got_v in zip(datas, valids, out_d, out_v):
        np.testing.assert_array_equal(_get(got_d), d[order])
        if v is None:
            assert got_v is None
        else:
            assert _get(got_v).dtype == np.bool_
            np.testing.assert_array_equal(_get(got_v), v[order])


# ---------------------------------------------------------------------------
# ORDER BY: sort_batch and lexsort_indices against Python's stable sorted()
# ---------------------------------------------------------------------------

def _sort_batch(cap, n, seed, float_key=False):
    r = np.random.default_rng(seed)
    k1 = r.integers(-50, 50, size=cap).astype(np.int64) * ((1 << 33) + 3)
    v1 = r.random(cap) > 0.2
    if float_key:
        k2 = r.integers(-2, 3, size=cap).astype(np.float64)
        k2[::5] = np.nan
        k2[1::9] = -0.0
        k2[2::9] = 0.0
    else:
        k2 = r.integers(0, 5, size=cap).astype(np.int32)
    pay = r.integers(0, 10**6, size=cap).astype(np.int64)
    t2 = dt.FLOAT64 if float_key else dt.INT32
    cols = [Column(dt.INT64, jnp.asarray(k1), jnp.asarray(v1)),
            Column(t2, jnp.asarray(k2), None),
            Column(dt.INT64, jnp.asarray(pay), None)]
    return ColumnarBatch(cols, n), [dt.INT64, t2, dt.INT64], \
        [(k1, v1), (k2, None), (pay, None)]


def _reference_order(host_cols, specs, n):
    """Spark's ORDER BY on the live rows: Python's stable sort over key
    tuples (null rank, NaN rank, value); -0.0 == 0.0, NaN greatest."""
    def term(spec, i):
        data, valid = host_cols[spec.ordinal]
        null = valid is not None and not valid[i]
        null_rank = int(not null) if spec.nulls_first else int(null)
        if null:
            return (null_rank, 0, 0)
        x = data[i].item()
        nan_rank = 0
        if isinstance(x, float) and np.isnan(x):
            nan_rank, x = 1, 0.0
        if not spec.ascending:
            nan_rank, x = -nan_rank, -x
        return (null_rank, nan_rank, x)

    return sorted(range(n), key=lambda i: tuple(term(s, i) for s in specs))


SPEC_CASES = {
    "desc_nulls_last_then_asc": (
        SortKeySpec(0, ascending=False, nulls_first=False),
        SortKeySpec(1, ascending=True, nulls_first=True)),
    "asc_nulls_first_then_desc": (
        SortKeySpec(0, ascending=True, nulls_first=True),
        SortKeySpec(1, ascending=False, nulls_first=False)),
    "second_key_only_desc": (
        SortKeySpec(1, ascending=False, nulls_first=True),),
}


@pytest.mark.parametrize("specs", list(SPEC_CASES), ids=list(SPEC_CASES))
@pytest.mark.parametrize("float_key", [False, True])
def test_sort_batch_differential(float_key, specs):
    """ops/sort.sort_batch: composite keys with nulls, asc/desc, NULLS
    FIRST/LAST, int64 beyond 2^32; the float key brings NaN and -0.0."""
    specs = SPEC_CASES[specs]
    n = 117
    batch, types, host = _sort_batch(160, n, seed=5, float_key=float_key)
    out = osort.sort_batch(batch, list(specs), types)
    want = _reference_order(host, specs, n)
    assert int(jax.device_get(out.num_rows_device())) == n
    for c, (data, valid) in zip(out.columns, host):
        np.testing.assert_array_equal(_get(c.data)[:n], data[want])
        if valid is None:
            assert c.validity is None
        else:
            np.testing.assert_array_equal(_get(c.validity)[:n],
                                          valid[want])


def test_sort_indices_differential():
    specs = (SortKeySpec(0, ascending=True, nulls_first=False),)
    batch, types, host = _sort_batch(96, 96, seed=9)
    cols = [(c.data, c.validity) for c in batch.columns]
    got = jax.jit(lambda cs, n: sortkeys.lexsort_indices(
        cs, types, list(specs), n))(cols, batch.num_rows_device())
    np.testing.assert_array_equal(_get(got),
                                  _reference_order(host, specs, 96))


@pytest.mark.parametrize("cap,n", [(64, 0), (1, 1), (1, 0)],
                         ids=["zero_live_rows", "capacity_1",
                              "capacity_1_empty"])
def test_sort_empty_partition(cap, n):
    """Zero live rows: every row is padding; what comes out is still
    the batch's rows, each once."""
    batch, types, host = _sort_batch(cap, n, seed=13)
    out = osort.sort_batch(batch, [SortKeySpec(0)], types)
    assert int(jax.device_get(out.num_rows_device())) == n
    np.testing.assert_array_equal(np.sort(_get(out.columns[2].data)),
                                  np.sort(host[2][0]))


# ---------------------------------------------------------------------------
# partition split and group-by against numpy / pandas
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap,n,parts", [(256, 201, 16), (256, 0, 4),
                                         (1, 1, 3), (512, 512, 200)])
def test_partition_kernel_matches_stable_numpy_partition(cap, n, parts):
    r = np.random.default_rng(cap + n + parts)
    pid = r.integers(0, parts, cap).astype(np.int32)
    d0 = r.integers(-(1 << 40), 1 << 40, cap).astype(np.int64)
    d1 = r.random(cap)
    v1 = r.random(cap) > 0.3
    out_d, out_v, counts = part._partition_kernel(
        [jnp.asarray(d0), jnp.asarray(d1)], [None, jnp.asarray(v1)],
        jnp.asarray(pid), jnp.int32(n), parts)
    want = np.argsort(pid[:n], kind="stable")
    np.testing.assert_array_equal(
        _get(counts), np.bincount(pid[:n], minlength=parts))
    np.testing.assert_array_equal(_get(out_d[0])[:n], d0[:n][want])
    np.testing.assert_array_equal(_get(out_d[1])[:n], d1[:n][want])
    assert out_v[0] is None
    np.testing.assert_array_equal(_get(out_v[1])[:n], v1[:n][want])


@pytest.mark.parametrize("with_nulls", [False, True])
def test_groupby_sort_path_int64_keys_beyond_2_32(with_nulls):
    """The sort path (no host-known range): int64 keys beyond 2^32 and
    negative next to an int32 key, groups in key order, nulls first."""
    r = np.random.default_rng(23)
    cap, n = 512, 431
    k0 = r.integers(-4, 4, cap).astype(np.int64) * ((1 << 35) + 11)
    k1 = r.integers(0, 3, cap).astype(np.int32)
    x = r.random(cap)
    v0 = r.random(cap) > 0.15 if with_nulls else None
    batch = ColumnarBatch(
        [Column(dt.INT64, jnp.asarray(k0),
                None if v0 is None else jnp.asarray(v0)),
         Column(dt.INT32, jnp.asarray(k1), None),
         Column(dt.FLOAT64, jnp.asarray(x), None)], n)
    out, _ = gb.groupby_aggregate(
        batch, [0, 1], [gb.AggSpec("sum", 2), gb.AggSpec("count_star")],
        [dt.INT64, dt.INT32, dt.FLOAT64])
    ng = int(jax.device_get(out.num_rows_device()))
    pdf = pd.DataFrame({"k0": k0[:n], "k1": k1[:n], "x": x[:n]})
    if v0 is not None:
        pdf["k0"] = pdf["k0"].astype("Int64").mask(~v0[:n])
    want = pdf.groupby(["k0", "k1"], dropna=False, sort=True).agg(
        s=("x", "sum"), c=("x", "size")).reset_index()
    if v0 is not None:   # pandas sorts NA last; the engine groups it first
        na = want["k0"].isna()
        want = pd.concat([want[na], want[~na]], ignore_index=True)
    assert ng == len(want)
    got_k0 = _get(out.columns[0].data)[:ng]
    if v0 is not None:
        valid = _get(out.columns[0].validity)[:ng]
        np.testing.assert_array_equal(valid, ~want["k0"].isna().values)
        got_k0 = np.where(valid, got_k0, 0)
    np.testing.assert_array_equal(
        got_k0, want["k0"].fillna(0).astype(np.int64).values)
    np.testing.assert_array_equal(_get(out.columns[1].data)[:ng],
                                  want["k1"].values)
    np.testing.assert_allclose(_get(out.columns[2].data)[:ng],
                               want["s"].values, rtol=1e-12)
    np.testing.assert_array_equal(_get(out.columns[3].data)[:ng],
                                  want["c"].values)


# ---------------------------------------------------------------------------
# structure: what a sort may carry
# ---------------------------------------------------------------------------

def _sort_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sort_eqns(inner)


def _q3_programs(cap):
    """TPC-H Q3's three sort programs at one capacity: the exchange's
    split of lineitem's cached columns, the group-by on (l_orderkey,
    o_orderdate, o_shippriority) summing revenue, the ORDER BY revenue
    DESC, o_orderdate. Shapes only: nothing runs."""
    def s(t):
        return jax.ShapeDtypeStruct((cap,), t)

    n = jax.ShapeDtypeStruct((), jnp.int32)
    lineitem = [jnp.int64] * 4 + [jnp.float64] * 4 + [jnp.int32] * 7
    yield "_partition_kernel", part._partition_kernel, (
        [s(t) for t in lineitem], [s(jnp.bool_)] * len(lineitem),
        s(jnp.int32), n), {"num_partitions": 16}
    agg_cols = [(s(jnp.int64), None), (s(jnp.int32), s(jnp.bool_)),
                (s(jnp.int32), None), (s(jnp.float64), s(jnp.bool_))]
    agg_types = (dt.INT64, dt.DATE, dt.INT32, dt.FLOAT64)
    yield "_groupby", gb._groupby, (
        agg_cols, agg_types, (0, 1, 2), (gb.AggSpec("sum", 3),), n), {}
    out_types = (dt.INT64, dt.FLOAT64, dt.DATE, dt.INT32)
    yield "_sort_batch", osort._sort_batch, (
        [s(jnp.int64), s(jnp.float64), s(jnp.int32), s(jnp.int32)],
        [None, s(jnp.bool_), s(jnp.bool_), None], out_types,
        (SortKeySpec(1, False, False), SortKeySpec(2, True, True)), n), {}


@pytest.mark.parametrize("program", ["_partition_kernel", "_groupby",
                                     "_sort_batch"])
def test_q3_sorts_take_one_key_word_and_the_index(program):
    """Every sort of Q3's programs has ONE word of key and, unless the
    word holds it, the int32 row index as operands, both compared, none
    carried, nothing of 64 bits but a float key; and capacity changes
    shapes and constants, not the program: the same operations in the
    same order at 1,024 and at 65,536 rows (the group-by's segmented
    scans unroll a level a doubling, so there the kinds of operation
    are compared, not their order)."""
    ops_at = {}
    for cap in (1024, 65536):
        name, fn, args, kw = next(
            p for p in _q3_programs(cap) if p[0] == program)
        traced = fn.trace(*args, **kw)
        sorts = list(_sort_eqns(traced.jaxpr.jaxpr))
        assert sorts, "no sort in " + name
        for eqn in sorts:
            kinds = [str(v.aval.dtype) for v in eqn.invars]
            assert eqn.params["num_keys"] == len(kinds), \
                f"{name}: a sort carries {kinds}"
            assert not eqn.params["is_stable"]
            assert all(v.aval.shape == (cap,) for v in eqn.invars)
            assert kinds == ["uint32"] or (
                len(kinds) == 2 and kinds[1] == "int32" and
                kinds[0] in ("uint32", "float32", "float64")), kinds
        ops = re.findall(r"stablehlo\.[a-z_]+", traced.lower().as_text())
        if program == "_groupby":
            ops = sorted(set(ops))
        ops_at[cap] = (ops, [[str(v.aval.dtype) for v in e.invars]
                             for e in sorts])
    assert ops_at[1024] == ops_at[65536]
    assert ops_at[1024][1] == {
        # partition id and row index in one word
        "_partition_kernel": [["uint32"]],
        # one compiled pass for the five words (rank | l_orderkey high,
        # low | o_orderdate | o_shippriority); then the group boundaries
        # and the row index in one word
        "_groupby": [["uint32", "int32"], ["uint32"]],
        # least significant first: o_orderdate's NULL rank and
        # o_orderdate (one compiled pass for both) | -revenue | the
        # padding, NULL and NaN ranks
        "_sort_batch": [["uint32", "int32"], ["float64", "int32"],
                        ["uint32", "int32"]],
    }[program]


def test_partition_id_and_index_share_one_sort_operand():
    """16 partitions of 2,097,152 rows: 5 + 21 bits, one uint32 lane."""
    name, fn, args, kw = next(iter(_q3_programs(1 << 21)))
    (eqn,) = _sort_eqns(fn.trace(*args, **kw).jaxpr.jaxpr)
    assert [str(v.aval.dtype) for v in eqn.invars] == ["uint32"]


def test_no_sort_outside_sortkeys_in_the_sources():
    """The git-grep fence: ``lax.sort(`` is called in ops/sortkeys.py
    (the one-word sort and the radix pass of ``stable_order``) and
    nowhere else under spark_rapids_tpu/, and nothing there reaches a
    device sort through ``jnp.argsort``/``jnp.lexsort``, whose index is
    an int64 lane under x64. (``jnp.sort`` of one lane alone carries
    nothing and stays: parallel/sort_step.py's sample bounds.)"""
    pkg = os.path.join(ROOT, "spark_rapids_tpu")
    call = re.compile(r"\b(lax\.sort(_key_val)?|jnp\.(argsort|lexsort))\(")
    found = []
    for base, _, files in os.walk(pkg):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(base, f)
                with open(path) as fh:
                    for i, line in enumerate(fh, 1):
                        if call.search(line):
                            found.append(
                                f"{os.path.relpath(path, ROOT)}:{i}")
    allowed = [x for x in found
               if x.startswith("spark_rapids_tpu/ops/sortkeys.py:")]
    assert found == allowed and len(allowed) == 2, found


# ---------------------------------------------------------------------------
# TPC-H Q3 through Session.sql over three cached tables
# ---------------------------------------------------------------------------

Q3 = """
    SELECT l_orderkey,
           sum(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer
    JOIN orders ON c_custkey = o_custkey
    JOIN lineitem ON l_orderkey = o_orderkey
    WHERE c_mktsegment = 'BUILDING'
      AND o_orderdate < DATE '1995-03-15'
      AND l_shipdate > DATE '1995-03-15'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate
    LIMIT 10
"""


def test_q3_over_cached_tables_matches_pandas_merge(tmp_path):
    """Q3 (TPC-H 2.4.3, validation parameters) at sf 0.02 from the
    benchmark's generator, the three tables cached on the device without
    their comment columns, against a pandas merge in float64."""
    import pyarrow.parquet as pq

    from benchmark.datagen import tpch_like
    from spark_rapids_tpu.api import Session

    tables = ("lineitem", "orders", "customer")
    tpch_like.write_tables(str(tmp_path), 0.02, 27, tables)
    s = Session()
    host = {}
    for t in tables:
        path = str(tmp_path / t)
        cols = [c for c in pq.read_schema(
            os.path.join(path, "part-000.parquet")).names
            if not c.endswith("_comment")]
        s.read.parquet(path, columns=cols).cache() \
            .create_or_replace_temp_view(t)
        host[t] = pq.read_table(path, columns=cols).to_pandas()
    df = s.sql(Q3)
    plan = df.explain()
    assert all(ln.strip().startswith("* ") for ln in plan.splitlines()
               if ln.strip()), plan
    got = df.collect()

    cut = pd.Timestamp(datetime.date(1995, 3, 15))
    c = host["customer"][host["customer"].c_mktsegment == "BUILDING"]
    o = host["orders"][pd.to_datetime(host["orders"].o_orderdate) < cut]
    li = host["lineitem"][
        pd.to_datetime(host["lineitem"].l_shipdate) > cut]
    m = c.merge(o, left_on="c_custkey", right_on="o_custkey") \
         .merge(li, left_on="o_orderkey", right_on="l_orderkey")
    m["revenue"] = m.l_extendedprice * (1.0 - m.l_discount)
    want = m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                     as_index=False)["revenue"].sum() \
        .sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                     kind="stable").head(10)

    assert list(got.columns) == ["l_orderkey", "revenue", "o_orderdate",
                                 "o_shippriority"]
    assert len(got) == len(want) == 10
    np.testing.assert_array_equal(got.l_orderkey.values,
                                  want.l_orderkey.values)
    np.testing.assert_array_equal(got.o_shippriority.values,
                                  want.o_shippriority.values)
    days = pd.to_datetime(want.o_orderdate).values.astype(
        "datetime64[D]").astype(np.int64)
    np.testing.assert_array_equal(
        np.asarray(got.o_orderdate, dtype=np.int64), days)
    np.testing.assert_allclose(got.revenue.values, want.revenue.values,
                               rtol=1e-10)
