"""Kernel-surface tests against numpy/pandas oracles (the reference's
CPU-as-oracle methodology, SURVEY.md §4, applied per kernel)."""
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import concat, filter as filt, groupby, hashing, \
    join, partition, sort
from spark_rapids_tpu.ops.groupby import AggSpec
from spark_rapids_tpu.ops.sortkeys import SortKeySpec


def make_batch(*arrays, validities=None, n=None):
    cols = []
    for i, a in enumerate(arrays):
        v = validities[i] if validities else None
        if isinstance(a[0] if len(a) else "", str) or (
                len(a) and a[0] is None and isinstance(a, list)):
            cols.append(StringColumn.from_strings(list(a)))
        else:
            cols.append(Column.from_numpy(np.asarray(a), validity=v))
    nn = n if n is not None else len(arrays[0])
    return ColumnarBatch(cols, nn)


# ---------------------------------------------------------------- filter

def test_filter_compact():
    b = make_batch(np.arange(10, dtype=np.int64))
    keep = jnp.asarray(np.pad(np.arange(10) % 3 == 0, (0, 118)))
    out = filt.compact_batch(b, keep)
    assert out.realized_num_rows() == 4
    vals, _ = out.columns[0].to_numpy(4)
    np.testing.assert_array_equal(vals, [0, 3, 6, 9])


def test_filter_null_predicate_drops():
    b = make_batch(np.arange(4, dtype=np.int64))
    keep = jnp.asarray(np.pad([True, True, False, True], (0, 124)))
    keep_valid = jnp.asarray(np.pad([True, False, True, True], (0, 124)))
    out = filt.compact_batch(b, keep, keep_valid)
    vals, _ = out.columns[0].to_numpy(out.realized_num_rows())
    np.testing.assert_array_equal(vals, [0, 3])


# ---------------------------------------------------------------- sort

def test_sort_two_keys_desc_nulls():
    a = np.array([3, 1, 2, 1, 3], dtype=np.int64)
    b = np.array([1.0, 2.0, np.nan, 1.0, -0.0])
    bv = np.array([True, True, True, False, True])
    batch = make_batch(a, b, validities=[None, bv])
    specs = [SortKeySpec.spark_default(0, True),
             SortKeySpec.spark_default(1, False)]  # b DESC -> nulls last
    out = sort.sort_batch(batch, specs, [dt.INT64, dt.FLOAT64])
    n = out.realized_num_rows()
    av, _ = out.columns[0].to_numpy(n)
    bvals, bval_v = out.columns[1].to_numpy(n)
    np.testing.assert_array_equal(av, [1, 1, 2, 3, 3])
    # a=1: b desc -> 2.0 then NULL(last); a=2: NaN; a=3: 1.0 then -0.0
    assert bvals[0] == 2.0
    assert bval_v is not None and not bval_v[1]
    assert np.isnan(bvals[2])
    assert bvals[3] == 1.0


def test_sort_nan_sorts_greatest_asc():
    x = np.array([np.nan, 1.0, -np.inf, np.inf, -1.0])
    batch = make_batch(x)
    out = sort.sort_batch(batch, [SortKeySpec.spark_default(0, True)],
                          [dt.FLOAT64])
    vals, _ = out.columns[0].to_numpy(5)
    assert vals[0] == -np.inf and vals[3] == np.inf and np.isnan(vals[4])


def test_sort_strings():
    s = ["pear", "apple", None, "fig"]
    batch = make_batch(s)
    out = sort.sort_batch(batch, [SortKeySpec.spark_default(0, True)],
                          [dt.STRING])
    vals, _ = out.columns[0].to_numpy(4)
    assert list(vals) == [None, "apple", "fig", "pear"]  # ASC nulls first


# ---------------------------------------------------------------- groupby

def test_groupby_sum_count_min_max():
    keys = np.array([2, 1, 2, 1, 3, 2], dtype=np.int64)
    vals = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    vv = np.array([True, True, False, True, True, True])
    batch = make_batch(keys, vals, validities=[None, vv])
    out, out_types = groupby.groupby_aggregate(
        batch, [0],
        [AggSpec("sum", 1), AggSpec("count", 1), AggSpec("min", 1),
         AggSpec("max", 1), AggSpec("count_star")],
        [dt.INT64, dt.FLOAT64])
    n = out.realized_num_rows()
    assert n == 3
    df = out.to_pandas()
    df.columns = ["k", "sum", "cnt", "mn", "mx", "cs"]
    df = df.sort_values("k").reset_index(drop=True)
    np.testing.assert_array_equal(df["k"], [1, 2, 3])
    np.testing.assert_array_equal(df["sum"], [6.0, 7.0, 5.0])
    np.testing.assert_array_equal(df["cnt"], [2, 2, 1])
    np.testing.assert_array_equal(df["mn"], [2.0, 1.0, 5.0])
    np.testing.assert_array_equal(df["mx"], [4.0, 6.0, 5.0])
    np.testing.assert_array_equal(df["cs"], [2, 3, 1])


def test_groupby_null_keys_group_together():
    keys = np.array([1, 0, 1, 0], dtype=np.int64)
    kv = np.array([True, False, True, False])
    vals = np.array([1, 2, 3, 4], dtype=np.int64)
    batch = make_batch(keys, vals, validities=[kv, None])
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.INT64])
    assert out.realized_num_rows() == 2
    kvals, kvalid = out.columns[0].to_numpy(2)
    sums, _ = out.columns[1].to_numpy(2)
    # nulls-first grouping: first group is the null key
    assert kvalid is not None and not kvalid[0]
    assert sums[0] == 6 and sums[1] == 4


def test_groupby_all_null_sum_is_null():
    keys = np.array([1, 1], dtype=np.int64)
    vals = np.array([0.0, 0.0])
    vv = np.array([False, False])
    batch = make_batch(keys, vals, validities=[None, vv])
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.FLOAT64])
    _, sv = out.columns[1].to_numpy(1)
    assert sv is not None and not sv[0]


def test_groupby_string_keys():
    s = ["b", "a", "b", None, "a", None]
    vals = np.arange(6, dtype=np.int64)
    batch = make_batch(s, vals)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.STRING, dt.INT64])
    assert out.realized_num_rows() == 3
    kvals, _ = out.columns[0].to_numpy(3)
    sums, _ = out.columns[1].to_numpy(3)
    m = dict(zip(kvals, sums))
    assert m["a"] == 5 and m["b"] == 2 and m[None] == 8


def test_reduce_grand_aggregate():
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    batch = make_batch(vals)
    out, _ = groupby.reduce_aggregate(
        batch, [AggSpec("sum", 0), AggSpec("count_star"),
                AggSpec("min", 0)], [dt.FLOAT64])
    assert out.realized_num_rows() == 1
    assert out.columns[0].to_numpy(1)[0][0] == 10.0
    assert out.columns[1].to_numpy(1)[0][0] == 4
    assert out.columns[2].to_numpy(1)[0][0] == 1.0


def test_groupby_nan_and_negzero_group():
    keys = np.array([np.nan, np.nan, -0.0, 0.0])
    vals = np.ones(4, dtype=np.int64)
    batch = make_batch(keys, vals)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("count", 1)],
                                       [dt.FLOAT64, dt.INT64])
    assert out.realized_num_rows() == 2  # NaN==NaN, -0.0==0.0


# ---------------------------------------------------------------- hashing

def test_hash_deterministic_across_batches():
    a1 = make_batch(np.array([1, 2, 3], dtype=np.int64))
    a2 = make_batch(np.array([3, 2, 1], dtype=np.int64))
    h1 = np.asarray(hashing.hash_columns(a1, [0], [dt.INT64]))[:3]
    h2 = np.asarray(hashing.hash_columns(a2, [0], [dt.INT64]))[:3]
    np.testing.assert_array_equal(h1, h2[::-1])


def test_hash_strings_dictionary_independent():
    s1 = make_batch(["apple", "kiwi"])
    s2 = make_batch(["kiwi", "zebra", "apple"])
    h1 = np.asarray(hashing.hash_columns(s1, [0], [dt.STRING]))
    h2 = np.asarray(hashing.hash_columns(s2, [0], [dt.STRING]))
    assert h1[1] == h2[0]  # kiwi hashes equal despite different dicts
    assert h1[0] == h2[2]


# ---------------------------------------------------------------- partition

def test_hash_partition_routes_consistently():
    k = np.array([5, 6, 5, 7, 6, 5], dtype=np.int64)
    b = make_batch(k)
    out, counts = partition.hash_partition(b, [0], [dt.INT64], 4)
    assert counts.sum() == 6
    parts = partition.slice_partitions(out, counts)
    seen = {}
    for p, pb in enumerate(parts):
        if pb is None:
            continue
        vals, _ = pb.columns[0].to_numpy(pb.realized_num_rows())
        for v in vals:
            assert seen.setdefault(v, p) == p  # same key -> same partition
    assert sum(counts) == 6


def test_round_robin_partition():
    b = make_batch(np.arange(10, dtype=np.int64))
    out, counts = partition.round_robin_partition(b, 3)
    assert counts.sum() == 10
    assert sorted(counts.tolist(), reverse=True)[0] == 4


def _slice_case_batch(kinds, capacity, n, seed=0):
    """A batch of ``n`` live rows at ``capacity`` whose padding rows are
    NOT zero (a slice must carry them as they are), and its host copy."""
    rng = np.random.default_rng(seed)
    cols, host = [], []
    for kind in kinds:
        validity = None
        if kind.endswith("?"):
            validity = rng.random(capacity) < 0.7
        if kind.startswith("str"):
            data = rng.integers(0, 3, capacity).astype(np.int32)
            col = StringColumn(
                jnp.asarray(data), np.array(["a", "b", "c"], dtype=object),
                None if validity is None else jnp.asarray(validity))
        else:
            t = {"i64": dt.INT64, "f64": dt.FLOAT64, "i32": dt.INT32,
                 "bool": dt.BOOLEAN}[kind.rstrip("?")]
            data = (rng.random(capacity) * 1000 + 1).astype(t.np_dtype)
            col = Column(t, jnp.asarray(data),
                         None if validity is None else jnp.asarray(validity))
        cols.append(col)
        host.append((data, validity))
    return ColumnarBatch(cols, n), host


_MIXED = ("i64?", "f64", "str?", "i32", "bool?")

_SLICE_CASES = {
    # name: (column kinds, input capacity, counts)
    "empty_partition": (_MIXED, 512, [3, 0, 5]),
    "single_at_start_0": (_MIXED, 512, [7]),
    "all_empty": (_MIXED, 512, [0, 0]),
    "start_plus_cap_past_capacity": (_MIXED, 512, [450, 50]),
    "larger_than_128_rows": (_MIXED, 512, [200, 300]),
    "whole_batch": (_MIXED, 512, [512]),
    "thirteen_mixed_capacities": (
        _MIXED, 2048,
        [100, 0, 130, 5, 128, 129, 257, 1, 90, 300, 0, 64, 700]),
    "string_column": (("str", "str?"), 512, [40, 2, 300]),
    "no_validity": (("i64", "f64", "i32"), 512, [40, 2, 300]),
    "all_validity": (("i64?", "f64?", "i32?"), 512, [40, 2, 300]),
}


@pytest.mark.parametrize("case", sorted(_SLICE_CASES))
def test_slice_partitions_against_numpy(case):
    """``slice_partitions`` against "zero-pad, then take [start, start +
    cap)": data and validity bit for bit (padding rows too), num_rows,
    capacity, dictionary; an empty partition stays None."""
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    kinds, capacity, counts = _SLICE_CASES[case]
    batch, host = _slice_case_batch(kinds, capacity, sum(counts))
    parts = partition.slice_partitions(batch, np.asarray(counts))
    assert len(parts) == len(counts)
    start = 0
    for n, part in zip(counts, parts):
        if n == 0:
            assert part is None
            continue
        cap = bucket_capacity(n)
        assert part.num_rows == n and part.capacity == cap
        for col, src, (data, validity) in zip(part.columns, batch.columns,
                                              host):
            assert type(col) is type(src) and col.dtype is src.dtype
            want = np.concatenate([data, np.zeros(cap, data.dtype)])
            got = np.asarray(col.data)
            assert got.dtype == data.dtype
            np.testing.assert_array_equal(got, want[start:start + cap])
            if validity is None:
                assert col.validity is None
            else:
                want_v = np.concatenate([validity, np.zeros(cap, bool)])
                np.testing.assert_array_equal(
                    np.asarray(col.validity), want_v[start:start + cap])
            if isinstance(src, StringColumn):
                assert col.dictionary is src.dictionary
        start += n
    # ColumnarBatch.slice is the one-range case of the same program
    if len(counts) > 1 and counts[1]:
        one = batch.slice(counts[0], counts[1])
        assert one.num_rows == counts[1]
        for a, b in zip(one.columns, parts[1].columns):
            np.testing.assert_array_equal(np.asarray(a.data),
                                          np.asarray(b.data))


def test_slice_programs_follow_shapes_not_counts():
    """50 random count vectors over 16 partitions at one input capacity
    compile no more programs than output rungs x chunk sizes: the program
    is keyed on (schema, capacity, output capacity, chunk), never on the
    counts."""
    from spark_rapids_tpu.columnar.batch import _slice_rows
    from spark_rapids_tpu.ops.buckets import ladder_rungs

    capacity = 2048
    batch, _ = _slice_case_batch(("i64?", "f64"), capacity, capacity)
    rng = np.random.default_rng(7)
    before = _slice_rows._cache_size()
    for _ in range(50):
        # skewed: a few large partitions, many small, some empty
        w = rng.random(16) ** 4
        counts = np.floor(w / w.sum() * rng.integers(1, capacity + 1))
        parts = partition.slice_partitions(batch, counts.astype(np.int64))
        assert [0 if p is None else p.num_rows for p in parts] == \
            counts.tolist()
    rungs = len(ladder_rungs(capacity))
    chunk_sizes = int(np.log2(16)) + 1
    assert _slice_rows._cache_size() - before <= rungs * chunk_sizes


# ---------------------------------------------------------------- concat

def test_concat_batches():
    b1 = make_batch(np.arange(5, dtype=np.int64))
    b2 = make_batch(np.arange(5, 8, dtype=np.int64))
    out = concat.concat_batches([b1, b2])
    assert out.realized_num_rows() == 8
    vals, _ = out.columns[0].to_numpy(8)
    np.testing.assert_array_equal(vals, np.arange(8))


def test_concat_strings_and_nulls():
    b1 = make_batch(["a", "c"], np.array([1.0, 2.0]))
    b2 = make_batch(["b", None], np.array([3.0, np.nan]),
                    validities=[None, np.array([True, False])])
    out = concat.concat_batches([b1, b2])
    svals, _ = out.columns[0].to_numpy(4)
    dvals, dv = out.columns[1].to_numpy(4)
    assert list(svals) == ["a", "c", "b", None]
    assert dv is not None and list(dv) == [True, True, True, False]


# ---------------------------------------------------------------- join

def _join_oracle(left, right, how):
    l = pd.DataFrame({"k": left[0], "lv": left[1]})
    r = pd.DataFrame({"k": right[0], "rv": right[1]})
    return l.merge(r, on="k", how=how)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_equi_join_vs_pandas(how):
    lk = np.array([1, 2, 3, 4, 2], dtype=np.int64)
    lv = np.arange(5, dtype=np.int64)
    rk = np.array([2, 2, 4, 5], dtype=np.int64)
    rv = np.arange(10, 14, dtype=np.int64)
    lb = make_batch(lk, lv)
    rb = make_batch(rk, rv)
    out, types = join.equi_join(lb, rb, [0], [0],
                                [dt.INT64, dt.INT64], [dt.INT64, dt.INT64],
                                how)
    n = out.realized_num_rows()
    got = out.to_pandas()
    got.columns = ["k", "lv", "k2", "rv"]
    got = got[["k", "lv", "rv"]].sort_values(["k", "lv", "rv"],
                                             na_position="last"
                                             ).reset_index(drop=True)
    exp = _join_oracle((lk, lv), (rk, rv), how)[["k", "lv", "rv"]] \
        .sort_values(["k", "lv", "rv"], na_position="last") \
        .reset_index(drop=True)
    assert len(got) == len(exp)
    np.testing.assert_array_equal(got["k"].to_numpy(np.int64),
                                  exp["k"].to_numpy(np.int64))
    np.testing.assert_array_equal(
        got["rv"].astype("float64").fillna(-1).to_numpy(),
        exp["rv"].astype("float64").fillna(-1).to_numpy())


def test_semi_anti_join():
    lk = np.array([1, 2, 3, 4], dtype=np.int64)
    lv = np.arange(4, dtype=np.int64)
    rk = np.array([2, 4, 4], dtype=np.int64)
    lb = make_batch(lk, lv)
    rb = make_batch(rk, np.zeros(3, dtype=np.int64))
    semi, _ = join.equi_join(lb, rb, [0], [0],
                             [dt.INT64, dt.INT64], [dt.INT64, dt.INT64],
                             "leftsemi")
    vals, _ = semi.columns[0].to_numpy(semi.realized_num_rows())
    assert sorted(vals.tolist()) == [2, 4]
    anti, _ = join.equi_join(lb, rb, [0], [0],
                             [dt.INT64, dt.INT64], [dt.INT64, dt.INT64],
                             "leftanti")
    vals, _ = anti.columns[0].to_numpy(anti.realized_num_rows())
    assert sorted(vals.tolist()) == [1, 3]


def test_join_null_keys_never_match():
    lk = np.array([1, 0], dtype=np.int64)
    lkv = np.array([True, False])
    rk = np.array([1, 0], dtype=np.int64)
    rkv = np.array([True, False])
    lb = make_batch(lk, np.arange(2, dtype=np.int64), validities=[lkv, None])
    rb = make_batch(rk, np.arange(2, dtype=np.int64), validities=[rkv, None])
    out, _ = join.equi_join(lb, rb, [0], [0],
                            [dt.INT64, dt.INT64], [dt.INT64, dt.INT64],
                            "inner")
    assert out.realized_num_rows() == 1


def test_full_outer_join():
    lk = np.array([1, 2], dtype=np.int64)
    rk = np.array([2, 3], dtype=np.int64)
    lb = make_batch(lk, np.array([10, 20], dtype=np.int64))
    rb = make_batch(rk, np.array([200, 300], dtype=np.int64))
    out, _ = join.equi_join(lb, rb, [0], [0],
                            [dt.INT64, dt.INT64], [dt.INT64, dt.INT64],
                            "full")
    assert out.realized_num_rows() == 3


def test_string_key_join_across_dictionaries():
    lb = make_batch(["apple", "fig"], np.array([1, 2], dtype=np.int64))
    rb = make_batch(["fig", "zebra"], np.array([30, 40], dtype=np.int64))
    out, _ = join.equi_join(lb, rb, [0], [0],
                            [dt.STRING, dt.INT64], [dt.STRING, dt.INT64],
                            "inner")
    assert out.realized_num_rows() == 1
    svals, _ = out.columns[0].to_numpy(1)
    assert svals[0] == "fig"


def test_reduce_first_last_empty_batch_is_null():
    # first/last over zero rows must be NULL, not padding garbage
    batch = make_batch(np.array([], dtype=np.float64))
    out, _ = groupby.reduce_aggregate(
        batch, [AggSpec("first", 0), AggSpec("last", 0),
                AggSpec("count", 0)], [dt.FLOAT64])
    assert out.realized_num_rows() == 1
    fv, fm = out.columns[0].to_numpy(1)
    lv, lm = out.columns[1].to_numpy(1)
    assert fm is not None and not fm[0]
    assert lm is not None and not lm[0]
    assert out.columns[2].to_numpy(1)[0][0] == 0


def test_groupby_live_mask_fused_filter():
    """live_mask fuses a filter into the groupby sort; results must equal
    filter-then-groupby. Regression: kept rows located beyond the
    post-filter count must not be treated as padding."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import groupby as gb

    rng = np.random.default_rng(0)
    n = 4096
    keys = rng.integers(0, 37, n).astype(np.int64)
    vals = rng.random(n)
    # keep mask biased so many kept rows sit in the BACK half
    keep = (np.arange(n) > n // 2) | (rng.random(n) < 0.1)
    cols = [(jnp.asarray(keys), None), (jnp.asarray(vals), None)]
    (kd, kv), (ad, av), ng = gb._groupby(
        cols, (dt.INT64, dt.FLOAT64), (0,),
        (gb.AggSpec("sum", 1), gb.AggSpec("count_star")),
        jnp.int32(n), live_mask=jnp.asarray(keep))
    ng = int(ng)
    got_keys = np.asarray(kd[0])[:ng]
    got_sums = np.asarray(ad[0])[:ng]
    got_cnts = np.asarray(ad[1])[:ng]
    import pandas as pd

    expect = (pd.DataFrame({"k": keys[keep], "v": vals[keep]})
              .groupby("k").agg(s=("v", "sum"), c=("v", "size")))
    assert ng == len(expect)
    order = np.argsort(got_keys)
    np.testing.assert_array_equal(got_keys[order], expect.index.values)
    np.testing.assert_allclose(got_sums[order], expect["s"], rtol=1e-9)
    np.testing.assert_array_equal(got_cnts[order], expect["c"])


# -------------------------------------------- float-sum IEEE edge cases

def test_groupby_float_sum_running_total_overflow_confined():
    """All-finite inputs whose RUNNING total overflows must not poison
    later groups: the isfinite(grand total) predicate routes to the
    per-segment-scan tail (cumsum diffs would give inf-inf = NaN)."""
    keys = np.array([0, 0, 1], dtype=np.int64)
    vals = np.array([1.5e308, 1.5e308, 1.0])
    batch = make_batch(keys, vals)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.FLOAT64])
    sums, _ = out.columns[1].to_numpy(2)
    assert np.isinf(sums[0]) and sums[0] > 0
    assert sums[1] == 1.0


def test_groupby_sum_of_squares_square_overflow():
    """A finite input whose SQUARE overflows must produce +inf, not be
    silently dropped (the predicate must test the squared lane)."""
    keys = np.array([0, 0, 0, 0], dtype=np.int64)
    vals = np.array([1e200, 1.0, 2.0, 3.0])
    batch = make_batch(keys, vals)
    out, _ = groupby.groupby_aggregate(
        batch, [0], [AggSpec("sum_of_squares", 1)],
        [dt.INT64, dt.FLOAT64])
    sums, _ = out.columns[1].to_numpy(1)
    assert np.isinf(sums[0]) and sums[0] > 0


def test_groupby_float_sum_no_cross_group_cancellation():
    """A huge group preceding a tiny one must not destroy the tiny
    group's sum: global cumsum diffs carry error scaling with the
    running prefix of OTHER groups (r2 advisor repro: group-1 sum came
    back 0.0 instead of 2.0). The per-segment scan confines error."""
    keys = np.array([0, 0, 1, 1], dtype=np.int64)
    vals = np.array([1e16, 1e16, 1.0, 1.0])
    batch = make_batch(keys, vals)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.FLOAT64])
    sums, _ = out.columns[1].to_numpy(2)
    assert sums[0] == 2e16
    assert sums[1] == 2.0


def test_groupby_packed_key_large_magnitude_int64():
    """int64/TIMESTAMP keys with small span but magnitude above 2^31:
    the packed-lane decode must widen BEFORE adding the range base (r2
    advisor repro: OverflowError / wrapped keys)."""
    base = 5_000_000_000
    keys = np.array([base, base + 1, base, base + 1], dtype=np.int64)
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    batch = make_batch(keys, vals)
    kcol = batch.columns[0]
    kcol.stats = (base, base + 1)
    qlo, qhi = groupby.key_range_of(kcol, dt.INT64)
    assert qlo <= base and base + 1 <= qhi
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.FLOAT64])
    got_k, _ = out.columns[0].to_numpy(2)
    sums, _ = out.columns[1].to_numpy(2)
    order = np.argsort(got_k)
    np.testing.assert_array_equal(got_k[order], [base, base + 1])
    np.testing.assert_allclose(sums[order], [4.0, 6.0])


def test_groupby_packed_key_large_magnitude_with_nulls():
    """Same large-magnitude decode, via the has-validity branch."""
    base = -5_000_000_000
    keys = np.array([base, base + 2, base, 0], dtype=np.int64)
    valid = np.array([True, True, True, False])
    vals = np.array([1.0, 2.0, 3.0, 4.0])
    batch = make_batch(keys, vals, validities=[valid, None])
    batch.columns[0].stats = (base, base + 2)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.INT64, dt.FLOAT64])
    got_k, got_kv = out.columns[0].to_numpy(3)
    sums, _ = out.columns[1].to_numpy(3)
    rows = sorted(zip(got_kv, got_k, sums))
    # null group first in Spark ASC ordering of our kernel (rank 0)
    assert rows[0][0] == np.False_ and rows[0][2] == 4.0
    assert (rows[1][1], rows[1][2]) == (base, 4.0)
    assert (rows[2][1], rows[2][2]) == (base + 2, 2.0)


def test_groupby_stats_survive_projection_and_pack():
    """Upload-time int stats flow through a passthrough projection into
    the groupby (packed-key path) without changing results."""
    from spark_rapids_tpu.ops.groupby import key_range_of

    from spark_rapids_tpu.api import Session, col, functions as F
    import pandas as pd

    pdf = pd.DataFrame({"k": np.array([5, 7, 5, 9], dtype=np.int64),
                        "v": [1.0, 2.0, 3.0, 4.0]})
    s = Session()
    df = s.create_dataframe(pdf)
    got = df.group_by("k").agg(F.sum(col("v")).alias("sv")).collect()
    got = got.sort_values("k").reset_index(drop=True)
    assert got["k"].tolist() == [5, 7, 9]
    assert got["sv"].tolist() == [4.0, 2.0, 4.0]

    # and the stats themselves exist at the scan boundary
    from spark_rapids_tpu.execs.interop import host_to_batch
    from spark_rapids_tpu.columnar.batch import Schema

    b = host_to_batch({"k": pdf["k"].to_numpy()}, {},
                      Schema(["k"], [dt.INT64]))
    assert b.columns[0].stats == (5, 9)
    # key ranges are quantized to pow2 spans on an aligned base
    qlo, qhi = key_range_of(b.columns[0], dt.INT64)
    assert qlo <= 5 and 9 <= qhi


def test_quantize_range():
    from spark_rapids_tpu.ops.groupby import quantize_range

    for lo, hi in [(0, 65535), (3, 17), (-7, 9), (100, 100),
                   (5_000_000_000, 5_000_000_001), (-20, -3)]:
        qlo, qhi = quantize_range(lo, hi)
        span = qhi - qlo + 1
        assert qlo <= lo and hi <= qhi
        assert span & (span - 1) == 0  # power-of-two span
        assert span <= 4 * max(hi - lo + 1, 1)
    # stability: nearby batches land on the SAME signature
    assert quantize_range(3, 17) == quantize_range(2, 16)
    assert quantize_range(0, 65535) == (0, 65535)


def test_derive_stats_through_projection():
    """Projected keys (k % 4, k + 10, year(d), cast) keep host-known
    ranges so the groupby still packs keys (r2 verdict weak #7)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.expressions import arithmetic as ar
    from spark_rapids_tpu.expressions import datetime as dte
    from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                                   Literal)
    from spark_rapids_tpu.expressions.cast import Cast
    from spark_rapids_tpu.expressions.compiler import derive_stats

    k = Column.from_numpy(np.arange(5, 95, dtype=np.int64))
    k.stats = (5, 94)
    d = Column.from_numpy(np.arange(11000, 12000, dtype=np.int32),
                          dtype=dt.DATE)
    d.stats = (11000, 11999)   # 2000-02-14 .. 2002-11-09
    cols = [k, d]
    ref = BoundReference(0, dt.INT64)
    assert derive_stats(ref, cols) == (5, 94)
    assert derive_stats(Alias(ref, "x"), cols) == (5, 94)
    assert derive_stats(ar.Pmod(ref, Literal(4, dt.INT64)), cols) == (0, 3)
    assert derive_stats(ar.Add(ref, Literal(10, dt.INT64)), cols) == \
        (15, 104)
    assert derive_stats(ar.Subtract(Literal(100, dt.INT64), ref),
                        cols) == (6, 95)
    assert derive_stats(ar.Multiply(ref, Literal(-2, dt.INT64)),
                        cols) == (-188, -10)
    assert derive_stats(Cast(ref, dt.INT32), cols) == (5, 94)
    y = derive_stats(dte.Year(BoundReference(1, dt.DATE)), cols)
    assert y == (2000, 2002)
    # non-derivable -> None
    assert derive_stats(ar.Add(ref, ref), cols) is None
    # date<->timestamp casts SCALE units — bounds must not pass through
    assert derive_stats(Cast(BoundReference(1, dt.DATE), dt.TIMESTAMP),
                        cols) is None
    # arithmetic whose bounds exceed the EXPRESSION dtype wraps on
    # device — no stats (r3 review finding)
    k32 = Column.from_numpy(np.arange(0, 60001, 30000, dtype=np.int32),
                            dtype=dt.INT32)
    k32.stats = (0, 60000)
    assert derive_stats(ar.Multiply(BoundReference(0, dt.INT32),
                                    Literal(100000, dt.INT32)),
                        [k32]) is None


def test_parquet_footer_stats_feed_packed_keys(tmp_path):
    """Parquet scans get Column.stats from footer statistics — no
    upload-time host pass — and the groupby packs keys off them."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.io import ParquetSource

    rng = np.random.default_rng(2)
    tdir = tmp_path / "t"
    tdir.mkdir()
    ks = rng.integers(10, 50, 500).astype(np.int64)
    pq.write_table(pa.table({"k": ks, "v": rng.random(500)}),
                   str(tdir / "a.parquet"))
    src = ParquetSource(str(tdir))
    st = src.split_stats(0)
    assert st is not None and st["k"] == (int(ks.min()), int(ks.max()))

    from spark_rapids_tpu.api import Session, col, functions as F

    s = Session()
    s.register_parquet("t", str(tdir))
    df = s.sql("SELECT k, SUM(v) AS sv FROM t GROUP BY k")
    exec_ = df._exec()
    # find the scan output column and check stats arrived
    scan = exec_
    while scan.children:
        scan = scan.children[0]
    b = next(scan.execute(0))
    assert b.columns[0].stats == (int(ks.min()), int(ks.max()))
    got = df.collect().sort_values("k").reset_index(drop=True)
    import pandas as pd

    want = (pd.DataFrame({"k": ks, "v": rng.random(500) * 0 + 1})
            .groupby("k").size())
    assert got["k"].tolist() == sorted(set(ks.tolist()))


def test_groupby_wide_agg_list_matches_oracle():
    """Eight aggregate columns at capacity 32,768 on the sort path (the
    shape an older libtpu could not compile whole, which a chunk loop
    worked around until PR 29): one program, identical to the
    oracle."""
    import jax
    import pandas as pd

    from spark_rapids_tpu.ops import groupby as gb

    rng = np.random.default_rng(13)
    cap, n, nagg = 1 << 15, 30_000, 8
    keys = rng.integers(0, 700, cap).astype(np.int64)
    live = np.arange(cap) < n
    cols = [Column(dt.INT64, jnp.asarray(keys), jnp.asarray(live))]
    vals = []
    for i in range(nagg):
        v = rng.integers(-50, 100, cap).astype(np.int64)
        vals.append(v)
        cols.append(Column(dt.INT64, jnp.asarray(v), None))
    b = ColumnarBatch(cols, n)
    aggs = [gb.AggSpec("sum", i + 1) for i in range(nagg)]
    out, _types = gb.groupby_aggregate(b, [0], aggs,
                                       [dt.INT64] * (nagg + 1))
    ng = out.realized_num_rows()
    pdf = pd.DataFrame({"k": keys[:n],
                        **{f"a{i}": vals[i][:n] for i in range(nagg)}})
    want = pdf.groupby("k").sum().sort_index()
    assert ng == len(want)
    k = np.asarray(jax.device_get(out.columns[0].data))[:ng]
    order = np.argsort(k)
    for i in range(nagg):
        got = np.asarray(jax.device_get(out.columns[1 + i].data))[:ng]
        np.testing.assert_array_equal(got[order],
                                      want[f"a{i}"].to_numpy())


# -------------------------------------------------- dense (sort-free) path

def _run_groupby_path(cols, dtypes, key_ords, aggs, n, key_ranges,
                      live_mask=None):
    from spark_rapids_tpu.ops import groupby as gb

    (kd, kv), (ad, av), ng = gb._groupby(
        cols, tuple(dtypes), tuple(key_ords), tuple(aggs), jnp.int32(n),
        live_mask=live_mask, key_ranges=key_ranges)
    ng = int(ng)
    out = {}
    for i in range(len(key_ords)):
        d = np.asarray(kd[i])[:ng].astype(object)
        if kv[i] is not None:
            d[~np.asarray(kv[i])[:ng]] = None
        out[f"k{i}"] = d
    for i in range(len(aggs)):
        d = np.asarray(ad[i])[:ng].astype(object)
        if av[i] is not None:
            d[~np.asarray(av[i])[:ng]] = None
        out[f"a{i}"] = d
    return pd.DataFrame(out), ng


def test_groupby_dense_matches_sort_path_all_ops():
    """The sort-free dense path (host-known key space <= 128 slots) must
    agree with the sort path op-for-op, including null keys, null
    inputs, bool keys, and a fused live-mask. Differential: same inputs
    through both kernels (key_ranges present vs absent), results
    compared after a key sort."""
    from spark_rapids_tpu.ops import groupby as gb

    rng = np.random.default_rng(17)
    cap, n = 2048, 1900
    k1 = rng.integers(10, 15, cap).astype(np.int64)
    k1v = rng.random(cap) > 0.15
    k2 = rng.integers(0, 2, cap).astype(bool)
    x = rng.normal(3.0, 50.0, cap)
    xv = rng.random(cap) > 0.25
    iy = rng.integers(-40, 90, cap).astype(np.int64)
    bz = rng.integers(0, 2, cap).astype(bool)
    bzv = rng.random(cap) > 0.5
    cols = [(jnp.asarray(k1), jnp.asarray(k1v)),
            (jnp.asarray(k2), None),
            (jnp.asarray(x), jnp.asarray(xv)),
            (jnp.asarray(iy), None),
            (jnp.asarray(bz), jnp.asarray(bzv))]
    dtypes = [dt.INT64, dt.BOOLEAN, dt.FLOAT64, dt.INT64, dt.BOOLEAN]
    aggs = [gb.AggSpec("sum", 2), gb.AggSpec("sum", 3),
            gb.AggSpec("sum_of_squares", 2), gb.AggSpec("count", 2),
            gb.AggSpec("count_star"), gb.AggSpec("min", 2),
            gb.AggSpec("max", 3), gb.AggSpec("min", 4),
            gb.AggSpec("max", 4), gb.AggSpec("first", 2),
            gb.AggSpec("last", 3), gb.AggSpec("any_valid", 2),
            gb.AggSpec("m2", 2), gb.AggSpec("rterm", 2)]
    ranges = (gb.quantize_range(10, 14), (0, 1))
    assert gb._dense_layout(dtypes, (0, 1), ranges,
                            (True, False)) is not None
    live = jnp.asarray(rng.random(cap) > 0.2)
    for mask in (None, live):
        dense, ng_d = _run_groupby_path(cols, dtypes, (0, 1), aggs, n,
                                        ranges, live_mask=mask)
        sortp, ng_s = _run_groupby_path(cols, dtypes, (0, 1), aggs, n,
                                        None, live_mask=mask)
        assert ng_d == ng_s and ng_d > 0
        key = ["k0", "k1"]
        dense = dense.sort_values(key, na_position="first",
                                  ignore_index=True)
        sortp = sortp.sort_values(key, na_position="first",
                                  ignore_index=True)
        for c in dense.columns:
            a, b = dense[c].to_numpy(), sortp[c].to_numpy()
            an = np.array([v is None for v in a])
            bn = np.array([v is None for v in b])
            np.testing.assert_array_equal(an, bn, err_msg=c)
            af = np.array([0.0 if v is None else float(v) for v in a])
            bf = np.array([0.0 if v is None else float(v) for v in b])
            np.testing.assert_allclose(af, bf, rtol=1e-9, err_msg=c)


def test_groupby_dense_wide_agg_list_matches_pandas():
    """A wide agg list over a dense-eligible key space must match
    pandas."""
    from spark_rapids_tpu.ops import groupby as gb

    rng = np.random.default_rng(23)
    cap, n, nagg = 1 << 15, 30_000, 9
    keys = rng.integers(0, 5, cap).astype(np.int64)
    cols = [Column(dt.INT64, jnp.asarray(keys), None,
                   stats=(0, 4))]
    vals = []
    for i in range(nagg):
        v = rng.normal(0, 10, cap)
        vals.append(v)
        cols.append(Column(dt.FLOAT64, jnp.asarray(v), None))
    b = ColumnarBatch(cols, n)
    aggs = [gb.AggSpec("sum", i + 1) for i in range(nagg)]
    out, _types = gb.groupby_aggregate(b, [0], aggs,
                                       [dt.INT64] + [dt.FLOAT64] * nagg)
    ng = out.realized_num_rows()
    pdf = pd.DataFrame({"k": keys[:n],
                        **{f"a{i}": vals[i][:n] for i in range(nagg)}})
    want = pdf.groupby("k").sum().sort_index()
    assert ng == len(want)
    import jax

    k = np.asarray(jax.device_get(out.columns[0].data))[:ng]
    order = np.argsort(k)
    for i in range(nagg):
        got = np.asarray(jax.device_get(out.columns[1 + i].data))[:ng]
        np.testing.assert_allclose(got[order], want[f"a{i}"].to_numpy(),
                                   rtol=1e-9)


def test_groupby_dense_string_keys_and_empty():
    """String keys ride the dense path through their dictionary range;
    an all-dead batch yields zero groups."""
    s = ["b", "a", "b", None, "c", "a"]
    v = np.array([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    batch = make_batch(np.asarray(s, dtype=object), v)
    out, _ = groupby.groupby_aggregate(batch, [0], [AggSpec("sum", 1)],
                                       [dt.STRING, dt.FLOAT64])
    df = out.to_pandas()
    df.columns = ["k", "s"]
    df = df.sort_values("k", na_position="first").reset_index(drop=True)
    assert df["s"].tolist() == [4.0, 8.0, 4.0, 5.0]
    assert df["k"].tolist()[1:] == ["a", "b", "c"]
    empty = make_batch(np.asarray(["x", "y"], dtype=object),
                       np.array([1.0, 2.0]), n=0)
    out2, _ = groupby.groupby_aggregate(empty, [0], [AggSpec("sum", 1)],
                                        [dt.STRING, dt.FLOAT64])
    assert out2.realized_num_rows() == 0
