"""The hash-join probe against plain oracles (ops/join).

The probe's contract is two ``searchsorted`` calls over the hash-sorted
build (``_hash_probe``) behind ``equi_join``; since PR 32 it is computed
by halving inside one bucket of a directory over the build's distinct
hashes (``HashIndex``). Until PR 29 these cases held a Pallas
bucket-table kernel bit-equal to that path; the kernel is gone and the
same data now holds the path that remains to oracles that share no code
with it: numpy's ``searchsorted`` for the probe's (lo, hi, counts,
total) contract, a nested loop over rows for every join type — across
single, composite and string keys, nulls on the key, an empty build side
and the build-once/probe-many prepared path.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import join as J


def _join_batch(n, cap, keyspace, seed, with_str=False):
    r = np.random.default_rng(seed)
    k1 = r.integers(0, keyspace, size=cap).astype(np.int64)
    k2 = r.integers(0, 3, size=cap).astype(np.int32)
    val = r.integers(0, 1000, size=cap).astype(np.int64)
    v1 = r.random(cap) > 0.15          # nulls on the first key column
    cols = [Column(dt.INT64, jnp.asarray(k1), jnp.asarray(v1)),
            Column(dt.INT32, jnp.asarray(k2), None),
            Column(dt.INT64, jnp.asarray(val), None)]
    types = [dt.INT64, dt.INT32, dt.INT64]
    if with_str:
        dic = np.array(["a", "bb", "ccc", "dddd"], dtype=object)
        codes = jnp.asarray(r.integers(0, 4, size=cap).astype(np.int32))
        cols.append(StringColumn(codes, dic, None))
        types.append(dt.STRING)
    return ColumnarBatch(cols, n), types


def _rows_of(batch):
    """Live rows as tuples, None for a null."""
    n = int(jax.device_get(batch.num_rows_device()))
    cols = []
    for c in batch.columns:
        d = np.asarray(jax.device_get(c.data))[:n]
        valid = np.ones(n, bool) if c.validity is None else \
            np.asarray(jax.device_get(c.validity))[:n]
        if isinstance(c, StringColumn):
            cols.append([str(c.dictionary[int(x)]) if v else None
                         for x, v in zip(d, valid)])
        else:
            cols.append([x.item() if v else None
                         for x, v in zip(d, valid)])
    return [tuple(col[i] for col in cols) for i in range(n)]


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((x is None, x) for x in r))


def _oracle_join(s_rows, b_rows, sk, bk, join_type, b_width):
    """Nested loop; a NULL key matches nothing (SQL equi-join)."""
    def key(row, ords):
        k = tuple(row[o] for o in ords)
        return None if any(x is None for x in k) else k

    out, b_hit = [], [False] * len(b_rows)
    for s in s_rows:
        ks = key(s, sk)
        hits = [i for i, b in enumerate(b_rows)
                if ks is not None and key(b, bk) == ks]
        for i in hits:
            b_hit[i] = True
        if join_type == "leftsemi":
            out.extend([s] if hits else [])
        elif join_type == "leftanti":
            out.extend([] if hits else [s])
        else:
            out.extend(s + b_rows[i] for i in hits)
            if not hits and join_type in ("left", "full"):
                out.append(s + (None,) * b_width)
    if join_type == "full":
        s_width = len(s_rows[0])
        out.extend((None,) * s_width + b
                   for b, hit in zip(b_rows, b_hit) if not hit)
    return out


def _run_join(join_type, sk, bk, with_str=False, prepared=False):
    s, st = _join_batch(90, 128, 40, seed=1, with_str=with_str)
    b, bt = _join_batch(50, 64, 40, seed=2, with_str=with_str)
    prep = None
    if prepared:
        prep = J.prepare_build(b, bk, bt, [st[o] for o in sk])
        assert prep is not None
    out, _ = J.equi_join(s, b, sk, bk, st, bt, join_type=join_type,
                         prepared=prep)
    want = _oracle_join(_rows_of(s), _rows_of(b), sk, bk, join_type,
                        len(bt))
    return _sorted(_rows_of(out)), _sorted(want)


@pytest.mark.parametrize("join_type",
                         ["inner", "left", "leftsemi", "leftanti",
                          "full"])
def test_join_probe_differential(join_type):
    """equi_join == nested loop, per join type, over single-column,
    composite and string keys (nulls on the probe/build key), plus the
    build-once/probe-many prepared path."""
    for sk, bk, ws in [([0], [0], False),        # single int64 key
                       ([0, 1], [0, 1], False),  # composite key
                       ([3], [3], True)]:        # string key
        got, want = _run_join(join_type, sk, bk, with_str=ws)
        assert got == want, (join_type, sk, ws)
    # prepared build reused across probes (non-string keys)
    got, want = _run_join(join_type, [0, 1], [0, 1], prepared=True)
    assert got == want, (join_type, "prepared")


def test_join_probe_empty_build():
    s, st = _join_batch(10, 16, 5, seed=3)
    b, bt = _join_batch(0, 8, 5, seed=4)
    out, _ = J.equi_join(s, b, [0], [0], st, bt, join_type="inner")
    assert int(jax.device_get(out.num_rows_device())) == 0
    out, _ = J.equi_join(s, b, [0], [0], st, bt, join_type="left")
    assert int(jax.device_get(out.num_rows_device())) == 10


_MAXH, _MINH = np.iinfo(np.int64).max, np.iinfo(np.int64).min


def _uniform(r, n):
    return r.integers(_MINH, _MAXH, size=n)


#: what a build side may hold, as (capacity, generator) -> its live hashes
_BUILDS = {
    "unique_uniform": lambda r, cap: _uniform(r, cap * 3 // 4),
    "37_distinct_repeated": lambda r, cap: r.choice(_uniform(r, 37),
                                                    cap * 3 // 4),
    "a_third_null": lambda r, cap: np.concatenate(
        [np.full(cap // 4, int(J._BUILD_NULL)), _uniform(r, cap // 2)]),
    "empty": lambda r, cap: _uniform(r, 0),
    "full_to_capacity": lambda r, cap: _uniform(r, cap),
}


def _build_args(h_b, b_cap):
    """A build side of one column, its row numbers, under the hashes
    ``h_b``: the arguments ``_build_sorted`` and ``_probe_counts`` share."""
    padded = np.zeros(b_cap, np.int64)
    padded[:len(h_b)] = h_b
    return ([jnp.arange(b_cap, dtype=jnp.int32)], [None],
            jnp.asarray(padded), jnp.asarray(len(h_b), jnp.int32))


def _build_and_probe(path, h_b, b_cap, h_p, s_rows):
    """(lo, hi, counts, total) and the sorted build's one column through
    the prepared pair of programs or the one unprepared program."""
    build = _build_args(h_b, b_cap)
    h_p_d, s_rows = jnp.asarray(h_p), jnp.asarray(s_rows, jnp.int32)
    if path == "_probe_sorted":
        index, (rows,), _ = J._build_sorted(*build)
        return J._probe_sorted(index, h_p_d, s_rows), rows
    (rows,), _, *out = J._probe_counts(*build, h_p_d, s_rows)
    return out, rows


@pytest.mark.parametrize("path", ["_probe_sorted", "_probe_counts"])
@pytest.mark.parametrize("b_cap", [128, 65536])
@pytest.mark.parametrize("build", list(_BUILDS))
def test_hash_probe_matches_numpy_searchsorted(build, b_cap, path):
    """``_hash_probe``'s (lo, hi, counts, total) contract is
    ``searchsorted`` left/right over the hash-sorted build side, with
    the probe's padding rows counting nothing: exact, whatever the build
    holds (repeats, a NULL-key run, nothing, no padding), for probes that
    hit, that miss, and that hold int64's ends and the probe's NULL."""
    r = np.random.default_rng([7, b_cap, list(_BUILDS).index(build)])
    h_b = _BUILDS[build](r, b_cap)
    s_cap = 2 * b_cap
    hits = r.choice(h_b, s_cap // 2) if len(h_b) else _uniform(r, s_cap // 2)
    h_p = np.concatenate([hits, _uniform(r, s_cap // 2 - 3),
                          [_MAXH, _MINH, int(J._PROBE_NULL)]])
    h_p = h_p[r.permutation(s_cap)]
    s_rows = s_cap - 5                  # 5 probe rows are padding
    (lo, hi, counts, total), rows = _build_and_probe(
        path, h_b, b_cap, h_p, s_rows)
    sh = np.sort(np.concatenate([h_b, np.full(b_cap - len(h_b), _MAXH)]),
                 kind="stable")
    want_lo = np.searchsorted(sh, h_p, side="left")
    want_hi = np.searchsorted(sh, h_p, side="right")
    want_counts = np.where(np.arange(s_cap) < s_rows, want_hi - want_lo, 0)
    assert lo.dtype == hi.dtype == jnp.int32 and counts.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    np.testing.assert_array_equal(np.asarray(hi), want_hi)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert int(total) == want_counts.sum()
    if len(h_b):
        assert int(total) >= s_cap // 2 - 5
    # the build's rows in hash order, ties in row order, padding last
    live = np.argsort(h_b, kind="stable")
    np.testing.assert_array_equal(np.asarray(rows)[:len(h_b)], live)


def test_probe_rounds_follow_distinct_hashes_not_rows():
    """50,000 distinct uniform hashes in a capacity of 65,536 settle in at
    most 5 halvings (a whole-build search makes 17, twice), and a hot key
    adds none: with half the build on ONE hash the fullest bucket holds no
    more distinct hashes than before."""
    r = np.random.default_rng(32)
    b_cap, n = 65536, 50000
    h_b = _uniform(r, n)

    def rounds(h):
        index, _, _ = J._build_sorted(*_build_args(h, b_cap))
        got, full = J.probe_rounds(J.PreparedBuild(None, index))
        assert full == 17
        return got

    spread = rounds(h_b)
    assert 1 <= spread <= 5
    hot = h_b.copy()
    hot[r.permutation(n)[:n // 2]] = h_b[0]
    assert rounds(hot) <= spread
