"""The hash-join probe against plain oracles (ops/join).

The probe is two ``searchsorted`` calls over the hash-sorted build
(``_hash_probe``) behind ``equi_join``. Until PR 29 these cases held a
Pallas bucket-table kernel bit-equal to that path; the kernel is gone
and the same data now holds the path that remains to oracles that share
no code with it: numpy's ``searchsorted`` for the probe's (lo, hi,
counts, total) contract, a nested loop over rows for every join type —
across single, composite and string keys, nulls on the key, an empty
build side and the build-once/probe-many prepared path.
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


def test_hash_probe_matches_numpy_searchsorted():
    """``_hash_probe``'s (lo, hi, counts, total) contract is
    ``searchsorted`` left/right over the hash-sorted build side, with
    the probe's padding rows counting nothing."""
    r = np.random.default_rng(7)
    maxh = np.iinfo(np.int64).max
    h_b = r.integers(-2**62, 2**62, size=64)
    h_b[48:] = maxh                     # tail is padding
    sh = np.sort(h_b)
    h_p = np.concatenate([r.choice(sh[:48], 20),
                          r.integers(-2**62, 2**62, size=12)])
    s_rows = 27                         # 5 probe rows are padding
    lo, hi, counts, total = J._probe_sorted(
        jnp.asarray(sh), jnp.asarray(h_p), jnp.asarray(s_rows, jnp.int32))
    want_lo = np.searchsorted(sh, h_p, side="left")
    want_hi = np.searchsorted(sh, h_p, side="right")
    want_counts = np.where(np.arange(32) < s_rows, want_hi - want_lo, 0)
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    np.testing.assert_array_equal(np.asarray(hi), want_hi)
    np.testing.assert_array_equal(np.asarray(counts), want_counts)
    assert int(total) == want_counts.sum() >= 20
