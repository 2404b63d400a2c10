"""Native Pallas kernel differential fences (native/kernels).

The kernel layer's correctness contract is BIT-EQUALITY with the jnp
implementations it replaces: for every routed op the gate-on and
gate-off paths must agree exactly — across composite keys, nulls,
empty partitions, string dictionaries, the streaming fold seam and the
8-shard SPMD mesh. CPU CI runs the kernels through the Pallas
interpreter (the registry pins ``interpret=True`` off-TPU), so these
fences exercise the same kernel bodies that compile for TPU.
"""
from __future__ import annotations

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.native import kernels as nk
from spark_rapids_tpu.ops import join as J

from tests.compare import assert_frames_equal


@pytest.fixture(autouse=True)
def _gates_reset():
    """Every test starts and ends at the shipped defaults (master off)."""
    nk.reset_config()
    yield
    nk.reset_config()


# ---------------------------------------------------------------------------
# gate defaults and knob routing
# ---------------------------------------------------------------------------


def test_gates_default_off_and_conf_routing():
    assert not nk.enabled("join")
    assert not nk.enabled("strings")
    from spark_rapids_tpu.config import RapidsConf

    conf = RapidsConf({"rapids.tpu.native.kernels.enabled": True,
                       "rapids.tpu.native.kernels.strings": False})
    nk.configure_from_conf(conf)
    assert nk.enabled("join")
    assert not nk.enabled("strings")   # sub-gate wins under the master
    tok_on = nk.cache_token()
    nk.reset_config()
    assert nk.cache_token() != tok_on  # knob flips must miss jit caches


# ---------------------------------------------------------------------------
# hash-join probe kernel: differential triples over ops/join.equi_join
# ---------------------------------------------------------------------------


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


def _join_rows(out, out_types):
    n = int(jax.device_get(out.num_rows_device()))
    rows = []
    for i in range(n):
        row = []
        for c in out.columns:
            d = np.asarray(jax.device_get(c.data))[i]
            valid = c.validity is None or \
                bool(np.asarray(jax.device_get(c.validity))[i])
            if isinstance(c, StringColumn):
                row.append(str(c.dictionary[int(d)]) if valid else None)
            else:
                row.append(d.item() if valid else None)
        rows.append(tuple(row))
    return sorted(rows, key=lambda r: tuple((x is None, x) for x in r))


def _run_join(join_type, kernels_on, sk, bk, with_str=False,
              prepared=False):
    nk.configure(enabled=kernels_on)
    s, st = _join_batch(90, 128, 40, seed=1, with_str=with_str)
    b, bt = _join_batch(50, 64, 40, seed=2, with_str=with_str)
    prep = None
    if prepared:
        prep = J.prepare_build(b, bk, bt, [st[o] for o in sk])
        assert prep is not None
    out, ot = J.equi_join(s, b, sk, bk, st, bt, join_type=join_type,
                          prepared=prep)
    return _join_rows(out, ot)


@pytest.mark.parametrize("join_type",
                         ["inner", "left", "leftsemi", "leftanti",
                          "full"])
def test_join_probe_kernel_differential(join_type):
    """kernel == jnp, per join type, over single-column, composite and
    string keys (nulls on the probe/build key), plus the
    build-once/probe-many prepared path."""
    for sk, bk, ws in [([0], [0], False),        # single int64 key
                       ([0, 1], [0, 1], False),  # composite key
                       ([3], [3], True)]:        # string key
        base = _run_join(join_type, False, sk, bk, with_str=ws)
        kern = _run_join(join_type, True, sk, bk, with_str=ws)
        assert base == kern, (join_type, sk, ws)
    # prepared build table reused across probes (non-string keys)
    base = _run_join(join_type, False, [0, 1], [0, 1], prepared=True)
    kern = _run_join(join_type, True, [0, 1], [0, 1], prepared=True)
    assert base == kern, (join_type, "prepared")


def test_join_probe_kernel_empty_build():
    nk.configure(enabled=True)
    s, st = _join_batch(10, 16, 5, seed=3)
    b, bt = _join_batch(0, 8, 5, seed=4)
    out, _ = J.equi_join(s, b, [0], [0], st, bt, join_type="inner")
    assert int(jax.device_get(out.num_rows_device())) == 0
    out, _ = J.equi_join(s, b, [0], [0], st, bt, join_type="left")
    assert int(jax.device_get(out.num_rows_device())) == 10


def test_probe_table_matches_searchsorted():
    """The probe kernel's (lo, cnt) contract IS searchsorted
    left/right over the hash-sorted build side — checked directly."""
    from spark_rapids_tpu.native.kernels import join as njoin

    nk.configure(enabled=True)
    r = np.random.default_rng(7)
    h_b = jnp.asarray(r.integers(-2**62, 2**62, size=64))
    n_valid = jnp.asarray(48)           # tail is padding
    maxh = jnp.iinfo(jnp.int64).max
    h_b = jnp.where(jnp.arange(64) < 48, h_b, maxh)
    sh = jnp.sort(h_b)
    table = njoin.build_table(sh, n_valid, njoin.table_bits_for(64))
    h_p = jnp.asarray(np.concatenate(
        [r.choice(np.asarray(jax.device_get(sh))[:48], 20),
         r.integers(-2**62, 2**62, size=12)]))
    lo, cnt = njoin.probe(table, h_p)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(lo)),
        np.searchsorted(np.asarray(jax.device_get(sh)),
                        np.asarray(jax.device_get(h_p)), side="left"))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(cnt)),
        np.searchsorted(np.asarray(jax.device_get(sh)),
                        np.asarray(jax.device_get(h_p)), side="right") -
        np.searchsorted(np.asarray(jax.device_get(sh)),
                        np.asarray(jax.device_get(h_p)), side="left"))


# ---------------------------------------------------------------------------
# dictionary-string kernels
# ---------------------------------------------------------------------------

_WORDS = ["", "a", "apple", "APPLESAUCE", "banana split", "a%b_c",
          "100%", "under_score", "the quick brown fox", "x", "ab" * 40]


def _string_colv(words, cap=64, seed=3, with_nulls=True):
    from spark_rapids_tpu.expressions.base import ColV

    r = np.random.default_rng(seed)
    dic = np.unique(np.array(words, dtype=object).astype(str)) \
        .astype(object)
    codes = jnp.asarray(r.integers(0, len(dic), cap).astype(np.int32))
    validity = jnp.asarray(r.random(cap) > 0.2) if with_nulls else None
    col = StringColumn(codes, dic, validity)
    return ColV(dt.STRING, codes, validity, col), dic


class _Child:
    """Minimal child expression yielding a fixed ColV."""

    children = []
    _colv = None

    def eval(self, ctx):
        return _Child._colv


def test_string_predicates_differential():
    """LIKE / contains / startswith / endswith: kernel == host
    dictionary map, over escapes, wildcards and nulls."""
    from spark_rapids_tpu.expressions import strings as S

    def run(on, build):
        nk.configure(enabled=on)
        colv, _dic = _string_colv(_WORDS)
        _Child._colv = colv
        res = build().eval(None)
        vals = np.asarray(jax.device_get(res.data))
        vmask = None if res.validity is None else \
            np.asarray(jax.device_get(res.validity))
        return vals, vmask

    cases = [
        lambda: S.Like(_Child(), "%apple%"),
        lambda: S.Like(_Child(), "a%b\\_c"),
        lambda: S.Like(_Child(), "100\\%"),
        lambda: S.Like(_Child(), "_pple"),
        lambda: S.Like(_Child(), "%quick%fox"),
        lambda: S.Contains(_Child(), "an"),
        lambda: S.StartsWith(_Child(), "a"),
        lambda: S.EndsWith(_Child(), "x"),
    ]
    for build in cases:
        base_v, base_m = run(False, build)
        kern_v, kern_m = run(True, build)
        np.testing.assert_array_equal(base_v, kern_v)
        if base_m is None:
            assert kern_m is None
        else:
            np.testing.assert_array_equal(base_m, kern_m)


def test_substring_differential():
    from spark_rapids_tpu.expressions import strings as S

    def run(on, pos, length):
        nk.configure(enabled=on)
        colv, _dic = _string_colv(_WORDS, seed=17)
        _Child._colv = colv
        res = S.Substring(_Child(), pos, length).eval(None)
        codes = np.asarray(jax.device_get(res.data))
        return [str(res.scol.dictionary[c]) for c in codes]

    for pos, length in [(1, 3), (2, 100), (-3, 2), (0, 2), (5, 0)]:
        assert run(False, pos, length) == run(True, pos, length), \
            (pos, length)


def test_string_kernel_non_ascii_fallback():
    """`_` wildcards and substring need ASCII byte==char; a non-ASCII
    dictionary must fall back (predicate_colv returns None) rather
    than answer wrong."""
    from spark_rapids_tpu.native.kernels import strings as nks

    nk.configure(enabled=True)
    colv, _dic = _string_colv(["café", "naïve", "日本語", "plain"],
                              with_nulls=False)
    assert nks.predicate_colv(colv, "like", "pl_in", "\\") is None
    assert nks.substring_colv(colv, 1, 2) is None
    # but byte-exact predicates still run on UTF-8
    got = nks.predicate_colv(colv, "contains", "ai")
    assert got is not None


def test_string_kernel_knob_off_returns_none():
    from spark_rapids_tpu.native.kernels import strings as nks

    colv, _dic = _string_colv(_WORDS)
    assert nks.predicate_colv(colv, "contains", "a") is None
    assert nks.substring_colv(colv, 1, 2) is None


# ---------------------------------------------------------------------------
# streaming fold seam
# ---------------------------------------------------------------------------


def test_streaming_fold_with_kernels_on():
    """A standing aggregation folded over appended micro-batches with
    kernels ON must match the batch oracle at every emit point — the
    fold seam re-enters the fused chain whose trace routed through the
    kernels."""
    from spark_rapids_tpu.api import Session

    nk.configure(enabled=True)
    s = Session()
    s.create_streaming_table(
        "events", Schema(["k", "v"], [dt.INT64, dt.INT64]))
    q = s.sql("SELECT k, SUM(v) AS sv, COUNT(v) AS c "
              "FROM events GROUP BY k")
    try:
        sq = s.service.register_standing(q)
        seen = []
        for i in range(3):
            r = np.random.default_rng(i)
            b = {"k": r.integers(0, 7, 120 + 11 * i).astype(np.int64),
                 "v": r.integers(0, 100,
                                 120 + 11 * i).astype(np.int64)}
            seen.append(pd.DataFrame(b))
            s.append_batch("events", b)
            oracle = pd.concat(seen, ignore_index=True).groupby("k") \
                .agg(sv=("v", "sum"), c=("v", "count")).reset_index()
            assert_frames_equal(oracle, sq.results())
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# SPMD mesh
# ---------------------------------------------------------------------------


def test_spmd_mesh_8_shard_bitexact():
    """Join + group-by + sort on the 8-shard mesh with kernels ON must
    be BIT-equal to the single-device kernels-on run and to the
    kernels-off run: kernel routing happens inside the shard_map
    programs and changes nothing observable."""
    from spark_rapids_tpu.api import Session

    n = 997                 # not divisible by 8: uneven shards
    r = np.random.default_rng(23)
    fact = pd.DataFrame({
        "k": r.integers(0, 40, n).astype(np.int64),
        "v": r.integers(0, 1000, n).astype(np.int64)})
    dim = pd.DataFrame({"k": np.arange(40, dtype=np.int64),
                        "w": (np.arange(40, dtype=np.int64) * 3) % 7})

    def run(mesh, kernels_on):
        nk.configure(enabled=kernels_on)
        conf = {"rapids.tpu.mesh.enabled": True,
                "rapids.tpu.mesh.devices": 8} if mesh else {}
        s = Session(conf)
        try:
            s.create_temp_view("fact", s.create_dataframe(fact))
            s.create_temp_view("dim", s.create_dataframe(dim))
            return s.sql(
                "SELECT dim.w AS w, SUM(fact.v) AS sv, COUNT(*) AS c "
                "FROM fact JOIN dim ON fact.k = dim.k "
                "GROUP BY dim.w ORDER BY w").to_pandas()
        finally:
            s.stop()

    base = run(mesh=False, kernels_on=False)
    single = run(mesh=False, kernels_on=True)
    mesh = run(mesh=True, kernels_on=True)
    for other, tag in ((single, "single+kernels"), (mesh, "mesh")):
        assert list(base.columns) == list(other.columns)
        for c in base.columns:
            np.testing.assert_array_equal(
                base[c].to_numpy(), other[c].to_numpy(),
                err_msg=f"{tag}: col {c}")
