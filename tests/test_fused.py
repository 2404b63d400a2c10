"""Cross-exec fusion (execs/fused.py): one program per pipeline segment.

Oracle strategy: every query runs twice — fusion on (default) and off —
and must produce identical frames; plan-shape assertions pin that the
fused execs actually replaced the per-op pipeline (the dispatch-count
reduction is structural: no FilterExec/BroadcastHashJoinExec remains in
a fused segment). Mirrors the reference's hash-join test matrix
(GpuHashJoin.scala:302-318 kinds) plus the duplicate-build fallback.
"""
import numpy as np
import pandas as pd
import pytest

from compare import assert_frames_equal
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.execs.fused import (FusedAggregateExec,
                                          FusedChainExec, JoinStep)

pytestmark = pytest.mark.smoke


def _sessions():
    on = Session(conf={"rapids.tpu.sql.fusion.enabled": True})
    off = Session(conf={"rapids.tpu.sql.fusion.enabled": False})
    return on, off


def _tables(rng, n=800, nulls=True):
    k = rng.integers(0, 30, n).astype(np.int64)
    fact = pd.DataFrame({
        "k": k,
        "v": rng.normal(size=n),
        "g": rng.integers(0, 6, n).astype(np.int64)})
    if nulls:
        fact.loc[rng.integers(0, n, 40), "v"] = None
    dim = pd.DataFrame({
        "id": np.arange(30, dtype=np.int64),
        "name": np.array([f"cat{i % 5}" for i in range(30)],
                         dtype=object),
        "w": (np.arange(30) * 1.5)})
    if nulls:
        dim.loc[3, "name"] = None
    return fact, dim


def _register(s, fact, dim):
    s.create_temp_view("f", s.create_dataframe(fact))
    s.create_temp_view("d", s.create_dataframe(dim))


def _both(sql, fact, dim):
    on, off = _sessions()
    _register(on, fact, dim)
    _register(off, fact, dim)
    got = on.sql(sql).collect()
    want = off.sql(sql).collect()
    assert_frames_equal(got, want)
    return on, got


def find(node, cls, out=None):
    out = [] if out is None else out
    if isinstance(node, cls):
        out.append(node)
    for c in node.children:
        find(c, cls, out)
    return out


def test_join_agg_becomes_fused_aggregate():
    rng = np.random.default_rng(7)
    fact, dim = _tables(rng)
    sql = ("SELECT d.name AS name, count(*) AS n, sum(f.v) AS sv "
           "FROM f JOIN d ON f.k = d.id WHERE f.g < 4 "
           "GROUP BY d.name ORDER BY name")
    on, _got = _both(sql, fact, dim)
    ex = on.sql(sql)._exec()
    fused = find(ex, FusedAggregateExec)
    assert fused, ex.tree_string()
    # the probe + filter + input projection all live in ONE chain
    assert any(isinstance(st, JoinStep) for st in fused[0].chain.steps)
    from spark_rapids_tpu.execs.basic import FilterExec
    from spark_rapids_tpu.execs.joins import BroadcastHashJoinExec

    assert not find(ex, FilterExec)
    assert not find(ex, BroadcastHashJoinExec)


@pytest.mark.parametrize("kind,sql", [
    ("inner", "SELECT f.k AS k, f.v AS v, d.w AS w FROM f JOIN d "
              "ON f.k = d.id WHERE f.g = 1 ORDER BY k, v"),
    ("left", "SELECT f.k AS k, f.v AS v, d.name AS name FROM f "
             "LEFT JOIN d ON f.k = d.id WHERE f.g = 2 ORDER BY k, v"),
    ("semi", "SELECT f.k AS k, f.v AS v FROM f WHERE f.k IN "
             "(SELECT d.id FROM d WHERE d.w > 10) ORDER BY k, v"),
    ("anti", "SELECT f.k AS k, f.v AS v FROM f WHERE f.k NOT IN "
             "(SELECT d.id FROM d WHERE d.w <= 40) AND f.k IS NOT NULL "
             "ORDER BY k, v"),
])
def test_fused_join_kinds_match_unfused(kind, sql):
    rng = np.random.default_rng(11)
    fact, dim = _tables(rng)
    # out-of-range keys so left/anti have unmatched rows
    fact.loc[rng.integers(0, len(fact), 60), "k"] = 99
    _both(sql, fact, dim)


def test_duplicate_build_keys_fall_back_exactly():
    """A build side with duplicate join keys needs multi-match
    expansion — the chain must detect it (hash-duplicate flag) and run
    the preserved general kernel, bit-identical to fusion-off."""
    rng = np.random.default_rng(13)
    fact, dim = _tables(rng)
    dup = dim.copy()
    dup.loc[len(dup)] = {"id": 5, "name": "dupe", "w": 123.0}
    sql = ("SELECT f.k AS k, count(*) AS n, sum(d.w) AS sw "
           "FROM f JOIN d ON f.k = d.id GROUP BY f.k ORDER BY k")
    on, _ = _both(sql, fact, dup)
    ex = on.sql(sql)._exec()
    # the exec owning the join's build side (a post-aggregate tail
    # chain with no builds may sit above it since the sort absorption)
    fused = [f for f in find(ex, (FusedAggregateExec, FusedChainExec))
             if f.builds]
    assert fused
    # force prep, then confirm the fallback path was chosen
    list(fused[0].execute(0))
    assert fused[0]._preps_ok is False


def test_mixed_int_float_keys_coerce():
    """pandas None->NaN turns an int64 key column float; the join must
    compare bigint = double as double (Spark implicit cast) in both
    the fused probe and the general kernel."""
    rng = np.random.default_rng(17)
    fact, dim = _tables(rng)
    fact.loc[rng.integers(0, len(fact), 50), "k"] = None  # -> float64
    sql = ("SELECT d.name AS name, count(*) AS n FROM f JOIN d "
           "ON f.k = d.id GROUP BY d.name ORDER BY name")
    _both(sql, fact, dim)


def test_multi_join_chain_one_program():
    """Two stacked dimension joins + filter + aggregate fuse into a
    single chain (q5/q26's fact->dim->dim shape)."""
    rng = np.random.default_rng(19)
    fact, dim = _tables(rng, nulls=False)
    dim2 = pd.DataFrame({"id2": np.arange(6, dtype=np.int64),
                         "label": np.array(
                             [f"l{i%3}" for i in range(6)], dtype=object)})
    sql = ("SELECT d2.label AS label, d.name AS name, sum(f.v) AS sv "
           "FROM f JOIN d ON f.k = d.id JOIN d2 ON f.g = d2.id2 "
           "WHERE f.v > -1 GROUP BY d2.label, d.name "
           "ORDER BY label, name")
    on, off = _sessions()
    for s in (on, off):
        _register(s, fact, dim)
        s.create_temp_view("d2", s.create_dataframe(dim2))
    got = on.sql(sql).collect()
    want = off.sql(sql).collect()
    assert_frames_equal(got, want)
    ex = on.sql(sql)._exec()
    fused = find(ex, FusedAggregateExec)
    assert fused, ex.tree_string()
    joins = [st for st in fused[0].chain.steps
             if isinstance(st, JoinStep)]
    assert len(joins) == 2, fused[0].chain.steps


def test_standalone_chain_compacts_lazily():
    """A filter+join segment NOT ending at an aggregate becomes a
    FusedChainExec whose output row count is a device scalar."""
    rng = np.random.default_rng(23)
    fact, dim = _tables(rng, nulls=False)
    sql = ("SELECT f.k AS k, d.w AS w FROM f JOIN d ON f.k = d.id "
           "WHERE f.g = 3 ORDER BY k, w")
    on, _ = _both(sql, fact, dim)
    ex = on.sql(sql)._exec()
    assert find(ex, FusedChainExec), ex.tree_string()


def test_nan_and_negzero_key_semantics_in_fused_probe():
    """NaN == NaN and -0.0 == 0.0 must hold inside the fused program
    (the add-zero canonicalization folds away in larger XLA programs —
    this pins the select-based canonicalization)."""
    on, off = _sessions()
    probe = pd.DataFrame({"y": np.array([0.0, 1.5, 7.25],
                                        dtype=np.float64)})
    build = pd.DataFrame({"y2": np.array([-0.0, np.inf],
                                         dtype=np.float64)})
    for s in (on, off):
        s.create_temp_view("p", s.create_dataframe(probe))
        s.create_temp_view("b", s.create_dataframe(build))
    sql = ("SELECT p.y AS y FROM p WHERE p.y NOT IN "
           "(SELECT y2 FROM b) ORDER BY y")
    got = on.sql(sql).collect()
    want = off.sql(sql).collect()
    assert_frames_equal(got, want)
    assert got["y"].tolist() == [1.5, 7.25]  # 0.0 cancels against -0.0


def test_string_predicates_fuse_into_chain():
    """String-vs-literal predicates (=, IN, <, >=) ride INSIDE the
    chain program as per-batch code-range operands — no FilterExec, no
    eager dictionary pass — and match the unfused engine exactly,
    including nulls and literals absent from the dictionary."""
    rng = np.random.default_rng(31)
    n = 900
    fact = pd.DataFrame({
        "k": rng.integers(0, 20, n).astype(np.int64),
        "v": rng.normal(size=n),
        "c": rng.choice(["web", "store", "catalog", "zzz"], n),
        "m": rng.choice(["M", "S", "D"], n)})
    fact.loc[rng.integers(0, n, 60), "c"] = None
    dim = pd.DataFrame({"id": np.arange(20, dtype=np.int64),
                        "w": np.arange(20) * 2.0})
    sql = ("SELECT f.k AS k, count(*) AS n, "
           "sum(CASE WHEN f.c = 'web' THEN f.v ELSE 0.0 END) AS wv "
           "FROM f JOIN d ON f.k = d.id "
           "WHERE f.m IN ('M', 'S') AND f.c >= 'catalog' "
           "AND f.c < 'x' AND f.c <> 'nope' "
           "GROUP BY f.k ORDER BY k")
    on, got = _both(sql, fact, dim)
    ex = on.sql(sql)._exec()
    from spark_rapids_tpu.execs.basic import FilterExec

    assert not find(ex, FilterExec), ex.tree_string()
    fused = find(ex, FusedAggregateExec)
    assert fused, ex.tree_string()
    assert fused[0].chain.n_aux > 0  # string preds became aux operands


def test_string_pred_literal_absent_from_dictionary():
    """A literal that never occurs in a batch's dictionary must match
    nothing (equality) / split correctly (range) — searchsorted gives a
    lo==hi empty range, not a false positive."""
    fact = pd.DataFrame({"k": np.arange(50, dtype=np.int64),
                         "c": np.array(
                             ["aa", "bb", "cc", "dd", "ee"] * 10,
                             dtype=object)})
    dim = pd.DataFrame({"id": np.arange(50, dtype=np.int64),
                        "w": np.arange(50) * 1.0})
    sql = ("SELECT count(*) AS n FROM f JOIN d ON f.k = d.id "
           "WHERE f.c = 'bbb' OR f.c > 'dd'")
    _both(sql, fact, dim)


def test_multi_join_distinct_key_shapes_pair_preps_correctly():
    """Regression (TPC-DS q83/q93): a chain with joins whose build
    sides have DIFFERENT widths and key ordinals must pair each
    prepared build with its own key spec — the builds list is in
    extraction order while steps run in execution order."""
    rng = np.random.default_rng(83)
    n = 600
    fact = pd.DataFrame({
        "k": rng.integers(0, 25, n).astype(np.int64),
        "s": rng.integers(0, 40, n).astype(np.int64),
        "v": rng.normal(size=n)})
    wide = pd.DataFrame({
        "pad0": np.arange(25) * 7.0,
        "pad1": np.arange(25) * 3.0,
        "id": np.arange(25, dtype=np.int64),     # key at ordinal 2
        "w": np.arange(25) * 1.5})
    narrow = pd.DataFrame({"sid": rng.choice(40, 15, replace=False)
                           .astype(np.int64)})   # 1-col semi build
    on, off = _sessions()
    for s in (on, off):
        s.create_temp_view("f", s.create_dataframe(fact))
        s.create_temp_view("wide", s.create_dataframe(wide))
        s.create_temp_view("narrow", s.create_dataframe(narrow))
    sql = ("SELECT f.k AS k, sum(f.v) AS sv, count(*) AS n "
           "FROM f JOIN wide ON f.k = wide.id "
           "WHERE f.s IN (SELECT sid FROM narrow) "
           "GROUP BY f.k ORDER BY k")
    got = on.sql(sql).collect()
    want = off.sql(sql).collect()
    assert_frames_equal(got, want)
    ex = on.sql(sql)._exec()
    fused = find(ex, FusedAggregateExec)
    assert fused, ex.tree_string()
    widths = sorted(len(s.build_types) for s in fused[0].chain.steps
                    if isinstance(s, JoinStep))
    assert len(widths) == 2 and widths[0] != widths[1], widths


# --------------------------------------------------- dense probe tables

def test_dense_probe_selected_and_matches_hash_path():
    """Single integral build keys probe through the dense inverse table
    (PreparedBuild.table); results must equal both the hash-probe path
    (forced via the denseProbe.maxSpan=0 config knob) and fusion-off,
    including negative keys, out-of-range probes, and null values."""
    rng = np.random.default_rng(29)
    n = 600
    fact = pd.DataFrame({
        "k": rng.integers(-40, 60, n).astype(np.int64),  # out-of-range
        "v": rng.normal(size=n)})                        # probes incl.
    fact.loc[rng.integers(0, n, 25), "v"] = None
    dim = pd.DataFrame({
        "id": np.arange(-30, 25, dtype=np.int64),    # negative base
        "w": rng.normal(size=55)})
    sql = ("SELECT f.k AS k, f.v AS v, d.w AS w FROM f JOIN d "
           "ON f.k = d.id ORDER BY k, v")
    on, _ = _both(sql, fact, dim)
    ex = on.sql(sql)._exec()
    fused = find(ex, (FusedAggregateExec, FusedChainExec))
    assert fused
    list(fused[0].execute(0))
    assert fused[0]._preps is not None
    assert fused[0]._preps[0].table is not None      # dense mode chosen

    # force the hash path via the config knob and compare exactly
    on2 = Session(conf={"rapids.tpu.sql.fusion.enabled": True,
                        "rapids.tpu.sql.fusion.denseProbe.maxSpan": 0})
    off2 = Session(conf={"rapids.tpu.sql.fusion.enabled": False})
    _register(on2, fact, dim)
    _register(off2, fact, dim)
    got_hash = on2.sql(sql).collect()
    want = off2.sql(sql).collect()
    assert_frames_equal(want, got_hash)
    ex2 = on2.sql(sql)._exec()
    fused2 = find(ex2, (FusedAggregateExec, FusedChainExec))
    list(fused2[0].execute(0))
    assert fused2[0]._preps[0].table is None         # hash mode forced


def test_dense_probe_multi_key_stays_hash():
    """Composite join keys keep the hash+searchsorted probe."""
    rng = np.random.default_rng(31)
    n = 400
    fact = pd.DataFrame({
        "a": rng.integers(0, 8, n).astype(np.int64),
        "b": rng.integers(0, 7, n).astype(np.int64),
        "v": rng.normal(size=n)})
    dim = pd.DataFrame({
        "x": np.repeat(np.arange(8, dtype=np.int64), 7),
        "y": np.tile(np.arange(7, dtype=np.int64), 8),
        "w": rng.normal(size=56)})
    sql = ("SELECT f.a AS a, f.b AS b, f.v AS v, d.w AS w FROM f "
           "JOIN d ON f.a = d.x AND f.b = d.y ORDER BY a, b, v")
    on, _ = _both(sql, fact, dim)
    ex = on.sql(sql)._exec()
    fused = find(ex, (FusedAggregateExec, FusedChainExec))
    assert fused
    list(fused[0].execute(0))
    assert fused[0]._preps[0].table is None


def test_wide_agg_compacts_before_sort_path(monkeypatch):
    """A wide (chunk-forcing) aggregate over a fused filter compacts
    survivors first when the batch is large: the 2^23-capacity chunked
    groupby shape costs a multi-ten-minute compile (q26 @ sf 1).
    Forced here via a tiny threshold; results must match fusion-off."""
    from spark_rapids_tpu.execs.aggregate import HashAggregateExec

    monkeypatch.setattr(HashAggregateExec, "_COMPACT_WIDE_MIN_CAP", 256)
    rng = np.random.default_rng(41)
    n = 3000
    fact = pd.DataFrame({
        # high-cardinality float key: defeats the dense path so the
        # compaction branch (sort path) is the one under test
        "k": rng.normal(0, 1000, n).round(3),
        **{f"v{i}": rng.normal(size=n) for i in range(8)}})
    sql = ("SELECT k, " +
           ", ".join(f"sum(v{i}) AS s{i}" for i in range(8)) +
           " FROM f WHERE v0 > 0 GROUP BY k ORDER BY k LIMIT 50")
    on, off = _sessions()
    on.create_temp_view("f", on.create_dataframe(fact))
    off.create_temp_view("f", off.create_dataframe(fact))
    got = on.sql(sql).collect()
    want = off.sql(sql).collect()
    assert_frames_equal(got, want)


def test_in_program_build_knob_off_matches_on():
    """inProgramBuild on (default: builds fold into the chain's first
    launch) vs off (host _prep_build + batched flag sync) must be
    frame-identical, and the on-path must actually resolve the builds
    from the inline launch rather than falling back."""
    rng = np.random.default_rng(29)
    fact, dim = _tables(rng)
    sql = ("SELECT d.name AS name, count(*) AS n, sum(f.v) AS sv "
           "FROM f JOIN d ON f.k = d.id WHERE f.g < 4 "
           "GROUP BY d.name ORDER BY name")
    key = "rapids.tpu.sql.fusion.inProgramBuild.enabled"
    s_on = Session(conf={key: True})
    s_off = Session(conf={key: False})
    _register(s_on, fact, dim)
    _register(s_off, fact, dim)
    got = s_on.sql(sql).collect()
    want = s_off.sql(sql).collect()
    assert_frames_equal(got, want)
    # the inline launch resolved the builds (no fallback, no host prep)
    ex = s_on.sql(sql)._exec()
    fused = [f for f in find(ex, (FusedAggregateExec, FusedChainExec))
             if f.builds]
    assert fused
    list(fused[0].execute(0))
    assert fused[0]._preps_ok is True
    assert fused[0]._preps and fused[0]._preps[0].ok
    # knob-off exec goes through the host path and agrees too
    ex_off = s_off.sql(sql)._exec()
    host = [f for f in find(ex_off,
                            (FusedAggregateExec, FusedChainExec))
            if f.builds]
    if host:  # fusion still on; only the build inlining is disabled
        assert not host[0]._inline_enabled()


def test_in_program_build_dense_table_from_stats():
    """A dim table with host-known key stats gets its dense inverse
    table built INSIDE the inline launch — the prepared build carries
    table + dense_lo without any separate _prep_build dispatch."""
    rng = np.random.default_rng(31)
    fact, dim = _tables(rng)
    sql = ("SELECT f.k AS k, f.v AS v, d.w AS w FROM f JOIN d "
           "ON f.k = d.id WHERE f.g = 1 ORDER BY k, v")
    on, _ = _sessions()
    _register(on, fact, dim)
    ex = on.sql(sql)._exec()
    fused = [f for f in find(ex, (FusedAggregateExec, FusedChainExec))
             if f.builds]
    assert fused
    list(fused[0].execute(0))
    assert fused[0]._preps_ok is True
    # dim ids are 0..29 with upload stats: dense-eligible
    assert fused[0]._preps[0].table is not None
    assert fused[0]._preps[0].dense_lo == 0
