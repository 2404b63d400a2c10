"""One launch a cached batch on the map side of a fused aggregate (PR 30).

Where a fused aggregate's partials have a small static shape (no grouping
keys, or keys in a dense layout) ``FusedAggregateExec._fold`` runs chain,
update and merge as ONE program whose only results are the running
partials; everything else keeps ``HashAggregateExec._fold``'s three
launches, and ``utils/tracing``'s ``fused_agg.*`` counters say which way
each batch went. Here: the answers bit for bit against the three-launch
path (put back by hand) and against the ``cpu/`` oracle, the odd batches
(empty, wholly filtered, of two capacities), and every reason to fall
back reached, counted and answered as before. The launch fence is in
tests/test_tracing.py, the chip's compiler in tests/test_tpu_compile.py.
"""
import math
import os

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import Session, col, functions as F
from spark_rapids_tpu.benchmarks import datagen
from spark_rapids_tpu.cpu.engine import execute_cpu
from spark_rapids_tpu.execs.aggregate import HashAggregateExec
from spark_rapids_tpu.execs.cache import CachedExec
from spark_rapids_tpu.execs.fused import FusedAggregateExec
from spark_rapids_tpu.memory import fault_injection as FI
from spark_rapids_tpu.service.batching import microbatch
from spark_rapids_tpu.utils import tracing

from compare import assert_frames_equal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS_CONF = "rapids.tpu.sql.reader.batchSizeRows"


def _statement(name: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "queries", name + ".sql")) as f:
        return f.read()


def _walk(e):
    yield e
    for c in e.children:
        yield from _walk(c)


def _counted(df):
    """-> (frame, the ``fused_agg.*`` counters the collect moved)."""
    pre = tracing.counters()
    out = df.collect()
    return out, {k[len("fused_agg."):]: v
                 for k, v in tracing.counters_delta(pre).items()
                 if k.startswith("fused_agg.")}


def _three_launches(monkeypatch):
    """The parent's path: update, concat, merge, a launch each."""
    monkeypatch.setattr(FusedAggregateExec, "_fold",
                        HashAggregateExec._fold)


def _batches(df):
    """Capacities of the cached batches beneath ``df``, a partition."""
    (cached,) = [e for e in _walk(df._exec()) if isinstance(e, CachedExec)]
    return [[b.capacity for b in cached.execute(p)]
            for p in range(cached.num_partitions)]


def _cached_parquet(tmp_path, pdf, rows_a_batch, parts=None, by=None):
    """``pdf`` as one parquet file, read ``rows_a_batch`` rows a batch and
    cached: one partition of ceil(len / rows_a_batch) batches in file
    order, or ``parts`` partitions of as many batches each."""
    path = str(tmp_path / "t")
    os.makedirs(path)
    pdf.to_parquet(os.path.join(path, "part-0.parquet"), index=False)
    s = Session({ROWS_CONF: rows_a_batch})
    df = s.read.parquet(path)
    if parts is not None:
        df = df.repartition(parts, *([by] if by else []))
    df = df.cache()
    assert df.count() == len(pdf)
    return s, df


# -- TPC-H q1 and q6, as the benchmark's cells send them --------------------


@pytest.fixture(scope="module")
def lineitem(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch"))
    datagen.write_tables(path, 0.002, tables=["lineitem"])
    rows = len(pd.read_parquet(os.path.join(path, "lineitem")))
    return os.path.join(path, "lineitem"), rows


@pytest.mark.parametrize("batches", [1, 2, 4])
@pytest.mark.parametrize("stmt", ["q1", "q6"])
def test_frames_bit_identical_to_three_launches(lineitem, monkeypatch, stmt,
                                                batches):
    """Two partitions of ``batches`` cached batches each: every batch takes
    the one launch, and the frame equals the three-launch path's to the
    bit (the same additions in the same order)."""
    path, rows = lineitem
    s = Session({ROWS_CONF: math.ceil(rows / batches)})
    try:
        base = s.read.parquet(path).repartition(2).cache()
        base.create_or_replace_temp_view("lineitem")
        assert base.count() == rows
        assert [len(p) for p in _batches(base)] == [batches, batches]
        got, counts = _counted(s.sql(_statement(stmt)))
        assert counts == {"engaged": 2 * batches}
        with monkeypatch.context() as m:
            _three_launches(m)
            want, none = _counted(s.sql(_statement(stmt)))
        assert none == {}
        assert len(got) == (6 if stmt == "q1" else 1)
        pd.testing.assert_frame_equal(got, want, check_exact=True)
    finally:
        s.stop()


# -- a mixed aggregate over nullable inputs and a nullable key --------------


def _nullable_frame(n=1900, seed=3):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 6, n).astype(float)
    k[rng.random(n) < 0.1] = np.nan
    v = rng.normal(0, 100, n)
    v[rng.random(n) < 0.2] = np.nan
    return pd.DataFrame({
        "k": pd.Series(k).astype("Int64"),
        "s": np.array([None if np.isnan(x) else "k%d" % x for x in k],
                      dtype=object),
        "v": v, "w": rng.integers(-50, 50, n), "keep": rng.random(n)})


def _mixed(df, key="k"):
    return df.filter(col("keep") > 0.15).group_by(key).agg(
        F.sum(col("v")).alias("sv"), F.avg(col("v")).alias("av"),
        F.min(col("v")).alias("nv"), F.max(col("w")).alias("xw"),
        F.count(col("v")).alias("cv"), F.count("*").alias("n"),
        F.first(col("w")).alias("fw"), F.last(col("v")).alias("lv"))


def test_mixed_aggregate_equals_the_cpu_oracle(tmp_path):
    """sum, avg, min, max, count, first, last: one partition of four batches
    in file order, so first and last have one right answer."""
    pdf = _nullable_frame()
    s, df = _cached_parquet(tmp_path, pdf, 500)
    try:
        assert [len(p) for p in _batches(df)] == [4]
        q = _mixed(df)
        got, counts = _counted(q)
        assert counts == {"engaged": 4}
        want = execute_cpu(_mixed(s.create_dataframe(pdf))._plan).to_pandas()
        assert len(got) == 7        # six keys and NULL
        assert_frames_equal(want, got, approx_float=1e-12)
    finally:
        s.stop()


def test_empty_partition_and_two_capacities(tmp_path, monkeypatch):
    """A hash repartition by a two-valued key into four partitions leaves
    at least two of them empty, and 1,100 rows at 500 a batch give batches
    of capacity 512 and 128 in one partition. Grouped by the string key:
    an exchange's slices keep a dictionary and no numeric range."""
    pdf = _nullable_frame(1100, seed=4)
    pdf["p"] = np.arange(len(pdf)) % 2
    s, df = _cached_parquet(tmp_path, pdf, 500, parts=4, by="p")
    try:
        shapes = _batches(df)
        assert sum(1 for p in shapes if p == [128] or not p) >= 2, shapes
        assert any(len(set(p)) > 1 for p in shapes), shapes
        q = _mixed(df, "s").order_by("s")
        got, counts = _counted(q)
        assert set(counts) == {"engaged"}
        assert _counted(_mixed(df))[1] == {
            "fallback.sort_path": counts["engaged"]}
        with monkeypatch.context() as m:
            _three_launches(m)
            want = q.collect()
        # first/last follow the exchange's row order: the same on both
        pd.testing.assert_frame_equal(got, want, check_exact=True)
    finally:
        s.stop()


def test_wholly_filtered_batch(tmp_path, monkeypatch):
    """The file is sorted by the filter's column: the first two batches
    pass no row, with keys and without."""
    pdf = _nullable_frame(1500, seed=5).sort_values("keep") \
        .reset_index(drop=True)
    cut = float(pdf["keep"][1100])
    s, df = _cached_parquet(tmp_path, pdf, 500)
    try:
        keyed = df.filter(col("keep") > cut).group_by("k").agg(
            F.sum(col("v")).alias("sv"), F.count("*").alias("n"))
        total = df.filter(col("keep") > cut).agg(
            F.sum(col("v")).alias("sv"), F.count("*").alias("n"),
            F.min(col("w")).alias("nw"))
        nothing = df.filter(col("keep") > 2.0).agg(
            F.sum(col("v")).alias("sv"), F.count("*").alias("n"))
        for q in (keyed, total, nothing):
            got, counts = _counted(q)
            assert counts == {"engaged": 3}
            with monkeypatch.context() as m:
                _three_launches(m)
                want = q.collect()
            pd.testing.assert_frame_equal(got, want, check_exact=True)
        assert int(total.collect()["n"][0]) == 1500 - 1101
        none = nothing.collect()
        assert int(none["n"][0]) == 0 and pd.isna(none["sv"][0])
    finally:
        s.stop()


# -- every reason to keep the three launches --------------------------------


@pytest.fixture()
def flags(tmp_path):
    """Three batches of one partition, from two files; the second file's
    batch has a key dictionary with a value the first file's lack."""
    rng = np.random.default_rng(9)
    n = 1500
    f = rng.choice(["A", "B"], n).astype(object)
    f[1000:] = rng.choice(["A", "B", "C"], n - 1000)
    pdf = pd.DataFrame({"f": f, "id": np.arange(n, dtype=np.int64),
                        "v": rng.random(n)})
    path = str(tmp_path / "t")
    os.makedirs(path)
    pdf[:1000].to_parquet(os.path.join(path, "part-0.parquet"), index=False)
    pdf[1000:].to_parquet(os.path.join(path, "part-1.parquet"), index=False)
    s = Session({ROWS_CONF: 500})
    df = s.read.parquet(path).cache()
    assert df.count() == n and [len(p) for p in _batches(df)] == [3]
    yield pdf, df
    s.stop()


def _by_flag(df):
    return df.filter(col("v") > 0.05).group_by("f").agg(
        F.sum(col("v")).alias("sv"), F.count("*").alias("n")).order_by("f")


def _oracle_by_flag(pdf):
    g = pdf[pdf["v"] > 0.05].groupby("f")["v"]
    return pd.DataFrame({"f": sorted(g.groups), "sv": g.sum().to_numpy(),
                         "n": g.count().to_numpy()})


def test_fallback_dictionary(flags):
    """The third batch's dictionary differs from the carry's: that batch
    goes through ``merge_partials``, which unifies them."""
    pdf, df = flags
    got, counts = _counted(_by_flag(df))
    assert counts == {"engaged": 2, "fallback.dictionary": 1}
    assert_frames_equal(_oracle_by_flag(pdf), got, approx_float=1e-12)


def test_fallback_sort_path(flags):
    """1,500 distinct int64 keys have no dense layout: the partials have
    the batch's capacity, and the old path keeps them."""
    pdf, df = flags
    q = df.filter(col("v") > 0.05).group_by("id").agg(
        F.sum(col("v")).alias("sv"))
    got, counts = _counted(q)
    assert counts == {"fallback.sort_path": 3}
    want = pdf[pdf["v"] > 0.05][["id", "v"]].rename(columns={"v": "sv"})
    assert_frames_equal(want.reset_index(drop=True), got,
                        approx_float=1e-12)


def test_fallback_batching(flags):
    """Under a service slice the chain program stays the unit that
    coalesces across queries."""
    pdf, df = flags
    q = _by_flag(df.filter(col("id") < 1000))
    token = microbatch.enter_slice(
        microbatch.MicroBatcher(enabled=False), "q-1", False)
    try:
        got, counts = _counted(q)
    finally:
        microbatch.exit_slice(token)
    assert counts == {"fallback.batching": 3}
    assert_frames_equal(_oracle_by_flag(pdf[pdf["id"] < 1000]), got,
                        approx_float=1e-12)


@pytest.mark.parametrize("keyed", [True, False])
def test_fallback_oom(flags, keyed):
    """An OOM in the first batch's fused launch sends it down
    ``_agg_batch``'s ladder; the second batch takes the one launch again,
    with the ladder's partials as its carry (with keys the third's
    dictionary differs, whatever the filter leaves of its rows; without,
    the ladder's one row has the batch's capacity)."""
    pdf, df = flags
    few = df.filter(col("id") < 1000)
    q = _by_flag(few) if keyed else few.filter(col("v") > 0.05).agg(
        F.sum(col("v")).alias("sv"), F.count("*").alias("n"))
    FI.get_injector().arm(at_call=1, sites=["aggregate.step"])
    try:
        got, counts = _counted(q)
        assert FI.get_injector().stats()["injections"] == 1
    finally:
        FI.get_injector().disarm()
    want = _oracle_by_flag(pdf[pdf["id"] < 1000])
    if keyed:
        assert counts == {"engaged": 1, "fallback.oom": 1,
                          "fallback.dictionary": 1}
    else:
        assert counts == {"engaged": 2, "fallback.oom": 1}
        want = pd.DataFrame({"sv": [want["sv"].sum()],
                             "n": [want["n"].sum()]})
    assert_frames_equal(want, got, approx_float=1e-12)


def test_fallback_inline_build(flags):
    """A chain with a broadcast join builds inside its first batch's
    program; from the second batch on the step runs the probe."""
    pdf, df = flags
    dim = pd.DataFrame({"id": np.arange(1500, dtype=np.int64),
                        "g": np.array(["x", "y", "z"],
                                      dtype=object)[np.arange(1500) % 3]})
    s = df.session
    q = df.join(s.create_dataframe(dim), on="id").filter(col("v") > 0.05) \
        .group_by("g").agg(F.sum(col("v")).alias("sv"),
                           F.count("*").alias("n")).order_by("g")
    assert any(isinstance(e, FusedAggregateExec) and e.builds
               for e in _walk(q._exec())), q.explain()
    got, counts = _counted(q)
    assert counts == {"engaged": 2, "fallback.inline_build": 1}
    j = pdf.merge(dim, on="id")
    g = j[j["v"] > 0.05].groupby("g")["v"]
    want = pd.DataFrame({"g": ["x", "y", "z"], "sv": g.sum().to_numpy(),
                         "n": g.count().to_numpy()})
    assert_frames_equal(want, got, approx_float=1e-12)
