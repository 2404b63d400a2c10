"""The exchange between a partial and a final aggregate (PR 28).

Without mesh or cluster one process holds every partition on its device,
so ``_AggregateRule`` plans a gather there (``("single",)``, one final
partition) and ``ShuffleExchangeExec`` hands each batch over as its block.
Here: the plan's shape and who still exchanges by hash, the answers bit
for bit against the hash plan built by hand, and what the catalog counts.
The launch fence is in tests/test_tracing.py.
"""
import os

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.api import Session, col, functions as F
from spark_rapids_tpu.benchmarks import datagen
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.execs import basic
from spark_rapids_tpu.execs.adaptive import (AdaptiveShuffledJoinExec,
                                             AdaptiveShuffleReaderExec)
from spark_rapids_tpu.execs.aggregate import HashAggregateExec
from spark_rapids_tpu.execs.base import collect
from spark_rapids_tpu.execs.batching import CoalesceBatchesExec, TargetSize
from spark_rapids_tpu.execs.cache import CachedExec
from spark_rapids_tpu.execs.exchange import (ShuffleExchangeExec,
                                             close_query_blocks)
from spark_rapids_tpu.execs.joins import ShuffledHashJoinExec
from spark_rapids_tpu.execs.sort import SortExec
from spark_rapids_tpu.execs.window import WindowExec
from spark_rapids_tpu.expressions.base import BoundReference
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.plan.optimizer import optimize
from spark_rapids_tpu.plan.overrides import apply_overrides

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _statement(name: str) -> str:
    """A statement of the benchmark's cells, as the cells send it."""
    with open(os.path.join(ROOT, "benchmark", "queries", name + ".sql")) as f:
        return f.read()


def _walk(e):
    yield e
    for c in e.children:
        yield from _walk(c)


def _find(e, klass):
    return [x for x in _walk(e) if isinstance(x, klass)]


def _above_cache(e):
    """The execs of a tree that the cached table does not hide."""
    yield e
    if not isinstance(e, CachedExec):
        for c in e.children:
            yield from _above_cache(c)


def _exchanges(e):
    return [x for x in _above_cache(e) if isinstance(x, ShuffleExchangeExec)]


@pytest.fixture(scope="module")
def session():
    s = Session()
    yield s
    s.stop()


def _cached(session, pdf, parts):
    df = session.create_dataframe(pdf).repartition(parts).cache()
    assert df.count() == len(pdf)
    return df


@pytest.fixture(scope="module")
def kv(session):
    rng = np.random.default_rng(5)
    n = 6000
    pdf = pd.DataFrame({"k": rng.integers(0, 9, n), "v": rng.random(n),
                        "j": rng.integers(0, 50, n)})
    return pdf, _cached(session, pdf, 4)


def _keyed(df):
    return df.group_by("k").agg(F.sum(col("v")).alias("sv"),
                                F.count("*").alias("n"))


# -- (a) the plan's shape ---------------------------------------------------


def test_keyed_aggregate_plans_a_gather(kv):
    """Partial, ``single`` exchange, final; no reader; one final partition."""
    tree = _keyed(kv[1])._exec()
    aggs = [x for x in _above_cache(tree)
            if getattr(x, "mode", None) in ("partial", "final")]
    assert [a.mode for a in aggs] == ["final", "partial"]
    (ex,) = _exchanges(tree)
    assert ex.partitioning == ("single",) and ex.num_out_partitions == 1
    assert ex.children[0] is aggs[1] and aggs[1].num_partitions == 4
    assert aggs[0].num_partitions == 1 and tree.num_partitions == 1
    assert not _find(tree, AdaptiveShuffleReaderExec)


def test_order_by_above_the_gather_plans_no_exchange(kv):
    tree = _keyed(kv[1]).order_by("k")._exec()
    (ex,) = _exchanges(tree)
    assert ex.partitioning == ("single",)
    assert ex.children[0].mode == "partial"
    assert tree.num_partitions == 1


@pytest.mark.parametrize("shuffle_partitions", [0, 5])
def test_shuffle_partitions_has_no_say_in_the_gather(kv, shuffle_partitions):
    conf = RapidsConf(
        {"rapids.tpu.sql.shuffle.partitions": shuffle_partitions})
    tree = apply_overrides(_keyed(kv[1]).order_by("k")._plan, conf)
    assert [x.partitioning[0] for x in _exchanges(tree)] == ["single"]


def test_cluster_mode_keeps_the_hash_exchange(kv):
    """Workers read co-partitioned blocks: hash by key under the reader."""
    conf = RapidsConf({"rapids.tpu.cluster.enabled": True})
    tree = apply_overrides(_keyed(kv[1])._plan, conf)
    (reader,) = _find(tree, AdaptiveShuffleReaderExec)
    ex = reader.exchange
    assert ex.partitioning == ("hash", [0])
    assert ex.num_out_partitions == min(cfg.resolve_shuffle_partitions(conf),
                                        4)
    assert getattr(ex.children[0], "mode", None) == "partial"


def test_mesh_conf_keeps_the_mesh_groupby(kv):
    from spark_rapids_tpu.parallel.execs import MeshGroupByExec

    conf = RapidsConf({"rapids.tpu.mesh.enabled": True,
                       "rapids.tpu.shuffle.inProgram.minRows": 0})
    tree = apply_overrides(_keyed(kv[1])._plan, conf)
    assert _find(tree, MeshGroupByExec)
    assert not [x for x in _exchanges(tree)
                if x.partitioning[0] == "single"]


def test_mesh_conf_below_its_floor_exchanges_by_hash(kv):
    """The mesh on but this boundary not lowered (too few rows): the hash
    exchange stays for ``_enable_in_program_exchanges`` to arm."""
    conf = RapidsConf({"rapids.tpu.mesh.enabled": True,
                       "rapids.tpu.shuffle.inProgram.minRows": 1 << 40})
    tree = apply_overrides(_keyed(kv[1])._plan, conf)
    assert [x.partitioning[0] for x in _exchanges(tree)] == ["hash"]


def test_joins_windows_and_repartition_exchange_as_before(kv, session):
    pdf, df = kv
    n_shuffle = cfg.resolve_shuffle_partitions(session.conf)
    # repartition by key: a hash exchange of the asked width, then a gather
    tree = _keyed(df.repartition(3, "k"))._exec()
    assert [(x.partitioning[0], x.num_out_partitions)
            for x in _exchanges(tree)] == [("single", 1), ("hash", 3)]
    # a shuffled join: both sides by hash, shuffle.partitions wide
    conf = RapidsConf({"rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    other = df.select(col("j").alias("j2"), col("v").alias("w"))
    tree = apply_overrides(
        df.join(other, [("j", "j2")])._plan, conf)
    assert _find(tree, (ShuffledHashJoinExec, AdaptiveShuffledJoinExec))
    assert sorted((x.partitioning[0], x.num_out_partitions)
                  for x in _exchanges(tree)) == [("hash", n_shuffle)] * 2
    # a PARTITION BY window: by hash under a reader
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    wnode = pn.WindowNode([0], [SortKeySpec.spark_default(1)],
                          [pn.WindowCall("row_number", "rn")], df._plan)
    tree = apply_overrides(wnode, session.conf)
    assert _find(tree, WindowExec)
    (reader,) = _find(tree, AdaptiveShuffleReaderExec)
    assert reader.exchange.partitioning == ("hash", [0])
    assert reader.exchange.num_out_partitions == n_shuffle


# -- (c) answers ------------------------------------------------------------


def _hash_plan(df, reducers=2):
    """The plan the planner made until PR 27, by hand around the SAME
    partial aggregate: hash exchange of ``reducers`` partitions, under a
    reader where there are two (a round-robin exchange into one partition
    where there is no key: rows move through the partition kernel and the
    slice), coalesce, final aggregate, and what the statement has above
    it, a range exchange under its sort."""
    conf = df.session.conf
    threads = conf.get(cfg.TASK_THREADS)
    bb = conf.get(cfg.BATCH_SIZE_BYTES)
    tree = df._exec()
    (ex,) = _exchanges(tree)
    assert ex.partitioning == ("single",)
    partial = ex.children[0]
    above, node = [], optimize(df._plan)
    while not isinstance(node, pn.AggregateNode):
        above.append(node)
        (node,) = node.children
    nkeys = len(node.grouping)
    if nkeys:
        moved = ShuffleExchangeExec(("hash", list(range(nkeys))), reducers,
                                    partial, task_threads=threads)
        if reducers > 1:
            moved = AdaptiveShuffleReaderExec(
                moved, conf.get(cfg.ADVISORY_PARTITION_SIZE))
    else:
        moved = ShuffleExchangeExec(("round_robin",), 1, partial,
                                    task_threads=threads)
    out = HashAggregateExec(
        [BoundReference(i, e.dtype) for i, e in enumerate(node.grouping)],
        node.aggs, CoalesceBatchesExec(moved, TargetSize(bb)),
        node.output_schema(), mode="final", conf=conf)
    for node in reversed(above):
        if isinstance(node, pn.ProjectNode):
            out = basic.ProjectExec(node.exprs, out, node.output_schema(),
                                    conf)
        else:
            assert isinstance(node, pn.SortNode) and node.global_sort
            out = SortExec(node.specs, ShuffleExchangeExec(
                ("range", list(node.specs), None), 2, out,
                task_threads=threads, batch_bytes=bb),
                global_sort=True, batch_bytes=bb)
    kinds = [x.partitioning[0] for x in _exchanges(out)]
    assert kinds == ["range"] * isinstance(out, SortExec) + \
        ["hash" if nkeys else "round_robin"], kinds
    return out


def _assert_equal(got: pd.DataFrame, want: pd.DataFrame, float_ulps=0):
    """Frames equal bit for bit, or their float columns to ``float_ulps``
    units in the last place."""
    assert list(got.columns) == list(want.columns)
    assert len(got) == len(want)
    for name in got.columns:
        g, w = got[name].to_numpy(), want[name].to_numpy()
        assert g.dtype == w.dtype, name
        if g.dtype.kind == "f" and float_ulps:
            assert np.array_equal(np.isnan(g), np.isnan(w)), name
            ok = ~np.isnan(g)
            np.testing.assert_array_max_ulp(g[ok], w[ok], maxulp=float_ulps)
        elif g.dtype.kind == "f":
            assert g.tobytes() == w.tobytes(), name
        else:
            assert pd.Series(g).equals(pd.Series(w)), name


@pytest.fixture(scope="module")
def lineitem_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("gather_tpch")
    datagen.write_tables(str(d), 0.002, tables=["lineitem"])
    return str(d / "lineitem")


@pytest.mark.parametrize("parts", [2, 4, 8])
@pytest.mark.parametrize("stmt", ["q1", "q6"])
def test_frames_equal_the_hash_plans_bit_for_bit(lineitem_dir, stmt, parts):
    """Against the hash exchange into ONE reduce partition, bit for bit at
    any width: the partials reach the final aggregate in the same order,
    map task by map task. Against two reduce partitions a group's partials
    lie at other rows of the coalesced batch, and the final aggregate's
    masked reduction adds them in an order that follows the rows: keys,
    counts and row order exact, float sums to the last few bits (as
    between any two settings of ``shuffle.partitions`` before)."""
    s = Session()
    try:
        s.read.parquet(lineitem_dir).repartition(parts).cache() \
            .create_or_replace_temp_view("lineitem")
        df = s.sql(_statement(stmt))
        got = df.collect()
        assert len(got) == (6 if stmt == "q1" else 1)  # datagen's flags
        (ex,) = _exchanges(df._last_exec)
        assert ex.partitioning == ("single",)
        assert ex.children[0].num_partitions == parts
        _assert_equal(got, collect(_hash_plan(df, reducers=1), conf=s.conf))
        _assert_equal(got, collect(_hash_plan(df, reducers=2), conf=s.conf),
                      float_ulps=4)
    finally:
        s.stop()


def _oracle(pdf):
    """pandas' group-by as Spark's: NULL keys one group, sums skip NULLs,
    a group of NULLs sums to NULL."""
    g = pdf.groupby("k", dropna=False, sort=True)
    out = pd.DataFrame({"sv": g["v"].sum(min_count=1), "n": g.size()})
    return out.reset_index()


def _assert_matches(df, pdf):
    got = df.collect().sort_values("k", na_position="last",
                                   ignore_index=True)
    want = _oracle(pdf).sort_values("k", na_position="last",
                                    ignore_index=True)
    assert len(got) == len(want)
    assert got["k"].isna().tolist() == want["k"].isna().tolist()
    assert got["k"].dropna().astype(np.int64).tolist() == \
        want["k"].dropna().astype(np.int64).tolist()
    assert got["n"].tolist() == want["n"].tolist()
    assert got["sv"].isna().tolist() == want["sv"].isna().tolist()
    np.testing.assert_allclose(got["sv"].dropna().astype(np.float64),
                               want["sv"].dropna().astype(np.float64),
                               rtol=1e-12)
    (ex,) = _exchanges(df._last_exec)
    assert ex.partitioning == ("single",)
    return got


def test_empty_input_through_the_gather(session):
    pdf = pd.DataFrame({"k": np.arange(40) % 3, "v": np.arange(40.0)})
    df = _cached(session, pdf, 4)
    none = _keyed(df.filter(col("v") < 0))
    assert len(none.collect()) == 0
    # collect() closes a query's blocks: look at them under the exec layer's
    tree = none._exec()
    assert len(collect(tree, conf=session.conf)) == 0
    (ex,) = _exchanges(tree)
    # a map task's one batch is handed over with its count still on the
    # device (PR 30): four partials of no group, not no block
    assert ex.partitioning == ("single",) and set(ex._blocks) == {0}
    assert [sb.num_rows for sb in ex._blocks[0]] == [0, 0, 0, 0]
    _close_blocks(tree)
    total = df.filter(col("v") < 0).agg(F.sum(col("v")).alias("sv"),
                                        F.count("*").alias("n")).collect()
    assert total["n"].tolist() == [0] and total["sv"].isna().all()


def test_every_partition_but_one_empty(session):
    """A hash repartition of one key leaves three of four partitions empty:
    three map tasks hand nothing over, the fourth its partials."""
    pdf = pd.DataFrame({"p": np.zeros(500, dtype=np.int64),
                        "k": np.arange(500) % 7, "v": np.arange(500.0)})
    df = session.create_dataframe(pdf).repartition(4, "p").cache()
    assert df.count() == 500
    q = _keyed(df)
    _assert_matches(q, pdf)
    tree = q._exec()
    collect(tree, conf=session.conf)
    (ex,) = _exchanges(tree)
    assert len(ex._blocks[0]) == 1
    _close_blocks(tree)


def test_null_keys_and_null_values_through_the_gather(session):
    rng = np.random.default_rng(9)
    n = 3000
    k = rng.integers(0, 5, n).astype(object)
    k[rng.random(n) < 0.2] = None
    v = rng.random(n)
    v[rng.random(n) < 0.2] = np.nan
    # one key whose every value is NULL: its sum is NULL, its count is not
    v[pd.Series(k).eq(4).to_numpy()] = np.nan
    pdf = pd.DataFrame({"k": pd.array(k, dtype="Int64"),
                        "v": pd.array(np.where(np.isnan(v), None, v),
                                      dtype="Float64")})
    q = _keyed(_cached(session, pdf, 5))
    got = _assert_matches(q, pdf.astype({"v": "float64"}))
    assert got["k"].isna().sum() == 1
    assert got.loc[got["k"] == 4, "sv"].isna().all()


@pytest.mark.parametrize("vdtype", [np.float32, np.float64])
def test_float_special_values_over_several_partitions(session, vdtype,
                                                      tmp_path):
    """The float cases of tests/test_groupby_float_semantics.py (NaN,
    -0.0, +0.0, exact ties, NULLs) through partial, gather and final: min,
    max and the counts exact against numpy, and the whole frame bit for
    bit the one-reducer hash plan's (NaN payloads and the sign of zero
    too); against two reducers the order-insensitive columns still are."""
    rng = np.random.default_rng(17)
    n, span = 4000, 96
    keys = rng.integers(0, span, n).astype(np.int64)
    keys[:span] = np.arange(span)
    vals = rng.standard_normal(n).astype(vdtype)
    vals[rng.random(n) < 0.02] = np.nan
    vals[rng.random(n) < 0.1] = vdtype(-0.0)
    vals[rng.random(n) < 0.1] = vdtype(0.0)
    vals[rng.random(n) < 0.15] = vdtype(1.5)
    null = rng.random(n) < 0.1
    # through arrow: pandas' nullable floats would read a NaN as a NULL
    import pyarrow as pa
    import pyarrow.parquet as pq

    pq.write_table(pa.table({"k": keys, "v": pa.array(vals, mask=null)}),
                   tmp_path / "t.parquet")
    df = session.read.parquet(str(tmp_path / "t.parquet")) \
        .repartition(6).cache()
    assert df.count() == n
    q = df.group_by("k").agg(F.min(col("v")).alias("lo"),
                             F.max(col("v")).alias("hi"),
                             F.sum(col("v")).alias("sv"),
                             F.count(col("v")).alias("nv"),
                             F.count("*").alias("n")).order_by("k")
    got = q.collect()
    assert got["k"].tolist() == list(range(span))
    assert got["n"].tolist() == np.bincount(keys, minlength=span).tolist()
    for k, row in got.iterrows():
        live = vals[(keys == k) & ~null]
        assert row["nv"] == len(live)
        if np.isnan(live).any():
            continue    # a NaN's place in min/max: the hash plans' below
        assert vdtype(row["lo"]).tobytes() == live.min().tobytes(), k
        assert vdtype(row["hi"]).tobytes() == live.max().tobytes(), k
    _assert_equal(got, collect(_hash_plan(q, reducers=1), conf=session.conf))
    two = collect(_hash_plan(q, reducers=2), conf=session.conf)
    _assert_equal(got.drop(columns="sv"), two.drop(columns="sv"))


# -- (d) what the catalog counts --------------------------------------------


def _owners(catalog, array) -> int:
    """How many entries of the catalog own ``array`` on the device."""
    with catalog._lock:
        return sum(c.data is array
                   for e in catalog._entries.values()
                   if e.device_batch is not None
                   for c in e.device_batch.columns)


def _close_blocks(tree):
    close_query_blocks(tree)
    assert all(ex._blocks is None for ex in _find(tree, ShuffleExchangeExec))


@pytest.mark.parametrize("pull", ["limit", "sort"])
def test_a_cached_batch_through_a_single_exchange_is_counted_once(
        session, pull):
    """A global limit or sort pulls ``CachedExec`` batches straight into a
    ``single`` exchange. The cache's entry owns them: the block keeps the
    copy it always made, no array is owned twice, and once the query's
    blocks are closed ``device_bytes`` is where it was."""
    pdf = pd.DataFrame({"k": np.arange(2000) % 11,
                        "v": np.arange(2000, dtype=np.float64)})
    df = _cached(session, pdf, 4)
    catalog = get_catalog()
    conf = RapidsConf({"rapids.tpu.sql.shuffle.partitions": 1})
    q = df.limit(10 ** 6) if pull == "limit" else df.order_by("v")
    before, held = catalog.device_bytes, len(catalog)
    tree = apply_overrides(q._plan, conf)
    (ex,) = _exchanges(tree)
    assert ex.partitioning == ("single",)
    out = collect(tree, conf=conf)
    assert sorted(out["v"].tolist()) == pdf["v"].tolist()
    blocks = ex._blocks[0]
    assert len(blocks) == 4 and len(catalog) == held + 4
    for h in blocks:
        with h.acquired() as b:
            assert [_owners(catalog, c.data) for c in b.columns] == [1, 1]
    assert catalog.device_bytes == before + sum(
        h.device_memory_size() for h in blocks)
    _close_blocks(tree)
    assert catalog.device_bytes == before and len(catalog) == held
    # the cache still owns its batches, and still answers
    assert df.count() == 2000


def test_a_fresh_batch_is_handed_over_not_copied(kv):
    """An aggregate's partials become the block as they are: the block's
    arrays are the ones the partial aggregate made."""
    pdf, df = kv
    tree = _keyed(df)._exec()
    (ex,) = _exchanges(tree)
    seen = []
    partial = ex.children[0]
    real = partial.execute

    def spy(p):
        for b in real(p):
            seen.append(b)
            yield b

    partial.execute = spy
    out = collect(tree, conf=df.session.conf)
    assert len(out) == pdf["k"].nunique()
    assert len(seen) == 4
    handed = []
    for h in ex._blocks[0]:
        with h.acquired() as b:
            handed.append(b)
    # map tasks finish in any order; blocks lie in partition order
    assert sorted(map(id, handed)) == sorted(map(id, seen))
    _close_blocks(tree)


def _small_batch(i):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column

    return ColumnarBatch([Column.from_numpy(np.arange(8) + i)], 8)


def test_owns_follows_a_batch_through_spill_and_removal():
    from spark_rapids_tpu.memory.catalog import BufferCatalog, StorageTier

    catalog = BufferCatalog()
    b = _small_batch(0)
    assert not catalog.owns(b)
    bid = catalog.register(b, 0)
    assert catalog.owns(b)
    catalog.spill_all_device()
    assert catalog.tier_of(bid) is StorageTier.HOST and not catalog.owns(b)
    back = catalog.acquire(bid)
    assert back is not b and catalog.owns(back) and not catalog.owns(b)
    catalog.remove(bid)                # acquired: deferred to the release
    assert catalog.owns(back)
    catalog.release(bid)
    assert not catalog.owns(back) and catalog._by_device_batch == {}


def test_owns_index_under_concurrent_register_spill_and_remove():
    """More workers than cores on one catalog, each registering, spilling,
    reading back and removing its own batches: afterwards the index holds
    exactly the device batches of the entries that are left."""
    import sys
    import threading

    from spark_rapids_tpu.memory.catalog import BufferCatalog

    catalog = BufferCatalog()
    kept, errors = [], []

    def work(w):
        try:
            for i in range(40):
                b = _small_batch(w * 1000 + i)
                bid = catalog.register(b, i % 3)
                if i % 4 == 0:
                    catalog.spill_all_device()
                got = catalog.acquire(bid)
                assert catalog.owns(got)
                catalog.release(bid)
                if i % 2:
                    catalog.remove(bid)
                else:
                    kept.append(bid)
        except BaseException as e:      # reported by the main thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[:1]
    assert len(catalog) == len(kept) == 32 * 20
    with catalog._lock:
        on_device = {id(e.device_batch): e for e in catalog._entries.values()
                     if e.device_batch is not None}
        assert catalog._by_device_batch == on_device
