"""A query's exchange blocks end with the query (PR 31).

Every batch a ``ShuffleExchangeExec`` or ``BroadcastExchangeExec``
registers is removed from the catalog when the root that ran the plan
(``collect()``, ``count()``, a write, the query service) has its result or
raised: ``len(get_catalog())`` is flat from query to query,
``exchange.blocks.registered`` equals ``exchange.blocks.closed``, and what
a ``cache()`` registered, also over the same batches, stays.
"""
import os

import numpy as np
import pandas as pd
import pytest
from test_benchmark_q3 import SF, TABLES, cached_session

from benchmark.datagen import tpch_like
from spark_rapids_tpu.api import Session, col, functions as F
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu.ops import partition as part_ops
from spark_rapids_tpu.utils import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _statement(name: str) -> str:
    with open(os.path.join(ROOT, "benchmark", "queries", name + ".sql")) as f:
        return f.read()


def _blocks(before: dict) -> tuple:
    d = tracing.counters_delta(before)
    return (d.get("exchange.blocks.registered", 0),
            d.get("exchange.blocks.closed", 0))


@pytest.fixture(scope="module")
def tpch(tmp_path_factory):
    """Three cached tables in several partitions, and a broadcast threshold
    under which customer's build is inlined into the chain over orders
    while orders and lineitem meet in a shuffled join: the plan of sf 1."""
    d = tmp_path_factory.mktemp("lifetime")
    tpch_like.write_tables(str(d), SF, 17, TABLES)
    s = cached_session({t: str(d / t) for t in TABLES}, {
        "rapids.tpu.sql.autoBroadcastJoinThreshold": "100k",
        "rapids.tpu.io.scan.maxPartitionBytes": "1m"})
    yield s
    s.stop()


@pytest.mark.parametrize("stmt,moves_rows", [("q3", True), ("q1", False)])
def test_catalog_is_flat_from_query_to_query(tpch, stmt, moves_rows):
    text = _statement(stmt)
    catalog = get_catalog()
    held, bytes_held = len(catalog), catalog.device_bytes
    first = None
    for k in range(1, 6):
        df = tpch.sql(text)
        before = tracing.counters()
        frame = df.collect()
        registered, closed = _blocks(before)
        first = frame if first is None else first
        pd.testing.assert_frame_equal(frame, first)
        if k in (1, 2, 5):
            assert registered == closed > 0, (k, registered, closed)
            assert len(catalog) == held, (k, len(catalog), held)
            assert catalog.device_bytes == bytes_held
    names = df._last_exec.tree_string()
    assert ("ShuffledHashJoinExec" in names) == moves_rows, names


def test_a_cache_filled_from_a_shuffle_outlives_the_filling_query():
    """The cache registers the batches the exchange's blocks hold: the
    query that filled it ends, the blocks' registrations go, the cache's
    stay and still answer."""
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame({"k": rng.integers(0, 7, 4000),
                        "v": rng.random(4000)})
    s = Session()
    try:
        catalog = get_catalog()
        held = len(catalog)
        before = tracing.counters()
        df = s.create_dataframe(pdf).repartition(4, "k").cache()
        assert df.count() == 4000
        registered, closed = _blocks(before)
        assert registered == closed > 0
        cached = len(catalog) - held
        assert 1 <= cached <= 4         # a batch a partition that has rows
        for _ in range(2):
            got = df.group_by("k").agg(F.sum(col("v")).alias("sv")) \
                .collect().sort_values("k", ignore_index=True)
            want = pdf.groupby("k", as_index=False)["v"].sum()
            assert got["k"].tolist() == want["k"].tolist()
            np.testing.assert_allclose(got["sv"], want["v"], rtol=1e-12)
            assert len(catalog) == held + cached
        df.unpersist()
        assert len(catalog) == held
    finally:
        s.stop()


def test_a_cache_filled_inside_a_service_query_outlives_it():
    """The service sweeps a query's owner tag at its end (staged join sides,
    its exchanges' blocks): the cache the query filled carries no tag."""
    pdf = pd.DataFrame({"k": np.arange(300) % 5, "v": np.arange(300.0)})
    s = Session()
    try:
        catalog = get_catalog()
        held = len(catalog)
        before = tracing.counters()
        df = s.create_dataframe(pdf).repartition(2, "k").cache()
        assert len(df.collect_async().result()) == 300
        registered, closed = _blocks(before)
        assert registered == closed > 0
        cached = len(catalog) - held
        assert cached >= 1
        assert df.count() == 300 and len(df.collect()) == 300
        assert len(catalog) == held + cached
    finally:
        s.stop()


def test_a_query_that_raises_mid_exchange_leaves_no_block(monkeypatch):
    rng = np.random.default_rng(4)
    pdf = pd.DataFrame({"k": rng.integers(0, 100, 3000),
                        "v": rng.random(3000)})
    s = Session({"rapids.tpu.sql.taskThreads": 1})
    try:
        df = s.create_dataframe(pdf).repartition(3).cache()
        assert df.count() == 3000
        catalog = get_catalog()
        held = len(catalog)
        real, calls = part_ops.slice_partitions, []

        def failing(batch, counts):
            calls.append(1)
            if len(calls) == 3:
                raise RuntimeError("planted: the third batch's slice")
            return real(batch, counts)

        monkeypatch.setattr(part_ops, "slice_partitions", failing)
        before = tracing.counters()
        with pytest.raises(RuntimeError, match="planted"):
            df.repartition(4, "k").collect()
        registered, closed = _blocks(before)
        assert registered == closed >= 2, (registered, closed)
        assert len(catalog) == held
        monkeypatch.undo()
        assert len(df.repartition(4, "k").collect()) == 3000
        assert len(catalog) == held
    finally:
        s.stop()


@pytest.mark.parametrize("entry", ["collect", "count", "write",
                                   "collect_async"])
def test_every_entry_that_runs_a_plan_closes_its_blocks(entry, tmp_path):
    pdf = pd.DataFrame({"k": np.arange(600) % 13, "v": np.arange(600.0)})
    s = Session()
    try:
        df = s.create_dataframe(pdf).repartition(3).cache()
        assert df.count() == 600
        catalog = get_catalog()
        held = len(catalog)
        q = df.repartition(4, "k")
        before = tracing.counters()
        if entry == "collect":
            assert len(q.collect()) == 600
        elif entry == "count":
            assert q.count() == 600
        elif entry == "write":
            q.write.parquet(str(tmp_path / "out"))
            assert len(pd.read_parquet(str(tmp_path / "out"))) == 600
        else:
            assert len(q.collect_async().result()) == 600
        registered, closed = _blocks(before)
        assert registered == closed > 0, (registered, closed)
        assert len(catalog) == held
    finally:
        s.stop()
