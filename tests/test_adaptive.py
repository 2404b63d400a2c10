"""Adaptive shuffle-read (AQE-equivalent) tests."""
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.execs.adaptive import (AdaptiveShuffleReaderExec,
                                             MapOutputStatistics,
                                             coalesce_groups)
from spark_rapids_tpu.expressions import aggregates as A
from spark_rapids_tpu.expressions.base import BoundReference
from spark_rapids_tpu.io import ParquetSource
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.plan.overrides import apply_overrides

from tests.compare import assert_cpu_and_tpu_equal


def test_coalesce_groups_algorithm():
    stats = MapOutputStatistics([10, 10, 10, 100, 5, 5, 5, 5])
    groups = coalesce_groups(stats, advisory_bytes=30)
    # contiguity + full coverage, groups near the target
    assert [p for g in groups for p in g] == list(range(8))
    assert groups == [[0, 1, 2], [3], [4, 5, 6, 7]]


def test_coalesce_groups_min_partitions():
    stats = MapOutputStatistics([1] * 8)
    groups = coalesce_groups(stats, advisory_bytes=1 << 30,
                             min_partitions=4)
    assert len(groups) >= 4
    assert [p for g in groups for p in g] == list(range(8))


def test_coalesce_min_parallelism_splits_byte_balanced():
    """Forced-parallelism splits cut at the byte-balanced point, not
    the index midpoint: one heavy partition must not drag half the
    light ones along with it."""
    stats = MapOutputStatistics([100, 1, 1, 1])
    groups = coalesce_groups(stats, advisory_bytes=1 << 30,
                             min_partitions=2)
    # midpoint would give [[0, 1], [2, 3]] (101 vs 2 bytes)
    assert groups == [[0], [1, 2, 3]]


def test_coalesce_min_parallelism_equal_sizes_midpoint():
    """With uniform sizes the byte-balanced cut IS the midpoint."""
    stats = MapOutputStatistics([10, 10, 10, 10])
    groups = coalesce_groups(stats, advisory_bytes=1 << 30,
                             min_partitions=2)
    assert groups == [[0, 1], [2, 3]]
    assert [p for g in groups for p in g] == list(range(4))


def test_skew_detection():
    sizes = [10] * 9 + [10_000_000_000]
    stats = MapOutputStatistics(sizes)
    assert stats.skewed_partitions() == [9]
    assert MapOutputStatistics([10] * 10).skewed_partitions() == []


def test_skew_detection_edges():
    # empty exchange: no partitions, no skew
    assert MapOutputStatistics([]).skewed_partitions() == []
    # strict >: everything exactly AT the cut is not skewed
    assert MapOutputStatistics([10, 10, 10]).skewed_partitions(
        factor=1.0, threshold=0) == []
    # every partition over the cut: all flagged (the cut is
    # max(threshold, factor*median), so a sub-1 factor exposes the
    # threshold floor and uniform-but-huge partitions all qualify)
    assert MapOutputStatistics([100, 100, 100]).skewed_partitions(
        factor=0.5, threshold=60) == [0, 1, 2]
    # threshold floors detection even with an aggressive factor
    assert MapOutputStatistics([1, 1, 40]).skewed_partitions(
        factor=1.5, threshold=1000) == []
    # all-zero sizes never divide by zero or flag anything
    assert MapOutputStatistics([0, 0, 0]).skewed_partitions(
        factor=1.0, threshold=0) == []


@pytest.fixture()
def multifile_scan(tmp_path):
    rng = np.random.default_rng(0)
    for k in range(4):
        n = 500
        t = pa.table({
            "k": rng.integers(0, 40, n).astype(np.int64),
            "v": rng.random(n),
        })
        pq.write_table(t, tmp_path / f"f{k}.parquet")
    src = ParquetSource(str(tmp_path))
    # these tests exercise multi-partition shuffle structure: keep the
    # tiny files as separate scan partitions (packing would collapse
    # the plan to a single partition and erase the exchanges under test)
    src.pack_splits = False
    return pn.ScanNode(src)


def _agg_plan(scan):
    return pn.AggregateNode(
        [BoundReference(0, dt.INT64)],
        [pn.AggCall(A.Sum(BoundReference(1, dt.FLOAT64)), "sv"),
         pn.AggCall(A.Count(BoundReference(1, dt.FLOAT64)), "cv")],
        scan, grouping_names=["k"])


def _find(exec_, klass):
    out = []
    stack = [exec_]
    while stack:
        e = stack.pop()
        if isinstance(e, klass):
            out.append(e)
        stack.extend(e.children)
    return out


def _window_plan(scan):
    """A hash exchange by ``k`` under one reader. (Since PR 28 a keyed
    aggregate without mesh or cluster plans a gather and no reader:
    tests/test_agg_gather.py; a PARTITION BY window still exchanges by
    hash.)"""
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    return pn.WindowNode(
        [0], [SortKeySpec.spark_default(1)],
        [pn.WindowCall(A.Sum(BoundReference(1, dt.FLOAT64)), "sv"),
         pn.WindowCall("row_number", "rn")], scan)


def test_adaptive_agg_coalesces_and_matches(multifile_scan):
    plan = _agg_plan(_window_plan(multifile_scan))
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    exec_ = assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6)
    readers = _find(exec_, AdaptiveShuffleReaderExec)
    assert len(readers) == 1, \
        "adaptive reader must wrap the window's hash exchange, and only it"
    r = readers[0]
    assert r.exchange.partitioning[0] == "hash"
    # tiny data -> far fewer coalesced groups than shuffle partitions
    assert r.num_partitions < r.exchange.num_out_partitions


def test_adaptive_disabled_no_reader(multifile_scan):
    plan = _agg_plan(_window_plan(multifile_scan))
    conf = RapidsConf({"rapids.tpu.sql.adaptive.enabled": False})
    exec_ = apply_overrides(plan, conf)
    assert not _find(exec_, AdaptiveShuffleReaderExec)
    assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6)


def test_adaptive_join_sides_stay_aligned(tmp_path, multifile_scan):
    rng = np.random.default_rng(1)
    n = 300
    t = pa.table({"k2": rng.integers(0, 40, n).astype(np.int64),
                  "w": rng.random(n)})
    pq.write_table(t, tmp_path / "right.parquet")
    pq.write_table(t, tmp_path / "right2.parquet")
    right = pn.ScanNode(ParquetSource(
        [str(tmp_path / "right.parquet"), str(tmp_path / "right2.parquet")]))
    plan = pn.JoinNode("inner", multifile_scan, right, [0], [0])
    # the shuffled path is the scenario under test: keep the small
    # build side from taking the broadcast-threshold shortcut
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True,
                       "rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    exec_ = assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6)
    readers = _find(exec_, AdaptiveShuffleReaderExec)
    assert len(readers) == 2
    # shared spec: identical groups on both sides
    assert readers[0].groups == readers[1].groups


def test_distributed_global_sort_range_partitioned(tmp_path):
    """Global sort over a multi-partition scan goes through a sampled
    range exchange (no single-partition funnel) and stays ordered."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.execs.sort import SortExec
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    rng = np.random.default_rng(3)
    for k in range(4):
        pq.write_table(pa.table(
            {"v": rng.random(400) * 1000,
             "tag": rng.integers(0, 5, 400).astype(np.int64)}),
            tmp_path / f"s{k}.parquet")
    src = ParquetSource(str(tmp_path))
    src.pack_splits = False  # multi-partition structure under test
    scan = pn.ScanNode(src)
    plan = pn.SortNode([SortKeySpec.spark_default(0)], scan)
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    exec_ = apply_overrides(plan, conf)
    exchanges = _find(exec_, ShuffleExchangeExec)
    assert exchanges and exchanges[0].partitioning[0] == "range"
    assert exchanges[0].num_out_partitions > 1
    assert isinstance(exec_, SortExec)
    # compare IN ORDER against the oracle
    from spark_rapids_tpu.cpu.engine import execute_cpu
    from spark_rapids_tpu.execs.base import collect
    from tests.compare import assert_frames_equal

    cpu_df = execute_cpu(plan).to_pandas()
    assert_frames_equal(cpu_df, collect(exec_), sort=False)


def test_distributed_sort_descending_strings(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    rng = np.random.default_rng(4)
    for k in range(3):
        strs = np.array([f"w{int(x)}" if x > 2 else None
                         for x in rng.integers(0, 40, 200)], dtype=object)
        pq.write_table(pa.table({"s": pa.array(strs, type=pa.string())}),
                       tmp_path / f"p{k}.parquet")
    src = ParquetSource(str(tmp_path))
    src.pack_splits = False  # multi-partition structure under test
    scan = pn.ScanNode(src)
    plan = pn.SortNode([SortKeySpec.spark_default(0, ascending=False)],
                       scan)
    from spark_rapids_tpu.cpu.engine import execute_cpu
    from spark_rapids_tpu.execs.base import collect
    from tests.compare import assert_frames_equal

    cpu_df = execute_cpu(plan).to_pandas()
    exec_ = apply_overrides(plan, RapidsConf(
        {"rapids.tpu.sql.test.enabled": True}))
    assert_frames_equal(cpu_df, collect(exec_), sort=False)


def test_distributed_multikey_global_sort(tmp_path):
    """Multi-key global sorts range-partition on full key tuples: ties
    on the first key must not split across partition boundaries."""
    rng = np.random.default_rng(7)
    for k in range(4):
        n = 300
        pq.write_table(pa.table({
            # heavy first-key ties force the lexicographic tiebreak
            "a": rng.integers(0, 4, n).astype(np.int64),
            "b": rng.random(n),
            "s": np.array([f"t{int(x)}" if x > 1 else None
                           for x in rng.integers(0, 30, n)],
                          dtype=object),
        }), tmp_path / f"m{k}.parquet")
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    src = ParquetSource(str(tmp_path))
    src.pack_splits = False  # multi-partition structure under test
    scan = pn.ScanNode(src)
    plan = pn.SortNode(
        [SortKeySpec.spark_default(0),
         SortKeySpec.spark_default(2, ascending=False),
         SortKeySpec.spark_default(1)], scan)
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    from spark_rapids_tpu.cpu.engine import execute_cpu
    from spark_rapids_tpu.execs.base import collect
    from tests.compare import assert_frames_equal

    cpu_df = execute_cpu(plan).to_pandas()
    exec_ = apply_overrides(plan, conf)
    exchanges = _find(exec_, ShuffleExchangeExec)
    assert exchanges and exchanges[0].partitioning[0] == "range"
    assert exchanges[0].num_out_partitions > 1
    assert_frames_equal(cpu_df, collect(exec_), sort=False)
