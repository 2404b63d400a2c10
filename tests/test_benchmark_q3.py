"""TPC-H Q3 over three cached tables against the benchmark's plain
reference, by the benchmark's comparison and limits (the cell
``tpch-sf1-join.cached-q3`` at sf 0.02 on the CPU): the engine's frame is
correct on two seeds, the reference in float32 (the control) is not, and
a planted fault (the cached lineitem or orders batch that holds the first
row's order left out) is not.
"""
import json
import os

import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmark import compare
from benchmark.datagen import tpch_like
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.execs.cache import CacheHolder

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SF = 0.02
SEEDS = [31, 2**31 + 31]
TABLES = ("lineitem", "orders", "customer")


def _reference():
    from benchmark import run

    return run.load_module("reference", "q3")


with open(os.path.join(ROOT, "benchmark", "reference", "q3.json")) as _f:
    LIMITS = json.load(_f)["limits"]
with open(os.path.join(ROOT, "benchmark", "queries", "q3.sql")) as _f:
    Q3 = _f.read()


@pytest.fixture(scope="module", params=SEEDS)
def data(request, tmp_path_factory):
    d = tmp_path_factory.mktemp(f"q3_{request.param}")
    tpch_like.write_tables(str(d), SF, request.param, TABLES)
    tables = {t: str(d / t) for t in TABLES}
    return tables, _reference().answer(tables)


def cached_session(tables, conf=None):
    """The cell's set-up: every column but the comments under
    ``read.parquet(columns).cache()`` views, each filled by a count."""
    s = Session(conf or {})
    for t, path in tables.items():
        cols = [c for c in pq.read_schema(
            os.path.join(path, "part-000.parquet")).names
            if not c.endswith("_comment")]
        df = s.read.parquet(path, columns=cols).cache()
        df.create_or_replace_temp_view(t)
        assert df.count() == tpch_like.table_rows(t, SF)
    return s


def _judge(want, got) -> dict:
    r = compare.compare_frames(want, got)
    r["correct"] = r["mismatches"] <= LIMITS["mismatches"] and \
        r["max_rel_err"] <= LIMITS["max_rel_err"]
    return r


def test_engine_frame_is_correct(data):
    tables, want = data
    s = cached_session(tables)
    try:
        df = s.sql(Q3)
        plan = df.explain()
        assert all(ln.strip().startswith("* ") for ln in plan.splitlines()
                   if ln.strip()), plan
        r = _judge(want, df.collect())
    finally:
        s.stop()
    assert len(want) == 10
    assert r["mismatches"] == 0 and r["correct"], r
    # a sum of at most seven products: far inside the limit
    assert r["max_rel_err"] < 1e-14, r


def test_float32_control_is_not_correct(data):
    tables, want = data
    r = _judge(want, _reference().answer(tables, dtype=np.float32))
    assert not r["correct"], r
    assert r["max_rel_err"] > LIMITS["max_rel_err"], r


@pytest.mark.parametrize("table,width", [("lineitem", 15), ("orders", 8)])
def test_a_cached_batch_left_out_is_not_correct(data, monkeypatch, table,
                                                width):
    """After the fill, the table's cached batch that holds the answer's
    first order key (column 0 of lineitem and of orders) is withheld from
    every query: the first row loses lines or its order."""
    tables, want = data
    key = int(want.l_orderkey[0])
    s = cached_session(
        tables, {"rapids.tpu.sql.reader.batchSizeRows": 20000})
    whole = CacheHolder.batches
    left_out = []

    def partial(self, p):
        kept = []
        for h in whole(self, p):
            with h.acquired() as b:
                hit = b.num_columns == width and key in np.asarray(
                    b.columns[0].data)[:b.realized_num_rows()]
            if hit:
                left_out.append(h)
            else:
                kept.append(h)
        return kept

    monkeypatch.setattr(CacheHolder, "batches", partial)
    try:
        r = _judge(want, s.sql(Q3).collect())
    finally:
        monkeypatch.undo()
        s.stop()
    assert left_out, "no cached batch held the first row's order"
    assert not r["correct"], r
