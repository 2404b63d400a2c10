"""The generator against the specification it is written after: q1's four
groups and q6's revenue near the published SF 1 answers (scaled: counts by
the scale factor, prices by the mean retail price, which rises with the
number of parts), orders consistent with their lines, exact row counts,
and the same seed giving the same tables. Needs no engine and no chip."""
import numpy as np
import pyarrow.parquet as pq
import pytest

from benchmark import run
from benchmark.datagen import tpch_like

SF = 0.1
#: TPC-H's published SF 1 answers: q1 count_order and avg_price a group
PUBLISHED_Q1 = {("A", "F"): (1478493, 38273.13), ("N", "F"): (38854, 38284.47),
                ("N", "O"): (2920374, 38249.12), ("R", "F"): (1478870, 38250.85)}
PUBLISHED_Q6 = 123141078.23


@pytest.fixture(scope="module")
def tables(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("tpch"))
    rows = tpch_like.write_tables(d, SF, 2**31 + 21,
                                  ["lineitem", "orders", "customer"])
    return d, rows


def test_q1_and_q6_are_near_the_published_answers(tables):
    d, _ = tables
    q1 = run.load_module("reference", "q1").answer(
        {"lineitem": d + "/lineitem"})
    got = {(r.l_returnflag, r.l_linestatus): (r.count_order, r.avg_price)
           for r in q1.itertuples()}
    assert sorted(got) == sorted(PUBLISHED_Q1)
    dearer = got["N", "O"][1] / PUBLISHED_Q1["N", "O"][1]
    assert 0.9 < dearer < 1.0
    for key, (count, price) in PUBLISHED_Q1.items():
        assert got[key][0] == pytest.approx(count * SF, rel=0.06), key
        assert got[key][1] == pytest.approx(price * dearer, rel=0.01), key
    q6 = run.load_module("reference", "q6").answer(
        {"lineitem": d + "/lineitem"})
    assert q6.revenue[0] == pytest.approx(PUBLISHED_Q6 * SF * dearer,
                                         rel=0.03)


def test_rows_are_exact_and_orders_follow_from_their_lines(tables):
    d, rows = tables
    assert rows == {"lineitem": 600_000, "orders": 150_000,
                    "customer": 15_000}
    li = pq.read_table(d + "/lineitem").to_pandas()
    od = pq.read_table(d + "/orders").to_pandas()
    assert len(li) == 600_000 and len(li.columns) == 16
    by = li.groupby("l_orderkey", sort=True)
    assert (by.size().index == od.o_orderkey).all()
    assert by.size().between(1, 7).all()
    assert (by.l_linenumber.max() == by.size()).all()
    charge = li.l_extendedprice * (1 + li.l_tax) * (1 - li.l_discount)
    total = charge.groupby(li.l_orderkey).sum()
    assert np.allclose(total.values, od.o_totalprice.values, atol=0.006)
    opened = (li.l_linestatus == "O").groupby(li.l_orderkey).mean()
    want = np.where(opened == 0, "F", np.where(opened == 1, "O", "P"))
    assert (want == od.o_orderstatus.values).all()
    assert li.l_comment.str.len().between(10, 43).all()
    assert 26 < li.l_comment.str.len().mean() < 27
    assert (od.o_custkey % 3 != 0).all()
    assert od.o_custkey.between(1, 15_000).all()


def test_the_same_seed_gives_the_same_table(tables, tmp_path):
    d, _ = tables
    tpch_like.write_tables(str(tmp_path), SF, 2**31 + 21, ["lineitem"])
    a = pq.read_table(d + "/lineitem")
    assert a.equals(pq.read_table(str(tmp_path) + "/lineitem"))
    tpch_like.write_tables(str(tmp_path / "other"), SF, 22, ["lineitem"])
    b = pq.read_table(str(tmp_path / "other") + "/lineitem")
    assert not a.column("l_partkey").equals(b.column("l_partkey"))
