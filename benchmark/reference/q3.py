"""Plain reference of TPC-H Q3, the Shipping Priority Query (spec 2.4.3,
validation parameters: SEGMENT BUILDING, DATE 1995-03-15), as
``queries/q3.sql`` states it.

pyarrow reads the columns the statement names from the files the engine
read; numpy filters the three tables, joins them by key membership
(``np.isin``: customer to orders on the customer key, orders to lineitem
on the order key; an order key is unique in orders, so every line has at
most one order and the join adds no row), computes a line's revenue in
``dtype`` (float64 is the answer; the control of ``correct`` passes
float32, the precision below the one the configuration states), adds an
order's lines in line order in ``dtype`` (at most seven a group, so the
float64 sums are good to a few units in the sixteenth place), and orders
the groups by revenue descending, then order date; the first ten are the
answer. ``l_orderkey`` alone decides a group: ``o_orderdate`` and
``o_shippriority`` are its order's. Dates are days since 1970, as the
engine returns a DATE. Imports nothing of ``spark_rapids_tpu``.

How rows 10 and 11 are told apart: by their revenues alone, which is
only sound while the two differ by more than the comparison's limit.
``answer_and_gap`` also returns (10th - 11th revenue) / 10th revenue,
and ``answer`` writes it to stderr: a gap under 1e-9 means the tenth row
rests on the last bits of a sum (``reference/q3.json``, ``tie_note``).
"""
import sys

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

LIMIT = 10
SEGMENT = "BUILDING"


def day(text: str) -> int:
    return int((np.datetime64(text) - np.datetime64("1970-01-01")).astype(int))


def read(tables: dict, name: str, columns: list) -> dict:
    """{column: numpy array}; a date as days, a string column as whether
    it equals SEGMENT (the statement compares no other string)."""
    t = pq.read_table(tables[name], columns=columns)
    out = {}
    for c in columns:
        col = t.column(c)
        if pa.types.is_date32(col.type):
            col = col.cast(pa.int32())
        elif pa.types.is_string(col.type):
            col = pc.fill_null(pc.equal(col, SEGMENT), False)
        out[c] = col.to_numpy()
    return out


def answer_and_gap(tables: dict, dtype=np.float64) -> tuple:
    """(the ten rows, relative gap between the 10th and 11th revenue or
    None where there is no 11th group)."""
    cut = day("1995-03-15")
    c = read(tables, "customer", ["c_custkey", "c_mktsegment"])
    building = c["c_custkey"][c["c_mktsegment"]]

    o = read(tables, "orders", ["o_orderkey", "o_custkey", "o_orderdate",
                                "o_shippriority"])
    keep = (o["o_orderdate"] < cut) & np.isin(o["o_custkey"], building)
    by_key = np.argsort(o["o_orderkey"][keep], kind="stable")
    okey = o["o_orderkey"][keep][by_key]
    odate = o["o_orderdate"][keep][by_key]
    oprio = o["o_shippriority"][keep][by_key]

    li = read(tables, "lineitem", ["l_orderkey", "l_extendedprice",
                                   "l_discount", "l_shipdate"])
    keep = (li["l_shipdate"] > cut) & np.isin(li["l_orderkey"], okey)
    lkey = li["l_orderkey"][keep]
    price = np.asarray(li["l_extendedprice"][keep], dtype=dtype)
    disc = np.asarray(li["l_discount"][keep], dtype=dtype)
    line_revenue = price * (dtype(1) - disc)

    columns = ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]
    if not len(lkey):
        return pd.DataFrame({n: [] for n in columns}), None
    in_group = np.argsort(lkey, kind="stable")      # lines in file order
    lkey, line_revenue = lkey[in_group], line_revenue[in_group]
    first = np.flatnonzero(np.r_[True, lkey[1:] != lkey[:-1]])
    group_key = lkey[first]
    revenue = np.add.reduceat(line_revenue, first, dtype=dtype)
    of = np.searchsorted(okey, group_key)
    group_date, group_prio = odate[of], oprio[of]

    order = np.lexsort((group_date, -revenue.astype(np.float64)))
    top = order[:LIMIT]
    gap = None
    if len(order) > LIMIT:
        tenth, next_ = (float(revenue[order[LIMIT - 1]]),
                        float(revenue[order[LIMIT]]))
        gap = (tenth - next_) / max(abs(tenth), 1.0)
    frame = pd.DataFrame({
        "l_orderkey": group_key[top].astype(np.int64),
        "revenue": revenue[top].astype(np.float64),
        "o_orderdate": group_date[top].astype(np.int32),
        "o_shippriority": group_prio[top].astype(np.int32)})
    return frame[columns], gap


def answer(tables: dict, dtype=np.float64) -> pd.DataFrame:
    frame, gap = answer_and_gap(tables, dtype)
    print(f"[reference q3, {np.dtype(dtype).name}] {len(frame)} rows; "
          f"(10th - 11th revenue) / 10th = {gap!r}"
          + (" : UNDER 1e-9, the tenth row rests on a sum's last bits"
             if gap is not None and gap < 1e-9 else ""),
          file=sys.stderr, flush=True)
    return frame
