"""Plain reference of TPC-H Q1 (spec 2.4.1, validation parameter DELTA 90:
shipdate <= 1998-09-02), as ``queries/q1.sql`` states it.

pyarrow reads the files the engine read, a block of rows at a time, one
thread a file; numpy computes every aggregate group by group in ``dtype``
(float64 is the answer; the control of ``correct`` passes float32, the
precision below the one the configuration states): every block's sums are
pairwise (``np.sum``), and the blocks' sums are added exactly
(``math.fsum``) in float64 and by ``np.sum`` in float32, so the float64
answer is good to a few units in the sixteenth place whatever the row
count. Dates are compared as days since 1970. Imports nothing of
``spark_rapids_tpu``.
"""
import glob
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

KEYS = ["l_returnflag", "l_linestatus"]
COLUMNS = KEYS + ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
                  "l_shipdate"]
SUMS = ["qty", "price", "disc_price", "charge", "disc"]
BLOCK_ROWS = 1 << 20


def day(text: str) -> int:
    return int((np.datetime64(text) - np.datetime64("1970-01-01")).astype(int))


def file_sums(path: str, dtype) -> dict:
    """{(flag, status): {"n": rows, sum name: [a sum a block]}} of a file."""
    last = day("1998-09-02")
    one = dtype(1)
    out = {}
    batches = pq.ParquetFile(path, read_dictionary=KEYS).iter_batches(
        BLOCK_ROWS, columns=COLUMNS)
    for b in batches:
        keep = b.column("l_shipdate").cast(pa.int32()).to_numpy() <= last
        flag, status = b.column("l_returnflag"), b.column("l_linestatus")
        fcode, scode = flag.indices.to_numpy(), status.indices.to_numpy()
        v = {n: np.asarray(b.column(c).to_numpy(), dtype=dtype)
             for n, c in (("qty", "l_quantity"), ("price", "l_extendedprice"),
                          ("disc", "l_discount"), ("tax", "l_tax"))}
        v["disc_price"] = v["price"] * (one - v["disc"])
        v["charge"] = v["disc_price"] * (one + v["tax"])
        for fi, f in enumerate(flag.dictionary.to_pylist()):
            for si, s in enumerate(status.dictionary.to_pylist()):
                m = keep & (fcode == fi) & (scode == si)
                n = int(m.sum())
                if not n:
                    continue
                g = out.setdefault((f, s), {"n": 0, **{k: [] for k in SUMS}})
                g["n"] += n
                for k in SUMS:
                    g[k].append(np.sum(v[k][m], dtype=dtype))
    return out


def total(sums: list, dtype):
    if dtype is np.float64:
        return dtype(math.fsum(sums))
    return np.sum(np.array(sums, dtype=dtype), dtype=dtype)


def answer(tables: dict, dtype=np.float64) -> pd.DataFrame:
    files = sorted(glob.glob(os.path.join(tables["lineitem"], "*.parquet")))
    groups = {}
    with ThreadPoolExecutor(4) as pool:
        for part in pool.map(lambda f: file_sums(f, dtype), files):
            for key, g in part.items():
                mine = groups.setdefault(key, {"n": 0, **{k: [] for k in SUMS}})
                mine["n"] += g["n"]
                for k in SUMS:
                    mine[k].extend(g[k])
    rows = []
    for (f, s), g in sorted(groups.items()):
        t = {k: total(g[k], dtype) for k in SUMS}
        n = dtype(g["n"])
        rows.append((f, s, float(t["qty"]), float(t["price"]),
                     float(t["disc_price"]), float(t["charge"]),
                     float(t["qty"] / n), float(t["price"] / n),
                     float(t["disc"] / n), g["n"]))
    return pd.DataFrame(rows, columns=KEYS + [
        "sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
        "avg_qty", "avg_price", "avg_disc", "count_order"])
