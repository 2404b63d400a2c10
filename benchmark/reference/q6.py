"""Plain reference of TPC-H Q6 (spec 2.4.6, validation parameters: DATE
1994-01-01, DISCOUNT 0.06, QUANTITY 24), as ``queries/q6.sql`` states it.

pyarrow reads the files the engine read, a block of rows at a time, one
thread a file; numpy computes the statement in ``dtype`` (float64 is the
answer; the control of ``correct`` passes float32, the precision below the
one the configuration states): every block's sum is pairwise
(``np.sum``), and the blocks' sums are added exactly (``math.fsum``) in
float64 and by ``np.sum`` in float32. Dates are compared as days since
1970. Imports nothing of ``spark_rapids_tpu``.
"""
import glob
import math
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq

COLUMNS = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
BLOCK_ROWS = 1 << 20


def day(text: str) -> int:
    return int((np.datetime64(text) - np.datetime64("1970-01-01")).astype(int))


def file_sums(path: str, dtype) -> list:
    """The sum of every block of one file that has a row to add."""
    lo, hi = day("1994-01-01"), day("1995-01-01")
    sums = []
    for b in pq.ParquetFile(path).iter_batches(BLOCK_ROWS, columns=COLUMNS):
        ship = b.column("l_shipdate").cast(pa.int32()).to_numpy()
        disc = np.asarray(b.column("l_discount").to_numpy(), dtype=dtype)
        qty = np.asarray(b.column("l_quantity").to_numpy(), dtype=dtype)
        price = np.asarray(b.column("l_extendedprice").to_numpy(),
                           dtype=dtype)
        keep = ((ship >= lo) & (ship < hi) & (disc >= dtype(0.05))
                & (disc <= dtype(0.07)) & (qty < dtype(24)))
        if keep.any():
            sums.append(np.sum(price[keep] * disc[keep], dtype=dtype))
    return sums


def total(sums: list, dtype):
    if dtype is np.float64:
        return math.fsum(sums)
    return np.sum(np.array(sums, dtype=dtype), dtype=dtype)


def answer(tables: dict, dtype=np.float64) -> pd.DataFrame:
    files = sorted(glob.glob(os.path.join(tables["lineitem"], "*.parquet")))
    with ThreadPoolExecutor(4) as pool:
        sums = [s for part in pool.map(lambda f: file_sums(f, dtype), files)
                for s in part]
    revenue = float(total(sums, dtype)) if sums else None
    return pd.DataFrame({"revenue": [revenue]})
