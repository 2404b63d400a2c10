"""Sort kernels (cuDF ``Table.orderBy`` analogue, GpuSortExec.scala:104).

The ORDER BY terms become key lanes (``sortkeys.order_key_arrays``),
``sortkeys.stable_order`` sorts the lanes and a row index, and every
column follows with one gather. No column rides the sort: a sort that
carried this module's payload columns took the chip's compiler 757 s at
65,536 rows for TPC-H Q3 (PR 23), and minutes more a carried column
(PERF.md section 6, PR 27).
"""
from __future__ import annotations

from functools import partial
from typing import List

import jax

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.ops.sortkeys import SortKeySpec


@partial(jax.jit, static_argnames=("dtypes", "specs"))
def _sort_batch(datas, validities, dtypes, specs, num_rows):
    order = sortkeys.lexsort_indices(
        list(zip(datas, validities)), list(dtypes), list(specs), num_rows)
    return sortkeys.take_rows(order, datas, validities)


def sort_batch(batch: ColumnarBatch, specs: List[SortKeySpec],
               dtypes) -> ColumnarBatch:
    datas = [c.data for c in batch.columns]
    validities = [c.validity for c in batch.columns]
    out_d, out_v = _sort_batch(datas, validities, tuple(dtypes),
                               tuple(specs), batch.num_rows_device())
    out_cols = [c._like(d, v)
                for c, d, v in zip(batch.columns, out_d, out_v)]
    return ColumnarBatch(out_cols, batch.num_rows)
