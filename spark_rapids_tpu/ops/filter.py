"""Filter as masked stable compaction.

cuDF ``tbl.filter(mask)`` (reference basicPhysicalOperators.scala:100-130)
allocates an exact-sized output. Under XLA we keep the capacity static:
a stable argsort on the negated keep-mask moves kept rows to the front in
their original order, and the new row count travels as a device scalar —
no host sync, the whole scan->filter->... chain stays on device.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops import sortkeys

ColPair = Tuple[jax.Array, Optional[jax.Array]]


@jax.jit
def _compact(datas, validities, keep: jax.Array, num_rows: jax.Array):
    capacity = keep.shape[0]
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    keep = keep & live
    # stable: kept rows first, original order preserved
    order, _ = sortkeys.stable_order([~keep])
    new_count = jnp.sum(keep).astype(jnp.int32)
    out_datas, out_validities = sortkeys.take_rows(order, datas, validities)
    return out_datas, out_validities, new_count


def compact_batch(batch: ColumnarBatch, keep: jax.Array,
                  keep_validity: Optional[jax.Array] = None) -> ColumnarBatch:
    """Rows where keep is true AND valid survive (SQL WHERE drops
    null-predicate rows)."""
    if keep_validity is not None:
        keep = keep & keep_validity
    datas = [c.data for c in batch.columns]
    validities = [c.validity for c in batch.columns]
    out_d, out_v, new_count = _compact(datas, validities, keep,
                                       batch.num_rows_device())
    cols = [c._like(d, v)
            for c, d, v in zip(batch.columns, out_d, out_v)]
    return ColumnarBatch(cols, new_count)


@partial(jax.jit, static_argnames=("out_capacity",))
def shrink_to(datas, validities, num_rows: jax.Array, out_capacity: int):
    """Copy the live prefix into a smaller capacity (post-filter
    re-bucketing at coalesce boundaries)."""
    out_d = [d[:out_capacity] for d in datas]
    out_v = [None if v is None else v[:out_capacity] for v in validities]
    return out_d, out_v


def rebucket(batch: ColumnarBatch) -> ColumnarBatch:
    """Re-bucket a batch to the tightest capacity for its realized count
    (host-sync; used at materialization/shuffle boundaries)."""
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    n = batch.realized_num_rows()
    cap = bucket_capacity(n)
    if cap >= batch.capacity:
        return batch
    datas = [c.data for c in batch.columns]
    validities = [c.validity for c in batch.columns]
    out_d, out_v = shrink_to(datas, validities, batch.num_rows_device(), cap)
    cols = [c._like(d, v) for c, d, v in zip(batch.columns, out_d, out_v)]
    return ColumnarBatch(cols, n)
