"""Device-side partitioning + contiguous split.

Replaces the cuDF ``Table.partition``/``contiguousSplit`` pair driven by the
reference's partitioners (GpuPartitioning.scala:44-70, GpuHashPartitioning,
GpuRoundRobinPartitioning, GpuRangePartitioning, GpuSinglePartitioning).

The kernel: compute a partition id per row, put the rows in the stable order
of their ids (``sortkeys.stable_order``: one sort of one packed lane, every
column follows with a gather), and read per-partition counts off the sorted
ids by binary search. The sorted
batch plus host-realized offsets is the analogue of a contiguous split —
each partition is a contiguous row range ready for slicing/serialization.
Range partitioning samples bounds host-side exactly like the reference
(GpuRangePartitioner.scala:42-95: CPU-sampled bounds, then device slice).
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.ops import hashing, sortkeys
from spark_rapids_tpu.ops.sortkeys import SortKeySpec


def hash_partition(batch: ColumnarBatch, key_ordinals: List[int],
                   dtypes: List[dt.DType], num_partitions: int
                   ) -> Tuple[ColumnarBatch, np.ndarray]:
    """Returns (rows sorted by partition id, int64 counts[num_partitions])."""
    h = hashing.hash_columns(batch, key_ordinals, dtypes)
    pid = _pmod(h, num_partitions)
    return _split_by_pid(batch, pid, num_partitions)


def round_robin_partition(batch: ColumnarBatch, num_partitions: int,
                          start: int = 0) -> Tuple[ColumnarBatch, np.ndarray]:
    pid = (jnp.arange(batch.capacity, dtype=jnp.int32) + start) \
        % num_partitions
    return _split_by_pid(batch, pid, num_partitions)


def single_partition(batch: ColumnarBatch) -> Tuple[ColumnarBatch, np.ndarray]:
    return batch, np.array([batch.realized_num_rows()], dtype=np.int64)


def range_partition(batch: ColumnarBatch, specs: List[SortKeySpec],
                    dtypes: List[dt.DType], bounds_values: np.ndarray,
                    num_partitions: int) -> Tuple[ColumnarBatch, np.ndarray]:
    """``bounds_values``: (num_partitions-1,) boundary *values* in the key's
    own domain (strings as str), sampled host-side once per exchange —
    exactly the reference's CPU-sampled-bounds design
    (GpuRangePartitioner.scala:42-95). Single-key ranges; the planner falls
    back for multi-key range partitioning."""
    from spark_rapids_tpu.columnar.column import StringColumn

    spec = specs[0]
    col = batch.columns[spec.ordinal]
    t = dtypes[spec.ordinal]
    last = num_partitions - 1
    if isinstance(col, StringColumn):
        # map string bounds into this batch's code space
        code_bounds = np.searchsorted(
            col.dictionary.astype(str) if len(col.dictionary)
            else np.array([], dtype=str),
            np.asarray(bounds_values, dtype=str), side="left")
        key = col.data
        bounds = jnp.asarray(code_bounds.astype(np.int32))
        if not spec.ascending:
            key = -key
            bounds = -jnp.asarray(code_bounds[::-1].astype(np.int32))
        pid = jnp.searchsorted(bounds, key, side="right").astype(jnp.int32)
    else:
        vals = np.asarray(bounds_values, dtype=t.np_dtype)
        key = col.data
        if t.is_floating:
            key = sortkeys.canonicalize_floats(key)
        if not spec.ascending:
            key = -key if (t.is_floating or t.is_numeric) else ~key
            vals = -vals[::-1] if (t.is_floating or t.is_numeric) \
                else np.bitwise_not(vals[::-1])
        pid = jnp.searchsorted(jnp.asarray(vals), key,
                               side="right").astype(jnp.int32)
        if t.is_floating:
            # NaN compares false everywhere; route it like "greatest"
            nan_pid = last if spec.ascending else 0
            pid = jnp.where(jnp.isnan(key), nan_pid, pid)
    if col.validity is not None:
        null_pid = 0 if spec.nulls_first else last
        pid = jnp.where(col.validity, pid, null_pid)
    return _split_by_pid(batch, pid, num_partitions)


def _col_cmp_vs_bound(col, t: dt.DType, spec: SortKeySpec, bval):
    """(gt, lt) boolean arrays: each row's key vs one scalar bound under
    the spec's ordering (direction + null ordering + NaN-greatest +
    -0.0 == 0.0). ``bval`` None = null bound."""
    from spark_rapids_tpu.columnar.column import StringColumn

    cap = col.capacity
    valid = col.validity if col.validity is not None else \
        jnp.ones(cap, dtype=bool)
    zeros = jnp.zeros(cap, dtype=bool)
    if bval is None:
        # null bound: non-null rows compare after it under NULLS FIRST,
        # before it under NULLS LAST; null rows are equal to it
        if spec.nulls_first:
            return valid, zeros
        return zeros, valid
    if isinstance(col, StringColumn):
        d = col.dictionary.astype(str) if len(col.dictionary) else \
            np.array([], dtype=str)
        p = int(np.searchsorted(d, str(bval), side="left"))
        bound_present = p < len(d) and d[p] == str(bval)
        code = col.data
        raw_gt = (code > p) | ((code == p) & (not bound_present))
        raw_lt = code < p
    else:
        x = col.data
        isnan = zeros
        if t.is_floating:
            x = sortkeys.canonicalize_floats(x)
            isnan = jnp.isnan(x)
        b = t.np_dtype.type(bval)
        if t.is_floating and np.isnan(b):
            raw_gt = zeros
            raw_lt = ~isnan  # NaN == NaN; everything else < NaN
        else:
            raw_gt = (x > b) | isnan  # NaN greatest
            raw_lt = (x < b) & ~isnan
    if not spec.ascending:
        raw_gt, raw_lt = raw_lt, raw_gt
    # null rows: before any non-null bound under NULLS FIRST, after
    # under NULLS LAST
    null_lt = jnp.where(valid, raw_lt, spec.nulls_first)
    null_gt = jnp.where(valid, raw_gt, not spec.nulls_first)
    return null_gt, null_lt


def range_partition_multi(batch: ColumnarBatch,
                          specs: List[SortKeySpec],
                          dtypes: List[dt.DType],
                          bounds: List[tuple], num_partitions: int
                          ) -> Tuple[ColumnarBatch, np.ndarray]:
    """Multi-key range partitioning: ``bounds`` is a sorted list of row
    tuples (one value-or-None per sort spec); each row's partition is
    the count of bounds <= its key tuple (lexicographic, the same
    searchsorted-right convention as the single-key path). Bounds is
    small (num_partitions - 1), so the comparison loop is
    O(num_partitions * num_keys) fused element-wise ops."""
    cap = batch.capacity
    pid = jnp.zeros(cap, dtype=jnp.int32)
    for bound in bounds:
        gt = jnp.zeros(cap, dtype=bool)
        eq = jnp.ones(cap, dtype=bool)
        for spec, bval in zip(specs, bound):
            g, l = _col_cmp_vs_bound(batch.columns[spec.ordinal],
                                     dtypes[spec.ordinal], spec, bval)
            gt = gt | (eq & g)
            eq = eq & ~(g | l)
        pid = pid + (gt | eq).astype(jnp.int32)
    return _split_by_pid(batch, pid, num_partitions)


def sample_range_bounds_rows(staged, specs: List[SortKeySpec],
                             dtypes: List[dt.DType],
                             num_partitions: int,
                             max_sample: int = 100_000) -> List[tuple]:
    """Multi-key bounds: sample whole key ROWS across the staged input,
    sort them host-side under the spec ordering, take equi-quantile rows
    as bound tuples (value or None per key)."""
    per_batch = max(max_sample // max(len(staged), 1), 1)
    rng = np.random.default_rng(0x5EED)
    col_samples = [[] for _ in specs]
    valid_samples = [[] for _ in specs]
    for sb in staged:
        with sb.acquired() as b:
            n = b.realized_num_rows()
            idx = np.arange(n) if n <= per_batch else \
                rng.choice(n, per_batch, replace=False)
            for j, spec in enumerate(specs):
                values, validity = b.columns[spec.ordinal].to_numpy(n)
                values = np.asarray(values)[:n][idx]
                v = np.ones(len(idx), dtype=bool) if validity is None \
                    else np.asarray(validity)[:n][idx]
                col_samples[j].append(values)
                valid_samples[j].append(v)
    cols = [np.concatenate(s) if s else np.array([])
            for s in col_samples]
    valids = [np.concatenate(s) if s else np.array([], dtype=bool)
              for s in valid_samples]
    total = len(cols[0]) if cols else 0
    if total == 0 or num_partitions <= 1:
        return []
    # host lexsort under spec semantics (mirrors cpu engine rank arrays)
    keys: List[np.ndarray] = []
    for j in reversed(range(len(specs))):
        spec = specs[j]
        t = dtypes[spec.ordinal]
        vals = cols[j]
        valid = valids[j]
        if t is dt.STRING:
            filled = np.array([x if x is not None else ""
                               for x in vals], dtype=object)
            _, codes = np.unique(filled, return_inverse=True)
            ranked = codes.astype(np.int64)
            nan_rank = np.zeros(total, dtype=np.int8)
        elif t.is_floating:
            f = vals.astype(np.float64)
            nan_rank = np.isnan(f).astype(np.int8)
            ranked = np.where(np.isnan(f), 0.0, f + 0.0)
        else:
            ranked = vals.astype(np.int64)
            nan_rank = np.zeros(total, dtype=np.int8)
        ranked = np.where(valid, ranked, ranked.dtype.type(0))
        nan_rank = np.where(valid, nan_rank, np.int8(0))
        null_rank = np.where(valid, 1, 0) if spec.nulls_first else \
            np.where(valid, 0, 1)
        if not spec.ascending:
            ranked = -ranked if t.is_floating else np.invert(ranked)
            nan_rank = -nan_rank
        keys.extend([ranked, nan_rank, null_rank])
    order = np.lexsort(keys)
    qs = [int(total * (i + 1) / num_partitions)
          for i in range(num_partitions - 1)]
    bounds = []
    for q in np.clip(qs, 0, total - 1):
        row = order[q]
        bound = []
        for j in range(len(specs)):
            if not valids[j][row]:
                bound.append(None)
            else:
                v = cols[j][row]
                bound.append(v if isinstance(v, str) or v is None
                             else v.item() if hasattr(v, "item") else v)
        bounds.append(tuple(bound))
    return bounds


def sample_range_bounds(batch: ColumnarBatch, spec: SortKeySpec,
                        dtypes: List[dt.DType], num_partitions: int
                        ) -> np.ndarray:
    """Host-side bounds sampling (GpuRangePartitioner analogue). Returns
    boundary values in the key's own domain."""
    col = batch.columns[spec.ordinal]
    n = batch.realized_num_rows()
    values, validity = col.to_numpy(n)
    if validity is not None:
        values = values[validity]
    values = np.sort(values)
    if len(values) == 0 or num_partitions <= 1:
        return np.array([], dtype=object)
    qs = [int(len(values) * (i + 1) / num_partitions)
          for i in range(num_partitions - 1)]
    picks = values[np.clip(qs, 0, len(values) - 1)]
    return picks if spec.ascending else picks[::-1]


def sample_range_bounds_multi(staged, specs: List[SortKeySpec],
                              dtypes: List[dt.DType],
                              num_partitions: int,
                              max_sample: int = 100_000) -> np.ndarray:
    """Bounds from ALL staged (spillable) batches of an exchange input:
    sample up to ``max_sample`` key values across batches, sort, take
    equi-quantile cut points (the reference samples the child RDD the
    same way through Spark's RangePartitioner)."""
    spec = specs[0]
    t = dtypes[spec.ordinal]
    per_batch = max(max_sample // max(len(staged), 1), 1)
    samples = []
    rng = np.random.default_rng(0x5EED)
    for sb in staged:
        with sb.acquired() as b:
            col = b.columns[spec.ordinal]
            n = b.realized_num_rows()
            values, validity = col.to_numpy(n)
            values = np.asarray(values[:n])
            if validity is not None:
                values = values[np.asarray(validity[:n], dtype=bool)]
            if len(values) > per_batch:
                values = rng.choice(values, per_batch, replace=False)
            samples.append(values)
    if t is dt.STRING:
        values = np.concatenate([s.astype(object) for s in samples]) \
            if samples else np.array([], dtype=object)
        values = np.array(sorted(values, key=str), dtype=object)
    else:
        values = np.concatenate(samples) if samples else \
            np.array([], dtype=t.np_dtype)
        values = np.sort(values)
        if t.is_floating:
            # NaN sorts last in np.sort; keep them out of the cut points
            values = values[~np.isnan(values)]
    if len(values) == 0 or num_partitions <= 1:
        return np.array([], dtype=object)
    qs = [int(len(values) * (i + 1) / num_partitions)
          for i in range(num_partitions - 1)]
    picks = values[np.clip(qs, 0, len(values) - 1)]
    return picks if spec.ascending else picks[::-1]


def _pmod(h: jax.Array, n: int) -> jax.Array:
    m = h % jnp.int64(n)
    return jnp.where(m < 0, m + n, m).astype(jnp.int32)


def _split_by_pid(batch: ColumnarBatch, pid: jax.Array, num_partitions: int
                  ) -> Tuple[ColumnarBatch, np.ndarray]:
    datas = [c.data for c in batch.columns]
    validities = [c.validity for c in batch.columns]
    out_d, out_v, counts = _partition_kernel(
        datas, validities, pid, batch.num_rows_device(), num_partitions)
    cols = [c._like(d, v) for c, d, v in zip(batch.columns, out_d, out_v)]
    out = ColumnarBatch(cols, batch.num_rows)
    return out, np.asarray(jax.device_get(counts))


@partial(jax.jit, static_argnames=("num_partitions",))
def _partition_kernel(datas, validities, pid, num_rows, num_partitions: int):
    """Contiguous-split by partition id: the id is the one key lane
    (with the row index it is ONE sort operand up to 2,047 partitions of
    2,097,152 rows), the columns are gathered into its order, and
    per-partition counts come from binary searches over the sorted ids
    (no segment_sum scatter). A sort that carried the columns took the
    chip's compiler 352 s at 32,768 rows (PR 23; PERF.md section 6)."""
    capacity = pid.shape[0]
    live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
    # padding rows to a virtual partition that sorts last
    pid_l = jnp.where(live, pid, num_partitions)
    order, (pid_s,) = sortkeys.stable_order(
        [pid_l], bits=[num_partitions.bit_length()])
    bounds = jnp.searchsorted(
        pid_s, jnp.arange(num_partitions + 1, dtype=pid_s.dtype))
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int64)
    out_d, out_v = sortkeys.take_rows(order, datas, validities)
    return out_d, out_v, counts


def slice_partitions(batch: ColumnarBatch, counts: np.ndarray
                     ) -> List[Optional[ColumnarBatch]]:
    """Materialize each contiguous partition as its own (re-bucketed) batch;
    empty partitions yield None (the caching writer skips them,
    RapidsShuffleInternalManager.scala:120)."""
    counts = np.asarray(counts, dtype=np.int64)
    kept = np.nonzero(counts)[0]
    starts = np.cumsum(counts) - counts
    out: List[Optional[ColumnarBatch]] = [None] * len(counts)
    for p, sub in zip(kept, batch.slices(starts[kept], counts[kept])):
        out[p] = sub
    return out
