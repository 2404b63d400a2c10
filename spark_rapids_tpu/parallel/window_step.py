"""Distributed window functions over the device mesh.

The reference runs windows per shuffle partition on-device
(GpuWindowExec.scala:92: partition-by keys hash-exchange upstream, then
each GPU batch computes its partitions' windows). The TPU shape fuses
those two stages into ONE compiled program per chip, exactly like the
distributed groupby (parallel/shuffle.py):

  1. hash the PARTITION BY columns -> destination chip per row,
  2. ``lax.all_to_all`` the rows (scatter-free: one stable order by
     destination, a gather a column into send blocks),
  3. per chip: one stable order by (partition keys, order keys), then
     the same segmented-scan ``WindowKernel`` the single-device exec
     runs (execs/window.py) — row_number/rank/lead/lag/frames all ride
     segment arithmetic, so the per-chip math is identical.

Hash routing puts every row of a partition-by group on one chip, so the
distributed result is exact with no merge stage. Rows come back grouped
by partition-key hash, not globally ordered — same contract as the
reference's post-shuffle window output.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.execs.window import WindowCall, WindowKernel
from spark_rapids_tpu.ops import hashing, sortkeys
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.parallel.shuffle import _exchange, _key_image
from spark_rapids_tpu.shims import get_shims


class DistributedWindowStep:
    """Compiled multi-chip window: route by partition keys, per-chip
    sort + segmented window kernel. Output columns are the child
    columns followed by one column per call; per-chip live counts ride
    back sharded."""

    def __init__(self, mesh: Mesh, pre_types: Sequence[dt.DType],
                 partition_ordinals: Sequence[int],
                 order_specs: Sequence[SortKeySpec],
                 calls: Sequence[WindowCall],
                 input_ordinals: Sequence[int], n_child: int,
                 axis: str = DATA_AXIS):
        assert partition_ordinals, \
            "un-partitioned windows are single-device by construction"
        self.mesh = mesh
        self.pre_types = tuple(pre_types)
        self.partition_ordinals = tuple(partition_ordinals)
        self.order_specs = tuple(order_specs)
        self.calls = tuple(calls)
        self.input_ordinals = tuple(input_ordinals)
        self.n_child = n_child
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self.kernel = WindowKernel(list(pre_types),
                                   list(partition_ordinals),
                                   list(order_specs), list(calls),
                                   list(input_ordinals))
        self._fn = self._build()

    def _build(self):
        n_dev = self.n_dev
        pre_types = self.pre_types
        part_ords = self.partition_ordinals
        axis = self.axis
        sort_specs = tuple(SortKeySpec(o, True, True)
                           for o in part_ords) + self.order_specs
        kernel = self.kernel
        n_child = self.n_child

        def device_step(datas, valids, n_rows):
            cap = datas[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < n_rows[0]
            imgs = tuple(_key_image(datas[o], valids[o], pre_types[o])
                         for o in part_ords)
            h = hashing._combine(imgs)
            dest = ((jax.lax.rem(h, jnp.int64(n_dev)) + jnp.int64(n_dev))
                    % jnp.int64(n_dev)).astype(jnp.int32)
            ex_d, ex_v, total = _exchange(list(datas), list(valids), dest,
                                          live, n_dev, axis)
            order = sortkeys.lexsort_indices(
                list(zip(ex_d, ex_v)), list(pre_types), list(sort_specs),
                total)
            cols = [Column(t, d, v) for t, d, v in
                    zip(pre_types, *sortkeys.take_rows(order, ex_d, ex_v))]
            call_cols = kernel(cols, total)
            out_cols = cols[:n_child] + call_cols
            rcap = n_dev * cap
            live_out = jnp.arange(rcap, dtype=jnp.int32) < total
            out_d = [c.data for c in out_cols]
            out_v = [c.validity_or_true() & live_out for c in out_cols]
            return out_d, out_v, total.reshape(1)

        n_cols = len(self.pre_types)
        n_out = self.n_child + len(self.calls)
        in_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                    P(self.axis))
        out_specs = ([P(self.axis)] * n_out, [P(self.axis)] * n_out,
                     P(self.axis))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, datas: List[jax.Array], valids: List[jax.Array],
                 counts: jax.Array):
        """datas[i]: (n_dev*cap,) row-sharded pre-projected columns.
        Returns (out_datas, out_valids, per_chip_counts)."""
        return self._fn(datas, valids, counts)

    def output_dtypes(self) -> List[dt.DType]:
        out = list(self.pre_types[:self.n_child])
        for c, io in zip(self.calls, self.input_ordinals):
            out.append(_call_dtype(c, self.pre_types, io))
        return out


def _call_dtype(c: WindowCall, pre_types, inp_ord: int) -> dt.DType:
    from spark_rapids_tpu.expressions.aggregates import (AggregateFunction,
                                                         Average, Count)

    if c.fn in ("row_number", "rank", "dense_rank"):
        return dt.INT32
    if isinstance(c.fn, tuple):
        return pre_types[inp_ord]
    assert isinstance(c.fn, AggregateFunction)
    if isinstance(c.fn, Count):
        return dt.INT64
    if isinstance(c.fn, Average):
        return dt.FLOAT64
    return c.fn.dtype
