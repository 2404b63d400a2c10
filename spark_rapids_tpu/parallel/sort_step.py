"""Distributed global sort over the device mesh.

The reference's distributed ORDER BY: sample range bounds on the driver,
range-partition through the shuffle, locally sort each range
(GpuRangePartitioner.scala:42-95 + GpuSortExec). TPU-native: the whole
pipeline is ONE compiled program per chip —

  1. per row, build an order-preserving f64 ROUTING LANE for the primary
     sort key (nulls/NaN mapped to ±inf per the spec's null ordering;
     descending negates; integer→f64 rounding is monotone, so ties can
     only merge onto one chip, never reorder),
  2. every chip samples its lane at fixed stride; one all_gather shares
     the samples; all chips derive IDENTICAL quantile bounds,
  3. rows route via lax.all_to_all (parallel/shuffle._exchange),
  4. each chip runs the full lexicographic local sort
     (ops/sortkeys.lexsort_indices, then one gather a column) on its
     range.

Chip order == global order: concatenating shard prefixes in device order
yields the sorted relation, with primary-key ties wholly inside one chip
so multi-key lexicographic order holds globally.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.shims import get_shims

_SAMPLES_PER_CHIP = 64


def _routing_lane(data, validity, dtype: dt.DType, spec: SortKeySpec,
                  live) -> jax.Array:
    """f64 lane whose ascending order == the spec's order. Dead rows to
    +inf (they park on the last chip and die there)."""
    if dtype.is_floating:
        x = sortkeys.canonicalize_floats(data).astype(jnp.float64)
        nanv = jnp.inf if spec.ascending else -jnp.inf
        x = jnp.where(jnp.isnan(x), nanv,
                      x if spec.ascending else -x)
    else:
        x = data.astype(jnp.float64)
        if not spec.ascending:
            x = -x
    if validity is not None:
        nullv = -jnp.inf if spec.nulls_first else jnp.inf
        if not spec.ascending:
            pass  # null placement is absolute, not direction-relative
        x = jnp.where(validity, x, nullv)
    return jnp.where(live, x, jnp.inf)


class DistributedSortStep:
    def __init__(self, mesh, dtypes: Sequence[dt.DType],
                 specs: Sequence[SortKeySpec], axis: str = DATA_AXIS):
        self.mesh = mesh
        self.dtypes = tuple(dtypes)
        self.specs = tuple(specs)
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    def _build(self):
        from spark_rapids_tpu.parallel.shuffle import _exchange

        n_dev = self.n_dev
        axis = self.axis
        dtypes = self.dtypes
        specs = self.specs
        k = _SAMPLES_PER_CHIP

        def device_step(datas, valids, n_rows):
            cap = datas[0].shape[0]
            iota = jnp.arange(cap, dtype=jnp.int32)
            live = iota < n_rows[0]
            s0 = specs[0]
            lane = _routing_lane(datas[s0.ordinal], valids[s0.ordinal],
                                 dtypes[s0.ordinal], s0, live)

            # fixed-stride sample of the live prefix; empty slots +inf
            idx = jnp.clip((jnp.arange(k) *
                            jnp.maximum(n_rows[0], 1)) // k, 0, cap - 1)
            samp = jnp.where(jnp.arange(k) < jnp.minimum(n_rows[0], k),
                             jnp.take(lane, idx), jnp.inf)
            allsamp = jax.lax.all_gather(samp, axis).reshape(-1)
            ssorted = jnp.sort(allsamp)
            total_s = allsamp.shape[0]
            # n_dev-1 interior quantile bounds over the finite samples
            nfin = jnp.sum(jnp.isfinite(ssorted)).astype(jnp.int32)
            nfin = jnp.maximum(nfin, 1)
            qpos = jnp.clip(
                (jnp.arange(1, n_dev) * nfin) // n_dev, 0, total_s - 1)
            bounds = jnp.take(ssorted, qpos)

            dest = jnp.searchsorted(bounds, lane,
                                    side="right").astype(jnp.int32)
            dest = jnp.clip(dest, 0, n_dev - 1)
            ex_d, ex_v, total = _exchange(list(datas), list(valids),
                                          dest, live, n_dev, axis)
            # local full lexicographic sort on this chip's range
            order = sortkeys.lexsort_indices(
                list(zip(ex_d, ex_v)), list(dtypes), list(specs), total)
            out_d, out_v = sortkeys.take_rows(order, ex_d, ex_v)
            rcap = ex_d[0].shape[0]
            riota = jnp.arange(rcap, dtype=jnp.int32)
            out_v = [v & (riota < total) for v in out_v]
            return out_d, out_v, total.reshape(1)

        n_cols = len(dtypes)
        in_specs = ([P(axis)] * n_cols, [P(axis)] * n_cols, P(axis))
        out_specs = ([P(axis)] * n_cols, [P(axis)] * n_cols, P(axis))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, datas, valids, counts):
        """Row-sharded columns in, RANGE-sorted shards out: device d's
        live prefix holds the d-th global range, locally sorted."""
        return self._fn(datas, valids, counts)
