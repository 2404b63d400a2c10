"""Sharded filter: mask + per-chip compaction over a DistributedBatch.

A FilterExec between two mesh execs would otherwise sever the sharded
hand-off (the chain gathers to host, filters, re-shards — exactly the
round trip the hand-off design removes). Filters are embarrassingly
parallel: the condition evaluates per chip with the SAME expression
evaluator the single-device compiled filter uses (expressions/compiler
EvalContext), then one stable order of the keep flag per chip
(ops/sortkeys.stable_order) and a gather a column compact kept rows to the
live prefix (the scatter-free compaction idiom of parallel/shuffle.py).
No collectives at all — rows never change chips.

Only deterministic device-only conditions lower here; nondeterministic
ones (rand) keep the single-device path where TaskInfo row bases are
well-defined.
"""
from __future__ import annotations

from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_tpu.shims import get_shims


class DistributedFilterStep:
    """Compiled per-chip mask + compact for one (condition, dtypes)."""

    def __init__(self, mesh: Mesh, dtypes: Sequence[dt.DType], condition,
                 axis: str = DATA_AXIS):
        self.mesh = mesh
        self.dtypes = tuple(dtypes)
        self.condition = condition
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    def _build(self):
        dtypes = self.dtypes
        condition = self.condition

        def device_step(datas, valids, n_rows):
            from spark_rapids_tpu.expressions.compiler import (ColV,
                                                               EvalContext,
                                                               broadcast)
            from spark_rapids_tpu.expressions.nondeterministic import \
                TaskInfo

            cap = datas[0].shape[0]
            cols = [ColV(t, d, v)
                    for t, d, v in zip(dtypes, datas, valids)]
            ctx = EvalContext(cols, cap, n_rows[0], in_jit=True,
                              task_info=TaskInfo.make())
            v = broadcast(condition.eval(ctx), ctx)
            keep = v.data if v.validity is None else (v.data & v.validity)
            iota = jnp.arange(cap, dtype=jnp.int32)
            keep = keep & (iota < n_rows[0])
            order, _ = sortkeys.stable_order([~keep])
            new_n = jnp.sum(keep).astype(jnp.int32)
            out_d, out_v = sortkeys.take_rows(order, datas, valids)
            out_v = [vv & (iota < new_n) for vv in out_v]
            return out_d, out_v, new_n.reshape(1)

        n_cols = len(self.dtypes)
        in_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                    P(self.axis))
        out_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                     P(self.axis))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, datas: List[jax.Array], valids: List[jax.Array],
                 counts: jax.Array):
        return self._fn(datas, valids, counts)
