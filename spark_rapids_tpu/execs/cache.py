"""Cached (persisted) datasets.

The reference routes ``.cache()`` through Spark's in-memory columnar
cache with host transitions (docs/FAQ.md:121); TPU-native caching is
strictly better-integrated: the materialized batches register with the
spill catalog as spillable buffers, so a cached DataFrame lives in HBM
while it fits and degrades through host/disk tiers under pressure —
identical machinery to shuffle blocks and broadcast tables."""
from __future__ import annotations

import threading
from contextlib import ExitStack
from spark_rapids_tpu.utils import lockorder
from typing import Dict, Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.memory import priorities
from spark_rapids_tpu.memory.catalog import set_buffer_owner
from spark_rapids_tpu.memory.spillable import SpillableBatch
from spark_rapids_tpu.plan.nodes import PlanNode
from spark_rapids_tpu.utils.tracing import TraceRange, count


class CacheNode(PlanNode):
    """Plan marker carrying a shared CacheHolder so repeated plans over
    the same cached DataFrame reuse one materialization."""

    def __init__(self, child: PlanNode):
        super().__init__([child])
        self.holder = CacheHolder()

    def output_schema(self) -> Schema:
        return self.children[0].output_schema()

    def describe(self) -> str:
        state = "materialized" if self.holder.is_materialized \
            else "lazy"
        return f"Cache[{state}]"


class CacheHolder:
    """Partition -> spillable batches, filled once."""

    def __init__(self):
        self._lock = lockorder.make_lock("execs.cache.materialize")
        self._parts: Optional[Dict[int, List[SpillableBatch]]] = None

    @property
    def is_materialized(self) -> bool:
        return self._parts is not None

    def materialize(self, child: TpuExec) -> None:
        with self._lock:
            if self._parts is not None:
                return
            parts: Dict[int, List[SpillableBatch]] = {}
            with TraceRange("cache.fill"):
                for p in range(child.num_partitions):
                    handles = []
                    for b in child.execute(p):
                        rows = b.realized_num_rows()
                        if rows == 0:
                            continue
                        # the cache outlives the query that fills it: no
                        # query's owner tag on its registrations (the
                        # service sweeps a query's tag when the query ends)
                        prev = set_buffer_owner(None)
                        try:
                            with TraceRange("cache.register"):
                                handles.append(SpillableBatch(
                                    b,
                                    priorities.INPUT_FROM_SHUFFLE_PRIORITY))
                        finally:
                            set_buffer_owner(prev)
                        count("cache.fill.rows", rows)
                        count("cache.fill.batches")
                    parts[p] = handles
            self._parts = parts

    def num_partitions(self) -> int:
        assert self._parts is not None
        return max(len(self._parts), 1)

    def batches(self, partition: int):
        assert self._parts is not None
        return self._parts.get(partition, [])

    def unpersist(self) -> None:
        with self._lock:
            if self._parts is None:
                return
            for handles in self._parts.values():
                for h in handles:
                    h.close()
            self._parts = None


class CachedExec(TpuExec):
    def __init__(self, node: CacheNode, child: TpuExec):
        super().__init__([child], child.schema)
        self.node = node

    @property
    def num_partitions(self) -> int:
        if self.node.holder.is_materialized:
            return self.node.holder.num_partitions()
        return self.children[0].num_partitions

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            self.node.holder.materialize(self.children[0])
            handles = self.node.holder.batches(partition)
            if not handles:
                yield ColumnarBatch.empty(self.schema)
                return
            for h in handles:
                with ExitStack() as held:
                    with TraceRange("CachedExec.acquire"):
                        batch = held.enter_context(h.acquired())
                    yield batch
        return timed(self, it())
