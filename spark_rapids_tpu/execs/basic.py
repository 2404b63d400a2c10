"""Basic execs: scan, project, filter, range, limit, union, expand, and the
CPU-fallback bridge (reference basicPhysicalOperators.scala,
GpuExpandExec.scala, and the transition execs)."""
from __future__ import annotations

import math
from typing import Iterator, List, Optional

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.execs import interop
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.expressions.base import Expression
from spark_rapids_tpu.expressions.compiler import (CompiledFilter,
                                                   CompiledProjection)
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.plan.nodes import DataSource
from spark_rapids_tpu.utils.tracing import TraceRange


class ScanExec(TpuExec):
    """Host read -> sliced device uploads (GpuFileSourceScanExec +
    the semaphore acquire before first device touch, GpuSemaphore.scala:106).
    Rows per upload slice come from the batch-size config. File sources
    with multiple splits expose them as scan partitions (the reference's
    FilePartition -> task mapping).

    The read itself runs through the bounded-depth async scan pipeline
    (io/scanpipe.py): chunk-granular reads (row groups / stripes) are
    re-sliced to exact batch_rows boundaries, packed on an IO thread,
    and double-buffered through device_put — slice k+1's transfer is in
    flight while the caller computes on slice k. Prefetch depth,
    pruning, and spillable landing come from the source's
    ``rapids.tpu.io.scan.*`` conf; depth 0 is the synchronous
    byte-identical reference path."""

    #: planner-set (fused.py): hand packed uploads to the consuming
    #: fused chain undecoded; the chain inlines the decode in-program
    defer_decode = False

    def __init__(self, source: DataSource, schema: Schema,
                 batch_rows: int = 1 << 20, pack: bool = True):
        super().__init__([], schema)
        self.source = source
        self.batch_rows = batch_rows
        self.pack = pack

    @property
    def num_partitions(self) -> int:
        return self.source.num_splits()

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.io import scanpipe

        return timed(self, scanpipe.scan_iter(self, partition))


class DeviceBatchesExec(TpuExec):
    """Serves pre-existing device batches without any host round trip
    (the InternalColumnarRddConverter ingestion path)."""

    def __init__(self, source, schema: Schema):
        super().__init__([], schema)
        self.source = source

    @property
    def num_partitions(self) -> int:
        return max(len(self.source.batches), 1)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            if not self.source.batches:
                yield ColumnarBatch.empty(self.schema)
                return
            yield self.source.batches[partition]
        return timed(self, it())


class ProjectExec(TpuExec):
    """One fused XLA computation per batch (GpuProjectExec,
    basicPhysicalOperators.scala:35-95)."""

    def __init__(self, exprs: List[Expression], child: TpuExec,
                 schema: Schema, conf=None):
        super().__init__([child], schema)
        self.projection = CompiledProjection(exprs, conf)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.expressions.nondeterministic import TaskInfo

        def it():
            row_base = 0
            for b in self.children[0].execute(partition):
                ti = TaskInfo.make(partition, row_base)
                with TraceRange("ProjectExec"):
                    out = self.projection(b, task_info=ti)
                row_base += b.realized_num_rows()
                yield out
        return timed(self, it())


class FilterExec(TpuExec):
    """Mask + compact in one jitted kernel (GpuFilterExec,
    basicPhysicalOperators.scala:100-130)."""

    def __init__(self, condition: Expression, child: TpuExec, conf=None):
        super().__init__([child], child.schema)
        self.filter = CompiledFilter(condition, conf)

    def __getstate__(self):
        # the mesh layer may cache a compiled sharded filter step on
        # this exec (parallel/execs._apply_mesh_filter); it holds live
        # Device handles and must not ship in cluster task closures
        state = dict(self.__dict__)
        state.pop("_mesh_filter_step", None)
        return state

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.expressions.nondeterministic import TaskInfo

        def it():
            row_base = 0
            for b in self.children[0].execute(partition):
                ti = TaskInfo.make(partition, row_base)
                with TraceRange("FilterExec"):
                    out = self.filter(b, task_info=ti)
                # a filter keeps file provenance (Spark's
                # input_file_name still works below a filter)
                out.origin = b.origin
                row_base += b.realized_num_rows()
                yield out
        return timed(self, it())


class RangeExec(TpuExec):
    """Generates batches on device (GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int, schema: Schema,
                 batch_rows: int = 1 << 20):
        super().__init__([], schema)
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            total = max(0, math.ceil((self.end - self.start) / self.step))
            if total == 0:
                yield ColumnarBatch.empty(self.schema)
                return
            for off in range(0, total, self.batch_rows):
                cnt = min(self.batch_rows, total - off)
                lo = self.start + off * self.step
                vals = np.arange(
                    lo, lo + cnt * self.step, self.step, dtype=np.int64)
                yield ColumnarBatch(
                    [Column.from_numpy(vals, dtype=dt.INT64)], cnt)
        return timed(self, it())


class LocalLimitExec(TpuExec):
    """Slices batches until n rows have been emitted (per partition).
    Span ``LocalLimitExec.limit``, one a batch: the fetch of its row count
    (a top-N's wait for the sorted batch lands here) and the cut."""

    def __init__(self, n: int, child: TpuExec):
        super().__init__([child], child.schema)
        self.n = n

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            remaining = self.n
            for b in self.children[0].execute(partition):
                if remaining <= 0:
                    break
                with TraceRange("LocalLimitExec.limit"):
                    rows = b.realized_num_rows()
                    if rows > remaining:
                        b = b.slice(0, remaining)
                remaining -= min(rows, remaining)
                yield b
        return timed(self, it())


class UnionExec(TpuExec):
    """Concatenates children lazily (GpuOverrides.scala:1777-1833 union).
    Child partition counts may differ; partitions are concatenated
    child-major."""

    def __init__(self, children: List[TpuExec], schema: Schema):
        super().__init__(children, schema)

    @property
    def num_partitions(self) -> int:
        return sum(c.num_partitions for c in self.children)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            p = partition
            for c in self.children:
                if p < c.num_partitions:
                    yield from c.execute(p)
                    return
                p -= c.num_partitions
            raise IndexError(partition)
        return timed(self, it())


class ExpandExec(TpuExec):
    """Per input batch, evaluate each projection then interleave row-major
    — Spark's ExpandExec/explode emission order, one output row per
    (input row, projection) pair (GpuExpandExec.scala)."""

    def __init__(self, projections: List[List[Expression]], child: TpuExec,
                 schema: Schema, conf=None):
        super().__init__([child], schema)
        self.projections = [CompiledProjection(p, conf)
                            for p in projections]

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.ops.concat import interleave_batches

        def it():
            for b in self.children[0].execute(partition):
                parts = [proj(b) for proj in self.projections]
                with TraceRange("ExpandExec.interleave"):
                    out = interleave_batches(parts)
                yield out
        return timed(self, it())


class CoalescePartitionsExec(TpuExec):
    """Maps n output partitions onto contiguous groups of child
    partitions — no data movement beyond sequential reads."""

    def __init__(self, num_partitions: int, child: TpuExec):
        super().__init__([child], child.schema)
        self._n = num_partitions

    @property
    def num_partitions(self) -> int:
        return min(self._n, max(self.children[0].num_partitions, 1))

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            child_n = self.children[0].num_partitions
            n = self.num_partitions
            per = -(-child_n // n)
            lo = partition * per
            hi = min(lo + per, child_n)
            empty = True
            for p in range(lo, hi):
                for b in self.children[0].execute(p):
                    if b.realized_num_rows() == 0:
                        continue
                    empty = False
                    yield b
            if empty:
                yield ColumnarBatch.empty(self.schema)
        return timed(self, it())


class CpuFallbackExec(TpuExec):
    """Executes a plan subtree on the CPU engine and uploads the result —
    the planner inserts this around nodes that can't go on TPU, with the
    tag reasons recorded (the reference's convertIfNeeded keeps such
    subtrees as CPU Spark plans, RapidsMeta.scala:600-615)."""

    def __init__(self, plan_node, schema: Schema, reasons: List[str],
                 tpu_children: Optional[List[TpuExec]] = None,
                 batch_rows: int = 1 << 20):
        super().__init__(tpu_children or [], schema)
        self.plan_node = plan_node
        self.reasons = reasons
        self.batch_rows = batch_rows

    @property
    def num_partitions(self) -> int:
        return 1

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.cpu.engine import execute_cpu

        def it():
            frame = execute_cpu(self.plan_node)
            n = frame.num_rows
            if n == 0:
                yield interop.frame_to_batch(frame)
                return
            for start in range(0, n, self.batch_rows):
                end = min(start + self.batch_rows, n)
                idx = np.arange(start, end)
                yield interop.frame_to_batch(frame.take(idx))
        return timed(self, it())
