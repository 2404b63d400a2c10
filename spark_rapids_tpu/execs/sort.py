"""Sort exec (GpuSortExec.scala:50, GpuColumnarBatchSorter :104).

Local sort: per-batch device lexsort. Global sort within one partition:
coalesce-to-one + one device lexsort while the data fits the sort
budget; beyond it, a RANGE-BUCKETED OUT-OF-CORE path (SURVEY §5.7's
mandate not to replicate the RequireSingleBatch cliff):

  1. stage incoming batches as spillable chunks (catalog-managed, so
     they can leave HBM under pressure),
  2. sample range bounds across the staged chunks host-side (the
     reference's CPU-sampled-bounds design, GpuRangePartitioner.scala:
     42-95) with enough buckets that each fits the budget,
  3. range-partition each chunk on device, regrouping slices per bucket
     (slices stay spillable until their bucket runs),
  4. concat + device-sort one bucket at a time, yielding buckets in
     bound order — the output stream is globally ordered without any
     single resident batch exceeding the budget.

TPU note: buckets are sorted independently (one sort program per
bucket at a bounded shape) — there is no k-way merge kernel to keep
resident; order across buckets comes from the range partitioning.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.ops.sort import sort_batch
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.utils.tracing import TraceRange


class SortExec(TpuExec):
    def __init__(self, specs: List[SortKeySpec], child: TpuExec,
                 global_sort: bool = True,
                 batch_bytes: Optional[int] = None,
                 sort_budget_rows: Optional[int] = None):
        super().__init__([child], child.schema)
        self.specs = specs
        self.global_sort = global_sort
        self.batch_bytes = batch_bytes
        self.sort_budget_rows = sort_budget_rows

    def _budget_rows(self) -> int:
        """THE budget formula (planner passes only the configured batch
        bytes; tests may pin rows directly)."""
        if self.sort_budget_rows is not None:
            return max(self.sort_budget_rows, 1)
        from spark_rapids_tpu import config as cfg

        bb = self.batch_bytes if self.batch_bytes is not None \
            else cfg.BATCH_SIZE_BYTES.default
        row_bytes = max(sum(t.byte_width for t in self.schema.types), 1)
        return max(bb // row_bytes, 1 << 16)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        types = list(self.schema.types)

        def it():
            if not self.global_sort:
                for b in self.children[0].execute(partition):
                    with TraceRange("SortExec.local"):
                        out = sort_batch(b, self.specs, types)
                    yield out
                return
            from spark_rapids_tpu.memory import priorities
            from spark_rapids_tpu.memory.retry import with_retry_no_split
            from spark_rapids_tpu.memory.spillable import SpillableBatch

            budget = self._budget_rows()
            # stage AS batches arrive: everything drained so far can
            # spill while later child batches still compute — the input
            # is never pinned whole in HBM. Counts stay LAZY while
            # staging (defer_count): when the whole input provably fits
            # the in-core budget by CAPACITY (capacity >= rows), the
            # single-batch fast path sorts without any host sync at
            # all, and the multi-batch path realizes every count in the
            # one batched get concat already pays — the per-batch
            # realize here used to cost one host sync each
            caps = 0
            staged: List[SpillableBatch] = []
            for b in self.children[0].execute(partition):
                caps += b.capacity
                staged.append(SpillableBatch(
                    b, priorities.INPUT_FROM_SHUFFLE_PRIORITY,
                    defer_count=True))
            if not staged:
                yield ColumnarBatch.empty(self.schema)
                return
            def sort_in_core(handles):
                from contextlib import ExitStack

                from spark_rapids_tpu.ops.concat import concat_batches

                with ExitStack() as stack:
                    parts = [stack.enter_context(sb.acquired())
                             for sb in handles]
                    with TraceRange("SortExec.global"):
                        # output contract is ONE globally sorted batch:
                        # spill rungs only (sorted halves would need a
                        # merge kernel the TPU path deliberately lacks)
                        merged = parts[0] if len(parts) == 1 else \
                            with_retry_no_split(
                                lambda: concat_batches(parts),
                                tag="sort.concat")
                        out = with_retry_no_split(
                            lambda: sort_batch(merged, self.specs,
                                               types),
                            tag="sort.sort")
                for sb in handles:
                    sb.close()
                return out

            if caps <= budget:
                yield sort_in_core(staged)
                return
            # above the capacity bound: realize every count in ONE
            # batched transfer, drop empties, and re-check the real
            # total (capacity over-estimates rows)
            SpillableBatch.realize_counts(staged)
            total = 0
            live: List[SpillableBatch] = []
            for sb in staged:
                n = sb.num_rows
                if n == 0:
                    sb.close()
                    continue
                total += n
                live.append(sb)
            staged = live
            if not staged:
                yield ColumnarBatch.empty(self.schema)
                return
            if total <= budget:
                yield sort_in_core(staged)
                return
            yield from self._out_of_core(staged, total, budget, types)

        return timed(self, it())

    def _out_of_core(self, staged, total: int, budget: int,
                     types) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.memory.spillable import SpillableBatch
        from spark_rapids_tpu.ops import partition as part_ops
        from spark_rapids_tpu.ops.concat import concat_batches

        # 2x margin absorbs sampling error; heavy key skew can still
        # overfill one bucket — the oom-retry spill path covers that
        n_buckets = max(-(-total // budget) * 2, 2)
        if len(self.specs) > 1:
            bounds = part_ops.sample_range_bounds_rows(
                staged, self.specs, types, n_buckets)
        else:
            bounds = part_ops.sample_range_bounds_multi(
                staged, self.specs, types, n_buckets)
        per_bucket: List[List[SpillableBatch]] = \
            [[] for _ in range(n_buckets)]
        for sb in staged:
            with sb.acquired() as b:
                with TraceRange("SortExec.oob.partition"):
                    if len(self.specs) > 1:
                        sorted_b, counts = part_ops.range_partition_multi(
                            b, self.specs, types, bounds, n_buckets)
                    else:
                        sorted_b, counts = part_ops.range_partition(
                            b, self.specs, types, bounds, n_buckets)
                    slices = part_ops.slice_partitions(sorted_b, counts)
                for p, sl in enumerate(slices):
                    if sl is not None:
                        per_bucket[p].append(SpillableBatch(
                            sl, priorities.OUTPUT_FOR_SHUFFLE_PRIORITY))
            sb.close()
        from contextlib import ExitStack

        for p in range(n_buckets):
            handles = per_bucket[p]
            if not handles:
                continue
            # handles stay ACQUIRED through concat+sort: releasing
            # early would let the oom-retry spill copy them to host
            # while `parts` still pins the device arrays (no memory
            # actually freed, catalog accounting corrupted)
            with ExitStack() as stack:
                parts = [stack.enter_context(h.acquired())
                         for h in handles]
                with TraceRange("SortExec.oob.bucket"):
                    merged = parts[0] if len(parts) == 1 else \
                        with_retry_no_split(
                            lambda: concat_batches(parts),
                            tag="sort.oob.concat")
                    out = with_retry_no_split(
                        lambda: sort_batch(merged, self.specs, types),
                        tag="sort.oob.sort")
            for h in handles:
                h.close()
            yield out
