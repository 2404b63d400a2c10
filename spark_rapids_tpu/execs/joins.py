"""Join execs.

Reference: GpuHashJoin (shims/spark300/.../GpuHashJoin.scala:302-318) builds
one side, streams the other through cuDF join kernels; conditions are
post-join filters (:285-291); SMJ is replaced by shuffled hash join
(GpuSortMergeJoinExec.scala). TPU equivalents use the sort-probe equi-join
kernel (ops/join.py) — no device hash tables, XLA sorts instead.

- BroadcastHashJoinExec: build side fully materialized (whole child), probe
  side streamed per batch. Safe for inner/left/semi/anti with a right
  build; full joins need both sides whole.
- ShuffledHashJoinExec: same kernel after both sides were hash-partitioned
  by an exchange, per-partition build.
- Conditioned outer joins fall back at the planner (the kernel applies
  conditions post-join, valid only for inner/cross).
"""
from __future__ import annotations

from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.execs.batching import RequireSingleBatch
from spark_rapids_tpu.expressions.base import Expression
from spark_rapids_tpu.expressions.compiler import CompiledFilter
from spark_rapids_tpu.ops.join import (PreparedBuild, cross_join, equi_join,
                                       nested_loop_join, prepare_build,
                                       probe_rounds)
from spark_rapids_tpu.utils.tracing import TraceRange, count, recording

_KIND_MAP = {"inner": "inner", "left": "left", "left_semi": "leftsemi",
             "left_anti": "leftanti", "full": "full"}


class HashJoinExec(TpuExec):
    """Build-side = children[1] (right); streams children[0] (left).
    ``right`` joins are planned as flipped ``left`` joins by the planner
    (Spark310-style buildSide handling lives there too).

    Out-of-core (SURVEY §5.7): a build side that exceeds the batch
    budget is NOT funneled into one device batch (the reference's
    RequireSingleBatch cliff, GpuCoalesceBatches.scala:91-127). Both
    sides hash-bucket by join key into spillable slices (matching rows
    share a bucket by construction) and each bucket joins independently
    at a bounded size — the sort exec's range-bucket pattern applied to
    the join build.

    Spans, once a partition: ``HashJoinExec.build`` (the build side
    staged, concatenated and prepared) over ``HashJoinExec.buildStage``
    (the build child drained into spillable chunks; an exchange's map
    side runs under the first partition's unless an adaptive join
    materialized it to decide) and ``HashJoinExec.buildPrepare``
    (concat, key range, hash and sort);
    then ``HashJoinExec.<kind>`` a stream batch. Counters, a partition
    each: ``join.build.hash``, ``join.build.dense``, ``join.oob`` (which
    way the build went), ``join.build_rows``; a stream batch each:
    ``join.probe_rows``, and ``join.out_rows`` only where an output's
    count is on the host already: an inner join's stays on the device
    (``_compact_pairs``) and is left out, no fetch is made for it. A
    hash build each, after the partition's last probe and only while
    spans are recorded (it is the one fetch a counter costs):
    ``join.probe.rounds``, the halvings a probe of that build made,
    against ``join.probe.rounds_full``, those of one search of the
    whole build."""

    def __init__(self, kind: str, left: TpuExec, right: TpuExec,
                 left_keys: List[int], right_keys: List[int],
                 schema: Schema, condition: Optional[Expression] = None,
                 conf=None, join_budget_rows: Optional[int] = None):
        super().__init__([left, right], schema)
        assert kind in _KIND_MAP, kind  # cross -> nested-loop/cartesian
        if condition is not None:
            assert kind == "inner", \
                "conditioned outer joins must fall back (planner bug)"
        self.kind = kind
        self.left_keys = left_keys
        self.right_keys = right_keys
        self.condition = CompiledFilter(condition, conf) \
            if condition is not None else None
        self.join_budget_rows = join_budget_rows
        # (max_span, min_density, min_rows) — AdaptiveShuffledJoinExec
        # attaches this to arm the hash->dense probe upgrade; None (the
        # default everywhere else) keeps the probe strictly hash-based
        self._dense_spec = None
        self._batch_bytes = None
        if conf is not None:
            from spark_rapids_tpu import config as cfg

            self._batch_bytes = conf.get(cfg.BATCH_SIZE_BYTES)

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    @property
    def children_coalesce_goal(self):
        # neither side needs a single batch any more: the exec stages
        # incoming batches spillably and buckets them itself
        return [None, None]

    def _budget_rows(self) -> int:
        """Rows of ONE side the in-core path may hold resident (the
        sort exec's budget formula over the build schema)."""
        if self.join_budget_rows is not None:
            return max(self.join_budget_rows, 1)
        from spark_rapids_tpu import config as cfg

        bb = self._batch_bytes if self._batch_bytes is not None \
            else cfg.BATCH_SIZE_BYTES.default
        row_bytes = max(sum(t.byte_width
                            for t in self.children[1].schema.types), 1)
        return max(bb // row_bytes, 1 << 16)

    def _stage(self, child_index: int, partition: int):
        """Drain one child into spillable chunks (staged chunks can
        leave HBM while later child batches still compute)."""
        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.spillable import SpillableBatch

        staged: List = []
        total = 0
        for b in self.children[child_index].execute(partition):
            n = b.realized_num_rows()
            if n == 0:
                continue
            total += n
            staged.append(SpillableBatch(
                b, priorities.INPUT_FROM_SHUFFLE_PRIORITY))
        return staged, total

    @staticmethod
    def _concat_staged(staged, schema) -> ColumnarBatch:
        from contextlib import ExitStack

        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.ops.concat import concat_batches

        if not staged:
            return ColumnarBatch.empty(schema)
        with ExitStack() as stack:
            parts = [stack.enter_context(sb.acquired()) for sb in staged]
            merged = parts[0] if len(parts) == 1 else \
                with_retry_no_split(lambda: concat_batches(parts),
                                    tag="join.build.concat")
        for sb in staged:
            sb.close()
        return merged

    def _probe_retry(self, b: ColumnarBatch, build: ColumnarBatch,
                     left_types, right_types, tag: str, prepared=None):
        """Probe one stream batch under split-and-retry: the stream
        side halves freely for every kind except full (a full join
        emits unmatched BUILD rows once per probe call, so its single
        stream batch must stay whole). Returns one output per final
        sub-batch. ``prepared`` is the build-once/probe-many state
        shared across stream batches (constant under stream splits)."""
        from spark_rapids_tpu.memory import retry as _retry

        split = _retry.halve_batch if self.kind != "full" else None
        outs = _retry.with_retry(
            b,
            lambda bb: equi_join(bb, build, self.left_keys,
                                 self.right_keys, left_types,
                                 right_types,
                                 join_type=_KIND_MAP[self.kind],
                                 prepared=prepared)[0],
            split=split, tag=tag)
        if self.condition is not None:
            outs = [self.condition(out) for out in outs]
        return outs

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        left_types = list(self.children[0].schema.types)
        right_types = list(self.children[1].schema.types)

        def it():
            budget = self._budget_rows()
            build_staged, build_total, build, prepared = self._build(
                partition, budget, left_types, right_types)
            if build is None:
                yield from self._out_of_core(partition, build_staged,
                                             build_total, budget,
                                             left_types, right_types)
                return
            if self.kind == "full":
                # unmatched-build rows are emitted exactly once, so the
                # stream side must arrive as one batch
                stream_staged, _n = self._stage(0, partition)
                stream_batches = [self._concat_staged(
                    stream_staged, self.children[0].schema)]
            else:
                stream_batches = self.children[0].execute(partition)
            saw = False
            for b in stream_batches:
                rows = b.realized_num_rows()
                if rows == 0 and saw:
                    continue
                saw = True
                count("join.probe_rows", rows)
                with TraceRange(f"HashJoinExec.{self.kind}"):
                    outs = self._probe_retry(b, build, left_types,
                                             right_types,
                                             tag="join.probe",
                                             prepared=prepared)
                self._count_out(outs)
                yield from outs
            if recording() and isinstance(prepared, PreparedBuild):
                rounds, full = probe_rounds(prepared)
                count("join.probe.rounds", rounds)
                count("join.probe.rounds_full", full)
        return timed(self, it())

    def _build(self, partition: int, budget: int, left_types, right_types):
        """One partition's build side: (staged, rows, build, prepared),
        ``build`` None where the rows pass ``budget`` and the join goes
        bucket by bucket (the staged chunks are then the caller's)."""
        with TraceRange("HashJoinExec.build"):
            with TraceRange("HashJoinExec.buildStage"):
                staged, total = self._stage(1, partition)
            count("join.build_rows", total)
            if total > budget:
                count("join.oob")
                return staged, total, None, None
            with TraceRange("HashJoinExec.buildPrepare"):
                build = self._concat_staged(staged,
                                            self.children[1].schema)
                # build-once/probe-many: hash + sort a single time,
                # reused by every stream batch (None when a join key is
                # a string column). With the AQE dense hint armed, a
                # measured-narrow key range upgrades the probe to a
                # direct slot lookup instead.
                prepared = self._dense_prepared(build, left_types,
                                                right_types)
                count("join.build.dense" if prepared is not None
                      else "join.build.hash")
                if prepared is None:
                    prepared = prepare_build(
                        build, self.right_keys, right_types,
                        [left_types[o] for o in self.left_keys])
        return staged, total, build, prepared

    @staticmethod
    def _count_out(outs) -> None:
        rows = sum(o.num_rows for o in outs
                   if isinstance(o.num_rows, int))
        if rows:
            count("join.out_rows", rows)

    def _dense_prepared(self, build: ColumnarBatch, left_types,
                        right_types):
        """AQE replan: measure the build key range and, when it is
        dense, slot-sort the build for direct-lookup probing
        (ops.join.DensePreparedBuild). None whenever the shape or the
        measurement disqualifies — the caller falls through to the hash
        prepare. ``full`` is excluded: its unmatched-BUILD emission
        order depends on the build sort (hash- vs slot-sorted), and
        replans must stay bit-identical to the static plan."""
        spec = self._dense_spec
        if spec is None or self.kind == "full" \
                or len(self.right_keys) != 1:
            return None
        from spark_rapids_tpu.columnar.column import StringColumn
        from spark_rapids_tpu.ops import join as join_ops

        max_span, min_density, min_rows = spec
        col = build.columns[self.right_keys[0]]
        if isinstance(col, StringColumn):
            return None
        common = join_ops.common_key_type(
            left_types[self.left_keys[0]],
            right_types[self.right_keys[0]])
        if common is None or not common.is_integral:
            return None
        if build.realized_num_rows() < min_rows:
            return None
        kmin, kmax, n_valid = join_ops.measure_key_range(
            col, build.num_rows_device())
        if n_valid <= 0:
            return None
        span = kmax - kmin + 1
        if not 0 < span <= max_span or n_valid / span < min_density:
            return None
        prepared = join_ops.prepare_build_dense(
            build, self.right_keys, right_types,
            [left_types[o] for o in self.left_keys], kmin, span)
        if prepared is not None:
            from spark_rapids_tpu.execs import adaptive

            adaptive.record_replan("strategy_switch",
                                   "hash->dense probe")
        return prepared

    def _bucket(self, staged, keys: List[int], types, n_buckets: int,
                trace: str):
        """Hash-partition each staged chunk by join key, regrouping
        slices per bucket (slices stay spillable until their bucket
        runs). The partitioner is the exchange's own hash kernel, so
        both sides agree on bucket placement."""
        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.spillable import SpillableBatch
        from spark_rapids_tpu.ops import partition as part_ops

        per_bucket: List[List] = [[] for _ in range(n_buckets)]
        for sb in staged:
            with sb.acquired() as b:
                with TraceRange(trace):
                    sorted_b, counts = part_ops.hash_partition(
                        b, keys, types, n_buckets)
                    slices = part_ops.slice_partitions(sorted_b, counts)
                for p, sl in enumerate(slices):
                    if sl is not None:
                        per_bucket[p].append(SpillableBatch(
                            sl, priorities.OUTPUT_FOR_SHUFFLE_PRIORITY))
            sb.close()
        return per_bucket

    def _out_of_core(self, partition: int, build_staged,
                     build_total: int, budget: int, left_types,
                     right_types) -> Iterator[ColumnarBatch]:
        """Bucket-by-bucket join at bounded resident size. Hash
        co-bucketing keeps every join kind exact: matches share a
        bucket; left/full unmatched rows surface from their own bucket,
        each build row is in exactly one bucket so full-outer emits its
        unmatched rows exactly once."""
        # 2x headroom over the mean bucket absorbs hash skew
        n_buckets = max(-(-build_total // budget) * 2, 2)
        build_buckets = self._bucket(build_staged, self.right_keys,
                                     right_types, n_buckets,
                                     "HashJoinExec.oob.build")
        stream_staged, _n = self._stage(0, partition)
        stream_buckets = self._bucket(stream_staged, self.left_keys,
                                      left_types, n_buckets,
                                      "HashJoinExec.oob.stream")
        emitted = False
        for p in range(n_buckets):
            stream_b = self._concat_staged(stream_buckets[p],
                                           self.children[0].schema)
            if stream_b.realized_num_rows() == 0 and \
                    (self.kind != "full" or not build_buckets[p]):
                for h in build_buckets[p]:
                    h.close()
                continue
            build_b = self._concat_staged(build_buckets[p],
                                          self.children[1].schema)
            count("join.probe_rows", stream_b.realized_num_rows())
            with TraceRange(f"HashJoinExec.oob.{self.kind}"):
                outs = self._probe_retry(stream_b, build_b, left_types,
                                         right_types,
                                         tag="join.oob.probe")
            self._count_out(outs)
            emitted = True
            yield from outs
        if not emitted:
            yield ColumnarBatch.empty(self.schema)


class BroadcastHashJoinExec(HashJoinExec):
    """Identical kernel; the build child is a BroadcastExchangeExec that
    materializes once and replays per partition
    (GpuBroadcastHashJoinExec)."""


class ShuffledHashJoinExec(HashJoinExec):
    """Both children sit below hash ShuffleExchangeExecs on the same keys,
    so partition p of each side holds co-partitioned rows
    (GpuShuffledHashJoinExec)."""


class _NestedLoopJoinBase(TpuExec):
    """Shared body of the brute-force joins: stream the left child's
    batches against a whole right-side build batch, emitting the cross
    product with any residual condition fused into the pair expansion
    (nested_loop_join kernel). Both subclasses are disabled by default at
    the planner — same OOM-risk stance as the reference
    (GpuOverrides.scala:1837-1856)."""

    def __init__(self, left: TpuExec, right: TpuExec, schema: Schema,
                 condition: Optional[Expression] = None, conf=None):
        super().__init__([left, right], schema)
        self.condition = CompiledFilter(condition, conf) \
            if condition is not None else None

    @property
    def children_coalesce_goal(self):
        return [None, RequireSingleBatch]

    def _join_batches(self, stream_it, build: ColumnarBatch):
        left_types = list(self.children[0].schema.types)
        right_types = list(self.children[1].schema.types)
        from spark_rapids_tpu.memory import retry as _retry

        saw = False
        for b in stream_it:
            if b.realized_num_rows() == 0 and saw:
                continue
            saw = True
            with TraceRange(self.name):
                # the pair expansion is per-stream-row, so the stream
                # batch halves freely under the retry ladder (the build
                # side stays whole — it is the broadcast)
                if self.condition is not None and self.condition.fused:
                    outs = _retry.with_retry(
                        b,
                        lambda bb: nested_loop_join(
                            bb, build, left_types, right_types,
                            self.condition.mask,
                            self.condition.condition.references())[0],
                        split=_retry.halve_batch,
                        tag="join.nestedloop")
                else:
                    outs = _retry.with_retry(
                        b,
                        lambda bb: cross_join(bb, build, left_types,
                                              right_types)[0],
                        split=_retry.halve_batch,
                        tag="join.nestedloop")
                    if self.condition is not None:
                        outs = [self.condition(o) for o in outs]
            yield from outs


class BroadcastNestedLoopJoinExec(_NestedLoopJoinBase):
    """Streams the left child's partitions against a broadcast right side
    (GpuBroadcastNestedLoopJoinExec, sql-plugin/.../execution/
    GpuBroadcastNestedLoopJoinExec.scala). Inner-with-condition and cross
    only; left keeps its partitioning."""

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.execs.batching import drain_to_single_batch

        def it():
            build = drain_to_single_batch(
                self.children[1].execute(partition),
                self.children[1].schema)
            yield from self._join_batches(
                self.children[0].execute(partition), build)
        return timed(self, it())


class CartesianProductExec(_NestedLoopJoinBase):
    """Both sides stay partitioned; the output partition grid is
    left_partitions x right_partitions, partition p reading
    (p // right_n, p % right_n) — the RDD-cartesian shape of
    GpuCartesianProductExec (org/apache/spark/sql/rapids/
    GpuCartesianProductExec.scala)."""

    @property
    def num_partitions(self) -> int:
        return (self.children[0].num_partitions *
                self.children[1].num_partitions)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.execs.batching import drain_to_single_batch

        rn = self.children[1].num_partitions
        lp, rp = divmod(partition, rn)

        def it():
            build = drain_to_single_batch(self.children[1].execute(rp),
                                          self.children[1].schema)
            yield from self._join_batches(
                self.children[0].execute(lp), build)
        return timed(self, it())
