"""Hash-aggregate exec: streaming per-batch aggregation with a running
merge loop.

Reference flow (aggregate.scala:380-478): input-project each batch ->
per-batch aggregation -> concat with the running aggregate -> merge-
aggregate; after the last batch, final projection (:503-545) and the
empty-input default-values path (:488-501). On TPU the per-batch aggregate
is the sort-based segmented kernel (ops/groupby.py) and all halves run as
jit-compiled XLA programs.
"""
from __future__ import annotations

from typing import Iterator, List, Optional

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression)
from spark_rapids_tpu.expressions.compiler import CompiledProjection
from spark_rapids_tpu.ops.buckets import MIN_CAPACITY
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.filter import rebucket
from spark_rapids_tpu.ops.groupby import AggSpec, groupby_aggregate, \
    reduce_aggregate
from spark_rapids_tpu.plan.nodes import AggCall
from spark_rapids_tpu.utils.tracing import TraceRange


class HashAggregateExec(TpuExec):
    """Modes (GpuHashAggregateExec / partial-final split):

    - complete: raw -> results in one exec
    - partial:  raw -> partial columns (update halves), feeds an exchange
    - final:    partials -> merged + evaluated results
    """

    #: planner-set (fused.py): yield raw (keys..., partials...) batches
    #: with a LAZY row count — the downstream fused chain absorbed the
    #: final projection, the HAVING filter and the compaction, so this
    #: exec's final-project dispatch and rebucket sync disappear
    defer_final = False
    #: deferred-final outputs above this capacity rebucket anyway: the
    #: consuming chain's in-program sort is a full-capacity
    #: sort network, so the dispatch saving must not buy a multi-
    #: million-lane sort (group counts overwhelmingly fit far below)
    _DEFER_FINAL_MAX_CAP = 1 << 20

    def __init__(self, grouping: List[Expression], aggs: List[AggCall],
                 child: TpuExec, schema: Schema, mode: str = "complete",
                 conf=None, fused_filter=None):
        super().__init__([child], schema)
        assert mode in ("complete", "partial", "final")
        self.grouping = grouping
        self.aggs = aggs
        self.mode = mode
        self.conf = conf
        # a CompiledFilter whose keep-mask rides into the groupby sort as
        # a live_mask — the planner fuses Filter(child) pairs here, saving
        # the per-batch compaction pass (argsort + per-column gathers)
        self.fused_filter = fused_filter
        # resolve the grouping-sets dense guard NOW, while the full
        # in-process subtree is visible: a cluster rewrite may later
        # swap it for a shuffle-read stub (runtime/cluster.py), and the
        # pickled exec must carry the already-resolved flag
        self._dense_ok()
        self._build()

    def _build(self):
        nkeys = len(self.grouping)
        if self.mode in ("complete", "partial"):
            # input projection: keys then each agg's input once per update op
            proj_exprs: List[Expression] = list(self.grouping)
            specs: List[AggSpec] = []
            for call in self.aggs:
                fn = call.fn
                if fn.input is not None:
                    ordinal = len(proj_exprs)
                    proj_exprs.append(fn.input)
                else:
                    ordinal = -1
                for op in fn.update_ops():
                    specs.append(AggSpec(op, ordinal
                                         if op != "count_star" else -1))
            self.input_proj: Optional[CompiledProjection] = \
                CompiledProjection(proj_exprs, self.conf)
            self.input_types = [e.dtype for e in proj_exprs]
            self.first_specs = specs
        else:
            # final mode: child emits keys then partial columns
            self.input_proj = None
            self.input_types = list(self.children[0].schema.types)
            specs = []
            p = nkeys
            for call in self.aggs:
                for op in call.fn.merge_ops():
                    specs.append(AggSpec(op, p))
                    p += 1
            self.first_specs = specs

        # merge specs re-aggregate this exec's own partial output (running
        # concat+merge loop): partial column i sits at nkeys+i.
        self.merge_specs: List[AggSpec] = []
        p = nkeys
        for call in self.aggs:
            for op in call.fn.merge_ops():
                self.merge_specs.append(AggSpec(op, p))
                p += 1
        self.partial_types: List[dt.DType] = []
        for call in self.aggs:
            self.partial_types.extend(call.fn.partial_types())

        # final projection over (keys..., partials...)
        if self.mode in ("complete", "final"):
            exprs: List[Expression] = [
                BoundReference(i, e.dtype) for i, e in
                enumerate(self.grouping)]
            base = nkeys
            for call in self.aggs:
                nparts = len(call.fn.partial_types())
                refs = [BoundReference(base + j, t) for j, t in
                        enumerate(call.fn.partial_types())]
                exprs.append(Alias(call.fn.evaluate(refs), call.name))
                base += nparts
            self.final_proj: Optional[CompiledProjection] = \
                CompiledProjection(exprs, self.conf)
        else:
            self.final_proj = None

    @property
    def coalesce_after(self):
        # the merge loop leaves exactly one batch per partition
        from spark_rapids_tpu.execs.batching import RequireSingleBatch

        return RequireSingleBatch

    @property
    def children_coalesce_goal(self):
        # final mode reads pre-reduced partials (often many tiny
        # shuffle blocks): coalescing them first turns N update+merge
        # kernel dispatches into one concat + one update, while the
        # TargetSize bound keeps memory behavior identical to the
        # streaming loop (which concats running+part at the same scale)
        if self.mode != "final":
            return [None]
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.execs.batching import TargetSize

        bb = self.conf.get(cfg.BATCH_SIZE_BYTES) if self.conf is not None \
            else cfg.BATCH_SIZE_BYTES.default
        return [TargetSize(bb)]

    # ------------------------------------------------------------------

    def _dense_ok(self) -> bool:
        """Grouping-set aggregates (an ExpandExec anywhere below) must
        not take the sort-free dense groupby for FLOAT sums: expand
        places each level's copy of the same rows at different
        positions, and the dense sweep's position-dependent reduction
        tree would break the cross-level bit-equality of float sums
        that rank()-over-sum ties rely on (TPC-DS q67). The kernel
        itself re-enables dense when no order-sensitive aggregate is
        present (ints/counts/min/max are order-invariant). Computed
        EAGERLY on first call in-process and cached on the exec, so a
        cluster rewrite that later replaces the subtree with a
        shuffle-read stub ships the already-resolved flag."""
        ok = getattr(self, "_dense_ok_cached", None)
        if ok is None:
            from spark_rapids_tpu.execs.basic import ExpandExec

            stack: list = [self]
            ok = True
            while stack:
                n = stack.pop()
                if isinstance(n, ExpandExec):
                    ok = False
                    break
                stack.extend(getattr(n, "children", ()))
            self._dense_ok_cached = ok
        return ok

    def _agg_batch(self, batch: ColumnarBatch, specs: List[AggSpec],
                   types: List[dt.DType], live_mask=None,
                   site: str = "aggregate.update") -> ColumnarBatch:
        """Aggregate one batch under the split-and-retry ladder: device
        OOM first spills the catalog and retries (the RMM event
        handler's spill-and-retry, DeviceMemoryEventHandler.scala:42),
        then HALVES the input and aggregates the halves — valid because
        partial aggregates re-merge with the merge ops, exactly what
        the streaming loop does between batches anyway."""
        from spark_rapids_tpu.memory import retry as _retry

        nkeys = len(self.grouping)

        def run(item):
            b, m = item
            if nkeys == 0:
                return reduce_aggregate(b, specs, types, m)[0]
            return groupby_aggregate(b, list(range(nkeys)), specs,
                                     types, m,
                                     dense_ok=self._dense_ok())[0]

        def split(item):
            b, m = item
            if m is not None:
                # the live-mask is capacity-aligned to THIS batch; a
                # row-range half would need a matching mask slice at a
                # rebucketed capacity — compact the survivors instead
                # so the halves carry no mask at all
                from spark_rapids_tpu.ops import filter as filt

                b = rebucket(filt.compact_batch(b, m))
            halves = _retry.halve_batch(b)
            if halves is None:
                return None
            return [(h, None) for h in halves]

        parts = _retry.with_retry((batch, live_mask), run, split=split,
                                  tag=site)
        out = parts[0]
        for part in parts[1:]:
            # the re-merge runs at the memory level that just OOM'd, so
            # it goes through the ladder too: the concat under the
            # spill rungs, the merge aggregate recursively guarded
            # (splittable — merge ops are associative over partials)
            merged_in = _retry.with_retry_no_split(
                lambda o=out, p=part: concat_batches([o, p]),
                tag="aggregate.merge.concat")
            out = self._agg_batch(merged_in, self.merge_specs,
                                  self._merge_types(),
                                  site="aggregate.merge")
        return out

    def _merge_types(self) -> List[dt.DType]:
        return [e.dtype for e in self.grouping] + self.partial_types

    # -- the incremental-combine seam ----------------------------------
    # The update/merge split built for the retry ladder doubles as an
    # incremental operator: partials from disjoint row sets re-merge to
    # the partials of their union, so a consumer may hold ``running``
    # partials across calls and fold new input in O(new input). The
    # batch execute() loop below and the streaming subsystem
    # (service/streaming/state.py) both drive these three methods.

    def update_partials(self, batch: ColumnarBatch,
                        site: str = "aggregate.update") -> ColumnarBatch:
        """One update-program launch: a raw child batch ->
        (keys..., partials...) in the merge schema."""
        b, mask = self._update_inputs(batch)
        b, mask = self._maybe_compact_wide(b, mask)
        return self._agg_batch(b, self.first_specs, self.input_types,
                               mask, site=site)

    def merge_partials(self, running: ColumnarBatch,
                       part: ColumnarBatch,
                       site: str = "aggregate.merge") -> ColumnarBatch:
        """One merge launch: concat two partial batches and re-aggregate
        with the merge specs (associative — any fold order yields the
        same partials for integral aggregates)."""
        merged_in = concat_batches([running, part])
        return self._agg_batch(merged_in, self.merge_specs,
                               self._merge_types(), site=site)

    def finalize_partials(self, running: ColumnarBatch) -> ColumnarBatch:
        """Final projection + compaction over accumulated partials.
        Does NOT consume ``running`` — a streaming consumer can emit
        now and keep folding into the same partials."""
        if self.final_proj is not None:
            with TraceRange("HashAggregateExec.finalProject"):
                running = self.final_proj(running)
        return rebucket(running)

    def _update_inputs(self, b: ColumnarBatch):
        """Per-batch update-side inputs: (projected batch, live-mask).
        FusedAggregateExec overrides this with its one-program chain."""
        mask = None
        if self.fused_filter is not None:
            # keep-mask over the RAW batch (condition binds to
            # the child schema), row-aligned through projection
            mask = self.fused_filter.mask(b)
        if self.input_proj is not None:
            b = self.input_proj(b)
        return b, mask

    # from this capacity a WIDE (seven aggregate columns or more)
    # sort-path aggregate over a filtered batch first compacts the
    # survivors: the 2^23-capacity 9-agg groupby shape costs a
    # multi-ten-minute remote XLA compile (TPCx-BB q26 @ sf 1), while
    # compact + count-sync + re-bucket turns it into an already-cached
    # small-capacity shape. Dense-eligible aggregates skip this (no
    # sort module to blow up).
    _COMPACT_WIDE_MIN_CAP = 1 << 22
    _COMPACT_WIDE_MIN_AGGS = 7

    def _maybe_compact_wide(self, b: ColumnarBatch, mask):
        from spark_rapids_tpu.ops import filter as filt
        from spark_rapids_tpu.ops import groupby as gb

        if mask is None or b.capacity < self._COMPACT_WIDE_MIN_CAP or \
                len(self.first_specs) < self._COMPACT_WIDE_MIN_AGGS or \
                not self.grouping:
            return b, mask
        key_ords = list(range(len(self.grouping)))
        kr = tuple(gb.key_range_of(b.columns[o], self.input_types[o])
                   for o in key_ords)
        khv = tuple(b.columns[o].validity is not None for o in key_ords)
        if self._dense_ok() and gb._dense_layout(
                list(self.input_types), key_ords, kr, khv) is not None:
            return b, mask   # dense path: no sort module to blow up
        with TraceRange("HashAggregateExec.compactWide"):
            small = rebucket(filt.compact_batch(b, mask))
        if small.capacity < b.capacity:
            return small, None
        return b, mask

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            running: Optional[ColumnarBatch] = None
            saw_input = False
            for b in self.children[0].execute(partition):
                if b.realized_num_rows() == 0:
                    continue
                saw_input = True
                running = self._fold(running, b)
            if running is None:
                if self.grouping or (self.mode == "final" and not saw_input):
                    # grouped agg over empty input -> no rows (in the
                    # deferred-final shape the consumer chain expects
                    # the merge schema, not the final one)
                    yield ColumnarBatch.empty(
                        self._merge_schema() if self.defer_final
                        else self.schema)
                    return
                running = self._empty_global_partials()
            if self.defer_final:
                # the consuming fused chain applies the final
                # projection, HAVING and compaction in ITS program;
                # the count stays a lazy device scalar. Above the
                # capacity bound, rebucket anyway (one sync + shrink):
                # the chain's SORT runs at this batch's
                # capacity, and a multi-million-lane sort network to
                # save two round trips is a net loss at large scale
                # factors
                if running.capacity > self._DEFER_FINAL_MAX_CAP:
                    running = rebucket(running)
                yield running
                return
            if self.final_proj is None and \
                    running.capacity <= MIN_CAPACITY:
                # partials bound for an exchange, too few to shrink: the
                # count stays on the device (rebucket would fetch it to
                # find nothing to do), and the consumer's one sync reads
                # every partition's
                yield running
                return
            yield self.finalize_partials(running)
        return timed(self, it())

    def _fold(self, running: Optional[ColumnarBatch],
              b: ColumnarBatch) -> ColumnarBatch:
        """One batch into the partition's running partials: an update
        launch and, from the second batch on, a concat and a merge
        launch. FusedAggregateExec overrides this with its one program
        where the partials have a small static shape."""
        with TraceRange("HashAggregateExec.updateAgg"):
            part = self.update_partials(b)
        if running is None:
            return part
        with TraceRange("HashAggregateExec.mergeAgg"):
            return self.merge_partials(running, part)

    def _merge_schema(self) -> Schema:
        types = self._merge_types()
        return Schema([f"_m{i}" for i in range(len(types))], types)

    def _empty_global_partials(self) -> ColumnarBatch:
        """Default partials for a global aggregate over zero rows: count=0,
        everything else null (aggregate.scala:488-501)."""
        import numpy as np

        from spark_rapids_tpu.ops.buckets import bucket_capacity

        cap = bucket_capacity(1)
        cols = []
        for call in self.aggs:
            for ptype, pop in zip(call.fn.partial_types(),
                                  call.fn.update_ops()):
                if pop in ("count", "count_star"):
                    cols.append(Column.from_numpy(
                        np.zeros(cap, dtype=np.int64), dtype=dt.INT64))
                else:
                    cols.append(Column.all_null(ptype, cap))
        return ColumnarBatch(cols, 1)
