"""Window exec: segmented-scan window functions on device.

Reference: GpuWindowExec.scala + GpuWindowExpression.scala:738-818 map window
specs onto cuDF rolling windows. The TPU formulation is better than a
rolling-window translation: sort rows by (partition keys, order keys) once,
derive segment ids from key-change boundaries, then every window function
is a segmented scan/reduction XLA fuses into one program:

- row_number/rank/dense_rank: index arithmetic against segment starts,
- running aggregates (unboundedPreceding..currentRow): prefix sums /
  ``lax.associative_scan`` with a segment-reset combiner,
- whole-partition aggregates: ``jax.ops.segment_*`` + gather,
- bounded row frames for sum/count/avg: prefix-sum differences,
- lead/lag: shifted gather with same-segment masking.

Partition-by requires the partition's rows in one batch (the reference has
the same constraint, GpuWindowExec.scala:92); the planner coalesces to
RequireSingleBatch below this exec.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.execs.batching import RequireSingleBatch
from spark_rapids_tpu.expressions.aggregates import (AggregateFunction,
                                                     Average, Count, First,
                                                     Last, Max, Min, Sum)
from spark_rapids_tpu.expressions.base import BoundReference, Expression
from spark_rapids_tpu.expressions.compiler import CompiledProjection
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.ops.sort import sort_batch
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.plan.nodes import WindowCall
from spark_rapids_tpu.utils.tracing import TraceRange


def _neq_prev(data: jax.Array, validity, dtype: dt.DType) -> jax.Array:
    """True where row i's key differs from row i-1's (null == null)."""
    if dtype.is_floating:
        d = sortkeys.canonicalize_floats(data)
        d = jnp.where(jnp.isnan(d), jnp.zeros((), d.dtype), d)
        nan = jnp.isnan(sortkeys.canonicalize_floats(data))
        neq = (d != jnp.roll(d, 1)) | (nan != jnp.roll(nan, 1))
    else:
        neq = data != jnp.roll(data, 1)
    if validity is not None:
        v = validity
        neq = jnp.where(v & jnp.roll(v, 1), neq, v != jnp.roll(v, 1))
    return neq.at[0].set(True)


class WindowKernel:
    """The post-sort window math over raw device columns: segment
    derivation + one output column per call. Pure function of traced
    arrays, so it runs identically under the single-device exec (below)
    and inside a per-chip ``shard_map`` body
    (parallel/window_step.py) — the mesh path is the same kernel after
    an all_to_all partition-key route."""

    def __init__(self, pre_types: List[dt.DType],
                 partition_ordinals: List[int],
                 order_specs: List[SortKeySpec], calls: List[WindowCall],
                 input_ordinals: List[int]):
        self.pre_types = list(pre_types)
        self.partition_ordinals = list(partition_ordinals)
        self.order_specs = list(order_specs)
        self.calls = list(calls)
        self._input_ordinal = list(input_ordinals)

    def __call__(self, cols: List[Column], num_rows) -> List[Column]:
        """``cols``: the pre-projected columns ALREADY sorted by
        (partition keys, order keys) with padding last; ``num_rows`` a
        device scalar. Returns one column per window call."""
        cap = cols[0].capacity
        live = jnp.arange(cap, dtype=jnp.int32) < num_rows

        part_b = self._boundary(cols, self.partition_ordinals, num_rows)
        order_cols = [spec.ordinal for spec in self.order_specs]
        order_b = part_b | self._boundary(cols, order_cols, num_rows) \
            if order_cols else part_b

        seg_id = jnp.cumsum(part_b.astype(jnp.int32)) - 1
        idx = jnp.arange(cap, dtype=jnp.int32)
        seg_start = jax.ops.segment_min(idx, seg_id, num_segments=cap,
                                        indices_are_sorted=True)
        start_of_row = jnp.take(seg_start, seg_id)
        # segment end (exclusive)
        seg_end = jax.ops.segment_max(idx, seg_id, num_segments=cap,
                                      indices_are_sorted=True) + 1
        end_of_row = jnp.take(seg_end, seg_id)

        out: List[Column] = []
        for c, inp_ord in zip(self.calls, self._input_ordinal):
            out.append(self._one_call(c, cols, inp_ord, seg_id, idx,
                                      start_of_row, end_of_row, order_b,
                                      live))
        return out

    def _boundary(self, cols: List[Column], ordinals: List[int],
                  num_rows) -> jax.Array:
        cap = cols[0].capacity
        boundary = jnp.zeros(cap, dtype=bool).at[0].set(True)
        for o in ordinals:
            c = cols[o]
            boundary = boundary | _neq_prev(c.data, c.validity,
                                            self.pre_types[o])
        # first padding row opens its own segment
        is_first_pad = jnp.arange(cap, dtype=jnp.int32) == num_rows
        return boundary | is_first_pad

    # ------------------------------------------------------------------

    def _one_call(self, c: WindowCall, cols: List[Column], inp_ord: int,
                  seg_id, idx, start_of_row, end_of_row, order_b,
                  live) -> Column:
        cap = cols[0].capacity
        if c.fn == "row_number":
            data = (idx - start_of_row + 1).astype(jnp.int32)
            return Column(dt.INT32, data, None)
        if c.fn in ("rank", "dense_rank"):
            tie_id = jnp.cumsum(order_b.astype(jnp.int32)) - 1
            tie_start = jax.ops.segment_min(idx, tie_id, num_segments=cap,
                                            indices_are_sorted=True)
            if c.fn == "rank":
                data = (jnp.take(tie_start, tie_id) - start_of_row + 1)
            else:
                cs = jnp.cumsum(order_b.astype(jnp.int32))
                data = cs - jnp.take(cs, start_of_row) + 1
            return Column(dt.INT32, data.astype(jnp.int32), None)
        if isinstance(c.fn, tuple):
            kind = c.fn[0]
            off = c.offset if kind == "lead" else -c.offset
            src = idx + off
            ok = (src >= 0) & (src < cap)
            src_c = jnp.clip(src, 0, cap - 1)
            same = jnp.take(seg_id, src_c) == seg_id
            ok = ok & same & jnp.take(live, src_c)
            inp = cols[inp_ord]
            data = jnp.take(inp.data, src_c)
            src_valid = jnp.take(inp.validity, src_c) \
                if inp.validity is not None else None
            if c.default is not None:
                fill = jnp.asarray(c.default, dtype=data.dtype)
                data = jnp.where(ok, data, fill)
                # out-of-frame slots take the (non-null) default
                valid = None if src_valid is None else \
                    jnp.where(ok, src_valid, True)
            else:
                valid = ok if src_valid is None else (ok & src_valid)
            return inp._like(data, valid)
        assert isinstance(c.fn, AggregateFunction)
        return self._window_agg(c, cols, inp_ord, seg_id, idx,
                                start_of_row, end_of_row, live)

    def _range_bounds(self, cols: List[Column], seg_id, start_of_row,
                      end_of_row, frame, live):
        """Per-row [lo, hi] row-index bounds of a RANGE frame over the
        single ascending order key. Null keys sort first and are all
        'equal': a null row's frame is exactly the null run."""
        okey_ord = self.order_specs[0].ordinal
        kcol = cols[okey_ord]
        cap = kcol.capacity
        key = kcol.data
        kvalid = (kcol.validity if kcol.validity is not None
                  else jnp.ones(cap, dtype=bool)) & live
        if self.pre_types[okey_ord].is_floating:
            key = sortkeys.canonicalize_floats(key)
        lo_arr = start_of_row if frame.lower is None else \
            _range_lower_upper_bound(seg_id, kvalid, key, seg_id,
                                     key + frame.lower, cap, upper=False)
        hi_arr = (end_of_row - 1) if frame.upper is None else \
            _range_lower_upper_bound(seg_id, kvalid, key, seg_id,
                                     key + frame.upper, cap,
                                     upper=True) - 1
        if frame.lower is not None:
            lo_arr = jnp.maximum(lo_arr, start_of_row)
        if frame.upper is not None:
            hi_arr = jnp.minimum(hi_arr, end_of_row - 1)
        # null-key rows: value offsets are undefined over null, so
        # BOUNDED sides clamp to the null run (null peers); UNBOUNDED
        # sides stay positional (partition start / end), like Spark
        invalid_live = (~kvalid) & live
        ps_null = jnp.cumsum(invalid_live.astype(jnp.int32))
        hi_null = jnp.take(ps_null, jnp.clip(end_of_row - 1, 0, cap - 1))
        lo_null = jnp.where(
            start_of_row > 0,
            jnp.take(ps_null, jnp.clip(start_of_row - 1, 0, cap - 1)), 0)
        nulls_in_seg = hi_null - lo_null
        # nulls-first: the null run always starts at the segment start,
        # so the lower bound is start_of_row for null rows either way
        lo_arr = jnp.where(kvalid, lo_arr, start_of_row)
        if frame.upper is not None:
            hi_arr = jnp.where(kvalid, hi_arr,
                               start_of_row + nulls_in_seg - 1)
        return lo_arr, hi_arr

    def _window_agg(self, c: WindowCall, cols: List[Column],
                    inp_ord: int, seg_id, idx, start_of_row, end_of_row,
                    live) -> Column:
        fn = c.fn
        cap = cols[0].capacity
        frame = c.frame
        if isinstance(fn, Count) and fn.input is None:
            vals = jnp.ones(cap, dtype=jnp.int64)
            valid_in = live
        else:
            inp = cols[inp_ord]
            vals = inp.data
            valid_in = live if inp.validity is None else \
                (live & inp.validity)

        if frame.kind == "range":
            lo_arr, hi_arr = self._range_bounds(cols, seg_id,
                                                start_of_row, end_of_row,
                                                frame, live)
        else:
            lo_arr = start_of_row if frame.lower is None else \
                jnp.maximum(idx + frame.lower, start_of_row)
            hi_arr = (end_of_row - 1) if frame.upper is None else \
                jnp.minimum(idx + frame.upper, end_of_row - 1)

        def prefix_range_sum(x):
            """sum over [frame_start, frame_end] rows per row."""
            ps = jnp.cumsum(x)
            empty = hi_arr < lo_arr  # e.g. rows (-2,-1) at segment start
            upper = jnp.take(ps, jnp.clip(hi_arr, 0, cap - 1))
            lower = jnp.where(
                lo_arr > 0,
                jnp.take(ps, jnp.clip(lo_arr - 1, 0, cap - 1)),
                jnp.zeros((), ps.dtype))
            return jnp.where(empty, jnp.zeros((), ps.dtype), upper - lower)

        if isinstance(fn, (First, Last)):
            # ignoreNulls=False: the boundary row's value as-is (its own
            # validity), NULL when the frame is empty
            pos = lo_arr if isinstance(fn, First) else hi_arr
            posc = jnp.clip(pos, 0, cap - 1)
            inp = cols[inp_ord]
            data = jnp.take(inp.data, posc)
            src_valid = jnp.take(inp.validity, posc) \
                if inp.validity is not None else jnp.ones(cap, dtype=bool)
            ok = (hi_arr >= lo_arr) & src_valid
            return inp._like(data, ok)

        if isinstance(fn, (Sum, Average, Count)):
            acc_t = jnp.int64 if fn.dtype.is_integral else jnp.float64
            x = jnp.where(valid_in, vals, 0).astype(acc_t)
            total = prefix_range_sum(x)
            cnt = prefix_range_sum(valid_in.astype(jnp.int64))
            if isinstance(fn, Count):
                return Column(dt.INT64, cnt, None)
            if isinstance(fn, Average):
                data = total.astype(jnp.float64) / \
                    jnp.maximum(cnt, 1).astype(jnp.float64)
                return Column(dt.FLOAT64, data, cnt > 0)
            return Column(fn.dtype, total.astype(fn.dtype.kernel_dtype),
                          cnt > 0)

        if isinstance(fn, (Min, Max)):
            is_min = isinstance(fn, Min)
            if frame.kind == "range":
                raise NotImplementedError(
                    "range-framed min/max windows fall back to CPU")
            if frame.lower is None and frame.upper == 0:
                data, cnt = _running_minmax(vals, valid_in, seg_id, is_min)
                return Column(fn.dtype, data.astype(fn.dtype.kernel_dtype),
                              cnt > 0)
            if frame.lower is None and frame.upper is None:
                seg_fn = jax.ops.segment_min if is_min else \
                    jax.ops.segment_max
                sentinel = _sentinel(vals.dtype, is_min)
                x = jnp.where(valid_in, vals, sentinel)
                per_seg = seg_fn(x, seg_id, num_segments=cap,
                                 indices_are_sorted=True)
                cnt = jax.ops.segment_sum(valid_in.astype(jnp.int32),
                                          seg_id, num_segments=cap,
                                          indices_are_sorted=True)
                data = jnp.take(per_seg, seg_id)
                return Column(fn.dtype, data.astype(fn.dtype.kernel_dtype),
                              jnp.take(cnt, seg_id) > 0)
            raise NotImplementedError(
                "bounded min/max window frames fall back to CPU")
        raise NotImplementedError(f"window aggregate {type(fn).__name__}")


def window_pre_projection(child_types: List[dt.DType],
                          calls: List[WindowCall], conf
                          ) -> Tuple[CompiledProjection, List[dt.DType],
                                     List[int]]:
    """Child columns + each call's input expression; returns the
    projection, its output types, and each call's input ordinal (-1 for
    input-free calls like row_number/count(*))."""
    exprs: List[Expression] = [
        BoundReference(i, t) for i, t in enumerate(child_types)]
    input_ordinals: List[int] = []
    for c in calls:
        if isinstance(c.fn, AggregateFunction):
            inp = c.fn.input
        elif isinstance(c.fn, tuple):
            inp = c.fn[1]
        else:
            inp = None
        if inp is None:
            input_ordinals.append(-1)
        else:
            input_ordinals.append(len(exprs))
            exprs.append(inp)
    return (CompiledProjection(exprs, conf), [e.dtype for e in exprs],
            input_ordinals)


class WindowExec(TpuExec):
    """Out-of-core (SURVEY §5.7): a partitioned-window input exceeding
    the batch budget hash-buckets by PARTITION BY keys (every window
    group lands wholly in one bucket by construction) and runs the
    kernel bucket-by-bucket at a bounded resident size — the join
    build's treatment applied to windows. Un-partitioned windows have
    no such split and keep the single-batch requirement (the reference
    has the same constraint, GpuWindowExec.scala:92)."""

    def __init__(self, partition_ordinals: List[int],
                 order_specs: List[SortKeySpec], calls: List[WindowCall],
                 child: TpuExec, schema: Schema, conf=None,
                 window_budget_rows=None):
        super().__init__([child], schema)
        self.partition_ordinals = partition_ordinals
        self.order_specs = order_specs
        self.calls = calls
        self.conf = conf
        self.window_budget_rows = window_budget_rows
        self.n_child = len(child.schema)
        self.pre_proj, self.pre_types, self._input_ordinal = \
            window_pre_projection(list(child.schema.types), calls, conf)
        self.kernel = WindowKernel(self.pre_types, partition_ordinals,
                                   order_specs, calls,
                                   self._input_ordinal)

    @property
    def children_coalesce_goal(self):
        return [None if self.partition_ordinals else RequireSingleBatch]

    def _budget_rows(self) -> int:
        if self.window_budget_rows is not None:
            return max(self.window_budget_rows, 1)
        from spark_rapids_tpu import config as cfg

        bb = cfg.BATCH_SIZE_BYTES.default if self.conf is None \
            else self.conf.get(cfg.BATCH_SIZE_BYTES)
        row_bytes = max(sum(t.byte_width for t in self.pre_types), 1)
        return max(bb // row_bytes, 1 << 16)

    # ------------------------------------------------------------------

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            from spark_rapids_tpu.memory import priorities
            from spark_rapids_tpu.memory.spillable import SpillableBatch

            staged: List[SpillableBatch] = []
            total = 0
            for b in self.children[0].execute(partition):
                n = b.realized_num_rows()
                if n == 0:
                    continue
                total += n
                staged.append(SpillableBatch(
                    b, priorities.INPUT_FROM_SHUFFLE_PRIORITY))
            if not staged:
                yield ColumnarBatch.empty(self.schema)
                return
            budget = self._budget_rows()
            if total > budget and self.partition_ordinals:
                yield from self._out_of_core(staged, total, budget)
                return
            b = self._concat_staged(staged)
            with TraceRange("WindowExec"):
                out = self._run(b)
            yield out
        return timed(self, it())

    @staticmethod
    def _concat_staged(staged) -> ColumnarBatch:
        from contextlib import ExitStack

        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.ops.concat import concat_batches

        with ExitStack() as stack:
            parts = [stack.enter_context(sb.acquired()) for sb in staged]
            merged = parts[0] if len(parts) == 1 else \
                with_retry_no_split(lambda: concat_batches(parts),
                                    tag="window.concat")
        for sb in staged:
            sb.close()
        return merged

    def _out_of_core(self, staged, total: int,
                     budget: int) -> Iterator[ColumnarBatch]:
        """Hash-bucket by PARTITION BY keys, window each bucket
        independently (groups never span buckets, so results are
        exact; output order is per-bucket, same contract as the
        post-shuffle window)."""
        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.retry import with_retry_no_split
        from spark_rapids_tpu.memory.spillable import SpillableBatch
        from spark_rapids_tpu.ops import partition as part_ops

        n_buckets = max(-(-total // budget) * 2, 2)
        child_types = list(self.children[0].schema.types)
        per_bucket: List[List[SpillableBatch]] = \
            [[] for _ in range(n_buckets)]
        for sb in staged:
            with sb.acquired() as b:
                with TraceRange("WindowExec.oob.partition"):
                    sorted_b, counts = part_ops.hash_partition(
                        b, list(self.partition_ordinals), child_types,
                        n_buckets)
                    slices = part_ops.slice_partitions(sorted_b, counts)
                for p, sl in enumerate(slices):
                    if sl is not None:
                        per_bucket[p].append(SpillableBatch(
                            sl, priorities.OUTPUT_FOR_SHUFFLE_PRIORITY))
            sb.close()
        emitted = False
        for p in range(n_buckets):
            if not per_bucket[p]:
                continue
            b = self._concat_staged(per_bucket[p])
            if b.realized_num_rows() == 0:
                continue
            with TraceRange("WindowExec.oob.bucket"):
                # a bucket holds whole PARTITION BY groups; halving by
                # rows would split a group, so no split rung here
                out = with_retry_no_split(lambda b=b: self._run(b),
                                          tag="window.bucket")
            emitted = True
            yield out
        if not emitted:
            yield ColumnarBatch.empty(self.schema)

    def _run(self, batch: ColumnarBatch) -> ColumnarBatch:
        ext = self.pre_proj(batch)
        sort_specs = [SortKeySpec(o, True, True)
                      for o in self.partition_ordinals] + self.order_specs
        s = sort_batch(ext, sort_specs, self.pre_types) if sort_specs \
            else ext
        call_cols = self.kernel(list(s.columns), s.num_rows_device())
        out_cols = list(s.columns[:self.n_child]) + call_cols
        return ColumnarBatch(out_cols, s.num_rows)


def _range_lower_upper_bound(seg_id, kvalid, key, tseg, tkey, cap: int,
                             upper: bool):
    """Vectorized binary search over rows ordered by (segment, nulls
    first, key): per row, the first index whose tuple is >= (>) the
    target. O(log n) unrolled steps of full-width gathers — range frames
    trade bandwidth for exactness (cuDF's range windows do a comparable
    per-row bounds search)."""
    import math

    lo = jnp.zeros(cap, dtype=jnp.int32)
    hi = jnp.full(cap, cap, dtype=jnp.int32)
    for _ in range(max(int(math.ceil(math.log2(max(cap, 2)))), 1) + 1):
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, cap - 1)
        sm = jnp.take(seg_id, midc)
        vm = jnp.take(kvalid, midc)
        km = jnp.take(key, midc)
        # tuple (sm, vm, km) vs (tseg, True, tkey); invalid (null) rows
        # sort first within a segment
        if upper:
            key_le = km <= tkey
        else:
            key_le = km < tkey
        less = (sm < tseg) | ((sm == tseg) & (~vm | (vm & key_le)))
        less = less & (mid < hi)  # converged lanes stay put
        lo = jnp.where(less, mid + 1, lo)
        hi = jnp.where(less, hi, mid)
    return lo


def _sentinel(dtype, is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype)
    info = jnp.iinfo(dtype)
    return jnp.asarray(info.max if is_min else info.min, dtype)


def _running_minmax(vals, valid, seg_id, is_min: bool
                    ) -> Tuple[jax.Array, jax.Array]:
    """Segmented running min/max via associative scan: the combiner resets
    when the segment changes."""
    sentinel = _sentinel(vals.dtype, is_min)
    x = jnp.where(valid, vals, sentinel)

    def combine(a, b):
        a_seg, a_val, a_cnt = a
        b_seg, b_val, b_cnt = b
        best = jnp.minimum(a_val, b_val) if is_min \
            else jnp.maximum(a_val, b_val)
        same = a_seg == b_seg
        return (b_seg,
                jnp.where(same, best, b_val),
                jnp.where(same, a_cnt + b_cnt, b_cnt))

    seg, out, cnt = jax.lax.associative_scan(
        combine, (seg_id, x, valid.astype(jnp.int32)))
    return out, cnt
