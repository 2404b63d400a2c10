"""Exec base: streaming columnar operators.

The reference's GpuExec contract (GpuExec.scala:65-137):
``doExecuteColumnar(): RDD[ColumnarBatch]`` + metrics + batching goals.
Here: ``execute(partition) -> Iterator[ColumnarBatch]`` over
``num_partitions`` logical partitions (the single-process analogue of
Spark's task partitions; the distributed runtime maps partitions onto mesh
devices).
"""
from __future__ import annotations

import threading
from spark_rapids_tpu.utils import lockorder
import time
from typing import Dict, Iterator, List, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema


class Metrics:
    """num_output_rows / num_output_batches / op_time_ns per exec
    (GpuMetricNames, GpuExec.scala:27-55). ``op_time_ns`` is self time —
    like the reference's totalTime it excludes time spent pulling child
    batches; ``pipeline_time_ns`` is inclusive.

    Row counts are recorded as DEVICE scalars and realized lazily when
    read: metric accounting must not inject a host sync per exec per
    batch into the pipeline (each sync is a full round trip behind a
    remote device attachment)."""

    def __init__(self):
        self._pending_rows = []
        self._rows = 0
        self.num_output_batches = 0
        self.op_time_ns = 0
        self.pipeline_time_ns = 0
        self._lock = lockorder.make_lock("execs.base.metrics")

    def record(self, batch: ColumnarBatch, elapsed_ns: int = 0,
               child_ns: int = 0):
        n = batch.num_rows
        with self._lock:  # partitions run on concurrent task threads
            self.num_output_batches += 1
            if isinstance(n, int):
                self._rows += n
            else:
                self._pending_rows.append(n)
            self.pipeline_time_ns += elapsed_ns
            self.op_time_ns += max(elapsed_ns - child_ns, 0)

    @property
    def num_output_rows(self) -> int:
        if self._pending_rows:
            import jax

            # ONE transfer for all pending scalars — per-batch
            # device_get here would re-serialize the round trips the
            # deferral exists to avoid
            realized = jax.device_get(self._pending_rows)
            self._rows += int(sum(int(n) for n in realized))
            self._pending_rows.clear()
        return self._rows

    # exec trees ship to remote executors as task closures (the cluster
    # runtime's map tasks, like Spark serializing RDD lineage); locks and
    # unrealized device scalars stay behind
    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_lock", None)
        state["_rows"] = self.num_output_rows  # realizes pending
        state["_pending_rows"] = []
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._lock = lockorder.make_lock("execs.base.metrics")


class TpuExec:
    """Base physical operator."""

    def __init__(self, children: List["TpuExec"], schema: Schema):
        self.children = children
        self.schema = schema
        self.metrics = Metrics()

    @property
    def name(self) -> str:
        return type(self).__name__

    @property
    def num_partitions(self) -> int:
        if self.children:
            return self.children[0].num_partitions
        return 1

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        raise NotImplementedError

    # -- batching contract (GpuExec.scala:71-86) --------------------------

    @property
    def coalesce_after(self) -> Optional[object]:
        """Goal describing batches this exec OUTPUTS (None = don't care)."""
        return None

    @property
    def children_coalesce_goal(self) -> List[Optional[object]]:
        """Goal each child's input must satisfy."""
        return [None] * len(self.children)

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.name]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def metric_children(self) -> List["TpuExec"]:
        """The execs under this one that did its work (a fused exec that
        fell back answers with the subtree that ran)."""
        return self.children

    def all_metrics(self) -> Dict[str, Metrics]:
        """Every exec's metrics, root first, keyed by class name; the
        second and later exec of one class get ``#2``, ``#3``, ... (q1's
        final and partial aggregate are both ``HashAggregateExec``)."""
        out: Dict[str, Metrics] = {}
        seen: Dict[str, int] = {}
        stack = [self]
        while stack:
            e = stack.pop()
            n = seen[e.name] = seen.get(e.name, 0) + 1
            out[e.name if n == 1 else f"{e.name}#{n}"] = e.metrics
            stack.extend(reversed(e.metric_children()))
        return out


def all_execs(root: TpuExec) -> Iterator[TpuExec]:
    """Every exec of a tree, each once: ``children``, and what a fused
    exec keeps beside them (its broadcast ``builds``, the unfused
    ``fallback`` subtree). After execution the tree is the one that ran:
    an adaptive join has put the plan it decided on in its children's
    place."""
    stack, seen = [root], set()
    while stack:
        e = stack.pop()
        if id(e) in seen:
            continue
        seen.add(id(e))
        yield e
        stack.extend(e.children)
        stack.extend(getattr(e, "builds", None) or ())
        fallback = getattr(e, "fallback", None)
        if fallback is not None:
            stack.append(fallback)


def timed(owner, it: Iterator[ColumnarBatch]
          ) -> Iterator[ColumnarBatch]:
    """Wrap an exec's output iterator with metric recording. ``owner`` is
    the TpuExec (self time = pull time minus children's pipeline time); a
    bare Metrics is accepted for exec-less iterators."""
    from spark_rapids_tpu.utils import dispatch as _disp
    from spark_rapids_tpu.utils import tracing as _tracing

    if isinstance(owner, Metrics):
        metrics, children = owner, ()
        stage = None
        span_name = None
    else:
        metrics, children = owner.metrics, owner.children
        # stage-cutting label (plan/optimizer.cut_stages): dispatches
        # issued while this exec's iterator advances attribute to its
        # pipeline stage in the telemetry
        stage = getattr(owner, "_stage_label", None)
        # with recording on, every pull that yields a batch is the span
        # `<ExecName>.next`, from the two clock reads the metrics take
        span_name = owner.name + ".next" if _tracing.recording() else None
    while True:
        child0 = sum(c.metrics.pipeline_time_ns for c in children)
        t0 = time.perf_counter_ns()
        span = _tracing.open_span(span_name, t0, annotate=True) \
            if span_name is not None else None
        tok = _disp.enter_stage(stage)
        batch = None
        try:
            batch = next(it)
        except StopIteration:
            return
        finally:
            _disp.exit_stage(tok)
            # no batch (the iterator ended, or raised): no metric, no span
            if batch is None and span is not None:
                _tracing.abandon_span(span)
        elapsed = time.perf_counter_ns() - t0
        if span is not None:
            _tracing.close_span(span, t0 + elapsed)
        child_ns = sum(c.metrics.pipeline_time_ns
                       for c in children) - child0
        metrics.record(batch, elapsed, child_ns)
        yield batch


def run_partitions(n_partitions: int, fn, task_threads: int = 4):
    """Drive ``fn(partition) -> result`` over all partitions on a worker
    pool, returning results in partition order. The reference's model:
    Spark schedules many concurrent tasks per executor while GpuSemaphore
    bounds how many touch the device (GpuSemaphore.scala:27-161,
    RapidsConf.scala:340) — here the pool is the task-slot analogue and
    execs acquire the shared TpuSemaphore at device entry, so host I/O of
    one partition overlaps device compute of another. ``task_threads<=1``
    or a single partition degrades to the serial loop (no thread hop)."""
    if n_partitions <= 1 or task_threads <= 1:
        return [fn(p) for p in range(n_partitions)]
    from concurrent.futures import ThreadPoolExecutor

    from spark_rapids_tpu.memory.catalog import (current_buffer_owner,
                                                 set_buffer_owner)
    from spark_rapids_tpu.service.batching import microbatch as _mb
    from spark_rapids_tpu.utils import dispatch as _disp
    from spark_rapids_tpu.utils import tracing as _tracing

    # the calling thread's wait on the pool; the tasks' spans hang under it
    with _tracing.TraceRange("run_partitions.wait"):
        # propagate the caller's buffer-owner tag, dispatch query tag,
        # micro-batching slice context and open span (all thread-local)
        # onto the pool threads: a query-service slice that fans out here
        # must have every batch the tasks register and every dispatch
        # they issue attributed to its query — and its stage programs
        # must stay coalescible — or cancel/deadline cleanup,
        # stalled-query spill demotion, ServiceStats per-query dispatch
        # counts, cross-query micro-batching and the query's span tree
        # would all miss pool work
        owner = current_buffer_owner()
        qid = _disp.current_query()
        bctx = _mb.current()
        span = _tracing.current()
        run = fn
        if owner is not None or qid is not None or bctx is not None \
                or span is not None:
            def run(p, _fn=fn, _owner=owner, _qid=qid, _bctx=bctx,
                    _span=span):
                prev = set_buffer_owner(_owner) if _owner is not None \
                    else None
                qtok = _disp.enter_query(_qid)
                btok = None
                if _bctx is not None:
                    btok = _mb.enter_slice(_bctx.batcher, _bctx.query_id,
                                           _bctx.multi)
                prev_span = _tracing.adopt(_span)
                try:
                    return _fn(p)
                finally:
                    _tracing.adopt(prev_span)
                    if _bctx is not None:
                        _mb.exit_slice(btok)
                    _disp.exit_query(qtok)
                    if _owner is not None:
                        set_buffer_owner(prev)

        with ThreadPoolExecutor(
                max_workers=min(task_threads, n_partitions),
                thread_name_prefix="tpu-task") as pool:
            return list(pool.map(run, range(n_partitions)))


def collect(exec_: TpuExec, conf=None):
    """Run all partitions and return one pandas DataFrame — the
    GpuColumnarToRowExec boundary (GpuColumnarToRowExec.scala:111).
    Partitions run concurrently on the task pool (see run_partitions);
    output row order is by partition then batch, same as the serial
    loop."""
    import pandas as pd

    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.utils import dispatch as _disp
    from spark_rapids_tpu.utils.tracing import TraceRange

    threads = (conf.get(cfg.TASK_THREADS) if conf is not None
               else cfg.TASK_THREADS.default)

    def one(p: int):
        # to_pandas fetches data + (possibly lazy) row count in ONE
        # device_get; a realized_num_rows() pre-filter here would pay a
        # separate round trip per batch just to skip empties. The fetch
        # is bracketed as the "result_sync" stage: it is the documented
        # end-of-query device->host transfer, not an unattributed
        # mid-plan sync, and the telemetry should say so.
        frames = []
        for batch in exec_.execute(p):
            tok = _disp.enter_stage("result_sync")
            try:
                with TraceRange("collect.fetch"):
                    frames.append(batch.to_pandas(exec_.schema))
            finally:
                _disp.exit_stage(tok)
        return [f for f in frames if len(f)]

    frames = [f for fs in
              run_partitions(exec_.num_partitions, one, threads)
              for f in fs]
    if not frames:
        cols = {n: pd.Series([], dtype=object)
                for n in exec_.schema.names}
        return pd.DataFrame(cols)
    with TraceRange("collect.concat"):
        return pd.concat(frames, ignore_index=True)
