"""Cluster query execution: SQL shuffles over the multi-process runtime.

Round-4 top verdict item: in the reference, the shuffle transport lives
INSIDE the shuffle manager real queries use — map tasks write partitioned
batches into the executor's catalog (RapidsCachingWriter,
RapidsShuffleInternalManager.scala:90-155), MapStatus registration names
the owning executor (:164-191), and reduce tasks read local hits
zero-copy plus remote blocks through the transport
(RapidsCachingReader.scala:59-145). Here the same wiring becomes
planner-reachable: with ``rapids.tpu.cluster.enabled``, every hash/single
``ShuffleExchangeExec`` in the final plan is swapped for a
``ClusterShuffleExchangeExec`` whose

- MAP side assigns child partitions round-robin over executors — the
  in-process ones AND remote worker processes
  (``shuffle/remote_worker.py`` task mode) that receive a pickled task
  closure (the Spark serialized-lineage model), execute it, register the
  partitioned output in their own catalog, and serve it over TCP;
- REDUCE side reads through ``ShuffleIterator`` over the TCP transport
  (local catalog hits + per-peer socket fetches), with fetch failures
  driving the Spark retry model: invalidate the dead executor's map
  outputs, re-run those map tasks on survivors, re-read.

Remote tasks whose subtree contains ANOTHER cluster exchange get it
replaced by a ``ClusterShuffleReadExec`` stub before pickling — the
worker then fetches that stage's blocks from wherever they live instead
of recomputing the upstream stage (Spark's stage DAG in miniature).
"""
from __future__ import annotations

import base64
import itertools
import pickle
import threading
import time
from spark_rapids_tpu.runtime import recovery
from spark_rapids_tpu.utils import lockorder
from typing import Dict, Iterator, List, Optional, Tuple

from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.execs.exchange import (ShuffleExchangeExec,
                                             partition_batch)
from spark_rapids_tpu.shuffle.cluster import LocalCluster
from spark_rapids_tpu.shuffle.iterator import (ShuffleFetchFailedError,
                                               ShuffleIterator)
from spark_rapids_tpu.shuffle.meta import BlockId
from spark_rapids_tpu.shuffle.transport import ShuffleClient
from spark_rapids_tpu.utils.tracing import TraceRange


def run_map_partitions(batches, partitioning, types, num_out: int
                       ) -> Dict[int, ColumnarBatch]:
    """Partition a map task's output batches into per-reduce-partition
    batches — the write half shared by local tasks and remote workers."""
    from spark_rapids_tpu.ops import partition as part_ops
    from spark_rapids_tpu.ops.concat import concat_batches

    parts: Dict[int, ColumnarBatch] = {}
    for b in batches:
        if b.realized_num_rows() == 0:
            continue
        sorted_b, counts = partition_batch(b, partitioning, types,
                                           num_out)
        subs = part_ops.slice_partitions(sorted_b, counts)
        for p, sub in enumerate(subs):
            if sub is None:
                continue
            parts[p] = sub if p not in parts else \
                concat_batches([parts[p], sub])
    return parts


def sample_rows_host(batches, schema: Schema, k: int, seed: int = 0x5EED):
    """Uniform row sample of executed batches as HOST arrays (raw kernel
    values — dates stay day counts, strings decode to objects) plus the
    TOTAL row count — the map-side half of cluster range-bounds
    sampling (GpuRangePartitioner.scala:42-95's sampling job; the total
    lets the driver weight each map's contribution by its size)."""
    import numpy as np

    live = [b for b in batches if b.realized_num_rows() > 0]
    rng = np.random.default_rng(seed)
    per_batch = max(k // max(len(live), 1), 1)
    datas = {n: [] for n in schema.names}
    valids = {n: [] for n in schema.names}
    total = 0
    for b in live:
        n = b.realized_num_rows()
        total += n
        idx = np.arange(n) if n <= per_batch else \
            rng.choice(n, per_batch, replace=False)
        for name, col in zip(schema.names, b.columns):
            vals, valid = col.to_numpy(n)
            datas[name].append(np.asarray(vals)[idx])
            valids[name].append(
                np.asarray(valid)[idx] if valid is not None
                else np.ones(len(idx), dtype=bool))
    out_d = {n: (np.concatenate(v) if v else np.array([]))
             for n, v in datas.items()}
    out_v = {n: (np.concatenate(v) if v else np.array([], dtype=bool))
             for n, v in valids.items()}
    return out_d, out_v, total


def host_sample_to_batch(data: dict, validity: dict,
                         schema: Schema) -> ColumnarBatch:
    """Rebuild one device batch from host sample arrays (driver side)."""
    import numpy as np

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.column import Column, StringColumn

    n = len(next(iter(data.values()))) if data else 0
    cols = []
    for name, t in zip(schema.names, schema.types):
        vals = np.asarray(data[name])
        valid = np.asarray(validity[name], dtype=bool)
        if t is dt.STRING:
            svals = [v if valid[i] else None
                     for i, v in enumerate(vals)]
            cols.append(StringColumn.from_strings(svals))
        else:
            cols.append(Column.from_numpy(
                vals, dtype=t,
                validity=None if valid.all() else valid))
    return ColumnarBatch(cols, n)


class ExecutorContext:
    """The process-local executor identity a ``ClusterShuffleReadExec``
    reads through: its catalog (local hits), its transport (peer
    fetches). The driver process sets one for executor 0; each worker
    process sets its own (remote_worker task mode)."""

    def __init__(self, executor, transport):
        self.executor = executor
        self.transport = transport
        self._clients: Dict[str, ShuffleClient] = {}
        self._lock = lockorder.make_lock("runtime.cluster.clients")

    def client_for(self, peer: str) -> ShuffleClient:
        with self._lock:
            c = self._clients.get(peer)
            if c is None:
                c = ShuffleClient(self.transport.connect(peer))
                self._clients[peer] = c
            return c

    def invalidate_client(self, peer: str) -> None:
        """Evict a cached peer client after a fetch error so the next
        attempt reconnects from the CURRENT address book — a respawned
        peer (new port) is unreachable through the stale socket."""
        with self._lock:
            c = self._clients.pop(peer, None)
        if c is not None:
            close = getattr(c.conn, "close", None)
            if close is not None:
                try:
                    close()
                except OSError:
                    pass


_CONTEXT: Optional[ExecutorContext] = None


def set_executor_context(ctx: Optional[ExecutorContext]) -> None:
    global _CONTEXT
    _CONTEXT = ctx


def executor_context() -> ExecutorContext:
    assert _CONTEXT is not None, \
        "no ExecutorContext in this process (cluster runtime not active)"
    return _CONTEXT


class ClusterShuffleReadExec(TpuExec):
    """Leaf exec serving one materialized cluster shuffle: a reduce
    task's view of the MapOutputTracker answer. Picklable — it carries
    only block locations + executor addresses; catalog and sockets come
    from the process's ExecutorContext (the reference's reader resolves
    its BlockManager the same way)."""

    def __init__(self, schema: Schema, shuffle_id: int, num_out: int,
                 num_maps: int,
                 map_outputs: Dict[int, Tuple[str, dict]],
                 addresses: Dict[str, Tuple[str, int]]):
        super().__init__([], schema)
        self.shuffle_id = shuffle_id
        self.num_out = num_out
        self.map_outputs = dict(map_outputs)
        self.addresses = dict(addresses)
        # an incomplete MapStatus set must NEVER become a stub: dropping
        # an in-recovery map from _locations would silently yield partial
        # data (Spark readers likewise demand every MapStatus up front)
        assert len(self.map_outputs) == num_maps, \
            (shuffle_id, sorted(self.map_outputs), num_maps)

    @property
    def num_partitions(self) -> int:
        return self.num_out

    def _locations(self, partition: int) -> Dict[BlockId, str]:
        locs: Dict[BlockId, str] = {}
        for map_id, (executor_id, partitions) in self.map_outputs.items():
            if partition in partitions:
                locs[BlockId(self.shuffle_id, map_id, partition)] = \
                    executor_id
        return locs

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            ctx = executor_context()
            for eid, addr in self.addresses.items():
                if eid != ctx.executor.executor_id:
                    ctx.transport.register_remote(eid, *addr)
            sit = ShuffleIterator(
                ctx.executor.shuffle_catalog,
                ctx.executor.executor_id, self._locations(partition),
                ctx.client_for, on_fetch_error=ctx.invalidate_client)
            empty = True
            for b in sit:
                if b.realized_num_rows() == 0:
                    continue
                empty = False
                yield b
            if empty:
                yield ColumnarBatch.empty(self.schema)
        return timed(self, it())


class ClusterShuffleExchangeExec(ShuffleExchangeExec):
    """ShuffleExchangeExec whose block store is the cluster runtime.

    ``wrap`` rebuilds from a planned single-process exchange; execution
    then follows the reference's write/read split instead of the
    per-process block dict."""

    def __init__(self, partitioning, num_out: int, child: TpuExec,
                 runtime: "ClusterRuntime", task_threads: int = 1,
                 batch_bytes: Optional[int] = None):
        super().__init__(partitioning, num_out, child,
                         task_threads=task_threads,
                         batch_bytes=batch_bytes)
        self.runtime = runtime
        self.shuffle_id: Optional[int] = None
        # set by ClusterRuntime.new_shuffle_id before map tasks run, so
        # make_read_stub can name the shuffle mid-materialization
        self._pending_sid: Optional[int] = None
        # reasons a map task was re-placed in-process instead of on its
        # assigned remote worker — surfaced in explain (tree_string) so
        # cluster-mode degradation is visible, never silent
        self.local_fallbacks: List[str] = []
        self._read_stub: Optional[ClusterShuffleReadExec] = None
        # the first reduce read of this exchange counts as one stage
        # boundary for the host-granularity fault injector
        self._reduce_stage_counted = False

    @classmethod
    def wrap(cls, ex: ShuffleExchangeExec, runtime: "ClusterRuntime"
             ) -> "ClusterShuffleExchangeExec":
        return cls(ex.partitioning, ex.num_out_partitions,
                   ex.children[0], runtime, task_threads=ex.task_threads,
                   batch_bytes=ex.collapse_bytes)

    def tree_string(self, indent: int = 0) -> str:
        label = "  " * indent + self.name
        if self.local_fallbacks:
            label += (f" [local fallback x{len(self.local_fallbacks)}:"
                      f" {self.local_fallbacks[0]}]")
        lines = [label]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    # -- map side ---------------------------------------------------------

    def _materialize(self) -> None:
        from spark_rapids_tpu.parallel import spmd
        from spark_rapids_tpu.shuffle import fault_injection

        with self._mat_lock:
            if self.shuffle_id is not None:
                return
            # this exchange's blocks cross the host boundary: the DCN
            # seam decision pairs with the ICI decisions the planner
            # records for host-local Mesh*Exec subtrees
            spmd.record_seam("exchange", spmd.SEAM_DCN,
                             "cluster exchange: map outputs cross the "
                             "host boundary over TCP")
            if fault_injection.get_injector().should_kill_host_at_stage():
                # host-granularity fault: SIGKILL a live worker at the
                # stage boundary. Recovery is NOT told — it discovers
                # the death through submit failures and reduce-side
                # fetch failures, the same signals a real host loss
                # produces.
                self.runtime.kill_one_host()
            sid = self.runtime.new_shuffle_id(self)
            child = self.children[0]
            if self.partitioning[0] == "range" and \
                    (len(self.partitioning) < 3 or
                     self.partitioning[2] is None):
                self._resolve_range_bounds(sid)
            with TraceRange("ClusterShuffleExchangeExec.map"):
                for map_id in range(child.num_partitions):
                    self.runtime.run_map_task(self, sid, map_id)
            self.shuffle_id = sid
            self._read_stub = self.make_read_stub()

    #: rows each map task contributes to the bounds sample
    SAMPLE_ROWS_PER_MAP = 4096

    def _resolve_range_bounds(self, sid: int) -> None:
        """Cluster range partitioning, the reference's two-job split
        (GpuRangePartitioner.scala:42-95): a SAMPLING pass runs the
        child on every executor and returns host key samples, the
        driver aggregates them into bounds, then the normal map phase
        ships tasks with bounds attached."""
        import numpy as np

        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.spillable import SpillableBatch
        from spark_rapids_tpu.ops import partition as part_ops

        child = self.children[0]
        per_map = []  # (data, validity, total_rows)
        with TraceRange("ClusterShuffleExchangeExec.sampleBounds"):
            for map_id in range(child.num_partitions):
                per_map.append(self.runtime.run_sample_task(
                    self, sid, map_id, self.SAMPLE_ROWS_PER_MAP))
            total_rows = sum(t for _d, _v, t in per_map)
            if self.num_out_partitions > 1 and total_rows * max(
                    sum(t.byte_width for t in self.schema.types), 1) \
                    <= self.collapse_bytes:
                # adaptive collapse, cluster edition: a tiny staged
                # input takes ONE partition — no bounds, no range
                # kernel in any map task
                self.num_out_partitions = 1
                self.partitioning = ("single",)
                return
            # weight each map's contribution by its share of the total
            # rows: unweighted merging over-represents small maps and
            # skews the quantile bounds (Spark's RangePartitioner
            # weights per-partition samples the same way)
            merged_d: dict = {n: [] for n in self.schema.names}
            merged_v: dict = {n: [] for n in self.schema.names}
            rng = np.random.default_rng(0x5EED)
            budget = self.SAMPLE_ROWS_PER_MAP * max(len(per_map), 1)
            for d, v, t in per_map:
                have = len(next(iter(d.values()))) if d else 0
                if have == 0:
                    continue
                want = max(int(round(budget * t / max(total_rows, 1))),
                           1)
                idx = np.arange(have) if have <= want else \
                    rng.choice(have, want, replace=False)
                for n in self.schema.names:
                    merged_d[n].append(np.asarray(d[n])[idx])
                    merged_v[n].append(
                        np.asarray(v[n], dtype=bool)[idx])
            data = {n: np.concatenate(a) if a else np.array([])
                    for n, a in merged_d.items()}
            val = {n: np.concatenate(a) if a else np.array([], bool)
                   for n, a in merged_v.items()}
            batch = host_sample_to_batch(data, val, self.schema)
            staged = [SpillableBatch(
                batch, priorities.INPUT_FROM_SHUFFLE_PRIORITY)]
            specs = list(self.partitioning[1])
            types = list(self.schema.types)
            if len(specs) > 1:
                bounds = part_ops.sample_range_bounds_rows(
                    staged, specs, types, self.num_out_partitions)
            else:
                bounds = part_ops.sample_range_bounds_multi(
                    staged, specs, types, self.num_out_partitions)
            for sb in staged:
                sb.close()
        self.partitioning = ("range", specs, bounds)

    def run_map_locally(self, shuffle_id: int, map_id: int,
                        executor_index: int) -> None:
        """Execute one map task in THIS process, writing into the given
        local executor's catalog (RapidsCachingWriter.write)."""
        child = self.children[0]
        parts = run_map_partitions(
            child.execute(map_id), self.partitioning,
            list(self.schema.types), self.num_out_partitions)
        self.runtime.cluster.write_map_output(shuffle_id, map_id,
                                              executor_index, parts)

    def task_payload(self, shuffle_id: int, map_id: int) -> dict:
        """The pickled closure a remote worker executes: child subtree
        with nested cluster exchanges stubbed to reads, plus the
        partitioning spec and the peer address book."""
        return {
            "shuffle_id": shuffle_id,
            "map_id": map_id,
            "subtree": self.runtime.task_tree(self.children[0]),
            "partitioning": self.partitioning,
            "num_out": self.num_out_partitions,
            "types": list(self.schema.types),
            "addresses": self.runtime.addresses(),
        }

    def map_output_sizes(self) -> List[int]:
        """Per-reduce-partition bytes from the cluster tracker's
        MapStatus sizes (the in-process exchange reads its block dict;
        here blocks live in per-executor catalogs across processes) —
        feeds AQE's coalesced reads in cluster mode."""
        sid = self.shuffle_id if self.shuffle_id is not None \
            else self._pending_sid
        sizes = [0] * self.num_out_partitions
        for _mid, (_eid, partitions) in \
                self.runtime.map_outputs_snapshot(sid).items():
            for p, s in partitions.items():
                sizes[int(p)] += int(s)
        return sizes

    def make_read_stub(self) -> ClusterShuffleReadExec:
        sid = self.shuffle_id if self.shuffle_id is not None \
            else self._pending_sid
        assert sid is not None, \
            "make_read_stub before new_shuffle_id registered this exchange"
        maps = self.runtime.map_outputs_snapshot(sid)
        return ClusterShuffleReadExec(
            self.schema, sid, self.num_out_partitions,
            self.children[0].num_partitions, maps,
            self.runtime.addresses())

    # -- reduce side ------------------------------------------------------

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            from spark_rapids_tpu.memory import priorities
            from spark_rapids_tpu.memory.spillable import SpillableBatch
            from spark_rapids_tpu.shuffle import fault_injection

            self._materialize()
            # the reduce entry is a stage boundary too (the map stage
            # ended, the read stage begins) — and it is the boundary
            # where a host death costs the most: every map output is
            # registered, so killing here deterministically drives the
            # full fetch-failure -> recover -> re-run ladder. Counted
            # once per exchange, not per reduce partition.
            with self._mat_lock:
                first_reduce = not self._reduce_stage_counted
                self._reduce_stage_counted = True
            if first_reduce and fault_injection.get_injector() \
                    .should_kill_host_at_stage():
                self.runtime.kill_one_host()
            # stage-retry barrier: buffer the partition so a mid-stream
            # fetch failure can restart the read without duplicating
            # already-yielded batches (Spark re-runs the whole task).
            # Buffered batches are SPILLABLE — a large reduce partition
            # must not pin its full size in HBM while the read drains
            staged: List[SpillableBatch] = []
            budget = max(int(self.runtime.max_stage_retries), 0)
            backoff_s = max(int(self.runtime.retry_backoff_ms), 0) / 1e3
            attempt = 0
            while True:
                stub = self._read_stub
                try:
                    for b in stub.execute(partition):
                        staged.append(SpillableBatch(
                            b, priorities.INPUT_FROM_SHUFFLE_PRIORITY))
                    break
                except ShuffleFetchFailedError as e:
                    for sb in staged:
                        sb.close()
                    staged = []
                    recovery.bump("fetch_failures")
                    if attempt >= budget:
                        # budget exhausted: the ORIGINAL fetch failure
                        # surfaces, chained from its transport cause
                        raise e from (
                            e.cause
                            if isinstance(e.cause, BaseException)
                            else None)
                    if backoff_s:
                        time.sleep(backoff_s * (2 ** attempt))
                    attempt += 1
                    recovery.bump("stage_retries")
                    self.runtime.recover(e)
                    self._read_stub = self.make_read_stub()
            for sb in staged:
                with sb.acquired() as b:
                    yield b
                sb.close()
        return timed(self, it())


class RemoteTaskError(RuntimeError):
    """A task shipped to a remote worker RAN there and failed (the
    worker reported an error reply). Distinct from RuntimeError so the
    scheduler's local re-placement never triggers on driver-side
    failures that merely share the base class."""


class RemoteWorkerHandle:
    """Driver-side handle to one worker process (a separate OS process
    hosting an executor: catalog + TCP shuffle server + task loop).

    Replies are pumped by a daemon reader thread into a queue, which
    buys two liveness properties at once: ``run_map`` can bound its wait
    (``task_timeout`` — a hung worker used to be an infinite
    ``readline``), and ``close`` never deadlocks against a worker
    blocked mid-write on a reply larger than the pipe buffer (the
    thread keeps draining stdout while the driver waits for exit)."""

    def __init__(self, executor_id: str, proc, host: str, port: int,
                 task_timeout: Optional[float] = None):
        import queue

        self.executor_id = executor_id
        self.proc = proc
        self.host = host
        self.port = port
        #: seconds run_map waits for a reply before declaring the worker
        #: hung, killing it, and re-placing the task (None = forever)
        self.task_timeout = task_timeout
        self._lock = lockorder.make_lock("runtime.cluster.worker")
        self._replies: "queue.Queue[Optional[str]]" = queue.Queue()
        self._reader = threading.Thread(
            target=self._drain_stdout,
            name=f"worker-reader-{executor_id}", daemon=True)
        self._reader.start()

    def _drain_stdout(self) -> None:
        try:
            for line in self.proc.stdout:
                self._replies.put(line)
        except (ValueError, OSError):
            pass
        finally:
            self._replies.put(None)  # EOF sentinel: the worker is gone

    @classmethod
    def spawn(cls, executor_id: str, mesh_devices: int = 0,
              task_timeout: Optional[float] = None
              ) -> "RemoteWorkerHandle":
        import os
        import subprocess
        import sys

        env = dict(os.environ)
        # workers compute on CPU: they must not fight over the single
        # attached TPU (a real deployment gives each its own chip)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("XLA_FLAGS", None)
        if mesh_devices >= 2:
            # shipped mesh subtrees reconstruct their mesh from THIS
            # process's devices (parallel/mesh.reconstruct_mesh): give
            # the worker the session's mesh width in virtual devices —
            # ICI collectives inside the task, TCP shuffle between
            # executors (SURVEY §5.8 ICI+DCN composition)
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count"
                                f"={mesh_devices}")
            # the worker also applies this explicitly at startup
            # (remote_worker.main)
            env["SRT_WORKER_MESH_DEVICES"] = str(mesh_devices)
        proc = subprocess.Popen(
            [sys.executable, "-m",
             "spark_rapids_tpu.shuffle.remote_worker"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env,
            text=True)
        proc.stdin.write(
            '{"executor_id": "%s", "mode": "task"}\n' % executor_id)
        proc.stdin.flush()
        # READY is read inline, BEFORE the reader thread exists (the
        # thread starts in __init__), so handshake and reply streams
        # never interleave
        line = proc.stdout.readline().split()
        assert line and line[0] == "READY", line
        return cls(executor_id, proc, line[1], int(line[2]),
                   task_timeout=task_timeout)

    def run_map(self, payload: dict,
                timeout: Optional[float] = None) -> dict:
        """Ship one map task; blocks until the worker reports or the
        liveness timeout expires. Raises ConnectionError on worker
        death or hang (the caller re-runs the task elsewhere)."""
        import json
        import queue

        from spark_rapids_tpu.shuffle import fault_injection

        if fault_injection.get_injector().should_kill_task():
            self.kill()  # injected worker death right before submit
        blob = base64.b64encode(pickle.dumps(payload)).decode()
        budget = self.task_timeout if timeout is None else timeout
        with self._lock:
            try:
                self.proc.stdin.write(
                    json.dumps({"cmd": "run_map", "payload_b64": blob}) +
                    "\n")
                self.proc.stdin.flush()
            except (BrokenPipeError, OSError, ValueError) as e:
                raise ConnectionError(
                    f"worker {self.executor_id} died at submit: {e}")
            try:
                line = self._replies.get(timeout=budget)
            except queue.Empty:
                # hung worker: kill it BEFORE re-placing the task, so a
                # late completion can never double-register its output
                self.kill()
                raise ConnectionError(
                    f"worker {self.executor_id} unresponsive after "
                    f"{budget}s (killed)") from None
        if line is None:
            raise ConnectionError(
                f"worker {self.executor_id} died")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RemoteTaskError(
                f"worker {self.executor_id} task failed: "
                f"{reply.get('error')}")
        return reply

    @property
    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self):
        self.proc.kill()
        self.proc.wait()

    def close(self):
        # the reader thread keeps draining stdout, so a worker blocked
        # writing an oversized reply finishes the write and sees the
        # stdin EOF instead of deadlocking against our wait
        try:
            self.proc.stdin.close()
        except (BrokenPipeError, OSError, ValueError):
            pass
        try:
            self.proc.wait(timeout=5)
        except Exception:
            self.kill()  # always escalate: close() must end the process


class ClusterRuntime:
    """Driver-side cluster state: executors (in-process + worker
    processes), the MapOutputTracker, task assignments for retry, and
    the stage scheduler hooks the cluster exchange calls into."""

    def __init__(self, n_executors: int = 2, n_workers: int = 1,
                 spill_dir: Optional[str] = None,
                 mesh_devices: int = 0,
                 max_stage_retries: int = 3,
                 task_timeout_sec: Optional[float] = 120.0,
                 blacklist_after: int = 3,
                 respawn_workers: bool = True,
                 retry_backoff_ms: int = 50):
        self.cluster = LocalCluster(max(n_executors, 1), transport="tcp",
                                    spill_dir=spill_dir)
        self.mesh_devices = mesh_devices
        self.max_stage_retries = max_stage_retries
        self.task_timeout_sec = task_timeout_sec
        self.blacklist_after = blacklist_after
        self.respawn_workers = respawn_workers
        self.retry_backoff_ms = retry_backoff_ms
        self.workers: List[RemoteWorkerHandle] = []
        for i in range(n_workers):
            w = RemoteWorkerHandle.spawn(f"exec-worker-{i}",
                                         mesh_devices=mesh_devices,
                                         task_timeout=task_timeout_sec)
            self.workers.append(w)
            self.cluster.register_remote_executor(w.executor_id, w.host,
                                                  w.port)
        # consecutive-failure counts + blacklist, per worker SLOT (the
        # generation-free base id: every respawn of exec-worker-1 shares
        # exec-worker-1's record — blacklisting targets the flapping
        # host, not one incarnation of it)
        self._failures: Dict[str, int] = {}
        self.blacklisted: set = set()
        # slots retired by remove_host: never respawned, never targeted
        # — DISTINCT from blacklisting (a decommission is an operator /
        # autoscaler decision, not a fault record)
        self.decommissioned: set = set()
        # next fresh slot index for add_host (existing slots are 0..n-1)
        self._next_slot = n_workers
        # membership-change journal: (action, executor_id, reason)
        self.scale_events: List[dict] = []
        self._sid = itertools.count()
        self._lock = lockorder.make_lock("runtime.cluster.state")
        # serializes fetch-failure recovery against stub rebuilds: the
        # window between invalidating a dead executor's MapStatus and the
        # re-run registering its replacement must not be observable (a
        # snapshot taken inside it would silently drop that map's blocks)
        self._recover_lock = lockorder.make_rlock("runtime.cluster.recover")
        # shuffle_id -> exchange exec (for upstream stage re-runs)
        self.exchanges: Dict[int, ClusterShuffleExchangeExec] = {}
        # shuffle_id -> map_id -> executor_id assignment
        self.assignments: Dict[int, Dict[int, str]] = {}
        self._rr = itertools.count()
        # injectable task placement: fn(shuffle_id, map_id, targets) ->
        # executor_id (or None = fall back to round-robin). Tests and
        # alternative schedulers steer placement through this seam
        # instead of coupling to the round-robin counter internals.
        self.placement_hook = None

    # -- identity ---------------------------------------------------------

    def new_shuffle_id(self, exchange: ClusterShuffleExchangeExec) -> int:
        with self._lock:
            sid = next(self._sid)
            self.exchanges[sid] = exchange
            exchange._pending_sid = sid
            self.assignments[sid] = {}
            return sid

    def addresses(self) -> Dict[str, Tuple[str, int]]:
        out = dict(self.cluster.transport._addrs)
        for w in self.workers:
            out[w.executor_id] = (w.host, w.port)
        return out

    def executor_ids(self) -> List[str]:
        ids = [ex.executor_id for ex in self.cluster.executors]
        ids += [w.executor_id for w in self.workers
                if w.alive and
                self._slot(w.executor_id) not in self.blacklisted and
                self._slot(w.executor_id) not in self.decommissioned]
        return ids

    def live_worker_slots(self) -> List[str]:
        """Distinct worker slots with a live, targetable generation —
        the autoscaler's notion of current cluster size."""
        slots = []
        for w in self.workers:
            slot = self._slot(w.executor_id)
            if w.alive and slot not in self.blacklisted and \
                    slot not in self.decommissioned and \
                    slot not in slots:
                slots.append(slot)
        return slots

    # -- worker supervision (respawn + blacklist) --------------------------

    @staticmethod
    def _slot(executor_id: str) -> str:
        """Generation-free worker slot id: respawns of exec-worker-1 are
        exec-worker-1~1, exec-worker-1~2, ... and all map to the slot."""
        return executor_id.split("~", 1)[0]

    def _note_worker_failure(self, executor_id: str) -> None:
        """Count one liveness failure (submit-time death, task-timeout
        kill, fetch-failure blame) against the worker's slot; the Kth
        consecutive one blacklists it. In-process executors are never
        blacklisted — they are the driver's own catalogs."""
        slot = self._slot(executor_id)
        if not any(self._slot(w.executor_id) == slot
                   for w in self.workers):
            return
        newly = False
        with self._lock:
            n = self._failures.get(slot, 0) + 1
            self._failures[slot] = n
            if self.blacklist_after and n >= self.blacklist_after and \
                    slot not in self.blacklisted:
                self.blacklisted.add(slot)
                newly = True
        if newly:
            recovery.bump("executors_blacklisted")

    def _note_worker_success(self, executor_id: str) -> None:
        with self._lock:
            self._failures[self._slot(executor_id)] = 0

    def _respawn_dead_workers(self) -> None:
        """Supervision sweep: every dead, non-blacklisted worker slot
        with no live generation gets a fresh process (new id, same
        slot), registered with the driver's transport; peers learn the
        address through the address book every task payload and read
        stub carries (``addresses()``). Dead handles stay in
        ``self.workers`` — their ids must keep resolving for blame and
        for tests that index the original list."""
        if not self.respawn_workers:
            return
        for w in list(self.workers):
            if w.alive:
                continue
            slot = self._slot(w.executor_id)
            if slot in self.blacklisted or slot in self.decommissioned:
                continue
            if any(self._slot(o.executor_id) == slot and o.alive
                   for o in self.workers):
                continue
            gen = sum(1 for o in self.workers
                      if self._slot(o.executor_id) == slot)
            try:
                nw = RemoteWorkerHandle.spawn(
                    f"{slot}~{gen}", mesh_devices=self.mesh_devices,
                    task_timeout=self.task_timeout_sec)
            except (OSError, AssertionError, ValueError):
                # the replacement would not even start: that is another
                # strike against the slot
                self._note_worker_failure(slot)
                continue
            self.workers.append(nw)
            self.cluster.register_remote_executor(nw.executor_id,
                                                  nw.host, nw.port)
            recovery.bump("workers_respawned")

    # -- elastic membership (hosts join and leave as recovery events) -----

    def add_host(self, reason: str = "scale-up") -> str:
        """Join a NEW worker host to the running cluster: fresh slot,
        fresh process, registered with the driver's transport so the
        next task placement and every subsequent read stub's address
        book can target it. No stage pauses — the membership change
        rides the same seam recovery uses (serialized under the recover
        lock so a concurrent fetch-failure recovery never observes a
        half-registered host)."""
        with self._recover_lock:
            slot_idx = self._next_slot
            self._next_slot += 1
            eid = f"exec-worker-{slot_idx}"
            w = RemoteWorkerHandle.spawn(
                eid, mesh_devices=self.mesh_devices,
                task_timeout=self.task_timeout_sec)
            self.workers.append(w)
            self.cluster.register_remote_executor(w.executor_id, w.host,
                                                  w.port)
            self.scale_events.append(
                {"action": "add", "executor_id": eid, "reason": reason})
        recovery.bump("hosts_added")
        return eid

    def remove_host(self, executor_id: str,
                    reason: str = "scale-down") -> List[Tuple[int, int]]:
        """Decommission a worker host mid-query, driving the SAME
        lineage ladder a host death does: kill every live generation of
        the slot, invalidate its registered map outputs, and re-run
        exactly the lost maps on the survivors — so reduces that later
        rebuild their stubs read repaired trackers, never the dead
        host. The slot is retired (no respawn, no future placement) but
        NOT blacklisted: leaving on request is not a fault. Returns the
        (shuffle_id, map_id) pairs that re-ran."""
        slot = self._slot(executor_id)
        rerun: List[Tuple[int, int]] = []
        with self._recover_lock:
            self.decommissioned.add(slot)
            gens = [w for w in self.workers
                    if self._slot(w.executor_id) == slot]
            assert gens, f"remove_host: unknown worker slot {slot}"
            for w in gens:
                if w.alive:
                    w.kill()
            gen_ids = {w.executor_id for w in gens}
            with self._lock:
                sids = sorted(self.assignments)
            for sid in sids:
                exchange = self.exchanges.get(sid)
                if exchange is None:
                    continue
                for eid in gen_ids:
                    lost = self.cluster.invalidate_map_output(sid, eid)
                    for map_id in lost:
                        self.run_map_task(exchange, sid, map_id,
                                          exclude=gen_ids)
                        rerun.append((sid, map_id))
            if rerun:
                recovery.bump("maps_rerun", len(rerun))
            self.scale_events.append(
                {"action": "remove", "executor_id": executor_id,
                 "reason": reason, "maps_rerun": len(rerun)})
        recovery.bump("hosts_removed")
        return rerun

    def kill_one_host(self) -> Optional[str]:
        """SIGKILL one live, targetable worker host (the fault
        injector's host-granularity primitive), PREFERRING a host that
        owns registered map output — a load-bearing loss, so the
        deterministic CI kill exercises the recovery ladder instead of
        an idle bystander. Deliberately does NO bookkeeping: recovery
        must discover the death through fetch failures, exactly as
        with a real host loss."""
        owners = {self._slot(eid) for maps in self.assignments.values()
                  for eid in maps.values()}
        candidates = [
            w for w in self.workers
            if w.alive and self._slot(w.executor_id) not in
            self.blacklisted and self._slot(w.executor_id) not in
            self.decommissioned]
        preferred = [w for w in candidates
                     if self._slot(w.executor_id) in owners]
        for w in (preferred or candidates):
            w.kill()
            return w.executor_id
        return None

    # -- task scheduling --------------------------------------------------

    def _place(self, shuffle_id: int, map_id: int,
               targets: List[str]) -> str:
        """Pick the executor for one task: the placement hook decides
        when set (and names a live target); round-robin otherwise —
        the reference gets placement from Spark's scheduler."""
        if self.placement_hook is not None:
            chosen = self.placement_hook(shuffle_id, map_id,
                                         list(targets))
            if chosen is not None and chosen in targets:
                return chosen
        return targets[next(self._rr) % len(targets)]

    def run_map_task(self, exchange: ClusterShuffleExchangeExec,
                     shuffle_id: int, map_id: int,
                     exclude: Optional[set] = None) -> None:
        """Assign + execute one map task."""
        targets = [e for e in self.executor_ids()
                   if not exclude or e not in exclude]
        assert targets, "no live executors"
        target = self._place(shuffle_id, map_id, targets)
        worker = next((w for w in self.workers
                       if w.executor_id == target), None)
        if worker is not None:
            # build the payload OUTSIDE the placement try: task_tree()
            # materializes nested upstream stages driver-side, and a
            # failure there is a query failure, not a placement problem
            payload = exchange.task_payload(shuffle_id, map_id)
            try:
                reply = worker.run_map(payload)
                self.cluster.register_remote_map_output(
                    shuffle_id, map_id, worker.executor_id,
                    reply["partitions"])
                with self._lock:
                    self.assignments[shuffle_id][map_id] = \
                        worker.executor_id
                self._note_worker_success(target)
                return
            except (ConnectionError, BrokenPipeError, OSError) as e:
                # dead or hung worker at SUBMIT time: place locally
                # instead, and count the strike toward its blacklist
                exchange.local_fallbacks.append(
                    f"worker {target} dead at submit: {e}")
                self._note_worker_failure(target)
            except (pickle.PicklingError, TypeError, AttributeError) as e:
                # unpicklable task subtree (cached relations hold locks):
                # this task can only run in-process — local placement,
                # not a query failure
                exchange.local_fallbacks.append(
                    f"unpicklable task subtree: {type(e).__name__}: {e}")
            except RemoteTaskError as e:
                # the worker RAN the task and it failed remotely — e.g. a
                # nested ClusterShuffleReadExec in the shipped subtree hit
                # a fetch failure against a dead peer. Re-place locally
                # (the driver process can recover through its own
                # exchange objects) instead of failing the whole query.
                exchange.local_fallbacks.append(
                    f"remote task failed on {target}, re-placed locally: "
                    f"{e}")
        idx = self._local_index(target)
        exchange.run_map_locally(shuffle_id, map_id, idx)
        with self._lock:
            self.assignments[shuffle_id][map_id] = \
                self.cluster.executors[idx].executor_id

    def run_sample_task(self, exchange: "ClusterShuffleExchangeExec",
                        shuffle_id: int, map_id: int, k: int):
        """Bounds-sampling pass for one map partition: run it remotely
        when its placement slot is a worker, else locally; either way
        return host sample arrays (data, validity)."""
        targets = self.executor_ids()
        target = self._place(shuffle_id, map_id, targets)
        worker = next((w for w in self.workers
                       if w.executor_id == target), None)
        if worker is not None:
            payload = exchange.task_payload(shuffle_id, map_id)
            payload["mode"] = "sample"
            payload["sample_rows"] = k
            try:
                reply = worker.run_map(payload)
                return pickle.loads(
                    base64.b64decode(reply["sample_b64"]))
            except (ConnectionError, BrokenPipeError, OSError,
                    pickle.PicklingError, TypeError, AttributeError,
                    RemoteTaskError) as e:
                exchange.local_fallbacks.append(
                    f"sample task on {target} failed, ran locally: "
                    f"{type(e).__name__}")
        child = exchange.children[0]
        return sample_rows_host(child.execute(map_id), exchange.schema, k)

    def _local_index(self, target: str) -> int:
        for i, ex in enumerate(self.cluster.executors):
            if ex.executor_id == target:
                return i
        return 0  # a worker id that died — fall back to executor 0

    def task_tree(self, node: TpuExec) -> TpuExec:
        """Copy of a task subtree with nested cluster exchanges replaced
        by read stubs (materializing them first): the remote worker
        FETCHES upstream stages instead of recomputing them."""
        import copy

        from spark_rapids_tpu.execs.adaptive import \
            AdaptiveShuffleReaderExec

        if isinstance(node, ClusterShuffleExchangeExec):
            node._materialize()
            return node.make_read_stub()
        if isinstance(node, AdaptiveShuffleReaderExec):
            # resolve the group spec against the LIVE exchange before
            # its child becomes a read stub (stats need the tracker)
            node.groups
        clone = copy.copy(node)
        clone.children = [self.task_tree(c) for c in node.children]
        return clone

    # -- failure recovery (fetch-failure -> stage retry) ------------------

    def map_outputs_snapshot(self, shuffle_id: int
                             ) -> Dict[int, Tuple[str, dict]]:
        """Tracker snapshot for stub building, serialized against
        recovery so it can never observe a half-recovered shuffle."""
        with self._recover_lock:
            return dict(self.cluster._map_outputs.get(shuffle_id, {}))

    def recover(self, err: ShuffleFetchFailedError) -> None:
        """Spark's fetch-failure handling: unregister the dead executor's
        map outputs (for the failed shuffle), then re-run those map tasks
        on the survivors. Concurrent reduce tasks failing on the same
        dead peer serialize here; the second finds nothing left to
        invalidate and just rebuilds its stub from the repaired tracker."""
        dead = err.executor_id
        sid = err.block.shuffle_id
        with self._recover_lock:
            for w in self.workers:
                if w.executor_id == dead and w.alive:
                    w.kill()  # a peer that failed a fetch is not trusted
            self._note_worker_failure(dead)
            self._respawn_dead_workers()
            lost = self.cluster.invalidate_map_output(sid, dead)
            exchange = self.exchanges[sid]
            for map_id in lost:
                self.run_map_task(exchange, sid, map_id, exclude={dead})
            if lost:
                recovery.bump("maps_rerun", len(lost))

    def shutdown(self):
        for w in self.workers:
            w.close()
        self.cluster.shutdown()
        set_executor_context(None)


# -- planner hook ---------------------------------------------------------

_SESSION_RUNTIME: Optional[ClusterRuntime] = None
_RUNTIME_KEY: Optional[tuple] = None


def session_cluster(conf) -> Optional[ClusterRuntime]:
    """Process-cached cluster runtime (like session_mesh): spawning
    worker processes per query would defeat the executor model."""
    from spark_rapids_tpu import config as cfg

    if conf is None or not conf.get(cfg.CLUSTER_ENABLED):
        return None
    global _SESSION_RUNTIME, _RUNTIME_KEY
    mesh_devices = 0
    if conf.get(cfg.MESH_ENABLED):
        from spark_rapids_tpu.parallel.mesh import session_mesh

        m = session_mesh(conf)
        if m is not None:
            # total devices (data * model): workers must be able to
            # reconstruct the full 2-D slice a shipped subtree names
            mesh_devices = int(m.devices.size)
    key = (conf.get(cfg.CLUSTER_EXECUTORS),
           conf.get(cfg.CLUSTER_WORKERS), mesh_devices,
           conf.get(cfg.CLUSTER_MAX_STAGE_RETRIES),
           conf.get(cfg.CLUSTER_TASK_TIMEOUT_SEC),
           conf.get(cfg.CLUSTER_BLACKLIST_AFTER),
           conf.get(cfg.CLUSTER_RESPAWN_WORKERS),
           conf.get(cfg.CLUSTER_RETRY_BACKOFF_MS))
    if _SESSION_RUNTIME is None or _RUNTIME_KEY != key:
        if _SESSION_RUNTIME is not None:
            _SESSION_RUNTIME.shutdown()
        _SESSION_RUNTIME = ClusterRuntime(
            n_executors=key[0], n_workers=key[1],
            mesh_devices=mesh_devices,
            max_stage_retries=key[3], task_timeout_sec=key[4],
            blacklist_after=key[5], respawn_workers=key[6],
            retry_backoff_ms=key[7])
        _RUNTIME_KEY = key
        set_executor_context(ExecutorContext(
            _SESSION_RUNTIME.cluster.executors[0],
            _SESSION_RUNTIME.cluster.transport))
        import atexit

        atexit.register(shutdown_session_cluster)
    return _SESSION_RUNTIME


def active_cluster() -> Optional[ClusterRuntime]:
    """The live session cluster runtime, if one has been built — the
    autoscaler's handle onto the elastic-membership seam (it must never
    CREATE a cluster, only grow one the session already runs)."""
    return _SESSION_RUNTIME


def shutdown_session_cluster() -> None:
    global _SESSION_RUNTIME, _RUNTIME_KEY
    if _SESSION_RUNTIME is not None:
        _SESSION_RUNTIME.shutdown()
        _SESSION_RUNTIME = None
        _RUNTIME_KEY = None


def install_cluster_exchanges(exec_: TpuExec, runtime: ClusterRuntime,
                              _memo: Optional[dict] = None) -> TpuExec:
    """Post-planning pass: swap hash/single exchanges for cluster-backed
    ones (the reference swaps the shuffle manager underneath the same
    exec; here the exec itself is the seam). The rewrite is memoized by
    node identity so a shared exchange (CTE/ReuseExchange) stays ONE
    cluster exchange — every parent reads the same materialized shuffle
    instead of each re-shuffling the shared stage. Adaptive readers work
    ABOVE cluster exchanges: statistics come from ``map_output_sizes``
    (tracker MapStatus sizes) and paired join readers resolve through
    the readers' CURRENT children, so this rewrite flows straight
    through them (GpuOverrides.scala:1874-1887 role). Range exchanges
    run cluster-wide too: the driver aggregates per-map key samples,
    resolves bounds, then ships partition tasks with bounds attached
    (GpuRangePartitioner.scala:42-95's sample-then-partition split)."""
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(exec_))
    if hit is not None:
        return hit[1]
    orig = exec_
    if isinstance(exec_, ShuffleExchangeExec) and \
            not isinstance(exec_, ClusterShuffleExchangeExec) and \
            exec_.partitioning[0] in ("hash", "single", "range"):
        exec_ = ClusterShuffleExchangeExec.wrap(exec_, runtime)
    exec_.children = [install_cluster_exchanges(c, runtime, _memo)
                      for c in exec_.children]
    # pin the original node in the memo value: id() reuse after GC is a
    # known landmine (see memory build-env-quirks)
    _memo[id(orig)] = (orig, exec_)
    return exec_
