"""Exchange execs: shuffle repartitioning and broadcast.

Reference: GpuShuffleExchangeExecBase partitions batches on device then
registers (partId, subBatch) pairs with the caching shuffle writer
(GpuShuffleExchangeExec.scala:146-248, RapidsShuffleInternalManager.scala:
90-155) — sub-batches are catalog-registered and spillable at priority 0;
readers take local device hits zero-copy (RapidsCachingReader.scala:59-145).

Single-process version: the shuffle "transport" is a per-exec block store of
SpillableBatch handles (the local-catalog-hit path). The multi-host bulk
path rides the mesh all_to_all in parallel/shuffle.py.

Blocks end with the query. Every batch an exchange registers goes through
its ``_register`` (counter ``exchange.blocks.registered``), and the root
that ran the plan (``DataFrame.collect()``/``count()``/a write, the query
service's finalize, a standing query's close) calls
:func:`close_query_blocks` once its result is fetched or the plan raised:
every registration of every exchange in the tree is removed from the
catalog, whichever tier it sits on (``exchange.blocks.closed``), and the
exchange is unmaterialized again, so a tree that is executed once more
runs its map side once more. Only the exchange's own registrations go:
a batch that a ``CachedExec`` also registered (a cache filled from these
blocks) stays the cache's, arrays and all.
"""
from __future__ import annotations

import threading
from spark_rapids_tpu.utils import lockorder
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.execs.base import TpuExec, all_execs, timed
from spark_rapids_tpu.memory import priorities
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu.memory.spillable import SpillableBatch
from spark_rapids_tpu.ops import partition as part_ops
from spark_rapids_tpu.ops.concat import concat_batches
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.utils.tracing import TraceRange, count


def partition_batch(b: ColumnarBatch, partitioning: Tuple, types,
                    num_out: int) -> Tuple[ColumnarBatch, np.ndarray]:
    """Partition one batch: returns (destination-sorted batch, per-
    partition counts). Shared by the in-process exchange and the cluster
    runtime's map tasks (local and remote-worker alike)."""
    kind = partitioning[0]
    if kind == "hash":
        return part_ops.hash_partition(b, list(partitioning[1]), types,
                                       num_out)
    if kind == "round_robin":
        return part_ops.round_robin_partition(b, num_out)
    if kind == "range":
        specs: List[SortKeySpec] = list(partitioning[1])
        bounds = partitioning[2]
        if len(specs) > 1:
            return part_ops.range_partition_multi(b, specs, types,
                                                  bounds, num_out)
        return part_ops.range_partition(b, specs, types, bounds, num_out)
    if kind == "single":
        return part_ops.single_partition(b)
    raise ValueError(kind)


class ShuffleExchangeExec(TpuExec):
    """partitioning: ('hash', key_ordinals) | ('range', specs) |
    ('round_robin',) | ('single',).

    ``('single',)`` is a gather, not a shuffle: every map task's batches
    become the one output partition's blocks as they are, in partition
    order (``_gather``; the span ``ShuffleExchangeExec.gather``, one a
    batch). The planner puts it under a global limit, a global sort or an
    unpartitioned window over several partitions, and (since PR 28)
    between the partial and the final aggregate of every session without
    mesh or cluster: one process then holds all partitions on its device,
    so routing partials by key would only hash, sort, cut and copy them.
    The other kinds move rows: partition kernel, one ``_slice_rows``
    launch a batch, a block a destination (``.partition`` >
    ``.partitionKernel``, ``.slice``, ``.register``)."""

    def __init__(self, partitioning: Tuple, num_out_partitions: int,
                 child: TpuExec, task_threads: int = 1,
                 batch_bytes: Optional[int] = None):
        super().__init__([child], child.schema)
        self.partitioning = partitioning
        self.num_out_partitions = num_out_partitions
        # bound for the range-exchange tiny-input collapse: the staged
        # input must fit ONE configured batch for a single sort task to
        # be the right plan (conf batchSizeBytes when the planner wires
        # it; capped by the spill chunk budget either way)
        self.collapse_bytes = min(
            self.CHUNK_BYTE_BUDGET,
            batch_bytes if batch_bytes is not None
            else self.CHUNK_BYTE_BUDGET)
        # default 1 (serial): concurrency is an OPT-IN the planner wires
        # from rapids.tpu.sql.taskThreads — unplumbed construction sites
        # (python-UDF exchanges running arbitrary user code, tests) must
        # not silently multithread
        self.task_threads = task_threads
        # block store: output partition -> spillable sub-batches
        self._blocks: Optional[Dict[int, List[SpillableBatch]]] = None
        # every registration this exchange made for the execution in
        # flight, blocks and staged range input alike, whether or not the
        # map side got as far as ``_blocks``: what ``close_blocks`` removes
        self._registered: List[SpillableBatch] = []
        # in-program mode (SPMD whole-stage exchange): the map side runs
        # as ONE compiled hash-route + all_to_all program over the mesh
        # instead of per-batch partition kernels + per-partition slices.
        # apply_overrides flips this on for eligible hash exchanges via
        # enable_in_program(); parallel/spmd.py owns the eligibility
        # decision and records every "no" with a reason.
        self.in_program = False
        self._in_program_mesh = None
        # AQE skew spec (parallel.spmd.SkewSpec) — when set, the
        # in-program map side detects hot reduce partitions host-side
        # (the input is already gathered for the collective) and salts
        # them across the device axis before the all_to_all
        self._skew_spec = None
        # reduce tasks run on concurrent threads; the map side must
        # materialize exactly once (Spark serializes this via stage
        # boundaries — here a lock is the stage barrier). A condition
        # rather than a bare lock: the in-program path runs its device
        # program OUTSIDE the lock (no device transfer while a
        # framework lock is held) and late arrivals wait on it.
        self._mat_lock = lockorder.make_condition(
            "exchange.shuffle.materialize")
        self._mat_running = False

    # an exchange shipping inside a remote task closure restarts clean:
    # blocks are per-process state (the receiving executor re-runs or
    # cluster-reads; cluster exchanges are stubbed out before pickling)
    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_mat_lock", None)
        state["_mat_running"] = False
        state["_blocks"] = None
        state["_registered"] = []
        # meshes are process-local device handles; a shipped exchange
        # re-decides on the receiving side (cluster mode shuffles over
        # TCP anyway — the spmd gate never enables both)
        state["in_program"] = False
        state["_in_program_mesh"] = None
        state["_skew_spec"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._mat_lock = lockorder.make_condition(
            "exchange.shuffle.materialize")

    def enable_in_program(self, mesh, skew=None) -> None:
        """Switch the map side to the compiled all_to_all program over
        ``mesh``. Partition count and per-row partition assignment are
        unchanged (the step reproduces the host partition kernel's pid
        exactly), so consumers — including a co-partitioned sibling
        exchange that stays on the host path — see identical blocks.

        ``skew`` (a parallel.spmd.SkewSpec) arms AQE salting: reduce
        partitions whose measured map-output bytes exceed the skew cut
        are spread across ALL devices by the collective instead of
        landing on ``pid % n_dev`` — the pid column is untouched, only
        the routing changes, so the per-partition blocks sliced after
        the collective are still exact."""
        assert self.partitioning[0] == "hash", self.partitioning
        assert self._blocks is None, "already materialized"
        from spark_rapids_tpu.parallel import spmd

        # per-exchange seam record (the plan-time gate records the
        # decision; this records an exchange actually ARMED onto it)
        spmd.record_seam("exchange", spmd.SEAM_ICI,
                         "in-program all_to_all armed over the "
                         "session mesh slice")
        self.in_program = True
        self._in_program_mesh = mesh
        self._skew_spec = skew

    @property
    def num_partitions(self) -> int:
        # range exchanges replan adaptively: the first partition-count
        # query OUTSIDE planning (collect's pre-execution walk)
        # materializes the map side, and _materialize collapses to ONE
        # partition when the staged input fits a single batch budget —
        # a global sort over a final aggregate's handful of rows must
        # not pay bounds sampling + range partitioning + N sort tasks
        # (AQE's materialize-then-replan, applied to the sort stage).
        if self.partitioning[0] == "range" and self._blocks is None:
            from spark_rapids_tpu.execs import adaptive as adaptive_exec

            if not adaptive_exec.planning_active():
                self._materialize()
        return self.num_out_partitions

    def _register(self, batch: ColumnarBatch, priority: int,
                  **kw) -> SpillableBatch:
        """The one way this exchange puts a batch into the catalog."""
        sb = SpillableBatch(batch, priority, **kw)
        self._registered.append(sb)      # map tasks append concurrently
        count("exchange.blocks.registered")
        return sb

    def close_blocks(self) -> None:
        """Remove every registration of the execution that just ended
        (or failed half way) from the catalog and forget the map side:
        the next ``execute`` materializes again. A handle a reader still
        holds acquired goes at its release (``BufferCatalog.remove``)."""
        registered, self._registered = self._registered, []
        self._blocks = None
        closed = sum(sb.close() for sb in registered)
        if closed:
            count("exchange.blocks.closed", closed)

    def _partition_batch(self, b: ColumnarBatch
                         ) -> Tuple[ColumnarBatch, np.ndarray]:
        return partition_batch(b, self.partitioning,
                               list(self.schema.types),
                               self.num_out_partitions)

    def _materialize(self) -> None:
        """Map-side write: run the child once, cache partitioned blocks
        (RapidsCachingWriter.write). Child partitions run as concurrent
        map tasks on the task pool (device entry gated by the shared
        TpuSemaphore inside the execs). Range partitioning with
        unresolved bounds stages the input (spillable) and samples bounds
        host-side first — the reference runs a separate sampling pass the
        same way (GpuRangePartitioner.scala:42-95)."""
        if self.in_program and self._in_program_mesh is not None:
            self._materialize_in_program_once()
            if self._blocks is not None:
                return
            # a device error degraded this exchange (in_program is now
            # False): fall through to the host/TCP path, once per query
        with self._mat_lock:
            if self._blocks is not None:
                return
            if self.partitioning[0] == "range" and \
                    (len(self.partitioning) < 3 or
                     self.partitioning[2] is None):
                from spark_rapids_tpu.execs.base import run_partitions

                def stage_task(in_p: int):
                    return [self._register(
                        b, priorities.INPUT_FROM_SHUFFLE_PRIORITY)
                        for b in self.children[0].execute(in_p)
                        if b.realized_num_rows() > 0]

                staged = [sb for part in run_partitions(
                    self.children[0].num_partitions, stage_task,
                    self.task_threads) for sb in part]
                total_rows = sum(sb.num_rows for sb in staged)
                row_bytes = max(sum(t.byte_width
                                    for t in self.schema.types), 1)
                if self.num_out_partitions > 1 and \
                        total_rows * row_bytes <= self.collapse_bytes:
                    # adaptive collapse: tiny staged input -> single
                    # partition, no bounds sampling, no partition kernel
                    self.num_out_partitions = 1
                    self._blocks = {0: staged}
                    return
                specs = list(self.partitioning[1])
                if len(specs) > 1:
                    bounds = part_ops.sample_range_bounds_rows(
                        staged, specs, list(self.schema.types),
                        self.num_out_partitions)
                else:
                    bounds = part_ops.sample_range_bounds_multi(
                        staged, specs, list(self.schema.types),
                        self.num_out_partitions)
                self.partitioning = ("range", self.partitioning[1],
                                     bounds)
                source = self._drain_staged(staged)
                blocks = self._write_blocks(source)
            else:
                from spark_rapids_tpu.execs.base import run_partitions

                def map_task(in_p: int):
                    # realize lazy counts in bounded chunks so an
                    # out-of-core child never has its whole partition
                    # resident at once — each chunk's batches move into
                    # spillable blocks before the next is read. The
                    # chunk boundary is a BYTE budget estimated from
                    # host-known capacities (no sync to compute), so an
                    # in-core partition of many small batches still pays
                    # its single realize_counts round trip
                    out: Dict[int, List[SpillableBatch]] = {
                        p: [] for p in range(self.num_out_partitions)}
                    chunk: List[ColumnarBatch] = []
                    chunk_bytes = 0

                    def flush():
                        nonlocal chunk_bytes
                        if self.partitioning[0] == "single" and \
                                len(chunk) == 1:
                            # a gather of a map task's one batch (an
                            # aggregate's partials) decides nothing by
                            # its count: it stays on the device, and
                            # the consumer's one sync reads every map
                            # task's (a consumer that fetched a count a
                            # batch would pay what this did, no more)
                            self._write_blocks(chunk, into=out)
                        else:
                            ColumnarBatch.realize_counts(chunk)
                            self._write_blocks(
                                (b for b in chunk
                                 if b.realized_num_rows() > 0), into=out)
                        chunk.clear()
                        chunk_bytes = 0

                    for b in self.children[0].execute(in_p):
                        chunk.append(b)
                        chunk_bytes += \
                            b.capacity * max(b.num_columns, 1) * 8
                        if chunk_bytes >= self.CHUNK_BYTE_BUDGET:
                            flush()
                    if chunk:
                        flush()
                    return out

                # merge per-map outputs in PARTITION order, not thread
                # completion order: float aggregates downstream must see
                # a deterministic batch order or a recomputed shared
                # subtree (tpch q15's revenue view) sums to a different
                # last-ulp value than its sibling
                outs = run_partitions(self.children[0].num_partitions,
                                      map_task, self.task_threads)
                blocks = {p: [] for p in range(self.num_out_partitions)}
                for out in outs:
                    for p, subs in out.items():
                        blocks[p].extend(subs)
            self._blocks = blocks

    # estimated resident bytes a map task may stage before realizing
    # counts and moving the chunk into spillable blocks
    CHUNK_BYTE_BUDGET = 256 << 20

    def _materialize_in_program_once(self) -> None:
        """Single-flight wrapper for the in-program map side: the
        compiled program and its host<->device transfers run OUTSIDE
        the materialize lock (holding a framework lock across a device
        transfer stalls every sibling reduce task for the transfer's
        full RTT); late arrivals wait on the condition instead of
        re-running the program."""
        with self._mat_lock:
            while self._mat_running:
                self._mat_lock.wait()
            # a waiter wakes to either a materialized exchange or one
            # the leader DEGRADED (in_program cleared) — both mean the
            # in-program attempt is over for this query
            if self._blocks is not None or not self.in_program:
                return
            self._mat_running = True
        blocks = None
        try:
            blocks = self._materialize_in_program()
        except Exception as e:
            from spark_rapids_tpu.parallel import spmd

            if not spmd.is_degradable_device_error(e):
                raise
            # SPMD degrade: a device error inside the compiled exchange
            # program falls back to the host/TCP path for this stage —
            # once per query (in_program stays off) — instead of
            # failing the query on a path that has a lossless fallback
            from spark_rapids_tpu.runtime import recovery

            spmd.record_degrade("exchange")
            recovery.bump("spmd_degrades")
            self.in_program = False
            self._in_program_mesh = None
        finally:
            with self._mat_lock:
                self._mat_running = False
                if blocks is not None and self._blocks is None:
                    self._blocks = blocks
                self._mat_lock.notify_all()

    def _materialize_in_program(self) -> Dict[int, List[SpillableBatch]]:
        """Map-side write over the mesh: stage child rows once, run ONE
        compiled hash-route + ``all_to_all`` program, slice each
        device's received block into that partition's store. Three
        dispatches total (staging gather, the program, result gather)
        regardless of batch or partition count — the host path pays a
        partition kernel per batch plus a slice per partition."""
        import jax
        from spark_rapids_tpu.memory.fault_injection import get_injector
        from spark_rapids_tpu.parallel import shuffle as pshuffle
        from spark_rapids_tpu.parallel.mesh import DATA_AXIS

        # deterministic degrade fence: the OOM injector can fail this
        # site (InjectedOOM classifies as a device error) so the
        # SPMD-degrade path runs on CPU CI without a real XLA fault
        get_injector().maybe_inject("exchange.inProgram")
        mesh = self._in_program_mesh
        n_dev = mesh.shape[DATA_AXIS]
        num_out = self.num_out_partitions
        types = list(self.schema.types)
        blocks: Dict[int, List[SpillableBatch]] = {
            p: [] for p in range(num_out)}
        batches = list(self._input_batches())
        ColumnarBatch.realize_counts(batches)
        batches = [b for b in batches if b.realized_num_rows() > 0]
        if not batches:
            return blocks
        # ONE host gather for every staged batch's columns (pytree get);
        # device_get returns host ndarrays, so everything below is pure
        # numpy with no further transfers
        host = jax.device_get(
            [[(c.data, c.validity) for c in b.columns] for b in batches])
        ns = [b.realized_num_rows() for b in batches]
        arrays, valids = [], []
        for ci in range(len(types)):
            arrays.append(np.concatenate(
                [host[bi][ci][0][:n] for bi, n in enumerate(ns)]))
            valids.append(np.concatenate(
                [np.ones(n, dtype=bool) if host[bi][ci][1] is None
                 else host[bi][ci][1][:n]
                 for bi, n in enumerate(ns)]))
        salt = self._salt_pids(arrays, valids, types)
        datas, vs, counts = pshuffle.distributed_batch_from_host(
            mesh, arrays, types, validities=valids)[:3]
        step = pshuffle.shuffle_step(mesh, types,
                                     list(self.partitioning[1]), num_out,
                                     salt_pids=salt)
        with TraceRange("ShuffleExchangeExec.all_to_all"):
            out_d, out_v, pids, recv = step(datas, vs, counts)
        hd, hv, hp, hn = jax.device_get(
            (list(out_d), list(out_v), pids, recv))
        rcap = len(hd[0]) // n_dev
        from spark_rapids_tpu.ops.buckets import bucket_capacity
        from spark_rapids_tpu.columnar.column import Column

        for d in range(n_dev):
            k = int(hn[d])
            if k == 0:
                continue
            seg = slice(d * rcap, d * rcap + k)
            seg_pids = hp[seg]
            # split the device's compacted block into per-partition
            # sub-blocks (pure numpy — no extra dispatch). Unsalted,
            # device d holds exactly the pids with pid % n_dev == d;
            # a SALTED pid arrives on every device, so enumerate the
            # pids actually present instead of the modular ladder
            for p in np.unique(seg_pids):
                p = int(p)
                idx = np.nonzero(seg_pids == p)[0]
                cap = bucket_capacity(len(idx))
                cols = [Column.from_numpy(
                    hd[ci][seg][idx], t,
                    validity=hv[ci][seg][idx],
                    capacity=cap) for ci, t in enumerate(types)]
                blocks[p].append(self._register(
                    ColumnarBatch(cols, len(idx)),
                    priorities.OUTPUT_FOR_SHUFFLE_PRIORITY))
        return blocks

    def _salt_pids(self, arrays, valids, types) -> Tuple[int, ...]:
        """Hot reduce-partition ids for the in-program map side, from a
        host mirror of the device partition hash over the already-
        gathered input. Empty when skew handling is off or nothing
        crosses the cut. Capped at 16 pids (largest first) — the salt
        set is a compile-time constant of the shuffle program and an
        unbounded set would fragment the program cache."""
        spec = self._skew_spec
        if spec is None or not arrays or not len(arrays[0]):
            return ()
        from spark_rapids_tpu.execs import adaptive as adaptive_exec
        from spark_rapids_tpu.ops import hashing

        pids = hashing.host_partition_ids(
            arrays, valids, types, list(self.partitioning[1]),
            self.num_out_partitions)
        row_bytes = max(sum(t.byte_width + 1 for t in types), 1)
        sizes = np.bincount(
            pids, minlength=self.num_out_partitions) * row_bytes
        stats = adaptive_exec.MapOutputStatistics(
            [int(s) for s in sizes])
        hot = stats.skewed_partitions(spec.factor, spec.threshold)
        if not hot:
            return ()
        hot = sorted(hot, key=lambda p: -sizes[p])[:16]
        for p in sorted(hot):
            adaptive_exec.record_replan(
                "skew_salt", f"partition {p} salted across mesh")
        return tuple(sorted(hot))

    def _write_blocks(self, source, into=None
                      ) -> Dict[int, List[SpillableBatch]]:
        blocks: Dict[int, List[SpillableBatch]] = into if into is not None \
            else {p: [] for p in range(self.num_out_partitions)}
        if self.partitioning[0] == "single":
            self._gather(source, blocks[0])
            return blocks
        for b in source:
            with TraceRange("ShuffleExchangeExec.partition"):
                with TraceRange("ShuffleExchangeExec.partitionKernel"):
                    sorted_b, counts = self._partition_batch(b)
                # one compiled program a batch and output capacity
                # (ColumnarBatch.slices): no eager launch under this span
                with TraceRange("ShuffleExchangeExec.slice"):
                    subs = part_ops.slice_partitions(sorted_b, counts)
                with TraceRange("ShuffleExchangeExec.register"):
                    for p, sub in enumerate(subs):
                        if sub is None:
                            continue
                        blocks[p].append(self._register(
                            sub, priorities.OUTPUT_FOR_SHUFFLE_PRIORITY))
        return blocks

    def _gather(self, source, block: List[SpillableBatch]) -> None:
        """``("single",)`` moves no rows: the batch a map task is handed
        IS its block, with no partition kernel, no slice, no launch and
        no transfer. Who owns the arrays: a batch fresh from its producer
        (an aggregate's partials, a limit's cut, a projection's output)
        is handed over, and the block's registration is its only one. A
        batch that a catalog entry already owns (a ``CachedExec``'s or an
        upstream exchange's block, pulled straight through by a global
        sort or limit) stays its owner's to spill and to close: a second
        registration would count its bytes twice and spill it as if that
        freed memory, so such a batch keeps the copy that every batch
        got before (one ``_slice_rows`` launch), and the copy is the
        block's alone."""
        catalog = get_catalog()
        for b in source:
            if isinstance(b.num_rows, int) and b.num_rows == 0:
                continue
            with TraceRange("ShuffleExchangeExec.gather"):
                if catalog.owns(b):
                    b = b.slice(0, b.realized_num_rows())
                block.append(self._register(
                    b, priorities.OUTPUT_FOR_SHUFFLE_PRIORITY,
                    defer_count=True))

    def map_output_sizes(self) -> List[int]:
        """Per-reduce-partition byte sizes of the materialized map output
        (MapStatus sizes; cluster exchanges answer from the tracker)."""
        assert self._blocks is not None, "materialize first"
        return [sum(h.device_memory_size() for h in self._blocks[p])
                for p in range(self.num_out_partitions)]

    def _input_batches(self):
        for in_p in range(self.children[0].num_partitions):
            for b in self.children[0].execute(in_p):
                # a host sync a batch: the count decides whether it is sent
                with TraceRange("ShuffleExchangeExec.inputRows"):
                    n = b.realized_num_rows()
                if n == 0:
                    continue
                yield b

    @staticmethod
    def _drain_staged(staged: List[SpillableBatch]):
        for sb in staged:
            with sb.acquired() as b:
                yield b
            if sb.close():
                count("exchange.blocks.closed")

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            self._materialize()
            handles = self._blocks[partition]
            if not handles:
                yield ColumnarBatch.empty(self.schema)
                return
            for h in handles:
                with h.acquired() as batch:
                    yield batch
        return timed(self, it())


class BroadcastExchangeExec(TpuExec):
    """Materializes the whole child once as a single batch, replayed to
    every consumer partition (GpuBroadcastExchangeExec.scala:237-380; the
    cached batch is spillable like the reference's host-serialized form)."""

    def __init__(self, child: TpuExec):
        super().__init__([child], child.schema)
        self._cached: Optional[SpillableBatch] = None
        self._mat_lock = lockorder.make_lock("exchange.broadcast.materialize")

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_mat_lock", None)
        state["_cached"] = None  # re-materializes on the receiving side
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._mat_lock = lockorder.make_lock("exchange.broadcast.materialize")

    @property
    def num_partitions(self) -> int:
        return 1

    @property
    def coalesce_after(self):
        from spark_rapids_tpu.execs.batching import RequireSingleBatch

        return RequireSingleBatch

    def _materialize(self) -> SpillableBatch:
        with self._mat_lock:
            return self._materialize_locked()

    def _materialize_locked(self) -> SpillableBatch:
        if self._cached is None:
            batches = []
            for p in range(self.children[0].num_partitions):
                batches.extend(self.children[0].execute(p))
            if len(batches) > 1:
                # one batched realize for ALL counts (was one host sync
                # per child batch), then drop empties before the concat
                ColumnarBatch.realize_counts(batches)
                batches = [b for b in batches
                           if b.realized_num_rows() > 0]
            if len(batches) == 1:
                # single batch: no concat, and the count can stay a
                # lazy device scalar — build prep consumes it as a
                # traced operand, so the whole broadcast+prep path
                # runs without a host sync of its own
                merged = batches[0]
            elif batches:
                merged = concat_batches(batches)
            else:
                merged = ColumnarBatch.empty(self.schema)
            self._cached = SpillableBatch(
                merged, priorities.INPUT_FROM_SHUFFLE_PRIORITY,
                defer_count=True)
            count("exchange.blocks.registered")
        return self._cached

    def close_blocks(self) -> None:
        """As ``ShuffleExchangeExec.close_blocks``: the broadcast batch's
        registration goes, the next ``execute`` materializes again."""
        cached, self._cached = self._cached, None
        if cached is not None and cached.close():
            count("exchange.blocks.closed")

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        def it():
            with self._materialize().acquired() as batch:
                yield batch
        return timed(self, it())


def close_query_blocks(root: TpuExec) -> None:
    """What the root of a query calls when its result is fetched or the
    plan raised: every exchange of the tree that ran (``all_execs``)
    closes its blocks."""
    for e in all_execs(root):
        if isinstance(e, (ShuffleExchangeExec, BroadcastExchangeExec)):
            e.close_blocks()
