"""Buffer catalog + tiered device→host→disk spill stores.

Re-design of RapidsBufferCatalog (RapidsBufferCatalog.scala:109: global
id→buffer map with acquire/ref-count), the RapidsBufferStore chain
(RapidsBufferStore.scala:39-88: per-store priority-ordered spill to the next
tier, wired device→host→disk at RapidsBufferCatalog.scala:132-137), the
bounded host store (RapidsHostMemoryStore.scala;
rapids.tpu.memory.host.spillStorageSize) and the disk store
(RapidsDiskStore.scala).

TPU adaptations:
- Buffers are whole ``ColumnarBatch``es (JAX arrays); XLA owns physical HBM,
  so the device "store" tracks logical bytes against a configurable budget
  rather than owning allocations.
- Device→host spill is ``jax.device_get`` into a ``HostBatch``; host→disk
  writes the serde wire format (serde.py) — the same bytes shuffle and
  broadcast use, like the reference reuses TableMeta/JCudfSerialization.
- Unspill on acquire copies back up the chain (RapidsBufferStore.scala's
  ``getColumnarBatch`` from a spilled tier).

Thread-safe: one lock guards the maps (the reference uses a ConcurrentHashMap
plus per-store synchronization; our operations are coarse enough for one
lock — spill IO happens outside it only for disk writes).
"""
from __future__ import annotations

import enum
import itertools
import logging
import os
import queue
import tempfile
import threading
from spark_rapids_tpu.utils import lockorder
from typing import Dict, Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar import serde
from spark_rapids_tpu.memory.hashed_pq import HashedPriorityQueue

log = logging.getLogger(__name__)


class SpillCorruptionError(RuntimeError):
    """A disk-tier spill file failed to decode (truncation, checksum
    mismatch, bad envelope). Raised instead of handing a kernel garbage
    data; chains the underlying decode error."""


class StorageTier(enum.IntEnum):
    """Where a buffer currently lives (StorageTier analogue)."""

    DEVICE = 0
    HOST = 1
    DISK = 2


class _Entry:
    __slots__ = ("buffer_id", "priority", "tier", "device_batch",
                 "host_batch", "disk_path", "size", "refcount", "seq",
                 "pending_remove", "owner", "bias")

    def __init__(self, buffer_id: int, priority: int, batch: ColumnarBatch,
                 size: int, seq: int, owner=None):
        self.buffer_id = buffer_id
        self.priority = priority
        self.tier = StorageTier.DEVICE
        self.device_batch: Optional[ColumnarBatch] = batch
        self.host_batch: Optional[serde.HostBatch] = None
        self.disk_path: Optional[str] = None
        self.size = size
        self.refcount = 0
        self.seq = seq
        self.pending_remove = False
        # owner tag (query id) + spill-priority bias: the query service
        # demotes buffers of queued/stalled queries so pressure evicts
        # the tenant that is NOT running (SpillPriorities aging analogue)
        self.owner = owner
        self.bias = 0

    def spill_key(self):
        return (self.priority + self.bias, self.seq)


# Thread-local buffer-ownership tag: the stage scheduler brackets each
# query slice with set_buffer_owner(query_id) so every batch the slice
# registers is attributable to its query — demotable while the query is
# stalled, removable wholesale on cancel/deadline.
_owner_tls = threading.local()


def set_buffer_owner(owner) -> object:
    """Set this thread's registration owner tag; returns the previous
    tag for restore (None = untagged)."""
    prev = getattr(_owner_tls, "owner", None)
    _owner_tls.owner = owner
    return prev


def current_buffer_owner():
    return getattr(_owner_tls, "owner", None)


class AsyncBatchWriter:
    """Bounded-queue single-thread async commit template (the PR 6
    double-buffered spill writer, generalized): the caller keeps
    computing while one writer thread processes submitted items. The
    bounded queue (depth 2 by default) is the double buffer — one item
    in flight, one staged — and doubles as backpressure: a storm of
    submissions blocks the submitter instead of queueing unbounded
    host memory. Subclasses implement ``_process`` (writer-thread
    body) and may override ``_on_error`` (must not raise); the
    host->disk spill path and the streaming checkpoint writer
    (service/streaming/durability.py) are the two instantiations."""

    _STOP = object()

    def __init__(self, cv: "threading.Condition", thread_name: str,
                 depth: int = 2):
        # the subclass makes the condition with a LITERAL lockorder
        # name at its own site, so the hierarchy stays statically
        # checkable (tpulint TPU303)
        self._q: "queue.Queue" = queue.Queue(maxsize=max(depth, 1))
        self._pending = 0
        self._cv = cv
        self._thread: Optional[threading.Thread] = None
        self._thread_name = thread_name

    def _process(self, item) -> None:
        raise NotImplementedError

    def _on_error(self, item, exc: BaseException) -> None:
        log.exception("async writer %s failed processing %r",
                      self._thread_name, item)

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name=self._thread_name, daemon=True)
            self._thread.start()

    def submit(self, item) -> None:
        with self._cv:
            self._pending += 1
            self._ensure_thread()
        self._q.put(item)  # blocks at depth: the backpressure point

    def pending(self) -> int:
        with self._cv:
            return self._pending

    def _loop(self) -> None:
        while True:
            e = self._q.get()
            if e is self._STOP:
                return
            try:
                self._process(e)
            except Exception as exc:  # noqa: BLE001 - must not kill the writer
                self._on_error(e, exc)
            finally:
                with self._cv:
                    self._pending -= 1
                    self._cv.notify_all()

    def drain(self) -> None:
        """Block until every submitted item committed (or aborted)."""
        with self._cv:
            while self._pending:
                self._cv.wait()

    def stop(self) -> None:
        """Drain, then end the writer thread — without this the parked
        queue.get() would pin the thread (and whatever the subclass
        references) for the life of the process."""
        self.drain()
        with self._cv:
            t = self._thread
        if t is None or not t.is_alive():
            return
        self._q.put(self._STOP)
        t.join(timeout=5.0)


class _AsyncSpillWriter(AsyncBatchWriter):
    """Double-buffered host->disk eviction (mirrors PR 1's upload
    pipeline, inverted): victims are catalog entries; processing is
    the same serialize+compress+commit as the inline spill path."""

    def __init__(self, catalog: "BufferCatalog", depth: int = 2):
        super().__init__(
            lockorder.make_condition("memory.catalog.spillWriter"),
            "srt-spill-writer", depth)
        self._catalog = catalog

    def _process(self, entry: "_Entry") -> None:
        self._catalog._finish_async_spill(entry)

    def _on_error(self, entry: "_Entry", exc: BaseException) -> None:
        log.exception("async host->disk spill of buffer %d failed; "
                      "entry stays on the host tier", entry.buffer_id)


class BufferCatalog:
    """id→buffer map + spill orchestration across the three tiers."""

    def __init__(self, device_budget: Optional[int] = None,
                 host_budget: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 disk_codec: str = "lz4",
                 async_spill: bool = False):
        self.disk_codec = disk_codec
        # host->disk eviction path: async (double-buffered writer
        # thread, compute overlaps the compressed write) or inline.
        # Default inline: unit tests and short-lived catalogs want
        # deterministic tier transitions; runtime.initialize flips it
        # on from rapids.tpu.memory.spill.asyncWrite.enabled.
        self.async_spill = async_spill
        self._writer: Optional[_AsyncSpillWriter] = None
        self._spilling_bytes = 0  # submitted to the writer, uncommitted
        self._lock = lockorder.make_rlock("memory.catalog.state")
        self._entries: Dict[int, _Entry] = {}
        self._ids = itertools.count(1)
        self._seq = itertools.count()
        self.device_budget = device_budget
        self.host_budget = host_budget
        self._spill_dir = spill_dir
        self._device_bytes = 0
        self._host_bytes = 0
        # per-tier spill-victim queues keyed by (priority, seq): O(log n)
        # victim selection instead of full scans (HashedPriorityQueue.java
        # analogue). Entries are queued only while refcount == 0.
        self._queues = {t: HashedPriorityQueue() for t in StorageTier}
        # id(device batch) -> its entry, while the entry holds it on the
        # device: what ``owns`` asks (the entry keeps the batch alive, so
        # the id is its own for as long as it is a key here)
        self._by_device_batch: Dict[int, _Entry] = {}
        # owner tag -> live entries: the query service biases/removes a
        # query's buffers once per stage slice, which must not scan the
        # whole catalog
        self._owners: Dict[object, set] = {}
        # sticky per-owner bias: set_owner_bias applies to entries the
        # owner registers LATER too (an out-of-core query keeps its
        # eager-spill bias for its whole life, not just for buffers
        # that existed when the scheduler set it)
        self._owner_bias: Dict[object, int] = {}
        self.spilled_device_bytes = 0  # task-metric accounting
        self.spilled_host_bytes = 0

    # -- registration / lifecycle ----------------------------------------

    def register(self, batch: ColumnarBatch, priority: int) -> int:
        """Add a device batch under catalog management; returns its id.
        (RapidsDeviceMemoryStore.addTable analogue.)"""
        size = batch.device_memory_size()
        with self._lock:
            bid = next(self._ids)
            e = _Entry(bid, priority, batch, size, next(self._seq),
                       owner=current_buffer_owner())
            self._entries[bid] = e
            self._by_device_batch[id(batch)] = e
            if e.owner is not None:
                self._owners.setdefault(e.owner, set()).add(e)
                e.bias = self._owner_bias.get(e.owner, 0)
            self._device_bytes += size
            self._queues[StorageTier.DEVICE].push(e, e.spill_key())
        self._maybe_spill_async()
        return bid

    def acquire(self, buffer_id: int) -> ColumnarBatch:
        """Ref-count acquire; unspills to device if needed
        (RapidsBufferCatalog.acquireBuffer, RapidsBufferCatalog.scala:44-55).
        The buffer cannot spill while refcount > 0."""
        with self._lock:
            e = self._entries.get(buffer_id)
            if e is None:
                raise KeyError(f"buffer {buffer_id} not in catalog")
            e.refcount += 1
            if e.refcount == 1:
                self._queues[e.tier].remove(e)  # pinned: not a victim
        try:
            return self._ensure_device(e)
        except BaseException:
            with self._lock:
                e.refcount -= 1
                if e.refcount == 0 and buffer_id in self._entries:
                    self._requeue(e)
            raise

    def release(self, buffer_id: int) -> None:
        path = None
        with self._lock:
            e = self._entries.get(buffer_id)
            if e is None:
                return
            e.refcount -= 1
            assert e.refcount >= 0
            if e.pending_remove and e.refcount == 0:
                self._entries.pop(buffer_id, None)
                self._drop_indexes(e)
                self._drop_tier_bytes(e)
                path = e.disk_path
            elif e.refcount == 0:
                self._requeue(e)
        if path and os.path.exists(path):
            os.unlink(path)

    def remove(self, buffer_id: int) -> None:
        """Drop the buffer from all tiers (RapidsBufferCatalog.removeBuffer).
        If the buffer is currently acquired (e.g. mid-unspill), removal is
        deferred until the last release so concurrent acquirers don't lose
        the backing file under them."""
        with self._lock:
            e = self._entries.get(buffer_id)
            if e is None:
                return
            if e.refcount > 0:
                e.pending_remove = True
                return
            self._entries.pop(buffer_id, None)
            self._drop_indexes(e)
            self._queues[e.tier].remove(e)
            self._drop_tier_bytes(e)
            path = e.disk_path
        if path and os.path.exists(path):
            os.unlink(path)

    def update_priority(self, buffer_id: int, priority: int) -> None:
        with self._lock:
            e = self._entries.get(buffer_id)
            if e is not None:
                e.priority = priority
                if e in self._queues[e.tier]:
                    self._queues[e.tier].update(e, e.spill_key())

    # -- per-owner control (query service hooks) --------------------------

    def _drop_indexes(self, e: "_Entry") -> None:
        """Called under lock when an entry leaves ``_entries``."""
        self._unindex_device_batch(e)
        if e.owner is not None:
            peers = self._owners.get(e.owner)
            if peers is not None:
                peers.discard(e)
                if not peers:
                    self._owners.pop(e.owner, None)

    def _unindex_device_batch(self, e: "_Entry") -> None:
        """Called under lock before ``e`` lets go of its device batch. Two
        entries may hold one batch (a cache filled from an exchange's
        blocks): the key is the later one's, and only it takes it away."""
        b = e.device_batch
        if b is not None and self._by_device_batch.get(id(b)) is e:
            del self._by_device_batch[id(b)]

    def set_owner_bias(self, owner, bias: int) -> int:
        """Re-bias the spill priority of every buffer registered under
        ``owner`` (negative bias -> spills earlier). The stage scheduler
        demotes stalled queries' batches with this so memory pressure
        evicts the tenant that is NOT on the device. Returns the number
        of entries touched."""
        n = 0
        with self._lock:
            if bias:
                self._owner_bias[owner] = bias
            else:
                self._owner_bias.pop(owner, None)
            for e in self._owners.get(owner, ()):
                if e.bias == bias:
                    continue
                e.bias = bias
                if e in self._queues[e.tier]:
                    self._queues[e.tier].update(e, e.spill_key())
                n += 1
        return n

    def owner_refcounts(self, owner) -> Dict[int, int]:
        """{buffer_id: refcount} of live entries registered under
        ``owner`` — the leak probe cancel/deadline tests assert on."""
        with self._lock:
            return {e.buffer_id: e.refcount
                    for e in self._owners.get(owner, ())}

    def owner_bytes(self, owner) -> int:
        with self._lock:
            return sum(e.size for e in self._owners.get(owner, ()))

    def remove_owner(self, owner) -> int:
        """Drop every buffer registered under ``owner`` from all tiers
        (deferred for entries currently acquired, like remove()). The
        query service's cancel/deadline cleanup: an abandoned exec tree
        must not leak its staged shuffle/broadcast batches."""
        with self._lock:
            ids = [e.buffer_id for e in self._owners.get(owner, ())]
            self._owner_bias.pop(owner, None)
        for bid in ids:
            self.remove(bid)
        return len(ids)

    # -- introspection ----------------------------------------------------

    def tier_of(self, buffer_id: int) -> StorageTier:
        with self._lock:
            return self._entries[buffer_id].tier

    def size_of(self, buffer_id: int) -> int:
        with self._lock:
            return self._entries[buffer_id].size

    def owns(self, batch: ColumnarBatch) -> bool:
        """Whether ``batch`` (by identity) is the device batch of an
        entry: its bytes are counted already, and its owner may spill or
        remove it."""
        with self._lock:
            return id(batch) in self._by_device_batch

    @property
    def device_bytes(self) -> int:
        return self._device_bytes

    @property
    def host_bytes(self) -> int:
        return self._host_bytes

    def __contains__(self, buffer_id: int) -> bool:
        with self._lock:
            return buffer_id in self._entries

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- spill machinery --------------------------------------------------

    def synchronous_spill(self, target_device_bytes: int) -> int:
        """Spill device buffers (lowest priority first, FIFO within equal
        priority) until tracked device bytes <= target. Returns bytes
        spilled. (RapidsBufferStore.synchronousSpill analogue.)"""
        spilled = 0
        while True:
            with self._lock:
                if self._device_bytes <= target_device_bytes:
                    return spilled
                victim = self._pick_spill_victim(StorageTier.DEVICE)
                if victim is None:
                    return spilled  # everything pinned
            spilled += self._spill_device_entry(victim)

    def spill_host_to_disk(self, target_host_bytes: int) -> int:
        if self.async_spill:
            return self._spill_host_to_disk_async(target_host_bytes)
        spilled = 0
        while True:
            with self._lock:
                if self._host_bytes <= target_host_bytes:
                    return spilled
                victim = self._pick_spill_victim(StorageTier.HOST)
                if victim is None:
                    return spilled
            spilled += self._spill_host_entry(victim)

    def _spill_host_to_disk_async(self, target_host_bytes: int) -> int:
        """Hand victims to the writer thread until host bytes MINUS the
        in-flight submissions reach the target, then return — the
        compressed writes land while the caller computes. Returns bytes
        submitted (an upper bound on bytes that will commit; a raced
        acquire can still rescue a victim)."""
        submitted = 0
        while True:
            with self._lock:
                if self._host_bytes - self._spilling_bytes \
                        <= target_host_bytes:
                    return submitted
                victim = self._pick_spill_victim(StorageTier.HOST)
                if victim is None:
                    return submitted
                self._spilling_bytes += victim.size
                if self._writer is None:
                    self._writer = _AsyncSpillWriter(self)
                writer = self._writer
            writer.submit(victim)
            submitted += victim.size

    def _finish_async_spill(self, e: "_Entry") -> None:
        """Writer-thread body: the same serialize+compress+commit as
        the inline path, then retire the in-flight accounting. A lost
        race (acquire/remove rescued the entry) leaves it at its
        current tier; if it is still an unpinned host victim it gets
        requeued by the release path as usual."""
        try:
            self._spill_host_entry(e)
        finally:
            with self._lock:
                self._spilling_bytes -= e.size

    def flush_spills(self) -> None:
        """Barrier for the async eviction pipeline: returns when every
        submitted host->disk write committed. Tests and shutdown paths
        use it; the hot path never waits here."""
        with self._lock:
            writer = self._writer
        if writer is not None:
            writer.drain()

    def close(self) -> None:
        """Quiesce the catalog's background machinery: drain pending
        disk writes and END the writer thread. A catalog being retired
        (runtime shutdown, test teardown) must not leave a parked
        daemon thread pinning it in memory; the catalog stays usable —
        a later spill lazily restarts the writer."""
        with self._lock:
            writer, self._writer = self._writer, None
        if writer is not None:
            writer.stop()

    def spill_all_device(self) -> int:
        return self.synchronous_spill(0)

    def _pick_spill_victim(self, tier: StorageTier) -> Optional[_Entry]:
        """Called under lock. Min (priority, seq) unpinned entry in
        tier — POPPED from its queue; the spill paths (or the release
        path after a raced acquire) requeue it at its landing tier."""
        return self._queues[tier].pop()

    def _requeue(self, e: _Entry) -> None:
        """Called under lock with refcount == 0: (re-)expose the entry
        as a spill victim at its current tier."""
        q = self._queues[e.tier]
        if e not in q:
            q.push(e, e.spill_key())

    def _spill_device_entry(self, e: _Entry) -> int:
        batch = e.device_batch
        if batch is None:
            return 0
        hb = serde.to_host_batch(batch)  # D2H outside lock
        with self._lock:
            if e.buffer_id not in self._entries or \
                    e.tier is not StorageTier.DEVICE or e.refcount > 0:
                return 0  # raced with remove/acquire
            e.host_batch = hb
            self._unindex_device_batch(e)
            e.device_batch = None
            e.tier = StorageTier.HOST
            self._device_bytes -= e.size
            self._host_bytes += e.size
            self.spilled_device_bytes += e.size
            self._requeue(e)  # now a host-tier victim
        # host store may itself now exceed budget → cascade to disk
        if self.host_budget is not None:
            self.spill_host_to_disk(self.host_budget)
        return e.size

    def _spill_host_entry(self, e: _Entry) -> int:
        with self._lock:
            hb = e.host_batch
            if e.buffer_id not in self._entries or \
                    e.tier is not StorageTier.HOST or hb is None or \
                    e.refcount > 0:
                return 0
        from spark_rapids_tpu.columnar import compression

        data = compression.wrap(serde.serialize_host_batch(hb),
                                self.disk_codec)
        path = os.path.join(self._ensure_spill_dir(),
                            f"spill-{e.buffer_id}.srt")
        with open(path, "wb") as f:
            f.write(data)
        with self._lock:
            if e.buffer_id not in self._entries or \
                    e.tier is not StorageTier.HOST or e.refcount > 0:
                # lost the race; never unlink a path another spill committed
                if e.disk_path != path:
                    os.unlink(path)
                return 0
            e.disk_path = path
            e.host_batch = None
            e.tier = StorageTier.DISK
            self._host_bytes -= e.size
            self.spilled_host_bytes += e.size
            self._requeue(e)  # disk entries stay tracked (removal)
        return e.size

    def _ensure_device(self, e: _Entry) -> ColumnarBatch:
        """Unspill up the chain if needed; caller holds a refcount."""
        with self._lock:
            if e.tier is StorageTier.DEVICE:
                return e.device_batch
            hb = e.host_batch
            path = e.disk_path
            tier = e.tier
        if tier is StorageTier.DISK:
            from spark_rapids_tpu.columnar import compression

            try:
                with open(path, "rb") as f:
                    hb = serde.deserialize_host_batch(
                        compression.unwrap(f.read()))
            except Exception as exc:
                # a truncated/bit-flipped spill file must fail loudly
                # here, not surface as garbage rows in a kernel
                raise SpillCorruptionError(
                    f"disk spill for buffer {e.buffer_id} at {path} "
                    f"is unreadable: {exc}") from exc
        batch = serde.to_device_batch(hb)
        with self._lock:
            if e.buffer_id not in self._entries:
                return batch  # removed mid-unspill: hand back untracked
            if e.tier is not StorageTier.DEVICE:
                if e.tier is StorageTier.HOST:
                    self._host_bytes -= e.size
                e.device_batch = batch
                self._by_device_batch[id(batch)] = e
                e.host_batch = None
                e.tier = StorageTier.DEVICE
                self._device_bytes += e.size
            return e.device_batch

    def _drop_tier_bytes(self, e: _Entry) -> None:
        if e.tier is StorageTier.DEVICE:
            self._device_bytes -= e.size
        elif e.tier is StorageTier.HOST:
            self._host_bytes -= e.size

    def _maybe_spill_async(self) -> None:
        """Budget enforcement on register: spill synchronously if over.
        (The reference spills from the RMM alloc-failed callback; we spill
        eagerly at the logical budget since XLA gives no callback.)"""
        if self.device_budget is not None and \
                self._device_bytes > self.device_budget:
            self.synchronous_spill(self.device_budget)

    def _ensure_spill_dir(self) -> str:
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="srt-spill-")
        else:
            os.makedirs(self._spill_dir, exist_ok=True)
        return self._spill_dir


_global_catalog: Optional[BufferCatalog] = None
_global_lock = lockorder.make_lock("memory.catalog.global")


def get_catalog() -> BufferCatalog:
    """Singleton catalog (RapidsBufferCatalog.init semantics,
    RapidsBufferCatalog.scala:128-142); configured lazily from RapidsConf
    at first use by the engine session."""
    global _global_catalog
    with _global_lock:
        if _global_catalog is None:
            _global_catalog = BufferCatalog()
        return _global_catalog


def reset_catalog(catalog: Optional[BufferCatalog] = None) -> BufferCatalog:
    global _global_catalog
    with _global_lock:
        _global_catalog = catalog if catalog is not None else BufferCatalog()
        return _global_catalog
