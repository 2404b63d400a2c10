"""Out-of-core ingest: the bounded-depth async scan pipeline.

ScanExec's per-split read becomes a prefetching producer/consumer chain
(the multi-threaded reader architecture of GpuMultiFileReader.scala, run
per split instead of per file):

- **pruning decides before any byte moves**: the split layout the
  sources advertise (io/parquet.py row groups, io/orc.py stripes) is
  already pruned by footer statistics, and this module only accounts
  the on-disk bytes that survived vs. the bytes pruning skipped;
- **an IO thread pool** streams a split's chunks (row groups / stripes)
  off the filesystem and packs them into :class:`~.interop.PackedHost`
  parts — pure host work, off the task thread;
- **double-buffered upload**: the consumer issues slice ``k+1``'s
  ``device_put`` before yielding slice ``k`` (the PR 6/PR 19
  ``AsyncBatchWriter`` template run in reverse), so the transfer
  hides behind the current batch's compute;
- **backpressure**: queued packed slices are bounded by
  ``rapids.tpu.io.scan.prefetch.depth`` and their host bytes charge the
  service admission budget (``admission_bytes``), so prefetch cannot
  silently overcommit memory the admission ledger thinks is free;
- **spillable landing** (``rapids.tpu.io.scan.landing.spillable``):
  scan results register as snapshot-versioned ``SpillableBatch``es in a
  scan cache keyed on the split identity + per-file ``(mtime_ns,
  size)`` — a re-scan of unchanged files hits warm device/host/disk
  tiers instead of the filesystem.

Slice boundaries are computed by a re-slicing accumulator and are
therefore IDENTICAL regardless of chunk granularity or prefetch depth —
``prefetch.depth=0`` (fully synchronous, no threads) is the
byte-identity reference path the ingest fence compares against, and
float aggregation order downstream never shifts with the pipeline
configuration.
"""
from __future__ import annotations

import os
import queue as _queue
import threading
import time
from typing import Optional

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.utils import lockorder
from spark_rapids_tpu.utils.tracing import TraceRange

# ---------------------------------------------------------------------------
# telemetry: the io.scan block (bytes read/pruned, decode/h2d seconds,
# overlap fraction) — same snapshot/delta idiom as utils/dispatch
# ---------------------------------------------------------------------------

_stats_lock = lockorder.make_lock("io.scanpipe.stats")

_counters = {
    "bytes_read": 0,          # on-disk bytes of chunks actually read
    "bytes_pruned": 0,        # on-disk bytes pruning skipped pre-read
    "chunks_read": 0,         # row groups / stripes / files read
    "chunks_pruned": 0,
    "splits_read": 0,
    "slices_uploaded": 0,
    "bytes_uploaded": 0,      # host bytes of the slices handed to device_put
    "decode_s": 0.0,          # host read + pack seconds (both paths)
    "h2d_s": 0.0,             # device_put issue seconds
    "prefetch_busy_s": 0.0,   # producer-thread busy seconds (async only)
    "prefetch_wait_s": 0.0,   # consumer blocked on the queue (async only)
    "pushdown_filters": 0,    # conjuncts the planner planted on sources
    "cache_hits": 0,
    "cache_misses": 0,
}
#: {(format, reason): [chunks, bytes]} — sources that cannot prune
#: (CSV has no footer stats, ORC files may lack stripe statistics)
#: record WHY, so bytes-read accounting stays honest across formats.
_unprunable: dict = {}
_inflight_bytes = 0


def record_pruned(fmt: str, chunks: int, nbytes: int) -> None:
    """A source pruned ``chunks`` chunks (``nbytes`` on disk) by footer
    statistics before any read."""
    with _stats_lock:
        _counters["chunks_pruned"] += int(chunks)
        _counters["bytes_pruned"] += int(nbytes)


def record_unprunable(fmt: str, reason: str, chunks: int,
                      nbytes: int) -> None:
    """A source had pushed-down filters but no statistics to prune with
    — the explicit complement of ``record_pruned``."""
    with _stats_lock:
        ent = _unprunable.setdefault((fmt, reason), [0, 0])
        ent[0] += int(chunks)
        ent[1] += int(nbytes)


def record_pushdown(n: int) -> None:
    """The planner planted ``n`` pruning conjuncts on a file source."""
    with _stats_lock:
        _counters["pushdown_filters"] += int(n)


def _bump(**kw) -> None:
    with _stats_lock:
        for k, v in kw.items():
            _counters[k] += v


def _add_inflight(nbytes: int) -> None:
    global _inflight_bytes
    with _stats_lock:
        _inflight_bytes = max(_inflight_bytes + int(nbytes), 0)


def inflight_bytes() -> int:
    """Host bytes of packed slices queued but not yet uploaded."""
    with _stats_lock:
        return _inflight_bytes


def admission_bytes() -> int:
    """Bytes this subsystem holds that the admission ledger must see:
    queued prefetch slices (host) + device-resident scan-cache
    landings. The query service adds this to its ``extra_bytes_fn``."""
    return inflight_bytes() + cache_device_bytes()


def snapshot() -> dict:
    with _stats_lock:
        out = dict(_counters)
        out["unprunable"] = {f"{fmt}:{reason}": (c, b)
                             for (fmt, reason), (c, b)
                             in _unprunable.items()}
        return out


def delta(before: dict) -> dict:
    """The ``io.scan`` telemetry block accumulated since ``before`` (a
    ``snapshot()``): byte/chunk counts, decode vs h2d seconds, and the
    measured scan–compute overlap fraction — the share of producer
    (read+pack) seconds hidden behind consumer compute, ``None`` when
    no async scan ran in the window."""
    now = snapshot()
    d = {k: round(now[k] - before.get(k, 0), 6)
         if isinstance(now[k], float) else now[k] - before.get(k, 0)
         for k in _counters}
    unp = {}
    for k, (c, b) in now["unprunable"].items():
        pc, pb = before.get("unprunable", {}).get(k, (0, 0))
        if c - pc or b - pb:
            unp[k] = {"chunks": c - pc, "bytes": b - pb}
    d["unprunable"] = unp
    busy = d["prefetch_busy_s"]
    wait = d["prefetch_wait_s"]
    d["overlap_fraction"] = (
        round(max(0.0, min(1.0, (busy - wait) / busy)), 4)
        if busy > 1e-9 else None)
    return d


def reset_stats() -> None:
    """Zero every counter (tests)."""
    global _inflight_bytes
    with _stats_lock:
        for k in _counters:
            _counters[k] = 0.0 if isinstance(_counters[k], float) else 0
        _unprunable.clear()
        _inflight_bytes = 0


# ---------------------------------------------------------------------------
# scan cache: snapshot-versioned spillable landing
# ---------------------------------------------------------------------------

_cache_lock = lockorder.make_lock("io.scanpipe.cache")
_cache: "dict[tuple, _CacheEntry]" = {}
_CACHE_MAX_ENTRIES = 256


class _CacheEntry:
    __slots__ = ("versions", "spillables", "catalog", "pins", "dead")

    def __init__(self, versions, spillables, catalog):
        self.versions = versions
        self.spillables = list(spillables)
        self.catalog = catalog
        self.pins = 0       # readers currently serving from this entry
        self.dead = False   # superseded/invalidated while pinned


def _close_entry(entry: "_CacheEntry") -> None:
    for sb in entry.spillables:
        try:
            sb.close()
        except Exception:  # catalog reset/closed under us: nothing to free
            pass


def _canon_desc(desc) -> tuple:
    """Hashable identity of one split descriptor, independent of how
    splits were packed into partitions."""
    from spark_rapids_tpu.io.filesrc import PackedSplit

    if isinstance(desc, PackedSplit):
        return ("#packed",) + tuple(_canon_desc(m) for m in desc.members)
    if isinstance(desc, str):
        return ("#file", desc)
    path = getattr(desc, "path", None)
    sub = getattr(desc, "row_groups", None)
    if sub is None:
        sub = getattr(desc, "stripes", None)
    return ("#chunks", path, tuple(sub or ()))


def _desc_paths(desc) -> list:
    from spark_rapids_tpu.io.filesrc import PackedSplit

    if isinstance(desc, PackedSplit):
        out = []
        for m in desc.members:
            out.extend(_desc_paths(m))
        return out
    if isinstance(desc, str):
        return [desc]
    p = getattr(desc, "path", None)
    return [p] if p else []


def _cache_key(exec_, partition: int):
    """(key, file-version vector) for one scan partition, or (None,
    None) when the source is unkeyable or a file vanished — then
    nothing lands (staleness must never be a guess)."""
    from spark_rapids_tpu.service.cache import snapshots

    source = exec_.source
    ident = snapshots.source_identity(source)
    if ident is None:
        return None, None
    descs = source.splits()
    if not descs:
        return None, None
    desc = descs[partition]
    paths = sorted(set(_desc_paths(desc)))
    versions = snapshots.file_versions(paths)
    if versions is None:
        return None, None
    key = (ident, int(getattr(source, "_snap_version", 0)),
           _canon_desc(desc), int(exec_.batch_rows), bool(exec_.pack))
    return key, (tuple(paths), versions)


def _cache_lookup(key, versions) -> Optional["_CacheEntry"]:
    """Pin and return a live, version-matching entry; invalidate and
    miss otherwise."""
    from spark_rapids_tpu.memory.catalog import get_catalog

    with _cache_lock:
        entry = _cache.get(key)
        if entry is None:
            _bump(cache_misses=1)
            return None
        stale = entry.versions != versions
        if entry.catalog is not get_catalog():
            # the catalog was reset under us: its buffers are gone, do
            # not try to close through the dead handle
            _cache.pop(key, None)
            _bump(cache_misses=1)
            return None
        if stale:
            _cache.pop(key, None)
            if entry.pins == 0:
                _close_entry(entry)
            else:
                entry.dead = True
            _bump(cache_misses=1)
            return None
        entry.pins += 1
        _bump(cache_hits=1)
        return entry


def _unpin(entry: "_CacheEntry") -> None:
    with _cache_lock:
        entry.pins -= 1
        if entry.dead and entry.pins == 0:
            _close_entry(entry)


def _cache_publish(key, versions, spillables, catalog) -> None:
    entry = _CacheEntry(versions, spillables, catalog)
    with _cache_lock:
        old = _cache.pop(key, None)
        if old is not None:
            if old.pins == 0:
                _close_entry(old)
            else:
                old.dead = True
        _cache[key] = entry
        while len(_cache) > _CACHE_MAX_ENTRIES:
            victim_key = next((k for k, e in _cache.items()
                               if e.pins == 0), None)
            if victim_key is None:
                break
            _close_entry(_cache.pop(victim_key))


def cache_device_bytes() -> int:
    """Device-tier bytes currently held by scan-cache landings."""
    from spark_rapids_tpu.memory.catalog import StorageTier

    with _cache_lock:
        entries = [(e.catalog, sb) for e in _cache.values()
                   for sb in e.spillables]
    total = 0
    for catalog, sb in entries:
        try:
            if catalog.tier_of(sb.buffer_id) == StorageTier.DEVICE:
                total += sb.device_memory_size()
        except Exception:
            continue
    return total


def cache_len() -> int:
    with _cache_lock:
        return len(_cache)


def clear_cache() -> None:
    """Drop every landed entry, closing catalog registrations (tests,
    and the explicit invalidation hook)."""
    with _cache_lock:
        entries = list(_cache.values())
        _cache.clear()
        for e in entries:
            if e.pins == 0:
                _close_entry(e)
            else:
                e.dead = True


# ---------------------------------------------------------------------------
# the re-slicing accumulator: chunk stream -> exact batch_rows slices
# ---------------------------------------------------------------------------


def _host_rows(data, schema) -> int:
    if not len(schema):
        return 0
    return len(data[schema.names[0]])


def _slice_host(data, validity, schema, start, end):
    d, v = {}, {}
    for name in schema.names:
        d[name] = data[name][start:end]
        vv = validity.get(name)
        v[name] = None if vv is None else vv[start:end]
    return d, v


class _SliceAccum:
    """Accumulates host chunks and emits slices of EXACTLY
    ``batch_rows`` rows (remainder only at end-of-split): batch
    boundaries match the read-everything-then-slice layout bit for bit,
    whatever the chunk granularity underneath."""

    def __init__(self, schema, batch_rows: int):
        self.schema = schema
        self.batch_rows = batch_rows
        self._parts: list = []
        self._rows = 0
        self.total = 0

    def add(self, part) -> None:
        n = _host_rows(part[0], self.schema)
        if n == 0:
            return
        self._parts.append(part)
        self._rows += n
        self.total += n

    def pop_slices(self, final: bool = False) -> list:
        """Drain every complete slice (plus the remainder when
        ``final``) as a list of (data, validity) views."""
        from spark_rapids_tpu.io import arrow_conv

        if self._rows < self.batch_rows and not (final and self._rows):
            return []
        if len(self._parts) == 1:
            data, validity = self._parts[0]
        else:
            data, validity = arrow_conv.concat_host(self._parts,
                                                    self.schema)
        n_full = self._rows // self.batch_rows
        out = []
        for i in range(n_full):
            out.append(_slice_host(data, validity, self.schema,
                                   i * self.batch_rows,
                                   (i + 1) * self.batch_rows))
        rem = self._rows - n_full * self.batch_rows
        if rem and final:
            out.append(_slice_host(data, validity, self.schema,
                                   n_full * self.batch_rows, self._rows))
            rem = 0
        if rem:
            tail = _slice_host(data, validity, self.schema,
                               self._rows - rem, self._rows)
            self._parts = [tail]
        else:
            self._parts = []
        self._rows = rem
        return out


# ---------------------------------------------------------------------------
# the IO pool (read + pack off the task thread)
# ---------------------------------------------------------------------------

_io_pool = None


def _get_io_pool(conf):
    """Shared producer pool: every running producer's consumer is
    blocked draining it, so each submitted producer terminates and
    queued ones always get a slot — saturation serializes, never
    deadlocks."""
    global _io_pool
    with _stats_lock:
        if _io_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _io_pool = ThreadPoolExecutor(
                max_workers=max(
                    int(conf.get(cfg.MULTIFILE_READ_THREADS)), 2),
                thread_name_prefix="scan-io")
        return _io_pool


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------


def _pack_slices(source, exec_, partition, stats, emit):
    """Producer body shared by both paths: stream the split's chunks,
    re-slice, pack; ``emit(packed)`` returns False to stop early.
    Returns (total_rows, busy_seconds)."""
    from spark_rapids_tpu.execs import interop

    schema = exec_.schema
    acc = _SliceAccum(schema, exec_.batch_rows)
    busy = 0.0
    # duck-typed sources (test doubles, third-party) may predate the
    # chunked-read contract; the whole split as one chunk is always
    # equivalent
    chunk_fn = getattr(source, "read_host_chunks", None)
    chunks = chunk_fn(partition) if chunk_fn is not None else \
        iter([source.read_host_split(partition)])

    def flush(final):
        nonlocal busy
        t0 = time.perf_counter()
        slices = acc.pop_slices(final=final)
        busy += time.perf_counter() - t0
        for data, validity in slices:
            t0 = time.perf_counter()
            with TraceRange("ScanExec.pack"):
                p = interop.pack_host(data, validity, schema, 0,
                                      _host_rows(data, schema),
                                      stats=stats, pack=exec_.pack)
            busy += time.perf_counter() - t0
            if not emit(p):
                return False
        return True

    while True:
        t0 = time.perf_counter()
        try:
            chunk = next(chunks)
        except StopIteration:
            busy += time.perf_counter() - t0
            break
        busy += time.perf_counter() - t0
        _bump(chunks_read=1)
        acc.add(chunk)
        if not flush(final=False):
            return acc.total, busy
    flush(final=True)
    return acc.total, busy


def scan_iter(exec_, partition: int):
    """The body of ScanExec.execute: yields uploaded batches for one
    scan partition through the prefetch pipeline (or the synchronous
    reference path at depth 0), serving/landing the scan cache when
    enabled."""
    from spark_rapids_tpu.memory import semaphore

    source = exec_.source
    schema = exec_.schema
    conf = getattr(source, "conf", None) or cfg.DEFAULT_CONF
    depth = max(int(conf.get(cfg.SCAN_PREFETCH_DEPTH)), 0)
    land = bool(conf.get(cfg.SCAN_LANDING_SPILLABLE)) and \
        not exec_.defer_decode
    key = versions = None
    if land:
        key, versions = _cache_key(exec_, partition)
        land = key is not None
    if land:
        entry = _cache_lookup(key, versions)
        if entry is not None:
            try:
                with semaphore.get():
                    for sb in entry.spillables:
                        b = sb.get_batch()
                        try:
                            yield b
                        finally:
                            sb.release()
            finally:
                _unpin(entry)
            return

    nbytes_fn = getattr(source, "split_nbytes", None)
    _bump(splits_read=1,
          bytes_read=int(nbytes_fn(partition)) if nbytes_fn else 0)
    origin = source.split_origin(partition)
    stats = source.split_stats(partition)
    landing = _Landing() if land else None
    published = False
    try:
        if depth == 0:
            yielded = yield from _scan_sync(exec_, partition, stats,
                                            origin, landing)
        else:
            yielded = yield from _scan_async(exec_, partition, stats,
                                             origin, depth, landing,
                                             conf)
        if land and yielded:
            from spark_rapids_tpu.memory.catalog import get_catalog

            landing.release_upto(len(landing.handles))
            _cache_publish(key, versions, landing.handles,
                           get_catalog())
            published = True
    finally:
        if landing is not None and not published:
            # abandoned (limit / downstream error) or nothing landed:
            # drop pins first so close() is not deferred forever behind
            # a refcount nobody will release
            landing.release_upto(len(landing.handles))
            for sb in landing.handles:
                try:
                    sb.close()
                except Exception:
                    pass


class _Landing:
    """Scan-cache landing in progress: the SpillableBatch handles plus
    a monotonic pin cursor. Each landed batch is registered with one
    acquire held (the active downstream input must not be a spill
    victim); the cursor releases each pin exactly once, in yield
    order, as the next batch takes over."""

    __slots__ = ("handles", "_released")

    def __init__(self):
        self.handles: list = []
        self._released = 0

    def land(self, batch) -> None:
        from spark_rapids_tpu.memory import priorities
        from spark_rapids_tpu.memory.catalog import set_buffer_owner
        from spark_rapids_tpu.memory.spillable import SpillableBatch

        prev = set_buffer_owner("io.scan")
        try:
            sb = SpillableBatch(batch, priorities.SCAN_CACHE_PRIORITY)
        finally:
            set_buffer_owner(prev)
        sb.get_batch()  # pin: active downstream input
        self.handles.append(sb)

    def release_upto(self, upto: int) -> None:
        upto = min(upto, len(self.handles))
        while self._released < upto:
            try:
                self.handles[self._released].release()
            except Exception:
                pass
            self._released += 1


def _scan_sync(exec_, partition, stats, origin, landing):
    """depth=0: fully synchronous read -> pack -> upload on the caller
    thread — the byte-identity reference path."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.execs import interop
    from spark_rapids_tpu.memory import semaphore

    source = exec_.source
    packed: list = []

    def emit(p):
        packed.append(p)
        return True

    # read+pack the whole split first (no overlap by design), then
    # upload under the semaphore exactly like the pre-pipeline scan
    t0 = time.perf_counter()
    total, busy = _pack_slices(source, exec_, partition, stats, emit)
    _bump(decode_s=time.perf_counter() - t0)
    if total == 0:
        yield ColumnarBatch.empty(exec_.schema)
        return False
    n_done = 0
    with semaphore.get():
        for p in packed:
            t0 = time.perf_counter()
            with TraceRange("ScanExec.upload"):
                b = interop.upload_packed(
                    p, defer_decode=exec_.defer_decode)
            _bump(h2d_s=time.perf_counter() - t0, slices_uploaded=1,
                  bytes_uploaded=p.nbytes())
            b.origin = origin
            if landing is not None:
                landing.land(b)
            yield b
            n_done += 1
            if landing is not None:
                landing.release_upto(n_done - 1)
    return True


def _scan_async(exec_, partition, stats, origin, depth, landing, conf):
    """depth>=1: producer (IO pool) reads+packs ahead through a bounded
    queue; the consumer issues slice k+1's device_put before yielding
    slice k."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.execs import interop
    from spark_rapids_tpu.memory import semaphore

    source = exec_.source
    q: "_queue.Queue" = _queue.Queue(maxsize=depth)
    stop = threading.Event()
    done_evt = threading.Event()

    def put(item) -> bool:
        """Bounded put that re-checks ``stop`` — a consumer that
        abandons the scan (limit, downstream error) must not leave the
        producer blocked forever pinning packed slices."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except _queue.Full:
                continue
        return False

    def produce():
        try:
            def emit(p):
                # charge the admission budget while the packed slice
                # sits in the queue; refunded at dequeue (or here when
                # the consumer already stopped us)
                nbytes = p.nbytes()
                _add_inflight(nbytes)
                if not put(("packed", p)):
                    _add_inflight(-nbytes)
                    return False
                return True

            total, busy = _pack_slices(source, exec_, partition, stats,
                                       emit)
            _bump(decode_s=busy, prefetch_busy_s=busy)
            put(("done", total))
        except BaseException as e:  # surface in the consumer
            put(("error", e))
        finally:
            done_evt.set()

    _get_io_pool(conf).submit(produce)
    pending = None
    n_done = 0
    try:
        with semaphore.get():
            while True:
                t0 = time.perf_counter()
                kind, val = q.get()
                _bump(prefetch_wait_s=time.perf_counter() - t0)
                if kind == "done":
                    if val == 0:
                        yield ColumnarBatch.empty(exec_.schema)
                        return False
                    if pending is not None:
                        yield pending
                        n_done += 1
                        if landing is not None:
                            landing.release_upto(n_done - 1)
                    break
                if kind == "error":
                    raise val
                _add_inflight(-val.nbytes())
                t0 = time.perf_counter()
                with TraceRange("ScanExec.upload"):
                    b = interop.upload_packed(
                        val, defer_decode=exec_.defer_decode)
                _bump(h2d_s=time.perf_counter() - t0, slices_uploaded=1,
                      bytes_uploaded=val.nbytes())
                b.origin = origin
                if landing is not None:
                    landing.land(b)
                if pending is not None:
                    yield pending
                    n_done += 1
                    if landing is not None:
                        landing.release_upto(n_done - 1)
                pending = b
        return True
    finally:
        stop.set()

        def drain():
            while True:
                try:
                    kind, val = q.get_nowait()
                except _queue.Empty:
                    return
                if kind == "packed":
                    _add_inflight(-val.nbytes())

        # a mid-put producer can still land one item after a single
        # drain pass, so keep draining until it reports done — it
        # always terminates once ``stop`` is visible
        while not done_evt.wait(timeout=0.05):
            drain()
        drain()
