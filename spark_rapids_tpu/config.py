"""Typed configuration system.

Mirrors the reference's RapidsConf builder DSL and registry
(sql-plugin/.../RapidsConf.scala:171-260: ``conf("key").doc(...)
.booleanConf.createWithDefault``), including:

- typed entries with docs and defaults, byte-size parsing,
- a global registry used to generate documentation (RapidsConf.help,
  RapidsConf.scala:133-168 -> docs/configs.md),
- auto-generated per-operator enable flags added by the planning layer
  (ReplacementRule.confKey, GpuOverrides.scala:129-137) checked during
  tagging, with incompat / disabled-by-default levels
  (GpuOverrides.scala:84-97).

Keys use the ``rapids.tpu.*`` namespace (the reference uses
``spark.rapids.*``).
"""
from __future__ import annotations

import os
import re
import threading
from spark_rapids_tpu.utils import lockorder
from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

T = TypeVar("T")

_REGISTRY: "Dict[str, ConfEntry]" = {}
_REGISTRY_LOCK = lockorder.make_lock("config.registry")

_BYTE_SUFFIXES = {
    "b": 1, "k": 1 << 10, "m": 1 << 20, "g": 1 << 30, "t": 1 << 40,
}


def parse_bytes(v) -> int:
    """Parse '512m', '2g', '1024' into bytes (ConfHelper.byteFromString
    analogue, RapidsConf.scala)."""
    if isinstance(v, (int, float)):
        return int(v)
    m = re.fullmatch(r"\s*(\d+(?:\.\d+)?)\s*([bkmgt]?)b?\s*", str(v).lower())
    if not m:
        raise ValueError(f"cannot parse byte size: {v!r}")
    num, suf = float(m.group(1)), m.group(2) or "b"
    return int(num * _BYTE_SUFFIXES[suf])


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    return str(v).strip().lower() in ("true", "1", "yes", "on")


class ConfEntry(Generic[T]):
    def __init__(self, key: str, default: T, doc: str,
                 converter: Callable[[Any], T], internal: bool = False):
        self.key = key
        self.default = default
        self.doc = doc
        self.converter = converter
        self.internal = internal

    def get(self, conf: "RapidsConf") -> T:
        return conf.get(self)

    def help(self) -> str:
        return f"{self.key}|{self.doc}|{self.default}"


class _Builder:
    """``conf("key").doc(...).boolean_conf.create_with_default(x)``"""

    def __init__(self, key: str):
        self._key = key
        self._doc = ""
        self._internal = False
        self._converter: Callable = lambda v: v

    def doc(self, d: str) -> "_Builder":
        self._doc = d
        return self

    def internal(self) -> "_Builder":
        self._internal = True
        return self

    @property
    def boolean_conf(self) -> "_Builder":
        self._converter = _parse_bool
        return self

    @property
    def int_conf(self) -> "_Builder":
        self._converter = int
        return self

    @property
    def double_conf(self) -> "_Builder":
        self._converter = float
        return self

    @property
    def string_conf(self) -> "_Builder":
        self._converter = str
        return self

    @property
    def bytes_conf(self) -> "_Builder":
        self._converter = parse_bytes
        return self

    def create_with_default(self, default) -> ConfEntry:
        entry = ConfEntry(self._key, default, self._doc, self._converter,
                          self._internal)
        with _REGISTRY_LOCK:
            _REGISTRY[self._key] = entry
        return entry


def conf(key: str) -> _Builder:
    return _Builder(key)


def registered_entries() -> List[ConfEntry]:
    with _REGISTRY_LOCK:
        return list(_REGISTRY.values())


#: keys present when plan/overrides finished importing — the exact set
#: a fresh docs-generation process sees. Per-op flags registered later
#: (overrides.NodeMeta, one per plan-node class at apply time) are an
#: open set no static docs file can contain.
_DOCS_SNAPSHOT: Optional[frozenset] = None


def snapshot_docs_registry() -> frozenset:
    """Freeze (once) and return the import-time registry key set."""
    global _DOCS_SNAPSHOT
    if _DOCS_SNAPSHOT is None:
        with _REGISTRY_LOCK:
            _DOCS_SNAPSHOT = frozenset(_REGISTRY)
    return _DOCS_SNAPSHOT


def register_op_flag(kind: str, name: str, desc: str,
                     default_enabled: bool = True,
                     incompat: Optional[str] = None) -> ConfEntry:
    """Auto-generated per-op enable flag: rapids.tpu.sql.<kind>.<Name>
    (ReplacementRule.confKey analogue, GpuOverrides.scala:129-137)."""
    key = f"rapids.tpu.sql.{kind}.{name}"
    with _REGISTRY_LOCK:
        if key in _REGISTRY:
            return _REGISTRY[key]
    doc = desc + (f" (incompatible: {incompat})" if incompat else "")
    return conf(key).doc(doc).boolean_conf.create_with_default(
        default_enabled and incompat is None)


# ---------------------------------------------------------------------------
# Core entries (subset of RapidsConf.scala:271-707 that applies TPU-side).
# ---------------------------------------------------------------------------

SQL_ENABLED = conf("rapids.tpu.sql.enabled").doc(
    "Enable (true) or disable (false) TPU acceleration of queries."
).boolean_conf.create_with_default(True)

EXPLAIN = conf("rapids.tpu.sql.explain").doc(
    "Explain why parts of a query were or were not placed on the TPU: "
    "NONE, ALL, NOT_ON_TPU."
).string_conf.create_with_default("NONE")

INCOMPATIBLE_OPS = conf("rapids.tpu.sql.incompatibleOps.enabled").doc(
    "Enable operators that produce results that differ in corner cases "
    "from Spark CPU semantics."
).boolean_conf.create_with_default(False)

CONCURRENT_TPU_TASKS = conf("rapids.tpu.sql.concurrentTpuTasks").doc(
    "Number of tasks that can execute concurrently per TPU chip "
    "(admission control; GpuSemaphore analogue, RapidsConf.scala:340)."
).int_conf.create_with_default(2)

TASK_THREADS = conf("rapids.tpu.sql.taskThreads").doc(
    "Worker threads driving partitions concurrently within this process "
    "(the role of Spark's executor task slots). More threads than "
    "concurrentTpuTasks lets host I/O (parquet decode, spill) overlap "
    "device compute while the semaphore bounds device entry "
    "(GpuSemaphore.scala:27-161 oversubscription strategy)."
).int_conf.create_with_default(4)

BATCH_SIZE_BYTES = conf("rapids.tpu.sql.batchSizeBytes").doc(
    "Target coalesced batch size in bytes (RapidsConf.scala:353-358; the "
    "reference defaults to 2GiB, we default lower: XLA prefers bounded "
    "shapes and HBM/chip is smaller than a V100's 32GB)."
).bytes_conf.create_with_default(512 << 20)

MAX_READER_BATCH_SIZE_ROWS = conf(
    "rapids.tpu.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per reader batch."
).int_conf.create_with_default(1 << 21)

MAX_READER_BATCH_SIZE_BYTES = conf(
    "rapids.tpu.sql.reader.batchSizeBytes").doc(
    "Soft cap on bytes per reader batch."
).bytes_conf.create_with_default(256 << 20)

SCAN_PREFETCH_DEPTH = conf("rapids.tpu.io.scan.prefetch.depth").doc(
    "Bounded depth of the async scan pipeline's packed-slice queue "
    "(io/scanpipe.py): an IO thread reads and packs up to this many "
    "slices ahead of the device upload, so decode and H2D transfer "
    "hide behind downstream compute. 0 disables the pipeline entirely "
    "(fully synchronous read->pack->upload on the caller thread — the "
    "byte-identity reference path the ingest fence compares against). "
    "Queued packed slices charge the service admission budget as "
    "backpressure."
).int_conf.create_with_default(2)

SCAN_PRUNING_ENABLED = conf("rapids.tpu.io.scan.pruning.enabled").doc(
    "Prune row groups (parquet) / stripes (ORC) whose footer min/max "
    "statistics cannot match the pushed-down filters, BEFORE any data "
    "byte is read. Pruning is conservative: chunks without statistics "
    "are always kept, and the plan's FilterNode still applies exact "
    "semantics. Disable to measure pruning effectiveness "
    "(scripts/ingest_check.py does)."
).boolean_conf.create_with_default(True)

SCAN_LANDING_SPILLABLE = conf(
    "rapids.tpu.io.scan.landing.spillable.enabled").doc(
    "Land scan results as snapshot-versioned SpillableBatches in the "
    "scan cache (keyed on per-file (mtime_ns, size)): a re-scan of "
    "unchanged files hits warm device/host/disk tiers instead of the "
    "filesystem. Cached bytes charge the service admission budget and "
    "spill under the scan-cache priority before any query's working "
    "batches."
).boolean_conf.create_with_default(False)

SCAN_MAX_PARTITION_BYTES = conf("rapids.tpu.io.scan.maxPartitionBytes").doc(
    "Target on-disk bytes per scan partition (Spark's "
    "sql.files.maxPartitionBytes): one file larger than this splits on "
    "parquet row-group boundaries so a single giant file parallelizes "
    "like many small ones, and small files pack together up to it."
).bytes_conf.create_with_default(128 << 20)

HBM_POOL_FRACTION = conf("rapids.tpu.memory.hbm.allocFraction").doc(
    "Fraction of HBM the framework may fill before spilling "
    "(RMM pool fraction analogue, RapidsConf.scala)."
).double_conf.create_with_default(0.9)

HBM_RESERVE = conf("rapids.tpu.memory.hbm.reserve").doc(
    "Bytes of HBM reserved for XLA scratch/fusion temporaries."
).bytes_conf.create_with_default(1 << 30)

HOST_SPILL_STORAGE_SIZE = conf("rapids.tpu.memory.host.spillStorageSize").doc(
    "Bounded host-memory spill target before falling to disk "
    "(RapidsConf.scala:319)."
).bytes_conf.create_with_default(8 << 30)

SPILL_DIR = conf("rapids.tpu.memory.spillDir").doc(
    "Directory for disk-tier spill files."
).string_conf.create_with_default("/tmp/rapids_tpu_spill")

DEVICE_BUDGET = conf("rapids.tpu.memory.device.budget").doc(
    "Explicit device-memory budget for the spill catalog in bytes; 0 "
    "(the default) derives the budget from reported HBM "
    "(allocFraction * HBM - reserve). Setting a deliberately tiny "
    "value forces the out-of-core execution paths end to end — the "
    "chaos regression fence runs real queries with a budget a quarter "
    "of their working set."
).bytes_conf.create_with_default(0)

SPILL_ASYNC_WRITE = conf("rapids.tpu.memory.spill.asyncWrite.enabled").doc(
    "Write host->disk spills on a double-buffered background writer "
    "(compressed serialization overlaps compute; a spill storm "
    "backpressures the evicting thread at the buffer depth) instead "
    "of inline on the evicting thread."
).boolean_conf.create_with_default(True)

RETRY_MAX_SPILL_RETRIES = conf("rapids.tpu.memory.retry.maxSpillRetries").doc(
    "Spill rungs of the OOM retry ladder before splitting/giving up: "
    "rung 1 spills tracked device buffers to half, rung 2 spills "
    "everything (DeviceMemoryEventHandler escalation analogue)."
).int_conf.create_with_default(2)

RETRY_MAX_SPLIT_DEPTH = conf("rapids.tpu.memory.retry.maxSplitDepth").doc(
    "Maximum recursive input halvings after the spill rungs are "
    "exhausted at a splittable call site (2^depth sub-batches at the "
    "bound); past it the computation fails with SplitAndRetryOOM."
).int_conf.create_with_default(8)

FAULT_INJECTION_ENABLED = conf(
    "rapids.tpu.memory.faultInjection.enabled").doc(
    "Arm the deterministic device-OOM injector: guarded device "
    "computations raise synthetic RESOURCE_EXHAUSTED per the "
    "faultInjection.* trigger config, exercising the full retry "
    "ladder (spill, spill-all, split, give-up) on any backend — "
    "including CPU-only CI. Never enable in production."
).boolean_conf.create_with_default(False)

FAULT_INJECTION_AT_CALL = conf(
    "rapids.tpu.memory.faultInjection.atCall").doc(
    "Fail the Nth eligible guarded device call (counted from 1 across "
    "the process, after the sites filter); 0 disables the "
    "deterministic trigger."
).int_conf.create_with_default(0)

FAULT_INJECTION_SITES = conf(
    "rapids.tpu.memory.faultInjection.sites").doc(
    "Comma-separated call-site tags eligible for injection (prefix "
    "match: 'join' hits join.probe and join.build.concat). Empty = "
    "every guarded site."
).string_conf.create_with_default("")

FAULT_INJECTION_PROBABILITY = conf(
    "rapids.tpu.memory.faultInjection.probability").doc(
    "Per-guarded-call injection probability for seeded chaos sweeps "
    "(0.0 disables). Reproducible via faultInjection.seed."
).double_conf.create_with_default(0.0)

FAULT_INJECTION_SEED = conf(
    "rapids.tpu.memory.faultInjection.seed").doc(
    "RNG seed for probabilistic injection — the same seed replays the "
    "same failure sequence."
).int_conf.create_with_default(0)

FAULT_INJECTION_CONSECUTIVE = conf(
    "rapids.tpu.memory.faultInjection.consecutive").doc(
    "Guarded calls failed in a row per firing point. Values above "
    "maxSpillRetries push the ladder past spill-and-retry into "
    "split-and-retry, which is why the default is 3 (= the default 2 "
    "spill rungs + 1)."
).int_conf.create_with_default(3)

FAULT_INJECTION_MAX = conf(
    "rapids.tpu.memory.faultInjection.maxInjections").doc(
    "Total injections cap (0 = unlimited) so probabilistic chaos runs "
    "terminate."
).int_conf.create_with_default(0)

DEBUG_LOCK_ORDER = conf("rapids.tpu.debug.lockOrder.enabled").doc(
    "Wrap every framework lock in a tracking proxy that asserts the "
    "declared hierarchy (utils/lockorder.py) on each acquire. Read at "
    "lock-CREATION time via its env spelling "
    "(RAPIDS_TPU_DEBUG_LOCKORDER_ENABLED), so it must be set before "
    "the framework imports; tests/conftest.py enables it for every "
    "tier-1 run. Static half of the same check: tpulint TPU301 "
    "(docs/static-analysis.md)."
).boolean_conf.create_with_default(False)

SHUFFLE_PARTITIONS = conf("rapids.tpu.sql.shuffle.partitions").doc(
    "Number of shuffle partitions; 0 (the default) auto-sizes to "
    "2 x attached device count. Spark's 200-partition default exists to "
    "feed many cheap CPU tasks; here every partition costs device "
    "dispatches (and, behind a remote attachment, ~100 ms round trips "
    "each), so fewer, larger partitions win until data exceeds HBM. "
    "Governs the hash exchanges of joins, windows and the pandas execs, "
    "the range exchange of a global sort, and an aggregate's exchange in "
    "a cluster or mesh session; without those an aggregate's partials are "
    "gathered into one final partition whatever this says (one process "
    "holds every partition on its device)."
).int_conf.create_with_default(0)


def resolve_shuffle_partitions(conf_obj) -> int:
    """SHUFFLE_PARTITIONS with 0 = auto (2 x device count)."""
    n = conf_obj.get(SHUFFLE_PARTITIONS)
    if n and n > 0:
        return n
    try:
        import jax

        return max(2 * len(jax.devices()), 2)
    except Exception:  # pragma: no cover - no backend at plan time
        return 8

MESH_ENABLED = conf("rapids.tpu.mesh.enabled").doc(
    "Lower planned queries onto the device mesh: hash exchanges become "
    "in-program lax.all_to_all collectives and aggregation/join execs run "
    "per-chip kernels inside one shard_map program (the planner-reachable "
    "multi-chip path; GpuShuffleExchangeExec.scala:146-248 re-imagined as "
    "ICI collectives)."
).boolean_conf.create_with_default(False)

MESH_DEVICES = conf("rapids.tpu.mesh.devices").doc(
    "Device count for the mesh data axis; 0 = all visible devices. A "
    "request larger than the attached backend clamps to what exists and "
    "records a mesh-fallback reason (parallel/mesh.mesh_fallback_snapshot, "
    "surfaced in runner telemetry next to shuffle_fallbacks)."
).int_conf.create_with_default(0)

MESH_MODEL_DEVICES = conf("rapids.tpu.mesh.modelDevices").doc(
    "Width of the mesh's model axis: the session mesh becomes a 2-D "
    "data x model layout (devices = data * model) with shuffles riding "
    "the data axis and the model axis reserved for tensor-parallel "
    "operators. 1 (default) keeps the 1-D data-only mesh. Values that "
    "leave fewer than 2 data devices disable the mesh with a recorded "
    "reason."
).int_conf.create_with_default(1)

MESH_HOSTS = conf("rapids.tpu.mesh.hosts").doc(
    "Host (process) count in the logical multi-host topology: each host "
    "owns one mesh slice and runs ONE SPMD program over its own devices "
    "with in-program ICI collectives; the DCN seam between hosts is "
    "carried by the TCP exchange path (parallel/mesh.HostTopology). "
    "0 = infer: 1 + rapids.tpu.cluster.workers when cluster mode is "
    "enabled, else 1."
).int_conf.create_with_default(0)

FUSION_ENABLED = conf("rapids.tpu.sql.fusion.enabled").doc(
    "Fuse filter/project/broadcast-join-probe chains into ONE compiled "
    "XLA program per batch (and feed the surviving-row mask straight "
    "into the groupby kernel when the chain ends at an aggregate). "
    "Each fused step removes its own dispatch round trip; behind a "
    "remote device attachment a dispatch costs ~100 ms, so a "
    "scan->filter->join->agg pipeline collapses from ~8 dispatches per "
    "batch to 2. Joins whose broadcast build side has duplicate key "
    "hashes fall back to the general expansion kernel automatically."
).boolean_conf.create_with_default(True)

FUSION_SORT_TAIL = conf("rapids.tpu.sql.fusion.sortTail").doc(
    "Absorb a global ORDER BY into the post-aggregate chain program "
    "(SortStep): final projection + HAVING + project + sort "
    "run as ONE dispatch over the aggregate's raw partials, and the "
    "aggregate skips its own final-project dispatch and rebucket host "
    "sync. Disable if the fused sort module misbehaves on a backend "
    "(the unfused SortExec path remains fully supported)."
).boolean_conf.create_with_default(True)

FUSION_DEFER_DECODE = conf("rapids.tpu.sql.fusion.deferScanDecode").doc(
    "Hand transfer-packed scan uploads to the consuming fused chain "
    "UNDECODED; the chain inlines the decode as its first traced steps "
    "so the scan stage pays zero decode dispatch. Disable to restore "
    "the standalone per-batch decode program."
).boolean_conf.create_with_default(True)

SCAN_PACK_TRANSFERS = conf("rapids.tpu.scan.packTransfers").doc(
    "Pack scan uploads before they cross the host->device link: string "
    "codes ship at the dictionary's width, integers offset-narrow to "
    "their footer-stat span, repeated-value doubles ship as codes plus "
    "a value table, validity masks bit-pack 8x; one jitted program per "
    "batch decodes on device, bit-exactly (verified host-side per "
    "column before each encoding is chosen). The TPU-native analogue "
    "of the reference's nvcomp-compressed transfers "
    "(GpuCompressedColumnVector) — a TPU cannot LZ4-decode, but it can "
    "widen and gather. TPC-H q1 @ sf 1 drops from ~264 to ~70 "
    "uploaded MB. Applies to scans of >= 65536 rows."
).boolean_conf.create_with_default(True)

FUSION_DENSE_PROBE_MAX_SPAN = conf(
    "rapids.tpu.sql.fusion.denseProbe.maxSpan").doc(
    "Ceiling on the build-key value span (table slots, 4 bytes each) "
    "for the fused chain's dense inverse-table join probe: "
    "table[key - lo] = build row, ONE gather per join. Spans above it "
    "use the int64 hash + searchsorted probe (a ~17-step binary-search "
    "gather loop). Single integral keys only; 0 disables."
).int_conf.create_with_default(1 << 22)

FUSION_IN_PROGRAM_BUILD = conf(
    "rapids.tpu.sql.fusion.inProgramBuild.enabled").doc(
    "Fold the broadcast-join build-side preparation (hash sort, "
    "duplicate probe, dense inverse table) INTO the consuming fused "
    "chain program's first launch instead of running it as a separate "
    "_prep_build dispatch plus a flag-fetch device_get. The chain's "
    "first batch runs a build-inlined program variant that also emits "
    "the prepared build arrays; later batches reuse them through the "
    "probe-only variant, so stage0 sheds two dispatches. The duplicate "
    "flag rides back with the (asynchronously fetched) speculative "
    "output — a duplicate-keyed build discards that output and falls "
    "back to the unfused join, exactly like the host path. Disable to "
    "restore the standalone host-side prepare_builds launch."
).boolean_conf.create_with_default(True)

CLUSTER_ENABLED = conf("rapids.tpu.cluster.enabled").doc(
    "Execute shuffle exchanges through the multi-process cluster runtime: "
    "map tasks write partitioned output into per-executor shuffle catalogs "
    "(spillable, priority 0) and reduce tasks read through the transport "
    "over real sockets — the reference's shuffle manager wired into query "
    "execution (RapidsShuffleInternalManager.scala:200-305, "
    "RapidsCachingReader.scala:59-145)."
).boolean_conf.create_with_default(False)

CLUSTER_EXECUTORS = conf("rapids.tpu.cluster.executors").doc(
    "In-process executors in the cluster runtime (each owns a spill "
    "catalog + TCP-served shuffle server)."
).int_conf.create_with_default(2)

CLUSTER_WORKERS = conf("rapids.tpu.cluster.workers").doc(
    "Remote worker processes: each is a separate OS process hosting an "
    "executor (shuffle/remote_worker.py) that RUNS map tasks and serves "
    "their output over TCP — the separate-executor-JVM model."
).int_conf.create_with_default(1)

CLUSTER_MAX_STAGE_RETRIES = conf(
    "rapids.tpu.cluster.maxStageRetries").doc(
    "Lineage-recovery budget per reduce read: each ShuffleFetchFailedError "
    "invalidates the dead executor's map outputs, re-runs the lost map "
    "tasks on survivors/respawned workers, and re-reads — at most this "
    "many times (with exponential backoff, cluster.retryBackoffMs base) "
    "before the ORIGINAL fetch failure re-raises chained from its "
    "transport cause (Spark's spark.stage.maxConsecutiveAttempts role)."
).int_conf.create_with_default(3)

CLUSTER_TASK_TIMEOUT_SEC = conf(
    "rapids.tpu.cluster.taskTimeoutSec").doc(
    "Liveness ceiling for one map task on a remote worker: a worker that "
    "has not replied within this window is presumed hung, is killed, and "
    "the task re-places (locally or on a respawned worker). Without it a "
    "wedged worker blocks the driver's reader forever."
).double_conf.create_with_default(120.0)

CLUSTER_BLACKLIST_AFTER = conf(
    "rapids.tpu.cluster.blacklistAfterFailures").doc(
    "Consecutive failures (submit-time death, task-timeout kill, "
    "fetch-failure blame) after which a worker SLOT is blacklisted: it "
    "is no longer respawned and placement stops targeting it, so "
    "retries quit landing on a flapping host. A successful task resets "
    "the slot's count. 0 disables blacklisting."
).int_conf.create_with_default(3)

CLUSTER_RESPAWN_WORKERS = conf(
    "rapids.tpu.cluster.respawnWorkers").doc(
    "Respawn dead worker processes during fetch-failure recovery (a "
    "fresh process per generation, re-registered with every peer). "
    "Disable to recover onto surviving executors only."
).boolean_conf.create_with_default(True)

CLUSTER_RETRY_BACKOFF_MS = conf(
    "rapids.tpu.cluster.retryBackoffMs").doc(
    "Base backoff before a stage retry re-runs lost map tasks; doubles "
    "per attempt (attempt k sleeps base * 2^k). Small by default: the "
    "local fault injector needs no settling time, real deployments "
    "should give a flapping peer a few seconds."
).int_conf.create_with_default(50)

CLUSTER_AUTOSCALE_ENABLED = conf(
    "rapids.tpu.cluster.autoscale.enabled").doc(
    "Let the service's autoscaler add worker hosts while queries queue: "
    "each admission pump observes queue depth, and sustained pressure "
    "above autoscale.queueDepthHigh invokes ClusterRuntime.add_host — "
    "the SAME elastic-membership seam operators and the recovery ladder "
    "use, so a scale-up is a recovery event, not a special deployment "
    "path. Requires rapids.tpu.cluster.enabled."
).boolean_conf.create_with_default(False)

CLUSTER_AUTOSCALE_MAX_WORKERS = conf(
    "rapids.tpu.cluster.autoscale.maxWorkers").doc(
    "Ceiling on live worker hosts the autoscaler may grow to (counting "
    "distinct live slots); scale-ups stop at this size."
).int_conf.create_with_default(4)

CLUSTER_AUTOSCALE_QUEUE_HIGH = conf(
    "rapids.tpu.cluster.autoscale.queueDepthHigh").doc(
    "Admission queue depth at or above which the autoscaler requests a "
    "new host on the next pump."
).int_conf.create_with_default(8)

CLUSTER_AUTOSCALE_COOLDOWN_SEC = conf(
    "rapids.tpu.cluster.autoscale.cooldownSec").doc(
    "Minimum seconds between autoscaler scale events (up or down), so "
    "one burst does not spawn a host per queued query before the first "
    "new host drains anything — and a scale-down cannot immediately "
    "chase a scale-up."
).double_conf.create_with_default(30.0)

CLUSTER_AUTOSCALE_QUEUE_LOW = conf(
    "rapids.tpu.cluster.autoscale.queueDepthLow").doc(
    "Scale-DOWN watermark: with the cluster idle — admission queue "
    "depth at or below this value AND zero inflight queries — "
    "sustained for autoscale.idleSec (and past the shared cooldown), "
    "the autoscaler retires one worker host through "
    "ClusterRuntime.remove_host: the SAME planned-decommission seam "
    "operators use (slot generations killed, its map outputs "
    "invalidated, lost maps re-run through the lineage ladder), never "
    "below autoscale.minWorkers. -1 (default) disables scale-down."
).int_conf.create_with_default(-1)

CLUSTER_AUTOSCALE_MIN_WORKERS = conf(
    "rapids.tpu.cluster.autoscale.minWorkers").doc(
    "Floor on live worker hosts the autoscaler may shrink to "
    "(counting distinct live slots); scale-downs stop at this size."
).int_conf.create_with_default(1)

CLUSTER_AUTOSCALE_IDLE_SEC = conf(
    "rapids.tpu.cluster.autoscale.idleSec").doc(
    "Seconds the idle condition (queue depth <= queueDepthLow, zero "
    "inflight) must hold continuously before a scale-down fires — a "
    "gap between dashboard refreshes must not decommission a host "
    "the next refresh needs."
).double_conf.create_with_default(60.0)

SHUFFLE_FI_ENABLED = conf(
    "rapids.tpu.shuffle.faultInjection.enabled").doc(
    "Arm the deterministic transport/worker fault injector "
    "(shuffle/fault_injection.py): connection drops, truncated chunk "
    "frames, and worker kills fire at exact request/task ordinals so "
    "the whole lineage-recovery ladder (fetch failure -> invalidate -> "
    "re-run -> re-read) runs deterministically on CPU CI "
    "(scripts/dist_chaos_check.py). Never enable in production."
).boolean_conf.create_with_default(False)

SHUFFLE_FI_DROP_AT = conf(
    "rapids.tpu.shuffle.faultInjection.dropConnectionAtRequest").doc(
    "Drop the client socket (and fail the round trip with a retryable "
    "TransportError) on the Nth transport request, counted from 1 "
    "across the process; 0 disables. Exercises the connection-level "
    "reconnect+backoff path (shuffle/tcp.py _roundtrip_retrying)."
).int_conf.create_with_default(0)

SHUFFLE_FI_TRUNCATE_AT = conf(
    "rapids.tpu.shuffle.faultInjection.truncateFrameAtRequest").doc(
    "Truncate the payload of the Nth chunk request (counted from 1); "
    "0 disables. The short chunk is detected ABOVE the connection retry "
    "loop (transport.py _fetch_payload), so it deterministically "
    "escalates to a fetch failure and a stage retry."
).int_conf.create_with_default(0)

SHUFFLE_FI_KILL_BEFORE_TASK = conf(
    "rapids.tpu.shuffle.faultInjection.killWorkerBeforeTask").doc(
    "SIGKILL the target worker process immediately before the Nth "
    "worker task submission (counted from 1); 0 disables. Earlier "
    "tasks' registered outputs then produce reduce-side fetch failures "
    "— the worker-death half of the recovery ladder."
).int_conf.create_with_default(0)

SHUFFLE_FI_PROBABILITY = conf(
    "rapids.tpu.shuffle.faultInjection.probability").doc(
    "Per-transport-request connection-drop probability for seeded "
    "chaos sweeps (0.0 disables). Reproducible via faultInjection.seed."
).double_conf.create_with_default(0.0)

SHUFFLE_FI_SEED = conf(
    "rapids.tpu.shuffle.faultInjection.seed").doc(
    "RNG seed for probabilistic transport faults — the same seed "
    "replays the same drop sequence."
).int_conf.create_with_default(0)

SHUFFLE_FI_CONSECUTIVE = conf(
    "rapids.tpu.shuffle.faultInjection.consecutive").doc(
    "Requests failed in a row per firing point (applies to drops and "
    "truncations). Values past the transport's transient-retry budget "
    "escalate a drop from a reconnect into a fetch failure; a huge "
    "value with truncateFrameAtRequest=1 makes EVERY chunk short — the "
    "budget-exhaustion fence."
).int_conf.create_with_default(1)

SHUFFLE_FI_MAX = conf(
    "rapids.tpu.shuffle.faultInjection.maxInjections").doc(
    "Total injections cap across all fault kinds (0 = unlimited) so "
    "probabilistic chaos runs terminate."
).int_conf.create_with_default(0)

SHUFFLE_FI_KILL_HOST_AT_STAGE = conf(
    "rapids.tpu.shuffle.faultInjection.killHostAtStage").doc(
    "SIGKILL one live worker HOST (preferring one that owns registered "
    "map output) at the Nth driver-side stage boundary — each shuffle "
    "map stage start and each exchange's first reduce read, counted "
    "from 1 across the process; 0 disables. Unlike "
    "killWorkerBeforeTask (which intercepts one submission), this kills "
    "the whole host out from under a running query: its earlier "
    "registered map outputs fail reduce-side fetches and the full "
    "elastic-membership ladder (invalidate, respawn {slot}~{gen}, "
    "re-run lost maps, re-read) runs deterministically on CPU CI "
    "(scripts/multihost_chaos_check.py)."
).int_conf.create_with_default(0)

SHUFFLE_FI_PARTITION_DCN_AT = conf(
    "rapids.tpu.shuffle.faultInjection.partitionDcnAtRequest").doc(
    "Partition the DCN seam starting at the Nth cross-host transport "
    "round trip (counted from 1); 0 disables. Each affected request "
    "fails like a downed inter-host link (socket dropped, retryable "
    "TransportError); combine with faultInjection.consecutive past the "
    "transport retry budget to escalate the partition into a fetch "
    "failure and a stage retry. Each distinct partition event bumps the "
    "dcn_partitions recovery counter."
).int_conf.create_with_default(0)

SHUFFLE_FI_CRASH_AT_FOLD = conf(
    "rapids.tpu.shuffle.faultInjection.crashAtFold").doc(
    "SIGKILL the CURRENT process at the start of the Nth standing-"
    "query fold (counted from 1 across the process; 0 disables) — "
    "after the delta's WAL record is durable, before the running "
    "state swaps. The hard-crash half of the streaming durability "
    "fence (scripts/stream_durability_check.py): a restarted service "
    "must recover the standing query from its latest checkpoint plus "
    "the WAL suffix, bit-exact, folding the interrupted delta exactly "
    "once."
).int_conf.create_with_default(0)

SHUFFLE_FI_TORN_CHECKPOINT_AT = conf(
    "rapids.tpu.shuffle.faultInjection.tornCheckpointAt").doc(
    "Tear the Nth streaming checkpoint commit (counted from 1; 0 "
    "disables): only the first half of the checkpoint bytes reach the "
    "final file name, modeling a crash mid-write that beat the atomic "
    "rename. Recovery must reject it on CRC (torn_rejected counter), "
    "fall back to an older checkpoint or — with "
    "faultInjection.consecutive large enough to tear EVERY checkpoint "
    "— to a full WAL-only refold, still bit-exact."
).int_conf.create_with_default(0)

SHUFFLE_FI_TRUNCATE_WAL_AT = conf(
    "rapids.tpu.shuffle.faultInjection.truncateWalAt").doc(
    "Write only half of the Nth WAL record's bytes (counted from 1; 0 "
    "disables), modeling a crash mid-append. Replay must tolerate the "
    "torn TAIL record — truncate it, count it in torn_rejected, and "
    "recover every record before it; mid-log corruption (valid "
    "records AFTER a bad CRC) is a loud WalCorruptionError instead, "
    "never silent data loss."
).int_conf.create_with_default(0)

SHUFFLE_IN_PROGRAM = conf("rapids.tpu.shuffle.inProgram.enabled").doc(
    "Fold mesh-internal shuffles into the compiled program: when the "
    "session mesh is active, hash-routed exchanges lower to in-program "
    "lax.all_to_all collectives inside the enclosing stage's shard_map "
    "program (scan-decode -> hash-partition -> all_to_all -> local "
    "join/aggregate/sort as ONE pjit launch), the SPMD analogue of the "
    "reference's UCX on-device shuffle (PAPER L7). Disable to force "
    "every exchange through the host/TCP block-store path even with a "
    "mesh attached; the planner records the fallback reason either way "
    "(parallel/spmd.fallback_snapshot, surfaced in run telemetry)."
).boolean_conf.create_with_default(True)

SHUFFLE_IN_PROGRAM_MIN_ROWS = conf(
    "rapids.tpu.shuffle.inProgram.minRows").doc(
    "Estimated-row floor for the in-program shuffle: below it the "
    "exchange stays on the host block-store path (an all_to_all "
    "program over a handful of rows pays mesh staging + a fresh "
    "compile for nothing). 0 = no floor."
).int_conf.create_with_default(0)

SHUFFLE_SEAM_ICI = conf(
    "rapids.tpu.shuffle.seam.intraHostIci.enabled").doc(
    "Per-seam shuffle routing in cluster mode: keep in-program ICI "
    "collectives for exchanges whose subtree ships to one host whole "
    "(the collective spans only that process's mesh slice) and use the "
    "TCP path ONLY at the DCN seam between hosts. Disable to restore "
    "the all-or-nothing cluster gate where ANY cluster session forces "
    "every exchange onto TCP. Every seam decision is recorded either "
    "way (parallel/spmd.seam_snapshot, surfaced in run telemetry)."
).boolean_conf.create_with_default(True)

SHUFFLE_COMPRESSION_CODEC = conf("rapids.tpu.shuffle.compression.codec").doc(
    "Compression for host-path shuffle payloads: none, lz4 (native C++ "
    "codec; the nvcomp-LZ4 analogue, RapidsConf.scala:685) or zlib."
).string_conf.create_with_default("lz4")

SHUFFLE_RETRY_JITTER_MS = conf(
    "rapids.tpu.shuffle.retry.jitterMs").doc(
    "Uniform random jitter (0..jitterMs) added to each transport "
    "reconnect backoff sleep, so hosts that watched the same DCN blip "
    "de-synchronize instead of stampeding one survivor with "
    "simultaneous reconnects. 0 disables jitter (deterministic "
    "backoff, useful under fault injection)."
).int_conf.create_with_default(10)

SHUFFLE_RETRY_MAX_RECONNECTS = conf(
    "rapids.tpu.shuffle.retry.maxReconnects").doc(
    "Transient-fault retry budget per transport request (each retry is "
    "also the one reconnect — the failed round trip already dropped "
    "the socket). Past it the error surfaces as a fetch failure and "
    "costs a stage retry."
).int_conf.create_with_default(3)

TEST_ENABLED = conf("rapids.tpu.sql.test.enabled").doc(
    "Test mode: assert the whole plan is on the TPU "
    "(GpuTransitionOverrides.scala:270-326)."
).internal().boolean_conf.create_with_default(False)

TEST_ALLOWED_NON_TPU = conf("rapids.tpu.sql.test.allowedNonTpu").doc(
    "Comma-separated exec/expr class names allowed to fall back in test mode."
).internal().string_conf.create_with_default("")

CAST_FLOAT_TO_STRING = conf(
    "rapids.tpu.sql.castFloatToString.enabled").doc(
    "Enable float->string cast (formatting differs from Java in corner "
    "cases; GpuCast gate analogue, RapidsConf.scala:450-482)."
).boolean_conf.create_with_default(False)

CAST_STRING_TO_FLOAT = conf(
    "rapids.tpu.sql.castStringToFloat.enabled").doc(
    "Enable string->float cast."
).boolean_conf.create_with_default(False)

CAST_STRING_TO_TIMESTAMP = conf(
    "rapids.tpu.sql.castStringToTimestamp.enabled").doc(
    "Enable string->timestamp cast."
).boolean_conf.create_with_default(False)

MULTIFILE_READ_THREADS = conf("rapids.tpu.sql.multiFile.numThreads").doc(
    "Thread pool size for multi-file reads "
    "(MultiFileThreadPoolFactory analogue, GpuParquetScan.scala:647)."
).int_conf.create_with_default(8)

UDF_COMPILER_ENABLED = conf("rapids.tpu.sql.udfCompiler.enabled").doc(
    "Trace Python UDFs into jittable jax expressions "
    "(udf-compiler analogue)."
).boolean_conf.create_with_default(True)

# -- file format gates (RapidsConf.scala per-format enables) ----------------

PARQUET_ENABLED = conf("rapids.tpu.sql.format.parquet.enabled").doc(
    "Enable parquet input and output on the TPU path."
).boolean_conf.create_with_default(True)

PARQUET_READ_ENABLED = conf("rapids.tpu.sql.format.parquet.read.enabled").doc(
    "Enable parquet scans."
).boolean_conf.create_with_default(True)

PARQUET_WRITE_ENABLED = conf(
    "rapids.tpu.sql.format.parquet.write.enabled").doc(
    "Enable parquet writes."
).boolean_conf.create_with_default(True)

ORC_ENABLED = conf("rapids.tpu.sql.format.orc.enabled").doc(
    "Enable ORC input and output on the TPU path."
).boolean_conf.create_with_default(True)

ORC_READ_ENABLED = conf("rapids.tpu.sql.format.orc.read.enabled").doc(
    "Enable ORC scans."
).boolean_conf.create_with_default(True)

ORC_WRITE_ENABLED = conf("rapids.tpu.sql.format.orc.write.enabled").doc(
    "Enable ORC writes."
).boolean_conf.create_with_default(True)

CSV_ENABLED = conf("rapids.tpu.sql.format.csv.enabled").doc(
    "Enable CSV input on the TPU path (the reference is read-only for CSV)."
).boolean_conf.create_with_default(True)

CSV_READ_ENABLED = conf("rapids.tpu.sql.format.csv.read.enabled").doc(
    "Enable CSV scans."
).boolean_conf.create_with_default(True)

OPTIMIZER_ENABLED = conf("rapids.tpu.sql.optimizer.enabled").doc(
    "Structural plan rules before override planning: collapse adjacent "
    "projections, combine filters, push filters through deterministic "
    "projections (each removed node is one fewer executable per batch)."
).boolean_conf.create_with_default(True)

ADAPTIVE_ENABLED = conf("rapids.tpu.sql.adaptive.enabled").doc(
    "Adaptive shuffle reads: after an exchange materializes, coalesce "
    "small reduce partitions toward the advisory size using exact map "
    "output statistics (GpuCustomShuffleReaderExec analogue, "
    "GpuOverrides.scala:1874-1887)."
).boolean_conf.create_with_default(True)

ADVISORY_PARTITION_SIZE = conf(
    "rapids.tpu.sql.adaptive.advisoryPartitionSizeBytes").doc(
    "Target bytes per coalesced shuffle partition."
).bytes_conf.create_with_default(64 << 20)

ADAPTIVE_SKEW_JOIN = conf("rapids.tpu.sql.adaptive.skewJoin.enabled").doc(
    "Replan rule 1 (OptimizeSkewedJoin analogue): shuffle partitions "
    "exceeding the skewedPartition cut are split into sub-reads on the "
    "host path, and salted across mesh devices before the in-program "
    "all_to_all, while the other join side replicates — the hot key "
    "stops setting the whole mesh's wall clock. Each split/salt is a "
    "skew replan event in the dispatch telemetry."
).boolean_conf.create_with_default(True)

ADAPTIVE_SKEW_FACTOR = conf(
    "rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor").doc(
    "A shuffle partition is skewed when its bytes exceed this multiple "
    "of the median partition size (and the threshold below) — Spark's "
    "skewedPartitionFactor."
).double_conf.create_with_default(5.0)

ADAPTIVE_SKEW_THRESHOLD = conf(
    "rapids.tpu.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes"
).doc(
    "Minimum bytes before a partition can be considered skewed, "
    "whatever the factor says — Spark's skewedPartitionThresholdInBytes."
).bytes_conf.create_with_default(256 << 20)

ADAPTIVE_SKEW_MAX_SPLITS = conf(
    "rapids.tpu.sql.adaptive.skewJoin.maxSplitsPerPartition").doc(
    "Upper bound on sub-reads one skewed partition is split into "
    "(bounds the replicated-side re-reads and the salt fan-out)."
).int_conf.create_with_default(8)

ADAPTIVE_STRATEGY_SWITCH = conf(
    "rapids.tpu.sql.adaptive.strategySwitch.enabled").doc(
    "Replan rule 2: once the build-side exchange has materialized, "
    "re-decide the join strategy from MEASURED bytes — a shuffled hash "
    "join whose build side came in under autoBroadcastJoinThreshold "
    "re-plans as a broadcast join (skipping the stream-side shuffle "
    "read restructure), and a dense key range upgrades the probe to "
    "the direct-address table. Recorded as strategy_switch replan "
    "events."
).boolean_conf.create_with_default(True)

ADAPTIVE_DENSE_JOIN = conf(
    "rapids.tpu.sql.adaptive.denseJoin.enabled").doc(
    "Allow the strategy switch to flip a shuffled hash join's probe to "
    "the dense direct-address table when the measured build key range "
    "is dense enough (minDensity/maxKeySpan below) — one gather per "
    "probe row instead of an int64 hash + binary search."
).boolean_conf.create_with_default(True)

ADAPTIVE_DENSE_MAX_SPAN = conf(
    "rapids.tpu.sql.adaptive.denseJoin.maxKeySpan").doc(
    "Largest (max-min+1) build key span eligible for the dense table; "
    "the start-offset table costs 4 bytes per slot of span."
).int_conf.create_with_default(1 << 23)

ADAPTIVE_DENSE_MIN_DENSITY = conf(
    "rapids.tpu.sql.adaptive.denseJoin.minDensity").doc(
    "Minimum build_rows / key_span ratio before the dense table is "
    "considered worth its memory."
).double_conf.create_with_default(0.125)

ADAPTIVE_DENSE_MIN_ROWS = conf(
    "rapids.tpu.sql.adaptive.denseJoin.minBuildRows").doc(
    "Skip the key-range measurement (one extra dispatch + sync per "
    "build) for builds smaller than this many rows — the hash probe is "
    "already cheap there."
).int_conf.create_with_default(1 << 16)

ADAPTIVE_REBUCKET = conf(
    "rapids.tpu.sql.adaptive.rebucket.enabled").doc(
    "Replan rule 3a: an adaptive join read serving a coalesced group "
    "of 2+ map blocks concatenates them into ONE batch bucketed at the "
    "MEASURED row count, so the progcache serves the right ladder rung "
    "instead of padding each small block to its own bucket. Recorded "
    "as rebucket replan events."
).boolean_conf.create_with_default(True)

ADAPTIVE_RUNTIME_STATS = conf(
    "rapids.tpu.sql.adaptive.runtimeStats.enabled").doc(
    "Replan rule 3b: measured exchange cardinalities feed "
    "estimate_footprint_bytes on later plans of the same shape, so "
    "out-of-core admission tightens as the workload runs instead of "
    "guessing from the static default row estimate."
).boolean_conf.create_with_default(True)

PARQUET_DEBUG_DUMP_PREFIX = conf(
    "rapids.tpu.sql.parquet.debug.dumpPrefix").doc(
    "When set, copy every parquet file a scan reads under this directory "
    "for offline repro (RapidsConf.scala:575-581 debug dump analogue)."
).string_conf.create_with_default("")

AUTO_BROADCAST_THRESHOLD = conf(
    "rapids.tpu.sql.autoBroadcastJoinThreshold").doc(
    "Equi-joins whose build side is ESTIMATED (scan statistics: parquet "
    "footer num_rows / host array lengths) at or below this many bytes "
    "broadcast instead of shuffling both sides - Spark's "
    "autoBroadcastJoinThreshold, which the reference inherits from the "
    "upstream optimizer. 0 disables (always shuffle when partitioned). "
    "Each skipped exchange pair saves partition/transfer dispatches."
).bytes_conf.create_with_default(10 << 20)

PYTHON_WORKER_PROCESS = conf(
    "rapids.tpu.python.worker.process.enabled").doc(
    "Run pandas UDFs (mapInPandas / applyInPandas / cogroup / "
    "window-in-pandas / pandas aggregates / scalar pandas UDFs) in "
    "POOLED SEPARATE worker processes instead of in-process — the "
    "reference's worker/daemon model (python/rapids/worker.py:22-50, "
    "daemon.py:36-60): on the accelerated execs a crashing or leaking "
    "UDF can no longer take the engine with it, and workers are pinned "
    "off the TPU. (CPU-fallback pandas execs still run in-process.)"
).boolean_conf.create_with_default(False)

PYTHON_WORKER_SLOTS = conf(
    "rapids.tpu.python.worker.processes").doc(
    "Worker processes in the pandas-UDF pool (checkout blocks, the "
    "process-level PythonWorkerSemaphore)."
).int_conf.create_with_default(2)

ORC_DEBUG_DUMP_PREFIX = conf(
    "rapids.tpu.sql.orc.debug.dumpPrefix").doc(
    "When set, copy every ORC file a scan reads under this directory "
    "for offline repro (the ORC half of the reference's debug dump, "
    "RapidsConf.scala:583-589)."
).string_conf.create_with_default("")

CSV_TIMESTAMPS_ENABLED = conf(
    "rapids.tpu.sql.csv.read.timestamps.enabled").doc(
    "Enable reading TIMESTAMP columns from CSV. Off by default: CSV "
    "timestamp text admits many format/timezone spellings and only the "
    "formats listed in rapids.tpu.sql.csv.timestampFormats parse "
    "identically to Spark CPU (the reference gates cuDF's CSV "
    "timestamp parsing the same way, RapidsConf.scala:482)."
).boolean_conf.create_with_default(False)

CSV_TIMESTAMP_FORMATS = conf(
    "rapids.tpu.sql.csv.timestampFormats").doc(
    "Comma-separated strptime patterns tried in order for CSV "
    "TIMESTAMP columns when csv.read.timestamps.enabled is true. Text "
    "matching none of them fails the scan (FAILFAST semantics)."
).string_conf.create_with_default(
    "%Y-%m-%dT%H:%M:%S,%Y-%m-%d %H:%M:%S,%Y-%m-%d")

# -- concurrent query service (service/ subsystem) --------------------------

SERVICE_QUEUE_LIMIT = conf("rapids.tpu.service.queueLimit").doc(
    "Maximum queries waiting for admission (across all tenants). "
    "Submissions beyond it are shed with a structured ServiceOverloaded "
    "rejection instead of queueing unboundedly — load shedding is the "
    "service's backpressure signal to callers."
).int_conf.create_with_default(64)

SERVICE_MAX_CONCURRENT = conf("rapids.tpu.service.maxConcurrent").doc(
    "Queries admitted concurrently (each admitted query gets stage "
    "slices interleaved on the dispatch path by the stage scheduler). "
    "Within the admitted set, device entry is still bounded by "
    "rapids.tpu.sql.concurrentTpuTasks semaphore permits."
).int_conf.create_with_default(4)

SERVICE_DEFAULT_DEADLINE = conf("rapids.tpu.service.defaultDeadlineSec").doc(
    "Default per-query deadline in seconds (queue time + run time). "
    "0 disables; submit(deadline=...) overrides per query. Expired "
    "queries fail with DeadlineExceeded and release their admission, "
    "semaphore permit and catalog buffers."
).double_conf.create_with_default(0.0)

SERVICE_FAIRNESS_WEIGHTS = conf("rapids.tpu.service.fairness.weights").doc(
    "Weighted-round-robin admission weights per tenant as "
    "'tenantA:2,tenantB:1'. Unlisted tenants weigh 1. A tenant's weight "
    "is how many queries it may admit per WRR cycle while other tenants "
    "have queued work — a flood from one tenant cannot starve another."
).string_conf.create_with_default("")

SERVICE_ADMISSION_BUDGET = conf("rapids.tpu.service.admission.hbmBudget").doc(
    "Device-memory budget admission controls against, in bytes. 0 (the "
    "default) uses the runtime's HBM budget (allocFraction * HBM - "
    "reserve) when a device reports memory, else admission is bounded "
    "only by maxConcurrent. A query whose estimated peak footprint "
    "does not fit next to the in-flight queries WAITS in the queue."
).bytes_conf.create_with_default(0)

SERVICE_DEFAULT_ROW_ESTIMATE = conf(
    "rapids.tpu.service.admission.defaultRowEstimate").doc(
    "Row-count assumption for plan nodes whose cardinality the "
    "optimizer cannot estimate (no footer stats); feeds the admission "
    "footprint estimate."
).int_conf.create_with_default(1 << 20)

SERVICE_OUT_OF_CORE = conf("rapids.tpu.service.outOfCore.enabled").doc(
    "Admit a query whose estimated peak footprint exceeds the WHOLE "
    "device budget in flagged out-of-core mode — planned with a "
    "forced-splitting batch budget and eager spill priority, charged "
    "a capped share of HBM — instead of parking it in the admission "
    "queue until the device drains (or its deadline fires)."
).boolean_conf.create_with_default(True)

SERVICE_OUT_OF_CORE_POLICY = conf("rapids.tpu.service.outOfCore.policy").doc(
    "What to do with an over-budget query when outOfCore.enabled: "
    "'run' executes it out-of-core (splitting + spilling to disk); "
    "'shed' rejects it at submit with a structured OutOfCoreRejected "
    "— for deployments that prefer failing whales fast over letting "
    "them occupy the device for a long spill-bound run."
).string_conf.create_with_default("run")

SERVICE_BATCHING_ENABLED = conf("rapids.tpu.service.batching.enabled").doc(
    "Cross-tenant micro-batching: a stage-program dispatch inside a "
    "service slice holds for batching.windowMs and coalesces with "
    "compatible same-program same-bucket dispatches from OTHER queries "
    "into one physical launch (per-query row-count scalars mask each "
    "participant's padding; results split inside the same compiled "
    "program). One launch then serves K tenants — the inference-"
    "serving batching trick applied to SQL stages. The hold only "
    "engages while more than one query is in flight."
).boolean_conf.create_with_default(True)

SERVICE_BATCHING_WINDOW_MS = conf(
    "rapids.tpu.service.batching.windowMs").doc(
    "Micro-batch hold window in milliseconds: how long a stage "
    "dispatch waits for compatible peers before launching. Behind a "
    "~100 ms-per-dispatch remote attachment a few ms buys up to a "
    "K-fold dispatch reduction; keep it well under the backend RTT."
).double_conf.create_with_default(2.0)

SERVICE_BATCHING_MAX = conf("rapids.tpu.service.batching.maxBatch").doc(
    "Maximum queries coalesced into one physical stage launch (a full "
    "group launches immediately, before the window expires). Each "
    "group size K compiles its own K-way program variant once, so "
    "keep this small."
).int_conf.create_with_default(8)

SERVICE_BATCHING_BUCKET_GROWTH = conf(
    "rapids.tpu.service.batching.bucketGrowth").doc(
    "Growth factor of the geometric capacity-bucket ladder "
    "(ops/buckets), installed process-wide at service construction. "
    "2.0 = classic power-of-two buckets. Coarser (e.g. 4.0) funnels "
    "more tenants onto the same compiled executables and coalescible "
    "shapes at the cost of more padding lanes; finer (e.g. 1.5) "
    "wastes less HBM but fragments the executable space. Padding is "
    "masked by the per-batch row-count scalar either way."
).double_conf.create_with_default(2.0)

SERVICE_WARMUP_ENABLED = conf("rapids.tpu.service.warmup.enabled").doc(
    "AOT-warm the compile caches when a query template is registered "
    "(QueryService.register_template): the template runs once under a "
    "reserved '__warmup__' tenant so its stage programs trace, "
    "compile, and land in the persistent progcache BEFORE the first "
    "tenant request — which otherwise eats the cold compile."
).boolean_conf.create_with_default(False)

SERVICE_WARMUP_LADDER = conf("rapids.tpu.service.warmup.ladder").doc(
    "After template warmup, replay each recorded stage program over "
    "the capacity-ladder rungs at/below its observed bucket with "
    "zero-filled operands (service/batching shape-bucket registry), "
    "pre-compiling the executables smaller batches will hit. Only "
    "applies when warmup.enabled is set."
).boolean_conf.create_with_default(True)

SERVICE_CACHE_ENABLED = conf("rapids.tpu.service.cache.enabled").doc(
    "Master switch for the semantic cache (service/cache): repeat "
    "queries over unchanged table snapshots are served from the exact "
    "result cache, and matching stage subplans from the fragment "
    "cache, instead of recomputing on the device. Keys are canonical "
    "plan fingerprints (plan/fingerprint) plus table snapshot "
    "versions, so invalidation is a version comparison — a replaced "
    "view, a rewritten file, or Session.bump_table_version all miss "
    "exactly. Sources without a stable identity (in-memory data) "
    "always bypass."
).boolean_conf.create_with_default(True)

SERVICE_CACHE_RESULT = conf(
    "rapids.tpu.service.cache.resultCache.enabled").doc(
    "Serve a query whose (canonical plan fingerprint, table snapshot "
    "versions) key matches a stored result directly from the host-side "
    "result cache — zero planning, zero device dispatches. Concurrent "
    "identical misses single-flight: one leader computes, followers "
    "are served a copy when it completes."
).boolean_conf.create_with_default(True)

SERVICE_CACHE_FRAGMENT = conf(
    "rapids.tpu.service.cache.fragmentCache.enabled").doc(
    "Materialize cacheable stage subplans (aggregate/join/sort/window "
    "roots — the stage-breaker analogues of plan/optimizer.cut_stages) "
    "as spillable batches on first execution and graft them into later "
    "plans as cached-scan leaves, so subplans shared across queries "
    "and tenants compute once. Entries ride the device->host->disk "
    "spill tiers under the normal priority machinery and their "
    "device-resident bytes count against admission's HBM budget."
).boolean_conf.create_with_default(True)

SERVICE_CACHE_MAX_BYTES = conf("rapids.tpu.service.cache.maxBytes").doc(
    "Combined byte budget for cached results (host frames) and cached "
    "fragments (spillable batches, measured at device width). Above "
    "it, least-recently-used unpinned entries are evicted; an entry "
    "larger than the whole budget is never stored. See "
    "docs/tuning-guide.md for sizing against the device budget."
).bytes_conf.create_with_default(256 << 20)

STREAMING_ENABLED = conf("rapids.tpu.streaming.enabled").doc(
    "Master switch for streaming ingestion & incremental queries "
    "(service/streaming): Session.create_streaming_table registers an "
    "appendable table, QueryService.ingest lands micro-batches as "
    "versioned deltas, and standing queries registered with "
    "QueryService.register_standing fold each delta into long-lived "
    "device-resident partial-aggregate state — one update launch plus "
    "one merge launch per micro-batch, O(batch) not O(total). "
    "Disabled, register_standing raises and appends still land (batch "
    "queries over the table keep working)."
).boolean_conf.create_with_default(True)

STREAMING_WATERMARK_MS = conf("rapids.tpu.streaming.watermarkMs").doc(
    "Default allowed event-time lateness in milliseconds for standing "
    "queries registered with an event-time column. The per-query "
    "watermark advances to max(event_time_seen) - watermarkMs and "
    "never retreats; rows arriving at-or-below the watermark are LATE "
    "(see rapids.tpu.streaming.lateData.policy), and windows whose "
    "end is at-or-below it are FINAL (StandingQuery.results("
    "final_only=True)). Per-registration override: the watermark_ms "
    "argument of register_standing."
).int_conf.create_with_default(0)

STREAMING_MAX_STATE_BYTES = conf("rapids.tpu.streaming.maxStateBytes").doc(
    "Upper bound on one standing query's partial-aggregate state, "
    "measured at device width (the SpillableBatch registered size — "
    "the state itself rides the device->host->disk spill tiers and "
    "its device-resident bytes charge the admission footprint). A "
    "fold that grows the state past this bound FAILS the standing "
    "query and tears its state down (owner-tag removal), exactly like "
    "cancel — unbounded key cardinality must not silently eat the "
    "spill store. 0 disables the bound."
).bytes_conf.create_with_default(0)

STREAMING_LATE_POLICY = conf("rapids.tpu.streaming.lateData.policy").doc(
    "What a standing query does with rows that arrive at-or-below its "
    "watermark: 'merge' (default) folds them through the same "
    "merge-spec path as on-time rows — already-emitted aggregates "
    "self-correct on the next emit, counted as late-row re-merges in "
    "the streaming stats block; 'drop' discards them host-side before "
    "the update launch. Per-registration override: the late_policy "
    "argument of register_standing."
).string_conf.create_with_default("merge")

STREAMING_CHECKPOINT_DIR = conf(
    "rapids.tpu.streaming.checkpoint.dir").doc(
    "Root directory of the streaming durability layer "
    "(service/streaming/durability.py). Set, every "
    "StreamTableSource.append persists its validated delta to a "
    "CRC-framed per-table write-ahead log BEFORE any standing query "
    "folds it, and every standing query checkpoints its running "
    "(keys..., partials...) state + watermark + sequence cursor at "
    "fold boundaries into atomically-renamed, CRC'd checkpoint files "
    "under the same root. A restarted service recovers through "
    "StreamingManager.recover(): latest valid checkpoint + WAL-suffix "
    "replay past its cursor = fold-exactly-once; no valid checkpoint "
    "falls back to a full refold from the WAL. Empty (default) "
    "disables durability — streaming state is process-memory only, "
    "as before PR 19."
).string_conf.create_with_default("")

STREAMING_CHECKPOINT_INTERVAL = conf(
    "rapids.tpu.streaming.checkpoint.intervalFolds").doc(
    "Checkpoint a standing query's state every N folds (counted per "
    "query). 1 (default) checkpoints at every fold boundary — the "
    "tightest recovery point; larger values trade restart replay "
    "length (up to N-1 WAL deltas refold) for less checkpoint I/O. "
    "Values < 1 clamp to 1."
).int_conf.create_with_default(1)

STREAMING_CHECKPOINT_RETAIN = conf(
    "rapids.tpu.streaming.checkpoint.retain").doc(
    "Checkpoint files kept per standing query; older ones are pruned "
    "after each successful write. Keeping >= 2 means a checkpoint torn "
    "by a crash mid-write still leaves the previous valid one to "
    "recover from (recovery tries newest to oldest, counting rejects "
    "in the torn_rejected streaming counter). Values < 1 clamp to 1."
).int_conf.create_with_default(2)

STREAMING_CHECKPOINT_WAL_SYNC = conf(
    "rapids.tpu.streaming.checkpoint.walSyncEvery").doc(
    "fsync the ingest write-ahead log every N appended records. 1 "
    "(default) syncs every append — an acknowledged ingest is durable "
    "before any fold sees it; larger values batch the fsync cost "
    "across appends at the price of the unsynced tail being lost on "
    "power failure (process crash alone loses nothing: the bytes are "
    "already in the page cache). Unsynced WAL bytes are charged to "
    "admission via the service's extra_bytes_fn."
).int_conf.create_with_default(1)

STREAMING_CHECKPOINT_ASYNC = conf(
    "rapids.tpu.streaming.checkpoint.asyncWrite.enabled").doc(
    "Write checkpoint files on the shared async batch-writer template "
    "(memory/catalog.py AsyncBatchWriter — the PR 6 double-buffered "
    "spill writer generalized): the fold returns while the serialized "
    "snapshot commits in the background, with the bounded queue as "
    "backpressure and pending bytes charged to admission. Disabled, "
    "checkpoints commit inline at the fold boundary (deterministic — "
    "what the durability unit tests use)."
).boolean_conf.create_with_default(True)

STREAMING_CHECKPOINT_ON_SIGTERM = conf(
    "rapids.tpu.streaming.checkpoint.onSigterm").doc(
    "With durability enabled, install a SIGTERM handler (main thread "
    "only) that checkpoint-then-drains the service instead of letting "
    "the default handler kill standing queries mid-fold: every live "
    "standing query writes a final checkpoint and suspends, then the "
    "previously-installed handler (if any) runs. SIGKILL needs no "
    "handler — that is what the WAL + checkpoint recovery path is "
    "for."
).boolean_conf.create_with_default(True)

SERVICE_CACHE_TTL = conf("rapids.tpu.service.cache.ttlSec").doc(
    "Time-to-live in seconds for cache entries: an entry older than "
    "this is treated as a miss on next touch and evicted — or, while "
    "queries still pin it (serving or holding it grafted in a queued "
    "plan), marked stale and evicted on the last unpin. 0 (default) "
    "disables TTL — snapshot-version invalidation alone decides "
    "freshness, which is exact for file-backed and protocol sources."
).double_conf.create_with_default(0.0)

FILTER_PUSHDOWN_ENABLED = conf(
    "rapids.tpu.sql.format.pushDownFilters.enabled").doc(
    "Push comparison conjuncts from a Filter above a file scan into the "
    "source for row-group/stripe pruning (GpuParquetScan.scala:228-265 "
    "row-group filtering analogue; exact filtering still runs on device)."
).boolean_conf.create_with_default(True)


class RapidsConf:
    """Immutable snapshot of configuration values.

    Values resolve: explicit dict > environment (dots->underscores,
    uppercased) > registered default.
    """

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def with_overrides(self, extra: Dict[str, Any]) -> "RapidsConf":
        s = dict(self._settings)
        s.update(extra)
        return RapidsConf(s)

    def get(self, entry: ConfEntry) -> Any:
        if entry.key in self._settings:
            return entry.converter(self._settings[entry.key])
        env_key = entry.key.upper().replace(".", "_")
        if env_key in os.environ:
            return entry.converter(os.environ[env_key])
        return entry.default

    def get_key(self, key: str, default=None):
        with _REGISTRY_LOCK:
            entry = _REGISTRY.get(key)
        if entry is not None:
            return self.get(entry)
        return self._settings.get(key, default)

    def is_op_enabled(self, kind: str, name: str, default: bool = True) -> bool:
        key = f"rapids.tpu.sql.{kind}.{name}"
        with _REGISTRY_LOCK:
            entry = _REGISTRY.get(key)
        if entry is None:
            return default
        return self.get(entry)

    # Convenience accessors used widely.
    @property
    def sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get(CONCURRENT_TPU_TASKS)

    @staticmethod
    def help() -> str:
        """Generate config docs (docs/configs.md analogue)."""
        lines = ["Name|Description|Default", "---|---|---"]
        for e in sorted(registered_entries(), key=lambda e: e.key):
            if not e.internal:
                lines.append(e.help())
        return "\n".join(lines)


DEFAULT_CONF = RapidsConf()
