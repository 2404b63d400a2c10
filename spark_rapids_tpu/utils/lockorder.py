"""Declared lock hierarchy + debug-mode runtime lock-order assertions.

The framework holds ~40 ``threading.Lock/RLock/Condition`` instances
across service/catalog/microbatcher/shuffle. A deadlock between any two
of them only reproduces under the exact interleaving that inverts their
acquisition order — runtime fences must get lucky. Instead the order is
DECLARED here once, every lock is created through :func:`make_lock` /
:func:`make_rlock` / :func:`make_condition` with its hierarchy name, and
two enforcement layers share the single source of truth:

- **statically**: ``spark_rapids_tpu/analysis/locks.py`` (tpulint
  TPU3xx) extracts nested ``with``-acquisitions across an
  intraprocedural call graph and checks every nesting edge against the
  ranks below;
- **at runtime**: when ``rapids.tpu.debug.lockOrder.enabled`` is set
  (env ``RAPIDS_TPU_DEBUG_LOCKORDER_ENABLED=1`` — read at lock-creation
  time, so it must be set before the framework imports; tests/conftest
  does this for every tier-1 run), each lock is wrapped in a tracking
  proxy that asserts, on every acquire, that no lock of EQUAL OR HIGHER
  rank is already held by the thread.

Rank semantics: a thread may acquire lock B while holding lock A iff
``rank(A) < rank(B)`` — lower ranks are the OUTER locks. Locks marked
*nestable* are per-instance locks whose distinct instances legitimately
nest (an exchange's materialize barrier runs its whole child subtree,
which may materialize inner exchanges); for those, same-name nesting is
allowed and the rank rule applies only against other names.

Disabled (the default), the factories return raw ``threading``
primitives — zero overhead in production.
"""
from __future__ import annotations

import os
import threading
import traceback
from typing import Dict, List, Optional, Tuple

#: The declared hierarchy: name -> rank. Lower rank = outer lock
#: (acquired first). Gaps left for future locks. Every make_lock /
#: make_rlock / make_condition name MUST appear here — tpulint TPU303
#: flags undeclared names statically and make_lock raises when tracking
#: is enabled.
LOCK_HIERARCHY: Dict[str, int] = {
    # -- query/service layer (outermost: these orchestrate everything) --
    "api.session.serviceInit": 10,
    "service.query": 20,              # QueryService RLock + done/work CVs
    # -- streaming ingestion (service/streaming): the manager registry
    # is taken under the service lock (stats) and holds the per-query
    # fold lock, which in turn runs whole exec subtrees (planBarrier,
    # >=30) and registers state in the catalog (100) ------------------
    "service.streaming.state": 24,
    "service.streaming.standing": 26, # per-standing-query fold lock
    # -- materialize-once stage barriers: held across whole child
    # subtree execution BY DESIGN (the lock is the stage boundary).
    # These four form the "planBarrier" GROUP (see GROUPS below): an
    # exchange's materialize runs its child subtree, which prepares
    # nested fused chains, which materialize THEIR broadcast builds —
    # a legitimate recursion over the (acyclic) plan DAG, so ordering
    # among group members is exempted rather than ranked. -------------
    "execs.cache.materialize": 30,
    "execs.adaptive.decide": 32,      # AQE replan decision barrier
    "exchange.shuffle.materialize": 34,
    "execs.fused.chainPrep": 36,
    "exchange.broadcast.materialize": 38,
    # -- runtime env swap: initialize/shutdown hold this across catalog
    # close, semaphore re-init, retry/fault-injection (re)configuration,
    # so it sits OUTSIDE the whole memory subsystem; get_env() takes it
    # briefly from inside stage barriers, so it sits inside those ------
    "runtime.device": 45,
    # -- cluster / distributed runtime ---------------------------------
    "runtime.cluster.recover": 50,
    "runtime.cluster.state": 52,
    "runtime.cluster.worker": 54,
    "runtime.cluster.clients": 56,
    "shuffle.cluster.state": 58,
    # -- python/UDF worker pools ---------------------------------------
    "execs.python.pool": 60,
    "udf.pyworker.pool": 62,
    # -- fused-chain build prep cache (global registry bookkeeping;
    # acquired UNDER chainPrep, never holds a barrier itself) ----------
    "execs.fused.prepCache": 70,
    # -- semantic cache registry (service/cache/manager): lookups run
    # under the service lock (20), publishes run inside fragment
    # materialize barriers (planBarrier, <=38), and eviction closes
    # spillable entries through the catalog (100) — so it sits between
    # the barriers and the memory subsystem --------------------------
    "service.cache.state": 76,
    # -- serving-layer batching ----------------------------------------
    "service.batching.microbatch": 80,
    "service.batching.buckets": 84,
    "expressions.fusedCache": 86,
    # -- io ------------------------------------------------------------
    "io.filesrc.splits": 90,
    # scan-cache registry (io/scanpipe): lookups/publishes hold this
    # while closing stale SpillableBatches through the catalog (100),
    # so it must sit OUTSIDE the memory subsystem ---------------------
    "io.scanpipe.cache": 91,
    # -- streaming table deltas: appends hold this while bumping the
    # snapshot counter (158); scans take it briefly to copy the delta
    # list before concatenating outside the lock ----------------------
    "service.streaming.source": 92,
    # -- streaming durability (service/streaming/durability): the WAL
    # lock is taken under the source lock (append persists the record
    # before the delta is visible); the checkpoint-store lock is taken
    # under the standing-query fold lock (26) and must stay OUTSIDE the
    # catalog (100) because loading a checkpoint registers state
    # buffers; the writer CV is the async-commit pending counter ------
    "service.streaming.wal": 94,
    "service.streaming.checkpoint": 96,
    "service.streaming.checkpointWriter": 98,
    # -- memory subsystem ----------------------------------------------
    "memory.catalog.state": 100,
    "memory.catalog.global": 102,
    "memory.catalog.spillWriter": 104,
    "memory.semaphore.instance": 106,
    "memory.semaphore": 108,
    "memory.addressSpace": 112,
    # -- shuffle transport ---------------------------------------------
    "shuffle.catalog.state": 116,
    "shuffle.tcp.registry": 118,  # shutdown closes servers under it
    "shuffle.tcp.server": 120,
    "shuffle.tcp.client": 124,
    "shuffle.transport.store": 132,
    "shuffle.transport.endpoints": 136,
    "shuffle.transport.throttle": 140,
    # -- leaf utility locks (never hold anything under these) ----------
    "execs.base.metrics": 150,
    "utils.progcache": 154,
    "service.cache.snapshots": 158,  # per-source version bump counter
    "memory.retry.policy": 160,
    "memory.retry.stats": 164,
    "memory.faultInjection": 168,
    "shuffle.faultInjection": 170,   # transport/worker fault injector
    "utils.dispatch.stage": 172,
    "execs.adaptive.replans": 174,   # replan-event + runtime-stat counters
    "parallel.spmd.fallbacks": 176,  # fallback/seam-decision counters
    "parallel.mesh.fallbacks": 177,  # mesh clamp/topology counters
    "io.scanpipe.stats": 179,        # scan-pipeline telemetry counters
    "runtime.recovery.stats": 178,   # process-global recovery counters
    "service.streaming.stats": 180,  # process-global fold counters
    "native.init": 184,
    "shims.init": 188,
    "config.registry": 192,
    # span table of utils/tracing: taken as a span closes, which may be
    # under any other lock, so it is the innermost of all
    "utils.tracing.table": 196,
}

#: Per-instance locks whose DISTINCT instances may nest (same name at
#: the same rank): materialize-once barriers recurse through child
#: subtrees that contain more of the same exec class, and a file
#: source's reentrant splits lock survives with_filters cloning.
NESTABLE = frozenset({
    "execs.cache.materialize",
    "exchange.shuffle.materialize",
    "exchange.broadcast.materialize",
    "io.filesrc.splits",
    "execs.base.metrics",
    "memory.catalog.state",       # one catalog instance per executor
    "shuffle.tcp.client",         # one client per peer connection
    "shuffle.transport.store",    # one store per executor server
    "runtime.cluster.worker",     # one handle per worker process
    "memory.addressSpace",
})

#: Mutual-exemption groups. Locks sharing a group skip the rank check
#: AGAINST EACH OTHER (in either direction): the planBarrier group's
#: members are per-plan-node stage barriers that recurse through an
#: acyclic plan DAG (exchange materialize -> child execution -> nested
#: chain prep -> inner broadcast materialize -> ...), so any pairwise
#: order can occur yet no cycle over lock INSTANCES is possible — the
#: DAG is always walked top-down. Ranks still order group members
#: against every lock outside the group.
GROUPS: Dict[str, str] = {
    "execs.cache.materialize": "planBarrier",
    "execs.adaptive.decide": "planBarrier",
    "exchange.shuffle.materialize": "planBarrier",
    "exchange.broadcast.materialize": "planBarrier",
    "execs.fused.chainPrep": "planBarrier",
}

_ENV_KEY = "RAPIDS_TPU_DEBUG_LOCKORDER_ENABLED"


def enabled() -> bool:
    """Whether lock-order tracking is on (the
    ``rapids.tpu.debug.lockOrder.enabled`` knob's env spelling, read
    directly so this module never imports config)."""
    return os.environ.get(_ENV_KEY, "").strip().lower() in (
        "1", "true", "yes", "on")


class LockOrderViolation(RuntimeError):
    """A lock was acquired while a lock of equal or higher rank was
    already held — an inversion of the declared hierarchy."""


_tls = threading.local()

_violations: List[dict] = []
_violations_lock = threading.Lock()
_raise_mode = False


def set_raise_mode(flag: bool) -> None:
    """raise on violation (unit tests) instead of recording (tier-1:
    conftest's sessionfinish hook reports recorded violations so one
    mis-nested acquire fails the run without corrupting unrelated
    tests mid-flight)."""
    global _raise_mode
    _raise_mode = bool(flag)


def violations() -> List[dict]:
    with _violations_lock:
        return list(_violations)


def reset_violations() -> None:
    with _violations_lock:
        _violations.clear()


def _held_stack() -> List["_TrackedLock"]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


class _TrackedLock:
    """Proxy around a threading lock that maintains a per-thread stack
    of held locks and validates the declared hierarchy on acquire.
    Unknown attributes forward to the wrapped lock, so
    ``threading.Condition`` built over a tracked RLock still reaches
    ``_release_save``/``_acquire_restore`` (wait() then bypasses the
    tracker symmetrically: the stack is identical before and after)."""

    __slots__ = ("_inner", "name", "rank", "nestable", "group")

    def __init__(self, inner, name: str):
        rank = LOCK_HIERARCHY.get(name)
        if rank is None:
            raise LockOrderViolation(
                f"lock name {name!r} is not declared in "
                f"utils/lockorder.py LOCK_HIERARCHY")
        self._inner = inner
        self.name = name
        self.rank = rank
        self.nestable = name in NESTABLE
        self.group = GROUPS.get(name)

    def _check(self) -> None:
        held = _held_stack()
        worst: Optional[Tuple[str, int]] = None
        for h in held:
            if h is self:
                return  # reentrant re-acquire of an RLock: always fine
            if self.group is not None and h.group == self.group:
                continue  # same-group barriers: exempt (see GROUPS)
            if h.rank > self.rank or (
                    h.rank == self.rank and
                    not (self.nestable and h.name == self.name)):
                if worst is None or h.rank > worst[1]:
                    worst = (h.name, h.rank)
        if worst is None:
            return
        rec = {
            "acquiring": self.name, "acquiring_rank": self.rank,
            "held": worst[0], "held_rank": worst[1],
            "thread": threading.current_thread().name,
            "stack": "".join(traceback.format_stack(limit=8)[:-2]),
        }
        if _raise_mode:
            raise LockOrderViolation(
                f"acquiring {self.name!r} (rank {self.rank}) while "
                f"holding {worst[0]!r} (rank {worst[1]}) inverts the "
                f"declared hierarchy")
        with _violations_lock:
            # dedup by edge: one report per (held, acquiring) pair
            for v in _violations:
                if v["acquiring"] == self.name and v["held"] == worst[0]:
                    return
            _violations.append(rec)

    # -- lock protocol -----------------------------------------------
    def acquire(self, blocking=True, timeout=-1):
        self._check()
        got = self._inner.acquire(blocking, timeout)
        if got:
            _held_stack().append(self)
        return got

    def release(self):
        self._inner.release()
        st = _held_stack()
        for i in range(len(st) - 1, -1, -1):
            if st[i] is self:
                del st[i]
                break

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    def __getattr__(self, name):
        return getattr(self._inner, name)


def make_lock(name: str):
    """A ``threading.Lock`` declared at hierarchy position ``name``
    (tracked proxy when lock-order debugging is enabled)."""
    if not enabled():
        return threading.Lock()
    return _TrackedLock(threading.Lock(), name)


def make_rlock(name: str):
    """A ``threading.RLock`` declared at hierarchy position ``name``."""
    if not enabled():
        return threading.RLock()
    return _TrackedLock(threading.RLock(), name)


def make_condition(name: str, lock=None):
    """A ``threading.Condition`` over ``lock`` (or a fresh declared
    RLock named ``name``). Waiting on a condition releases its OWN lock;
    holding any other lock across a ``wait`` is exactly the hazard the
    static pass (TPU302) flags."""
    if lock is None:
        lock = make_rlock(name)
    return threading.Condition(lock)
