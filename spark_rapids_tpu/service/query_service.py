"""QueryService: the multi-tenant front door over the engine.

``submit()`` plans the query on the caller thread (override planning +
stage cutting + footprint estimation are cheap host work), then hands
the physical tree to admission; scheduler workers drive admitted
queries' stage slices cooperatively. One service per Session — it owns
nothing global except through the runtime singletons the engine already
uses (catalog, semaphore, program caches), which is precisely why
concurrent queries compose: every shared structure below the service
was already concurrent-safe for intra-query task threads.
"""
from __future__ import annotations

import itertools
import threading
from spark_rapids_tpu.utils import lockorder
import time
from typing import Dict, Optional

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.execs.exchange import close_query_blocks
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu.service.admission import (AdmissionController,
                                                parse_fairness_weights)
from spark_rapids_tpu.service.autoscaler import ClusterAutoscaler
from spark_rapids_tpu.service.cache.manager import CacheManager
from spark_rapids_tpu.service.scheduler import StageScheduler
from spark_rapids_tpu.service.stats import Histogram, ServiceStats
from spark_rapids_tpu.service.types import (DeadlineExceeded,
                                            OutOfCoreRejected, Query,
                                            QueryCancelled, QueryHandle,
                                            QueryState, ServiceOverloaded)

# process-global id stream: query ids must be unique ACROSS services —
# per-query dispatch telemetry (utils/dispatch._query_counts) and
# catalog owner tags key on them, and two Sessions each numbering from
# 1 would corrupt each other's buckets
_GLOBAL_QUERY_IDS = itertools.count(1)

#: terminal queries kept for stats()/per_query history; older ones are
#: evicted from the registry (their handles keep working — a handle
#: references the Query object directly)
FINISHED_RETENTION = 256


class QueryService:
    def __init__(self, conf: Optional[RapidsConf] = None, session=None):
        self.conf = conf if isinstance(conf, RapidsConf) else \
            RapidsConf(conf)
        self.session = session
        self._lock = lockorder.make_rlock("service.query")
        self._done_cv = lockorder.make_condition("service.query", lock=self._lock)   # result() waits
        self._work_cv = lockorder.make_condition("service.query", lock=self._lock)   # workers wait
        self._queries: Dict[int, Query] = {}
        self._finished_order: list = []  # terminal qids, oldest first
        self._counters = {"submitted": 0, "admitted": 0, "shed": 0,
                          "done": 0, "failed": 0, "cancelled": 0,
                          "deadline_expired": 0,
                          "admitted_out_of_core": 0,
                          "oom_retries": 0, "oom_splits": 0,
                          "scale_ups": 0, "scale_downs": 0}
        self._queue_time = Histogram()
        self._run_time = Histogram()
        self._shutdown = False
        self._pumping = False
        self.admission = AdmissionController(
            queue_limit=self.conf.get(cfg.SERVICE_QUEUE_LIMIT),
            max_concurrent=self.conf.get(cfg.SERVICE_MAX_CONCURRENT),
            budget_bytes=self._resolve_budget(),
            semaphore=None,  # resolve live: runtime init may replace it
            weights=parse_fairness_weights(
                self.conf.get(cfg.SERVICE_FAIRNESS_WEIGHTS)))
        self.scheduler = StageScheduler(
            self, n_workers=self.conf.get(cfg.SERVICE_MAX_CONCURRENT))
        # queue-pressure autoscaler (service/autoscaler.py): observes
        # every admission pump, grows the session cluster through the
        # elastic-membership seam when queries keep queuing
        self.autoscaler = ClusterAutoscaler(self.conf)
        # semantic result & fragment cache (service/cache): per-service
        # like the admission ledger. Its device-resident fragment bytes
        # charge the admission budget so cached data and inflight
        # queries never overcommit HBM between them.
        self.cache = CacheManager(self.conf)
        # streaming ingestion & standing queries (service/streaming):
        # long-lived aggregate state is device-resident between folds,
        # so it charges the admission budget alongside cached fragments
        from spark_rapids_tpu.service.streaming.manager import \
            StreamingManager

        self.streaming = StreamingManager(self.conf)
        # pending checkpoint/WAL host buffers charge admission too: a
        # burst of async checkpoint blobs is real host memory, and the
        # admission ledger is the one place that sees every subsystem
        from spark_rapids_tpu.io import scanpipe

        self.admission.extra_bytes_fn = lambda: (
            self.cache.device_resident_bytes()
            + self.streaming.device_resident_bytes()
            + self.streaming.durability_pending_bytes()
            # scan-pipeline backpressure: packed slices queued for
            # upload + device-resident scan-cache landings
            + scanpipe.admission_bytes())
        # restart recovery (PR 19): discover what the checkpoint dir
        # holds; the actual WAL replays / checkpoint restores run when
        # the caller re-creates its tables and re-registers its queries
        self.recovery_report = self.streaming.recover()
        self._sigterm_prev = None
        self._install_sigterm()
        #: result-cache key -> live leader Query (single-flight)
        self._result_leaders: Dict = {}
        # cross-tenant micro-batching (service/batching): the ladder
        # growth installs process-wide (capacities are compared across
        # subsystems — one ladder per process; last service wins, the
        # intended deployment is one service per process anyway)
        from spark_rapids_tpu.ops import buckets as _ladder
        from spark_rapids_tpu.service.batching import (MicroBatcher,
                                                       get_registry)

        _ladder.set_ladder_growth(
            self.conf.get(cfg.SERVICE_BATCHING_BUCKET_GROWTH))
        self.batcher = MicroBatcher(
            window_s=self.conf.get(cfg.SERVICE_BATCHING_WINDOW_MS)
            / 1e3,
            max_batch=self.conf.get(cfg.SERVICE_BATCHING_MAX),
            enabled=self.conf.get(cfg.SERVICE_BATCHING_ENABLED),
            registry=get_registry(),
            inflight_fn=lambda: len(self.admission.inflight))
        self._templates: list = []   # (name, plan) for warmup replay

    def _resolve_budget(self) -> Optional[int]:
        """Only an EXPLICIT configured budget is captured; None lets
        admission resolve the runtime device budget live (the runtime
        commonly initializes after the service is constructed)."""
        explicit = self.conf.get(cfg.SERVICE_ADMISSION_BUDGET)
        return explicit if explicit else None

    # -- front door -------------------------------------------------------

    def submit(self, df_or_plan, tenant: str = "default",
               priority: int = 0,
               deadline: Optional[float] = None) -> QueryHandle:
        """Plan + enqueue a query; returns immediately with a handle.
        Raises ServiceOverloaded (state SHED) past the queue limit.
        ``deadline`` is seconds from submission (queue + run time); the
        conf default applies when None."""
        plan = getattr(df_or_plan, "_plan", df_or_plan)
        if deadline is None:
            d = self.conf.get(cfg.SERVICE_DEFAULT_DEADLINE)
            deadline = d if d and d > 0 else None
        # shed BEFORE any planning: under overload — exactly when the
        # backpressure signal matters — a rejection must not pay the
        # planner walk, and result_key is already a plan walk with an
        # os.stat per source file, so even IT comes after this check
        with self._lock:
            if self._shutdown:
                raise RuntimeError("QueryService is shut down")
            self._counters["submitted"] += 1
            if self.admission.would_shed(tenant):
                raise self._shed_locked(plan, tenant, priority, deadline)
        # result tier: an exact hit needs no planning and no device
        # work; a live leader for the same key absorbs this submit as
        # a single-flight follower
        ckey = self.cache.result_key(plan)
        if ckey is not None:
            with self._lock:
                if self._shutdown:
                    raise RuntimeError("QueryService is shut down")
                served = self._serve_cached_locked(ckey, tenant,
                                                   priority, deadline)
                if served is not None:
                    return served
        try:
            planned = self._plan_query(plan, tenant)
        except OutOfCoreRejected as err:
            with self._lock:
                rec = self._record_shed_locked(tenant, priority,
                                               deadline)
            err.query_id = rec.query_id
            raise
        # from here the grafted fragment registrations/pins are this
        # frame's responsibility until a Query takes them over — any
        # exit without a handoff must release them, or the PENDING
        # entries block every future capture of the same keys forever
        pending_frags = planned["pending"]
        served_frags = planned["served"]
        try:
            with self._lock:
                if self._shutdown:
                    raise RuntimeError("QueryService is shut down")
                if self.admission.would_shed(tenant):
                    # concurrent submitters planned past the first
                    # check and filled the queue meanwhile — the bound
                    # is hard
                    raise self._shed_locked(plan, tenant, priority,
                                            deadline)
                if ckey is not None:
                    # a concurrent identical submit may have become
                    # leader (or finished) while this thread planned
                    served = self._serve_cached_locked(ckey, tenant,
                                                       priority,
                                                       deadline,
                                                       count=False)
                    if served is not None:
                        self.cache.abort_pending(pending_frags)
                        self.cache.release_served(served_frags)
                        pending_frags, served_frags = [], []
                        return served
                q = Query(next(_GLOBAL_QUERY_IDS), tenant, plan,
                          planned["exec"], priority, deadline,
                          planned["footprint"], planned["stages"],
                          self._done_cv)
                # ownership of the fragment registrations/pins moves
                # to the query (finalize aborts/releases them)
                q.pending_fragments, pending_frags = pending_frags, []
                q.served_fragments, served_frags = served_frags, []
                if ckey is not None:
                    q.result_cache_key = ckey
                    self._result_leaders[ckey] = q
                if planned["out_of_core"]:
                    q.out_of_core = True
                    q.charge = planned["charge"]
                self._queries[q.query_id] = q
                self.admission.offer(q)
                self._pump_locked()
            return QueryHandle(self, q)
        except BaseException:
            self.cache.abort_pending(pending_frags)
            self.cache.release_served(served_frags)
            raise

    def _plan_query(self, plan, tenant: str) -> dict:
        """The planning core shared by submit() and single-flight
        follower promotion: fragment graft, footprint estimate, the
        out-of-core decision, physical planning and stage cutting. On
        ANY failure — including OutOfCoreRejected(policy=shed), which
        the caller records — the grafted fragment registrations and
        graft-time pins are released before the exception propagates,
        so a planner error can never leak PENDING registry entries."""
        from spark_rapids_tpu.plan.optimizer import (
            estimate_footprint_bytes, cut_stages)
        from spark_rapids_tpu.plan.overrides import apply_overrides

        # fragment tier: replace READY cached stage roots with serve
        # leaves (pinned at graft — see CacheManager.graft_fragments),
        # wrap first-seen ones in capture nodes; footprint and physical
        # planning run on the grafted plan (a serve leaf costs what it
        # stores, not what its subtree would recompute)
        plan_to_run, pending, served = self.cache.graft_fragments(plan)
        try:
            # AQE runtime stats (replan rule 3b): measured exchange
            # cardinalities from earlier runs answer for nodes the
            # static estimator cannot, tightening admission over time
            runtime_rows = None
            if self.conf.get(cfg.ADAPTIVE_ENABLED) and \
                    self.conf.get(cfg.ADAPTIVE_RUNTIME_STATS):
                from spark_rapids_tpu.execs import adaptive

                runtime_rows = adaptive.plan_cardinality_rows
            footprint = estimate_footprint_bytes(
                plan_to_run, default_rows=self.conf.get(
                    cfg.SERVICE_DEFAULT_ROW_ESTIMATE),
                runtime_rows=runtime_rows)
            # out-of-core decision BEFORE physical planning: a query
            # whose estimated peak exceeds the WHOLE device budget can
            # never fit, so either shed it now (policy=shed) or plan it
            # with a forced-splitting batch budget so every staging
            # exec takes its bucketed out-of-core path and the spill
            # chain absorbs the overflow (ROADMAP item 3)
            plan_conf = self.conf
            out_of_core = False
            charge = None
            budget = self.admission.current_budget()
            if budget is not None and footprint > budget and \
                    self.conf.get(cfg.SERVICE_OUT_OF_CORE):
                policy = str(self.conf.get(
                    cfg.SERVICE_OUT_OF_CORE_POLICY)).strip().lower()
                if policy == "shed":
                    raise OutOfCoreRejected(tenant, footprint, budget)
                out_of_core = True
                forced = max(budget // 4, 1 << 20)
                plan_conf = self.conf.with_overrides(
                    {cfg.BATCH_SIZE_BYTES.key: forced})
                # charge half the device: the forced-splitting plan
                # bounds the resident working set far below the
                # footprint, and a whale must not occupy the whole
                # budget ledger while it spills
                charge = min(footprint, max(budget // 2, 1))
            exec_ = apply_overrides(plan_to_run, plan_conf)
            stages = cut_stages(exec_)
        except BaseException:
            self.cache.abort_pending(pending)
            self.cache.release_served(served)
            raise
        return {"exec": exec_, "stages": stages,
                "footprint": footprint, "out_of_core": out_of_core,
                "charge": charge, "pending": pending, "served": served}

    # -- streaming front door (service/streaming) -------------------------

    def ingest(self, table, data, validity: Optional[dict] = None
               ) -> int:
        """Append one micro-batch to a streaming table (a
        StreamTableSource or the name of one registered as a temp view
        on this service's Session) and fold it into every standing
        query over it; returns the rows landed."""
        return self.streaming.ingest(self._resolve_stream(table), data,
                                     validity)

    def register_standing(self, df_or_plan, tenant: str = "default",
                          **kwargs):
        """Register a continuous aggregation over a streaming table;
        returns a StandingQuery handle (results()/cancel()). See
        StreamingManager.register_standing for the knob set."""
        return self.streaming.register_standing(df_or_plan, tenant,
                                                **kwargs)

    def _resolve_stream(self, table):
        from spark_rapids_tpu.plan.incremental import \
            is_streaming_source

        if isinstance(table, str):
            if self.session is None:
                raise ValueError(
                    f"cannot resolve streaming table {table!r}: the "
                    "service has no Session — pass the "
                    "StreamTableSource itself")
            table = self.session.streaming_table(table)
        if not is_streaming_source(table):
            raise ValueError(
                f"{type(table).__name__} is not a streaming table — "
                "create one with Session.create_streaming_table")
        return table

    # -- warmup (ROADMAP item 2: AOT-warm the progcache at startup) -------

    def register_template(self, df_or_plan, name: Optional[str] = None,
                          max_rung: Optional[int] = None):
        """Register a query template the service expects tenants to
        run. With ``rapids.tpu.service.warmup.enabled`` the template is
        warmed immediately (returns the warmup report); otherwise it is
        only recorded for a later explicit ``warmup()`` call.
        ``max_rung`` caps the ladder replay: a single-query caller that
        knows its input capacity skips compiling rungs above it."""
        plan = getattr(df_or_plan, "_plan", df_or_plan)
        entry = (name or f"template{len(self._templates)}", plan)
        self._templates.append(entry)
        if self.conf.get(cfg.SERVICE_WARMUP_ENABLED):
            return self.warmup([entry], max_rung=max_rung)
        return None

    def warmup(self, templates=None, timeout: float = 600.0,
               max_rung: Optional[int] = None) -> dict:
        """Run each template once under the reserved ``__warmup__``
        tenant — tracing + compiling its stage programs into the
        in-process chain-key cache and the persistent compile cache —
        then (warmup.ladder) replay the recorded stage programs across
        the capacity-ladder rungs so smaller buckets are compiled too.
        The first REAL tenant request then starts hot instead of
        eating the cold compile."""
        t0 = time.perf_counter()
        todo = list(self._templates) if templates is None \
            else list(templates)
        ran = errors = 0
        for _name, plan in todo:
            try:
                self.submit(plan, tenant="__warmup__").result(
                    timeout=timeout)
                ran += 1
            except Exception as e:
                from spark_rapids_tpu.memory.retry import is_oom_error

                if is_oom_error(e):
                    # an OOM that survived the in-query retry ladder is
                    # a capacity fault, not a bad template: surface it
                    # instead of shipping a service that admits load it
                    # cannot hold (tpulint TPU401)
                    raise
                errors += 1   # warmup is advisory: a template that
                #               cannot run fails ITS tenant later, not
                #               service startup
        ladder: dict = {}
        if self.batcher.registry is not None and \
                self.conf.get(cfg.SERVICE_WARMUP_LADDER):
            ladder = self.batcher.registry.warm(max_rung=max_rung)
        coalesced = self.batcher.warm_coalesced()
        return {"templates": ran, "errors": errors, "ladder": ladder,
                "coalesced": coalesced,
                "seconds": round(time.perf_counter() - t0, 3)}

    def _record_shed_locked(self, tenant: str, priority: int,
                            deadline) -> Query:
        """Record a rejection as a terminal SHED query so the lifecycle
        is observable (stats().per_query history)."""
        q = Query(next(_GLOBAL_QUERY_IDS), tenant, None, None,
                  priority, deadline, 0, [], self._done_cv)
        q.state = QueryState.SHED
        q.finished_at = time.perf_counter()
        self._queries[q.query_id] = q
        self._retain_locked(q)
        self._counters["shed"] += 1
        return q

    def _serve_cached_locked(self, ckey, tenant: str, priority: int,
                             deadline, count: bool = True):
        """Serve a result-cache hit, or register behind a live leader.
        Returns a handle, or None when this submit must run (and lead).
        Hits finalize DONE immediately with zero device work; followers
        park until the leader finalizes. Both stamp admitted/started so
        stats never sees a DONE query without timing."""
        frame = self.cache.lookup_result(ckey, count=count)
        if frame is not None:
            q = Query(next(_GLOBAL_QUERY_IDS), tenant, None, None,
                      priority, deadline, 0, [], self._done_cv)
            q.cache_hit = True
            q.admitted_at = q.started_at = time.perf_counter()
            q.result = frame
            self._queries[q.query_id] = q
            self._finalize_locked(q, QueryState.DONE)
            return QueryHandle(self, q)
        leader = self._result_leaders.get(ckey)
        if leader is not None and not leader.terminal:
            q = Query(next(_GLOBAL_QUERY_IDS), tenant, None, None,
                      priority, deadline, 0, [], self._done_cv)
            q.cache_hit = True
            self.cache.note_follower()
            leader.cache_followers.append(q)
            self._queries[q.query_id] = q
            return QueryHandle(self, q)
        return None

    def _shed_locked(self, plan, tenant: str, priority: int,
                     deadline) -> ServiceOverloaded:
        """Record + build the overload rejection — the caller gets no
        handle back, but the exception carries the id for gateway-side
        correlation."""
        q = self._record_shed_locked(tenant, priority, deadline)
        err = ServiceOverloaded(
            tenant, self.admission.queue_depth(),
            self.admission.queue_limit)
        err.query_id = q.query_id
        return err

    def stats(self) -> ServiceStats:
        from spark_rapids_tpu.memory import retry as _retry
        from spark_rapids_tpu.runtime import recovery as _recovery
        from spark_rapids_tpu.utils import dispatch as _disp
        from spark_rapids_tpu.utils import progcache

        with self._lock:
            qcounts = _disp.query_counts()
            qcoal = _disp.query_coalesced_counts()
            per_query = []
            running = 0
            for q in self._queries.values():
                if q.state is QueryState.RUNNING:
                    running += 1
                per_query.append({
                    "query_id": q.query_id,
                    "tenant": q.tenant,
                    "state": q.state.value,
                    "footprint_bytes": q.footprint,
                    "out_of_core": q.out_of_core,
                    "slices": q.slices_done,
                    # float: coalesced launches contribute a 1/K share
                    # so per-query counts SUM to physical launches
                    "dispatches": round(qcounts.get(q.query_id,
                                                    q.dispatches), 4),
                    "coalesced_dispatches": qcoal.get(q.query_id,
                                                      q.coalesced),
                    # live queries read the retry map; terminal ones
                    # keep the snapshot finalize popped
                    "retry": q.retry or _retry.owner_stats(
                        q.owner_tag),
                    "queue_time_s": q.queue_time_s(),
                    "run_time_s": q.run_time_s(),
                })
            semaphore = self.admission.current_semaphore()
            return ServiceStats(
                retry=_retry.stats(),
                batching=self.batcher.stats(),
                cache=self.cache.stats(),
                streaming=self.streaming.stats(),
                recovery=_recovery.snapshot(),
                autoscaler=self.autoscaler.stats(),
                queue_depth=self.admission.queue_depth(),
                running=running,
                admitted_inflight=len(self.admission.inflight),
                inflight_bytes=self.admission.inflight_bytes,
                budget_bytes=self.admission.current_budget(),
                counters=dict(self._counters),
                queue_time_hist=self._queue_time.snapshot(),
                run_time_hist=self._run_time.snapshot(),
                per_query=per_query,
                progcache=progcache.stats(),
                semaphore={
                    "available": semaphore.available(),
                    "max": semaphore.max_permits,
                })

    # -- graceful termination (PR 19) -------------------------------------

    def _install_sigterm(self) -> None:
        """With durability on, SIGTERM means checkpoint-then-drain, not
        query slaughter: standing queries suspend behind a final
        checkpoint and queued durability writes land before the process
        exits. Main-thread only (signal API constraint); the previous
        handler is chained and restored at shutdown."""
        import signal
        import threading

        if not (self.streaming.durability.enabled
                and self.streaming.durability.on_sigterm
                and threading.current_thread()
                is threading.main_thread()):
            return

        def _on_sigterm(signum, frame):
            self.shutdown(cancel_running=False)
            prev = self._sigterm_prev
            if callable(prev):
                prev(signum, frame)
            elif prev == signal.SIG_DFL:
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                signal.raise_signal(signal.SIGTERM)

        try:
            self._sigterm_prev = signal.signal(signal.SIGTERM,
                                               _on_sigterm)
        except (ValueError, OSError):
            self._sigterm_prev = None

    def _restore_sigterm(self) -> None:
        import signal
        import threading

        if self._sigterm_prev is None or threading.current_thread() \
                is not threading.main_thread():
            return
        try:
            signal.signal(signal.SIGTERM, self._sigterm_prev)
        except (ValueError, OSError):
            pass
        self._sigterm_prev = None

    def shutdown(self, cancel_running: bool = True) -> None:
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
            for q in list(self._queries.values()):
                if not q.terminal:
                    if q.state is QueryState.QUEUED:
                        self.admission.remove_queued(q)
                        self._finalize_locked(q, QueryState.CANCELLED)
                    elif cancel_running:
                        q.cancel_requested = True
            self.scheduler.stop()
        self.scheduler.join()
        # workers are gone: no future slice will observe the cancel
        # flags, so finalize whatever they left mid-flight here — a
        # waiter blocked in result() must terminate, and the queries'
        # admission charges + catalog buffers must release
        with self._lock:
            for q in list(self._queries.values()):
                if not q.terminal:
                    self._finalize_locked(q, QueryState.CANCELLED)
        # standing queries first: their teardown (suspend-with-final-
        # checkpoint when durable, cancel otherwise) releases the
        # owner-tagged streaming state through the catalog, and no fold
        # can be in flight once ingest starts refusing work
        self.streaming.shutdown()
        # workers joined and every query finalized: no capture or serve
        # can still be touching an entry's spillable handles
        self.cache.close()
        self._restore_sigterm()

    # -- handle backends --------------------------------------------------

    def _poll(self, q: Query) -> QueryState:
        with self._lock:
            self._maybe_expire_locked(q)
            return q.state

    def _result(self, q: Query, timeout: Optional[float]):
        deadline = None if timeout is None else \
            time.perf_counter() + timeout
        with self._lock:
            while True:
                self._maybe_expire_locked(q)
                if q.terminal:
                    break
                wait = None
                if q.deadline_at is not None:
                    # floor keeps the re-check from busy-looping while
                    # an overdue RUNNING query finishes its slice (the
                    # scheduler, not this waiter, expires it)
                    wait = max(q.deadline_at - time.perf_counter(),
                               0.25)
                if deadline is not None:
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"query {q.query_id} still "
                            f"{q.state.value} after {timeout}s")
                    wait = remaining if wait is None else \
                        min(wait, remaining)
                self._done_cv.wait(wait)
            if q.state is QueryState.DONE:
                return q.result
            if q.state is QueryState.CANCELLED:
                raise QueryCancelled(
                    f"query {q.query_id} was cancelled")
            raise q.error or RuntimeError(
                f"query {q.query_id} {q.state.value}")

    def _cancel(self, q: Query) -> bool:
        with self._lock:
            if q.terminal:
                return q.state is QueryState.CANCELLED
            if q.state is QueryState.QUEUED:
                self.admission.remove_queued(q)
                self._finalize_locked(q, QueryState.CANCELLED)
                return True
            # admitted/running: flag it; a stalled query in the ready
            # deque finalizes via its next slice's interrupt check
            q.cancel_requested = True
            return True

    # -- internals --------------------------------------------------------

    def _maybe_expire_locked(self, q: Query) -> None:
        """Lazily expire an overdue query that no worker is driving:
        QUEUED (still in admission), or ADMITTED and parked in the
        ready deque (a stalled query may never reach a worker while a
        long slice hogs maxConcurrent — its deadline must still fire).
        A RUNNING query is expired by its own slice-boundary check."""
        if q.terminal or not q.deadline_expired():
            return
        if q.state is QueryState.QUEUED:
            self.admission.remove_queued(q)
            where = "while queued"
        elif q.state is QueryState.ADMITTED and self.scheduler.drop(q):
            where = "while awaiting a scheduler slot"
        else:
            return
        self._finalize_locked(
            q, QueryState.FAILED,
            DeadlineExceeded(
                f"query {q.query_id} exceeded its "
                f"{q.deadline_s:.3f}s deadline {where}"))

    def _pump_locked(self) -> None:
        """Admit queries while capacity allows (called on submit and on
        every release). Reentrancy guard: expiring a queued query below
        calls _finalize_locked, whose own tail pump must not recurse —
        one stack frame per expired query would blow the stack on a
        deep queue of dead deadlines; the guard makes the inner call a
        no-op and this loop re-scans instead."""
        if self._pumping:
            return
        self._pumping = True
        try:
            while True:
                nxt = self.admission.next_admissible()
                if nxt is None:
                    # nothing admissible: queued work is admission
                    # pressure (maybe grow a host), an empty queue is
                    # idleness (maybe shrink one past the sustained-
                    # idle window) — the autoscaler sees both
                    pre_downs = self.autoscaler.scale_downs
                    eid = self.autoscaler.observe(
                        self.admission.queue_depth(),
                        len(self.admission.inflight))
                    if eid is not None:
                        self._counters["scale_ups"] += 1
                    self._counters["scale_downs"] += \
                        self.autoscaler.scale_downs - pre_downs
                    return
                if nxt.deadline_expired():
                    self._finalize_locked(
                        nxt, QueryState.FAILED,
                        DeadlineExceeded(
                            f"query {nxt.query_id} exceeded its "
                            f"deadline while queued"))
                    continue
                self.admission.admit(nxt)
                self._counters["admitted"] += 1
                if nxt.out_of_core:
                    self._counters["admitted_out_of_core"] += 1
                self.scheduler.enqueue(nxt)
        finally:
            self._pumping = False

    def _finalize(self, q: Query, state: QueryState,
                  error: Optional[BaseException] = None) -> None:
        if state is QueryState.DONE and q.result is None:
            # assemble OUTSIDE the lock: the finishing worker still owns
            # the query exclusively, and a multi-GB pd.concat must not
            # stall every submit/poll/worker on the service lock
            q.result = self._assemble(q)
        with self._lock:
            self._finalize_locked(q, state, error)

    def _finalize_locked(self, q: Query, state: QueryState,
                         error: Optional[BaseException] = None) -> None:
        from spark_rapids_tpu.memory import retry as _retry
        from spark_rapids_tpu.utils import dispatch as _disp

        if q.terminal:
            return
        if state is QueryState.DONE and q.cancel_requested:
            # cancel() already told its caller the query will not
            # complete — honor that even when the final slice raced it
            # to the finish (flag and transition share this lock, so
            # the race closes here); the assembled result is discarded
            state = QueryState.CANCELLED
            q.result = None
        if state is QueryState.DONE and q.result is None:
            q.result = self._assemble(q)  # _finalize pre-assembles
        q.state = state
        q.error = error
        q.finished_at = time.perf_counter()
        q.dispatches = _disp.pop_query_count(q.query_id)
        q.coalesced = _disp.pop_query_coalesced(q.query_id)
        q.retry = _retry.pop_owner_stats(q.owner_tag)
        self._counters["oom_retries"] += q.retry["oom_retries"]
        self._counters["oom_splits"] += q.retry["oom_splits"]
        # semantic cache bookkeeping — BEFORE q.plan is dropped below,
        # because publish revalidates the plan's fingerprint against
        # current snapshot versions (a table bumped while this query
        # ran must not install a stale result under a fresh key)
        if q.result_cache_key is not None:
            if self._result_leaders.get(q.result_cache_key) is q:
                self._result_leaders.pop(q.result_cache_key, None)
            if state is QueryState.DONE and q.result is not None \
                    and q.plan is not None:
                self.cache.publish_result(q.result_cache_key, q.plan,
                                          q.result)
        if q.pending_fragments:
            # capture entries this query registered but never published
            # (failed/cancelled, or the capture path was never driven):
            # drop them so a future query can retry the capture
            self.cache.abort_pending(q.pending_fragments)
            q.pending_fragments = []
        if q.served_fragments:
            # graft-time pins on the READY entries this query's serve
            # leaves referenced — held since submit so eviction could
            # not close the stored parts while the query sat queued
            self.cache.release_served(q.served_fragments)
            q.served_fragments = []
        followers = [f for f in q.cache_followers if not f.terminal]
        q.cache_followers = []
        if followers:
            if state is QueryState.DONE and q.result is not None:
                for f in followers:
                    f.result = q.result.copy()
                    f.admitted_at = f.started_at = time.perf_counter()
                    self._finalize_locked(f, QueryState.DONE)
            else:
                self._promote_follower_locked(q, state, error,
                                              followers)
        # release every resource the query may still hold: admission
        # charge, catalog buffers (an abandoned exec tree must not leak
        # staged batches), and its execution cursor
        self.admission.release(q)
        if q.exec is not None:
            # the tree's exchange blocks end with the query, as under
            # DataFrame.collect(); the owner sweep takes what else the
            # slices registered (staged join sides, sort runs)
            close_query_blocks(q.exec)
        get_catalog().remove_owner(q.owner_tag)
        # drop the heavy execution state: the retention registry keeps
        # up to FINISHED_RETENTION terminal queries for stats history,
        # and pinning each one's exec/plan tree and staged frames would
        # grow host RAM with query size, not query count. q.result
        # stays — handle.result() after completion is the contract.
        q._iters = {}
        q.frames = {}
        q.exec = None
        q.plan = None
        if state is QueryState.DONE:
            self._counters["done"] += 1
        elif state is QueryState.CANCELLED:
            self._counters["cancelled"] += 1
        elif state is QueryState.FAILED:
            self._counters["failed"] += 1
            if isinstance(error, DeadlineExceeded):
                self._counters["deadline_expired"] += 1
        qt, rt = q.queue_time_s(), q.run_time_s()
        if qt is not None:
            self._queue_time.add(qt)
        if rt is not None and q.admitted_at is not None:
            self._run_time.add(rt)
        self.scheduler.drop(q)
        self._retain_locked(q)
        self._pump_locked()
        self._done_cv.notify_all()

    def _promote_follower_locked(self, leader: Query, state: QueryState,
                                 error, followers) -> None:
        """The single-flight leader finalized WITHOUT a result
        (cancelled / failed / deadline-expired). Followers are
        independent client submissions that only parked on the
        leader's computation as an optimization — they must not
        inherit its fate: promote the first live one to a fresh leader
        that computes the shared plan itself; the rest stay parked
        behind the new leader (and are promoted in turn if it dies
        too). Falls back to propagating the leader's terminal state
        only when promotion is impossible (service shutting down, plan
        already dropped); a failed replan fails the followers with the
        REPLAN's error, their own."""
        plan, ckey = leader.plan, leader.result_cache_key
        if self._shutdown or plan is None:
            for f in followers:
                self._finalize_locked(f, state, error)
            return
        new_leader, rest = followers[0], followers[1:]
        try:
            planned = self._plan_query(plan, new_leader.tenant)
        except Exception as e:
            for f in followers:
                self._finalize_locked(f, QueryState.FAILED, e)
            return
        from spark_rapids_tpu.execs import adaptive as adaptive_exec

        new_leader.plan = plan
        new_leader.exec = planned["exec"]
        new_leader.stages = planned["stages"]
        new_leader.footprint = planned["footprint"]
        new_leader.out_of_core = planned["out_of_core"]
        new_leader.charge = planned["charge"] \
            if planned["out_of_core"] else planned["footprint"]
        new_leader.pending_fragments = planned["pending"]
        new_leader.served_fragments = planned["served"]
        new_leader.cache_hit = False
        new_leader.cache_followers = rest
        new_leader.result_cache_key = ckey
        if ckey is not None:
            self._result_leaders[ckey] = new_leader
        with adaptive_exec.planning_mode():
            new_leader.planned_partitions = \
                planned["exec"].num_partitions
        self.admission.offer(new_leader)
        # the finalize that triggered this promotion ends in
        # _pump_locked, which admits the new leader if capacity allows

    def _retain_locked(self, q: Query) -> None:
        """Bounded history: a service alive for days must not pin every
        finished query's result frame + exec tree in the registry."""
        self._finished_order.append(q.query_id)
        while len(self._finished_order) > FINISHED_RETENTION:
            self._queries.pop(self._finished_order.pop(0), None)

    def _assemble(self, q: Query):
        """Partition-then-batch order concat — identical row order to
        the serial collect() path (execs/base.collect)."""
        import pandas as pd

        frames = [f for p in sorted(q.frames) for f in q.frames[p]]
        if not frames:
            exec_ = q.exec
            if exec_ is None:
                # an outside finalize (cancel/shutdown) already dropped
                # the tree; _finalize_locked discards this result anyway
                return None
            cols = {n: pd.Series([], dtype=object)
                    for n in exec_.schema.names}
            return pd.DataFrame(cols)
        return pd.concat(frames, ignore_index=True)
