"""Dispatch telemetry: how many device round trips a query costs.

Every dispatch pays a fixed host-side launch overhead (``measure_rtt``
measures it on the backend in use), so full-query wall clock divides
into ``dispatch_count x overhead`` plus true on-device time — the split
the reference's per-query methodology reports
(docs/benchmarks.md:26-169). This module counts the three dispatch
sources:

- executions of framework-jitted programs (``jax.jit`` is wrapped
  BEFORE the framework modules import, so module-level ``@jit``
  decorators capture the counting binding),
- eager op-by-op primitive applications (host-orchestrated glue
  between jitted kernels — each one is its own tiny executable),
- explicit device->host transfers (``jax.device_get``).

Each of the three also times the host seconds spent inside the call and
hands them to ``utils/tracing`` as a launch timer (``launch.jit``,
``launch.eager``, ``launch.device_get``): ``install()`` is also the one
switch that turns span recording on, and ``snapshot()``/``delta()`` carry
the span table beside the counts.

``install()`` must run before importing any ``spark_rapids_tpu``
compute module (``import jax`` may come first or after); the benchmark
runner does this when ``--dispatch-telemetry`` is passed. Zero overhead
when not installed.
"""
from __future__ import annotations

import functools
import threading
import time

from spark_rapids_tpu.utils import lockorder, tracing

_installed = False
_jit_calls = 0
_eager_calls = 0
_transfers = 0
_compiled_fns: list = []

# -- per-stage attribution --------------------------------------------------
# The stage-cutting pass (plan/optimizer.cut_stages) labels every exec
# with its pipeline stage; base.timed() brackets each batch pull with
# enter_stage/exit_stage so every dispatch lands in the innermost
# active stage's bucket. Thread-local: concurrent task threads each
# carry their own stage.
_tls = threading.local()
_stage_counts: dict = {}
# {stage_label: {program_label: count}} — which PROGRAMS a stage's
# dispatches ran, not just how many (round-7: BENCH r05->r06 could say
# "stage0: 6" but not name the six, so a fusion regression and a
# legitimate chunked loop were indistinguishable from the JSON alone).
# jit launches label as the traced fn's qualname, eager primitives as
# "eager:<prim>", transfers as "device_get".
_stage_programs: dict = {}
_stage_lock = lockorder.make_lock("utils.dispatch.stage")
# A query launches hundreds to thousands of times from four task threads,
# so a launch inside a stage takes no lock: it counts into the thread's
# own ``_tls.pending`` ({(kind, program): n}), which reaches the shared
# tables under ONE lock when the stage is left, and before any of the
# thread's tags (stage, query, coalesced) changes, so every launch lands
# under the tags it ran with. ``_tls.pending`` is a dict exactly while a
# stage is set.


def enter_stage(label):
    """Set the current thread's stage; returns a token for exit_stage.
    Near-zero cost when telemetry is not installed or label is None."""
    if not _installed or label is None:
        return None
    prev = getattr(_tls, "stage", None)
    if prev is None:
        _tls.pending = {}
    else:
        _flush()
    _tls.stage = label
    return (prev,)


def exit_stage(token) -> None:
    if token is not None:
        _flush()
        _tls.stage = token[0]
        if token[0] is None:
            _tls.pending = None


# -- per-query attribution --------------------------------------------------
# The query service brackets each stage slice with enter_query/exit_query
# so concurrent queries' dispatches split per query id in ServiceStats —
# same thread-local scheme as stages, orthogonal bucket.
#
# Coalesced dispatches (service/batching/microbatch): ONE physical
# launch serves K queries. The launch counts once globally and once in
# _tagged_total; each participant's _query_counts entry takes a 1/K
# share (per-query counts SUM to the physical launch count — counting
# 1 per participant would inflate the global picture K-fold) and its
# _query_coalesced entry records the participation itself.
_query_counts: dict = {}
_query_coalesced: dict = {}
_tagged_total = 0.0  # physical dispatches attributed to ANY query


def enter_query(query_id):
    """Tag this thread's dispatches with ``query_id``; returns a token
    for exit_query. No-op (None token) when telemetry isn't installed."""
    if not _installed or query_id is None:
        return None
    _flush()
    prev = getattr(_tls, "query", None)
    _tls.query = query_id
    return (prev,)


def exit_query(token) -> None:
    if token is not None:
        _flush()
        _tls.query = token[0]


def current_query():
    """The query id tagging this thread's dispatches, or None —
    run_partitions propagates it onto its pool threads the same way it
    propagates the catalog buffer-owner tag."""
    return getattr(_tls, "query", None)


def enter_coalesced(query_ids):
    """Mark this thread's NEXT dispatches as one physical launch
    serving every query in ``query_ids`` (the micro-batch leader wraps
    exactly the coalesced program call). Each launch then counts once
    globally and 1/K per participant, with the participation itself
    recorded in the coalesced counter. Returns a token for
    exit_coalesced; no-op (None) when telemetry isn't installed."""
    if not _installed or not query_ids:
        return None
    _flush()
    prev = getattr(_tls, "coalesced", None)
    _tls.coalesced = tuple(query_ids)
    return (prev,)


def exit_coalesced(token) -> None:
    if token is not None:
        _flush()
        _tls.coalesced = token[0]


def query_counts() -> dict:
    """{query_id: dispatch_count} accumulated so far (live queries).
    Counts are floats: a coalesced launch contributes a 1/K share to
    each of its K participants."""
    with _stage_lock:
        return dict(_query_counts)


def query_coalesced_counts() -> dict:
    """{query_id: coalesced launches participated in} (live queries)."""
    with _stage_lock:
        return dict(_query_coalesced)


def tagged_total() -> float:
    """Physical dispatches attributed to any query so far — by
    construction equal to the sum of per-query counts (the attribution
    invariant tests/test_batching.py fences)."""
    with _stage_lock:
        return _tagged_total


def pop_query_count(query_id) -> float:
    """Final dispatch count of a finished query, removed from the live
    map — a long-lived service must not accumulate one entry per query
    ever submitted."""
    with _stage_lock:
        return _query_counts.pop(query_id, 0)


def pop_query_coalesced(query_id) -> int:
    """Final coalesced-participation count of a finished query."""
    with _stage_lock:
        return _query_coalesced.pop(query_id, 0)


def _bump_stage(kind: str, program: str = None) -> None:
    key = (kind, program)
    pending = getattr(_tls, "pending", None)
    if pending is None:
        _count({key: 1})    # outside any stage: rare
    else:
        pending[key] = pending.get(key, 0) + 1


def _flush() -> None:
    pending = getattr(_tls, "pending", None)
    if pending:
        _count(pending)
        pending.clear()


def _count(launches: dict) -> None:
    """``{(kind, program): n}`` launches of this thread, under its tags."""
    global _tagged_total
    label = getattr(_tls, "stage", None) or "<unstaged>"
    qid = getattr(_tls, "query", None)
    group = getattr(_tls, "coalesced", None)
    total = sum(launches.values())
    with _stage_lock:
        d = _stage_counts.get(label)
        if d is None:
            d = _stage_counts[label] = {"jit": 0, "eager": 0, "get": 0}
        for (kind, program), n in launches.items():
            d[kind] += n
            if program is not None:
                progs = _stage_programs.setdefault(label, {})
                progs[program] = progs.get(program, 0) + n
        if group:
            share = total / len(group)
            for g in group:
                _query_counts[g] = _query_counts.get(g, 0) + share
                _query_coalesced[g] = _query_coalesced.get(g, 0) + total
            _tagged_total += total
        elif qid is not None:
            _query_counts[qid] = _query_counts.get(qid, 0) + total
            _tagged_total += total


def install() -> None:
    """Wrap jax.jit / eager primitive application / device_get with
    counters. Idempotent; affects only this process."""
    global _installed
    if _installed:
        return
    import jax
    from jax._src import dispatch as jdispatch

    # eager primitives: every primitive bound its impl to the ORIGINAL
    # apply_primitive (a partial) when jax was imported, so replacing
    # that attribute is never seen. What apply_primitive looks up at call
    # time is the module global xla_primitive_callable, which hands back
    # the primitive's jitted callable: that is wrapped below. A JAX that
    # moved either is an error here, before anything is replaced, and not
    # a counter that reads 0.
    real_callable = getattr(jdispatch, "xla_primitive_callable", None)
    apply_code = getattr(getattr(jdispatch, "apply_primitive", None),
                         "__code__", None)
    if real_callable is None or apply_code is None \
            or "xla_primitive_callable" not in apply_code.co_names:
        raise RuntimeError(
            f"utils/dispatch.install(): jax {jax.__version__} does not "
            f"apply eager primitives through jax._src.dispatch."
            f"apply_primitive -> xla_primitive_callable; the eager-launch "
            f"counter has nothing to hook")

    real_jit = jax.jit

    def counting_jit(fn=None, **kw):
        if fn is None:
            return lambda f: counting_jit(f, **kw)
        compiled = real_jit(fn, **kw)
        _compiled_fns.append(compiled)

        name = getattr(fn, "__qualname__", None) or \
            getattr(fn, "__name__", repr(fn))

        class _Counted:
            def __call__(self, *a, **k):
                global _jit_calls
                _jit_calls += 1
                _bump_stage("jit", name)
                t0 = time.perf_counter_ns()
                try:
                    return compiled(*a, **k)
                finally:
                    tracing.leaf("launch.jit", t0, time.perf_counter_ns())

            def __getattr__(self, name_):
                return getattr(compiled, name_)

        w = _Counted()
        try:
            functools.update_wrapper(w, fn)
        except Exception:
            pass
        return w

    jax.jit = counting_jit

    def counting_callable(prim, **params):
        fun = real_callable(prim, **params)
        label = "eager:" + getattr(prim, "name", "?")

        def launch(*args):
            global _eager_calls
            _eager_calls += 1
            _bump_stage("eager", label)
            t0 = time.perf_counter_ns()
            try:
                return fun(*args)
            finally:
                tracing.leaf("launch.eager", t0, time.perf_counter_ns())

        return launch

    # keeps the cache's cache_clear/cache_info, which jax's test_util calls
    functools.update_wrapper(counting_callable, real_callable)
    jdispatch.xla_primitive_callable = counting_callable

    real_get = jax.device_get

    def counting_get(x):
        global _transfers
        _transfers += 1
        _bump_stage("get", "device_get")
        t0 = time.perf_counter_ns()
        try:
            return real_get(x)
        finally:
            tracing.leaf("launch.device_get", t0, time.perf_counter_ns())

    jax.device_get = counting_get
    tracing.start_recording()
    _installed = True


def installed() -> bool:
    return _installed


_LAUNCH_KEYS = ("jit_calls", "eager_op_calls", "transfers")


def snapshot() -> dict:
    return {"jit_calls": _jit_calls, "eager_op_calls": _eager_calls,
            "transfers": _transfers, "spans": tracing.table(),
            "queries": tracing.queries(),
            "counters": tracing.counters()}


def delta(before: dict) -> dict:
    """Since ``before`` (a ``snapshot()``): the three launch counts and
    their sum ``dispatch_count``, the span table's rows that moved
    (``spans``: ``{name: {"count", "total_s", "self_s"}}``) and the
    ``query`` roots closed (``queries``), and the program's counters that
    moved (``counters``: ``{name: n}``)."""
    now = snapshot()
    d = {k: now[k] - before[k] for k in _LAUNCH_KEYS}
    d["dispatch_count"] = sum(d.values())
    d["spans"] = tracing.table_delta(before["spans"])
    d["queries"] = now["queries"] - before["queries"]
    d["counters"] = tracing.counters_delta(before["counters"])
    return d


def stage_snapshot() -> dict:
    """Per-stage {label: {jit, eager, get}} counts so far."""
    with _stage_lock:
        return {k: dict(v) for k, v in _stage_counts.items()}


def stage_delta(before: dict) -> dict:
    """Per-stage dispatch totals accumulated since ``before`` (a
    stage_snapshot), empty buckets dropped."""
    now = stage_snapshot()
    out = {}
    for label, counts in now.items():
        prev = before.get(label, {})
        n = sum(counts[k] - prev.get(k, 0) for k in counts)
        if n:
            out[label] = n
    return out


def stage_programs_snapshot() -> dict:
    """Per-stage {label: {program_label: count}} so far."""
    with _stage_lock:
        return {k: dict(v) for k, v in _stage_programs.items()}


def stage_program_delta(before: dict) -> dict:
    """Per-stage PROGRAM attribution accumulated since ``before`` (a
    stage_programs_snapshot): {stage: {program_label: launches}} with
    zero-delta programs dropped. The named complement of stage_delta —
    "stage0: 6" becomes "stage0: chain@a1b2 x4 + groupby x1 + get x1"."""
    now = stage_programs_snapshot()
    out = {}
    for label, progs in now.items():
        prev = before.get(label, {})
        d = {p: n - prev.get(p, 0) for p, n in progs.items()
             if n - prev.get(p, 0)}
        if d:
            out[label] = d
    return out


def executable_count() -> int:
    """Distinct compiled executables across all jitted entry points
    (one jit fn compiles once per argument-shape signature)."""
    total = 0
    for f in _compiled_fns:
        try:
            total += f._cache_size()
        except Exception:
            total += 1
    return total


def measure_rtt(samples: int = 5) -> float:
    """Least wall time of a trivial dispatch — the fixed per-dispatch
    overhead on this backend."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(8)
    times = []
    for _ in range(samples + 1):
        t0 = time.perf_counter()
        jax.block_until_ready(x + 1)
        times.append(time.perf_counter() - t0)
    # MIN, not median: the fixed overhead is a floor; host scheduling
    # noise only ever inflates a sample
    return min(times[1:])  # drop the compile
