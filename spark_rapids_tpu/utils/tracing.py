"""Spans: where a query's host time goes.

The reference wraps every operator and transport step in NVTX ranges
(``NvtxRange``, sql-plugin/.../NvtxWithMetrics.scala:44). The TPU
equivalent is :class:`TraceRange`: a ``jax.profiler.TraceAnnotation``, so
every span lies in the profiler's trace on the device events' clock.

When recording is on (``utils/dispatch.install()`` turns it on; nothing
else does) every span also leaves a record: name, start and end
(``time.perf_counter_ns``), the span that caused it, the thread, and the
query it belongs to. A span's parent is the span open on its thread when
it started; ``run_partitions`` carries the caller's span onto its pool
threads, so a task's spans hang under the caller's. All spans under one
``query`` root share that root's query. Self time is a span's duration
less what its children ON THE SAME THREAD cover; the launch timers of
``utils/dispatch`` (``launch.*``) count as children, so an exec's self time
is its own Python and numpy and not the launch beneath it. A query has
hundreds to thousands of launches, so a timer keeps no record of its own:
it adds to a row (one a name) on the span it ran under, with no lock and
no allocation, and the row reaches the table when that span closes.

Beside the spans there are plain counters (:func:`count`: which way
the program decided, a batch at a time), kept whether recording is on or
off.

Kept in memory only: a cumulative table ``{name: {"count", "total_s",
"self_s"}}`` (:func:`table`; ``dispatch.snapshot()`` carries it) and the
spans of the last :data:`RING_QUERIES` queries (:func:`profile`;
``DataFrame.last_profile()`` returns one). Off, a ``TraceRange`` makes its
annotation and reads no clock.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Optional

from spark_rapids_tpu.utils import lockorder

try:
    import jax.profiler as _jprof

    _HAVE_PROFILER = True
except Exception:  # pragma: no cover
    _HAVE_PROFILER = False

#: queries whose spans are kept for ``DataFrame.last_profile()``
RING_QUERIES = 64
#: spans kept a query; a longer query's later spans reach the table only
MAX_QUERY_SPANS = 1 << 16
ROOT = "query"

_recording = False
_tls = threading.local()
_lock = lockorder.make_lock("utils.tracing.table")
_table: dict = {}     # name -> [count, total_ns, self_ns]
_roots = 0            # `query` roots closed so far
_counters: dict = {}  # name -> n (`count`)
_ring: collections.deque = collections.deque(maxlen=RING_QUERIES)
_query_ids = itertools.count(1)


class _Query:
    __slots__ = ("id", "spans", "dropped")

    def __init__(self):
        self.id = next(_query_ids)
        self.spans = []
        self.dropped = 0


class Span:
    """One record. ``parent`` is the causing span (maybe another
    thread's); ``child_ns`` what same-thread children covered."""

    __slots__ = ("name", "start_ns", "end_ns", "parent", "thread", "query",
                 "child_ns", "elided", "leaves", "_ann")

    def __init__(self, name: str, start_ns: int, parent: Optional["Span"]):
        self.name = name
        self.start_ns = start_ns
        self.end_ns = None
        self.parent = parent
        self.thread = threading.get_ident()
        self.query = parent.query if parent is not None else None
        self.child_ns = 0
        self.elided = False
        self.leaves = None      # {name: [count, ns]} of the timers under it
        self._ann = None


def recording() -> bool:
    return _recording


def start_recording() -> None:
    """Called by ``utils/dispatch.install()`` and by nothing else."""
    global _recording
    _recording = True


def current() -> Optional[Span]:
    """The span open on this thread, for ``adopt`` on another."""
    return getattr(_tls, "span", None)


def adopt(span: Optional[Span]) -> Optional[Span]:
    """Make ``span`` (another thread's) the parent of what this thread
    opens next; returns what to hand back to ``adopt`` afterwards."""
    prev = getattr(_tls, "span", None)
    _tls.span = span
    return prev


def open_span(name: str, start_ns: int, annotate: bool = False) -> Span:
    """Open a span from a clock read the caller made. ``annotate`` also
    writes it into the profiler's trace (``TraceRange`` makes its own)."""
    sp = Span(name, start_ns, getattr(_tls, "span", None))
    if annotate and _HAVE_PROFILER:
        sp._ann = _jprof.TraceAnnotation(name)
        sp._ann.__enter__()
    _tls.span = sp
    return sp


def _leaf_rows(leaves) -> list:
    return [(name, n, ns, ns) for name, (n, ns) in (leaves or {}).items()]


def _add_rows(rows) -> None:
    """``(name, count, total_ns, self_ns)`` each, into the table."""
    with _lock:
        for name, n, total, own in rows:
            row = _table.get(name)
            if row is None:
                row = _table[name] = [0, 0, 0]
            row[0] += n
            row[1] += total
            row[2] += own


def _record(sp: Span, dur: int) -> None:
    parent = sp.parent
    if parent is not None and parent.thread == sp.thread:
        parent.child_ns += dur
    q = sp.query
    if q is not None:
        if len(q.spans) < MAX_QUERY_SPANS:
            q.spans.append(sp)
        else:
            q.dropped += 1
    _add_rows([(sp.name, 1, dur, dur - sp.child_ns)] + _leaf_rows(sp.leaves))


def _leave(sp: Span) -> None:
    if sp._ann is not None:
        sp._ann.__exit__(None, None, None)
        sp._ann = None      # the ring keeps the span, not the annotation
    _tls.span = sp.parent


def close_span(sp: Span, end_ns: int) -> None:
    _leave(sp)
    sp.end_ns = end_ns
    _record(sp, end_ns - sp.start_ns)


def abandon_span(sp: Span) -> None:
    """Close without a record (a pull that yielded no batch, or raised):
    what its children covered passes to its parent, and a tree shows them
    under the nearest span that was kept."""
    _leave(sp)
    sp.elided = True
    parent = sp.parent
    if parent is not None and parent.thread == sp.thread:
        parent.child_ns += sp.child_ns
        for name, (n, ns) in (sp.leaves or {}).items():
            _add_leaf(parent, name, n, ns)
    elif sp.leaves:
        _add_rows(_leaf_rows(sp.leaves))


def _add_leaf(sp: Span, name: str, n: int, ns: int) -> None:
    rows = sp.leaves
    if rows is None:
        rows = sp.leaves = {}
    row = rows.get(name)
    if row is None:
        rows[name] = [n, ns]
    else:
        row[0] += n
        row[1] += ns


def leaf(name: str, start_ns: int, end_ns: int) -> None:
    """A launch timer: ``end_ns - start_ns`` under ``name``, as a child
    of the span open on this thread, with no annotation (JAX writes its
    own ``PjitFunction(..)`` and ``DevicePut``). The hot path of every
    launch: it touches that span only. Under no span of this thread (a
    pool task outside any exec) it goes to the table alone."""
    dur = end_ns - start_ns
    sp = getattr(_tls, "span", None)
    if sp is not None and sp.thread == threading.get_ident():
        sp.child_ns += dur
        _add_leaf(sp, name, 1, dur)
    else:
        _add_rows([(name, 1, dur, dur)])


def covered_leaf(name: str, ns: int) -> None:
    """A timer another clock measured (a compile step, from JAX's own
    monitoring event): ``ns`` under ``name`` beside the launch timers of
    the span open on this thread, but not one of its children, since it
    ran inside the launch that asked for it and that launch's timer
    already counts. Nothing while recording is off; reads no clock."""
    if not _recording:
        return
    sp = getattr(_tls, "span", None)
    if sp is not None and sp.thread == threading.get_ident():
        _add_leaf(sp, name, 1, ns)
    else:
        _add_rows([(name, 1, ns, ns)])


class TraceRange:
    """Named profiler span (NvtxRange analogue); recorded when recording
    is on."""

    __slots__ = ("_name", "_ann", "_span")

    def __init__(self, name: str):
        self._name = name
        self._ann = _jprof.TraceAnnotation(name) if _HAVE_PROFILER else None
        self._span = None

    def __enter__(self):
        if self._ann is not None:
            self._ann.__enter__()
        if _recording:
            self._span = open_span(self._name, time.perf_counter_ns())
        return self

    def __exit__(self, *exc):
        if self._span is not None:
            close_span(self._span, time.perf_counter_ns())
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


class QueryRange(TraceRange):
    """The ``query`` root around one ``collect()``/``count()``: every span
    opened under it, on this thread or on a pool thread that adopted one
    of them, shares its query. ``query_id`` is None while recording is
    off, and inside another query (the inner one is then a plain span)."""

    __slots__ = ("query_id",)

    def __init__(self):
        super().__init__(ROOT)
        self.query_id = None

    def __enter__(self):
        super().__enter__()
        sp = self._span
        if sp is not None and sp.query is None:
            sp.query = _Query()
            self.query_id = sp.query.id
        return self

    def __exit__(self, *exc):
        global _roots
        sp = self._span
        super().__exit__(*exc)
        if self.query_id is not None:
            with _lock:
                _roots += 1
                _ring.append(sp.query)
        return False


def table() -> dict:
    """The cumulative table so far, seconds."""
    with _lock:
        return {name: {"count": c, "total_s": t / 1e9, "self_s": s / 1e9}
                for name, (c, t, s) in _table.items()}


def table_delta(before: dict) -> dict:
    """Rows of ``table()`` accumulated since ``before`` (a ``table()``),
    rows that did not move dropped."""
    out = {}
    for name, row in table().items():
        prev = before.get(name)
        if prev is not None:
            row = {k: row[k] - prev[k] for k in row}
        if row["count"]:
            out[name] = row
    return out


def queries() -> int:
    """``query`` roots closed so far."""
    return _roots


def count(name: str, n: int = 1) -> None:
    """A counter of the program: which way a decision went
    (``fused_agg.engaged`` against ``fused_agg.fallback.<reason>``, one
    a batch). Kept whether recording is on or not, since it reads no
    clock; ``dispatch.snapshot()`` carries it as ``counters``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def counters() -> dict:
    with _lock:
        return dict(_counters)


def counters_delta(before: dict) -> dict:
    """Counters that moved since ``before`` (a ``counters()``)."""
    return {name: n - before.get(name, 0)
            for name, n in counters().items() if n != before.get(name, 0)}


def _kept_parent(sp: Span) -> Optional[Span]:
    p = sp.parent
    while p is not None and p.elided:
        p = p.parent
    return p


def profile(query_id) -> dict:
    """The span tree of one of the last ``RING_QUERIES`` queries: nested
    ``{"name", "count", "start_ns", "end_ns", "total_ns", "self_ns",
    "thread", "query", "children"}`` from the ``query`` root down, children
    in start order (a child on another thread is a pool task of
    ``run_partitions``), then one node a name for the launch timers that
    ran directly under the span: ``count`` launches, ``total_ns`` together,
    no start and no end. Empty where the query is not held (recording
    off, or pushed out)."""
    with _lock:
        held = [q for q in _ring if q.id == query_id]
    if not held:
        return {}
    q = held[0]
    def node(name, n, start, end, total, own, thread):
        return {"name": name, "count": n, "start_ns": start, "end_ns": end,
                "total_ns": total, "self_ns": own, "thread": thread,
                "query": q.id, "children": []}

    nodes = {id(sp): node(sp.name, 1, sp.start_ns, sp.end_ns,
                          sp.end_ns - sp.start_ns,
                          sp.end_ns - sp.start_ns - sp.child_ns, sp.thread)
             for sp in q.spans}
    root = {}
    for sp in sorted(q.spans, key=lambda s: s.start_ns):
        parent = _kept_parent(sp)
        if parent is None:
            root = nodes[id(sp)]
        elif id(parent) in nodes:
            nodes[id(parent)]["children"].append(nodes[id(sp)])
    for sp in q.spans:
        nodes[id(sp)]["children"].extend(
            node(name, n, None, None, ns, ns, sp.thread)
            for name, (n, ns) in (sp.leaves or {}).items())
    if root and q.dropped:
        root["spans_dropped"] = q.dropped
    return root
