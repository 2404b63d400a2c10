"""Cross-exec fusion: one compiled program per pipeline segment.

The reference executes each physical operator as its own cuDF kernel
launch; kernel launches on a local GPU cost microseconds, so per-op
dispatch is free there. Behind a remote TPU attachment every dispatch is
a full round trip (~100 ms measured), so a scan->filter->join->aggregate
chain that is correct op-by-op is dispatch-bound end-to-end (the round-4
telemetry: TPCx-BB q9 = 131 dispatches x RTT IS the wall clock).

This module collapses a *pipeline segment* — a unary chain of

    FilterExec | ProjectExec | BroadcastHashJoinExec(probe side)

— into ONE jitted XLA program per input batch. The design is
count-oblivious: no step materializes a compacted result, so no step
needs the host to size an output buffer mid-chain:

- filters contribute a live-mask (rows stay in place, dead lanes ride
  along) — the same discipline ops/groupby.py uses for fused filters;
- broadcast join probes become a searchsorted against the build side's
  hash-sorted table, valid whenever the build's key hashes are UNIQUE
  (each probe row then has at most one candidate): the probe is a
  gather, matches fold into the live-mask (inner/semi/anti) or into the
  gathered columns' validity (left outer). Dimension tables joined on
  their key — the TPC fact->dim shape — are exactly this case. A build
  with duplicate key hashes falls back to the general expansion kernel
  (ops/join.py) via the preserved unfused subtree;
- a chain ending at a hash aggregate hands the live-mask directly to the
  groupby kernel (FusedAggregateExec), so the segment runs as chain
  program + shared groupby kernel: 2 dispatches per batch total;
- a standalone chain compacts once at the end of the program (stable
  argsort on the live-mask), its row count a lazy device scalar.

Reference parity anchors: the per-batch update pipeline shape of
aggregate.scala:420-478, GpuHashJoin.scala:302-318 (build once, stream
probe), and the 3-7x end-to-end bar of docs/FAQ.md:60-67 that motivates
attacking dispatch count rather than per-op time.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from spark_rapids_tpu.utils import lockorder
from functools import partial
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.execs import aggregate as agg_exec
from spark_rapids_tpu.execs import basic, joins
from spark_rapids_tpu.execs.base import TpuExec, timed
from spark_rapids_tpu.execs.exchange import BroadcastExchangeExec
from spark_rapids_tpu.expressions.base import (Alias, BoundReference, ColV,
                                               EvalContext, Expression,
                                               Literal, broadcast)
from spark_rapids_tpu.expressions.compiler import (
    _unwrap_alias, derive_stats, fused_cache_get_or_build)
from spark_rapids_tpu.ops import groupby as gb
from spark_rapids_tpu.ops import hashing, sortkeys
from spark_rapids_tpu.ops import join as join_ops
from spark_rapids_tpu.ops.buckets import MIN_CAPACITY
from spark_rapids_tpu.ops.join import _BUILD_NULL, _PROBE_NULL
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.utils.tracing import TraceRange

_MAXH = jnp.iinfo(jnp.int64).max

# dense-probe table ceiling: 4M i32 slots = 16 MB HBM per build. TPC
# dim surrogate keys are 1..|dim| so even sf 1000 date/time/store/
# household dims fit; above it the hash+searchsorted path stands.
_DENSE_SPAN_MAX = 1 << 22


# ---------------------------------------------------------------------------
# step descriptors (host-side, picklable)
# ---------------------------------------------------------------------------


class _AuxStringPred(Expression):
    """Trace-time stand-in for a string-vs-literal predicate inside a
    fused chain. Dictionaries are SORTED (code order == string order,
    columnar/column.py), so every comparison against a literal is a
    code-range test whose boundaries are that batch's dictionary
    searchsorted positions — delivered to the cached program as scalar
    OPERANDS (``ctx.aux``), never baked in as constants. This is what
    lets string filters (category = 'Books', marital_status = 'M', IN
    lists) ride INSIDE one fused program instead of breaking the chain
    into eager dictionary evaluation + a separate compaction pass.

    ``op``: 'eq_any' (EqualTo / IN — one [lo, hi) pair per literal),
    'lt' | 'le' (codes < bound), 'gt' | 'ge' (codes >= bound)."""

    def __init__(self, ref, op: str, literals: List[str],
                 base_slot: int = -1):
        super().__init__([ref])
        self.op = op
        self.literals = [str(v) for v in literals]
        self.base_slot = base_slot

    @property
    def dtype(self):
        return dt.BOOLEAN

    @property
    def device_only(self) -> bool:
        return True

    @property
    def deterministic(self) -> bool:
        return True

    def n_slots(self) -> int:
        return 2 * len(self.literals) if self.op == "eq_any" else 1

    def aux_values(self, dictionary) -> List[int]:
        """Per-batch dictionary positions for this predicate's slots."""
        d = dictionary.astype(str) if dictionary is not None and \
            len(dictionary) else np.array([], dtype=str)
        if self.op == "eq_any":
            out = []
            for lit in self.literals:
                out.append(int(np.searchsorted(d, lit, side="left")))
                out.append(int(np.searchsorted(d, lit, side="right")))
            return out
        lit = self.literals[0]
        side = "left" if self.op in ("lt", "ge") else "right"
        return [int(np.searchsorted(d, lit, side=side))]

    def eval(self, ctx):
        v = self.children[0].eval(ctx)
        v = broadcast(v, ctx)
        codes = v.data
        aux = ctx.aux
        b = self.base_slot
        if self.op == "eq_any":
            keep = jnp.zeros(codes.shape, dtype=bool)
            for i in range(len(self.literals)):
                keep = keep | ((codes >= aux[b + 2 * i]) &
                               (codes < aux[b + 2 * i + 1]))
        elif self.op in ("lt", "le"):
            keep = codes < aux[b]
        else:  # gt / ge
            keep = codes >= aux[b]
        return ColV(dt.BOOLEAN, keep, v.validity)


def _flip_cmp(op: str) -> str:
    return {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le"}[op]


def _as_string_pred(node) -> Optional[_AuxStringPred]:
    """The aux-operand replacement for ``node`` when it is a string-vs-
    literal predicate on a plain column reference; None otherwise."""
    from spark_rapids_tpu.expressions import predicates as pr

    _CMP = {pr.EqualTo: "eq_any", pr.LessThan: "lt",
            pr.LessThanOrEqual: "le", pr.GreaterThan: "gt",
            pr.GreaterThanOrEqual: "ge"}
    if isinstance(node, pr.In):
        ref = node.children[0]
        if isinstance(ref, BoundReference) and ref.dtype is dt.STRING \
                and node.values and all(
                    isinstance(v, str) for v in node.values):
            return _AuxStringPred(ref, "eq_any", list(node.values))
        return None
    op = _CMP.get(type(node))
    if op is None:
        return None
    a, b = node.children
    if isinstance(a, BoundReference) and a.dtype is dt.STRING and \
            isinstance(b, Literal) and isinstance(b.value, str):
        return _AuxStringPred(a, op, [b.value])
    if isinstance(b, BoundReference) and b.dtype is dt.STRING and \
            isinstance(a, Literal) and isinstance(a.value, str):
        return _AuxStringPred(
            b, op if op == "eq_any" else _flip_cmp(op), [a.value])
    return None


def chain_transform(e: Expression) -> Tuple[Expression,
                                            List[_AuxStringPred]]:
    """Rewrite string-literal predicates into aux-operand nodes; the
    result is chain-traceable iff it ends up device_only."""
    preds: List[_AuxStringPred] = []

    def fn(node):
        repl = _as_string_pred(node)
        if repl is not None:
            preds.append(repl)
            return repl
        return node

    return e.transform(fn), preds


def chain_traceable(e: Expression) -> bool:
    """Can this expression run inside a fused chain program (directly or
    after the string-predicate transform)?"""
    if not e.deterministic:
        return False
    if e.device_only:
        return True
    t, _ = chain_transform(e)
    return t.device_only


@dataclasses.dataclass
class FilterStep:
    condition: Expression
    aux_preds: List[_AuxStringPred] = dataclasses.field(
        default_factory=list)

    def key(self):
        k = self.condition.tree_key()
        return None if k is None else ("F", k)


@dataclasses.dataclass
class ProjectStep:
    exprs: List[Expression]
    aux_preds: List[_AuxStringPred] = dataclasses.field(
        default_factory=list)

    def key(self):
        ks = tuple(_unwrap_alias(e).tree_key() for e in self.exprs)
        return None if any(k is None for k in ks) else ("P", ks)


def make_filter_step(condition: Expression) -> FilterStep:
    t, preds = chain_transform(condition)
    return FilterStep(t, preds)


def make_project_step(exprs: Sequence[Expression]) -> ProjectStep:
    out, preds = [], []
    for e in exprs:
        t, p = chain_transform(e)
        out.append(t)
        preds.extend(p)
    return ProjectStep(out, preds)


@dataclasses.dataclass
class SortStep:
    """Terminal ORDER BY inside a chain program: the ORDER BY lanes
    and a row index are sorted (``sortkeys.stable_order``), every column
    follows with a gather, dead
    lanes (filtered rows, padding) sink to the end, and the live count
    comes out as a lazy device scalar — so a post-aggregate
    HAVING/project/sort tail runs as ONE compiled program instead of
    compaction + rebucket + a separate sort dispatch. Only the planner
    may append one, and only over a source that emits exactly one batch
    on one partition (a hash aggregate): a per-batch sort of a
    multi-batch stream would NOT be a global sort."""

    specs: tuple  # Tuple[SortKeySpec, ...] (frozen, hashable)

    def key(self):
        return ("S", tuple((s.ordinal, s.ascending, s.nulls_first)
                           for s in self.specs))


@dataclasses.dataclass
class JoinStep:
    kind: str                  # inner | left | left_semi | left_anti
    stream_keys: List[int]     # ordinals into the working columns
    build_keys: List[int]      # ordinals into the build schema
    build_index: int           # which prepared build feeds this step
    build_types: List[dt.DType]
    key_common: List[dt.DType]  # per-pair comparison type (mixed-type
    #                             keys cast to it on both sides)

    def key(self):
        return ("J", self.kind, tuple(self.stream_keys),
                tuple(self.build_keys), self.build_index,
                tuple(self.build_types), tuple(self.key_common))


# ---------------------------------------------------------------------------
# build-side preparation (once per query per broadcast)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class PreparedBuild:
    """Hash-sorted broadcast build table. ``ok`` False means duplicate
    matchable key hashes were found — the chain must fall back to the
    general join kernel for exact multi-match expansion.

    ``table`` (when set) is a dense inverse index over the build key's
    value range: ``table[key - dense_lo]`` = sorted build row, -1 =
    absent. Single integral keys whose span fits ``_DENSE_SPAN_MAX``
    (every TPC fact->dim surrogate key) probe with ONE gather instead
    of an int64 hash + searchsorted — the searchsorted lowers to a
    ~17-step binary-search loop whose per-step gather costs ~100 ms at
    multi-million-row probe widths on a v5e, which made the probe THE
    on-device cost of TPCx-BB q9 at sf 1."""

    ok: bool
    h_sorted: Optional[jax.Array] = None
    datas: Optional[tuple] = None
    vals: Optional[tuple] = None
    n_valid: Optional[jax.Array] = None   # device scalar
    ghosts: Optional[list] = None         # host wrap info per column
    table: Optional[jax.Array] = None     # dense inverse index
    dense_lo: int = 0


def _hash_keys(key_cols: Sequence[ColV], types: Sequence[dt.DType],
               targets: Sequence[dt.DType], sentinel) -> jax.Array:
    """Traceable combined int64 hash of key columns, each cast to its
    pair's common comparison type first; rows where ANY key is null
    collapse to ``sentinel`` (disjoint sentinels per side keep SQL
    null-never-matches semantics — ops/join.py:38-56)."""
    vals = []
    any_null = None
    for c, t, tgt in zip(key_cols, types, targets):
        if tgt is dt.STRING:
            raise AssertionError("string join keys are not fusable")
        d = c.data if t is tgt else c.data.astype(tgt.kernel_dtype)
        v = hashing._numeric_to_int64(d, tgt)
        if c.validity is not None:
            nn = ~c.validity
            any_null = nn if any_null is None else (any_null | nn)
            v = jnp.where(c.validity, v, jnp.int64(hashing._NULL_HASH))
        vals.append(v)
    h = hashing._combine(tuple(vals))
    if any_null is not None:
        h = jnp.where(any_null, sentinel, h)
    return h


def _prep_build_arrays(datas, vals, num_rows, key_ords, types, hash_types,
                       key_range=False, dense_span=0, dense_lo=0):
    """Traceable build-side preparation — the body of ``_prep_build``,
    shared verbatim by the chain engine's build-inlined program variant
    (the in-program build traces this INSIDE the consuming chain, so
    the standalone prep dispatch and its flag sync disappear).

    Sort the build by key hash; null-key and padding rows park at the
    +inf sentinel (they can never match). Returns the duplicate flag the
    host checks once per query, plus (when ``key_range``) the single
    key's valid-row (min, max) in its comparison type — fetched in the
    same sync as the dup flag so the host can build the dense probe
    table without another round trip. When the key's range is already
    HOST-known (footer/upload stats survived the build subtree),
    ``dense_span``/``dense_lo`` fold the dense inverse-table build into
    THIS program — no flag round trip feeds it and the separate
    _prep_dense_table dispatch disappears."""
    cols = [ColV(t, d, v) for t, d, v in zip(types, datas, vals)]
    h = _hash_keys([cols[o] for o in key_ords],
                   [types[o] for o in key_ords], hash_types, _BUILD_NULL)
    cap = h.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
    h_l = jnp.where(live & (h != _BUILD_NULL), h, _MAXH)
    order, (sh,) = sortkeys.stable_order([h_l])
    sdatas, svals = sortkeys.take_rows(order, datas, vals)
    if cap > 1:
        dup = jnp.any((sh[1:] == sh[:-1]) & (sh[:-1] != _MAXH))
    else:
        dup = jnp.zeros((), dtype=bool)
    n_valid = jnp.sum(sh != _MAXH).astype(jnp.int32)
    if key_range:
        o = key_ords[0]
        kd = cols[o].data.astype(hash_types[0].kernel_dtype).astype(
            jnp.int64)
        matchable = live & (h != _BUILD_NULL)
        kmin = jnp.min(jnp.where(matchable, kd, jnp.int64(2**62)))
        kmax = jnp.max(jnp.where(matchable, kd, jnp.int64(-2**62)))
    else:
        kmin = jnp.int64(0)
        kmax = jnp.int64(-1)
    if dense_span > 0:
        table = _dense_table_arrays(sdatas[key_ords[0]], n_valid,
                                    dense_lo, dense_span)
    else:
        table = jnp.zeros(0, dtype=jnp.int32)
    return sh, sdatas, svals, dup, n_valid, kmin, kmax, table


@partial(jax.jit, static_argnames=("key_ords", "types", "hash_types",
                                   "key_range", "dense_span"))
def _prep_build(datas, vals, num_rows, key_ords, types, hash_types,
                key_range=False, dense_span=0, dense_lo=0):
    """Standalone (host-path) build prep: one dispatch per build. The
    in-program-build default inlines _prep_build_arrays into the chain
    instead; this program remains for the knob-off / fallback path."""
    return _prep_build_arrays(datas, vals, num_rows, key_ords, types,
                              hash_types, key_range=key_range,
                              dense_span=dense_span, dense_lo=dense_lo)


def _dense_table_arrays(keys_sorted, n_valid, lo, span):
    """Traceable core of the dense inverse index over the hash-sorted
    build: valid (live, non-null-key) rows occupy the sorted prefix
    [0, n_valid), so scatter their key positions once; absent values
    stay -1."""
    cap = keys_sorted.shape[0]
    iota = jnp.arange(cap, dtype=jnp.int32)
    pos = (keys_sorted.astype(jnp.int64) - lo).astype(jnp.int32)
    pos = jnp.where(iota < n_valid, pos, jnp.int32(span))
    pos = jnp.clip(pos, 0, span)          # sentinel slot = span
    table = jnp.full(span + 1, -1, dtype=jnp.int32)
    table = table.at[pos].set(iota)
    return table[:span]


@partial(jax.jit, static_argnames=("span",))
def _prep_dense_table(keys_sorted, n_valid, lo, span):
    """Dense inverse index as its own program — the runtime-range path,
    used when the key bounds only became host-known via the flag sync.
    One small scatter per query per build — prep-time only."""
    return _dense_table_arrays(keys_sorted, n_valid, lo, span)


def _ghost_of(col: Column) -> "_Ghost":
    return _Ghost(col.dtype,
                  col.dictionary if isinstance(col, StringColumn) else None,
                  getattr(col, "stats", None))


#: prep results keyed by broadcast exchange object — a side table (not
#: attributes) so the exchange stays picklable for cluster map tasks and
#: the device arrays die with the query's plan objects. The global lock
#: guards only cache BOOKKEEPING; build materialization (arbitrarily
#: expensive, and possibly recursing into prepare_build for a chain
#: nested inside the build subtree) runs outside it, coordinated by a
#: per-(exchange, key) event so concurrent consumers wait on their own
#: build, never on an unrelated one.
_PREP_CACHE: "weakref.WeakKeyDictionary" = None
_PREP_LOCK = lockorder.make_lock("execs.fused.prepCache")


def _finalize_entries_locked(entries) -> None:
    """Caller holds _PREP_LOCK. Fetch the dup/key-range flags for every
    launched-but-unfinished entry in ONE device_get and build their
    PreparedBuilds (dense tables launch async). Safe under the global
    lock: finalization never materializes a subtree, so it cannot
    recurse into the prep machinery."""
    todo = [e for e in entries
            if not e["done"].is_set() and e.get("pending") is not None]
    if not todo:
        return
    try:
        flags = jax.device_get(
            [(e["pending"][0][3], e["pending"][0][5],
              e["pending"][0][6]) for e in todo])
    except BaseException as exc:
        for e in todo:
            e["error"] = exc
            # drop the poisoned entry like the launch-failure path: a
            # transient error during the flag sync must not
            # permanently fail every later consumer of this exchange
            cache, key = e["slot"]
            if cache.get(key) is e:
                cache.pop(key, None)
            e["done"].set()
        raise
    for e, (dup_h, kmin_h, kmax_h) in zip(todo, flags):
        (sh, sdatas, svals, _d, n_valid, _kn, _kx, table), \
            ghosts, want_range, build_keys, span_max, dense_span, \
            dense_lo = e.pop("pending")
        if bool(dup_h):
            prep = PreparedBuild(ok=False)
        else:
            prep = PreparedBuild(
                ok=True, h_sorted=sh, datas=tuple(sdatas),
                vals=tuple(svals), n_valid=n_valid, ghosts=ghosts)
            if dense_span > 0:
                # stats-known range: the table came out of _prep_build
                prep.table = table
                prep.dense_lo = dense_lo
            elif want_range and int(kmin_h) <= int(kmax_h):
                from spark_rapids_tpu.ops.groupby import quantize_range

                qlo, qhi = quantize_range(int(kmin_h), int(kmax_h))
                span = qhi - qlo + 1
                if span <= span_max:
                    with TraceRange("FusedChain.denseTable"):
                        prep.table = _prep_dense_table(
                            sdatas[build_keys[0]], n_valid,
                            jnp.int64(qlo), span=span)
                    prep.dense_lo = qlo
        e["prep"] = prep
        e["done"].set()


def prepare_builds(specs) -> List[PreparedBuild]:
    """Materialize + hash-sort MANY broadcast build sides with (at
    most) ONE host sync. ``specs``: [(exchange, build_keys,
    build_types, hash_types, dense_span_max)].

    Per-build prep costs a dispatch (+1 for a dense table) but the dup/
    key-range flags need a blocking device_get; done per build that is
    4 round trips on a q9-class 4-dim join chain. Builds are claimed
    and LAUNCHED one at a time (a build's materialization can recurse
    into prepare_builds for a fused chain nested in its subtree — a
    sibling claimed later is then simply unowned and the nested call
    owns it; a sibling launched earlier is finalizable by ANY caller,
    so no claim is ever held un-launched while waiting). The flag sync
    itself batches over every still-pending launch. Cached per
    exchange object so every consumer partition and every chain
    sharing the broadcast pays its prep only once."""
    import weakref

    global _PREP_CACHE
    entries = []   # (cache, key, entry, owner) per spec
    for exch, build_keys, build_types, hash_types, span_max in specs:
        key = (tuple(build_keys), tuple(hash_types), span_max)
        with _PREP_LOCK:
            if _PREP_CACHE is None:
                _PREP_CACHE = weakref.WeakKeyDictionary()
            cache = _PREP_CACHE.get(exch)
            if cache is None:
                cache = _PREP_CACHE[exch] = {}
            entry = cache.get(key)
            if entry is None:
                entry = cache[key] = {"done": threading.Event(),
                                      "prep": None, "error": None,
                                      "pending": None,
                                      "slot": (cache, key)}
                owner = True
            else:
                owner = False
        entries.append((cache, key, entry, owner))
        if not owner:
            continue
        # launch this build's prep now (async, no sync); materialize
        # may recurse into prepare_builds for nested chains
        try:
            want_range = span_max > 0 and len(build_keys) == 1 and (
                hash_types[0].is_integral or
                hash_types[0] in (dt.DATE, dt.TIMESTAMP, dt.BOOLEAN))
            with exch._materialize().acquired() as b:
                # when footer/upload stats survived the build subtree
                # the key range is host-known NOW: fold the dense table
                # into the prep program and skip the runtime-range
                # machinery (stats are bounds, possibly loose — the
                # table just covers a wider span)
                dense_span = 0
                dense_lo = 0
                if want_range and b.columns:
                    st = getattr(b.columns[build_keys[0]], "stats",
                                 None)
                    if st is not None:
                        from spark_rapids_tpu.ops.groupby import \
                            quantize_range

                        qlo, qhi = quantize_range(int(st[0]),
                                                  int(st[1]))
                        if qhi - qlo + 1 <= span_max:
                            dense_span = qhi - qlo + 1
                            dense_lo = qlo
                with TraceRange("FusedChain.prepareBuild"):
                    out = _prep_build(
                        [c.data for c in b.columns],
                        [c.validity for c in b.columns],
                        b.num_rows_device(), tuple(build_keys),
                        tuple(build_types), tuple(hash_types),
                        key_range=want_range and not dense_span,
                        dense_span=dense_span,
                        dense_lo=np.int64(dense_lo))
                ghosts = [_ghost_of(c) for c in b.columns]
            with _PREP_LOCK:
                entry["pending"] = (out, ghosts, want_range,
                                    tuple(build_keys), span_max,
                                    dense_span, dense_lo)
        except BaseException as e:
            entry["error"] = e
            with _PREP_LOCK:
                cache.pop(key, None)  # a later caller may retry
            entry["done"].set()
            raise

    # one sync finalizes every build this call launched
    with _PREP_LOCK:
        _finalize_entries_locked([e for _c, _k, e, own in entries
                                  if own])
    out: List[PreparedBuild] = []
    for _cache, _key, entry, _own in entries:
        if not entry["done"].is_set():
            # someone else launched it: finalize if launched, else wait
            # for their launch to post (short — the launcher is inside
            # materialize+dispatch, never inside a wait on us)
            with _PREP_LOCK:
                _finalize_entries_locked([entry])
            if not entry["done"].is_set():
                entry["done"].wait()
        if entry["error"] is not None:
            raise entry["error"]
        out.append(entry["prep"])
    return out


def prepare_build(exch: BroadcastExchangeExec, build_keys: Sequence[int],
                  build_types: Sequence[dt.DType],
                  hash_types: Sequence[dt.DType],
                  dense_span_max: int = _DENSE_SPAN_MAX
                  ) -> PreparedBuild:
    """Single-build convenience wrapper over prepare_builds."""
    return prepare_builds([(exch, build_keys, build_types,
                            hash_types, dense_span_max)])[0]


# ---------------------------------------------------------------------------
# the chain engine
# ---------------------------------------------------------------------------


def _batching_ctx():
    """The thread's micro-batching slice context, or None outside a
    query-service slice (the common library path: one sys.modules hit
    plus a thread-local read)."""
    try:
        from spark_rapids_tpu.service.batching import microbatch as _mb
    except Exception:  # pragma: no cover - service package unavailable
        return None
    return _mb.current()


@dataclasses.dataclass
class _Ghost:
    """Host mirror of one working column during the ghost walk: what the
    program can't carry through jit (dictionaries, footer stats)."""

    dtype: dt.DType
    dictionary: Optional[np.ndarray] = None
    stats: Optional[tuple] = None


class FusedChain:
    """Compiles a step list into one jitted program over raw arrays."""

    def __init__(self, steps: List, source_types: List[dt.DType],
                 n_builds: int):
        self.steps = list(steps)
        self.source_types = list(source_types)
        self.n_builds = n_builds
        self._number_aux_slots()
        self._programs: dict = {}

    def _number_aux_slots(self) -> None:
        # aux operand slots for string predicates: number sequentially
        # in (step, pred) order — run() collects per-batch values in
        # the same order
        slot = 0
        for s in self.steps:
            for p in getattr(s, "aux_preds", ()):
                p.base_slot = slot
                slot += p.n_slots()
        self.n_aux = slot

    # jit closures and compiled programs never ship to remote executors
    def __getstate__(self):
        return {"steps": self.steps, "source_types": self.source_types,
                "n_builds": self.n_builds}

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._number_aux_slots()
        self._programs = {}

    def chain_key(self, compact_out: bool, modes: tuple = (),
                  decode: tuple = (), inline: tuple = ()):
        ks = tuple(s.key() for s in self.steps)
        if any(k is None for k in ks):
            return None
        return ("fused_chain", ks, tuple(self.source_types), compact_out,
                modes, decode, inline)

    def _program(self, compact_out: bool, modes: tuple = (),
                 decode: tuple = (), inline: tuple = ()):
        ckey = (compact_out, modes, decode, inline)
        prog = self._programs.get(ckey)
        if prog is not None:
            return prog
        key = self.chain_key(compact_out, modes, decode, inline)
        # single-flight: concurrent same-template queries (different
        # tenants) racing a cold key trace it ONCE and share the
        # program — the cross-tenant compile fence
        prog = fused_cache_get_or_build(
            key, lambda: self._build_program(compact_out, modes,
                                             decode, inline))
        self._programs[ckey] = prog
        return prog

    def _build_program(self, compact_out: bool, modes: tuple = (),
                       decode: tuple = (), inline: tuple = ()):
        run, label = self._trace_fn(compact_out, modes, decode, inline)
        run.__name__ = run.__qualname__ = label
        return partial(jax.jit, static_argnames=("types",))(run)

    def _trace_fn(self, compact_out: bool, modes: tuple = (),
                  decode: tuple = (), inline: tuple = ()):
        """-> (the chain's traceable function, its label). A program of
        its own through ``_build_program``; FusedAggregateExec traces it
        into its one-launch step."""
        steps = self.steps
        sort_step = steps[-1] if steps and \
            isinstance(steps[-1], SortStep) else None

        def run_steps(cols, live, num_rows, builds, aux, capacity):
            for step in steps:
                if isinstance(step, FilterStep):
                    ctx = EvalContext(cols, capacity, num_rows,
                                      in_jit=True)
                    ctx.aux = aux
                    v = broadcast(step.condition.eval(ctx), ctx)
                    keep = v.data
                    if v.validity is not None:
                        keep = keep & v.validity
                    live = live & keep
                elif isinstance(step, ProjectStep):
                    ctx = EvalContext(cols, capacity, num_rows,
                                      in_jit=True)
                    ctx.aux = aux
                    cols = [broadcast(e.eval(ctx), ctx)
                            for e in step.exprs]
                elif isinstance(step, SortStep):
                    continue  # terminal; handled below
                else:
                    cols, live = _apply_join(step, cols, live,
                                             builds[step.build_index])
            outs = [(c.data, c.validity) for c in cols]
            if sort_step is not None:
                # dead lanes (padding + filtered rows) sink last via
                # the live mask
                order = sortkeys.lexsort_indices(
                    outs, [c.dtype for c in cols],
                    list(sort_step.specs), num_rows, live_mask=live)
            elif compact_out:
                order, _ = sortkeys.stable_order([~live])
            else:
                return outs, live
            datas, vals = sortkeys.take_rows(
                order, [d for d, _ in outs], [v for _, v in outs])
            return list(zip(datas, vals)), \
                jnp.sum(live).astype(jnp.int32)

        def inline_build_ops(raw_builds):
            # in-program build: trace the build-side prep (hash sort,
            # dup probe, stats-known dense table) INSIDE this program.
            # Per build, hand run_steps the probe-ready ops tuple and
            # hand the caller the prepared arrays + dup flag so later
            # batches reuse them via the probe-only variant — the
            # standalone _prep_build dispatch and its flag-sync
            # device_get both disappear from the stage.
            ops, prepared = [], []
            for spec, (bdatas, bvals, bnum) in zip(inline, raw_builds):
                bkeys, btypes, htypes, dspan, dlo = spec
                (sh, sdatas, svals, dup, n_valid, _kn, _kx,
                 table) = _prep_build_arrays(
                    list(bdatas), list(bvals), bnum, bkeys, btypes,
                    htypes, dense_span=dspan, dense_lo=dlo)
                ops.append((sh, tuple(sdatas), tuple(svals), n_valid,
                            table if dspan > 0 else None,
                            dlo if dspan > 0 else None))
                prepared.append((sh, tuple(sdatas), tuple(svals), dup,
                                 n_valid, table))
            return ops, tuple(prepared)

        if decode:
            # scan-decode prelude: the chain starts from the PACKED
            # upload buffers and inlines the transfer decode, so the
            # scan->filter->join->project stage pays zero decode
            # dispatch (see interop.PackedBatch)
            from spark_rapids_tpu.execs import interop as _interop

            dec_specs, col_map, cap = decode

            if inline:
                def run(bufs, bases, num_rows, raw_builds, aux, types):
                    decoded = _interop.unpack_arrays(list(bufs), bases,
                                                     dec_specs, cap)
                    cols = [ColV(t, decoded[bi],
                                 None if vi < 0 else decoded[vi])
                            for t, (_k, bi, vi) in zip(types, col_map)]
                    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    builds, prepared = inline_build_ops(raw_builds)
                    outs, live = run_steps(cols, live, num_rows,
                                           builds, aux, cap)
                    return outs, live, prepared
            else:
                def run(bufs, bases, num_rows, builds, aux, types):
                    decoded = _interop.unpack_arrays(list(bufs), bases,
                                                     dec_specs, cap)
                    cols = [ColV(t, decoded[bi],
                                 None if vi < 0 else decoded[vi])
                            for t, (_k, bi, vi) in zip(types, col_map)]
                    live = jnp.arange(cap, dtype=jnp.int32) < num_rows
                    return run_steps(cols, live, num_rows, builds, aux,
                                     cap)
        elif inline:
            def run(datas, vals, num_rows, raw_builds, aux, types):
                capacity = datas[0].shape[0] if datas else 128
                cols = [ColV(t, d, v)
                        for t, d, v in zip(types, datas, vals)]
                live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
                builds, prepared = inline_build_ops(raw_builds)
                outs, live = run_steps(cols, live, num_rows, builds,
                                       aux, capacity)
                return outs, live, prepared
        else:
            def run(datas, vals, num_rows, builds, aux, types):
                capacity = datas[0].shape[0] if datas else 128
                cols = [ColV(t, d, v)
                        for t, d, v in zip(types, datas, vals)]
                live = jnp.arange(capacity, dtype=jnp.int32) < num_rows
                return run_steps(cols, live, num_rows, builds, aux,
                                 capacity)

        # distinct per-chain names so dispatch telemetry attributes each
        # chain program separately (every chain would otherwise report
        # as one 'run' bucket). The crc tag separates chains that share
        # a step-type shape but compile different expressions (q9's five
        # filter+project branches); it keys on the SAME (compact_out,
        # modes) tuple as the program cache so dense-probe and
        # hash-probe variants of one chain attribute separately
        import zlib

        key = self.chain_key(compact_out, modes, decode, inline)
        tag = zlib.crc32(repr(key if key is not None
                              else id(self)).encode()) & 0xFFFF
        label = "fused_chain[" + ("build+" if inline else "") + \
            ("decode+" if decode else "") + \
            "+".join(type(s).__name__.replace("Step", "").lower()
                     for s in steps) + f"]@{tag:04x}"
        return run, label

    def run(self, batch, preps: List[PreparedBuild],
            compact_out: bool):
        """-> (outs, live_mask | new_count, final output ghosts). The
        ghost walk runs ONCE per batch, serving both the aux operand
        collection and the caller's output wrapping. ``batch`` may be a
        still-packed upload (interop.PackedBatch): the program then
        inlines the transfer decode as its first traced steps.

        Under a query-service slice (service/batching context on this
        thread) the launch routes through the micro-batcher: same-key
        same-bucket dispatches from concurrent queries coalesce into
        one physical program launch, and the shape-bucket registry logs
        the (program, bucket) observation for warmup/stats."""
        modes, decode, args, final_ghosts = self.call_args(
            batch, preps, batch.num_rows_device())
        prog = self._program(compact_out, modes, decode)
        statics = {"types": tuple(self.source_types)}
        ctx = _batching_ctx()
        key = None if ctx is None else \
            self.chain_key(compact_out, modes, decode)
        if ctx is None or key is None:
            # unkeyed chains (some step has no structural key) must NOT
            # coalesce: the only stable identity would be id(prog), and
            # a recycled object id after GC could hand another chain's
            # cached K-way program back — silently wrong results
            outs, live = prog(*args, **statics)
        else:
            reg = getattr(ctx.batcher, "registry", None)
            if reg is not None and not decode:
                # packed chains bake the decode capacity in as a
                # static, so their shapes are not ladder-replayable.
                # stream_args=2: leaves of (datas, vals) ride the
                # ladder; build_ops/aux keep their recorded shapes
                reg.record(key, prog, args, statics, stream_args=2)
            outs, live = ctx.batcher.call(key, prog, args, statics,
                                          ctx.query_id, ctx.multi)
        return outs, live, final_ghosts

    def call_args(self, batch, preps: List[PreparedBuild], num_rows):
        """-> (modes, decode, the chain function's positional arguments,
        final output ghosts) for one batch; the ghost walk runs once."""
        from spark_rapids_tpu.execs import interop as _interop

        states, final_ghosts = self._ghost_states(batch, preps)
        build_ops = tuple(
            (p.h_sorted, p.datas, p.vals, p.n_valid, p.table,
             None if p.table is None else p.dense_lo)
            for p in preps)
        # dense/hash probe mode is per-build runtime information (key
        # stats), so it keys the compiled program separately
        modes = tuple(p.table is not None for p in preps)
        aux = self._aux_from_states(states)
        if isinstance(batch, _interop.PackedBatch):
            return modes, batch.decode_key(), \
                (tuple(batch.bufs), tuple(batch.dec_bases), num_rows,
                 build_ops, aux), final_ghosts
        return modes, (), \
            ([c.data for c in batch.columns],
             [c.validity for c in batch.columns], num_rows, build_ops,
             aux), final_ghosts

    def run_inline(self, batch, descs: tuple, raw_builds: Sequence,
                   build_ghosts: Sequence, compact_out: bool):
        """First-batch launch of the build-inlined program variant:
        -> (outs, live | count, prepared build array tuples, output
        ghosts). ``descs`` is the static per-build descriptor
        ((build_keys, build_types, hash_types, dense_span, dense_lo),
        ...); ``raw_builds`` the matching raw (datas, vals, num_rows)
        triples. Deliberately bypasses the micro-batcher and the
        warmup-ladder registry: the variant runs ONCE per (chain,
        query) — its argument layout puts raw build arrays where
        probe-only launches put prepared ops, so a ladder replay would
        re-prepare builds for nothing, and a one-shot launch has no
        cross-tenant sharing to win."""
        from spark_rapids_tpu.execs import interop as _interop

        ghost_preps = [PreparedBuild(ok=True, ghosts=list(g))
                       for g in build_ghosts]
        states, final_ghosts = self._ghost_states(batch, ghost_preps)
        aux = self._aux_from_states(states)
        raw_ops = tuple((tuple(d), tuple(v), n)
                        for d, v, n in raw_builds)
        if isinstance(batch, _interop.PackedBatch):
            decode = batch.decode_key()
            prog = self._program(compact_out, (), decode, inline=descs)
            args = (tuple(batch.bufs), tuple(batch.dec_bases),
                    batch.num_rows_device(), raw_ops, aux)
        else:
            prog = self._program(compact_out, (), inline=descs)
            args = ([c.data for c in batch.columns],
                    [c.validity for c in batch.columns],
                    batch.num_rows_device(), raw_ops, aux)
        outs, live, prepared = prog(*args,
                                    types=tuple(self.source_types))
        return outs, live, prepared, final_ghosts

    # -- host mirror --------------------------------------------------------

    def _ghost_states(self, batch, preps: List[PreparedBuild]):
        """Per-step INPUT ghost lists, plus the final output ghosts."""
        from spark_rapids_tpu.execs import interop as _interop

        if isinstance(batch, _interop.PackedBatch):
            ghosts = [_Ghost(t, d, s) for t, d, s in batch.ghost_info()]
        else:
            ghosts = [_ghost_of(c) for c in batch.columns]
        states = []
        for step in self.steps:
            states.append(ghosts)
            if isinstance(step, (FilterStep, SortStep)):
                continue
            if isinstance(step, ProjectStep):
                ghosts = [self._project_ghost(e, ghosts)
                          for e in step.exprs]
                continue
            if step.kind in ("left_semi", "left_anti"):
                continue
            ghosts = ghosts + list(preps[step.build_index].ghosts)
        return states, ghosts

    def _aux_from_states(self, states) -> tuple:
        """Per-batch scalar operands for string predicates: dictionary
        searchsorted positions of each predicate's literals, in slot
        order (matching the numbering done at construction)."""
        if self.n_aux == 0:
            return ()
        aux: List[int] = []
        for step, ghosts in zip(self.steps, states):
            for p in getattr(step, "aux_preds", ()):
                g = ghosts[p.children[0].ordinal]
                aux.extend(p.aux_values(g.dictionary))
        assert len(aux) == self.n_aux, (len(aux), self.n_aux)
        # plain ints: jit traces them as scalar operands shipped with
        # the call (a jnp.int32() per value would be its own transfer)
        return tuple(aux)

    @staticmethod
    def _project_ghost(e: Expression, ghosts: List[_Ghost]) -> _Ghost:
        u = _unwrap_alias(e)
        if isinstance(u, BoundReference):
            g = ghosts[u.ordinal]
            return _Ghost(e.dtype, g.dictionary, g.stats)
        if e.dtype is dt.STRING:
            assert isinstance(u, Literal), \
                "device_only string expr must be a ref or literal"
            dictionary = np.array(
                [] if u.value is None else [u.value], dtype=object)
            return _Ghost(dt.STRING, dictionary, None)
        return _Ghost(e.dtype, None, derive_stats(e, ghosts))

    def wrap(self, outs, ghosts: List[_Ghost], num_rows) -> ColumnarBatch:
        cols: List[Column] = []
        for (data, validity), g in zip(outs, ghosts):
            if g.dtype is dt.STRING:
                cols.append(StringColumn(data, g.dictionary, validity))
            else:
                cols.append(Column(g.dtype, data, validity,
                                   stats=g.stats))
        return ColumnarBatch(cols, num_rows)


def _apply_join(step: JoinStep, cols: List[ColV], live,
                b: Tuple) -> Tuple[List[ColV], jax.Array]:
    """Unique-build probe. Dense mode (fact->dim surrogate keys): ONE
    gather into the prep-time inverse table — exact by construction, no
    hashing, no verification. Hash mode: searchsorted into the
    hash-sorted build + exact key verification. Either way each probe
    row has at most one candidate; matches fold into the live-mask
    (inner/semi/anti) or gathered validity (left)."""
    sh, datas, vals, n_valid, table, dense_lo = b
    b_cap = sh.shape[0]
    if table is not None:
        span = table.shape[0]
        sc = cols[step.stream_keys[0]]
        pos = sc.data.astype(jnp.int64) - dense_lo
        inb = (pos >= 0) & (pos < span)
        idx = jnp.take(table,
                       jnp.clip(pos, 0, span - 1).astype(jnp.int32))
        found = inb & (idx >= 0)
        if sc.validity is not None:
            found = found & sc.validity
        lo_c = jnp.clip(idx, 0, b_cap - 1)
    else:
        key_cols = [cols[o] for o in step.stream_keys]
        h_p = _hash_keys(key_cols, [c.dtype for c in key_cols],
                         step.key_common, _PROBE_NULL)
        lo = jnp.searchsorted(sh, h_p, side="left").astype(jnp.int32)
        lo_c = jnp.clip(lo, 0, b_cap - 1)
        found = (jnp.take(sh, lo_c) == h_p) & (lo < n_valid)
        for so, bo, ct in zip(step.stream_keys, step.build_keys,
                              step.key_common):
            sc = cols[so]
            sd = sc.data if sc.dtype is ct else \
                sc.data.astype(ct.kernel_dtype)
            bd = jnp.take(datas[bo], lo_c)
            if step.build_types[bo] is not ct:
                bd = bd.astype(ct.kernel_dtype)
            bv = vals[bo]
            bv = None if bv is None else jnp.take(bv, lo_c)
            s_comps, s_valid = sortkeys.equality_parts(sd, sc.validity,
                                                       ct)
            b_comps, b_valid = sortkeys.equality_parts(bd, bv, ct)
            found = found & s_valid & b_valid
            for scp, bcp in zip(s_comps, b_comps):
                found = found & (scp == bcp)
    if step.kind == "left_semi":
        return cols, live & found
    if step.kind == "left_anti":
        return cols, live & ~found
    out = list(cols)
    for bd, bv, bt in zip(datas, vals, step.build_types):
        gd = jnp.take(bd, lo_c)
        gv = None if bv is None else jnp.take(bv, lo_c)
        if step.kind == "left":
            gv = found if gv is None else (gv & found)
        out.append(ColV(bt, gd, gv))
    return out, (live & found) if step.kind == "inner" else live


# ---------------------------------------------------------------------------
# execs
# ---------------------------------------------------------------------------


def _build_key_specs(steps) -> list:
    """(build_keys, build_types, key_common) per JoinStep, ordered by
    ``build_index`` — the inputs prepare_build needs, shared by both
    fused execs. ORDER MATTERS: the builds list is in extraction
    (reverse-execution) order while steps run in execution order;
    indexing by build_index keeps spec[i] paired with builds[i] (a
    mismatch cross-hashes the wrong key columns: loud IndexError when
    widths differ, silently empty probes when they coincide)."""
    joins = sorted((s for s in steps if isinstance(s, JoinStep)),
                   key=lambda s: s.build_index)
    return [(tuple(s.build_keys), tuple(s.build_types),
             tuple(s.key_common)) for s in joins]


class FusedChainExec(TpuExec):
    """Standalone fused segment: filters/projections/broadcast probes
    (and, for post-aggregate tails, the final ORDER BY) in one program
    per batch, compacted once at the end (lazy row count). Falls back
    to the preserved unfused subtree when a build side has duplicate
    key hashes."""

    #: planner-set: the packed scan feeding this chain (its decode runs
    #: inside the chain program); reset to eager decode on fallback
    _defer_scan = None

    def __init__(self, source: TpuExec, chain: FusedChain,
                 builds: List[BroadcastExchangeExec], schema: Schema,
                 fallback: TpuExec, conf=None):
        super().__init__([source], schema)
        self.chain = chain
        self.builds = builds
        self.fallback = fallback
        self.conf = conf
        self.build_key_specs = _build_key_specs(chain.steps)
        self._preps: Optional[List[PreparedBuild]] = None
        self._preps_ok: Optional[bool] = None
        self._inline_evt = None
        self._prep_lock = lockorder.make_lock("execs.fused.chainPrep")

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_prep_lock", None)
        state.pop("_inline_evt", None)
        state["_preps"] = None
        state["_preps_ok"] = None
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._inline_evt = None
        self._prep_lock = lockorder.make_lock("execs.fused.chainPrep")

    @property
    def num_partitions(self) -> int:
        return self.children[0].num_partitions

    def _ensure_preps(self) -> bool:
        with self._prep_lock:
            if self._preps_ok is None:
                from spark_rapids_tpu import config as cfg

                conf = getattr(self, "conf", None)
                span_max = conf.get(cfg.FUSION_DENSE_PROBE_MAX_SPAN) \
                    if conf is not None else _DENSE_SPAN_MAX
                preps = prepare_builds(
                    [(exch, keys, types, commons, span_max)
                     for exch, (keys, types, commons) in zip(
                         self.builds, self.build_key_specs)])
                ok = all(p.ok for p in preps)
                if not ok and self._defer_scan is not None:
                    # the fallback subtree re-executes the scan and is
                    # not fusion-aware: restore eager decode first
                    self._defer_scan.defer_decode = False
                self._preps = preps if ok else None
                self._preps_ok = ok
            return self._preps_ok

    def _inline_enabled(self) -> bool:
        """In-program build applies when the chain HAS builds and the
        knob is on; chains without joins take the (free) host path."""
        if not self.builds:
            return False
        from spark_rapids_tpu import config as cfg

        conf = getattr(self, "conf", None)
        return bool(conf.get(cfg.FUSION_IN_PROGRAM_BUILD)
                    if conf is not None
                    else cfg.FUSION_IN_PROGRAM_BUILD.default)

    def _inline_first(self, batch, compact_out: bool):
        """Single-flight first-batch inline build. Returns the chain
        output triple when THIS thread ran the build-inlined launch, or
        None when the builds were resolved (or failed to duplicates) by
        another thread / the dup fallback engaged — the caller then
        consults ``_preps_ok``. A leader that errors leaves ``_preps_ok``
        None; the next waiter retries as the new leader (same contract
        as the _PREP_CACHE poisoned-entry drop)."""
        while True:
            leader = False
            with self._prep_lock:
                if self._preps_ok is not None:
                    return None
                evt = self._inline_evt
                if evt is None:
                    evt = self._inline_evt = threading.Event()
                    leader = True
            if leader:
                try:
                    return self._inline_launch(batch, compact_out)
                finally:
                    with self._prep_lock:
                        self._inline_evt = None
                    evt.set()
            evt.wait()
            if self._preps_ok is not None:
                return None

    def _inline_launch(self, batch, compact_out: bool):
        """Materialize the build sides RAW and run the chain's
        build-inlined program variant on the first stream batch: hash
        sort, duplicate probe and (stats-known) dense table trace
        INSIDE the chain program, so stage0 sheds the standalone
        _prep_build dispatch AND its flag-sync device_get. The launch
        is SPECULATIVE — probe results are garbage if a build has
        duplicate key hashes — so the dup flags ride back as program
        outputs and are read via np.asarray, a transfer that overlaps
        the (already in-flight) program instead of costing its own
        dispatch. Duplicates discard the output, restore eager scan
        decode, and fall back to the preserved unfused subtree, exactly
        like the host path. Returns (outs, live|count, ghosts) or None
        on fallback. Unlike the host path the runtime-key-range dense
        table is NOT built here (it needed the flag sync this variant
        exists to remove): builds without host-known stats probe in
        hash mode."""
        import contextlib

        from spark_rapids_tpu import config as cfg

        conf = getattr(self, "conf", None)
        span_max = conf.get(cfg.FUSION_DENSE_PROBE_MAX_SPAN) \
            if conf is not None else _DENSE_SPAN_MAX
        descs, raw, ghosts_l = [], [], []
        with contextlib.ExitStack() as stack:
            for exch, (bkeys, btypes, commons) in zip(
                    self.builds, self.build_key_specs):
                bb = stack.enter_context(exch._materialize().acquired())
                dense_span = 0
                dense_lo = 0
                want_range = span_max > 0 and len(bkeys) == 1 and (
                    commons[0].is_integral or
                    commons[0] in (dt.DATE, dt.TIMESTAMP, dt.BOOLEAN))
                if want_range and bb.columns:
                    st = getattr(bb.columns[bkeys[0]], "stats", None)
                    if st is not None:
                        from spark_rapids_tpu.ops.groupby import \
                            quantize_range

                        qlo, qhi = quantize_range(int(st[0]),
                                                  int(st[1]))
                        if qhi - qlo + 1 <= span_max:
                            dense_span = qhi - qlo + 1
                            dense_lo = qlo
                descs.append((tuple(bkeys), tuple(btypes),
                              tuple(commons), dense_span, dense_lo))
                raw.append(([c.data for c in bb.columns],
                            [c.validity for c in bb.columns],
                            bb.num_rows_device()))
                ghosts_l.append([_ghost_of(c) for c in bb.columns])
            with TraceRange("FusedChainExec.inlineBuild"):
                outs, live, prepared, ghosts = self.chain.run_inline(
                    batch, tuple(descs), raw, ghosts_l, compact_out)
        # np.asarray, not device_get: the flag rides home with the
        # in-flight program's results rather than as its own counted
        # round trip (the telemetry's device_get wrapper is the
        # dispatch boundary; __array__ coercion isn't)
        if any(bool(np.asarray(p[3])) for p in prepared):
            with self._prep_lock:
                self._preps = None
                self._preps_ok = False
            if self._defer_scan is not None:
                # the fallback subtree re-executes the scan and is
                # not fusion-aware: restore eager decode first
                self._defer_scan.defer_decode = False
            return None
        preps = []
        for (bkeys, _bt, _cm, dspan, dlo), p, g in zip(descs, prepared,
                                                       ghosts_l):
            sh, sdatas, svals, _dup, n_valid, table = p
            prep = PreparedBuild(ok=True, h_sorted=sh, datas=sdatas,
                                 vals=svals, n_valid=n_valid, ghosts=g)
            if dspan > 0:
                prep.table = table
                prep.dense_lo = dlo
            preps.append(prep)
        with self._prep_lock:
            self._preps = preps
            self._preps_ok = True
        return outs, live, ghosts

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        if self._preps_ok is None and self._inline_enabled():
            return timed(self, self._iter_inline(partition))
        if not self._ensure_preps():
            return self.fallback.execute(partition)
        return timed(self, self._iter_probe(partition))

    def _iter_probe(self, partition: int):
        saw = False
        has_sort = any(isinstance(s, SortStep)
                       for s in self.chain.steps)
        for b in self.children[0].execute(partition):
            # skip empties only when the count is ALREADY host-side:
            # forcing a lazy count here would cost the same round
            # trip the skip is trying to save
            n = b.num_rows
            if isinstance(n, int) and n == 0 and saw:
                continue
            if saw and has_sort:
                # not an assert: must survive python -O — a second
                # batch through a SortStep chain would silently
                # produce per-batch (non-global) order
                raise RuntimeError(
                    "SortStep chain fed more than one batch "
                    "(planner bug: source must be a single-batch "
                    "aggregate)")
            saw = True
            with TraceRange("FusedChainExec"):
                outs, n, ghosts = self.chain.run(b, self._preps,
                                                 compact_out=True)
            yield self.chain.wrap(outs, ghosts, n)

    def _iter_inline(self, partition: int):
        """First batch runs the build-inlined variant (or waits for a
        peer partition's); every later batch takes the probe-only path
        over the prepared arrays it produced."""
        saw = False
        has_sort = any(isinstance(s, SortStep)
                       for s in self.chain.steps)
        for b in self.children[0].execute(partition):
            n = b.num_rows
            if isinstance(n, int) and n == 0 and saw:
                continue
            if saw and has_sort:
                raise RuntimeError(
                    "SortStep chain fed more than one batch "
                    "(planner bug: source must be a single-batch "
                    "aggregate)")
            if self._preps_ok is None:
                res = self._inline_first(b, compact_out=True)
                if res is not None:
                    saw = True
                    outs, n2, ghosts = res
                    yield self.chain.wrap(outs, ghosts, n2)
                    continue
                # a peer thread may have prepared the builds; fall
                # through to the shared dup check / probe path
            if not self._preps_ok:
                # duplicate build-key hashes: the speculative output
                # is discarded, the preserved subtree runs. Checked
                # OUTSIDE the is-None branch: a peer partition's
                # leader can set the dup flag between our execute()
                # routing decision and this batch, in which case
                # _preps is None and the probe path must not run.
                yield from self.fallback.execute(partition)
                return
            saw = True
            with TraceRange("FusedChainExec"):
                outs, n2, ghosts = self.chain.run(b, self._preps,
                                                  compact_out=True)
            yield self.chain.wrap(outs, ghosts, n2)

    def tree_string(self, indent: int = 0) -> str:
        return _fused_tree_string(self, indent,
                                  f"[{len(self.chain.steps)} fused steps]")

    def metric_children(self):
        return _fused_metric_children(self)


def _fused_tree_string(exec_, indent: int, note: str) -> str:
    """Explain output for a fused exec — when the duplicate-build
    fallback ran, the UNfused subtree did the work and must be what
    explain shows (degradation is never silent, same rule as cluster
    local-placement)."""
    label = "  " * indent + exec_.name + " " + note
    if exec_._preps_ok is False:
        label += " [FELL BACK: duplicate build key hashes]"
        return "\n".join([label,
                          exec_.fallback.tree_string(indent + 1)])
    lines = [label]
    for c in exec_.children:
        lines.append(c.tree_string(indent + 1))
    return "\n".join(lines)


def _fused_metric_children(exec_):
    """What ``all_metrics`` walks under a fused exec: the preserved
    unfused subtree when the duplicate-build fallback did the work."""
    if exec_._preps_ok is False:
        return [exec_.fallback]
    return exec_.children


def _valid_lane(validity, capacity: int):
    return jnp.ones(capacity, dtype=bool) if validity is None \
        else validity


def _same_dictionary(a, b) -> bool:
    """A host compare, for dictionaries as small as a dense key's; two
    large ones are the same only if they are one object."""
    return a is b or (len(a) == len(b) <= gb._DENSE_MAX_GROUPS and
                      bool(np.array_equal(a, b)))


@functools.lru_cache(maxsize=64)
def _identity_carry(dtypes: tuple, capacity: int):
    """Running partials of no rows in the one-launch step's carry
    layout, on the device once a process: what a partition's first
    batch merges into."""
    return ([(jax.device_put(np.zeros(capacity, dtype=np.dtype(t))),
              jax.device_put(np.zeros(capacity, dtype=bool)))
             for t in dtypes], np.int32(0))


class _InlineDupFallback(Exception):
    """Internal: the speculative build-inlined first launch found
    duplicate build-key hashes. Raised out of
    FusedAggregateExec._update_inputs — safe because the aggregate
    yields nothing before its first _update_inputs — and caught in
    execute(), which reruns the partition through the preserved
    unfused subtree."""


class FusedAggregateExec(agg_exec.HashAggregateExec):
    """Hash aggregate whose update side consumes a fused chain: per
    batch, ONE chain program produces the projected aggregate inputs
    plus a live-mask that rides into the groupby sort — the reference's
    per-batch update pipeline (aggregate.scala:420-478) as two compiled
    programs instead of a dispatch per operator."""

    _defer_scan = None  # see FusedChainExec

    def __init__(self, grouping, aggs, schema, mode, conf,
                 source: TpuExec, steps: List,
                 builds: List[BroadcastExchangeExec],
                 fallback: agg_exec.HashAggregateExec):
        super().__init__(grouping, aggs, source, schema, mode=mode,
                         conf=conf, fused_filter=None)
        steps = list(steps)
        if fallback.fused_filter is not None:
            steps.append(make_filter_step(
                fallback.fused_filter.condition))
        assert self.input_proj is not None
        # absorb the input projection only when it can trace (directly
        # or via the string-predicate transform); remaining dictionary-
        # dependent string expressions keep CompiledProjection's eager
        # path (it carries the source StringColumn; the chain's ColVs
        # don't)
        self._proj_in_chain = all(chain_traceable(e)
                                  for e in self.input_proj.exprs)
        if self._proj_in_chain:
            steps.append(make_project_step(self.input_proj.exprs))
        self.chain = FusedChain(steps, list(source.schema.types),
                                len(builds))
        self.builds = builds
        self.fallback = fallback
        self.build_key_specs = _build_key_specs(self.chain.steps)
        self._preps: Optional[List[PreparedBuild]] = None
        self._preps_ok: Optional[bool] = None
        self._inline_evt = None
        self._prep_lock = lockorder.make_lock("execs.fused.chainPrep")

    __getstate__ = FusedChainExec.__getstate__
    __setstate__ = FusedChainExec.__setstate__
    _ensure_preps = FusedChainExec._ensure_preps
    _inline_enabled = FusedChainExec._inline_enabled
    _inline_first = FusedChainExec._inline_first
    _inline_launch = FusedChainExec._inline_launch

    def _update_inputs(self, b: ColumnarBatch):
        if self._preps_ok is None and self._inline_enabled():
            res = self._inline_first(b, compact_out=False)
            if res is None:
                if not self._preps_ok:
                    raise _InlineDupFallback()
                # a peer thread prepared the builds: probe path below
            else:
                outs, live, ghosts = res
                out = self.chain.wrap(outs, ghosts, b.num_rows)
                if not self._proj_in_chain:
                    out = self.input_proj(out)
                return out, live
        with TraceRange("FusedAggregateExec.chain"):
            outs, live, ghosts = self.chain.run(b, self._preps,
                                                compact_out=False)
        out = self.chain.wrap(outs, ghosts, b.num_rows)
        if not self._proj_in_chain:
            # eager projection outside the chain (string dictionary
            # ops); row-aligned, so the live-mask stays valid
            out = self.input_proj(out)
        return out, live

    # -- one launch a batch --------------------------------------------
    # Where the partials have a small static shape (no grouping keys, or
    # keys in a dense layout) chain, update and merge are ONE program a
    # batch: its arguments are the batch's columns, the row count as a
    # host int32 and the running partials; its only results are the new
    # running partials. The kernels are ops/groupby's own, traced into
    # it: the update over the chain's outputs under its live mask, then
    # the merge kernel over [running rows, this batch's rows], the rows
    # and the order ``merge_partials`` hands it after its concat. What
    # the code cannot observe to be that case keeps the three launches
    # of ``HashAggregateExec._fold``; ``fused_agg.*`` counts which.

    def _fold(self, running, b):
        from spark_rapids_tpu.memory import retry as _retry
        from spark_rapids_tpu.memory.fault_injection import get_injector

        reason, plan = self._step_plan(running, b)
        if reason is None:
            try:
                get_injector().maybe_inject("aggregate.step")
                with TraceRange("FusedAggregateExec.step"):
                    out = self._step(running, *plan)
                tracing.count("fused_agg.engaged")
                return out
            except Exception as exc:
                if not _retry.is_oom_error(exc):
                    raise
                # the ladder (spill, retry, halve) is _agg_batch's
                reason = "oom"
        tracing.count("fused_agg.fallback." + reason)
        return super()._fold(running, b)

    def _step_plan(self, running, b):
        """-> (why this batch keeps the three launches, None) or (None,
        what ``_step`` needs), from what the host can see before the
        launch."""
        if _batching_ctx() is not None:
            return "batching", None     # the chain program coalesces
        if self._preps_ok is not True:
            return "inline_build", None
        if not self._proj_in_chain:
            return "eager_projection", None     # of string dictionaries
        if not self.input_proj.exprs:
            return "rows_only", None    # count(*) alone: no column to reduce
        known = isinstance(b.num_rows, int)
        call = self.chain.call_args(
            b, self._preps,
            np.int32(b.num_rows) if known else b.num_rows)
        ghosts = self._partial_ghosts(call[3])
        if running is not None and not all(
                g is not None and _same_dictionary(c.dictionary,
                                                   g.dictionary)
                for c, g in zip(running.columns, ghosts)
                if isinstance(c, StringColumn)):
            return "dictionary", None
        nkeys = len(self.grouping)
        types = self._merge_types()
        if not nkeys:
            return None, (call, ghosts, types, (), MIN_CAPACITY)
        if not self._dense_ok() and gb.order_sensitive(
                self.first_specs, self.input_types):
            return "sort_path", None
        for i in range(nkeys):
            g = ghosts[i]
            if running is not None and g.stats is not None:
                # a numeric key's range covers the carry's rows too
                rs = running.columns[i].stats
                ghosts[i] = _Ghost(g.dtype, None, None if rs is None else (
                    min(g.stats[0], rs[0]), max(g.stats[1], rs[1])))
        ranges = tuple(gb.key_range_of(ghosts[i], types[i])
                       for i in range(nkeys))
        # the carry's keys always have a validity lane: a slot for NULL
        layout = gb._dense_layout(types, range(nkeys), ranges,
                                  (True,) * nkeys)
        if layout is None:
            return "sort_path", None
        return None, (call, ghosts, types, ranges, layout[3])

    def _partial_ghosts(self, ghosts: List[_Ghost]) -> list:
        """The host mirror of each column of the merge schema: a key's
        own, an aggregate's input's where its partial keeps the input's
        dictionary (min, max, first, last of a string), else None."""
        out = list(ghosts[:len(self.grouping)])
        for spec, t in zip(self.first_specs, self.partial_types):
            out.append(ghosts[spec.ordinal]
                       if t is dt.STRING and spec.ordinal >= 0 else None)
        return out

    def _step(self, running, call, ghosts, types, ranges, capacity):
        modes, decode, args, _ = call
        if running is None:
            carry = _identity_carry(
                tuple(t.np_dtype.str for t in types), capacity)
        else:
            n = running.num_rows
            carry = ([(c.data, c.validity) for c in running.columns],
                     np.int32(n) if isinstance(n, int) else n)
        outs, n = self._step_program(modes, decode)(
            args, carry, key_ranges=ranges,
            types=tuple(self.chain.source_types))
        cols: List[Column] = []
        for (data, validity), g, t in zip(outs, ghosts, types):
            if t is dt.STRING and g is not None:
                cols.append(StringColumn(data, g.dictionary, validity))
            else:
                cols.append(Column(t, data, validity,
                                   stats=None if g is None else g.stats))
        return ColumnarBatch(cols, 1 if n is None else n)

    def _step_program(self, modes: tuple, decode: tuple):
        programs = self.chain._programs
        prog = programs.get(("fused_agg", modes, decode))
        if prog is None:
            ckey = self.chain.chain_key(False, modes, decode)
            key = None if ckey is None else (
                "fused_agg", ckey, len(self.grouping),
                tuple(self.first_specs), tuple(self.input_types),
                tuple(self.merge_specs), tuple(self._merge_types()))
            prog = programs[("fused_agg", modes, decode)] = \
                fused_cache_get_or_build(
                    key, lambda: self._build_step(modes, decode))
        return prog

    def _build_step(self, modes: tuple, decode: tuple):
        chain_fn, chain_label = self.chain._trace_fn(False, modes, decode)
        nkeys = len(self.grouping)
        key_ords = tuple(range(nkeys))
        in_types = tuple(self.input_types)
        first = tuple(self.first_specs)
        merge_types = tuple(self._merge_types())
        merge = tuple(self.merge_specs)

        def step(chain_args, carry, key_ranges, types):
            outs, live = chain_fn(*chain_args, types=types)
            num_rows = chain_args[2]
            carry_cols, carry_n = carry
            if nkeys:
                (kd, kv), (ad, av), n = gb._groupby(
                    list(outs), in_types, key_ords, first, num_rows,
                    live_mask=live, key_ranges=key_ranges, dense_ok=True)
                part = list(zip(kd, kv)) + list(zip(ad, av))
            else:
                ad, av = gb._reduce(list(outs), in_types, first,
                                    num_rows, live)
                # _reduce spreads its one row over the batch's capacity,
                # and so does a carry that the three launches left
                part = [(d[:1], None if v is None else v[:1])
                        for d, v in zip(ad, av)]
                carry_cols = [(d[:1], None if v is None else v[:1])
                              for d, v in carry_cols]
                n = 1
            # the merge's input: the carry's rows, then this batch's
            cc, pc = carry_cols[0][0].shape[0], part[0][0].shape[0]
            rows = jnp.concatenate([
                jnp.arange(cc, dtype=jnp.int32) < carry_n,
                jnp.arange(pc, dtype=jnp.int32) < n])
            cat = [(jnp.concatenate([cd, pd]),
                    jnp.concatenate([_valid_lane(cv, cc),
                                     _valid_lane(pv, pc)]))
                   for (cd, cv), (pd, pv) in zip(carry_cols, part)]
            if nkeys:
                (kd, kv), (ad, av), n = gb._groupby(
                    cat, merge_types, key_ords, merge,
                    jnp.int32(cc + pc), live_mask=rows,
                    key_ranges=key_ranges, dense_ok=True)
                new = list(zip(kd, kv)) + list(zip(ad, av))
            else:
                ad, av = gb._reduce(cat, merge_types, merge,
                                    jnp.int32(cc + pc), rows)
                new = [(jnp.full(MIN_CAPACITY, d[0]),
                        None if v is None else jnp.full(MIN_CAPACITY, v[0]))
                       for d, v in zip(ad, av)]
                n = None        # one row, known to the host
            # every column leaves with a validity lane, whatever the
            # kernel knew statically: the carry's structure is then the
            # merge schema's alone and one program serves every batch
            return [(d, _valid_lane(v, d.shape[0])) for d, v in new], n

        step.__name__ = step.__qualname__ = \
            "fused_agg[" + chain_label[len("fused_chain["):]
        return partial(jax.jit,
                       static_argnames=("key_ranges", "types"))(step)

    def execute(self, partition: int = 0) -> Iterator[ColumnarBatch]:
        if self._preps_ok is None and self._inline_enabled():
            # builds resolve lazily inside the first _update_inputs; a
            # duplicate-keyed build surfaces as _InlineDupFallback
            # BEFORE the aggregate yields anything, so the fallback
            # subtree can still own the whole partition
            def it():
                try:
                    yield from super(FusedAggregateExec,
                                     self).execute(partition)
                except _InlineDupFallback:
                    yield from self.fallback.execute(partition)
            return it()
        if not self._ensure_preps():
            return self.fallback.execute(partition)
        return super().execute(partition)

    def tree_string(self, indent: int = 0) -> str:
        return _fused_tree_string(
            self, indent,
            f"[{len(self.chain.steps)} fused steps, {self.mode}]")

    def metric_children(self):
        return _fused_metric_children(self)


# ---------------------------------------------------------------------------
# planner pass
# ---------------------------------------------------------------------------

_FUSABLE_JOIN_KINDS = ("inner", "left", "left_semi", "left_anti")


def _broadcast_of(j: joins.BroadcastHashJoinExec
                  ) -> Optional[BroadcastExchangeExec]:
    from spark_rapids_tpu.plan.overrides import _ReplayExec

    b = j.children[1]
    if isinstance(b, _ReplayExec):
        b = b.children[0]
    return b if isinstance(b, BroadcastExchangeExec) else None


def _fusable_join(node) -> bool:
    if type(node) is not joins.BroadcastHashJoinExec:
        return False
    if node.kind not in _FUSABLE_JOIN_KINDS:
        return False
    if node.condition is not None and not (
            node.kind == "inner" and node.condition.fused and
            node.condition.condition.deterministic):
        return False
    if _broadcast_of(node) is None:
        return False
    stream_types = node.children[0].schema.types
    build_types = node.children[1].schema.types
    for so, bo in zip(node.left_keys, node.right_keys):
        c = join_ops.common_key_type(stream_types[so], build_types[bo])
        if c is None or c is dt.STRING:
            return False
    return True


def _extract(node: TpuExec):
    """Walk down a maximal fusable chain; returns (steps bottom-up,
    source, build exchanges, walked exec nodes) or None. ``walked`` is
    every intermediate exec the chain absorbed — a stage-widening
    rewrite that MUTATES the source (defer_final) must verify none of
    them is shared, because a second parent of a shared intermediate
    reaches the source through it and still expects the unmutated
    output contract."""
    steps: List = []
    builds: List[BroadcastExchangeExec] = []
    walked: List[TpuExec] = []
    cur = node
    while True:
        if isinstance(cur, basic.FilterExec) and \
                chain_traceable(cur.filter.condition):
            steps.append(make_filter_step(cur.filter.condition))
            walked.append(cur)
            cur = cur.children[0]
        elif isinstance(cur, basic.ProjectExec) and \
                all(chain_traceable(e)
                    for e in cur.projection.exprs):
            steps.append(make_project_step(cur.projection.exprs))
            walked.append(cur)
            cur = cur.children[0]
        elif _fusable_join(cur):
            if cur.condition is not None:
                steps.append(make_filter_step(cur.condition.condition))
            stream_types = cur.children[0].schema.types
            build_types = list(cur.children[1].schema.types)
            commons = [join_ops.common_key_type(stream_types[so],
                                                build_types[bo])
                       for so, bo in zip(cur.left_keys, cur.right_keys)]
            steps.append(JoinStep(
                cur.kind, list(cur.left_keys), list(cur.right_keys),
                len(builds), build_types, commons))
            builds.append(_broadcast_of(cur))
            walked.append(cur)
            cur = cur.children[0]
        else:
            break
    if not steps:
        return None
    steps.reverse()
    return steps, cur, builds, walked


def _is_mesh(node: TpuExec) -> bool:
    """Chains must not absorb operators sitting directly on a mesh
    exec: the mesh layer runs filters between mesh execs SHARDED
    (parallel/filter_step.py) — wrapping them would gather the chain
    to one chip."""
    from spark_rapids_tpu.parallel import execs as pex

    return isinstance(node, (pex.MeshGroupByExec, pex.MeshShuffledJoinExec,
                             pex.MeshWindowExec, pex.MeshSortExec))


def _counts(steps) -> Tuple[int, int, int]:
    nf = sum(1 for s in steps if isinstance(s, FilterStep))
    np_ = sum(1 for s in steps if isinstance(s, ProjectStep))
    nj = sum(1 for s in steps if isinstance(s, JoinStep))
    return nf, np_, nj


def fuse_pipelines(root: TpuExec, conf=None) -> TpuExec:
    """Post-conversion pass (before coalesce insertion): absorb fusable
    chains into FusedAggregateExec / FusedChainExec, widen post-
    aggregate tails (final projection + HAVING + project + ORDER BY)
    into one chain program, and hand packed scan uploads straight to
    the chain that decodes them in-program. Memoized by node identity
    so shared (CTE) subtrees stay shared; stage-widening rewrites that
    MUTATE a source (defer_final, defer_decode) only apply to sources
    with a single parent."""
    from spark_rapids_tpu import config as cfg

    if conf is not None and not conf.get(cfg.FUSION_ENABLED):
        return root
    return _fuse_node(root, conf, {}, _multi_parent_ids(root))


def _multi_parent_ids(root: TpuExec) -> set:
    """ids of exec nodes referenced by MORE than one parent (shared CTE
    subtrees): stage-widening must not change their output contract."""
    counts: dict = {}
    seen: set = set()
    stack = [root]
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        for c in n.children:
            counts[id(c)] = counts.get(id(c), 0) + 1
            stack.append(c)
    return {i for i, c in counts.items() if c > 1}


def _absorb_final(steps, fused_src):
    """Pull an aggregate source's final projection into the consuming
    chain: the aggregate then emits raw (keys..., partials...) with a
    lazy count (defer_final) and the chain's program applies final-
    project + HAVING + compaction — removing the aggregate's own
    final-projection dispatch AND its rebucket host sync. Returns
    (steps, source_types) with source_types None when not absorbed.
    Only chains WITHOUT join steps qualify: a join chain can fall back
    to its preserved subtree, which must then see the aggregate's
    normal (finalized) output."""
    if any(isinstance(s, JoinStep) for s in steps):
        return steps, None
    if not isinstance(fused_src, agg_exec.HashAggregateExec):
        return steps, None
    if fused_src.mode not in ("complete", "final") or \
            fused_src.final_proj is None or fused_src.defer_final:
        return steps, None
    exprs = fused_src.final_proj.exprs
    if not all(chain_traceable(e) for e in exprs):
        return steps, None
    new_steps = [make_project_step(exprs)] + list(steps)
    src_types = [e.dtype for e in fused_src.grouping] + \
        list(fused_src.partial_types)
    fused_src.defer_final = True
    fb = getattr(fused_src, "fallback", None)
    if isinstance(fb, agg_exec.HashAggregateExec):
        # the prep-failure fallback aggregate feeds the SAME chain, so
        # it must emit the same deferred shape
        fb.defer_final = True
    return new_steps, src_types


def _maybe_defer_scan(out, new_source, shared, conf) -> None:
    """Hand a packed scan's upload buffers straight to the fused chain:
    the chain's program inlines the transfer decode (zero decode
    dispatch). Single-parent scans only — any other consumer would see
    PackedBatches it cannot read."""
    from spark_rapids_tpu import config as cfg

    if conf is not None and not conf.get(cfg.FUSION_DEFER_DECODE):
        return
    if isinstance(new_source, basic.ScanExec) and new_source.pack and \
            id(new_source) not in shared:
        new_source.defer_decode = True
        out._defer_scan = new_source


def _fuse_sort_tail(node, conf, memo: dict, shared: set):
    """Absorb a global ORDER BY into the post-aggregate chain below it:
    Sort(Project(Filter(Agg))) becomes ONE chain program (final-project
    + HAVING + project + in-program sort) over the aggregate's
    raw partials. Valid only when the source emits exactly one batch on
    one partition — a hash aggregate — because a per-batch sort of a
    multi-batch stream is not a global sort."""
    ch = _extract(node.children[0])
    steps, source, builds, walked = ch if ch \
        else ([], node.children[0], [], [])
    if _is_mesh(source) or id(source) in shared:
        return None
    new_source = _fuse_node(source, conf, memo, shared)
    if not (isinstance(new_source, agg_exec.HashAggregateExec) and
            new_source.mode in ("complete", "final") and
            new_source.num_partitions == 1):
        return None
    src_types = None
    if not any(id(w) in shared for w in walked):
        # defer_final mutates the aggregate; a shared intermediate
        # (CTE-reused Project/Filter) would expose the mutated output
        # to a second consumer that expects finalized columns
        steps, src_types = _absorb_final(steps, new_source)
    steps = list(steps) + [SortStep(tuple(node.specs))]
    for bx in builds:
        bx.children = [_fuse_node(bx.children[0], conf, memo, shared)]
    chain = FusedChain(steps,
                       src_types or list(new_source.schema.types),
                       len(builds))
    return FusedChainExec(new_source, chain, builds, node.schema,
                          fallback=node, conf=conf)


def _fuse_node(node: TpuExec, conf, memo: dict, shared: set) -> TpuExec:
    hit = memo.get(id(node))
    if hit is not None:
        return hit[1]
    out = None
    if type(node) is agg_exec.HashAggregateExec and \
            node.mode in ("partial", "complete"):
        ch = _extract(node.children[0])
        steps, source, builds = ch[:3] if ch \
            else ([], node.children[0], [])
        # an empty chain still pays off when the agg carries a fused
        # filter: mask+project collapse into one program
        if _is_mesh(source):
            steps = None
        if steps or (steps is not None and node.fused_filter is not None):
            new_source = _fuse_node(source, conf, memo, shared)
            for bx in builds:
                bx.children = [_fuse_node(bx.children[0], conf, memo,
                                          shared)]
            out = FusedAggregateExec(
                node.grouping, node.aggs, node.schema, node.mode,
                node.conf, new_source, steps, builds, fallback=node)
            _maybe_defer_scan(out, new_source, shared, conf)
    if out is None:
        from spark_rapids_tpu.execs.sort import SortExec

        from spark_rapids_tpu import config as cfg

        sort_tail_on = conf is None or conf.get(cfg.FUSION_SORT_TAIL)
        if sort_tail_on and type(node) is SortExec and \
                node.global_sort and node.specs:
            out = _fuse_sort_tail(node, conf, memo, shared)
    if out is None:
        ch = _extract(node)
        if ch is not None and not _is_mesh(ch[1]):
            steps, source, builds, walked = ch
            nf, np_, nj = _counts(steps)
            # savings estimate: each filter ~2 dispatches, project 1,
            # join ~6; the chain costs 1. Skip a lone projection.
            if 2 * nf + np_ + 6 * nj - 1 >= 1:
                new_source = _fuse_node(source, conf, memo, shared)
                src_types = None
                if id(source) not in shared and not any(
                        id(w) in shared for w in walked):
                    # see _fuse_sort_tail: defer_final must not leak
                    # through a shared intermediate node
                    steps, src_types = _absorb_final(steps, new_source)
                for bx in builds:
                    bx.children = [_fuse_node(bx.children[0], conf,
                                              memo, shared)]
                chain = FusedChain(
                    steps, src_types or list(new_source.schema.types),
                    len(builds))
                out = FusedChainExec(new_source, chain, builds,
                                     node.schema, fallback=node,
                                     conf=conf)
                _maybe_defer_scan(out, new_source, shared, conf)
    if out is None:
        node.children = [_fuse_node(c, conf, memo, shared)
                         for c in node.children]
        out = node
    memo[id(node)] = (node, out)
    return out
