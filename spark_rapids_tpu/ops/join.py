"""Equi-joins: sorted-hash probe with exact verification.

The reference drives cuDF's hash joins (GpuHashJoin.scala:302-318:
inner/left/leftSemi/leftAnti/full). TPUs have no device hash tables; the
TPU-native design uses *sorted hashes + a bucketed search*:

  build:  h_b = hash64(keys);  sort build rows by h_b           (one sort)
          the build's DISTINCT hashes with each one's run start, and a
          directory over their top bits (:class:`HashIndex`)    (once a build)
  probe:  h_p = hash64(keys);  lo/hi = searchsorted(h_b, h_p) left/right,
          found by halving inside h_p's directory bucket alone: a gather
          costs by the element, and a bucket of a few distinct hashes
          takes 4 rounds where the whole build took 2 x 20 (PR 32)
  expand: pair k -> (probe_row i, build_row lo[i] + k-offset[i]) via one
          searchsorted over the match-count prefix sum
  verify: exact key equality per pair kills hash collisions; compaction
          drops dead pairs.

The expansion capacity is data-dependent: the only host sync in the kernel
realizes the total match count, mirroring where cuDF also sizes its output.
Null join keys never match (SQL equi-join semantics); the reference filters
them too (GpuHashJoin.scala:134-193) — here build/probe nulls get disjoint
hash sentinels so they cannot collide with anything.

Join conditions beyond the equi-keys are applied by the exec layer as a
post-join filter, same as the reference (GpuHashJoin.scala:285-291).
"""
from __future__ import annotations

from functools import partial
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn, unify_dictionaries
from spark_rapids_tpu.ops import hashing, sortkeys
from spark_rapids_tpu.ops.buckets import bucket_capacity

_BUILD_NULL = jnp.int64(-0x6789ABCDEF01)
_PROBE_NULL = jnp.int64(0x13579BDF2468)

JOIN_TYPES = ("inner", "left", "right", "leftsemi", "leftanti", "full",
              "cross")


def common_key_type(a: dt.DType, b: dt.DType) -> Optional[dt.DType]:
    """Comparison type for a mixed-type equi-key pair (Spark's implicit
    cast: bigint = double compares as double). None = no numeric
    common type (date/timestamp/string mixes stay unsupported)."""
    if a is b:
        return a
    def _num(t):
        return t.is_floating or t.is_integral or t is dt.BOOLEAN
    if _num(a) and _num(b):
        return dt.FLOAT64 if (a.is_floating or b.is_floating) \
            else dt.INT64
    return None


def _key_hashes(batch: ColumnarBatch, ordinals: List[int],
                dtypes: List[dt.DType], null_sentinel,
                target_types: Optional[List[dt.DType]] = None
                ) -> jax.Array:
    """``target_types``: per-key comparison type — mismatched sides are
    cast so both sides hash identical values identically."""
    if target_types is not None and any(
            t is not dtypes[o] for t, o in zip(target_types, ordinals)):
        cols = list(batch.columns)
        for t, o in zip(target_types, ordinals):
            if t is not dtypes[o] and not isinstance(cols[o], StringColumn):
                cols[o] = Column(t, cols[o].data.astype(t.kernel_dtype),
                                 cols[o].validity)
        batch = ColumnarBatch(cols, batch.num_rows)
        dtypes = list(dtypes)
        for t, o in zip(target_types, ordinals):
            dtypes[o] = t
    h = hashing.hash_columns(batch, ordinals, dtypes)
    any_null = None
    for o in ordinals:
        v = batch.columns[o].validity
        if v is not None:
            nn = ~v
            any_null = nn if any_null is None else (any_null | nn)
    if any_null is not None:
        h = jnp.where(any_null, null_sentinel, h)
    return h


def unify_join_strings(left: ColumnarBatch, right: ColumnarBatch,
                       left_keys: List[int], right_keys: List[int]
                       ) -> Tuple[ColumnarBatch, ColumnarBatch]:
    """String key columns must share dictionaries so code equality means
    string equality."""
    lcols, rcols = list(left.columns), list(right.columns)
    for lo, ro in zip(left_keys, right_keys):
        lc, rc = lcols[lo], rcols[ro]
        if isinstance(lc, StringColumn) and isinstance(rc, StringColumn):
            u = unify_dictionaries([lc, rc])
            lcols[lo], rcols[ro] = u[0], u[1]
    return (ColumnarBatch(lcols, left.num_rows),
            ColumnarBatch(rcols, right.num_rows))


class HashIndex(NamedTuple):
    """What a probe searches in place of the hash-sorted build ``sb_h``
    (capacity ``b_cap``): its ``n_u`` distinct hashes and a directory
    over their top bits, made once with the build. Over DISTINCT hashes
    because the build's padding (int64 max), a NULL-key run and a hot
    key are each one long run: they cost the directory one entry and the
    search no round, where a directory over ``sb_h`` itself would take
    its round count from the longest run. Both tables are int32 lanes
    stacked as ``take_rows`` stacks columns: on the v5e a gather costs by
    the index, and a 64-bit element twice a 32-bit one, so a probe
    fetches a hash as two words and everything it needs of a row at
    once."""

    runs: jax.Array    # int32[4, b_cap + 1], a distinct hash a column,
    #                    ascending: its two words (_hash_words), where
    #                    its run starts in sb_h, where the next one's
    #                    does; from n_u on int64 max's words and b_cap
    dir: jax.Array     # int32[2, 2**bits]: bucket b's distinct hashes
    #                    are columns dir[0, b] to dir[1, b] of runs
    rounds: jax.Array  # int32 scalar, on the device: halvings that
    #                    settle the fullest bucket


class PreparedBuild(NamedTuple):
    """Build side prepared once and probed across every stream batch:
    the hash-sorted build and the index a probe searches. Only valid
    when no JOIN KEY is a string column — string keys re-unify
    dictionaries per stream batch, changing the build hashes (non-key
    string columns are fine)."""

    sorted_build: ColumnarBatch
    index: HashIndex


def probe_rounds(prepared: PreparedBuild) -> Tuple[int, int]:
    """(halvings a probe of this build makes, halvings ONE whole-build
    search of its capacity makes): one fetch from the device, for the
    ``join.probe.rounds*`` counters of a traced run."""
    b_cap = prepared.index.runs.shape[1] - 1
    return int(jax.device_get(prepared.index.rounds)), b_cap.bit_length()


def prepare_build(build: ColumnarBatch, build_keys: List[int],
                  build_types: List[dt.DType],
                  stream_types_for_keys: List[dt.DType]
                  ) -> Optional[PreparedBuild]:
    """Hash + sort the build side once for
    reuse across stream batches. Returns None when a join key is a
    string column (per-batch dictionary unification makes the build
    hash stream-dependent)."""
    if any(isinstance(build.columns[o], StringColumn) for o in build_keys):
        return None
    commons = [common_key_type(st, build_types[bo])
               for st, bo in zip(stream_types_for_keys, build_keys)]
    if any(c is None for c in commons):
        return None
    h_b = _key_hashes(build, build_keys, build_types, _BUILD_NULL,
                      target_types=commons)
    index, sb_datas, sb_vals = _build_sorted(
        [c.data for c in build.columns],
        [c.validity for c in build.columns], h_b,
        build.num_rows_device())
    cols = [c._like(d, v) for c, d, v in
            zip(build.columns, sb_datas, sb_vals)]
    return PreparedBuild(ColumnarBatch(cols, build.num_rows), index)


class DensePreparedBuild(NamedTuple):
    """Dense-probe build (AQE hash->dense strategy switch): when the
    measured build key range is narrow, the probe is a direct table
    lookup instead of a binary search. ``start`` holds run offsets of
    the slot-sorted build — slot s's rows sit at
    ``sorted_build[start[s]:start[s+1]]`` — so DUPLICATE keys work (the
    fused broadcast path's inverse table is one-row-per-slot and bails
    on dups). Probe-row match runs come out in original build order
    (stable slot sort), exactly like the hash path's stable hash sort,
    so matched-pair output is bit-identical to the hash probe."""

    sorted_build: ColumnarBatch
    start: jax.Array  # int32[table_span + 1] slot run offsets
    kmin: np.int64
    span: np.int64
    table_span: int  # static padded slot count (>= span + 1)


def measure_key_range(col: Column, rows) -> Tuple[int, int, int]:
    """(min, max, valid-row count) of a numeric key column — the one
    device round trip of the dense-probe decision. Count 0 means no
    measurable rows (all-null or empty)."""
    kmin, kmax, n = jax.device_get(
        _key_range(col.data, col.validity, rows))
    return int(kmin), int(kmax), int(n)


@jax.jit
def _key_range(data, valid, rows):
    cap = data.shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < rows
    ok = live if valid is None else (live & valid)
    big = jnp.int64(1) << 62
    k = data.astype(jnp.int64)
    return (jnp.min(jnp.where(ok, k, big)),
            jnp.max(jnp.where(ok, k, -big)),
            jnp.sum(ok.astype(jnp.int64)))


def prepare_build_dense(build: ColumnarBatch, build_keys: List[int],
                        build_types: List[dt.DType],
                        stream_types_for_keys: List[dt.DType],
                        kmin: int, span: int
                        ) -> Optional[DensePreparedBuild]:
    """Slot-sort the build for dense probing. None when the shape does
    not qualify (only single integral non-string keys slot densely);
    the caller decides WHETHER dense pays (density/span policy) from
    :func:`measure_key_range` before building."""
    if len(build_keys) != 1 or span <= 0:
        return None
    o = build_keys[0]
    if isinstance(build.columns[o], StringColumn):
        return None
    common = common_key_type(stream_types_for_keys[0], build_types[o])
    if common is None or not common.is_integral:
        return None
    table_span = bucket_capacity(span + 1)
    sb_datas, sb_vals, start = _build_dense(
        [c.data for c in build.columns],
        [c.validity for c in build.columns],
        build.num_rows_device(), np.int64(kmin),
        key_ord=o, table_span=table_span)
    cols = [c._like(d, v) for c, d, v in
            zip(build.columns, sb_datas, sb_vals)]
    return DensePreparedBuild(ColumnarBatch(cols, build.num_rows),
                              start, np.int64(kmin), np.int64(span),
                              table_span)


@partial(jax.jit, static_argnames=("key_ord", "table_span"))
def _build_dense(b_datas, b_vals, b_rows, kmin, key_ord: int,
                 table_span: int):
    """Stable slot sort + run-offset table. kmin rides as a TRACED
    operand so every partition's build shares one compiled program."""
    cap = b_datas[key_ord].shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < b_rows
    valid = b_vals[key_ord]
    ok = live if valid is None else (live & valid)
    slot64 = b_datas[key_ord].astype(jnp.int64) - kmin
    ok = ok & (slot64 >= 0) & (slot64 < jnp.int64(table_span))
    # nulls/padding park at table_span: past every probed slot, so they
    # can never enter a run ([start[s], start[s+1]) with s < table_span)
    slot = jnp.where(ok, slot64, jnp.int64(table_span)).astype(jnp.int32)
    order, (s_slot,) = sortkeys.stable_order(
        [slot], bits=[table_span.bit_length()])
    sb_datas, sb_vals = sortkeys.take_rows(order, b_datas, b_vals)
    start = jnp.searchsorted(
        s_slot,
        jnp.arange(table_span + 1, dtype=jnp.int32)).astype(jnp.int32)
    return sb_datas, sb_vals, start


@partial(jax.jit, static_argnames=("table_span",))
def _probe_dense(start, kmin, span, p_key, p_valid, s_rows,
                 table_span: int):
    """Dense probe: two gathers replace two binary searches. Same
    (lo, hi, counts, total) contract as :func:`_hash_probe`, feeding
    the unchanged expand/verify/emit tail."""
    s_cap = p_key.shape[0]
    live_p = jnp.arange(s_cap, dtype=jnp.int32) < s_rows
    slot64 = p_key.astype(jnp.int64) - kmin
    ok = live_p & (slot64 >= 0) & (slot64 < span)
    if p_valid is not None:
        ok = ok & p_valid
    slot = jnp.where(ok, slot64, 0).astype(jnp.int32)
    lo = jnp.take(start, slot)
    hi = jnp.take(start, slot + 1)
    counts = jnp.where(ok, hi - lo, 0).astype(jnp.int64)
    total = jnp.sum(counts)
    return lo, hi, counts, total


def equi_join(stream: ColumnarBatch, build: ColumnarBatch,
              stream_keys: List[int], build_keys: List[int],
              stream_types: List[dt.DType], build_types: List[dt.DType],
              join_type: str = "inner",
              prepared: Optional[PreparedBuild] = None
              ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    """Join ``stream`` (probe/left) against ``build`` (right). Output columns:
    stream columns then build columns (semi/anti: stream only). ``right``
    joins are planned as flipped ``left`` by the exec layer.
    ``prepared`` reuses a :func:`prepare_build` result across stream
    batches (the exec layer's build-once/probe-many seam)."""
    assert join_type in ("inner", "left", "leftsemi", "leftanti", "full")
    if prepared is None:
        stream, build = unify_join_strings(stream, build, stream_keys,
                                           build_keys)

    commons = [common_key_type(stream_types[so], build_types[bo])
               for so, bo in zip(stream_keys, build_keys)]
    assert all(c is not None for c in commons), (
        "no common comparison type for join keys",
        [stream_types[o] for o in stream_keys],
        [build_types[o] for o in build_keys])
    if isinstance(prepared, DensePreparedBuild):
        # ---- phase 1 (device), dense: direct slot lookup, no hashing
        # of either side at all
        sorted_build = prepared.sorted_build
        so = stream.columns[stream_keys[0]]
        lo, hi, counts, total = _probe_dense(
            prepared.start, prepared.kmin, prepared.span,
            so.data, so.validity, stream.num_rows_device(),
            prepared.table_span)
    elif prepared is not None:
        # ---- phase 1 (device), amortized: probe the prepared table
        h_p = _key_hashes(stream, stream_keys, stream_types, _PROBE_NULL,
                          target_types=commons)
        sorted_build = prepared.sorted_build
        lo, hi, counts, total = _probe_sorted(
            prepared.index, h_p, stream.num_rows_device())
    else:
        h_p = _key_hashes(stream, stream_keys, stream_types, _PROBE_NULL,
                          target_types=commons)
        # ---- phase 1 (device): sort build, probe, count matches
        b_datas = [c.data for c in build.columns]
        b_vals = [c.validity for c in build.columns]
        sb_datas, sb_vals, lo, hi, counts, total = _probe_counts(
            b_datas, b_vals, _key_hashes(
                build, build_keys, build_types, _BUILD_NULL,
                target_types=commons),
            build.num_rows_device(), h_p, stream.num_rows_device())
        sorted_build_cols = [c._like(d, v) for c, d, v in
                             zip(build.columns, sb_datas, sb_vals)]
        sorted_build = ColumnarBatch(sorted_build_cols, build.num_rows)

    # ---- the one host sync: candidate-pair count -> output capacity
    total_i = int(jax.device_get(total))
    out_cap = bucket_capacity(max(total_i, 1))

    # ---- phase 2 (device): expand pairs, verify exact equality (on the
    # per-pair common comparison type)
    def _cast(d, t, c):
        return d if t is c else d.astype(c.kernel_dtype)

    key_pairs = tuple(
        (_cast(stream.columns[so].data, stream_types[so], c),
         stream.columns[so].validity,
         _cast(sorted_build.columns[bo].data, build_types[bo], c),
         sorted_build.columns[bo].validity)
        for so, bo, c in zip(stream_keys, build_keys, commons))
    key_types = tuple(commons)
    pi, bi, match = _expand_verify(lo, hi, counts, total, key_pairs,
                                   key_types, out_cap)

    return _emit(stream, sorted_build, stream_types, build_types,
                 pi, bi, match, counts, total, join_type, out_cap)


def _sort_build(b_datas, b_vals, h_b, b_rows):
    b_cap = h_b.shape[0]
    live_b = jnp.arange(b_cap, dtype=jnp.int32) < b_rows
    # Push padding rows to the top of the sort with int64 max. Real hashes
    # span the full int64 range, so any smaller sentinel can sort BELOW a
    # real row and break the "positions [0, b_rows) are real" invariant
    # _emit's full-join path relies on. If a real hash ties the sentinel,
    # stable argsort still orders it first (pads have the highest indices),
    # and the exact-key verification kills any pad candidate pairs.
    h_b_l = jnp.where(live_b, h_b, jnp.iinfo(jnp.int64).max)
    order, (sb_h,) = sortkeys.stable_order([h_b_l])
    sb_datas, sb_vals = sortkeys.take_rows(order, b_datas, b_vals)
    return _hash_index(sb_h), sb_datas, sb_vals


def _directory_bits(b_cap: int) -> int:
    """Top bits of a hash that name its directory bucket: as many
    buckets as the build has capacity, so a bucket of a uniform 64-bit
    hash holds under one distinct hash on average and the fullest of
    half a million about 8 (4 rounds)."""
    return max((b_cap - 1).bit_length(), 1)


def _bucket(h: jax.Array, bits: int) -> jax.Array:
    """Directory bucket of an int64 hash, in [0, 2**bits): its top bits,
    shifted so that signed order is bucket order."""
    return ((h >> (64 - bits)) + (1 << (bits - 1))).astype(jnp.int32)


def _hash_words(h: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """An int64 hash as two int32 words that order as it does, compared
    high word first (the low word's sign bit flipped: unsigned order)."""
    low = h.astype(jnp.uint32) ^ jnp.uint32(1 << 31)
    return ((h >> 32).astype(jnp.int32),
            jax.lax.bitcast_convert_type(low, jnp.int32))


def _hash_index(sb_h: jax.Array) -> HashIndex:
    b_cap = sb_h.shape[0]
    bits = _directory_bits(b_cap)
    pos = jnp.arange(b_cap, dtype=jnp.int32)
    first = (pos == 0) | (sb_h != jnp.roll(sb_h, 1))
    starts, _ = sortkeys.stable_order([~first])  # run starts to the front
    live_u = pos < jnp.sum(first, dtype=jnp.int32)
    u_h = jnp.append(jnp.where(live_u, jnp.take(sb_h, starts),
                               jnp.iinfo(jnp.int64).max),
                     jnp.iinfo(jnp.int64).max)
    u_start = jnp.append(jnp.where(live_u, starts, b_cap),
                         jnp.full((2,), b_cap, jnp.int32))
    runs = jnp.stack([*_hash_words(u_h), u_start[:-1], u_start[1:]])
    # distinct hashes a bucket (entries past them park in a bucket no
    # probe names), then where each bucket starts and ends: a scatter-add
    # over sorted indices and a prefix sum, 5 ms where a searchsorted of
    # every bucket number took 70 (scripts/probecost.py, PR 32)
    u_bucket = jnp.where(live_u, _bucket(u_h[:-1], bits), 1 << bits)
    held = jax.ops.segment_sum(jnp.ones_like(u_bucket), u_bucket,
                               num_segments=(1 << bits) + 1,
                               indices_are_sorted=True)[:-1]
    ends = _prefix_sum(held)
    return HashIndex(runs, jnp.stack([ends - held, ends]),
                     32 - jax.lax.clz(jnp.max(held)))  # its bit length


def _hash_probe(index: HashIndex, h_p, s_rows):
    """Leftmost hash-match position + run length per probe row: exactly
    ``searchsorted(sb_h, h_p)`` left and right, found among the distinct
    hashes of ``h_p``'s directory bucket. Gathers of the stream batch's
    width: ``rounds + 2`` of stacked int32 lanes, where two whole-build
    searches made ``2 * bit_length(b_cap)`` of int64. ``rounds`` is read
    on the device (a traced trip count is a ``while``): no fetch, and one
    program whatever the build holds. Distinct hashes that share their
    top bits cost rounds, up to a whole-build search's; repeats of one
    hash, NULL keys and padding cost none."""
    runs, directory, rounds = index
    bits = (directory.shape[1] - 1).bit_length()
    s_cap = h_p.shape[0]
    live_p = jnp.arange(s_cap, dtype=jnp.int32) < s_rows
    high, low = _hash_words(h_p)
    start, end = jnp.take(directory, _bucket(h_p, bits), axis=1,
                          mode="clip")

    def halve(_, span):
        l, r = span
        mid = (l + r) >> 1
        m_high, m_low, _, _ = jnp.take(runs, mid, axis=1, mode="clip")
        below = (l < r) & ((m_high < high) |
                           ((m_high == high) & (m_low < low)))
        return jnp.where(below, mid + 1, l), jnp.where(below, r, mid)

    # j: the first distinct hash of the bucket that is >= h_p, or the
    # bucket's end, where the next bucket's first run starts
    j, _ = jax.lax.fori_loop(0, rounds, halve, (start, end))
    j_high, j_low, lo, nxt = jnp.take(runs, j, axis=1, mode="clip")
    found = (j < end) & (j_high == high) & (j_low == low)
    hi = jnp.where(found, nxt, lo)
    counts = jnp.where(live_p, hi - lo, 0).astype(jnp.int64)
    total = jnp.sum(counts)
    return lo, hi, counts, total


@jax.jit
def _probe_counts(b_datas, b_vals, h_b, b_rows, h_p, s_rows):
    index, sb_datas, sb_vals = _sort_build(b_datas, b_vals, h_b, b_rows)
    lo, hi, counts, total = _hash_probe(index, h_p, s_rows)
    return sb_datas, sb_vals, lo, hi, counts, total


@jax.jit
def _build_sorted(b_datas, b_vals, h_b, b_rows):
    """Build-once half of the prepared path: one program sorts the
    build and makes the index its probes search."""
    return _sort_build(b_datas, b_vals, h_b, b_rows)


@jax.jit
def _probe_sorted(index: HashIndex, h_p, s_rows):
    """Probe-many half of the prepared path (one program per stream
    batch, no build work)."""
    return _hash_probe(index, h_p, s_rows)


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum (exact: integers), in rows of 1,024: a sum
    along each row, then the rows' totals. One ``jnp.cumsum`` over
    1,048,576 int64 values took the chip's compiler 65 s, this takes
    2 s (a described v5e, PR 27)."""
    n = x.shape[0]
    if n <= 1024 or n % 1024:
        return jnp.cumsum(x)
    rows = jnp.cumsum(x.reshape(-1, 1024), axis=1)
    totals = rows[:, -1]
    return (rows + (jnp.cumsum(totals) - totals)[:, None]).reshape(n)


@partial(jax.jit, static_argnames=("key_types", "out_cap"))
def _expand_verify(lo, hi, counts, total, key_pairs, key_types,
                   out_cap: int):
    """pair k in [0,out_cap): probe row pi[k], build row bi[k], and whether
    the pair is live and exactly key-equal."""
    offsets = _prefix_sum(counts)  # inclusive
    k = jnp.arange(out_cap, dtype=jnp.int64)
    pi = jnp.searchsorted(offsets, k, side="right").astype(jnp.int32)
    pi_c = jnp.clip(pi, 0, lo.shape[0] - 1)
    excl = offsets - counts  # exclusive prefix
    bi = (jnp.take(lo, pi_c) + (k - jnp.take(excl, pi_c))).astype(jnp.int32)
    live_pair = k < total
    match = live_pair
    for (sd, sv, bd, bv), t in zip(key_pairs, key_types):
        s_comps, s_valid = sortkeys.equality_parts(sd, sv, t)
        b_comps, b_valid = sortkeys.equality_parts(bd, bv, t)
        bi_c = jnp.clip(bi, 0, bd.shape[0] - 1)
        match = match & jnp.take(s_valid, pi_c) & jnp.take(b_valid, bi_c)
        for sc, bc in zip(s_comps, b_comps):
            match = match & (jnp.take(sc, pi_c) == jnp.take(bc, bi_c))
    return pi_c, bi, match


def _emit(stream: ColumnarBatch, build: ColumnarBatch,
          stream_types, build_types, pi, bi, match, counts, total,
          join_type: str, out_cap: int
          ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    s_rows = stream.num_rows_device()
    s_cap = stream.capacity

    if join_type in ("leftsemi", "leftanti"):
        matched = _probe_matched(counts, match, s_cap)
        live_s = jnp.arange(s_cap, dtype=jnp.int32) < s_rows
        keep = (matched if join_type == "leftsemi" else ~matched) & live_s
        from spark_rapids_tpu.ops.filter import compact_batch
        out = compact_batch(stream, keep)
        return out, list(stream_types)

    # matched pairs, compacted
    pi_s, bi_s, n_match = _compact_pairs(pi, bi, match)
    pair_live = jnp.arange(out_cap, dtype=jnp.int32) < n_match

    cols: List[Column] = []
    for c in stream.columns:
        cols.append(c.gather(pi_s, in_bounds_mask=None))
    for c in build.columns:
        cols.append(c.gather(bi_s, in_bounds_mask=None))
    inner = ColumnarBatch(cols, n_match)
    out_types = list(stream_types) + list(build_types)

    if join_type == "inner":
        return inner, out_types

    # left/full: append unmatched stream rows with null build side
    matched = _probe_matched(counts, match, s_cap)
    live_s = jnp.arange(s_cap, dtype=jnp.int32) < s_rows
    from spark_rapids_tpu.ops.concat import concat_batches
    from spark_rapids_tpu.ops.filter import compact_batch

    unmatched_keep = (~matched) & live_s
    un_stream = compact_batch(stream, unmatched_keep)
    null_build = [Column.all_null(t, un_stream.capacity)
                  for t in build_types]
    left_extra = ColumnarBatch(list(un_stream.columns) + null_build,
                               un_stream.num_rows)
    pieces = [inner, left_extra]

    if join_type == "full":
        b_rows = build.num_rows_device()
        b_cap = build.capacity
        bmatched = _build_matched(bi, match, b_cap)
        live_b = jnp.arange(b_cap, dtype=jnp.int32) < b_rows
        un_build = compact_batch(build, (~bmatched) & live_b)
        null_stream = [Column.all_null(t, un_build.capacity)
                       for t in stream_types]
        pieces.append(ColumnarBatch(null_stream + list(un_build.columns),
                                    un_build.num_rows))
    return concat_batches(pieces), out_types


@partial(jax.jit, static_argnames=("s_cap",))
def _probe_matched(counts, match, s_cap: int):
    """Per-probe-row "has a match": pairs are laid out in ascending probe
    order, so each row's pairs are the contiguous run
    [offsets[r]-counts[r], offsets[r]) — a cumsum difference answers
    "any match in the run" with gathers only (the segment_max scatter
    this replaces measured ~30x a cumsum on TPU)."""
    offsets = jnp.cumsum(counts)  # inclusive
    cs = jnp.cumsum(match.astype(jnp.int64))
    pair_cap = cs.shape[0]
    hi_idx = jnp.clip(offsets - 1, 0, pair_cap - 1).astype(jnp.int32)
    excl = offsets - counts
    lo_gate = excl > 0
    lo_idx = jnp.clip(excl - 1, 0, pair_cap - 1).astype(jnp.int32)
    hi = jnp.take(cs, hi_idx)
    lo = jnp.where(lo_gate, jnp.take(cs, lo_idx), 0)
    got = jnp.where(counts > 0, hi - lo, 0)
    out = got > 0
    # counts has stream-capacity length == s_cap
    return out[:s_cap]


@partial(jax.jit, static_argnames=("b_cap",))
def _build_matched(bi, match, b_cap: int):
    bi_c = jnp.where(match, bi, b_cap)  # dead pairs park out of range
    return jax.ops.segment_max(
        match.astype(jnp.int32), jnp.clip(bi_c, 0, b_cap),
        num_segments=b_cap + 1)[:b_cap] > 0


def cross_join(stream: ColumnarBatch, build: ColumnarBatch,
               stream_types, build_types
               ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    """Brute-force cartesian product (GpuCartesianProductExec analogue —
    disabled by default at the planner, GpuOverrides.scala:1841-1856)."""
    n_s = stream.realized_num_rows()
    n_b = build.realized_num_rows()
    total = n_s * n_b
    out_cap = bucket_capacity(max(total, 1))
    k = jnp.arange(out_cap, dtype=jnp.int64)
    pi = (k // max(n_b, 1)).astype(jnp.int32)
    bi = (k % max(n_b, 1)).astype(jnp.int32)
    cols = [c.gather(pi) for c in stream.columns] + \
           [c.gather(bi) for c in build.columns]
    return (ColumnarBatch(cols, total),
            list(stream_types) + list(build_types))


def nested_loop_join(stream: ColumnarBatch, build: ColumnarBatch,
                     stream_types, build_types, cond_mask,
                     referenced: List[int]
                     ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    """Cross product with the residual condition fused into pair expansion
    (GpuBroadcastNestedLoopJoinExec analogue, sql-plugin/.../execution/
    GpuBroadcastNestedLoopJoinExec.scala — the reference materializes the
    full product then filters; here only the columns the condition actually
    reads are gathered at full n_s*n_b width, all remaining columns are
    gathered once at the compacted match count).

    ``cond_mask`` is a CompiledFilter.mask-style callable batch->bool[cap];
    ``referenced`` lists the joined-schema ordinals the condition reads."""
    n_s = stream.realized_num_rows()
    n_b = build.realized_num_rows()
    total = n_s * n_b
    pair_cap = bucket_capacity(max(total, 1))
    pi, bi, live = _pair_grid(pair_cap, max(n_b, 1), total)

    refset = set(referenced)
    n_left = len(stream.columns)
    pair_cols: List[Column] = []
    for o, (c, t) in enumerate(zip(stream.columns, stream_types)):
        pair_cols.append(c.gather(pi) if o in refset
                         else Column.all_null(t, pair_cap))
    for o, (c, t) in enumerate(zip(build.columns, build_types)):
        pair_cols.append(c.gather(bi) if (n_left + o) in refset
                         else Column.all_null(t, pair_cap))
    keep = cond_mask(ColumnarBatch(pair_cols, total))

    pi_s, bi_s, n_match = _compact_pairs(pi, bi, keep & live)
    n_match_i = int(jax.device_get(n_match))  # the one host sync
    out_cap = bucket_capacity(max(n_match_i, 1))
    pi_s, bi_s = pi_s[:out_cap], bi_s[:out_cap]

    cols = [c.gather(pi_s) for c in stream.columns] + \
           [c.gather(bi_s) for c in build.columns]
    return (ColumnarBatch(cols, n_match_i),
            list(stream_types) + list(build_types))


@partial(jax.jit, static_argnames=("pair_cap",))
def _pair_grid(pair_cap: int, n_b, total):
    k = jnp.arange(pair_cap, dtype=jnp.int64)
    pi = (k // n_b).astype(jnp.int32)
    bi = (k % n_b).astype(jnp.int32)
    return pi, bi, k < total


@jax.jit
def _compact_pairs(pi, bi, match):
    order, _ = sortkeys.stable_order([~match])
    return (jnp.take(pi, order), jnp.take(bi, order),
            jnp.sum(match).astype(jnp.int32))
