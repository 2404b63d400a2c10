"""Group-by aggregation: sort-based segmented reduction.

cuDF gives the reference a hash-based ``groupBy.aggregate``
(aggregate.scala:810-890). TPUs have no device hash tables, but XLA's sort
is fast, so the TPU-native plan is:

  1. ONE stable sort of the key lanes and a row index
     (``sortkeys.stable_order``) clusters equal keys (nulls group; NaN==NaN
     and -0.0==0.0 per Spark grouping semantics); the aggregate inputs
     follow with one gather each. When every key's value
     range is host-known (string dictionaries always are; numeric columns
     via footer/upload stats) all keys PACK into a single int32/int64 sort
     lane — measured 37 ms vs 52 ms for the multi-lane layout at 4M rows
     on a v5e,
  2. boundaries where any key lane differs from the previous row,
  3. per-aggregate ROW-SPACE lanes: prefix sums for sum/count (cumsum
     diffs at segment edges — exact for ints even across wrap), segmented
     scans for min/max, shifted lanes for first/last,
  4. ONE more stable order, keyed on ~boundary, says where each group
     starts; every per-group output lane is gathered to a group prefix.
     No lane rides either sort: carrying them cost the chip's compiler
     435 s at 65,536 rows for TPC-H Q3's int64-key group-by (PR 23) against
     what a gather costs at run time (PERF.md section 6, PR 27),
  5. segment aggregates become roll/subtract arithmetic on the compacted
     lanes; the group count stays a device scalar (no host sync).

Float sums always use the per-segment scan (never global cumsum diffs):
a global prefix sum's diffs carry rounding error that scales with the
running prefix of OTHER groups — catastrophic cancellation when a huge
group precedes a tiny one — and Inf/NaN inputs poison every later
segment. The segmented scan confines both error and poison to the group
they belong to, matching the reference's per-group hash aggregation
error behavior (cuDF groupBy.aggregate). Integer sums and counts keep
exact cumsum diffs (wrap-exact for ints). Keeping one unconditional tail
(no lax.cond) also halves the compiled program vs a dual-branch design —
compile time is a first-class cost (the chip's compiler spends seconds
to minutes on each x64 sort program).

TPU scatter (segment_sum et al.) measured ~30x slower than cumsum at 4M
rows — no scatters appear anywhere on this path.

Both halves of the reference's CudfAggregate split (update-from-raw and
merge-of-partials, AggregateFunctions.scala) map onto the same kernel with
different op lists — partial results are just another batch.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import sortkeys

# Aggregate op names understood by the kernel. ``m2`` is the exact
# per-group centered second moment sum((x - group_mean)^2) — computed
# shifted by the group's first value so no large-magnitude cancellation
# occurs (variance/stddev building block; Spark's CentralMomentAgg /
# cuDF variance role). ``rterm`` is the Konig merge-correction term
# (sum x)^2 / n that lets m2 partials merge by plain addition.
AGG_OPS = ("sum", "min", "max", "count", "count_star", "first", "last",
           "any_valid", "sum_of_squares", "m2", "rterm")


@dataclasses.dataclass(frozen=True)
class AggSpec:
    """One aggregation: op name + input ordinal (ignored for count_star).
    ``count`` counts valid rows of the input; ``first``/``last`` take the
    boundary row of each run (Spark first/last with ignoreNulls=False)."""

    op: str
    ordinal: int = -1


def quantize_range(lo: int, hi: int) -> Tuple[int, int]:
    """Widen a (lo, hi) key range to a power-of-two span on an aligned
    base. ``key_ranges`` is a STATIC jit argument — raw per-batch
    min/max would compile a fresh kernel per distinct pair (a
    compilation storm with per-row-group footer stats); quantized
    ranges bound the distinct signatures to O(log(range) * alignments).
    Correctness only needs a SUPERSET of the true range."""
    span = max(hi - lo + 1, 1)
    grid = 1 << (span - 1).bit_length()
    qlo = (lo // grid) * grid          # base on a span-scale grid
    need = hi - qlo + 1
    p = 1 << (need - 1).bit_length()   # pow2 span covering [qlo, hi]
    return (qlo, qlo + p - 1)


def key_range_of(col, dtype: dt.DType) -> Optional[Tuple[int, int]]:
    """Host-known closed value range for packed-key grouping, if any
    (quantized — see quantize_range). String dictionaries and booleans
    always have one; numerics only when the column carries stats.
    ``col`` is a column or its host mirror (a fused chain's ghost):
    anything with a ``dictionary`` or ``stats``."""
    dictionary = getattr(col, "dictionary", None)
    if dictionary is not None:
        return quantize_range(0, max(len(dictionary) - 1, 0))
    if dtype is dt.BOOLEAN:
        return (0, 1)
    if dtype.is_integral or dtype in (dt.DATE, dt.TIMESTAMP):
        s = getattr(col, "stats", None)
        if s is not None:
            return quantize_range(int(s[0]), int(s[1]))
    return None


def order_sensitive(aggs: Sequence["AggSpec"],
                    dtypes: Sequence[dt.DType]) -> bool:
    """Does any aggregate's value depend on the shape of the reduction
    tree (float sums and what is built from them)? The others (integer
    sums, counts, min/max, first/last) are exact on either path."""
    return any(spec.op in ("sum_of_squares", "m2", "rterm") or
               (spec.op == "sum" and spec.ordinal >= 0 and
                dtypes[spec.ordinal].is_floating)
               for spec in aggs)


def groupby_aggregate(batch: ColumnarBatch, key_ordinals: List[int],
                      aggs: List[AggSpec], dtypes: List[dt.DType],
                      live_mask=None, dense_ok: bool = True
                      ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    """Returns (result batch [keys..., agg results...], result dtypes).
    ``live_mask`` fuses an upstream filter into the sort pass.
    ``dense_ok`` False forces the sort path even for tiny key spaces:
    grouping-set (ROLLUP/CUBE) aggregates need it, because the expand
    step places each level's copy of the same rows at different
    positions and the dense sweep's reduction tree is position-
    dependent — levels summing the SAME value set would differ in the
    last ulp, splitting rank()-over-sum ties the sort path (segment-
    relative scan order) keeps exact. Any number of aggregates is ONE
    ``_groupby`` launch (``tests/test_tpu_compile.py`` keeps the wide
    sort-path program compiling for the v5e)."""
    cols = [(c.data, c.validity) for c in batch.columns]
    key_ranges = tuple(key_range_of(batch.columns[o], dtypes[o])
                       for o in key_ordinals)
    # dense_ok=False only needs to suppress ORDER-SENSITIVE float
    # reductions; integer sums/counts/min/max are exact regardless of
    # reduction-tree shape, so a grouping-set aggregate over those
    # keeps the dense path
    if not dense_ok and not order_sensitive(aggs, dtypes):
        dense_ok = True
    (key_d, key_v), (agg_d, agg_v), num_groups = _groupby(
        cols, tuple(dtypes), tuple(key_ordinals), tuple(aggs),
        batch.num_rows_device(), live_mask=live_mask,
        key_ranges=key_ranges, dense_ok=dense_ok)
    out_cols: List[Column] = []
    out_types: List[dt.DType] = []
    for i, ord_ in enumerate(key_ordinals):
        src = batch.columns[ord_]
        out_cols.append(src._like(key_d[i], key_v[i]))
        out_types.append(dtypes[ord_])
    for i, spec in enumerate(aggs):
        rtype = agg_result_dtype(spec, dtypes)
        if rtype is dt.STRING and spec.ordinal >= 0 and \
                isinstance(batch.columns[spec.ordinal], StringColumn):
            # preserve the dictionary: codes order == string order, so
            # min/max/first/last on codes are min/max/first/last on strings
            out_cols.append(
                batch.columns[spec.ordinal]._like(agg_d[i], agg_v[i]))
        else:
            out_cols.append(Column(rtype, agg_d[i], agg_v[i]))
        out_types.append(rtype)
    return ColumnarBatch(out_cols, num_groups), out_types


def agg_result_dtype(spec: AggSpec, dtypes: List[dt.DType]) -> dt.DType:
    if spec.op in ("count", "count_star"):
        return dt.INT64
    in_t = dtypes[spec.ordinal]
    if spec.op == "sum":
        # Spark: sum over integrals -> bigint, over fractionals -> double
        return dt.INT64 if in_t.is_integral or in_t is dt.BOOLEAN \
            else dt.FLOAT64
    if spec.op == "sum_of_squares":
        return dt.FLOAT64
    return in_t  # min/max/first/last/any_valid preserve type


# ---------------------------------------------------------------------------
# sort-lane construction
# ---------------------------------------------------------------------------


def _pack_plan(dtypes, key_ordinals, key_ranges):
    """Static decision: MAY every key pack into one integer lane?
    Returns the validated per-key ranges (all present, all discrete
    types) or None. The caller derives cards/strides/lane width from
    them — and still falls back to the generic lanes if the cardinality
    product overflows int64."""
    if key_ranges is None or len(key_ranges) != len(key_ordinals):
        return None
    if not key_ordinals:
        return None
    for r, o in zip(key_ranges, key_ordinals):
        if r is None:
            return None
        if not (dtypes[o].is_integral or dtypes[o] in
                (dt.DATE, dt.TIMESTAMP, dt.BOOLEAN, dt.STRING)):
            return None
    return key_ranges


# ---------------------------------------------------------------------------
# dense path: tiny host-known key spaces need no sort at all
# ---------------------------------------------------------------------------

# Above this slot count the masked-reduction sweep (total x capacity work
# per aggregate lane) loses to the sort kernel; below it the sweep wins
# by a wide margin — it deletes BOTH sorts and every cumsum.
_DENSE_MAX_GROUPS = 128


def _dense_layout(dtypes, key_ordinals, key_ranges, key_has_v):
    """Static layout for the sort-free dense groupby: validated ranges
    plus cards/strides/total when every key packs into at most
    ``_DENSE_MAX_GROUPS`` slots (the TPC-H q1 returnflag x linestatus
    shape). None when the packed space is too large or unpackable."""
    ranges = _pack_plan(dtypes, key_ordinals, key_ranges)
    if ranges is None:
        return None
    cards = []
    for (lo, hi), hv in zip(ranges, key_has_v):
        cards.append((hi - lo + 1) + (1 if hv else 0))
    total = 1
    for card in cards:
        total *= max(card, 1)
    if total > _DENSE_MAX_GROUPS:
        return None
    strides = []
    s = 1
    for card in reversed(cards):
        strides.append(s)
        s *= max(card, 1)
    strides.reverse()
    return ranges, tuple(cards), tuple(strides), total


def _dense_groupby(cols, dtypes, key_ordinals, aggs, live, layout):
    """Sort-free groupby for tiny host-known key spaces: rows map to a
    packed slot code, and each aggregate is ONE masked reduction over a
    [slots, capacity] broadcast compare that XLA fuses into a single
    sweep — no sort, no cumsum. The slot axis compacts with an argsort
    over <= 128 elements. Matches the semantics of the sort path exactly:
    same null-first slot encoding, same validity rules per op.

    The reference reaches the same shapes through cuDF's hash groupby
    (aggregate.scala:810-890); a TPU has no device hash table, but for a
    known-tiny key space the dense sweep is the natural MXU/VPU-friendly
    replacement — pure vectorized compare+reduce, no data movement."""
    ranges, cards, strides, total = layout
    capacity = cols[0][0].shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)

    pack = jnp.zeros(capacity, dtype=jnp.int32)
    for (lo, hi), strd, o in zip(ranges, strides, key_ordinals):
        d, v = cols[o]
        dd = d.astype(jnp.int32) if dtypes[o] is dt.BOOLEAN else d
        code = (dd - jnp.asarray(lo, dd.dtype)).astype(jnp.int32)
        if v is not None:
            code = jnp.where(v, code + 1, jnp.zeros((), jnp.int32))
        pack = pack + code * jnp.int32(strd)
    codes = jnp.where(live, pack, jnp.int32(total))  # dead -> sentinel

    slots = jnp.arange(total, dtype=jnp.int32)
    eq = codes[None, :] == slots[:, None]            # [total, capacity]
    sizes = jnp.sum(eq, axis=1).astype(jnp.int32)
    exists = sizes > 0

    def rowmask(o):
        v = cols[o][1]
        return live if v is None else (v & live)

    def nvalid_of(o):
        if cols[o][1] is None:
            return sizes
        return jnp.sum(eq & cols[o][1][None, :], axis=1).astype(jnp.int32)

    def first_idx(mask):
        return jnp.min(jnp.where(mask, iota[None, :], capacity), axis=1)

    agg_d, agg_v = [], []
    for spec in aggs:
        if spec.op == "count_star":
            agg_d.append(sizes.astype(jnp.int64))
            agg_v.append(exists)
            continue
        o = spec.ordinal
        d, v = cols[o]
        if spec.op == "count":
            agg_d.append(nvalid_of(o).astype(jnp.int64))
            agg_v.append(exists)
        elif spec.op == "sum" and not dtypes[o].is_floating:
            x = jnp.where(rowmask(o), d.astype(jnp.int64),
                          jnp.zeros((), jnp.int64))
            agg_d.append(jnp.sum(jnp.where(eq, x[None, :],
                                           jnp.zeros((), jnp.int64)),
                                 axis=1))
            agg_v.append(nvalid_of(o) > 0)
        elif spec.op in ("sum", "sum_of_squares"):
            x = d.astype(jnp.float64)
            if spec.op == "sum_of_squares":
                x = x * x
            xm = jnp.where(rowmask(o), x, 0.0)
            agg_d.append(jnp.sum(jnp.where(eq, xm[None, :], 0.0), axis=1))
            agg_v.append(nvalid_of(o) > 0)
        elif spec.op == "rterm":
            xm = jnp.where(rowmask(o), d.astype(jnp.float64), 0.0)
            s = jnp.sum(jnp.where(eq, xm[None, :], 0.0), axis=1)
            nf = jnp.maximum(nvalid_of(o), 1).astype(jnp.float64)
            agg_d.append((s * s) / nf)
            agg_v.append(nvalid_of(o) > 0)
        elif spec.op == "m2":
            x = d.astype(jnp.float64)
            contrib = rowmask(o)
            m = eq & contrib[None, :]
            fi = jnp.clip(first_idx(m), 0, capacity - 1)
            xf_row = jnp.take(jnp.take(x, fi),
                              jnp.clip(codes, 0, total - 1))
            dd = jnp.where(contrib, x - xf_row, 0.0)
            sd = jnp.sum(jnp.where(eq, dd[None, :], 0.0), axis=1)
            sd2 = jnp.sum(jnp.where(eq, (dd * dd)[None, :], 0.0), axis=1)
            n = nvalid_of(o)
            nf = jnp.maximum(n, 1).astype(jnp.float64)
            agg_d.append(jnp.maximum(sd2 - (sd * sd) / nf, 0.0))
            agg_v.append(n > 0)
        elif spec.op in ("min", "max"):
            in_t = dtypes[o]
            dd = d.astype(jnp.int8) if in_t is dt.BOOLEAN else d
            kd = dd.dtype
            if in_t.is_floating:
                big = jnp.asarray(jnp.inf, kd)
                small = jnp.asarray(-jnp.inf, kd)
            elif in_t is dt.BOOLEAN:
                big, small = jnp.asarray(1, kd), jnp.asarray(0, kd)
            else:
                big = jnp.asarray(jnp.iinfo(kd).max, kd)
                small = jnp.asarray(jnp.iinfo(kd).min, kd)
            fill = big if spec.op == "min" else small
            xm = jnp.where(rowmask(o), dd, fill)
            red = jnp.min if spec.op == "min" else jnp.max
            vals = red(jnp.where(eq, xm[None, :], fill), axis=1)
            if in_t is dt.BOOLEAN:
                vals = vals.astype(jnp.bool_)
            agg_d.append(vals)
            agg_v.append(nvalid_of(o) > 0)
        elif spec.op in ("first", "any_valid"):
            m = eq & rowmask(o)[None, :] if spec.op == "any_valid" else eq
            fi = jnp.clip(first_idx(m), 0, capacity - 1)
            agg_d.append(jnp.take(d, fi))
            if spec.op == "any_valid":
                agg_v.append(nvalid_of(o) > 0)
            else:
                agg_v.append(exists if v is None
                             else (jnp.take(v, fi) & exists))
        elif spec.op == "last":
            li = jnp.clip(jnp.max(jnp.where(eq, iota[None, :], -1),
                                  axis=1), 0, capacity - 1)
            agg_d.append(jnp.take(d, li))
            agg_v.append(exists if v is None
                         else (jnp.take(v, li) & exists))
        else:
            raise ValueError(f"unknown aggregate op {spec.op}")

    key_d, key_v_arr = [], []
    for ki, o in enumerate(key_ordinals):
        card = max(cards[ki], 1)
        code = (slots // jnp.int32(strides[ki])) % jnp.int32(card)
        wide = jnp.int32 if dtypes[o] is dt.BOOLEAN else cols[o][0].dtype
        if cols[o][1] is not None:
            kv = (code > 0) & exists
            kd = (code - 1).astype(wide) + jnp.asarray(ranges[ki][0], wide)
        else:
            kv = exists
            kd = code.astype(wide) + jnp.asarray(ranges[ki][0], wide)
        if dtypes[o] is dt.BOOLEAN:
            kd = kd.astype(jnp.bool_)
        key_d.append(kd)
        key_v_arr.append(kv)

    order, _ = sortkeys.stable_order([~exists])
    num_groups = jnp.sum(exists).astype(jnp.int32)

    def take(x):
        return jnp.take(x, order)

    key_has_v = tuple(cols[o][1] is not None for o in key_ordinals)
    key_v = [take(key_v_arr[i]) if key_has_v[i] else None
             for i in range(len(key_ordinals))]
    agg_vo = [None if spec.op in ("count", "count_star")
              else take(agg_v[i]) for i, spec in enumerate(aggs)]
    return ([take(x) for x in key_d], key_v), \
        ([take(x) for x in agg_d], agg_vo), num_groups


def _equality_lanes(d, v, dtype):
    """Sort-key lanes for one UNPACKED key column, every lane directly
    equality-comparable row-to-row (floats contribute a NaN-zeroed value
    plus an isnan flag so NaN==NaN without bitcasts)."""
    valid = v if v is not None else None
    if dtype.is_floating:
        # NOT d + 0: XLA folds add-zero inside fused programs, keeping
        # -0.0's sign (see sortkeys.canonicalize_floats)
        zero = jnp.zeros((), d.dtype)
        x = jnp.where(d == zero, zero, d)
        isn = jnp.isnan(x)
        if valid is not None:
            isn = isn & valid
            x = jnp.where(valid, x, jnp.zeros((), x.dtype))
        x = jnp.where(isn, jnp.zeros((), x.dtype), x)
        return [x, isn]
    k = d.astype(jnp.int8) if dtype is dt.BOOLEAN else d
    if valid is not None:
        k = jnp.where(valid, k, jnp.zeros((), k.dtype))
    return [k]


def _shift1(x):
    """x shifted down one row: out[i] = x[i-1], out[0] = 0."""
    z = jnp.zeros((1,), x.dtype)
    return jnp.concatenate([z, x[:-1]])


def _cumsum_isolated(x):
    """cumsum fenced from fusion: the TPU reduce-window lowering of a
    wide (i64/f64 = 32-bit pair) prefix sum exceeds the 16 MiB scoped
    VMEM limit when neighbouring ops fuse into it at multi-million-row
    shapes. Standalone it compiles and runs fine (~30-44 ms at 4M rows on
    a v5e), so barrier it off instead of lowering the whole program's
    fusion level."""
    x = jax.lax.optimization_barrier(x)
    return jax.lax.optimization_barrier(jnp.cumsum(x))


@partial(jax.jit, static_argnames=("dtypes", "key_ordinals", "aggs",
                                   "key_ranges", "dense_ok"))
def _groupby(cols, dtypes, key_ordinals, aggs, num_rows,
             live_mask=None, key_ranges=None, dense_ok=True):
    """``live_mask``: optional fused filter — masked-out rows are dead
    (they sort last with the padding and never reach a segment)."""
    capacity = cols[0][0].shape[0]
    iota = jnp.arange(capacity, dtype=jnp.int32)
    live = iota < num_rows
    if live_mask is not None:
        live = live & live_mask
        num_rows = jnp.sum(live).astype(jnp.int32)

    key_has_v = tuple(cols[o][1] is not None for o in key_ordinals)
    dense = _dense_layout(dtypes, key_ordinals, key_ranges, key_has_v) \
        if dense_ok else None
    if dense is not None:
        return _dense_groupby(cols, dtypes, key_ordinals, aggs, live,
                              dense)

    ranges = _pack_plan(dtypes, key_ordinals, key_ranges)

    # ---- 1. sort-key lanes ------------------------------------------------
    packed = None
    key_lane_slices = []  # per key: (start, count) into sort_keys
    if ranges is not None:
        cards = []
        for (lo, hi), has_v in zip(ranges, key_has_v):
            cards.append((hi - lo + 1) + (1 if has_v else 0))
        total = 1
        for c in cards:
            total *= max(c, 1)
        if total + 1 <= 0x7FFFFFFF:
            lane_dt = jnp.int32
        elif total + 1 <= (1 << 62):
            lane_dt = jnp.int64
        else:
            ranges = None
    if ranges is not None:
        pack = jnp.zeros(capacity, dtype=lane_dt)
        strides = []
        stride = 1
        for card in reversed(cards):
            strides.append(stride)
            stride *= max(card, 1)
        strides.reverse()
        for (lo, hi), has_v, strd, o in zip(ranges, key_has_v, strides,
                                            key_ordinals):
            d, v = cols[o]
            # subtract the range base BEFORE narrowing: int64 keys with a
            # small span but large magnitude must not wrap
            dd = d.astype(jnp.int32) if dtypes[o] is dt.BOOLEAN else d
            code = (dd - jnp.asarray(lo, dd.dtype)).astype(lane_dt)
            if has_v:
                code = jnp.where(v, code + 1, jnp.zeros((), lane_dt))
            pack = pack + code * lane_dt(strd)
        sentinel = lane_dt(total)
        packed = jnp.where(live, pack, sentinel)
        sort_keys = [packed]
        # an int32 lane holds [0, total]; an int64 lane sorts as two words
        key_bits = [total.bit_length() if lane_dt is jnp.int32 else None]
    else:
        rank = (~live).astype(jnp.int32)
        for o, has_v in zip(key_ordinals, key_has_v):
            if has_v:
                # valid rows rank 1: nulls group FIRST (matching the
                # packed path's reserved 0 slot and Spark's ASC default)
                rank = (rank << 1) | cols[o][1].astype(jnp.int32)
        sort_keys = [rank]
        key_bits = [1 + sum(key_has_v)]
        for o in key_ordinals:
            d, v = cols[o]
            lanes = _equality_lanes(d, v, dtypes[o])
            key_lane_slices.append((len(sort_keys), len(lanes)))
            sort_keys.extend(lanes)
            key_bits.extend([None] * len(lanes))

    # ---- 2. agg-input columns not derivable from keys follow the order ----
    order, s_keys = sortkeys.stable_order(sort_keys, key_bits)
    needed = [o for o in dict.fromkeys(spec.ordinal for spec in aggs)
              if o >= 0 and o not in key_ordinals]
    datas, vals = sortkeys.take_rows(order, [cols[o][0] for o in needed],
                                     [cols[o][1] for o in needed])
    sorted_cols = {o: (d, v) for o, d, v in zip(needed, datas, vals)}

    # reconstruct key columns (data, validity) in sorted order from the
    # sorted key lanes themselves: no gather
    if ranges is not None:
        sp = s_keys[0]
        for ki, o in enumerate(key_ordinals):
            code = (sp // lane_dt(strides[ki])) % lane_dt(
                max(cards[ki], 1))
            # widen to the column dtype BEFORE adding the range base:
            # int64/TIMESTAMP keys with magnitude above the lane dtype's
            # range (small span, large base) must not wrap in lane_dt
            wide = jnp.int32 if dtypes[o] is dt.BOOLEAN else \
                cols[o][0].dtype
            if key_has_v[ki]:
                kv = code > 0
                kd = (code - 1).astype(wide) + jnp.asarray(
                    ranges[ki][0], wide)
            else:
                kv = None
                kd = code.astype(wide) + jnp.asarray(ranges[ki][0], wide)
            if dtypes[o] is dt.BOOLEAN:
                kd = kd.astype(jnp.bool_)
            sorted_cols[o] = (kd, kv)
    else:
        s_rank = s_keys[0]
        nbits = sum(1 for h in key_has_v if h)
        bit = nbits
        for ki, o in enumerate(key_ordinals):
            start, cnt = key_lane_slices[ki]
            if dtypes[o].is_floating:
                val, isn = s_keys[start], s_keys[start + 1]
                kd = jnp.where(isn, jnp.asarray(jnp.nan, val.dtype), val)
            else:
                kd = s_keys[start]
                if dtypes[o] is dt.BOOLEAN:
                    kd = kd.astype(jnp.bool_)
            if key_has_v[ki]:
                bit -= 1
                kv = ((s_rank >> bit) & 1) == 1
            else:
                kv = None
            sorted_cols[o] = (kd, kv)

    live_sorted = iota < num_rows

    # ---- 3. boundaries ----------------------------------------------------
    def lane_diff(lane):
        return jnp.concatenate(
            [jnp.ones(1, dtype=bool), lane[1:] != lane[:-1]])

    boundary = jnp.zeros(capacity, dtype=bool).at[0].set(True)
    if ranges is not None:
        boundary = boundary | lane_diff(s_keys[0])
    else:
        for lane in s_keys:
            boundary = boundary | lane_diff(lane)
    boundary = boundary & live_sorted
    num_groups = jnp.sum(boundary).astype(jnp.int32)

    # ---- 4. aggregate tail ------------------------------------------------
    key_d, key_v_arr, agg_d, agg_v_arr = _segments_tail(
        sorted_cols, dtypes, key_ordinals, aggs, boundary,
        live_sorted, num_rows, num_groups, capacity)

    key_v = [key_v_arr[i] if key_has_v[i] else None
             for i in range(len(key_ordinals))]
    # counts are never null (reference: CudfCount merges to 0, not null)
    agg_v = [None if spec.op in ("count", "count_star") else agg_v_arr[i]
             for i, spec in enumerate(aggs)]
    return (list(key_d), key_v), (list(agg_d), agg_v), num_groups


def _segments_tail(sorted_cols, dtypes, key_ordinals, aggs, boundary,
                   live_sorted, num_rows, num_groups, capacity):
    """Row-space lanes -> ONE compaction order -> group-space arithmetic.
    Returns (key_d, key_v_arrays, agg_d, agg_v_arrays) with validity as
    plain bool arrays (the caller maps Nones back)."""
    iota = jnp.arange(capacity, dtype=jnp.int32)

    # ---- row-space lanes per aggregate
    # each entry: (kind, lanes...) consumed positionally after compaction
    lane_specs = []   # static description
    lanes = []        # arrays gathered to the group prefix

    def add_lane(x):
        lanes.append(x)
        return len(lanes) - 1

    def contrib_of(o):
        d, v = sorted_cols[o]
        return live_sorted if v is None else (v & live_sorted)

    count_lane_of = {}

    def ensure_count_lane(o):
        """Segment valid-count via i32 cumsum (exact: counts <= capacity
        < 2^31). Returns (lane index, grand total) — or (None, None) when
        the column has no validity: live rows are a prefix after the sort,
        so the valid count IS the segment size (no cumsum, no lane)."""
        if sorted_cols[o][1] is None:
            return (None, None)
        if o not in count_lane_of:
            cs = _cumsum_isolated(contrib_of(o).astype(jnp.int32))
            count_lane_of[o] = (add_lane(_shift1(cs)), cs[-1])
        return count_lane_of[o]

    for si, spec in enumerate(aggs):
        if spec.op == "count_star":
            lane_specs.append(("sizes",))
            continue
        o = spec.ordinal
        d, v = sorted_cols[o]
        contrib = contrib_of(o)
        valid_arr = v if v is not None else live_sorted
        if spec.op == "count":
            idx, tot = ensure_count_lane(o)
            if idx is None:
                lane_specs.append(("sizes",))
            else:
                lane_specs.append(("count", idx, tot))
        elif spec.op == "sum" and not dtypes[o].is_floating:
            x = jnp.where(contrib, d.astype(jnp.int64),
                          jnp.zeros((), jnp.int64))
            cs = _cumsum_isolated(x)
            idx = add_lane(_shift1(cs))
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("isum", idx, cs[-1], cidx, ctot))
        elif spec.op in ("sum", "sum_of_squares"):
            # per-segment inclusive scan, never global cumsum diffs:
            # confines rounding error AND Inf/NaN poison to each group
            # (a global prefix's diffs carry error scaling with the
            # running prefix of OTHER groups)
            x = d.astype(jnp.float64)
            if spec.op == "sum_of_squares":
                x = x * x
            xm = jnp.where(contrib, x, 0.0)
            scan = _seg_scan(xm, boundary, jnp.add)
            sidx = add_lane(_shift1(scan))
            last = jax.lax.dynamic_index_in_dim(
                scan, jnp.maximum(num_rows - 1, 0), keepdims=False)
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("scan", sidx, last, cidx, ctot, False))
        elif spec.op == "rterm":
            # (sum x)^2 / n per group: rides the same xm seg scan shape
            # as a float sum; squared/divided in group space
            x = d.astype(jnp.float64)
            xm = jnp.where(contrib, x, 0.0)
            scan = _seg_scan(xm, boundary, jnp.add)
            sidx = add_lane(_shift1(scan))
            last = jax.lax.dynamic_index_in_dim(
                scan, jnp.maximum(num_rows - 1, 0), keepdims=False)
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("rterm", sidx, last, cidx, ctot))
        elif spec.op == "m2":
            # exact per-group centered second moment: shift every row by
            # the group's FIRST valid value (a segmented first-valid
            # scan), then m2 = sum(d^2) - (sum d)^2 / n — algebraically
            # identical to sum((x - mean)^2) and free of the
            # large-magnitude cancellation of the raw sum-of-squares
            # formula (r3 advisor finding)
            x = d.astype(jnp.float64)
            xf = _seg_first_valid(x, contrib, boundary)
            dd = jnp.where(contrib, x - xf, 0.0)
            scan_d = _seg_scan(dd, boundary, jnp.add)
            scan_d2 = _seg_scan(dd * dd, boundary, jnp.add)
            sidx_d = add_lane(_shift1(scan_d))
            last_d = jax.lax.dynamic_index_in_dim(
                scan_d, jnp.maximum(num_rows - 1, 0), keepdims=False)
            sidx_d2 = add_lane(_shift1(scan_d2))
            last_d2 = jax.lax.dynamic_index_in_dim(
                scan_d2, jnp.maximum(num_rows - 1, 0), keepdims=False)
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("m2", sidx_d, last_d, sidx_d2, last_d2,
                               cidx, ctot))
        elif spec.op in ("min", "max"):
            in_t = dtypes[o]
            kd = d.dtype
            dd = d
            if in_t is dt.BOOLEAN:
                dd = d.astype(jnp.int8)
                kd = jnp.int8
            if in_t.is_floating:
                big = jnp.asarray(jnp.inf, kd)
                small = jnp.asarray(-jnp.inf, kd)
            elif in_t is dt.BOOLEAN:
                big, small = jnp.asarray(1, kd), jnp.asarray(0, kd)
            else:
                big = jnp.asarray(jnp.iinfo(kd).max, kd)
                small = jnp.asarray(jnp.iinfo(kd).min, kd)
            if spec.op == "min":
                x = jnp.where(contrib, dd, big)
                scan = _seg_scan(x, boundary, jnp.minimum)
            else:
                x = jnp.where(contrib, dd, small)
                scan = _seg_scan(x, boundary, jnp.maximum)
            sidx = add_lane(_shift1(scan))
            last = jax.lax.dynamic_index_in_dim(
                scan, jnp.maximum(num_rows - 1, 0), keepdims=False)
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("scan", sidx, last, cidx, ctot,
                               dtypes[o] is dt.BOOLEAN))
        elif spec.op == "first":
            didx = add_lane(d)
            vidx = add_lane(valid_arr)
            lane_specs.append(("first", didx, vidx, "first", None, None))
        elif spec.op == "any_valid":
            # first VALID value per group (Spark first(ignoreNulls=true);
            # the CPU oracle takes rows[valid] — cpu/engine.py:384-389).
            # The boundary row's raw value is NOT it when that row is
            # null, so ride the segmented first-valid scan and read it at
            # each segment's LAST row (the scan-decode shape min/max use)
            fv = _seg_first_valid(d, contrib, boundary)
            sidx = add_lane(_shift1(fv))
            last = jax.lax.dynamic_index_in_dim(
                fv, jnp.maximum(num_rows - 1, 0), keepdims=False)
            cidx, ctot = ensure_count_lane(o)
            lane_specs.append(("anyv", sidx, last, cidx, ctot))
        elif spec.op == "last":
            didx = add_lane(_shift1(d))
            vidx = add_lane(_shift1(valid_arr))
            dlast = jax.lax.dynamic_index_in_dim(
                d, jnp.maximum(num_rows - 1, 0), keepdims=False)
            vlast = jax.lax.dynamic_index_in_dim(
                valid_arr, jnp.maximum(num_rows - 1, 0), keepdims=False)
            lane_specs.append(("last", didx, vidx, dlast, vlast))
        else:
            raise ValueError(f"unknown aggregate op {spec.op}")

    # key output lanes
    key_lane_idx = []
    for o in key_ordinals:
        d, v = sorted_cols[o]
        di = add_lane(d)
        vi = add_lane(v) if v is not None else None
        key_lane_idx.append((di, vi))

    # ---- ONE compaction order: boundary rows to a group prefix
    first_idx, _ = sortkeys.stable_order([~boundary])
    c, _ = sortkeys.take_rows(first_idx, lanes, [None] * len(lanes))
    # group g at row g

    giota = iota
    glive = giota < num_groups
    is_last_group = giota == (num_groups - 1)

    def roll_next(x, last_value):
        """x[g+1] for g < ng-1; ``last_value`` for the final group."""
        nxt = jnp.roll(x, -1)
        return jnp.where(is_last_group,
                         jnp.asarray(last_value, x.dtype), nxt)

    next_first = roll_next(first_idx, num_rows)
    seg_sizes = jnp.where(glive, next_first - first_idx, 0)

    def nvalid_of(cidx, ctot):
        """Per-group valid count: cumsum-lane diff, or the segment size
        when the input had no validity lane."""
        if cidx is None:
            return seg_sizes
        clo = c[cidx]
        return roll_next(clo, ctot) - clo

    # ---- group-space decode
    agg_d, agg_v = [], []
    for ls in lane_specs:
        kind = ls[0]
        if kind == "sizes":
            agg_d.append(seg_sizes.astype(jnp.int64))
            agg_v.append(glive)
            continue
        if kind == "count":
            _, idx, tot = ls
            lo = c[idx]
            n = roll_next(lo, tot) - lo
            agg_d.append(n.astype(jnp.int64))
            agg_v.append(glive)
            continue
        if kind == "isum":
            _, idx, tot, cidx, ctot = ls
            lo = c[idx]
            s = roll_next(lo, tot) - lo
            nvalid = nvalid_of(cidx, ctot)
            agg_d.append(s)
            agg_v.append(glive & (nvalid > 0))
            continue
        if kind == "scan":
            _, sidx, last, cidx, ctot, was_bool = ls
            vals = roll_next(c[sidx], last)
            if was_bool:
                vals = vals.astype(jnp.bool_)
            nvalid = nvalid_of(cidx, ctot)
            agg_d.append(vals)
            agg_v.append(glive & (nvalid > 0))
            continue
        if kind == "rterm":
            _, sidx, last, cidx, ctot = ls
            s = roll_next(c[sidx], last)
            nvalid = nvalid_of(cidx, ctot)
            nf = jnp.maximum(nvalid, 1).astype(jnp.float64)
            agg_d.append((s * s) / nf)
            agg_v.append(glive & (nvalid > 0))
            continue
        if kind == "m2":
            _, sidx_d, last_d, sidx_d2, last_d2, cidx, ctot = ls
            sd = roll_next(c[sidx_d], last_d)
            sd2 = roll_next(c[sidx_d2], last_d2)
            nvalid = nvalid_of(cidx, ctot)
            nf = jnp.maximum(nvalid, 1).astype(jnp.float64)
            m2 = sd2 - (sd * sd) / nf
            agg_d.append(jnp.maximum(m2, 0.0))
            agg_v.append(glive & (nvalid > 0))
            continue
        if kind == "first":
            _, didx, vidx, op, cidx, ctot = ls
            agg_d.append(c[didx])
            agg_v.append(glive & c[vidx] & (seg_sizes > 0))
            continue
        if kind == "anyv":
            _, sidx, last, cidx, ctot = ls
            nvalid = nvalid_of(cidx, ctot)
            agg_d.append(roll_next(c[sidx], last))
            agg_v.append(glive & (nvalid > 0))
            continue
        if kind == "last":
            _, didx, vidx, dlast, vlast = ls
            agg_d.append(roll_next(c[didx], dlast))
            agg_v.append(glive & roll_next(c[vidx], vlast) &
                         (seg_sizes > 0))
            continue

    key_d, key_v = [], []
    for (di, vi) in key_lane_idx:
        key_d.append(c[di])
        key_v.append((c[vi] & glive) if vi is not None else glive)
    return tuple(key_d), tuple(key_v), tuple(agg_d), tuple(agg_v)


def _seg_scan(x: jax.Array, boundary: jax.Array, op) -> jax.Array:
    """Segmented inclusive scan: row i = op-reduce over [seg_start..i]."""
    def combine(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, op(av, bv)), af | bf
    v, _ = jax.lax.associative_scan(combine, (x, boundary))
    return v


def _seg_first_valid(x: jax.Array, valid: jax.Array,
                     boundary: jax.Array) -> jax.Array:
    """Row i = first VALID x in [seg_start..i] (i's own value when it is
    the first). Rows before their segment's first valid value get 0 —
    callers mask those rows out anyway."""
    xm = jnp.where(valid, x, jnp.zeros((), x.dtype))

    def combine(a, b):
        av, aseen, af = a
        bv, bseen, bf = b
        v = jnp.where(bf, bv, jnp.where(aseen, av, bv))
        seen = jnp.where(bf, bseen, aseen | bseen)
        return v, seen, af | bf

    v, _, _ = jax.lax.associative_scan(combine, (xm, valid, boundary))
    return v


# ---------------------------------------------------------------------------
# whole-batch reductions (no keys)
# ---------------------------------------------------------------------------


def reduce_aggregate(batch: ColumnarBatch, aggs: List[AggSpec],
                     dtypes: List[dt.DType], live_mask=None
                     ) -> Tuple[ColumnarBatch, List[dt.DType]]:
    """Whole-batch reduction (no keys): grand aggregates
    (aggregate.scala:488-501 reduction path). Returns a 1-row batch."""
    if not batch.columns:
        # rows-only batch: only count(*) is expressible. A fused filter
        # mask still applies — count the LIVE rows.
        if live_mask is not None:
            iota = jnp.arange(live_mask.shape[0], dtype=jnp.int32)
            n = int(jax.device_get(jnp.sum(
                live_mask & (iota < batch.num_rows_device()))))
        else:
            n = batch.realized_num_rows()
        out_cols = [Column(dt.INT64,
                           jnp.full(128, n, dtype=jnp.int64))
                    for spec in aggs]
        return ColumnarBatch(out_cols, 1), [dt.INT64] * len(aggs)
    cols = [(c.data, c.validity) for c in batch.columns]
    agg_d, agg_v = _reduce(cols, tuple(dtypes), tuple(aggs),
                           batch.num_rows_device(), live_mask)
    out_cols, out_types = [], []
    for i, spec in enumerate(aggs):
        rtype = agg_result_dtype(spec, dtypes)
        out_cols.append(Column(rtype, agg_d[i], agg_v[i]))
        out_types.append(rtype)
    return ColumnarBatch(out_cols, 1), out_types


@partial(jax.jit, static_argnames=("dtypes", "aggs"))
def _reduce(cols, dtypes, aggs, num_rows, live_mask=None):
    """Direct whole-array reductions — no sort, no segments. IEEE
    semantics (Inf/NaN) come straight from jnp reductions."""
    capacity = cols[0][0].shape[0] if cols else 128
    iota = jnp.arange(capacity, dtype=jnp.int32)
    live = iota < num_rows
    if live_mask is not None:
        live = live & live_mask
    n_live = jnp.sum(live.astype(jnp.int32))
    any_live = n_live > 0
    first_live = jnp.where(any_live, jnp.argmax(live).astype(jnp.int32), 0)
    last_live = jnp.where(
        any_live,
        (capacity - 1 - jnp.argmax(live[::-1])).astype(jnp.int32), 0)

    def full(x):
        return jnp.full(capacity, x)

    agg_d, agg_v = [], []
    for spec in aggs:
        if spec.op == "count_star":
            agg_d.append(full(n_live.astype(jnp.int64)))
            agg_v.append(None)
            continue
        d, v = cols[spec.ordinal]
        valid = v if v is not None else jnp.ones(capacity, dtype=bool)
        contrib = valid & live
        n_valid = jnp.sum(contrib.astype(jnp.int64))
        out_valid = full(n_valid > 0)
        in_t = dtypes[spec.ordinal]
        if spec.op == "count":
            agg_d.append(full(n_valid))
            agg_v.append(None)
        elif spec.op == "sum":
            if in_t.is_integral or in_t is dt.BOOLEAN:
                x = jnp.where(contrib, d.astype(jnp.int64),
                              jnp.zeros((), jnp.int64))
                agg_d.append(full(jnp.sum(x)))
            else:
                x = jnp.where(contrib, d.astype(jnp.float64), 0.0)
                agg_d.append(full(jnp.sum(x)))
            agg_v.append(out_valid)
        elif spec.op == "sum_of_squares":
            x = d.astype(jnp.float64)
            x = jnp.where(contrib, x * x, 0.0)
            agg_d.append(full(jnp.sum(x)))
            agg_v.append(out_valid)
        elif spec.op in ("m2", "rterm"):
            x = jnp.where(contrib, d.astype(jnp.float64), 0.0)
            s = jnp.sum(x)
            nf = jnp.maximum(n_valid, 1).astype(jnp.float64)
            if spec.op == "rterm":
                agg_d.append(full((s * s) / nf))
            else:
                # exact whole-batch second moment: mean available in one
                # program, no shift trick needed
                mean = s / nf
                dd = jnp.where(contrib,
                               d.astype(jnp.float64) - mean, 0.0)
                agg_d.append(full(jnp.maximum(jnp.sum(dd * dd), 0.0)))
            agg_v.append(out_valid)
        elif spec.op in ("min", "max"):
            kd = d.dtype
            dd = d
            if in_t is dt.BOOLEAN:
                dd = d.astype(jnp.int8)
                kd = jnp.int8
            if in_t.is_floating:
                big = jnp.asarray(jnp.inf, kd)
            elif in_t is dt.BOOLEAN:
                big = jnp.asarray(1, kd)
            else:
                big = jnp.asarray(jnp.iinfo(kd).max, kd)
            if spec.op == "min":
                r = jnp.min(jnp.where(contrib, dd, big))
            else:
                small = -big if in_t.is_floating else \
                    jnp.asarray(0, kd) if in_t is dt.BOOLEAN else \
                    jnp.asarray(jnp.iinfo(kd).min, kd)
                r = jnp.max(jnp.where(contrib, dd, small))
            if in_t is dt.BOOLEAN:
                r = r.astype(jnp.bool_)
            agg_d.append(full(r))
            agg_v.append(out_valid)
        elif spec.op in ("first", "any_valid"):
            val = jax.lax.dynamic_index_in_dim(d, first_live,
                                               keepdims=False)
            agg_d.append(full(val))
            if spec.op == "any_valid":
                agg_v.append(out_valid)
            else:
                fv = jax.lax.dynamic_index_in_dim(valid, first_live,
                                                  keepdims=False)
                agg_v.append(full(fv & any_live))
        elif spec.op == "last":
            val = jax.lax.dynamic_index_in_dim(d, last_live,
                                               keepdims=False)
            lv = jax.lax.dynamic_index_in_dim(valid, last_live,
                                              keepdims=False)
            agg_d.append(full(val))
            agg_v.append(full(lv & any_live))
        else:
            raise ValueError(f"unknown aggregate op {spec.op}")
    return agg_d, agg_v
