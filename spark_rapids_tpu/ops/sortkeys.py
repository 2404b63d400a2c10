"""Sort/equality key machinery shared by sort, groupby, join and partition.

cuDF's ``Table.orderBy``/``groupBy`` handle null ordering, NaN and descending
natively (reference: SortUtils.scala, GpuSortExec.scala:104). On TPU we reduce
every key column to a small list of key lanes and hand them to
:func:`stable_order`, the one place the engine puts rows in order: it sorts
32-bit words of key and an int32 row index, nothing else, and every column
follows through :func:`take_rows`.

TPU constraint worth recording: ``bitcast_convert`` on f64 is not supported
by XLA's X64-rewriting pass on TPU (f64 is emulated as a float pair), so the
classic "bitcast float to int, twist sign" total-order key is *not* used on
device. Instead:

- floats stay floats in the sort (jnp sort order places NaN last, which is
  exactly Spark's "NaN greatest" for ascending); descending negates the
  value and adds a small NaN-rank key (Spark: DESC puts NaN first);
  -0.0 is normalized to +0.0 and NaNs canonicalized first,
- equality (grouping/join) uses *component lists*: two rows are equal iff
  all components compare equal — floats contribute (value-with-NaN-zeroed,
  isnan) so NaN==NaN without any bitcast,
- strings are dictionary codes (sorted dicts => order-isomorphic),
- nulls get a leading rank key implementing NULLS FIRST/LAST,
- padding rows (index >= num_rows) always sort last.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import dtypes as dt


@dataclasses.dataclass(frozen=True)
class SortKeySpec:
    """One ORDER BY term: column ordinal + direction + null ordering."""

    ordinal: int
    ascending: bool = True
    nulls_first: bool = True  # Spark default: NULLS FIRST for ASC

    @staticmethod
    def spark_default(ordinal: int, ascending: bool = True) -> "SortKeySpec":
        # Spark: ASC -> NULLS FIRST, DESC -> NULLS LAST
        return SortKeySpec(ordinal, ascending, nulls_first=ascending)


def canonicalize_floats(x: jax.Array) -> jax.Array:
    """-0.0 -> +0.0 and all NaNs -> one canonical quiet NaN
    (NormalizeFloatingNumbers analogue, reference
    sql-plugin/.../NormalizeFloatingNumbers.scala).

    NOT ``x + 0``: XLA's algebraic simplifier folds add-zero away inside
    larger fused programs (observed on the CPU backend), silently
    keeping -0.0's sign bit. The select below survives optimization
    because IEEE ``-0.0 == 0.0`` is true, so both zeros take the +0.0
    branch."""
    zero = jnp.zeros((), dtype=x.dtype)
    x = jnp.where(x == zero, zero, x)
    return jnp.where(jnp.isnan(x), jnp.asarray(jnp.nan, dtype=x.dtype), x)


def sort_key_arrays(data: jax.Array, validity: Optional[jax.Array],
                    dtype: dt.DType, spec: SortKeySpec) -> List[jax.Array]:
    """Key arrays for one ORDER BY term, most significant first."""
    keys: List[jax.Array] = []
    if validity is not None:
        # valid rows rank 1 when nulls first, rank 0 when nulls last;
        # a bool lane is one bit of a packed lane in stable_order
        keys.append(validity if spec.nulls_first else ~validity)
    if dtype.is_floating:
        x = canonicalize_floats(data)
        if validity is not None:
            x = jnp.where(validity, x, jnp.zeros((), x.dtype))
        if spec.ascending:
            # jnp/np sort order: NaN greatest — matches Spark ASC
            keys.append(x)
        else:
            # DESC: NaN first => NaN-rank key ahead of the negated value
            isn = jnp.isnan(x)
            keys.append(~isn)
            keys.append(jnp.where(isn, jnp.zeros((), x.dtype), -x))
        return keys
    k = data
    if validity is not None:
        k = jnp.where(validity, k, jnp.zeros((), k.dtype))
    if not spec.ascending:
        k = ~k   # bitwise on integers, logical on BOOLEAN: both reverse
    keys.append(k)
    return keys


def order_key_arrays(cols: List[Tuple[jax.Array, Optional[jax.Array]]],
                     dtypes: List[dt.DType],
                     specs: List[SortKeySpec],
                     num_rows: jax.Array,
                     live_mask: Optional[jax.Array] = None
                     ) -> List[jax.Array]:
    """Sort keys MOST significant first: pad rank (padding and
    masked-out rows last — ``live_mask`` is the fused-filter liveness),
    then each spec's key arrays."""
    capacity = cols[0][0].shape[0]
    pad_rank = jnp.arange(capacity, dtype=jnp.int32) >= num_rows
    if live_mask is not None:
        pad_rank = pad_rank | ~live_mask
    keys: List[jax.Array] = [pad_rank]
    for spec in specs:
        data, validity = cols[spec.ordinal]
        keys.extend(sort_key_arrays(data, validity,
                                    dtypes[spec.ordinal], spec))
    return keys


def lexsort_indices(cols: List[Tuple[jax.Array, Optional[jax.Array]]],
                    dtypes: List[dt.DType],
                    specs: List[SortKeySpec],
                    num_rows: jax.Array,
                    live_mask: Optional[jax.Array] = None) -> jax.Array:
    """Stable int32 permutation ordering live rows by ``specs``; padding
    and masked-out rows sort last. ``cols`` indexed by spec.ordinal."""
    order, _ = stable_order(
        order_key_arrays(cols, dtypes, specs, num_rows, live_mask))
    return order


def stable_order(lanes: Sequence[jax.Array],
                 bits: Optional[Sequence[Optional[int]]] = None
                 ) -> Tuple[jax.Array, List[jax.Array]]:
    """The one way the engine puts rows in order. ``lanes`` are key
    lanes, most significant first, padding already ranked by the caller;
    returns ``(order, sorted_lanes)``: the int32 permutation that sorts
    the rows by the lanes, ties in row order (the stable order), and the
    lanes in that order. Columns follow through :func:`take_rows`.

    Every ``lax.sort`` here has one or two operands: a 32-bit word of
    key and the row index. The chip's compiler unrolls the sort network
    over every operand and every compared lane, so its time and code
    grow steeply with both and hardly with rows (PERF.md section 6,
    PR 27, a described v5e at 2,097,152 rows: one packed word 5 s, a
    word and the index 18 s, a 64-bit key and the index 44 s, six lanes
    137 s at 32,768 rows; a column carried through the sort cost about
    as much again each, 1,143 s for the programs of TPC-H Q3, PR 23).

    - ``bits[i]`` promises lane ``i`` holds integers in
      ``[0, 2**bits[i])`` (bool lanes: one bit, known without a
      promise). Neighbouring lanes with a promise share one uint32 word.
    - Where the whole key and the row index fit one word (a liveness
      flag, a partition id: 2,047 partitions of 2,097,152 rows), ONE
      sort of ONE operand gives the order in its low bits.
    - Otherwise the key is cut into words, most significant first: an
      integer lane as uint32 words that compare as it does (sign bit
      flipped; a 64-bit lane as its high and its low word), a float
      lane as itself (XLA's total order: NaN last; callers canonicalise
      -0.0 and NaN, see :func:`sort_key_arrays`; float64 has no bitcast
      on this chip). Then one radix pass a word, least significant
      first, all the words of one type through ONE compiled sort in a
      loop: the word is gathered into the order so far and sorted with
      its position as the last key. No two (word, position) pairs are
      equal, so an unstable sort returns the one stable answer, and the
      compiler adds no index of its own."""
    n = lanes[0].shape[0]
    bits = [None] * len(lanes) if bits is None else list(bits)
    bits = [1 if x.dtype == jnp.bool_ else b for x, b in zip(lanes, bits)]
    index_bits = max(n - 1, 0).bit_length()
    iota = jnp.arange(n, dtype=jnp.int32)

    def pack(members):   # [(lane, bits)] -> one uint32 word
        word = members[0][0].astype(jnp.uint32)
        for x, b in members[1:]:
            word = (word << b) | x.astype(jnp.uint32)
        return word

    if all(b is not None for b in bits) and sum(bits) + index_bits <= 32:
        word = (pack(list(zip(lanes, bits))) << index_bits) | \
            iota.astype(jnp.uint32)
        (word,) = jax.lax.sort((word,), num_keys=1, is_stable=False)
        order = (word & jnp.uint32((1 << index_bits) - 1)).astype(jnp.int32)
        sorted_lanes, shift = [], index_bits + sum(bits)
        for x, b in zip(lanes, bits):
            shift -= b
            sorted_lanes.append(((word >> shift) & jnp.uint32(
                (1 << b) - 1)).astype(x.dtype))
        return order, sorted_lanes

    words: List[jax.Array] = []   # most significant first
    members: List[tuple] = []     # promised lanes sharing the next word
    for x, b in zip(lanes, bits):
        if b is not None:
            if sum(w for _, w in members) + b > 32:
                words.append(pack(members))
                members = []
            members.append((x, b))
            continue
        if members:
            words.append(pack(members))
            members = []
        if jnp.issubdtype(x.dtype, jnp.floating):
            words.append(x)
        elif x.dtype == jnp.uint64:
            words += [(x >> 32).astype(jnp.uint32), x.astype(jnp.uint32)]
        elif x.dtype == jnp.int64:
            words += [(x >> 32).astype(jnp.uint32) ^ jnp.uint32(1 << 31),
                      x.astype(jnp.uint32)]
        elif jnp.issubdtype(x.dtype, jnp.signedinteger):
            words.append(x.astype(jnp.int32).astype(jnp.uint32) ^
                         jnp.uint32(1 << 31))
        else:
            words.append(x.astype(jnp.uint32))
    if members:
        words.append(pack(members))

    def by_position(word):
        return jax.lax.sort((word, iota), num_keys=2, is_stable=False)[1]

    order = by_position(words.pop()) if len(words) == 1 else None
    while words:
        # the least significant run of words of one type: one compiled
        # pass for all of them
        k = len(words) - 1
        while k > 0 and words[k - 1].dtype == words[-1].dtype:
            k -= 1
        stacked, words = jnp.stack(words[k:]), words[:k]

        def radix_pass(i, order, stacked=stacked):
            word = jnp.take(stacked[stacked.shape[0] - 1 - i], order)
            return jnp.take(order, by_position(word))

        if order is None:
            order = iota
            # inside shard_map a loop's carry must vary over the mesh
            # axes from the start, as the words do
            axes = tuple(jax.typeof(stacked).vma)
            if axes:
                order = jax.lax.pcast(order, axes, to="varying")
        order = jax.lax.fori_loop(0, stacked.shape[0], radix_pass, order)
    return order, [jnp.take(x, order) for x in lanes]


def take_rows(order: jax.Array, datas: Sequence[jax.Array],
              validities: Sequence[Optional[jax.Array]]
              ) -> Tuple[List[jax.Array], List[Optional[jax.Array]]]:
    """Columns and validities in the row order ``order`` (a permutation
    from :func:`stable_order`): ONE gather a dtype, of all its columns
    stacked, and validities 32 to a uint32 word. On the v5e a gather
    costs by the index, not by what it fetches: 15.9 ms for one int32
    array of 2,097,152 rows, 17.8 ms for a bool array, 34.0 ms for a
    64-bit one, and for a batch of 15 columns with their validities
    856 ms an array at a time, 500 ms with the validities as bits, 76 ms
    stacked by dtype (PERF.md section 6, PR 27)."""
    present = [v for v in validities if v is not None]
    arrays = list(datas)
    for lo in range(0, len(present), 32):
        word = present[lo].astype(jnp.uint32)
        for j, v in enumerate(present[lo + 1:lo + 32], 1):
            word = word | (v.astype(jnp.uint32) << j)
        arrays.append(word)
    by_dtype: dict = {}
    for i, x in enumerate(arrays):
        by_dtype.setdefault(x.dtype, []).append(i)
    for members in by_dtype.values():
        moved = jnp.take(jnp.stack([arrays[i] for i in members]), order,
                         axis=1)
        for j, i in enumerate(members):
            arrays[i] = moved[j]
    words = arrays[len(datas):]
    taken = iter(((words[i // 32] >> (i % 32)) & 1).astype(jnp.bool_)
                 for i in range(len(present)))
    return (arrays[:len(datas)],
            [None if v is None else next(taken) for v in validities])


def equality_parts(data: jax.Array, validity: Optional[jax.Array],
                   dtype: dt.DType) -> Tuple[List[jax.Array], jax.Array]:
    """(components, valid): rows are grouping/join-equal iff their validity
    matches and, when valid, every component compares equal. Implements
    NaN == NaN and -0.0 == 0.0 (Spark grouping semantics) without f64
    bitcasts."""
    valid = validity if validity is not None else \
        jnp.ones(data.shape[0], dtype=bool)
    if dtype.is_floating:
        x = canonicalize_floats(data)
        isn = jnp.isnan(x)
        xz = jnp.where(isn | ~valid, jnp.zeros((), x.dtype), x)
        return [xz, isn & valid], valid
    z = jnp.where(valid, data, jnp.zeros((), data.dtype))
    return [z], valid
