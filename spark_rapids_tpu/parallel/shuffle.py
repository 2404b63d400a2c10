"""Distributed row exchange + aggregation over a device mesh.

The TPU-native re-imagining of the reference's GPU shuffle (SURVEY.md §2.8):
GpuShuffleExchangeExec partitions batches on device and hands the pieces to
a UCX transport that tag-routes them between executor GPUs
(GpuShuffleExchangeExec.scala:146-248; shuffle-plugin/.../UCX.scala). Here
every chip is a position on a ``jax.sharding.Mesh``; the whole exchange is
ONE compiled program per chip:

  1. per-device: hash the key columns → destination device per row,
  2. sort rows by destination (the contiguous-split trick the reference
     does with ``Table.partition``, GpuPartitioning.scala:44-70),
  3. scatter into fixed (n_dev, capacity) send blocks,
  4. ``jax.lax.all_to_all`` the blocks + per-destination counts — XLA lowers
     this onto ICI links directly (no bounce buffers, no progress thread),
  5. compact received rows to a live prefix and run the local sort-based
     groupby kernel (ops/groupby.py) on them.

Because keys are hash-routed, each device ends up owning a disjoint key
space — the distributed aggregate is exact with no final merge step (the
reference needs a second shuffle stage for the same guarantee).

Dynamic-size note: counts ride as data through the same all_to_all, so the
entire step stays statically shaped; only materialization realizes counts.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.shims import get_shims

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import groupby as gb
from spark_rapids_tpu.ops import hashing, sortkeys
from spark_rapids_tpu.parallel.mesh import DATA_AXIS


def _key_image(data: jax.Array, validity: jax.Array,
               dtype: dt.DType) -> jax.Array:
    """int64 hashable image per row; nulls collapse to one image.
    STRING columns must already be on a mesh-wide unified dictionary, so
    their codes are a faithful content image."""
    if dtype is dt.STRING:
        img = data.astype(jnp.int64)
    else:
        img = hashing._numeric_to_int64(data, dtype)
    return jnp.where(validity, img, jnp.int64(-0x61C8864680B583EB))


def _exchange(datas: List[jax.Array], valids: List[jax.Array],
              dest: jax.Array, live: jax.Array, n_dev: int, axis: str
              ) -> Tuple[List[jax.Array], List[jax.Array], jax.Array]:
    """All-to-all rows by per-row destination device. Returns compacted
    (datas, valids, total_rows) with capacity n_dev * local_capacity.

    Scatter-free: the destination is the one sort key
    (``sortkeys.stable_order``; padding to a sentinel bucket), per-dest
    counts come from binary searches over the sorted destinations, and
    the (n_dev, cap) send blocks are ONE gather a column through the
    order, from the contiguous runs — TPU scatters measured ~30x a
    cumsum, so none appear here. No column rides a sort: with the
    columns carried this step took the chip's compiler 191 s at 65,536
    rows a device and q1's DistributedGroupByStep did not compile in
    3,000 s (PR 23; PERF.md section 6, PR 27)."""
    cap = dest.shape[0]
    dest_l = jnp.where(live, dest, n_dev)  # padding → sentinel bucket
    order, (dest_s,) = sortkeys.stable_order([dest_l],
                                             bits=[n_dev.bit_length()])

    bounds = jnp.searchsorted(
        dest_s, jnp.arange(n_dev + 1, dtype=dest_s.dtype)).astype(jnp.int32)
    counts = bounds[1:] - bounds[:-1]
    start = bounds[:-1]

    k = jnp.arange(n_dev * cap, dtype=jnp.int32)
    d_of = k // cap
    j_of = k % cap
    src = jnp.take(order,
                   jnp.clip(jnp.take(start, d_of) + j_of, 0, cap - 1))
    sel = j_of < jnp.take(counts, d_of)

    def to_blocks(x):
        vals = jnp.where(sel, jnp.take(x, src), jnp.zeros((), x.dtype))
        return vals.reshape(n_dev, cap)

    recv_d = [jax.lax.all_to_all(to_blocks(d), axis, 0, 0)
              for d in datas]
    recv_v = [jax.lax.all_to_all(to_blocks(v), axis, 0, 0)
              for v in valids]
    counts_recv = jax.lax.all_to_all(
        counts.reshape(n_dev, 1), axis, 0, 0).reshape(n_dev)

    # compact received rows to a live prefix: one more stable order,
    # keyed on liveness
    rcap = n_dev * cap
    riota = jnp.arange(rcap, dtype=jnp.int32)
    live_r = (riota % cap) < jnp.take(counts_recv, riota // cap)
    total = jnp.sum(counts_recv).astype(jnp.int32)
    order_r, _ = sortkeys.stable_order([~live_r])
    out_d, out_v = sortkeys.take_rows(
        order_r, [r.reshape(rcap) for r in recv_d],
        [r.reshape(rcap) for r in recv_v])
    return out_d, [v & (riota < total) for v in out_v], total


class DistributedGroupByStep:
    """Compiled multi-chip groupby-aggregate: shard rows → hash-route →
    all_to_all → per-device sort-based aggregation. The flagship distributed
    pipeline (shuffle exchange + hash aggregate fused into one program)."""

    def __init__(self, mesh: Mesh, dtypes: Sequence[dt.DType],
                 key_ordinals: Sequence[int], aggs: Sequence[gb.AggSpec],
                 axis: str = DATA_AXIS):
        self.mesh = mesh
        self.dtypes = tuple(dtypes)
        self.key_ordinals = tuple(key_ordinals)
        self.aggs = tuple(aggs)
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    def _build(self):
        n_dev = self.n_dev
        dtypes = self.dtypes
        key_ordinals = self.key_ordinals
        aggs = self.aggs
        axis = self.axis

        def device_step(datas, valids, n_rows):
            # block shapes: datas[i] (cap,), n_rows (1,)
            cap = datas[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < n_rows[0]
            imgs = tuple(
                _key_image(datas[o], valids[o], dtypes[o])
                for o in key_ordinals)
            h = hashing._combine(imgs)
            dest = (jax.lax.rem(h, jnp.int64(n_dev)) +
                    jnp.int64(n_dev)) % jnp.int64(n_dev)
            dest = dest.astype(jnp.int32)
            ex_d, ex_v, total = _exchange(list(datas), list(valids), dest,
                                          live, n_dev, axis)
            cols = [(d, v) for d, v in zip(ex_d, ex_v)]
            (key_d, key_v), (agg_d, agg_v), ng = gb._groupby(
                cols, dtypes, key_ordinals, aggs, total)
            rcap = n_dev * cap
            ones = jnp.ones(rcap, dtype=bool)
            out_d = list(key_d) + list(agg_d)
            out_v = [ones if v is None else v for v in key_v] + \
                    [ones if v is None else v for v in agg_v]
            return out_d, out_v, ng.reshape(1)

        n_cols = len(self.dtypes)
        n_out = len(self.key_ordinals) + len(self.aggs)
        in_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                    P(self.axis))
        out_specs = ([P(self.axis)] * n_out, [P(self.axis)] * n_out,
                     P(self.axis))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, datas: List[jax.Array], valids: List[jax.Array],
                 counts: jax.Array):
        """datas[i]: (n_dev*cap,) row-sharded; counts: (n_dev,) per-shard
        live row counts. Returns (out_datas, out_valids, group_counts)."""
        return self._fn(datas, valids, counts)

    # -- result typing ----------------------------------------------------

    def output_dtypes(self) -> List[dt.DType]:
        out = [self.dtypes[o] for o in self.key_ordinals]
        out += [gb.agg_result_dtype(s, list(self.dtypes)) for s in self.aggs]
        return out


class DistributedShuffleStep:
    """Compiled in-program exchange: hash-route rows by key columns →
    ``lax.all_to_all`` → per-device compacted rows. The transport half
    of :class:`DistributedGroupByStep` without the aggregate tail —
    ``ShuffleExchangeExec``'s in-program mode and the shuffle bench's
    TCP-vs-ICI head-to-head ride this.

    Partition ids are computed EXACTLY like the host partition kernel
    (ops/hashing.hash_columns images incl. the null seed, then pmod by
    ``num_out``), and each row's pid travels through the collective as
    an extra routed column: device ``d`` receives every row whose
    ``pid % n_dev == d`` and the caller splits by pid host-side. That
    identity makes an in-program exchange partition-for-partition
    interchangeable with a host-path one — a co-partitioned sibling
    under a shuffled join may stay on the host path and still line up.

    ``salt_pids`` (AQE replan rule 1, the in-program half): partition
    ids in this tuple are SKEWED — their rows fan out round-robin by
    row position over ALL devices instead of landing on ``pid % n_dev``,
    so one hot key stops making a single chip's receive the straggler
    of the collective. Pids are untouched (only ``dest`` changes); the
    caller's pid-keyed split reassembles full partitions host-side, so
    downstream consumers — including the co-partitioned join contract —
    see identical partition contents, just sourced from several
    devices' blocks.
    """

    def __init__(self, mesh: Mesh, dtypes: Sequence[dt.DType],
                 key_ordinals: Sequence[int], num_out: int,
                 axis: str = DATA_AXIS,
                 salt_pids: Sequence[int] = ()):
        self.mesh = mesh
        self.dtypes = tuple(dtypes)
        self.key_ordinals = tuple(key_ordinals)
        self.num_out = num_out
        self.axis = axis
        self.salt_pids = tuple(sorted(salt_pids))
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    def _build(self):
        n_dev = self.n_dev
        num_out = self.num_out
        dtypes = self.dtypes
        key_ordinals = self.key_ordinals
        axis = self.axis
        salt_pids = self.salt_pids

        def device_step(datas, valids, n_rows):
            cap = datas[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < n_rows[0]
            # host-hash-matching images: _numeric_to_int64 + the null
            # seed hash_columns uses (NOT _key_image's sentinel) so pid
            # here == pid from ops/partition.hash_partition
            imgs = tuple(
                jnp.where(valids[o],
                          hashing._numeric_to_int64(datas[o], dtypes[o]),
                          jnp.int64(hashing._NULL_HASH))
                for o in key_ordinals)
            h = hashing._combine(imgs)
            m = h % jnp.int64(num_out)
            pid = jnp.where(m < 0, m + num_out, m).astype(jnp.int32)
            dest = pid % n_dev
            if salt_pids:
                hot = pid == jnp.int32(salt_pids[0])
                for p in salt_pids[1:]:
                    hot = hot | (pid == jnp.int32(p))
                iota = jnp.arange(cap, dtype=jnp.int32)
                dest = jnp.where(hot, (pid + iota) % n_dev, dest)
            ex = _exchange(list(datas) + [pid.astype(jnp.int64)],
                           list(valids) + [live],
                           dest, live, n_dev, axis)
            ex_d, ex_v, total = ex
            return (ex_d[:-1], ex_v[:-1], ex_d[-1].astype(jnp.int32),
                    total.reshape(1))

        n_cols = len(self.dtypes)
        in_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                    P(self.axis))
        out_specs = ([P(self.axis)] * n_cols, [P(self.axis)] * n_cols,
                     P(self.axis), P(self.axis))
        return get_shims().shard_map()(device_step, mesh=self.mesh,
                                       in_specs=in_specs,
                                       out_specs=out_specs)

    def __call__(self, datas: List[jax.Array], valids: List[jax.Array],
                 counts: jax.Array):
        """datas[i]: (n_dev*cap,) row-sharded; counts: (n_dev,). Returns
        (out_datas, out_valids, pids, recv_counts): per-device capacity
        n_dev*cap, recv_counts[d] live rows on device d, pids[j] the
        output partition of row j (only pids with pid % n_dev == d land
        on device d)."""
        return _run_shuffle_step(self, list(datas), list(valids), counts)


@partial(jax.jit, static_argnames=("step",))
def _run_shuffle_step(step, datas, valids, counts):
    """ONE module-level jit entry for every shuffle step (the
    execs/interop.py memoized idiom): the trace cache lives here, keyed
    by the identity-stable ``step`` (static) + operand shapes, so a
    fresh wrapper is never minted per call."""
    return step._fn(datas, valids, counts)


# one step per (mesh, schema, keys, parts): identity-stable steps keep
# the shard_map/jit caches warm across repeated exchanges of the same
# plan shape (the progcache in-process layer for sharded programs)
_SHUFFLE_STEPS: dict = {}


def shuffle_step(mesh: Mesh, dtypes: Sequence[dt.DType],
                 key_ordinals: Sequence[int], num_out: int,
                 salt_pids: Sequence[int] = ()) -> DistributedShuffleStep:
    key = (id(mesh), tuple(dtypes), tuple(key_ordinals), num_out,
           tuple(sorted(salt_pids)))
    got = _SHUFFLE_STEPS.get(key)
    if got is None:
        if len(_SHUFFLE_STEPS) >= 64:  # bound: distinct schemas are few
            _SHUFFLE_STEPS.clear()
        got = _SHUFFLE_STEPS[key] = DistributedShuffleStep(
            mesh, dtypes, key_ordinals, num_out, salt_pids=salt_pids)
    return got


def distributed_batch_from_host(mesh: Mesh, arrays: List[np.ndarray],
                                dtypes: List[dt.DType],
                                validities: Optional[List[Optional[np.ndarray]]] = None,
                                axis: str = DATA_AXIS):
    """Shard host rows round-robin-contiguously over the mesh: returns
    (datas, valids, counts) global device arrays with every column
    row-sharded ``P(axis)`` (the reference's RDD partitioning step)."""
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    n_dev = mesh.shape[axis]
    n = len(arrays[0])
    per = -(-n // n_dev)
    cap = bucket_capacity(max(per, 1))
    sharding = NamedSharding(mesh, P(axis))
    datas, valids = [], []
    counts = np.zeros(n_dev, dtype=np.int32)
    for d in range(n_dev):
        lo = min(d * per, n)
        counts[d] = min(per, n - lo) if lo < n else 0
    for a, t in zip(arrays, dtypes):
        buf = np.zeros(n_dev * cap, dtype=t.np_dtype)
        for d in range(n_dev):
            lo = d * per
            seg = a[lo:lo + counts[d]]
            buf[d * cap:d * cap + len(seg)] = seg
        datas.append(jax.device_put(jnp.asarray(buf), sharding))
    vin = validities or [None] * len(arrays)
    for a, v in zip(arrays, vin):
        buf = np.zeros(n_dev * cap, dtype=bool)
        for d in range(n_dev):
            lo = d * per
            c = counts[d]
            buf[d * cap:d * cap + c] = True if v is None else v[lo:lo + c]
        valids.append(jax.device_put(jnp.asarray(buf), sharding))
    counts_dev = jax.device_put(jnp.asarray(counts),
                                NamedSharding(mesh, P(axis)))
    return datas, valids, counts_dev, cap


def gather_distributed_result(out_datas, out_valids, group_counts,
                              dtypes: List[dt.DType], n_dev: int
                              ) -> ColumnarBatch:
    """Collect each device's group prefix to one host-side batch (only for
    result materialization / tests — production consumers keep it sharded)."""
    host_d = [np.asarray(jax.device_get(d)) for d in out_datas]
    host_v = [np.asarray(jax.device_get(v)) for v in out_valids]
    ng = np.asarray(jax.device_get(group_counts))
    rcap = len(host_d[0]) // n_dev
    parts_d = [[] for _ in host_d]
    parts_v = [[] for _ in host_d]
    for dev in range(n_dev):
        k = int(ng[dev])
        for i in range(len(host_d)):
            parts_d[i].append(host_d[i][dev * rcap:dev * rcap + k])
            parts_v[i].append(host_v[i][dev * rcap:dev * rcap + k])
    total = int(ng.sum())
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    cap = bucket_capacity(max(total, 1))
    cols = []
    for i, t in enumerate(dtypes):
        vals = np.concatenate(parts_d[i]) if total else \
            np.zeros(0, dtype=t.np_dtype)
        mask = np.concatenate(parts_v[i]) if total else np.zeros(0, bool)
        cols.append(Column.from_numpy(vals, t, validity=mask, capacity=cap))
    return ColumnarBatch(cols, total)
