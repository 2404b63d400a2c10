"""Distributed broadcast (dimension) join over a device mesh.

The multi-chip analogue of GpuBroadcastHashJoinExec: the small build side
is replicated to every chip (XLA keeps an unsharded operand resident per
device — the broadcast), the fact side stays row-sharded, and each chip
probes locally inside ONE compiled program. With a unique-key build side
(the dimension-table contract) the output is row-aligned with the stream
side, so the whole step is statically shaped: matches surface as a
live-mask (inner-join semantics compose with the fused-filter groupby
downstream — enrich + aggregate never materializes a compaction).

Probe strategy: sort the build keys once per step (host or device), then
per-chip vectorized binary search — the TPU replacement for cuDF's hash
probe (no device hash tables; sorted search is branch-free and fuses).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from spark_rapids_tpu.shims import get_shims
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops import sortkeys
from spark_rapids_tpu.parallel.mesh import DATA_AXIS


class DistributedDimJoinStep:
    """inner join fact (row-sharded) with dim (replicated, unique keys).

    ``__call__(fact_datas, fact_valids, fact_counts, dim_datas,
    dim_valids)`` returns (out_datas, out_valids, live_mask, counts):
    the fact columns followed by the gathered dim payload columns,
    row-aligned with the fact shards; ``live_mask`` marks matched rows.
    """

    def __init__(self, mesh: Mesh, fact_dtypes: Sequence[dt.DType],
                 dim_dtypes: Sequence[dt.DType], fact_key: int,
                 dim_key: int, axis: str = DATA_AXIS):
        self.mesh = mesh
        self.fact_dtypes = tuple(fact_dtypes)
        self.dim_dtypes = tuple(dim_dtypes)
        self.fact_key = fact_key
        self.dim_key = dim_key
        self.axis = axis
        self._fn = self._build()

    def _build(self):
        fact_key = self.fact_key
        dim_key = self.dim_key
        n_fact = len(self.fact_dtypes)
        n_dim = len(self.dim_dtypes)

        def device_step(f_datas, f_valids, f_count, d_datas, d_valids):
            cap = f_datas[0].shape[0]
            live = jnp.arange(cap, dtype=jnp.int32) < f_count[0]
            dcap = d_datas[0].shape[0]
            dkey = d_datas[dim_key]
            dvalid = d_valids[dim_key]
            # sort the dim by key (per device, tiny) for binary search;
            # invalid keys to the back
            order, (dkey_s, dinvalid_s) = sortkeys.stable_order(
                [dkey, ~dvalid])
            dvalid_s = ~dinvalid_s
            skey = f_datas[fact_key]
            svalid = f_valids[fact_key]
            pos = jnp.searchsorted(
                jnp.where(dvalid_s, dkey_s,
                          jnp.iinfo(jnp.int64).max
                          if dkey_s.dtype == jnp.int64
                          else dkey_s.max(initial=0) + 1),
                skey)
            posc = jnp.clip(pos, 0, dcap - 1)
            hit = (jnp.take(dkey_s, posc) == skey) & \
                jnp.take(dvalid_s, posc) & svalid & live
            out_d = list(f_datas)
            out_v = list(f_valids)
            src = jnp.take(order, posc)
            for j in range(n_dim):
                if j == dim_key:
                    continue
                out_d.append(jnp.take(d_datas[j], src))
                out_v.append(jnp.take(d_valids[j], src) & hit)
            new_count = jnp.sum(hit).astype(jnp.int32)
            return out_d, out_v, hit, new_count.reshape(1)

        ax = self.axis
        in_specs = ([P(ax)] * n_fact, [P(ax)] * n_fact, P(ax),
                    [P()] * n_dim, [P()] * n_dim)
        n_out = n_fact + n_dim - 1
        out_specs = ([P(ax)] * n_out, [P(ax)] * n_out, P(ax), P(ax))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                       in_specs=in_specs, out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, fact_datas, fact_valids, fact_counts,
                 dim_datas, dim_valids):
        return self._fn(fact_datas, fact_valids, fact_counts,
                        dim_datas, dim_valids)

    def output_dtypes(self) -> List[dt.DType]:
        out = list(self.fact_dtypes)
        out += [t for j, t in enumerate(self.dim_dtypes)
                if j != self.dim_key]
        return out


class DistributedShuffledJoinStep:
    """Shuffled equi-join over the mesh: BOTH sides hash-route their rows
    by join key through a ``lax.all_to_all`` (the multi-chip analogue of
    the two hash ShuffleExchangeExecs under GpuShuffledHashJoinExec), so
    equal keys co-locate; each chip then probes its local build shard with
    a sorted-hash binary search — all inside ONE compiled program.

    Build-side contract: the ROUTED build shard must have unique join keys
    (the PK/dimension side). Duplicate keys (or hash-collision runs longer
    than ``W``) raise a per-chip ``dup`` flag in the output; the caller
    must then fall back (or flip sides) — results with dup=0 are exact.

    String key columns must ride a dictionary UNIFIED across both sides
    (ops/join.unify_join_strings) so codes are faithful equality images.

    Kinds: inner / left / leftsemi / leftanti. Null join keys never match
    (SQL equi-join semantics; the reference filters them the same way,
    GpuHashJoin.scala:134-193).
    """

    W = 4  # candidate window per probe row (hash-collision tolerance)

    def __init__(self, mesh: Mesh, kind: str,
                 stream_dtypes: Sequence[dt.DType],
                 build_dtypes: Sequence[dt.DType],
                 stream_keys: Sequence[int], build_keys: Sequence[int],
                 axis: str = DATA_AXIS):
        assert kind in ("inner", "left", "leftsemi", "leftanti"), kind
        self.mesh = mesh
        self.kind = kind
        self.stream_dtypes = tuple(stream_dtypes)
        self.build_dtypes = tuple(build_dtypes)
        self.stream_keys = tuple(stream_keys)
        self.build_keys = tuple(build_keys)
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    @property
    def emits_build_columns(self) -> bool:
        return self.kind in ("inner", "left")

    def output_dtypes(self) -> List[dt.DType]:
        out = list(self.stream_dtypes)
        if self.emits_build_columns:
            out += list(self.build_dtypes)
        return out

    def _build(self):
        from spark_rapids_tpu.ops import hashing
        from spark_rapids_tpu.parallel.shuffle import _exchange, _key_image

        kind = self.kind
        n_dev = self.n_dev
        axis = self.axis
        sdt, bdt = self.stream_dtypes, self.build_dtypes
        skeys, bkeys = self.stream_keys, self.build_keys
        W = self.W
        emits_build = self.emits_build_columns
        I64MAX = jnp.int64(0x7FFFFFFFFFFFFFFF)

        def device_step(s_datas, s_valids, s_count, b_datas, b_valids,
                        b_count):
            scap = s_datas[0].shape[0]
            bcap = b_datas[0].shape[0]
            s_live = jnp.arange(scap, dtype=jnp.int32) < s_count[0]
            b_live = jnp.arange(bcap, dtype=jnp.int32) < b_count[0]

            def key_parts(datas, valids, ordinals, dtypes):
                imgs = tuple(_key_image(datas[o], valids[o], dtypes[o])
                             for o in ordinals)
                nul = jnp.zeros(datas[0].shape[0], dtype=bool)
                for o in ordinals:
                    nul = nul | ~valids[o]
                return imgs, nul

            s_imgs, s_nul = key_parts(s_datas, s_valids, skeys, sdt)
            b_imgs, b_nul = key_parts(b_datas, b_valids, bkeys, bdt)
            h_s = hashing._combine(s_imgs)
            h_b = hashing._combine(b_imgs)

            def dest_of(h):
                d = (jax.lax.rem(h, jnp.int64(n_dev)) +
                     jnp.int64(n_dev)) % jnp.int64(n_dev)
                return d.astype(jnp.int32)

            ex_s_d, ex_s_v, s_total = _exchange(
                list(s_datas), list(s_valids), dest_of(h_s), s_live,
                n_dev, axis)
            ex_b_d, ex_b_v, b_total = _exchange(
                list(b_datas), list(b_valids), dest_of(h_b), b_live,
                n_dev, axis)

            pcap = ex_s_d[0].shape[0]  # n_dev * scap
            qcap = ex_b_d[0].shape[0]
            p_iota = jnp.arange(pcap, dtype=jnp.int32)
            q_iota = jnp.arange(qcap, dtype=jnp.int32)
            p_live = p_iota < s_total
            q_live = q_iota < b_total

            # recompute key images on the routed shards
            p_imgs, p_nul = key_parts(ex_s_d, ex_s_v, skeys, sdt)
            q_imgs, q_nul = key_parts(ex_b_d, ex_b_v, bkeys, bdt)
            h_p = hashing._combine(p_imgs)
            h_q = hashing._combine(q_imgs)

            # sort the local build shard by hash; dead/null rows park at
            # +inf and carry a usable=False lane so they can never match
            q_use = q_live & ~q_nul
            q_key = jnp.where(q_use, h_q, I64MAX)
            b_order, (bq_key,) = sortkeys.stable_order([q_key])
            bq_imgs = [jnp.take(q, b_order) for q in q_imgs]
            nb = len(ex_b_d)
            bq_d, bq_v = sortkeys.take_rows(b_order, ex_b_d, ex_b_v)
            bq_use = jnp.take(q_use, b_order)

            p_use = p_live & ~p_nul
            lo = jnp.searchsorted(bq_key, h_p, side="left").astype(jnp.int32)
            hi = jnp.searchsorted(bq_key, h_p, side="right").astype(jnp.int32)

            nmatch = jnp.zeros(pcap, dtype=jnp.int32)
            first_src = jnp.zeros(pcap, dtype=jnp.int32)
            for k in range(W):
                cand = jnp.clip(lo + k, 0, qcap - 1)
                in_run = (lo + k) < hi
                exact = in_run & jnp.take(bq_use, cand) & p_use
                for pi, qi in zip(p_imgs, bq_imgs):
                    exact = exact & (pi == jnp.take(qi, cand))
                first_src = jnp.where(exact & (nmatch == 0), cand,
                                      first_src)
                nmatch = nmatch + exact.astype(jnp.int32)
            hit = nmatch > 0
            # any probe run longer than the window could hide a match past
            # it — flag regardless of hit, or results would be silently
            # wrong, not just non-unique
            dup = jnp.any((nmatch > 1) | (p_use & ((hi - lo) > W)))

            if kind == "inner":
                live_out = hit
            elif kind == "left":
                live_out = p_live
            elif kind == "leftsemi":
                live_out = hit
            else:  # leftanti
                live_out = p_live & ~hit
            out_d = list(ex_s_d)
            out_v = [v & live_out for v in ex_s_v]
            if emits_build:
                for j in range(nb):
                    out_d.append(jnp.take(bq_d[j], first_src))
                    out_v.append(jnp.take(bq_v[j], first_src) & hit &
                                 live_out)
            # compact live rows to a prefix (scatter-free liveness order)
            total = jnp.sum(live_out).astype(jnp.int32)
            c_order, _ = sortkeys.stable_order([~live_out])
            res_d, res_v = sortkeys.take_rows(c_order, out_d, out_v)
            res_v = [v & (p_iota < total) for v in res_v]
            return res_d, res_v, total.reshape(1), dup.reshape(1)

        ax = self.axis
        n_s, n_b = len(sdt), len(bdt)
        n_out = n_s + (n_b if emits_build else 0)
        in_specs = ([P(ax)] * n_s, [P(ax)] * n_s, P(ax),
                    [P(ax)] * n_b, [P(ax)] * n_b, P(ax))
        out_specs = ([P(ax)] * n_out, [P(ax)] * n_out, P(ax), P(ax))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, stream_datas, stream_valids, stream_counts,
                 build_datas, build_valids, build_counts):
        """All operands row-sharded ``P(axis)``; counts are per-shard live
        row counts. Returns (out_datas, out_valids, out_counts, dup_flags)
        — dup_flags nonzero on any chip means the unique-build contract
        failed and the result must be discarded."""
        return self._fn(stream_datas, stream_valids, stream_counts,
                        build_datas, build_valids, build_counts)


def replicate_dim(mesh: Mesh, arrays, dtypes, validities=None):
    """Place the dim table unsharded (replicated) on the mesh."""
    sharding = NamedSharding(mesh, P())
    datas, valids = [], []
    vin = validities or [None] * len(arrays)
    for a, t, v in zip(arrays, dtypes, vin):
        datas.append(jax.device_put(
            jnp.asarray(np.asarray(a, dtype=t.np_dtype)), sharding))
        mask = np.ones(len(a), dtype=bool) if v is None else \
            np.asarray(v, dtype=bool)
        valids.append(jax.device_put(jnp.asarray(mask), sharding))
    return datas, valids


class DistributedExpandJoinStep:
    """Shuffled equi-join over the mesh with ARBITRARY fan-out
    (fact x fact): the many-to-many shape the windowed unique-build step
    (DistributedShuffledJoinStep) must dup-flag away. Single join key.

    Both sides route rows by the key's int64 content image (injective —
    not a lossy hash), so per-chip probes are EXACT:

      1. all_to_all route both sides by key image,
      2. sort the local build shard by image: each probe row's match run
         is [searchsorted(left), searchsorted(right)) — exact count, no
         collision window, no dup flag,
      3. inner/left expand: output row j maps back to its probe row via
         one searchsorted over the inclusive-cumsum of match counts,
         then stream/build columns GATHER into a static ``out_cap``
         buffer (the reference's cuDF join also gathers both sides,
         GpuHashJoin.scala:302-318),
      4. semi/anti need no expansion — mask + liveness compaction.

    Output capacity is static; ``overflow`` flags chips whose true join
    size exceeded it — the caller re-plans with a bigger bucket (a
    recompile, bounded by pow2 capacity buckets), never wrong results.
    """

    def __init__(self, mesh: Mesh, kind: str,
                 stream_dtypes: Sequence[dt.DType],
                 build_dtypes: Sequence[dt.DType],
                 stream_key: int, build_key: int, out_cap: int,
                 axis: str = DATA_AXIS):
        assert kind in ("inner", "left", "leftsemi", "leftanti"), kind
        self.mesh = mesh
        self.kind = kind
        self.stream_dtypes = tuple(stream_dtypes)
        self.build_dtypes = tuple(build_dtypes)
        self.stream_key = stream_key
        self.build_key = build_key
        self.out_cap = out_cap
        self.axis = axis
        self.n_dev = mesh.shape[axis]
        self._fn = self._build()

    @property
    def emits_build_columns(self) -> bool:
        return self.kind in ("inner", "left")

    def output_dtypes(self) -> List[dt.DType]:
        out = list(self.stream_dtypes)
        if self.emits_build_columns:
            out += list(self.build_dtypes)
        return out

    def _build(self):
        from spark_rapids_tpu.parallel.shuffle import (_exchange,
                                                       _key_image)

        kind = self.kind
        n_dev = self.n_dev
        axis = self.axis
        sdt, bdt = self.stream_dtypes, self.build_dtypes
        skey_o, bkey_o = self.stream_key, self.build_key
        ocap = self.out_cap
        emits_build = self.emits_build_columns
        I64MAX = jnp.int64(0x7FFFFFFFFFFFFFFF)

        def device_step(s_datas, s_valids, s_count, b_datas, b_valids,
                        b_count):
            scap = s_datas[0].shape[0]
            bcap = b_datas[0].shape[0]
            s_live = jnp.arange(scap, dtype=jnp.int32) < s_count[0]
            b_live = jnp.arange(bcap, dtype=jnp.int32) < b_count[0]
            s_img = _key_image(s_datas[skey_o], s_valids[skey_o],
                               sdt[skey_o])
            b_img = _key_image(b_datas[bkey_o], b_valids[bkey_o],
                               bdt[bkey_o])

            def dest_of(img):
                d = (jax.lax.rem(img, jnp.int64(n_dev)) +
                     jnp.int64(n_dev)) % jnp.int64(n_dev)
                return d.astype(jnp.int32)

            ex_s_d, ex_s_v, s_total = _exchange(
                list(s_datas), list(s_valids), dest_of(s_img), s_live,
                n_dev, axis)
            ex_b_d, ex_b_v, b_total = _exchange(
                list(b_datas), list(b_valids), dest_of(b_img), b_live,
                n_dev, axis)

            pcap = ex_s_d[0].shape[0]
            qcap = ex_b_d[0].shape[0]
            p_iota = jnp.arange(pcap, dtype=jnp.int32)
            q_iota = jnp.arange(qcap, dtype=jnp.int32)
            p_live = p_iota < s_total
            q_live = q_iota < b_total

            p_img = _key_image(ex_s_d[skey_o], ex_s_v[skey_o],
                               sdt[skey_o])
            q_img = _key_image(ex_b_d[bkey_o], ex_b_v[bkey_o],
                               bdt[bkey_o])
            p_use = p_live & ex_s_v[skey_o]
            q_use = q_live & ex_b_v[bkey_o]

            # sort local build: USABLE rows first (by exact key image),
            # dead/null rows after. The usable rows form a prefix, so
            # clamping [lo, hi) to it makes sentinel collisions
            # impossible — a live key equal to I64MAX can never match a
            # dead row (r3 review finding)
            q_key = jnp.where(q_use, q_img, I64MAX)
            b_order, (_, bq_key) = sortkeys.stable_order([~q_use, q_key])
            nb = len(ex_b_d)
            bq_d, bq_v = sortkeys.take_rows(b_order, ex_b_d, ex_b_v)
            n_usable = jnp.sum(q_use).astype(jnp.int32)

            probe = jnp.where(p_use, p_img, I64MAX)
            lo = jnp.searchsorted(bq_key, probe,
                                  side="left").astype(jnp.int32)
            hi = jnp.searchsorted(bq_key, probe,
                                  side="right").astype(jnp.int32)
            lo = jnp.minimum(lo, n_usable)
            hi = jnp.minimum(hi, n_usable)
            nmatch = jnp.where(p_use, hi - lo, 0)
            hit = nmatch > 0

            if kind in ("leftsemi", "leftanti"):
                live_out = (hit if kind == "leftsemi"
                            else p_live & ~hit)
                total = jnp.sum(live_out).astype(jnp.int32)
                c_order, _ = sortkeys.stable_order([~live_out])
                res_d, res_v = sortkeys.take_rows(c_order, ex_s_d, ex_s_v)
                res_v = [v & (p_iota < total) for v in res_v]
                return (res_d, res_v, total.reshape(1),
                        total.astype(jnp.int64).reshape(1))

            # inner/left expansion. int64 accumulation: a hot key can
            # expand past 2^31 rows per chip — int32 would wrap the
            # total negative and mask the overflow flag (r3 review)
            emit = nmatch if kind == "inner" else \
                jnp.where(p_live, jnp.maximum(nmatch, 1), 0)
            csum = jnp.cumsum(emit.astype(jnp.int64))
            total = csum[-1]  # TRUE size, returned so the caller can
            # size the retry bucket exactly on overflow
            j = jnp.arange(ocap, dtype=jnp.int64)
            p_of = jnp.searchsorted(csum, j,
                                    side="right").astype(jnp.int32)
            p_of = jnp.clip(p_of, 0, pcap - 1)
            start = (jnp.take(csum, p_of) -
                     jnp.take(emit, p_of).astype(jnp.int64))
            off = (j - start).astype(jnp.int32)
            jlive = j < jnp.minimum(total, jnp.int64(ocap))
            j = j.astype(jnp.int32)
            b_of = jnp.clip(jnp.take(lo, p_of) + off, 0, qcap - 1)
            matched = jnp.take(hit, p_of) & jlive
            out_d = [jnp.take(d, p_of) for d in ex_s_d]
            out_v = [jnp.take(v, p_of) & jlive for v in ex_s_v]
            for jb in range(nb):
                out_d.append(jnp.take(bq_d[jb], b_of))
                out_v.append(jnp.take(bq_v[jb], b_of) & matched)
            return (out_d, out_v,
                    jnp.minimum(total,
                                jnp.int64(ocap)).astype(jnp.int32)
                    .reshape(1),
                    total.reshape(1))

        ax = self.axis
        n_s, n_b = len(sdt), len(bdt)
        n_out = n_s + (n_b if emits_build else 0)
        in_specs = ([P(ax)] * n_s, [P(ax)] * n_s, P(ax),
                    [P(ax)] * n_b, [P(ax)] * n_b, P(ax))
        out_specs = ([P(ax)] * n_out, [P(ax)] * n_out, P(ax), P(ax))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, stream_datas, stream_valids, stream_counts,
                 build_datas, build_valids, build_counts):
        """Returns (out_datas, out_valids, out_counts, true_totals);
        per-chip true_totals (int64, UNclamped) above out_cap mean the
        static bucket was too small — the caller rebuilds with
        bucket_capacity(max(true_totals)) and reruns, so one retry
        always suffices."""
        return self._fn(stream_datas, stream_valids, stream_counts,
                        build_datas, build_valids, build_counts)


class DistributedNullExtendUnionStep:
    """Per-chip union of the two FULL OUTER halves, entirely sharded.

    The left half carries the full (left + right) output schema (a LEFT
    join's rows); the anti half carries only the right-side columns (the
    unmatched right rows). Each chip appends the anti half's live prefix
    after the left half's, synthesizing all-null left columns for the
    appended rows — no ``all_to_all``, no host gather. This keeps the
    round-3 sharded hand-off contract: a chained mesh parent consumes
    the unioned result without ever leaving the devices (the reference
    emits both halves from one kernel, GpuHashJoin.scala:302-318; here
    the halves are separate programs so the union is its own tiny one).

    Output capacity is static per (left-cap, anti-cap) shape pair and
    always sufficient: out_cap = bucket_capacity(lcap + acap) bounds
    every per-chip row count by construction, so no overflow flag.
    """

    def __init__(self, mesh: Mesh, left_dtypes: Sequence[dt.DType],
                 right_dtypes: Sequence[dt.DType], axis: str = DATA_AXIS):
        self.mesh = mesh
        self.left_dtypes = tuple(left_dtypes)
        self.right_dtypes = tuple(right_dtypes)
        self.axis = axis
        self._fn = self._build()

    def output_dtypes(self) -> List[dt.DType]:
        return list(self.left_dtypes) + list(self.right_dtypes)

    def _build(self):
        from spark_rapids_tpu.ops.buckets import bucket_capacity

        n_left = len(self.left_dtypes)
        n_right = len(self.right_dtypes)

        def device_step(a_datas, a_valids, a_count, b_datas, b_valids,
                        b_count):
            acap = a_datas[0].shape[0]
            bcap = b_datas[0].shape[0]
            # shapes are static at trace time, so the output bucket is too
            ocap = bucket_capacity(acap + bcap)
            c1 = a_count[0]
            c2 = b_count[0]
            j = jnp.arange(ocap, dtype=jnp.int32)
            from_a = j < c1
            ai = jnp.clip(j, 0, acap - 1)
            bi = jnp.clip(j - c1, 0, bcap - 1)
            live = j < (c1 + c2)
            out_d, out_v = [], []
            for i in range(n_left):
                # left columns: the anti half contributes NULLs
                da = jnp.take(a_datas[i], ai)
                out_d.append(jnp.where(from_a, da,
                                       jnp.zeros((), da.dtype)))
                out_v.append(jnp.where(from_a,
                                       jnp.take(a_valids[i], ai),
                                       False) & live)
            for i in range(n_right):
                out_d.append(jnp.where(
                    from_a, jnp.take(a_datas[n_left + i], ai),
                    jnp.take(b_datas[i], bi)))
                out_v.append(jnp.where(
                    from_a, jnp.take(a_valids[n_left + i], ai),
                    jnp.take(b_valids[i], bi)) & live)
            return out_d, out_v, (c1 + c2).reshape(1)

        ax = self.axis
        n_a = n_left + n_right
        n_out = n_left + n_right
        in_specs = ([P(ax)] * n_a, [P(ax)] * n_a, P(ax),
                    [P(ax)] * n_right, [P(ax)] * n_right, P(ax))
        out_specs = ([P(ax)] * n_out, [P(ax)] * n_out, P(ax))
        fn = get_shims().shard_map()(device_step, mesh=self.mesh,
                                     in_specs=in_specs,
                                     out_specs=out_specs)
        return jax.jit(fn)

    def __call__(self, left_datas, left_valids, left_counts,
                 anti_datas, anti_valids, anti_counts):
        """left_* carry (n_left + n_right) columns; anti_* carry n_right.
        Returns (out_datas, out_valids, out_counts) sharded ``P(axis)``."""
        return self._fn(left_datas, left_valids, left_counts,
                        anti_datas, anti_valids, anti_counts)
