"""What a join probe and a join build cost on the chip at TPC-H Q3's shapes
(PR 32; tracked, ROADMAP D12, so that a later session can repeat it): build
capacity 524,288 holding 364,181 distinct uniform hashes, stream capacity
1,048,576 holding 404,215 rows. ms a call, `--calls` calls
back to back after one warm call, until the last result is ready.

    chiprun -- python scripts/probecost.py            # writes chiprun_out/probecost.json
    JAX_PLATFORMS=cpu python scripts/probecost.py --small   # rehearsal, no times worth reading
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax
import jax.numpy as jnp

import spark_rapids_tpu  # noqa: F401  (x64)
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops import sortkeys


def timed(fn, args, calls):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return {"ms": (time.perf_counter() - t0) / calls * 1e3,
            "first_call_s": first}, out


def parent_probe(sb_h, h_p, s_rows, method="scan"):
    live_p = jnp.arange(h_p.shape[0], dtype=jnp.int32) < s_rows
    lo = jnp.searchsorted(sb_h, h_p, side="left", method=method)
    hi = jnp.searchsorted(sb_h, h_p, side="right", method=method)
    counts = jnp.where(live_p, hi - lo, 0).astype(jnp.int64)
    return lo, hi, counts, jnp.sum(counts)


def parent_build(b_datas, b_vals, h_b, b_rows):
    live_b = jnp.arange(h_b.shape[0], dtype=jnp.int32) < b_rows
    h_b_l = jnp.where(live_b, h_b, jnp.iinfo(jnp.int64).max)
    order, (sb_h,) = sortkeys.stable_order([h_b_l])
    sb_datas, sb_vals = sortkeys.take_rows(order, b_datas, b_vals)
    return sb_h, sb_datas, sb_vals


def separate_index(sb_h):
    """This PR's first form (first chip call): the distinct hashes as one
    int64 array, their run starts as another, the directory by a
    ``searchsorted`` of every bucket number."""
    b_cap = sb_h.shape[0]
    bits = J._directory_bits(b_cap)
    pos = jnp.arange(b_cap, dtype=jnp.int32)
    first = (pos == 0) | (sb_h != jnp.roll(sb_h, 1))
    starts, _ = sortkeys.stable_order([~first])
    live_u = pos < jnp.sum(first, dtype=jnp.int32)
    u_start = jnp.append(jnp.where(live_u, starts, b_cap), jnp.int32(b_cap))
    u_h = jnp.where(live_u, jnp.take(sb_h, starts), jnp.iinfo(jnp.int64).max)
    u_bucket = jnp.where(live_u, J._bucket(u_h, bits), 1 << bits)
    directory = jnp.searchsorted(
        u_bucket, jnp.arange((1 << bits) + 1, dtype=jnp.int32)
    ).astype(jnp.int32)
    fullest = jnp.max(directory[1:] - directory[:-1])
    return u_h, u_start, directory, 32 - jax.lax.clz(fullest)


def separate_build(b_datas, b_vals, h_b, b_rows):
    sb_h, sb_datas, sb_vals = parent_build(b_datas, b_vals, h_b, b_rows)
    return separate_index(sb_h), sb_datas, sb_vals


def separate_probe(index, h_p, s_rows):
    """4 gathers of int32 and rounds + 1 of int64."""
    u_h, u_start, directory, rounds = index
    bits = (directory.shape[0] - 1).bit_length() - 1
    live_p = jnp.arange(h_p.shape[0], dtype=jnp.int32) < s_rows
    b = J._bucket(h_p, bits)
    end = jnp.take(directory, b + 1)

    def halve(_, span):
        l, r = span
        mid = (l + r) >> 1
        below = (l < r) & (jnp.take(u_h, mid, mode="clip") < h_p)
        return jnp.where(below, mid + 1, l), jnp.where(below, r, mid)

    j, _ = jax.lax.fori_loop(0, rounds, halve, (jnp.take(directory, b), end))
    found = (j < end) & (jnp.take(u_h, j, mode="clip") == h_p)
    lo = jnp.take(u_start, j)
    hi = jnp.where(found, jnp.take(u_start, j + 1, mode="clip"), lo)
    counts = jnp.where(live_p, hi - lo, 0).astype(jnp.int64)
    return lo, hi, counts, jnp.sum(counts)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--small", action="store_true")
    a = ap.parse_args()
    b_cap, b_rows, s_cap, s_rows = (524288, 364181, 1048576, 404215)
    if a.small:
        b_cap, b_rows, s_cap, s_rows = (4096, 2845, 8192, 3158)
    dev = jax.devices()[0]
    rec = {"device": dev.device_kind, "platform": dev.platform,
           "shapes": [b_cap, b_rows, s_cap, s_rows], "calls": a.calls}
    r = np.random.default_rng(32)
    lim = np.iinfo(np.int64)
    h_b = r.integers(lim.min, lim.max, size=b_cap)
    # the stream: two thirds find their order, the rest find nothing
    h_p = np.where(r.random(s_cap) < 0.66, r.choice(h_b[:b_rows], s_cap),
                   r.integers(lim.min, lim.max, size=s_cap))
    # Q3's orders side: 8 columns, no validity
    dts = [np.int64] * 2 + [np.int32] + [np.float64] + [np.int32] * 4
    b_datas = [jnp.asarray(r.integers(0, 1 << 30, size=b_cap).astype(t))
               for t in dts]
    b_vals = [None] * len(dts)
    h_b, h_p = jnp.asarray(h_b), jnp.asarray(h_p)
    n_b, n_s = jnp.int32(b_rows), jnp.int32(s_rows)

    # a gather by the element, the law the prediction rests on
    idx = jnp.asarray(r.integers(0, b_cap, size=s_cap).astype(np.int32))
    for name, t in (("gather_int32", jnp.int32), ("gather_int64", jnp.int64)):
        table = jnp.arange(b_cap, dtype=t)
        rec[name], _ = timed(jax.jit(lambda tb, i: jnp.take(tb, i)),
                             (table, idx), a.calls)
    lanes = jnp.stack([jnp.arange(b_cap, dtype=jnp.int32)] * 4)
    rec["gather_int32_4_lanes_stacked"], _ = timed(
        jax.jit(lambda tb, i: jnp.take(tb, i, axis=1)), (lanes, idx), a.calls)

    rec["build_parent"], (sb_h, _, _) = timed(
        jax.jit(parent_build), (b_datas, b_vals, h_b, n_b), a.calls)
    rec["probe_parent"], want = timed(
        jax.jit(parent_probe), (sb_h, h_p, n_s), a.calls)
    want = [np.asarray(x) for x in want]
    try:
        rec["probe_parent_method_sort"], got = timed(
            jax.jit(lambda s, h, n: parent_probe(s, h, n, method="sort")),
            (sb_h, h_p, n_s), a.calls)
        rec["probe_parent_method_sort"]["exact"] = all(
            np.array_equal(np.asarray(g), w) for g, w in zip(got, want))
        del got
    except Exception as e:  # for the record only
        rec["probe_parent_method_sort"] = {"error": repr(e)[:300]}

    def exact(got):
        return all(np.array_equal(np.asarray(g), w)
                   for g, w in zip(got, want))

    rec["build_separate"], (index, _, _) = timed(
        jax.jit(separate_build), (b_datas, b_vals, h_b, n_b), a.calls)
    rec["probe_separate"], got = timed(
        jax.jit(separate_probe), (index, h_p, n_s), a.calls)
    rec["probe_separate"].update(rounds=int(index[3]), exact=exact(got))

    # the directory alone, two ways to make it, at the capacity's own bits
    full_bits = J._directory_bits(b_cap)
    u_bucket = jnp.sort(J._bucket(h_b, full_bits))
    rec["dir_searchsorted"], d1 = timed(jax.jit(lambda u: jnp.searchsorted(
        u, jnp.arange((1 << full_bits) + 1, dtype=jnp.int32)
    ).astype(jnp.int32)), (u_bucket,), a.calls)
    rec["dir_segment_sum"], d2 = timed(jax.jit(lambda u: jnp.append(
        jnp.int32(0), J._prefix_sum(jax.ops.segment_sum(
            jnp.ones_like(u), u, num_segments=1 << full_bits,
            indices_are_sorted=True)))), (u_bucket,), a.calls)
    rec["dir_segment_sum"]["exact"] = bool(jnp.array_equal(d1, d2))

    # the tree's own form, at the capacity's bits and at fewer
    real_bits = J._directory_bits
    for less in (0, 1, 2, 3):
        J._directory_bits = lambda cap, less=less: max(real_bits(cap) - less,
                                                       1)
        key = "bits_%d" % (full_bits - less)
        rec["build_" + key], (index, _, _) = timed(
            jax.jit(lambda *x: J._sort_build(*x)),
            (b_datas, b_vals, h_b, n_b), a.calls)
        rec["probe_" + key], got = timed(
            jax.jit(J._hash_probe), (index, h_p, n_s), a.calls)
        rec["probe_" + key].update(rounds=int(index.rounds), exact=exact(got))
    J._directory_bits = real_bits

    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/probecost.json", "w") as f:
        json.dump(rec, f, indent=1)
    for k, v in rec.items():
        print(k, json.dumps(v))


if __name__ == "__main__":
    main()
