"""Native-kernel microbench: each Pallas kernel vs the jnp (or host)
implementation it replaces, op by op (KERNEL_r01 record).

Two ops, matching the two gated kernel kinds:

- ``join_probe``  device hash-table probe  vs  two searchsorted passes
- ``string_contains``  char-table kernel   vs  the host dictionary map

Every op asserts bit-equality between the two paths before timing —
``scripts/kernel_check.py`` turns that into the CI fence (equality on
any backend; the >=2x ratio only on a real TPU, where the kernels are
compiled rather than interpreted).

    python -m spark_rapids_tpu.benchmarks.kernel_bench --rows 2000000
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def _time(fn, iterations: int, warmup: int = 1) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iterations):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def bench_join_probe(build_rows: int, probe_rows: int, iterations: int,
                     seed: int = 5) -> dict:
    """Probe side of the hash join, build table amortized (the
    build-once/probe-many contract of ops/join.prepare_build)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.native.kernels import join as njoin

    r = np.random.default_rng(seed)
    h_b = jnp.sort(jnp.asarray(
        r.integers(-2**62, 2**62, build_rows)))
    h_p = jnp.asarray(np.concatenate([
        r.choice(np.asarray(jax.device_get(h_b)), probe_rows // 2),
        r.integers(-2**62, 2**62, probe_rows - probe_rows // 2)]))
    n_valid = jnp.asarray(build_rows)
    table = jax.block_until_ready(njoin.build_table(
        h_b, n_valid, njoin.table_bits_for(build_rows)))

    @jax.jit
    def base(sh, hp):
        lo = jnp.searchsorted(sh, hp, side="left")
        hi = jnp.searchsorted(sh, hp, side="right")
        return lo, hi - lo

    @jax.jit
    def kern(t, hp):
        return njoin.probe(t, hp)

    bl, bc = jax.device_get(base(h_b, h_p))
    kl, kc = jax.device_get(kern(table, h_p))
    equal = np.array_equal(bl, kl) and np.array_equal(bc, kc)
    base_s = _time(lambda: base(h_b, h_p), iterations)
    kern_s = _time(lambda: kern(table, h_p), iterations)
    return {"n": probe_rows, "jnp_s": round(base_s, 4),
            "kernel_s": round(kern_s, 4),
            "ratio": round(base_s / kern_s, 3), "equal": bool(equal)}


def bench_string_contains(dict_entries: int, iterations: int,
                          seed: int = 11) -> dict:
    """contains() over the dictionary: device char-table kernel vs the
    host per-entry python map (the expressions/strings fallback)."""
    import jax

    from spark_rapids_tpu.native.kernels import strings as nks

    r = np.random.default_rng(seed)
    alpha = np.array(list("abcdefgh"))
    dic = np.array(
        ["".join(r.choice(alpha, r.integers(2, 24)))
         for _ in range(dict_entries)], dtype=object)
    dic = np.unique(dic.astype(str)).astype(object)
    needle = "cde"
    chars, lens, ascii_only = nks.encode_dictionary(dic)

    def host():
        return np.array([needle in s for s in dic])

    def kern():
        return nks._match_table(chars, lens, "contains",
                                needle.encode("utf-8"))

    equal = np.array_equal(host(), np.asarray(jax.device_get(kern())))
    t0 = time.perf_counter()
    for _ in range(iterations):
        host()
    host_s = (time.perf_counter() - t0) / iterations
    kern_s = _time(kern, iterations)
    return {"n": int(len(dic)), "jnp_s": round(host_s, 4),
            "kernel_s": round(kern_s, 4),
            "ratio": round(host_s / kern_s, 3), "equal": bool(equal)}


def run(rows: int = 2_000_000, iterations: int = 3) -> dict:
    import jax

    import spark_rapids_tpu  # noqa: F401  (x64 on)
    from spark_rapids_tpu.native import kernels as nk

    ops = {
        "join_probe": bench_join_probe(
            max(rows // 8, 1024), rows, iterations),
        "string_contains": bench_string_contains(20_000, iterations),
    }
    return {
        "metric": "native_kernel_vs_jnp",
        "backend": jax.default_backend(),
        "interpret": nk.interpret_mode(),
        "ops": ops,
        "all_equal": all(o["equal"] for o in ops.values()),
        "max_ratio": max(o["ratio"] for o in ops.values()),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=2_000_000)
    ap.add_argument("--iterations", type=int, default=3)
    args = ap.parse_args(argv)
    print(json.dumps(run(args.rows, args.iterations)))


if __name__ == "__main__":
    main()
