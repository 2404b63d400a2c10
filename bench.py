"""Benchmark: q5-like scan→filter→groupby-aggregate throughput, TPU vs CPU.

Models the reference's integration-test q5-like (parquet-scan + filter
+ hash aggregate, integration_tests/.../TpchLikeSpark.scala methodology):
identical relational work is timed on the TPU pipeline and on a pandas CPU baseline, and the
ratio is reported (the reference's own headline metric is this CPU-vs-GPU
speedup shape, docs/FAQ.md:60-67).

Prints exactly one JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

N_ROWS = 4_000_000
N_KEYS = 65_536
WARMUP = 2
ITERS = 5


def gen_data(n=N_ROWS, seed=7):
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, N_KEYS, n).astype(np.int64)
    key_valid = rng.random(n) > 0.02
    vals = rng.random(n)
    return keys, key_valid, vals


def bench_tpu(keys, key_valid, vals):
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import entry  # the same fused pipeline

    step, _ = entry()
    jstep = jax.jit(step)
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    n = len(keys)
    cap = bucket_capacity(n)
    kd = jnp.asarray(np.concatenate(
        [keys, np.zeros(cap - n, dtype=np.int64)]))
    kv = jnp.asarray(np.concatenate([key_valid, np.zeros(cap - n, bool)]))
    vd = jnp.asarray(np.concatenate([vals, np.zeros(cap - n)]))
    nr = jnp.int32(n)
    for _ in range(WARMUP):
        out = jstep(kd, kv, vd, nr)
        jax.device_get(out[4])
    # steady-state throughput: dispatches pipeline (async), the final
    # device_get forces the LAST step — device execution is in-order, so
    # every earlier step has completed by then.
    t0 = time.perf_counter()
    outs = [jstep(kd, kv, vd, nr) for _ in range(ITERS)]
    out = outs[-1]
    jax.device_get(out[4])
    dt = (time.perf_counter() - t0) / ITERS
    return dt, out


def bench_cpu(keys, key_valid, vals):
    import pandas as pd

    df = pd.DataFrame({"k": keys, "valid": key_valid, "v": vals})

    def run():
        f = df[(df["v"] > 0.5) & df["valid"]]
        return f.groupby("k").agg(s=("v", "sum"), c=("v", "count"),
                                  n=("v", "size"))

    run()  # warmup
    t0 = time.perf_counter()
    for _ in range(max(ITERS // 2, 1)):
        out = run()
    dt = (time.perf_counter() - t0) / max(ITERS // 2, 1)
    return dt, out


def _service_warmup(runner, benchmark: str):
    """Warm compile caches through the service warmup ladder before the
    timed run: register_template traces + compiles the query's stage
    programs (persisted via progcache, which IS process-global), then
    replays the bucket-registry rungs so smaller capacity buckets start
    hot too. The throwaway service is discarded — its per-service
    result cache is never consulted by the timed BenchmarkRunner path,
    so the measurement below is a genuine cold-data/hot-code run."""
    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.benchmarks.runner import ALL_BENCHMARKS
    from spark_rapids_tpu.service.query_service import QueryService

    runner.ensure_data(benchmark)
    plan = ALL_BENCHMARKS[benchmark](runner.data_dir)
    # single-query run: rungs above the input's own bucket can never be
    # hit, so cap the ladder replay there (BENCH_r08 showed an 11.75 s
    # full-ladder warmup for a 1.6 s q26 run)
    max_rung = _input_rung(plan)
    svc = QueryService({cfg.SERVICE_WARMUP_ENABLED.key: True})
    try:
        report = svc.register_template(plan, name=benchmark,
                                       max_rung=max_rung) or {}
    finally:
        svc.shutdown()
    ladder = report.get("ladder") or {}
    return {"templates": report.get("templates"),
            "ladder_replays": ladder.get("replays"),
            "rungs_skipped": ladder.get("rungs_skipped"),
            "max_rung": max_rung,
            "seconds": report.get("seconds")}


def _input_rung(plan):
    """Ladder bucket of the query's largest input table (from scan-leaf
    row-count estimates), or None when any leaf count is unknown."""
    from spark_rapids_tpu.ops import buckets as _ladder
    from spark_rapids_tpu.plan.nodes import ScanNode

    rows = []
    stack = [getattr(plan, "_plan", plan)]
    while stack:
        node = stack.pop()
        if isinstance(node, ScanNode):
            n = node.source.estimated_row_count()
            if n is None:
                return None
            rows.append(int(n))
        stack.extend(node.children)
    if not rows:
        return None
    return _ladder.bucket_capacity(max(rows))


def bench_full_query(benchmark: str = "tpcxbb_q26", sf: float = 0.1,
                     warmup_service: bool = True, conf=None,
                     iterations: int = 2, data_dir: str = None,
                     skew: float = 0.0):
    """One REAL TPC query end-to-end through the engine (round-5
    verdict: the driver-visible bench must capture a full query whose
    number moves with engine work, not only the q5lite microbench).
    Reports wall, dispatch split, measured on-device seconds, spill
    traffic, and the CPU-oracle comparison — the reference's per-query
    JSON record shape (docs/benchmarks.md:26-169,
    BenchmarkRunner.scala)."""
    from spark_rapids_tpu.benchmarks.runner import BenchmarkRunner

    family = benchmark.split("_")[0]
    # skewed data lands in its own dir: the marker protocol allows one
    # dataset per dir, and a skewed run must not poison uniform reruns
    default_dir = os.path.join(
        "/tmp", f"srt_bench_{family}" + (f"_skew{skew}" if skew else ""))
    r = BenchmarkRunner(data_dir or default_dir, sf, conf=conf,
                        skew=skew)
    warmed = _service_warmup(r, benchmark) if warmup_service else None
    res = r.run(benchmark, iterations=iterations, warmup=1,
                compare=True)
    wall = res["min_time_sec"]
    dt = res.get("dispatch_telemetry", {})
    cmp_ = res.get("compare", {})
    cpu_s = cmp_.get("cpu_time_sec", 0.0)
    mem = res.get("memory", {})
    return {
        "benchmark": benchmark,
        "sf": sf,
        # backend identity: which device actually produced these
        # numbers (platform, kind, count) plus the measured per-dispatch
        # floor — the JSON alone must say what ran it
        "backend": res.get("env"),
        "wall_s": round(wall, 3),
        "dispatch_count": dt.get("dispatch_count"),
        # stage-cut attribution: measured round trips per pipeline
        # stage (the whole-plan coalescing target is ~1 per stage)
        "per_stage_dispatch": dt.get("per_stage"),
        # the named complement: WHICH programs each stage launched, so
        # a regression in fusion shows up as a program-name diff rather
        # than a bare count bump (round-7)
        "per_stage_programs": dt.get("per_stage_programs"),
        # mesh-requested shuffles that stayed on the host/TCP path,
        # with the spmd gate's reason (empty = all folded in-program)
        "shuffle_fallbacks": dt.get("shuffle_fallbacks"),
        # every AQE replan the run made (skew splits/salting, strategy
        # switches, re-bucketing) with counts; empty = static plan ran
        "replan_events": res.get("replan_events"),
        "io_scan": res.get("io_scan"),
        # generator provenance: a skewed record names its distribution
        # so the JSON alone says what data produced these numbers
        "skew_params": {
            "skew": skew,
            "distribution": f"zipf(s=2, ranks={_skew_ranks()})",
            "hot_key_fraction": skew,
            "table": "lineitem", "column": "l_orderkey",
        } if skew else None,
        "rtt_share": round(
            min(dt.get("est_dispatch_overhead_s", 0.0) / wall, 1.0), 3)
        if wall else None,
        "cpu_oracle_s": round(cpu_s, 3),
        "vs_cpu_oracle": round(cpu_s / wall, 3) if wall else None,
        "matches_cpu": cmp_.get("matches_cpu"),
        # spill-tier traffic over the run (deltas) + the enforced
        # budget: nonzero spilled_* here is the proof an sf>=1 run
        # exercised the out-of-core chain on real query data
        "spilled_device_bytes": mem.get("spilled_device_bytes"),
        "spilled_host_bytes": mem.get("spilled_host_bytes"),
        "device_budget": mem.get("device_budget"),
        "warmup": warmed,
    }


def _skew_ranks() -> int:
    from spark_rapids_tpu.benchmarks import datagen

    return datagen.SKEW_RANKS


def _scale_main():
    """``python bench.py --query tpch_q1 --sf 1 [--device-budget N]``:
    one full query at scale, printed as a single JSON line. This is the
    sf >= 1 measurement path (CPU-oracle crossover, spill engagement);
    the flagless invocation keeps the driver's q5lite + q26 round
    unchanged. ``--device-budget`` bounds the spill catalog (bytes) so
    a large-sf run models a device whose HBM the working set exceeds —
    the recorded JSON carries the budget so the spill counters are
    interpretable."""
    from spark_rapids_tpu.utils import dispatch as disp

    disp.install()
    from spark_rapids_tpu.utils import progcache

    progcache.install()

    def arg(name, default=None, cast=str):
        if name in sys.argv:
            return cast(sys.argv[sys.argv.index(name) + 1])
        return default

    benchmark = arg("--query")
    sf = arg("--sf", 1.0, float)
    budget = arg("--device-budget", 0, int)
    iters = arg("--iterations", 2, int)
    skew = arg("--skew", 0.0, float)

    def _conf_value(v: str):
        if v.lower() in ("true", "false"):
            return v.lower() == "true"
        for cast in (int, float):
            try:
                return cast(v)
            except ValueError:
                pass
        return v

    # repeatable --conf key=value passthrough (session knobs only —
    # e.g. forcing adaptive skew thresholds for a skewed-join record)
    overrides = {}
    for i, a in enumerate(sys.argv):
        if a == "--conf" and i + 1 < len(sys.argv):
            k, _, v = sys.argv[i + 1].partition("=")
            overrides[k] = _conf_value(v)
    conf = None
    if budget or overrides:
        from spark_rapids_tpu import config as cfg
        from spark_rapids_tpu.config import RapidsConf
        from spark_rapids_tpu.runtime import device as rt

        conf_d = dict(overrides)
        if budget:
            conf_d[cfg.DEVICE_BUDGET.key] = budget
        conf = RapidsConf(conf_d)
        if budget:
            rt.initialize(conf)  # budgeted spill catalog
    full = bench_full_query(benchmark, sf=sf,
                            warmup_service="--no-warmup" not in sys.argv,
                            conf=conf, iterations=iters,
                            data_dir=arg("--data-dir"), skew=skew)
    print(json.dumps({"metric": "full_query_scale", "full_query": full}))


def main():
    # telemetry wraps jax.jit; must precede every compute-module import
    from spark_rapids_tpu.utils import dispatch as disp

    disp.install()
    # persist every executable compiled below (utils/progcache's
    # directory rule) — a repeated bench run starts hot even in a
    # fresh process
    from spark_rapids_tpu.utils import progcache

    progcache.install()
    keys, key_valid, vals = gen_data()
    tpu_dt, tpu_out = bench_tpu(keys, key_valid, vals)
    cpu_dt, cpu_out = bench_cpu(keys, key_valid, vals)
    # --warmup is default-on (PR 7 ladder: first real query starts
    # hot); --no-warmup opts out for cold-compile measurements
    full = bench_full_query(
        warmup_service="--no-warmup" not in sys.argv)

    # cross-check: group count and total sum must agree
    import jax

    ng = int(jax.device_get(tpu_out[4]))
    tpu_sum = float(np.asarray(jax.device_get(tpu_out[1]))[:ng].sum())
    cpu_sum = float(cpu_out["s"].sum())
    assert ng == len(cpu_out), (ng, len(cpu_out))
    assert abs(tpu_sum - cpu_sum) / max(abs(cpu_sum), 1) < 1e-9

    rows_per_sec = N_ROWS / tpu_dt
    speedup = cpu_dt / tpu_dt
    print(json.dumps({
        "metric": "q5lite_filter_groupby_rows_per_sec",
        "value": round(rows_per_sec, 1),
        "unit": "rows/s",
        "vs_baseline": round(speedup, 3),
        "full_query": full,
    }))


if __name__ == "__main__":
    if "--query" in sys.argv:
        _scale_main()
    else:
        main()
