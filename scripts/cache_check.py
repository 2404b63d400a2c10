"""Semantic-cache fence: repeated dashboards must get cheaper, never
wronger (CLI twin of tests/test_cache.py, the service/cache analogue of
scripts/slo_check.py).

The serving claim the cache makes is measured directly: an open-loop
mix of REPEATED query templates over unchanged data runs twice — once
with ``rapids.tpu.service.cache.enabled=false`` (control) and once with
the cache on, same Poisson arrivals, same seed. The fence requires:

  1. **latency**  : cache-on p99 total (queue+run) <= control p99 / 2
  2. **dispatch** : cache-on physical device dispatches <= control / 2
  3. **oracle**   : EVERY served frame — miss, hit, follower — matches
                    the CPU oracle for its template
  4. **staleness**: after a MID-RUN version bump (the backing parquet
                    is rewritten), the next submit returns the NEW
                    oracle, not the cached old frame

Criteria 1-2 are RATIOS against a control measured in the same process
on the same backend, so the fence is meaningful on CPU CI and on a
chip alike.

    python scripts/cache_check.py [--queries 24] [--sf 0.01]
                                  [--output SLO_r02.json]

Prints one JSON report; exit code 0 = fence holds.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _run_phase(service, make_query, oracles, mix, offered_qps, n,
               tenants, seed, disp):
    """Open loop over repeated templates; unlike slo.run_open_loop this
    drains every FRAME and oracle-matches it (the stock harness only
    keeps latency stats)."""
    from spark_rapids_tpu.benchmarks.runner import _frames_match
    from spark_rapids_tpu.service.batching import slo

    gaps = slo.poisson_gaps(offered_qps, n, seed=seed)
    pre = disp.snapshot()
    handles = []
    shed = failed = 0
    t0 = time.perf_counter()
    next_at = t0
    for i, gap in enumerate(gaps):
        next_at += gap
        delay = next_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            handles.append((i, service.submit(
                make_query(i), tenant=f"tenant{i % max(tenants, 1)}")))
        except Exception:
            shed += 1
    totals, mismatches = [], []
    for i, h in handles:
        try:
            frame = h.result(timeout=600)
        except Exception as e:
            failed += 1
            mismatches.append(f"q{i} failed: {e}")
            continue
        info = h.info()
        totals.append((info["queue_time_s"] or 0.0) +
                      (info["run_time_s"] or 0.0))
        ok, msg = _frames_match(oracles[mix[i % len(mix)]], frame)
        if not ok:
            mismatches.append(f"q{i} ({mix[i % len(mix)]}): {msg}")
    delta = disp.delta(pre)
    return {
        "done": len(totals), "shed": shed, "failed": failed,
        "wall_s": round(time.perf_counter() - t0, 4),
        "p50_s": round(slo.percentile(totals, 50), 4),
        "p99_s": round(slo.percentile(totals, 99), 4),
        "dispatch_count": delta["dispatch_count"],
        "oracle_mismatches": mismatches,
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    # enough repeats that the per-template cold misses (2 here) fall
    # below the nearest-rank p99 of the cached phase
    p.add_argument("--queries", type=int, default=240)
    p.add_argument("--mix", default="tpch_q1,tpch_q6")
    p.add_argument("--tenants", type=int, default=4)
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--data-dir", default="/tmp/rapids_tpu_cache_check")
    p.add_argument("--min-speedup", type=float, default=2.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--output", default=None)
    args = p.parse_args(argv)

    # telemetry wraps jax.jit; must precede every compute-module import
    from spark_rapids_tpu.utils import dispatch as disp

    disp.install()

    import pandas as pd

    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.benchmarks.runner import (ALL_BENCHMARKS,
                                                    BenchmarkRunner)
    from spark_rapids_tpu.benchmarks.runner import _frames_match
    from spark_rapids_tpu.cpu.engine import execute_cpu
    from spark_rapids_tpu.execs.base import collect
    from spark_rapids_tpu.plan.overrides import apply_overrides
    from spark_rapids_tpu.service import QueryService

    mix = args.mix.split(",")
    runner = BenchmarkRunner(args.data_dir, args.sf)
    for name in dict.fromkeys(mix):
        runner.ensure_data(name)

    def fresh_plan(name):
        return ALL_BENCHMARKS[name](args.data_dir)

    oracles = {name: execute_cpu(fresh_plan(name)).to_pandas()
               for name in dict.fromkeys(mix)}

    # warm the process-global compile caches so the control phase
    # measures steady-state recompute, not first-compile — inflating
    # the control would make the fence trivially (and dishonestly)
    # pass. The SECOND run's time (compiles already warm) sets the
    # offered rate.
    serial_s = 0.0
    for name in dict.fromkeys(mix):
        collect(apply_overrides(fresh_plan(name), runner.conf))
        t0 = time.perf_counter()
        collect(apply_overrides(fresh_plan(name), runner.conf))
        serial_s = max(serial_s, time.perf_counter() - t0)
    offered_qps = min(max(0.35 / max(serial_s, 1e-4), 0.5), 24.0)

    def make_query(i):
        return fresh_plan(mix[i % len(mix)])

    # -- phase A: control, cache off ----------------------------------
    svc_off = QueryService({cfg.SERVICE_CACHE_ENABLED.key: False})
    control = _run_phase(svc_off, make_query, oracles, mix,
                         offered_qps, args.queries, args.tenants,
                         args.seed, disp)
    svc_off.shutdown()

    # -- phase B: cache on, same arrivals -----------------------------
    svc = QueryService()
    cached = _run_phase(svc, make_query, oracles, mix, offered_qps,
                        args.queries, args.tenants, args.seed, disp)
    cache_stats = svc.stats().to_dict()["cache"]

    # -- phase C: mid-run version bump must not serve stale -----------
    # rewrite one lineitem part (both q1 and q6 read the table) with
    # fewer rows: a different answer is guaranteed, and the file's
    # (mtime_ns, size) snapshot version changes with it
    li = os.path.join(args.data_dir, "lineitem", "part-000.parquet")
    frame = pd.read_parquet(li)
    frame.iloc[:max(len(frame) - max(len(frame) // 10, 1), 1)] \
        .to_parquet(li)
    os.utime(li, ns=(time.time_ns(), time.time_ns()))
    bump_name = mix[0]
    new_oracle = execute_cpu(fresh_plan(bump_name)).to_pandas()
    stale_would_differ, _ = _frames_match(oracles[bump_name],
                                          new_oracle)
    got = svc.submit(fresh_plan(bump_name)).result(timeout=600)
    fresh_ok, fresh_msg = _frames_match(new_oracle, got)
    svc.shutdown()

    p99_ratio = control["p99_s"] / max(cached["p99_s"], 1e-6)
    disp_ratio = control["dispatch_count"] / \
        max(cached["dispatch_count"], 1)
    checks = {
        "p99_speedup": {
            "control_p99_s": control["p99_s"],
            "cached_p99_s": cached["p99_s"],
            "ratio": round(p99_ratio, 3),
            "threshold": args.min_speedup,
            "ok": bool(p99_ratio >= args.min_speedup),
        },
        "dispatch_drop": {
            "control_dispatches": control["dispatch_count"],
            "cached_dispatches": cached["dispatch_count"],
            "ratio": round(disp_ratio, 3),
            "threshold": args.min_speedup,
            "ok": bool(disp_ratio >= args.min_speedup),
        },
        "oracle_matched": {
            "control_mismatches": control["oracle_mismatches"],
            "cached_mismatches": cached["oracle_mismatches"],
            "ok": bool(not control["oracle_mismatches"] and
                       not cached["oracle_mismatches"] and
                       control["failed"] == 0 and
                       cached["failed"] == 0),
        },
        "version_bump_not_stale": {
            # guard the guard: the mutation must actually change the
            # answer, else "fresh" and "stale" are indistinguishable
            "mutation_changed_answer": bool(not stale_would_differ),
            "served_fresh": fresh_ok,
            "detail": None if fresh_ok else fresh_msg,
            "ok": bool(fresh_ok and not stale_would_differ),
        },
    }
    report = {
        "benchmark": "cache_check",
        "scale_factor": args.sf,
        "queries": args.queries,
        "mix": mix,
        "offered_qps": round(offered_qps, 3),
        "control": control,
        "cached": cached,
        "cache_stats": cache_stats,
        "checks": checks,
        "ok": all(c["ok"] for c in checks.values()),
    }
    text = json.dumps(report, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)
    if not report["ok"]:
        print("CACHE FENCE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
