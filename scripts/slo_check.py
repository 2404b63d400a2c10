"""Sustained-QPS SLO fence for the cross-tenant serving layer (CLI twin
of the fast smoke in tests/test_service.py / tests/test_batching.py).

ROADMAP item 4 fence: at N=64 concurrent q1/q6 instances the p99
queue+run latency must stay within 3x the SERIAL single-query time.
The criterion is RATIO-based (p99 / measured serial reference), never
an absolute seconds threshold, so it is meaningful on CPU CI and on a
chip alike.

Two measurements, one warmed service (shape-bucketed executables +
micro-batching enabled):

  1. open-loop : Poisson arrivals at a rate CALIBRATED from the
                 measured serial time (``--load-factor`` x the
                 interleaving capacity), the regime an SLO is defined
                 over — asserts the p99 ratio criterion and reports
                 shed rate vs offered QPS.
  2. burst     : all N submitted at once (closed loop) — reported for
                 context (queue depth dominates), not asserted.

Also asserts the sharing fence the batching layer exists for: across
the whole run, same-template queries must hit the shared program cache
(cross-tenant hit rate) rather than re-compiling per tenant.

    python scripts/slo_check.py [--queries 64] [--sf 0.01]
                                [--ratio 3.0] [--load-factor 0.5]
                                [--output SLO.json]

Prints one JSON report; exit code 0 = fence holds.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--queries", type=int, default=64)
    p.add_argument("--mix", default="tpch_q1,tpch_q6")
    p.add_argument("--tenants", type=int, default=8)
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--data-dir", default="/tmp/rapids_tpu_slo")
    p.add_argument("--ratio", type=float, default=3.0,
                   help="p99 total latency must be <= ratio x serial "
                        "single-query time at the calibrated rate")
    p.add_argument("--load-factor", type=float, default=0.35,
                   help="offered_qps = load_factor / serial_s — the "
                        "sustained operating point the SLO is "
                        "evaluated at, as a fraction of the device's "
                        "single-stream throughput (1/serial). "
                        "maxConcurrent interleaves queries on ONE "
                        "dispatch path, it does not multiply "
                        "throughput; coalescing is what buys headroom "
                        "above 1.0")
    p.add_argument("--min-hit-rate", type=float, default=0.875,
                   help="cross-tenant progcache hit-rate floor "
                        "(>= 7/8: N same-template queries, <= 1 "
                        "compile per stage bucket)")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--output", default=None)
    args = p.parse_args(argv)

    from spark_rapids_tpu import config as cfg
    from spark_rapids_tpu.benchmarks.runner import (ALL_BENCHMARKS,
                                                    BenchmarkRunner)
    from spark_rapids_tpu.benchmarks.service_bench import (
        _serial_single_query_s, run_service_bench)
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.expressions.compiler import _FUSED_CACHE_STATS
    from spark_rapids_tpu.service import QueryService
    from spark_rapids_tpu.service.batching import slo

    mix = args.mix.split(",")
    conf = RapidsConf({
        cfg.SERVICE_BATCHING_ENABLED.key: True,
        # wider than the dispatch-coalescing default: the fence runs
        # many tiny queries, so a longer hold harvests bigger groups
        cfg.SERVICE_BATCHING_WINDOW_MS.key: 10.0,
        cfg.SERVICE_WARMUP_ENABLED.key: False,  # warmed explicitly
    })
    runner = BenchmarkRunner(args.data_dir, args.sf, conf=conf)
    for name in dict.fromkeys(mix):
        runner.ensure_data(name)
    serial = _serial_single_query_s(runner, mix, args.data_dir)
    serial_s = serial["max_s"]

    service = QueryService(conf)
    for name in dict.fromkeys(mix):
        service.register_template(ALL_BENCHMARKS[name](args.data_dir),
                                  name)
    warmup_report = service.warmup()

    # the sharing fence window opens AFTER warmup: every tenant query
    # from here on should reuse, not compile
    hits0 = dict(_FUSED_CACHE_STATS)

    offered_qps = max(args.load_factor / max(serial_s, 1e-4), 0.5)

    def make_query(i):
        return ALL_BENCHMARKS[mix[i % len(mix)]](args.data_dir)

    open_loop = slo.run_open_loop(service, make_query, offered_qps,
                                  args.queries, tenants=args.tenants,
                                  seed=args.seed)
    stats_open = service.stats()
    service.shutdown()

    hits1 = dict(_FUSED_CACHE_STATS)
    d_hits = hits1["hits"] - hits0["hits"]
    d_misses = hits1["misses"] - hits0["misses"]
    hit_rate = d_hits / (d_hits + d_misses) if d_hits + d_misses \
        else 1.0

    # burst context: fresh service, all N at once (not asserted — a
    # burst's tail latency is queue depth by construction)
    burst = run_service_bench(args.data_dir, args.sf,
                              queries=args.queries, mix=mix,
                              tenants=args.tenants, conf=conf,
                              warmup=False)

    p99 = open_loop["latency_s"]["total"]["p99"]
    p99_ratio = p99 / max(serial_s, 1e-9)
    checks = {
        "slo_p99_within_ratio": {
            "p99_total_s": p99,
            "serial_s": serial_s,
            "p99_over_serial": round(p99_ratio, 3),
            "threshold": args.ratio,
            "at_offered_qps": round(offered_qps, 3),
            "ok": bool(p99_ratio <= args.ratio and
                       open_loop["failed"] == 0),
        },
        "cross_tenant_sharing": {
            "hits": d_hits, "misses": d_misses,
            "hit_rate": round(hit_rate, 4),
            "threshold": args.min_hit_rate,
            "ok": bool(hit_rate >= args.min_hit_rate),
        },
        "open_loop_completed": {
            "done": open_loop["done"], "shed": open_loop["shed"],
            "failed": open_loop["failed"],
            "ok": bool(open_loop["done"] + open_loop["shed"] ==
                       args.queries and open_loop["failed"] == 0),
        },
    }
    report = {
        "benchmark": "slo_check",
        "scale_factor": args.sf,
        "queries": args.queries,
        "mix": mix,
        "serial": serial,
        "warmup": warmup_report,
        "open_loop": open_loop,
        "burst": {
            "wall_time_sec": burst["wall_time_sec"],
            "total_p99_s": burst["total_time_sec"]["p99"],
            "batching": burst["service_stats"]["batching"],
        },
        "batching": stats_open.to_dict()["batching"],
        "checks": checks,
        "ok": all(c["ok"] for c in checks.values()),
    }
    text = json.dumps(report, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)
    if not report["ok"]:
        print("SLO FENCE FAILED", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
