"""AQE skew fence: a deliberately skewed join must stay within 1.5x
the uniform-data wall clock, match the CPU oracle bit for bit, and
leave a nonzero replan-event trail — otherwise the adaptive layer has
silently stopped replanning (or started corrupting).

Two scenarios:

A. **Host-path skewed join** — tpch q12 (orders x lineitem on
   l_orderkey) twice: uniform data, then data with half of lineitem on
   one hot key (``--skew 0.5``). The skewed run must produce skew
   replan events (the fence lowers the skew cut so the detection
   triggers at the chosen sf) and hold the wall-clock ratio.
B. **In-program salting** — a direct exchange-layer check on the
   8-virtual-device CPU mesh: a hot hash partition is salted across
   devices before the ``all_to_all`` and per-partition content stays
   bit-equal to the host path.

    python scripts/aqe_check.py [--sf 1.0] [--skew 0.5]
                                [--query tpch_q12]
                                [--data-dir /tmp/srt_aqe]
                                [--output AQE.json]

Prints one JSON report; exit code 0 = fence holds.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# scenario B needs a multi-device mesh before jax initializes
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

# telemetry must wrap jax.jit before any compute module import
from spark_rapids_tpu.utils import dispatch as disp  # noqa: E402

disp.install()

#: wall-clock gate: skewed wall <= RATIO * uniform wall + SLACK_S
#: (the slack absorbs compile/IO jitter at small sf where both walls
#: are fractions of a second)
RATIO = 1.5
SLACK_S = 2.0


def _aqe_conf(sf: float):
    """Skew thresholds scaled so detection triggers at this sf: the
    hot partition at --skew 0.5 carries ~half the shuffled bytes, so a
    cut at ~1/8 of the uniform partition's natural size flags it and
    nothing else at factor 2."""
    from spark_rapids_tpu.config import RapidsConf

    cut = max(int(sf * 64 * 1024), 1024)
    return RapidsConf({
        "rapids.tpu.sql.adaptive.skewJoin."
        "skewedPartitionThresholdInBytes": cut,
        "rapids.tpu.sql.adaptive.skewJoin.skewedPartitionFactor": 2.0,
        # advisory at the cut: a partition past the skew cut is then
        # always alone in its coalesced group, i.e. splittable
        "rapids.tpu.sql.adaptive.advisoryPartitionSizeBytes": cut,
        # the skewed join is the scenario under test: keep the build
        # side off the (static or measured) broadcast shortcut
        "rapids.tpu.sql.autoBroadcastJoinThreshold": 0,
        # keep sf-1 scans multi-partition (the default 256 MiB reader
        # packing folds q12's 5-column lineitem scan into ONE split,
        # which erases the exchanges AQE replans over)
        "rapids.tpu.sql.reader.batchSizeBytes":
            max(int(sf * 32) << 20, 1 << 20),
    })


def run_join(query: str, sf: float, data_dir: str, skew: float) -> dict:
    from spark_rapids_tpu.benchmarks.runner import (ALL_BENCHMARKS,
                                                    BenchmarkRunner)
    from spark_rapids_tpu.execs import adaptive
    from spark_rapids_tpu.execs.base import collect
    from spark_rapids_tpu.plan.overrides import apply_overrides

    r = BenchmarkRunner(data_dir, sf, conf=_aqe_conf(sf), skew=skew)
    r.ensure_data("tpch")
    # warm run traces + compiles; the fence times the steady state
    collect(apply_overrides(ALL_BENCHMARKS[query](data_dir), r.conf))
    pre = adaptive.replan_snapshot()
    t0 = time.perf_counter()
    df = collect(apply_overrides(ALL_BENCHMARKS[query](data_dir),
                                 r.conf))
    wall = time.perf_counter() - t0
    events = adaptive.replan_delta(pre)
    cmp_ = r.compare_results(query, df)
    return {
        "skew": skew,
        "wall_s": round(wall, 3),
        "replan_events": events,
        "matches_cpu": cmp_["matches_cpu"],
        "detail": cmp_.get("detail", ""),
    }


def check_salting() -> dict:
    """Scenario B: in-program salted exchange == host path, with a
    skew_salt replan event."""
    import numpy as np

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.execs import adaptive
    from spark_rapids_tpu.execs.base import TpuExec
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.parallel.mesh import data_mesh
    from spark_rapids_tpu.parallel.spmd import SkewSpec

    rng = np.random.default_rng(7)
    parts = []
    for _ in range(4):
        keys = rng.integers(0, 40, 2000).astype(np.int64)
        keys[rng.random(2000) < 0.7] = 11  # hot key
        parts.append((keys, rng.random(2000)))

    class _Rows(TpuExec):
        def __init__(self):
            super().__init__([], Schema(["k", "v"],
                                        [dt.INT64, dt.FLOAT64]))

        @property
        def num_partitions(self):
            return len(parts)

        def execute(self, partition=0):
            keys, vals = parts[partition]
            yield ColumnarBatch(
                [Column.from_numpy(keys, dt.INT64),
                 Column.from_numpy(vals, dt.FLOAT64)], len(keys))

    def drain(ex):
        out = {}
        for p in range(ex.num_out_partitions):
            rows = []
            for b in ex.execute(p):
                pdf = b.to_pandas()
                rows += [(int(r.iloc[0]), float(r.iloc[1]))
                         for _, r in pdf.iterrows()]
            out[p] = sorted(rows)
        return out

    num_out = 5
    want = drain(ShuffleExchangeExec(("hash", [0]), num_out, _Rows()))
    pre = adaptive.replan_snapshot()
    prog = ShuffleExchangeExec(("hash", [0]), num_out, _Rows())
    prog.enable_in_program(data_mesh(8),
                           skew=SkewSpec(factor=2.0, threshold=1024,
                                         max_splits=8))
    got = drain(prog)
    events = adaptive.replan_delta(pre)
    salted = any(k.startswith("skew_salt") for k in events)
    equal = bool(prog.in_program) and all(
        got[p] == want[p] for p in range(num_out))
    return {"in_program": bool(prog.in_program),
            "content_equal": equal, "replan_events": events,
            "ok": salted and equal}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--sf", type=float, default=1.0)
    p.add_argument("--skew", type=float, default=0.5)
    p.add_argument("--query", default="tpch_q12")
    p.add_argument("--data-dir", default="/tmp/srt_aqe")
    p.add_argument("--output", default=None)
    args = p.parse_args(argv)

    uniform = run_join(args.query, args.sf, args.data_dir, 0.0)
    skewed = run_join(args.query, args.sf,
                      args.data_dir + f"_skew{args.skew}", args.skew)

    wall_ok = skewed["wall_s"] <= RATIO * uniform["wall_s"] + SLACK_S
    replanned = any(k.startswith(("skew_split", "skew_salt"))
                    for k in skewed["replan_events"])
    salt = check_salting()
    ok = bool(wall_ok and replanned and skewed["matches_cpu"] and
              uniform["matches_cpu"] and salt["ok"])
    report = {
        "fence": "aqe_check", "sf": args.sf, "query": args.query,
        "ok": ok,
        "wall_ratio": round(skewed["wall_s"] /
                            max(uniform["wall_s"], 1e-9), 3),
        "wall_ratio_limit": RATIO,
        "wall_ok": wall_ok,
        "skew_replanned": replanned,
        "uniform": uniform,
        "skewed": skewed,
        "salting": salt,
    }
    text = json.dumps(report, indent=1)
    print(text)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
