"""Run a cell several times and say how widely its metrics spread.

    python benchmark/sets.py --workload <cell> --seeds 11,12,13,14,15,16 \
        --sets 2 [--seconds S] [--trace-seeds 11] [--out file.json]

The way the bounds of ``BENCHMARK.json`` were measured: each set runs the
cell once a seed, the same seeds in every set; a metric's spread in a set
is the distance between its first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median; the
bound is about five times the widest spread over the cells. This parent
never imports JAX: every run is a child that owns the chip alone, one
after the other. ``--trace-seeds`` adds ``--trace 1`` runs after the sets.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def one_run(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)]
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    rec = {"seed": seed, "trace": trace, "rc": p.returncode,
           "stderr_tail": p.stderr.strip().splitlines()[-8:]}
    if p.returncode == 0 and lines:
        rec["result"] = json.loads(lines[-1])
        rec["info"] = json.loads(lines[-2]) if len(lines) > 1 else None
    return rec


def spread(values: list):
    """(median, (q3 - q1) / median)."""
    if len(values) < 2:
        return (values[0] if values else None), None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return med, (q3 - q1) / med


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",")]
    report = {"workload": args.workload, "seconds": seconds, "sets": [],
              "traced": []}
    for si in range(args.sets):
        runs = [one_run(args.workload, s, seconds, 0) for s in seeds]
        summary = {}
        good = [r["result"] for r in runs if "result" in r]
        for name in sorted({m for r in good for m in r["metrics"]}):
            values = [r["metrics"][name]["value"] for r in good
                      if name in r["metrics"]]
            med, spr = spread(values)
            summary[name] = {"median": med, "spread": spr, "values": values}
        summary["correct"] = [r.get("result", {}).get("correct")
                              for r in runs]
        report["sets"].append({"runs": runs, "summary": summary})
        print(json.dumps({"set": si, **{k: (v if k == "correct" else {
            "median": v["median"], "spread": v["spread"]})
            for k, v in summary.items()}}), flush=True)
    for s in [int(x) for x in args.trace_seeds.split(",") if x]:
        r = one_run(args.workload, s, seconds, 1)
        report["traced"].append(r)
        print(json.dumps({"traced": s, "rc": r["rc"],
                          "result": r.get("result")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    bad = [r for st in report["sets"] for r in st["runs"]
           if not r.get("result", {}).get("correct")]
    bad += [r for r in report["traced"]
            if not r.get("result", {}).get("correct")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
