"""The readings a limit of ``correct`` is set from, in one process.

    python benchmark/readings.py --workload <cell> --seeds 1,2,3,... \
        --control-seeds 1,2,3 [--seconds S] [--out file.json]

For every seed: the cell as ``run.py`` runs it (same set-up, same loop, a
short window), and the numbers its frames were compared by: the lower
reading of a limit is the largest of them over a dozen seeds. For every
control seed: the same numbers with the plain reference computed in
float32 (the precision below the float64 the configurations state) put in
the engine's place: the upper reading is the smallest of them. One process,
one session after the other, because every process pays the start-up and
the cache reads again. Needs the chip unless ``--rehearse SF`` is given.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--rehearse", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    spec = run.load_cell(args.workload)

    import jax
    import numpy as np

    if args.rehearse is None and (
            jax.devices()[0].platform != "tpu"
            or len(jax.devices()) != spec["cell"]["chips"]):
        run.log(f"readings.py: needs {spec['cell']['chips']} TPU chip(s), "
                f"JAX sees {jax.devices()}")
        return 1
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    report = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        result, info = run.run_cell(spec, seed, args.seconds, False,
                                    args.rehearse)
        rec = {"seed": seed, "correct": result["correct"],
               "program": {k: v["value"]
                           for k, v in result["compared"].items()},
               "metrics": {k: v["value"]
                           for k, v in result["metrics"].items()},
               "prepare_s": info["prepare_s"], "data_s": info["data_s"],
               "first_call_s": info["first_call_s"],
               "reference_s": info["reference_s"],
               "memory_peak_bytes": info["memory_peak_bytes"]}
        if seed in controls:
            tables, _, _ = run.ensure_data(spec, seed, args.rehearse)
            ok, compared, _ = run.judge(spec, tables, None,
                                        dtype=np.float32)
            rec["control_float32"] = {k: v["value"]
                                      for k, v in compared.items()}
            rec["control_correct"] = ok
        report.append(rec)
        print(json.dumps(rec), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
