"""BenchmarkRunner CLI (the reference's BenchmarkRunner.scala + BenchUtils:
run a named query N times, capture env/plan/timings as JSON, optionally
verify TPU results against the CPU oracle — docs/benchmarks.md:26-190).

    python -m spark_rapids_tpu.benchmarks.runner \
        --benchmark tpch_q1 --sf 0.01 --iterations 3 --compare \
        --data-dir /tmp/tpch --output q1.json
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
from typing import Optional

# dispatch telemetry must wrap jax.jit BEFORE the compute modules
# import (module-level @jit decorators capture the binding) — hence
# this pre-parse ahead of the framework imports below
if "--dispatch-telemetry" in sys.argv:  # pragma: no cover - CLI path
    from spark_rapids_tpu.utils import dispatch as _dispatch

    _dispatch.install()

from spark_rapids_tpu.benchmarks import (datagen, mortgage, tpcds, tpch,
                                         tpcxbb)
from spark_rapids_tpu.config import RapidsConf

ALL_BENCHMARKS = dict(tpch.QUERIES)
ALL_BENCHMARKS.update(tpcds.QUERIES)
ALL_BENCHMARKS.update(tpcxbb.QUERIES)
ALL_BENCHMARKS["mortgage_etl"] = mortgage.etl


class BenchmarkRunner:
    def __init__(self, data_dir: str, sf: float,
                 conf: Optional[RapidsConf] = None, skew: float = 0.0):
        self.data_dir = data_dir
        self.sf = sf
        self.conf = conf or RapidsConf()
        # hot-key fraction for the skewed generator (tpch lineitem
        # only); 0.0 keeps the uniform data AND the uniform marker name
        self.skew = skew

    def ensure_data(self, benchmark: str = "tpch") -> None:
        if benchmark.startswith("mortgage"):
            family = "mortgage"
        elif benchmark.startswith("tpcds"):
            family = "tpcds"
        elif benchmark.startswith("tpcxbb"):
            family = "tpcxbb"
        else:
            family = "tpch"
        suffix = f"-skew-{self.skew}" if self.skew else ""
        marker = os.path.join(self.data_dir,
                              f".{family}-sf-{self.sf}{suffix}")
        if os.path.exists(marker):
            return
        os.makedirs(self.data_dir, exist_ok=True)
        # a dir holds exactly one scale factor per family: drop stale
        # markers so a later run at the old sf regenerates instead of
        # silently reading this sf's tables under the old label
        for stale in glob.glob(
                os.path.join(self.data_dir, f".{family}-sf-*")):
            os.remove(stale)
        if family == "mortgage":
            mortgage.gen_tables(self.data_dir, self.sf)
        elif family == "tpcds":
            tpcds.write_tables(self.data_dir, self.sf)
        elif family == "tpcxbb":
            tpcxbb.write_tables(self.data_dir, self.sf)
        else:
            datagen.write_tables(self.data_dir, self.sf,
                                 skew=self.skew)
        with open(marker, "w") as f:
            f.write("ok")

    @staticmethod
    def _env() -> dict:
        import jax

        import spark_rapids_tpu
        from spark_rapids_tpu.utils import dispatch as _disp

        # measured, not assumed: the fixed per-dispatch floor of the
        # backend that produced the record
        rtt = round(_disp.measure_rtt(), 6)
        return {
            "framework_version": getattr(spark_rapids_tpu, "__version__",
                                         "dev"),
            "jax_version": jax.__version__,
            "backend": jax.devices()[0].platform,
            "device_count": len(jax.devices()),
            "device_kind": jax.devices()[0].device_kind,
            "rtt_probe_s": rtt,
        }

    def run(self, benchmark: str, iterations: int = 3,
            compare: bool = False, warmup: int = 1) -> dict:
        from spark_rapids_tpu.execs.base import collect
        from spark_rapids_tpu.plan.overrides import apply_overrides

        self.ensure_data(benchmark)
        plan_fn = ALL_BENCHMARKS[benchmark]
        result: dict = {
            "benchmark": benchmark,
            "scale_factor": self.sf,
            "env": self._env(),
            "iterations": [],
        }
        from spark_rapids_tpu.memory import fault_injection as _fi
        from spark_rapids_tpu.memory import retry as _retry
        from spark_rapids_tpu.memory.catalog import get_catalog
        from spark_rapids_tpu.execs import adaptive
        from spark_rapids_tpu.io import scanpipe
        from spark_rapids_tpu.utils import dispatch as disp

        from spark_rapids_tpu.parallel import spmd

        from spark_rapids_tpu.parallel import mesh as pmesh

        telemetry = disp.installed()
        df = None
        pre_stage = None
        pre_prog = None
        # fallback telemetry covers the WHOLE run (planning records the
        # reasons, and planning happens inside the iteration loop)
        run_pre_fb = spmd.fallback_snapshot()
        # mesh-construction fallbacks (device clamp, dropped model axis)
        # and ICI-vs-DCN seam decisions over the same window
        run_pre_mesh_fb = pmesh.mesh_fallback_snapshot()
        run_pre_seam = spmd.seam_snapshot()
        # AQE replan events over the whole run (execs.adaptive's counters)
        run_pre_replan = adaptive.replan_snapshot()
        # scan-pipeline activity over the run (io/scanpipe counters:
        # bytes read vs pruned, decode/h2d seconds, overlap fraction)
        run_pre_scan = scanpipe.snapshot()
        # run-relative snapshots: totals, per-site map, catalog spill
        # counters and injector counts all report DELTAS over this run
        # — a second benchmark in the same process must not inherit the
        # first one's OOM activity in its report
        run_pre_retry = _retry.snapshot()
        run_pre_sites = _retry.stats()["per_site"]
        from spark_rapids_tpu.service.streaming import stats as _sstats

        run_pre_stream = _sstats.snapshot()
        from spark_rapids_tpu.runtime import recovery as _recovery

        run_pre_recovery = _recovery.snapshot()
        cat = get_catalog()
        pre_spill_dev = cat.spilled_device_bytes
        pre_spill_host = cat.spilled_host_bytes
        pre_inj = _fi.get_injector().stats()
        for i in range(warmup + iterations):
            plan = plan_fn(self.data_dir)  # fresh plan: no cached blocks
            exec_ = apply_overrides(plan, self.conf)
            pre = disp.snapshot() if telemetry else None
            pre_stage = disp.stage_snapshot() if telemetry else None
            pre_prog = disp.stage_programs_snapshot() if telemetry \
                else None
            pre_retry = _retry.snapshot()
            t0 = time.perf_counter()
            df = collect(exec_)
            elapsed = time.perf_counter() - t0
            if i >= warmup:
                it_rec = {"time_sec": elapsed,
                          "oom_retry": _retry.delta(pre_retry)}
                if telemetry:
                    it_rec["dispatch"] = disp.delta(pre)
                result["iterations"].append(it_rec)
        # OOM-resilience accounting across the whole run: the retry
        # ladder's per-site counters plus the spill catalog's tier
        # traffic — nonzero numbers here are the proof an over-budget
        # or fault-injected run actually exercised the machinery
        run_retry = _retry.delta(run_pre_retry)
        run_retry["per_site"] = _retry.site_delta(run_pre_sites)
        inj = _fi.get_injector().stats()
        result["memory"] = {
            "oom_retry": run_retry,
            "spilled_device_bytes": cat.spilled_device_bytes -
            pre_spill_dev,
            "spilled_host_bytes": cat.spilled_host_bytes -
            pre_spill_host,
            "device_budget": cat.device_budget,
            "fault_injection": {
                "armed": inj["armed"],
                "calls": inj["calls"] - pre_inj["calls"],
                "injections": inj["injections"] - pre_inj["injections"],
            },
        }
        # streaming ingestion activity during the run (zeros for pure
        # batch benchmarks; a dashboard-replay harness that appends
        # micro-batches between iterations shows its folds here)
        result["streaming"] = _sstats.delta(run_pre_stream)
        # lineage fault recovery during the run (zeros on a healthy
        # cluster; a chaos run shows its re-run maps and respawns here)
        result["recovery"] = _recovery.delta(run_pre_recovery)
        # every AQE replan this run made (skew splits/salting, strategy
        # switches, re-bucketing), with counts — zeros/empty when the
        # static plan ran unchanged
        result["replan_events"] = adaptive.replan_delta(run_pre_replan)
        # ingest telemetry: how much the scan layer read, what pruning
        # saved, and how much of the read+pack hid behind compute
        result["io_scan"] = scanpipe.delta(run_pre_scan)
        if telemetry and result["iterations"]:
            # the BASELINE.md-promised split: dispatch_count x RTT vs
            # time actually spent computing on the device
            from spark_rapids_tpu.plan.optimizer import cut_stages
            from spark_rapids_tpu.utils import progcache

            rtt = disp.measure_rtt()
            last = result["iterations"][-1]
            count = last["dispatch"]["dispatch_count"]
            result["dispatch_telemetry"] = {
                "executable_count": disp.executable_count(),
                "dispatch_count": count,
                "dispatch_rtt_s": round(rtt, 4),
                "est_dispatch_overhead_s": round(count * rtt, 3),
                "est_on_device_s": round(
                    max(last["time_sec"] - count * rtt, 0.0), 3),
                # measured per-stage round trips of the LAST iteration,
                # next to the plan's static per-stage estimate — the
                # split that shows WHERE the dispatch budget sits
                "per_stage": disp.stage_delta(pre_stage),
                # which PROGRAMS each stage launched (round-7: names
                # the six dispatches a bare "stage0: 6" hides)
                "per_stage_programs": disp.stage_program_delta(pre_prog),
                "stages": [
                    {"stage": s["stage"],
                     "ops": "+".join(s["ops"]),
                     "est_dispatches": s["est_dispatches"],
                     "mesh_internal": s["mesh_internal"]}
                    for s in cut_stages(exec_)],
                # every mesh-requested shuffle that stayed on the
                # host/TCP path this run, with the gate's reason
                "shuffle_fallbacks": spmd.fallback_delta(run_pre_fb),
                # mesh construction that downgraded the conf's request
                # (device clamp, dropped model axis) — the silent-clamp
                # fix: a too-big rapids.tpu.mesh.devices shows up here
                "mesh_fallbacks": pmesh.mesh_fallback_delta(
                    run_pre_mesh_fb),
                # which seam (intra-host ICI vs cross-host DCN) carried
                # each shuffle decision this run
                "seam_decisions": spmd.seam_delta(run_pre_seam),
                "replan_events": adaptive.replan_delta(run_pre_replan),
                "compile_cache": progcache.stats(),
            }
        result["query_plan"] = exec_.tree_string()
        result["metrics"] = {
            name: {"rows": m.num_output_rows,
                   "batches": m.num_output_batches,
                   "op_time_ms": m.op_time_ns / 1e6}
            for name, m in exec_.all_metrics().items()}
        times = [it["time_sec"] for it in result["iterations"]]
        result["min_time_sec"] = min(times)
        result["rows_returned"] = len(df)
        if compare:
            result["compare"] = self.compare_results(benchmark, df)
        return result

    def compare_results(self, benchmark: str, tpu_df) -> dict:
        """BenchUtils.compareResults: run the CPU oracle and diff."""
        from spark_rapids_tpu.cpu.engine import execute_cpu

        plan = ALL_BENCHMARKS[benchmark](self.data_dir)
        t0 = time.perf_counter()
        cpu_df = execute_cpu(plan).to_pandas()
        cpu_time = time.perf_counter() - t0
        ok, reason = _frames_match(cpu_df, tpu_df)
        return {"matches_cpu": ok, "cpu_time_sec": cpu_time,
                "detail": reason}


def _frames_match(cpu_df, tpu_df) -> "tuple[bool, str]":
    try:
        from tests.compare import assert_frames_equal
    except ImportError:  # installed without tests/: structural check only
        ok = len(cpu_df) == len(tpu_df)
        return ok, "" if ok else "row count mismatch"
    try:
        assert_frames_equal(cpu_df, tpu_df, approx_float=1e-6)
        return True, ""
    except AssertionError as e:
        return False, str(e)[:500]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--benchmark", required=True,
                   choices=sorted(ALL_BENCHMARKS))
    p.add_argument("--sf", type=float, default=0.01)
    p.add_argument("--iterations", type=int, default=3)
    p.add_argument("--warmup", type=int, default=1)
    p.add_argument("--compare", action="store_true")
    p.add_argument("--dispatch-telemetry", action="store_true",
                   help="count jit/eager/transfer dispatches per "
                        "iteration and report the dispatch-RTT vs "
                        "on-device split (install happens at module "
                        "import, before the compute modules load)")
    p.add_argument("--skew", type=float, default=0.0,
                   help="hot-key fraction for the skewed tpch "
                        "generator (0.5 = one orderkey carries half "
                        "of lineitem); 0 keeps uniform data")
    p.add_argument("--data-dir", default="/tmp/rapids_tpu_tpch")
    p.add_argument("--output", default=None)
    args = p.parse_args(argv)
    if args.dispatch_telemetry:
        from spark_rapids_tpu.utils import dispatch as disp

        if not disp.installed():
            # too late: the compute modules already imported with the
            # real jax.jit (module-level @jit decorators captured it).
            # The flag only works as a literal CLI token, which the
            # import-time pre-parse above matched before the imports.
            p.error("--dispatch-telemetry must appear verbatim in "
                    "sys.argv before module import (no abbreviations; "
                    "for programmatic use call "
                    "spark_rapids_tpu.utils.dispatch.install() before "
                    "importing the runner)")
    runner = BenchmarkRunner(args.data_dir, args.sf, skew=args.skew)
    result = runner.run(args.benchmark, iterations=args.iterations,
                        compare=args.compare, warmup=args.warmup)
    text = json.dumps(result, indent=2)
    if args.output:
        with open(args.output, "w") as f:
            f.write(text)
    print(text)


if __name__ == "__main__":
    main()
