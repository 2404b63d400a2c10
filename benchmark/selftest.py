"""Self-check of the yardstick, on the CPU with no chip.

    python -m benchmark.selftest

1. ``BENCHMARK.json`` against the files: every cell's configuration, mix,
   statements (sql, reference, limits) and every metric's file and reader
   are there; a cell reports ``setup_s``, another end-to-end metric and a
   per-layer metric.
2. ``trace_reduce`` on the recorded trace ``testdata/v5e_cached_sf0.02``
   (one v5e chip, ``tpch-sf1.cached-q1q6 --rehearse 0.02`` on the first,
   uniform data, PR 24) against
   the numbers recorded beside it, and its interval arithmetic on made-up
   intervals.
3. ``compare_frames`` on made-up frames.
4. The ``io.scan`` and dispatch counters and their readers on two tiny
   CPU rehearsals: a cell as it is (queries over the cache read no file)
   and the same with the mix's ``prepare`` emptied (every query scans the
   files: the regime a later scan cell times). Counts repeat exactly from
   round to round.

Exit code 0 and a last line ``selftest ok`` when all hold.
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

RECORDED = os.path.join(HERE, "testdata", "v5e_cached_sf0.02")


def check(cond, what: str) -> None:
    if not cond:
        raise SystemExit(f"selftest FAILED: {what}")
    print(f"ok: {what}", flush=True)


def check_files() -> None:
    from benchmark import run

    bench = run.load_json(ROOT, "BENCHMARK.json")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        how = run.load_json(HERE, "metrics", m["name"] + ".json")
        check(hasattr(run.load_module("readers", how["reader"]), "read"),
              f"metric {m['name']}: file and reader {how['reader']}")
    for m in bench["per_layer"]:
        check(m["moves"] in e2e, f"{m['name']} moves {m['moves']}")
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"])
        check(spec["config"]["chips"] == w["chips"],
              f"{w['name']}: chips as the configuration states")
        for stmt in spec["mix"]["statements"]:
            ref = run.load_module("reference", stmt)
            limits = run.load_json(HERE, "reference", stmt + ".json")
            check(callable(ref.answer) and "max_rel_err" in limits["limits"],
                  f"{w['name']}: statement {stmt} has sql, reference, limits")
        mine = [m["name"] for m in run.metrics_of(bench, w["name"],
                                                  "end_to_end")]
        layer = run.metrics_of(bench, w["name"], "per_layer")
        check("setup_s" in mine and len(mine) >= 2 and layer,
              f"{w['name']}: reports setup_s, {len(mine) - 1} other "
              f"end-to-end and {len(layer)} per-layer metrics")


def check_trace_reduce() -> None:
    from benchmark import trace_reduce as tr

    check(tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]],
          "union merges overlapping and touching intervals")
    check(tr.covered([[0, 3], [5, 8]], 2, 6) == 2,
          "covered clips to the span asked for")
    check(tr.stable_name(
        "jit_fused_chain_decode_filter_project__4651(2802005294660667982)")
        == "jit_fused_chain_decode_filter_project"
        and tr.stable_name("jit__groupby(123)") == "jit__groupby",
        "stable_name cuts fingerprint and chain hash")
    spans = [(0, 100, "outer"), (10, 40, "inner"), (12, 14, "tiny")]
    check(tr.name_gaps([(11, 31), (50, 60), (200, 300)], spans)
          == {"inner": 20, "outer": 10, "no host span": 100},
          "name_gaps takes the shortest span over half of each gap")

    with open(RECORDED + ".expected.json") as f:
        want = json.load(f)
    got = tr.reduce(RECORDED + ".xplane.pb")
    for key in ("chips", "queries_traced", "statement_queries"):
        check(got[key] == want[key], f"recorded trace: {key} = {got[key]}")
    for key in ("window_s", "busy_s"):
        check(abs(got[key] - want[key]) <= 1e-9 * want[key],
              f"recorded trace: {key} = {got[key]}")
    check(got["device_ops"][0][0] == want["device_ops"][0][0]
          and 0 < got["busy_s"] < got["window_s"],
          f"recorded trace: largest module {got['device_ops'][0][0]}, "
          f"busy under the window")
    check(all(0 < v <= got["busy_s"] * got["queries_traced"]
              for v in got["statement_busy_s"].values()),
          "recorded trace: every statement has device time")


def check_compare() -> None:
    import pandas as pd

    from benchmark.compare import compare_frames

    a = pd.DataFrame({"k": ["A", "B"], "v": [1.0, 2.0], "n": [3, 4]})
    same = compare_frames(a, a.copy())
    check(same["mismatches"] == 0 and same["max_rel_err"] == 0.0,
          "compare: equal frames")
    b = a.copy()
    b.loc[1, "v"] = 2.0 * (1 + 1e-6)
    r = compare_frames(a, b)
    check(r["mismatches"] == 0 and abs(r["max_rel_err"] - 1e-6) < 1e-9,
          "compare: a float gap is a relative error, not a mismatch")
    for what, frame in (("a count", a.assign(n=[3, 5])),
                        ("a key", a.assign(k=["A", "C"])),
                        ("a row", a.iloc[:1]),
                        ("a column", a.rename(columns={"v": "w"})),
                        ("a NULL", a.assign(v=[1.0, None]))):
        check(compare_frames(a, frame)["mismatches"] > 0,
              f"compare: {what} altered is a mismatch")


def check_counters() -> None:
    from benchmark import run

    seed = 2**31 + 11
    for cached in (True, False):
        spec = run.load_cell("tpch-sf1.cached-q1q6")
        if not cached:
            spec["mix"] = {**spec["mix"], "prepare": {"cache": []}}
        what = "cached rehearsal" if cached else "scan rehearsal"
        result, info = run.run_cell(spec, seed=seed, seconds=0.5,
                                    trace=True, rehearse_sf=0.01)
        rounds = info["rounds"]
        queries = rounds * len(spec["mix"]["statements"])
        check(result["correct"], f"{what}: answers equal the reference's")
        check(info["window"]["programs_compiled"] == 0,
              f"{what}: nothing compiled inside the window")
        m = result["metrics"]
        check(info["dispatch"]["dispatch_count"] % rounds == 0
              and m["dispatches_per_query"]["value"] * queries
              == info["dispatch"]["dispatch_count"],
              f"{what}: dispatch counter {info['dispatch']['dispatch_count']}"
              f" over {rounds} rounds, the same every round")
        check(m["plan_ms"]["value"] > 0 and m["query_s.q1"]["value"] > 0
              and m["first_call_s"]["value"] > 0,
              f"{what}: host-clock readers read above 0")
        check("device_idle_share" not in m and "q1_roofline" not in m
              and "busy_s" not in result["device"],
              f"{what}: no device metric from a CPU run")
        if cached:
            check(info["bytes_read"] == 0 and info["bytes_uploaded"] == 0,
                  "cached rehearsal: the window read and uploaded nothing")
            continue
        tables, _, _ = run.ensure_data(spec, seed, 0.01)
        files = sum(os.path.getsize(os.path.join(d, f))
                    for d in tables.values() for f in os.listdir(d))
        check(info["bytes_read"] % rounds == 0
              and 0 < info["bytes_read"] // queries <= files,
              f"io.scan bytes_read: {info['bytes_read'] // queries} a query,"
              f" the same every round, at most the files' {files}")


def main() -> int:
    check_files()
    check_trace_reduce()
    check_compare()
    check_counters()
    print("selftest ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
