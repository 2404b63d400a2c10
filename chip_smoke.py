"""First-run proof on the chip: TPC-H q6 and q1 (and, with
``--with-join``, q3) at sf 1 through ``Session.sql`` on one TPU chip,
every answer checked against the ``cpu/`` engine on the same files (q3:
against a pandas merge).

    python chip_smoke.py              # one chip (what the driver runs)
    python chip_smoke.py --with-join  # q3 too, from files: minutes of
                                      # compilation when cold (1,143 s
                                      # before PR 27; over cached tables
                                      # 104 s since)
    python chip_smoke.py --chips 4    # ONLY the mesh phase: q1 (and q3 with
                                      # --with-join) with rapids.tpu.mesh
                                      # over four chips vs a single-device
                                      # session in the same process

One process, no child, no service thread pool: a chip belongs to one
process. The script never selects a platform. It fails, non-zero and
before any query, unless ``jax.devices()[0].platform == "tpu"``.

``--rehearse SF`` is the CPU rehearsal (``JAX_PLATFORMS=cpu``, tiny sf):
given by hand only, it lifts the platform check, shrinks the data and
makes the last line say ``"ok": false`` so that a rehearsal can never be
read as a chip run.

Earlier lines of stdout: one JSON object for the environment (jax
version, cache directory in force, whether it was warm), then one per
query (rows scanned, cold and warm wall, programs compiled, seconds
compiling, dispatches, bytes uploaded, peak device bytes). Last line:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

Q6 = """
    SELECT sum(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01'
      AND l_shipdate < DATE '1995-01-01'
      AND l_discount BETWEEN 0.05 AND 0.07
      AND l_quantity < 24
"""

Q1 = """
    SELECT l_returnflag, l_linestatus,
           sum(l_quantity) AS sum_qty,
           sum(l_extendedprice) AS sum_base_price,
           sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           sum(l_extendedprice * (1 - l_discount) * (1 + l_tax))
               AS sum_charge,
           avg(l_quantity) AS avg_qty,
           avg(l_extendedprice) AS avg_price,
           avg(l_discount) AS avg_disc,
           count(*) AS count_order
    FROM lineitem
    WHERE l_shipdate <= DATE '1998-09-02'
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus
"""

Q3 = """
    SELECT l_orderkey,
           sum(l_extendedprice * (1 - l_discount)) AS revenue,
           o_orderdate, o_shippriority
    FROM customer
    JOIN orders ON c_custkey = o_custkey
    JOIN lineitem ON l_orderkey = o_orderkey
    WHERE c_mktsegment = 'BUILDING'
      AND o_orderdate < DATE '1995-03-15'
      AND l_shipdate > DATE '1995-03-15'
    GROUP BY l_orderkey, o_orderdate, o_shippriority
    ORDER BY revenue DESC, o_orderdate
    LIMIT 10
"""

QUERIES = {"q6": Q6, "q1": Q1, "q3": Q3}
TABLES = ("lineitem", "orders", "customer")


class CompileMeter:
    """Counts what JAX compiled and what it took from the persistent
    cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0      # backend compile-or-fetch calls
        self.cache_hits = 0
        self.seconds = 0.0     # spent in those calls, hits included
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.requests, self.cache_hits, self.seconds)

    def delta(self, before):
        r, h, s = (a - b for a, b in zip(self.snapshot(), before))
        return {"programs_compiled": r - h, "cache_hits": h,
                "compile_s": s}


def ensure_data(sf: float) -> str:
    """TPC-H parquet under the checkout, from datagen's fixed seeds.
    Outside every timed window."""
    from spark_rapids_tpu.benchmarks import datagen

    data_dir = os.path.join(ROOT, "bench_data", f"tpch_sf{sf:g}")
    marker = os.path.join(data_dir, "_SUCCESS")
    if not os.path.exists(marker):
        datagen.write_tables(data_dir, sf, tables=list(TABLES))
        with open(marker, "w") as f:
            f.write("ok")
    return data_dir


def open_session(data_dir: str, conf=None):
    from spark_rapids_tpu.api import Session

    s = Session(conf, initialize_runtime=True)
    for t in TABLES:
        s.register_parquet(t, os.path.join(data_dir, t))
    return s


def assert_on_device(name: str, plan_text: str) -> None:
    """Every node of the tagged plan carries '*': on this path a
    reasoned CPU fallback ('!') is a failure, not a degradation."""
    lines = [ln.strip() for ln in plan_text.splitlines() if ln.strip()]
    bad = [ln for ln in lines if not ln.startswith("* ")]
    if not lines or bad:
        raise AssertionError(
            f"{name}: plan nodes not on the device:\n" + "\n".join(bad)
            + "\nfull plan:\n" + plan_text)


def walk(exec_):
    stack = [exec_]
    while stack:
        e = stack.pop()
        yield e
        stack.extend(e.children)


def peak_bytes() -> list:
    import jax

    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def frames_equal(want, got) -> None:
    """The repo's own comparison: ordered, 1e-6 relative on floats."""
    tests = os.path.join(ROOT, "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    from compare import assert_frames_equal

    assert_frames_equal(want, got, sort=False, approx_float=1e-6)


def q3_by_pandas(data_dir: str):
    """Q3 as a pandas merge in float64 over the same files (the cpu/
    engine takes 1,440 s for it at sf 1, PR 23: too long to hold a chip
    for). DATE comes back as days since 1970, as the engine returns it."""
    import numpy as np
    import pandas as pd
    import pyarrow.parquet as pq

    def read(table, cols):
        return pq.read_table(os.path.join(data_dir, table),
                             columns=cols).to_pandas()

    def days(col):
        return pd.to_datetime(col).values.astype("datetime64[D]").astype(
            np.int32)

    c = read("customer", ["c_custkey", "c_mktsegment"])
    o = read("orders", ["o_orderkey", "o_custkey", "o_orderdate",
                        "o_shippriority"])
    li = read("lineitem", ["l_orderkey", "l_extendedprice", "l_discount",
                           "l_shipdate"])
    cut = (np.datetime64("1995-03-15") - np.datetime64("1970-01-01")
           ).astype(np.int32)
    o["o_orderdate"] = days(o.o_orderdate)
    m = c[c.c_mktsegment == "BUILDING"] \
        .merge(o[o.o_orderdate < cut], left_on="c_custkey",
               right_on="o_custkey") \
        .merge(li[days(li.l_shipdate) > cut], left_on="o_orderkey",
               right_on="l_orderkey")
    m["revenue"] = m.l_extendedprice * (1.0 - m.l_discount)
    g = m.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    return g[["l_orderkey", "revenue", "o_orderdate",
              "o_shippriority"]].reset_index(drop=True)


def check_answers(name: str, session_df, data_dir: str, *frames) -> None:
    """The same statement on the cpu/ engine over the same files (q3: a
    pandas merge), run once, against every frame the device path
    returned."""
    from spark_rapids_tpu.cpu.engine import execute_cpu

    want = q3_by_pandas(data_dir) if name == "q3" \
        else execute_cpu(session_df._plan).to_pandas()
    assert len(want) > 0, f"{name}: the reference answer is empty"
    for got in frames:
        frames_equal(want, got)


def run_query(session, name: str, meter: CompileMeter) -> tuple:
    """Cold call, warm call, counters.
    Returns (record, cold frame, warm frame, df)."""
    from spark_rapids_tpu.io import scanpipe
    from spark_rapids_tpu.utils import dispatch as disp

    df = session.sql(QUERIES[name])
    plan_text = df.explain()
    assert_on_device(name, plan_text)

    c0, d0, s0 = meter.snapshot(), disp.snapshot(), scanpipe.snapshot()
    t0 = time.perf_counter()
    cold = df.collect()            # ends in the host frame: synchronous
    cold_s = time.perf_counter() - t0
    compiled = meter.delta(c0)
    cold_dispatch = disp.delta(d0)
    cold_scan = scanpipe.delta(s0)
    execs = list(walk(df._last_exec))
    rows = sum(e.metrics.num_output_rows for e in execs
               if "Scan" in type(e).__name__)

    df2 = session.sql(QUERIES[name])
    c1, d1, s1 = meter.snapshot(), disp.snapshot(), scanpipe.snapshot()
    t0 = time.perf_counter()
    warm = df2.collect()
    warm_s = time.perf_counter() - t0
    rec = {
        "query": name, "rows_scanned": rows, "rows_returned": len(warm),
        "cold_wall_s": cold_s, "warm_wall_s": warm_s,
        "programs_compiled": compiled["programs_compiled"],
        "cache_hits": compiled["cache_hits"],
        "compile_s": compiled["compile_s"],
        "warm_programs_compiled": meter.delta(c1)["programs_compiled"],
        "cold_dispatch_count": cold_dispatch["dispatch_count"],
        "warm_dispatch_count": disp.delta(d1)["dispatch_count"],
        "bytes_uploaded": cold_scan["bytes_uploaded"],
        "warm_bytes_uploaded": scanpipe.delta(s1)["bytes_uploaded"],
        "bytes_read": cold_scan["bytes_read"],
        "peak_bytes_in_use": peak_bytes(),
        "execs": [type(e).__name__ for e in execs],
    }
    return rec, cold, warm, df


def one_chip(sf: float, names) -> None:
    meter = CompileMeter()
    data_dir = ensure_data(sf)
    session = open_session(data_dir)
    hbm = session.runtime.device_manager.hbm_bytes()
    import jax

    if jax.devices()[0].platform != "cpu":
        assert isinstance(hbm, int) and hbm > 0, \
            f"TpuDeviceManager.hbm_bytes() = {hbm!r}: no HBM budget"
    print(json.dumps({
        "hbm_bytes": hbm,
        "device_budget": session.runtime.catalog.device_budget}),
        flush=True)
    try:
        for name in names:
            rec, cold, warm, df = run_query(session, name, meter)
            check_answers(name, df, data_dir, cold, warm)
            rec["matches_reference"] = True
            print(json.dumps(rec), flush=True)
    finally:
        session.stop()


def watch_sharded_handoffs() -> list:
    """Record where every single-device batch lands when the mesh execs
    shard it: [(device ids of the shards, per-device live-row counts)].
    Code that has only seen one chip may put everything on the first."""
    from spark_rapids_tpu.parallel import execs as pexecs

    seen = []
    real = pexecs._to_sharded

    def watching(mesh, batch, dtypes):
        db = real(mesh, batch, dtypes)
        seen.append((sorted(s.device.id
                            for s in db.datas[0].addressable_shards),
                     db.counts))
        return db

    pexecs._to_sharded = watching
    return seen


def four_chips(sf: float, names) -> None:
    import jax

    from spark_rapids_tpu.parallel import mesh as pmesh

    meter = CompileMeter()
    data_dir = ensure_data(sf)
    single = {}
    session = open_session(data_dir)
    try:
        for name in names:
            rec, cold, warm, df = run_query(session, name, meter)
            check_answers(name, df, data_dir, warm)
            rec["session"] = "single_device"
            single[name] = warm
            print(json.dumps(rec), flush=True)
    finally:
        session.stop()

    fb0 = pmesh.mesh_fallback_snapshot()
    handoffs = watch_sharded_handoffs()
    session = open_session(data_dir, {"rapids.tpu.mesh.enabled": True,
                                      "rapids.tpu.mesh.devices": 4})
    try:
        for name in names:
            rec, cold, warm, df = run_query(session, name, meter)
            mesh_execs = [e for e in rec["execs"] if e.startswith("Mesh")]
            assert mesh_execs, \
                f"{name}: no Mesh*Exec in the executed plan: {rec['execs']}"
            fallbacks = pmesh.mesh_fallback_delta(fb0)
            assert not fallbacks, f"{name}: mesh fallbacks {fallbacks}"
            frames_equal(single[name], warm)
            check_answers(name, df, data_dir, cold)
            rec["session"] = "mesh_4"
            rec["mesh_execs"] = mesh_execs
            rec["matches_single_device"] = True
            # the largest hand-off is the lineitem scan: every device
            # must hold live rows of it
            shards = [(ids, [int(c) for c in jax.device_get(counts)])
                      for ids, counts in handoffs]
            del handoffs[:]
            ids, rows = max(shards, key=lambda s: sum(s[1]))
            assert len(set(ids)) == 4 and len(rows) == 4 \
                and all(r > 0 for r in rows), \
                f"{name}: lineitem not spread over four devices: {shards}"
            rec["lineitem_rows_per_device"] = rows
            rec["lineitem_shard_devices"] = ids
            shares = rec["peak_bytes_in_use"]
            if jax.devices()[0].platform != "cpu":
                assert all(isinstance(b, int) and b > 0 for b in shares), \
                    f"{name}: a device held no bytes: {shares}"
            print(json.dumps(rec), flush=True)
    finally:
        session.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--with-join", action="store_true",
                    help="also run q3 (minutes of compilation when cold)")
    ap.add_argument("--rehearse", type=float, metavar="SF", default=None,
                    help="CPU rehearsal at this scale factor; the last "
                         "line then says ok: false")
    args = ap.parse_args()

    import jax

    sf = 1.0 if args.rehearse is None else args.rehearse
    dev = jax.devices()[0]
    if args.rehearse is None and dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax.devices()[0] is {dev!r}",
              file=sys.stderr)
        return 1
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(jax.devices())} devices", file=sys.stderr)
        return 1

    # telemetry wraps jax.jit; must precede every compute-module import
    from spark_rapids_tpu.utils import dispatch as disp

    disp.install()
    from spark_rapids_tpu.utils import progcache

    progcache.install()
    cache_dir = progcache.cache_dir()
    entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(json.dumps({
        "jax": jax.__version__, "cache_dir": cache_dir,
        "cache_dir_from_env": bool(os.environ.get(progcache.CACHE_DIR_ENV)),
        "cache_entries_at_start": entries, "cache_warm": entries > 0,
        "sf": sf, "rehearsal": args.rehearse is not None}), flush=True)

    join = ("q3",) if args.with_join else ()
    if args.chips == 4:
        four_chips(sf, ("q1",) + join)
    else:
        one_chip(sf, ("q6", "q1") + join)
    print(json.dumps({
        "ok": args.rehearse is None,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
