"""The benchmark's one command: one cell, one process, no child.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``benchmark/configs/<config>.json``: a deployment, its tables and its
generator) under a traffic mix (``benchmark/traffic/<mix>.json``: what to
cache, which statements, in which order). Statements are
``benchmark/queries/<statement>.sql`` with their plain reference
``benchmark/reference/<statement>.py`` and its limits
``benchmark/reference/<statement>.json``; metrics are
``benchmark/metrics/<metric>.json`` naming a reader under
``benchmark/readers/``. This file holds no table of names: it resolves
every name to a file, so new cells, mixes, statements and metrics are new
files and new entries.

The run: set-up (tables from ``--seed`` unless there, a ``Session`` with
every knob at its default, the mix's ``prepare``, every statement called
once: the first call, which compiles or reads the cache), then a closed loop of one client that sends the
mix's statements in order through ``Session.sql(text).collect()`` until
``--seconds`` are over and the round in flight is done. With ``--trace 1``
the dispatch counters are installed before the engine is imported, and a
few more rounds follow the window under ``jax.profiler``. Then the device's
peak is read, the session stopped, the plain reference computed, and every
frame the loop returned compared with it.

It refuses to run, non-zero and with no result line, unless JAX's first
device is a TPU and the device count is the cell's ``chips``.
``--rehearse SF`` (by hand only) lifts that, replaces the scale factor,
and makes the last line say ``"correct": false`` and ``"rehearsal"``, so
that a rehearsal can never be read as a chip result.

stdout: one JSON line of what the last line cannot hold (versions, cache
directory, compile counts, bytes read and uploaded), then the result line.
stderr: progress, and as its last lines each number compared beside its
limit. Traces and per-query detail go to ``benchmark/out/``.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: rounds under the profiler after the window: enough to span this long,
#: at least TRACE_ROUNDS_MIN, at most a quarter of the window's rounds
TRACE_SPAN_S = 1.0
TRACE_ROUNDS_MIN = 2


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str) -> dict:
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = load_json(HERE, "traffic", cell["traffic"] + ".json")
    return {
        "bench": bench, "cell": cell, "mix": mix,
        "config": load_json(ROOT, entry["file"]),
        "statements": {s: open(os.path.join(HERE, "queries", s + ".sql")
                               ).read() for s in mix["statements"]},
    }


def metrics_of(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def device_peaks(kind: str) -> dict:
    table = load_json(HERE, "peaks.json")["devices"]
    if kind not in table:
        raise SystemExit(f"run.py: no peaks for device kind {kind!r} in "
                         f"benchmark/peaks.json; it knows {sorted(table)}")
    return table[kind]


def ensure_data(spec: dict, seed: int, rehearse_sf=None) -> tuple:
    """The mix's tables under benchmark/data/<config>/seed<n>/ (a
    rehearsal's under <config>-rehearse<sf>), made from the seed unless
    they are there. Returns ({table: directory}, {table: rows}, made)."""
    gen = load_module("datagen", spec["config"]["generator"])
    names = spec["mix"]["tables"]
    sf, tag = spec["config"]["scale_factor"], spec["cell"]["config"]
    if rehearse_sf is not None:
        sf, tag = rehearse_sf, f"{tag}-rehearse{rehearse_sf:g}"
    data_dir = os.path.join(HERE, "data", tag, f"seed{seed}")
    marker = os.path.join(data_dir, "_SUCCESS")
    made = not os.path.exists(marker)
    if made:
        shutil.rmtree(data_dir, ignore_errors=True)
        gen.write_tables(data_dir, sf, seed, names)
        with open(marker, "w") as f:
            f.write("ok")
    return ({t: os.path.join(data_dir, t) for t in names},
            {t: gen.table_rows(t, sf) for t in names}, made)


def has_fallback(exec_) -> bool:
    stack = [exec_]
    while stack:
        e = stack.pop()
        if type(e).__name__ == "CpuFallbackExec":
            return True
        stack.extend(e.children)
    return False


class Loop:
    """The closed loop of one client: the mix's statements in order,
    round after round. Keeps every query's times and every frame."""

    def __init__(self, session, statements: dict):
        import jax.profiler

        self.session = session
        self.statements = statements
        self.span = jax.profiler.TraceAnnotation
        self.queries = []
        self.frames = {s: [] for s in statements}
        self.errors = []

    def query(self, stmt: str) -> None:
        text = self.statements[stmt]
        rec = {"statement": stmt, "ok": False, "wall_s": None,
               "plan_s": None}
        t0 = time.perf_counter()
        try:
            with self.span("bench.query." + stmt):
                with self.span("bench.sql"):
                    df = self.session.sql(text)
                t1 = time.perf_counter()
                with self.span("bench.collect"):
                    frame = df.collect()
            t2 = time.perf_counter()
            rec.update(wall_s=t2 - t0, plan_s=t1 - t0)
            if has_fallback(df._last_exec):
                self.errors.append(f"{stmt}: a plan node fell back to "
                                   f"the CPU:\n{df.explain()}")
            else:
                rec["ok"] = True
                self.frames[stmt].append(frame)
        except Exception as e:  # a failed query is counted, not fatal
            rec["wall_s"] = time.perf_counter() - t0
            self.errors.append(f"{stmt}: {type(e).__name__}: {e}")
        self.queries.append(rec)

    def round(self) -> None:
        for stmt in self.statements:
            self.query(stmt)

    def run_for(self, seconds: float) -> tuple:
        """Rounds until ``seconds`` are over; the round in flight is
        finished. Returns (window seconds, rounds)."""
        rounds = 0
        start = time.perf_counter()
        while True:
            self.round()
            rounds += 1
            now = time.perf_counter()
            if now - start >= seconds:
                return now - start, rounds


def trace_rounds(loop: Loop, window_s: float, rounds: int, out_dir: str):
    """A few more rounds under the profiler. Returns the trace
    reduction (see trace_reduce.reduce)."""
    import jax.profiler

    from benchmark import trace_reduce

    n = max(TRACE_ROUNDS_MIN, min(math.ceil(TRACE_SPAN_S * rounds / window_s),
                                  rounds // 4))
    trace_dir = os.path.join(out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # frames of every Python call: not read
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        for _ in range(n):
            loop.round()
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))


def judge(spec: dict, tables: dict, frames: dict, dtype=None) -> tuple:
    """Every frame the loop returned against the plain reference.
    Returns (correct, {name: {"value", "limit"}}, [first differences]).
    ``dtype`` is for the control only: the reference, in that precision,
    takes the engine's place (and ``frames`` is ignored)."""
    from benchmark import compare

    compared, notes, correct = {}, [], True
    for stmt in spec["mix"]["statements"]:
        ref = load_module("reference", stmt)
        limits = load_json(HERE, "reference", stmt + ".json")["limits"]
        want = ref.answer(tables)
        got = [ref.answer(tables, dtype=dtype)] if dtype is not None \
            else frames[stmt]
        unique = {}
        for f in got:
            unique.setdefault(f.to_json(double_precision=15), f)
        worst = {"mismatches": 0, "max_rel_err": 0.0}
        if len(want) == 0 or not got:
            worst["mismatches"] += 1
            notes.append(f"{stmt}: no rows in the reference's answer, or "
                         f"no frame to compare")
        for f in unique.values():
            r = compare.compare_frames(want, f)
            worst["mismatches"] += r["mismatches"]
            worst["max_rel_err"] = max(worst["max_rel_err"],
                                       r["max_rel_err"])
            if r["first"]:
                notes.append(f"{stmt}: {r['first']}")
        for k in ("mismatches", "max_rel_err"):
            compared[f"{stmt}.{k}"] = {"value": worst[k],
                                       "limit": limits[k]}
            correct = correct and worst[k] <= limits[k]
    return correct, compared, notes


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             rehearse_sf=None) -> tuple:
    """Set-up, window, traced rounds, judgement. Returns (the result
    line as a dict, the earlier info line as a dict)."""
    import jax

    cell = spec["cell"]["name"]
    config, mix = spec["config"], spec["mix"]
    dev = jax.devices()[0]
    # an unknown device is an error; only a rehearsal may go without peaks
    peaks = device_peaks(dev.device_kind) \
        if rehearse_sf is None or dev.platform == "tpu" else None

    if trace:
        # the counters wrap jax.jit: before every engine module is imported
        from spark_rapids_tpu.utils import dispatch as disp

        disp.install()
    else:
        disp = None
    from spark_rapids_tpu.utils import progcache

    progcache.install()
    cache_dir = progcache.cache_dir()
    cache_entries = len(os.listdir(cache_dir)) \
        if cache_dir and os.path.isdir(cache_dir) else 0

    from benchmark.meter import CompileMeter, assert_on_device

    meter = CompileMeter()
    t = time.perf_counter()
    tables, rows, made = ensure_data(spec, seed, rehearse_sf)
    data_s = time.perf_counter() - t
    log(f"[{cell}] data {'made' if made else 'found'} in {data_s:.1f} s")

    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.io import scanpipe

    out_dir = os.path.join(HERE, "out", cell, f"seed{seed}.trace{int(trace)}")
    os.makedirs(out_dir, exist_ok=True)
    # every knob at its default but one path: the default spill directory
    # is a fixed /tmp path that two checkouts on one machine would share
    session = Session({"rapids.tpu.memory.spillDir": os.path.join(
        tempfile.gettempdir(), "rapids_tpu_spill")}, initialize_runtime=True)
    try:
        c0 = meter.snapshot()
        t = time.perf_counter()
        for name, path in tables.items():
            if name in mix["prepare"]["cache"]:
                # the mix may name the columns it holds: the reader then
                # decodes no other (a select() before cache() would not
                # reach the scan)
                cached = session.read.parquet(path, columns=mix["prepare"].get(
                    "columns", {}).get(name)).cache()
                cached.create_or_replace_temp_view(name)
                filled = cached.count()     # one full materialisation
                if filled != rows[name]:
                    raise RuntimeError(f"{name}: cached {filled} rows, "
                                       f"generated {rows[name]}")
            else:
                session.register_parquet(name, path)
        prepare_s = time.perf_counter() - t
        loop = Loop(session, spec["statements"])
        for stmt, text in spec["statements"].items():
            assert_on_device(stmt, session.sql(text).explain())
        t = time.perf_counter()
        loop.round()
        first_call_s = time.perf_counter() - t
        setup_compiles = meter.delta(c0)
        warm_queries = len(loop.queries)
        log(f"[{cell}] prepare {prepare_s:.1f} s, first calls "
            f"{first_call_s:.1f} s, {setup_compiles}")

        setup_s = time.perf_counter() - T0
        c1, s1 = meter.snapshot(), scanpipe.snapshot()
        d1 = disp.snapshot() if disp else None
        window_s, rounds = loop.run_for(seconds)
        window_queries = loop.queries[warm_queries:]
        window_compiles = meter.delta(c1)
        scan = scanpipe.delta(s1)
        dispatch = disp.delta(d1) if disp else None
        log(f"[{cell}] window {window_s:.2f} s, {rounds} rounds, "
            f"{window_compiles}")
        traced = trace_rounds(loop, window_s, rounds, out_dir) \
            if trace else None
        for stmt, text in spec["statements"].items():
            assert_on_device(stmt, session.sql(text).explain())
        stats = [d.memory_stats() or {} for d in jax.devices()]
        memory_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    finally:
        session.stop()

    t = time.perf_counter()
    correct, compared, notes = judge(spec, tables, loop.frames)
    reference_s = time.perf_counter() - t
    after_setup = loop.queries[warm_queries:]
    attempted = len(after_setup)
    failed = sum(1 for q in after_setup if not q["ok"])
    # a failed warm-up query fails the run too: its answer was due
    all_failed = sum(1 for q in loop.queries if not q["ok"])
    compared["queries_failed"] = {"value": all_failed, "limit": 0}
    correct = correct and all_failed == 0
    for n in (loop.errors + notes)[:20]:
        log(f"[{cell}] {n}")

    run = {"config": config, "mix": mix, "peaks": peaks, "rows": rows,
           "statements": spec["statements"], "setup_s": setup_s,
           "first_call_s": first_call_s, "scan": scan,
           "dispatch": dispatch, "trace": traced,
           "window": {"seconds": window_s, "rounds": rounds,
                      "queries": window_queries}}
    metrics = {}
    group = "per_layer" if trace else "end_to_end"
    for m in metrics_of(spec["bench"], cell, group):
        how = load_json(HERE, "metrics", m["name"] + ".json")
        value = load_module("readers", how["reader"]).read(run, **how["args"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if traced and traced.get("busy_s"):
        device["busy_s"] = traced["busy_s"]
        device["window_s"] = traced["window_s"]
        result["breakdown"] = {"device_ops": traced["device_ops"],
                               "idle_gaps": traced["idle_gaps"]}
    result["compared"] = compared

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    info = {
        "workload": cell, "seed": seed, "seconds": seconds,
        "trace": int(trace), "jax": jax.__version__, "libtpu": libtpu,
        "cache_dir": cache_dir, "cache_entries_at_start": cache_entries,
        "setup": setup_compiles, "window": window_compiles,
        "data_made": made, "data_s": data_s, "prepare_s": prepare_s,
        "first_call_s": first_call_s, "reference_s": reference_s,
        "rows": rows, "rounds": rounds, "window_s": window_s,
        "bytes_read": scan["bytes_read"],
        "bytes_uploaded": scan["bytes_uploaded"],
        "dispatch": dispatch, "memory_peak_bytes": memory_peak,
        "queries_traced": traced["queries_traced"] if traced else 0,
        "frames_compared": {s: len(f) for s, f in loop.frames.items()},
    }
    with open(os.path.join(out_dir, "detail.json"), "w") as f:
        json.dump({"info": info, "result": result, "scan": scan,
                   "trace": traced, "queries": loop.queries,
                   "errors": loop.errors, "notes": notes}, f, indent=1)
    return result, info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, metavar="SF", default=None,
                    help="by hand only: run at this scale factor on "
                         "whatever device there is; the last line then "
                         "says correct: false")
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)

    import jax

    devices = jax.devices()
    if args.rehearse is None:
        if devices[0].platform != "tpu":
            log(f"run.py: no TPU: jax.devices()[0] is {devices[0]!r}")
            return 1
        if len(devices) != spec["cell"]["chips"]:
            log(f"run.py: {args.workload} asks for "
                f"{spec['cell']['chips']} chips, JAX sees {len(devices)}")
            return 1

    result, info = run_cell(spec, args.seed, args.seconds,
                            bool(args.trace), args.rehearse)
    if args.rehearse is not None:
        compared = result.pop("compared")
        result["rehearsal"] = {"scale_factor": args.rehearse,
                               "correct_at_this_size": result["correct"]}
        result["correct"] = False
        result["compared"] = compared
    print(json.dumps(info), flush=True)
    for name, c in result["compared"].items():
        log(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    log(f"correct = {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
