"""Dispatch-budget regression fence + unit tests for the round-6
dispatch-coalescing work.

Every dispatch (jit call, eager op, device_get) costs a fixed
host-side overhead, so the DISPATCH COUNT of a small query — not its
on-device time — sets the wall clock floor. The fence below pins the
full-query dispatch count of
tpcxbb q26 (scan -> filter -> broadcast join -> grouped aggregate ->
HAVING -> project -> ORDER BY) so a future PR cannot silently re-add
round trips: a stray ``device_get`` in a hot path stalls the dispatch
pipeline on the real hardware even though it is invisible on a local
CPU run.

The fence runs in a SUBPROCESS because dispatch telemetry must wrap
``jax.jit`` before the compute modules import (module-level ``@jit``
decorators capture the binding); inside a long-lived pytest process
that moment is long gone.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the hard ceiling for tpcxbb q26 at sf 0.1: measured 5 after the
# in-program build + single-pass groupby work (was 8 after the round-6
# whole-plan coalescing, 16 before that): stage0 = build-inlined chain
# + groupby + sort-tail chain, stage3 = 1 chain, result_sync = 1 fetch.
# See docs/tuning-guide.md "Dispatch cost model & stage fusion" for the
# stage-by-stage budget. The ceiling holds compiled programs and
# transfers; the eager launches have a ceiling of their own.
Q26_DISPATCH_BUDGET = 5
# eager primitives of the same query: three convert_element_type, two in
# stage0 and one in stage3 (each its own tiny executable and launch)
Q26_EAGER_BUDGET = 3

_FENCE_SCRIPT = r"""
import json, os, sys
sys.path.insert(0, __ROOT__)
from spark_rapids_tpu.utils import dispatch as disp
disp.install()   # BEFORE any compute module import
from spark_rapids_tpu.benchmarks.runner import (ALL_BENCHMARKS,
                                                BenchmarkRunner)
from spark_rapids_tpu.execs.base import collect
from spark_rapids_tpu.plan.overrides import apply_overrides

data_dir = __DATA_DIR__
r = BenchmarkRunner(data_dir, 0.1)
r.ensure_data("tpcxbb_q26")

# warm run: traces + compiles; the fence measures the steady state the
# driver's bench also reports
plan = ALL_BENCHMARKS["tpcxbb_q26"](data_dir)
collect(apply_overrides(plan, r.conf))

pre = disp.snapshot()
pre_stage = disp.stage_snapshot()
plan = ALL_BENCHMARKS["tpcxbb_q26"](data_dir)
df = collect(apply_overrides(plan, r.conf))
d = disp.delta(pre)

cmp_ = r.compare_results("tpcxbb_q26", df)
print(json.dumps({
    "dispatch_count": d["dispatch_count"],
    "detail": d,
    "per_stage": disp.stage_delta(pre_stage),
    "matches_cpu": cmp_["matches_cpu"],
    "mismatch": cmp_.get("detail", ""),
}))
"""


def test_q26_full_query_dispatch_budget(tmp_path):
    """tpcxbb q26 sf0.1, warm, end to end: at most 5 compiled programs
    and transfers and 3 eager primitives AND the
    result still matches the CPU oracle (a budget met by breaking the
    query would be worthless). Every dispatch must also carry a stage
    label — the old stray ``<unstaged>`` device_get is now part of the
    documented ``result_sync`` stage, and nothing may regress to an
    unattributed bucket."""
    # persistent data dir (marker-guarded, like bench.py's): datagen is
    # the expensive part and the tables are deterministic per sf
    data_dir = os.path.join("/tmp", "srt_dispatch_fence")
    script = _FENCE_SCRIPT.replace("__ROOT__", repr(ROOT)).replace(
        "__DATA_DIR__", repr(data_dir))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["matches_cpu"], rec["mismatch"]
    d = rec["detail"]
    sources = {k: d[k] for k in ("jit_calls", "eager_op_calls",
                                 "transfers")}
    assert rec["dispatch_count"] == sum(sources.values())
    assert d["jit_calls"] + d["transfers"] <= Q26_DISPATCH_BUDGET, (
        f"{d['jit_calls']} programs + {d['transfers']} transfers exceed "
        f"the {Q26_DISPATCH_BUDGET}-dispatch fence; per-source "
        f"{sources}, per-stage {rec['per_stage']} — a new host "
        f"sync or un-fused launch crept into the pipeline")
    assert d["eager_op_calls"] <= Q26_EAGER_BUDGET, (
        f"{d['eager_op_calls']} eager primitives exceed the "
        f"{Q26_EAGER_BUDGET} the query is known to launch; per-stage "
        f"{rec['per_stage']} — op-by-op glue crept in between programs")
    # attribution fence: every warm dispatch belongs to a pipeline
    # stage or the documented end-of-query result_sync fetch; an
    # <unstaged> bucket means an unattributed host sync came back
    assert "<unstaged>" not in (rec["per_stage"] or {}), rec["per_stage"]
    assert rec["per_stage"].get("result_sync", 0) >= 1, rec["per_stage"]


# ---------------------------------------------------------------------------
# unit tests for the round-6 satellite fixes
# ---------------------------------------------------------------------------


def test_narrow_uint_dictionary_boundary():
    """Exactly-256/65536-entry dictionaries pack at the narrow width:
    max code is len-1 (ADVICE r5: the old call passed len and lost the
    power-of-two boundary cases)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.execs import interop

    n = interop._PACK_MIN_ROWS
    for n_dict, want in ((256, np.uint8), (257, np.uint16)):
        vals = np.array([f"s{i:05d}" for i in range(n_dict)],
                        dtype=object)
        data = {"s": vals[np.arange(n) % n_dict]}
        packed = interop.pack_host(data, {"s": None},
                                   Schema(["s"], [dt.STRING]))
        (kind, bi, _vi, typ, dictionary, _st) = packed.col_specs[0]
        assert len(dictionary) == n_dict
        assert packed.host_bufs[bi].dtype == np.dtype(want), (
            n_dict, packed.host_bufs[bi].dtype)
        # decode must round-trip exactly
        b = interop.upload_packed(packed)
        got, _ = b.columns[0].to_numpy(n)
        assert list(got[:5]) == list(data["s"][:5])


def test_prep_cache_recovers_from_transient_sync_failure(monkeypatch):
    """A device_get failure during the prep flag sync must POP the
    (exchange, key) cache entry — like the launch-failure path — so a
    retry by a later consumer succeeds instead of seeing the poisoned
    entry forever (ADVICE r5)."""
    import types

    import jax

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.execs import fused
    from spark_rapids_tpu.execs.basic import DeviceBatchesExec
    from spark_rapids_tpu.execs.exchange import BroadcastExchangeExec

    keys = np.arange(8, dtype=np.int64)
    batch = ColumnarBatch(
        [Column.from_numpy(keys, dtype=dt.INT64)], len(keys))
    src = types.SimpleNamespace(batches=[batch])
    exch = BroadcastExchangeExec(
        DeviceBatchesExec(src, Schema(["k"], [dt.INT64])))

    real_get = jax.device_get
    boom = {"armed": True}

    def flaky_get(x):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("transient transfer error")
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", flaky_get)
    with pytest.raises(RuntimeError, match="transient"):
        fused.prepare_build(exch, [0], [dt.INT64], [dt.INT64])
    # the poisoned entry must be gone: this retry re-launches and wins
    prep = fused.prepare_build(exch, [0], [dt.INT64], [dt.INT64])
    assert prep.ok


def test_chain_program_tag_includes_probe_mode():
    """Dense-probe and hash-probe variants of one chain must carry
    DIFFERENT telemetry names/crc tags (ADVICE r5: they shared one,
    blurring per-program dispatch attribution)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.execs.fused import FusedChain, JoinStep

    chain = FusedChain(
        [JoinStep("inner", [0], [0], 0, [dt.INT64], [dt.INT64])],
        [dt.INT64], 1)
    names = set()
    for modes in ((True,), (False,)):
        prog = chain._build_program(True, modes)
        name = getattr(prog, "__name__", None) or \
            prog.__wrapped__.__name__
        names.add(name)
        assert name.startswith("fused_chain[join]")
    assert len(names) == 2, names
    # and the cache keys differ too (correctness was already keyed)
    assert chain.chain_key(True, (True,)) != \
        chain.chain_key(True, (False,))


def test_chain_program_label_marks_inline_build():
    """The build-inlined chain variant must carry a ``build+`` label
    prefix and a distinct cache key: telemetry readers tell a first
    launch that prepared the builds in-program apart from the steady-
    state probe-only launches of the same chain."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.execs.fused import FusedChain, JoinStep

    chain = FusedChain(
        [JoinStep("inner", [0], [0], 0, [dt.INT64], [dt.INT64])],
        [dt.INT64], 1)
    inline = (((0,), (dt.INT64,), (dt.INT64,), 0, 0),)
    prog_probe = chain._build_program(True, (False,))
    prog_inline = chain._build_program(True, (False,), (), inline)
    name_p = getattr(prog_probe, "__name__", None) or \
        prog_probe.__wrapped__.__name__
    name_i = getattr(prog_inline, "__name__", None) or \
        prog_inline.__wrapped__.__name__
    assert name_p.startswith("fused_chain[join]"), name_p
    assert name_i.startswith("fused_chain[build+join]"), name_i
    assert chain.chain_key(True, (False,)) != \
        chain.chain_key(True, (False,), (), inline)


def test_arrow_dictionary_with_null_slot():
    """A null INSIDE an arrow DictionaryArray's dictionary must fold
    into the validity mask — not surface as the literal string 'None'
    (ADVICE r5)."""
    pa = pytest.importorskip("pyarrow")

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.io.arrow_conv import column_to_host

    col = pa.DictionaryArray.from_arrays(
        pa.array([0, 1, 2, 0, 1], type=pa.int32()),
        pa.array(["b", None, "a"]))
    hs, valid = column_to_host(col, dt.STRING)
    assert valid is not None
    assert list(valid) == [True, False, True, True, False]
    decoded = [hs.dictionary[c] if v else None
               for c, v in zip(hs.codes, valid)]
    assert decoded == ["b", None, "a", "b", None]
    assert "None" not in set(hs.dictionary[hs.codes[valid]])


def test_spillable_deferred_count_realizes_batched():
    """defer_count keeps the register path sync-free and
    realize_counts fetches many counts in one transfer."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.memory.spillable import SpillableBatch

    handles = []
    for n in (3, 5):
        b = ColumnarBatch(
            [Column.from_numpy(np.arange(8, dtype=np.int64),
                               dtype=dt.INT64)],
            jnp.asarray(n, dtype=jnp.int32))  # lazy device count
        handles.append(SpillableBatch(b, 0, defer_count=True))
    assert all(sb._rows is None for sb in handles)
    SpillableBatch.realize_counts(handles)
    assert [sb.num_rows for sb in handles] == [3, 5]
    for sb in handles:
        sb.close()


def test_sort_tail_fusion_matches_unfused():
    """The absorbed post-aggregate tail (defer_final + SortStep) must
    produce frames identical to the conf-disabled path — including
    HAVING over the final projection and a DESC sort with nulls."""
    import pandas as pd

    from compare import assert_frames_equal
    from spark_rapids_tpu.api import Session

    rng = np.random.default_rng(23)
    n = 500
    df = pd.DataFrame({
        "k": rng.integers(0, 40, n).astype(np.int64),
        "v": rng.normal(size=n)})
    df.loc[rng.integers(0, n, 25), "v"] = None
    sql = ("SELECT k, sum(v) AS sv, count(*) AS c FROM t "
           "GROUP BY k HAVING count(*) > 5 ORDER BY sv DESC, k")
    frames = []
    for tail in (True, False):
        s = Session(conf={"rapids.tpu.sql.fusion.sortTail": tail})
        s.create_temp_view("t", s.create_dataframe(df))
        frames.append(s.sql(sql).collect())
    assert_frames_equal(frames[0], frames[1])


def test_defer_scan_decode_matches_eager(tmp_path):
    """A packed parquet scan feeding a fused chain must produce the
    same frame whether the decode runs standalone or inlined in the
    chain program (>= _PACK_MIN_ROWS rows so packing engages)."""
    import pandas as pd

    from compare import assert_frames_equal
    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.execs.interop import _PACK_MIN_ROWS

    pa = pytest.importorskip("pyarrow")
    pq = pytest.importorskip("pyarrow.parquet")

    n = _PACK_MIN_ROWS + 1000
    rng = np.random.default_rng(29)
    tbl = pa.table({
        "k": pa.array(rng.integers(0, 50, n).astype(np.int64)),
        "cat": pa.array([f"c{int(i) % 7}"
                         for i in rng.integers(0, 7, n)]),
        "v": pa.array(rng.integers(0, 1000, n).astype(np.int64))})
    path = str(tmp_path / "t.parquet")
    pq.write_table(tbl, path)
    sql = ("SELECT k, count(*) AS c, sum(v) AS sv FROM t "
           "WHERE cat = 'c3' AND v > 100 GROUP BY k ORDER BY k")
    frames = []
    for defer in (True, False):
        s = Session(conf={
            "rapids.tpu.sql.fusion.deferScanDecode": defer})
        s.register_parquet("t", path)
        frames.append(s.sql(sql).collect())
    assert_frames_equal(frames[0], frames[1])


def test_defer_final_not_absorbed_through_shared_intermediate():
    """defer_final mutates the aggregate's output contract; when the
    Project between Sort and Agg is SHARED with a second consumer, the
    absorption must decline — otherwise the second consumer reads raw
    partials as finalized columns."""
    import pandas as pd

    from compare import assert_frames_equal
    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.execs.aggregate import HashAggregateExec
    from spark_rapids_tpu.execs.basic import ProjectExec
    from spark_rapids_tpu.execs.basic import UnionExec
    from spark_rapids_tpu.execs.fused import fuse_pipelines
    from spark_rapids_tpu.execs.sort import SortExec
    from spark_rapids_tpu.execs.base import collect
    from spark_rapids_tpu.ops.sortkeys import SortKeySpec

    # build the exec tree by hand so the ProjectExec object is shared
    # by two parents (the CTE shape)
    s = Session()
    pdf = pd.DataFrame({"k": np.arange(200) % 9,
                        "v": np.arange(200, dtype=np.float64)})
    s.create_temp_view("t", s.create_dataframe(pdf))
    agg_exec_tree = s.sql(
        "SELECT k, sum(v) AS sv FROM t GROUP BY k")._exec()
    # locate the aggregate (strip any coalesce/wrappers above it)
    node = agg_exec_tree
    while not isinstance(node, HashAggregateExec):
        node = node.children[0]
    agg = node
    proj = ProjectExec(
        [__import__("spark_rapids_tpu.expressions.base",
                    fromlist=["BoundReference"]).BoundReference(i, t)
         for i, t in enumerate(agg.schema.types)],
        agg, agg.schema)
    sort_parent = SortExec([SortKeySpec.spark_default(0)], proj)
    root = UnionExec([sort_parent, proj], proj.schema)
    fused_root = fuse_pipelines(root, None)
    assert agg.defer_final is False, (
        "defer_final leaked through a shared Project: the second "
        "Union arm would read raw partials")
    # and the result must equal pandas on both arms
    got = collect(fused_root)
    kcol, vcol = got.columns[0], got.columns[1]
    want = pdf.groupby("k").agg(sv=("v", "sum")).reset_index()
    arm = got.iloc[:len(want)].reset_index(drop=True)
    arm2 = got.iloc[len(want):].reset_index(drop=True)
    for a in (arm, arm2):
        a = a.sort_values(kcol).reset_index(drop=True)
        assert np.allclose(a[vcol].astype(float).values,
                           want["sv"].values)


def test_cut_stages_labels_and_estimates():
    """The stage-cutting pass labels every exec reachable from the
    root (children AND broadcast builds) with a stage and attaches a
    positive dispatch estimate per stage."""
    import pandas as pd

    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.plan.optimizer import cut_stages

    s = Session()
    df = pd.DataFrame({"k": np.arange(100) % 7,
                       "v": np.arange(100, dtype=np.float64)})
    s.create_temp_view("t", s.create_dataframe(df))
    ex = s.sql("SELECT k, sum(v) AS sv FROM t WHERE v > 10 "
               "GROUP BY k ORDER BY k")._exec()
    stages = cut_stages(ex)
    assert stages and all(st["ops"] for st in stages)
    assert all(st["est_dispatches"] >= 0 for st in stages)
    assert sum(st["est_dispatches"] for st in stages) > 0
    labels = set()

    def walk(e):
        labels.add(getattr(e, "_stage_label", None))
        for c in e.children:
            walk(c)
        for bx in getattr(e, "builds", ()) or ():
            walk(bx)
    walk(ex)
    assert None not in labels


@pytest.mark.slow
def test_sf1_oracle_smoke():
    """Slow tier: one full query at sf 1 through scripts/sf1_check.py —
    warm dispatch count within budget, result oracle-matched, every
    dispatch stage-attributed. q6 is the cheapest sf-1 query; the
    nightly fence (scripts/sf1_check.py default) runs q1 too."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "sf1_check.py"),
         "--queries", "tpch_q6", "--sf", "1.0"],
        env=env, capture_output=True, text=True, timeout=1800)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    rec = json.loads(out.stdout)
    assert rec["ok"], rec
