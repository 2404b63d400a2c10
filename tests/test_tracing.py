"""Spans and launch timers (utils/tracing, utils/dispatch).

Recording is on exactly when ``dispatch.install()`` has run, and that must
precede the engine's imports, so everything "on" runs in ONE subprocess
(as tests/test_dispatch_budget.py does) that prints a record a check; the
tests below each assert on their part of it. The pytest process itself
never installs: it is the "off" case.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ON_SCRIPT = r"""
import json, sys, threading
sys.path.insert(0, __ROOT__)
from spark_rapids_tpu.utils import dispatch as disp
disp.install()   # BEFORE any compute module import
from spark_rapids_tpu.utils import tracing
import numpy as np, pandas as pd
import jax, jax.numpy as jnp
from jax import lax
from spark_rapids_tpu.api import Session, col, functions as F
from spark_rapids_tpu.execs.base import run_partitions

rec = {}

# -- a hand-made tree on made-up clocks: a[0,100] > b[10,30] > two
#    launch timers, [12,15] and [20,22]
before = tracing.table()
with tracing.QueryRange() as q:
    a = tracing.open_span("hand.a", 0)
    b = tracing.open_span("hand.b", 10)
    tracing.leaf("hand.launch", 12, 15)
    tracing.leaf("hand.launch", 20, 22)
    tracing.close_span(b, 30)
    c = tracing.open_span("hand.c", 40)
    tracing.abandon_span(c)          # leaves no record
    tracing.close_span(a, 100)
rec["hand_tree"] = tracing.profile(q.query_id)
rec["hand_table"] = tracing.table_delta(before)

# -- one query across run_partitions' pool threads; each task launches
#    250 times inside a stage and once outside any
def task(p):
    disp._bump_stage("jit", "hand.outside")
    tok = disp.enter_stage("hand.stage")
    with tracing.TraceRange("hand.task"):
        for _ in range(250):
            disp._bump_stage("eager", "hand.inside")
    disp.exit_stage(tok)
    return threading.get_ident()
stages, progs = disp.stage_snapshot(), disp.stage_programs_snapshot()
with tracing.QueryRange() as q:
    idents = run_partitions(4, task, 4)
rec["pool_tree"] = tracing.profile(q.query_id)
rec["pool_query"] = q.query_id
rec["pool_idents"] = idents
rec["pool_stages"] = disp.stage_delta(stages)
rec["pool_programs"] = disp.stage_program_delta(progs)
rec["main_ident"] = threading.get_ident()

# -- the ring holds RING_QUERIES queries and no more
ids = []
for _ in range(tracing.RING_QUERIES + 6):
    with tracing.QueryRange() as q:
        pass
    ids.append(q.query_id)
rec["ring"] = {"held": len(tracing._ring), "limit": tracing.RING_QUERIES,
               "first_gone": tracing.profile(ids[0]) == {},
               "seventh_held": tracing.profile(ids[6]) != {},
               "last_held": tracing.profile(ids[-1]) != {}}

# -- three eager dynamic_slice on a device array, outside any jit
__EAGER__
rec["eager"] = eager

# -- one batch of q1's partial-aggregate schema (two string keys, sums
#    and averages with validity, counts without) cut into 2 partitions
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.ops import partition
live = jnp.arange(128) < 4
keys = [StringColumn(jnp.zeros(128, jnp.int32), np.array(["A"], dtype=object))
        for _ in range(2)]
sums = [Column(dt.FLOAT64, jnp.ones(128, jnp.float64), live) for _ in range(7)]
counts = [Column(dt.INT64, jnp.ones(128, jnp.int64)) for _ in range(4)]
partial = ColumnarBatch(keys + sums + counts, 4)
jax.block_until_ready(partition.slice_partitions(partial, np.array([3, 1]))[1]
                      .columns[0].data)
pre = disp.snapshot()
with tracing.QueryRange():
    with tracing.TraceRange("hand.slice"):
        subs = partition.slice_partitions(partial, np.array([1, 3]))
rec["slice"] = dict(disp.delta(pre), rows=[b.num_rows for b in subs])

# -- a q1-shaped query over a cached frame, one task thread so that the
#    metrics' child times are taken on the thread that spent them
s = Session({"rapids.tpu.sql.taskThreads": 1})
rng = np.random.default_rng(0)
n = 20000
pdf = pd.DataFrame({"k": rng.integers(0, 4, n), "v": rng.random(n),
                    "w": rng.random(n)})
base = s.create_dataframe(pdf).repartition(3).cache()
base.count()
q1 = (base.filter(col("v") > 0.1).group_by("k")
          .agg(F.sum(col("v")).alias("sv"), F.count("*").alias("n"))
          .order_by("k"))
q1.collect()
pre, stages = disp.snapshot(), disp.stage_snapshot()
q1.collect()
rec["q1_delta"] = disp.delta(pre)
rec["q1_stages"] = disp.stage_delta(stages)
rec["q1_tree"] = q1.last_profile()
rec["q1_metrics"] = q1.last_metrics()

# -- the same aggregate over a hash repartition by its key: the exchange
#    that still partitions, slices and registers
by_hash = (base.repartition(2, "k").group_by("k")
               .agg(F.sum(col("v")).alias("sv")))
by_hash.collect()
by_hash.collect()
rec["hash_tree"] = by_hash.last_profile()

# -- TPC-H q1 and q6 through Session.sql over lineitem cached in 2, 4 and
#    8 partitions of 1 and 2 batches each: the launch fence of the gather
#    and of the one launch a cached batch
import math, tempfile
from spark_rapids_tpu.benchmarks import datagen
tmp = tempfile.mkdtemp()
datagen.write_tables(tmp, 0.01, tables=["lineitem", "orders", "customer"])
rows = len(pd.read_parquet(tmp + "/lineitem"))
rec["tpch"] = {}
for batches in (1, 2):
    for parts in (2, 4, 8):
        s = Session({"rapids.tpu.sql.reader.batchSizeRows":
                     math.ceil(rows / batches)})
        s.read.parquet(tmp + "/lineitem").repartition(parts).cache() \
            .create_or_replace_temp_view("lineitem")
        for name in ("q1", "q6"):
            text = open(__ROOT__ + "/benchmark/queries/" + name
                        + ".sql").read()
            s.sql(text).collect()
            df = s.sql(text)
            pre = disp.snapshot()
            df.collect()
            rec["tpch"]["%s.%d.%d" % (name, parts, batches)] = {
                "delta": disp.delta(pre), "tree": df.last_profile()}
        s.stop()

# -- TPC-H Q3 over three cached tables, one task thread: a sort-path
#    aggregate (an int64 key of thousands of groups) over a chain with two
#    joins keeps the three launches a batch
import chip_smoke
s = Session({"rapids.tpu.sql.taskThreads": 1,
             "rapids.tpu.sql.reader.batchSizeRows": 20000})
for t in ("lineitem", "orders", "customer"):
    df = s.read.parquet(tmp + "/" + t).repartition(2).cache()
    df.create_or_replace_temp_view(t)
    df.count()
s.sql(chip_smoke.Q3).collect()
df = s.sql(chip_smoke.Q3)
from spark_rapids_tpu.memory.catalog import get_catalog
held = len(get_catalog())
pre = disp.snapshot()
out = df.collect()
rec["q3"] = {"delta": disp.delta(pre), "rows": len(out),
             "tree": df.last_profile(),
             "catalog": [held, len(get_catalog())]}
s.stop()

# -- the same statement where orders is too large to broadcast (as at sf 1):
#    customer's build is inlined into the chain over orders, orders and
#    lineitem meet in a shuffled hash join over two exchanges that move rows
from spark_rapids_tpu.execs.joins import HashJoinExec
s = Session({"rapids.tpu.sql.taskThreads": 1,
             "rapids.tpu.sql.reader.batchSizeRows": 20000,
             "rapids.tpu.sql.autoBroadcastJoinThreshold": "50k"})
for t in ("lineitem", "orders", "customer"):
    df = s.read.parquet(tmp + "/" + t).repartition(2).cache()
    df.create_or_replace_temp_view(t)
    df.count()
s.sql(chip_smoke.Q3).collect()
df = s.sql(chip_smoke.Q3)
held = len(get_catalog())
pre = disp.snapshot()
out = df.collect()
def joins_of(e):
    found = [e] if isinstance(e, HashJoinExec) else []
    for c in e.children:
        found += joins_of(c)
    return found
rec["q3_shuffled"] = {
    "delta": disp.delta(pre), "rows": len(out), "tree": df.last_profile(),
    "catalog": [held, len(get_catalog())],
    "joins": [[type(j).__name__, j.num_partitions]
              for j in joins_of(df._last_exec)]}
s.stop()
print(json.dumps(rec))
"""

# the shuffled Q3 of _ON_SCRIPT with the recorder OFF: no install(), the
# transfers counted by a plain wrapper the engine's calls look up
_OFF_SCRIPT = r"""
import json, sys, tempfile
sys.path.insert(0, __ROOT__)
import jax
real_get, gets = jax.device_get, []
def counting_get(x):
    gets.append(1)
    return real_get(x)
jax.device_get = counting_get
from spark_rapids_tpu.utils import tracing
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.benchmarks import datagen
import chip_smoke
tmp = tempfile.mkdtemp()
datagen.write_tables(tmp, 0.01, tables=["lineitem", "orders", "customer"])
s = Session({"rapids.tpu.sql.taskThreads": 1,
             "rapids.tpu.sql.reader.batchSizeRows": 20000,
             "rapids.tpu.sql.autoBroadcastJoinThreshold": "50k"})
for t in ("lineitem", "orders", "customer"):
    df = s.read.parquet(tmp + "/" + t).repartition(2).cache()
    df.create_or_replace_temp_view(t)
    df.count()
s.sql(chip_smoke.Q3).collect()
df = s.sql(chip_smoke.Q3)
before, n = tracing.counters(), len(gets)
out = df.collect()
print(json.dumps({"recording": tracing.recording(), "rows": len(out),
                  "transfers": len(gets) - n,
                  "counters": tracing.counters_delta(before)}))
s.stop()
"""

_AFTER_JAX_SCRIPT = r"""
import json, sys
sys.path.insert(0, __ROOT__)
import jax, jax.numpy as jnp          # jax FIRST, then install()
from jax import lax
from spark_rapids_tpu.utils import dispatch as disp
disp.install()
__EAGER__
print(json.dumps(eager))
"""

# lax.dynamic_slice clamps its start indices before it binds the
# primitive, so a call is the primitive and, by the index's type, a
# convert_element_type and a select_n: each its own eager launch, each
# counted once and named in the per-stage programs
_EAGER = r"""
x = jax.block_until_ready(jnp.arange(100.0))
pre, progs = disp.snapshot(), disp.stage_programs_snapshot()
for i in range(3):
    lax.dynamic_slice(x, (i,), (10,))
eager = dict(disp.delta(pre), programs=disp.stage_program_delta(progs))
"""


def _run(script: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", script.replace("__ROOT__", repr(ROOT))
         .replace("__EAGER__", _EAGER)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def on():
    return _run(_ON_SCRIPT)


def _walk(node):
    yield node
    for c in node["children"]:
        yield from _walk(c)


def _named(tree, name):
    return [n for n in _walk(tree) if n["name"] == name]


#: what an exchange that moves rows leaves in a tree; a gather none of them
_MOVING_SPANS = ("ShuffleExchangeExec.partition",
                 "ShuffleExchangeExec.partitionKernel",
                 "ShuffleExchangeExec.slice", "ShuffleExchangeExec.register")


# -- off: the pytest process never ran dispatch.install() -------------------


def test_off_a_query_leaves_no_record():
    from spark_rapids_tpu.api import Session, col
    from spark_rapids_tpu.utils import dispatch, tracing

    assert not dispatch.installed() and not tracing.recording()
    before, roots = tracing.table(), tracing.queries()
    df = Session().create_dataframe(
        pd.DataFrame({"k": np.arange(50) % 5, "v": np.arange(50.0)}))
    pipe = df.filter(col("v") > 3).group_by("k").count()
    assert pipe.last_profile() == {}
    assert len(pipe.collect()) == 5
    assert pipe.last_profile() == {}
    assert df.count() == 50 and df.last_profile() == {}
    assert tracing.table() == before == {}
    assert tracing.queries() == roots == 0
    assert tracing.current() is None


def test_off_trace_range_reads_no_clock(monkeypatch):
    from spark_rapids_tpu.utils import tracing

    def no_clock():
        raise AssertionError("a clock was read with recording off")

    monkeypatch.setattr(tracing.time, "perf_counter_ns", no_clock)
    with tracing.TraceRange("off.site"), tracing.QueryRange() as q:
        pass
    assert q.query_id is None


# -- on ---------------------------------------------------------------------


def test_hand_tree_nesting_and_parent(on):
    tree = on["hand_tree"]
    assert tree["name"] == "query" and tree["query"] is not None
    (a,) = tree["children"]
    assert (a["name"], a["start_ns"], a["end_ns"]) == ("hand.a", 0, 100)
    (b,) = a["children"]          # hand.c was abandoned: no record
    assert (b["name"], b["start_ns"], b["end_ns"]) == ("hand.b", 10, 30)
    (launch,) = b["children"]     # the two timers: one node a name
    assert launch["name"] == "hand.launch" and launch["children"] == []
    assert (launch["count"], launch["total_ns"]) == (2, 5)
    assert launch["start_ns"] is None and a["count"] == 1
    assert {n["query"] for n in _walk(tree)} == {tree["query"]}
    assert {n["thread"] for n in _walk(tree)} == {tree["thread"]}


def test_hand_tree_self_time(on):
    (a,) = on["hand_tree"]["children"]
    (b,) = a["children"]
    # a launch timer counts as a child: b's 20 less the launches' 3 + 2
    assert a["self_ns"] == 100 - 20
    assert b["self_ns"] == 20 - 5
    assert b["children"][0]["self_ns"] == 5
    t = on["hand_table"]
    assert "hand.c" not in t
    assert t["hand.a"] == {"count": 1, "total_s": 100e-9, "self_s": 80e-9}
    assert t["hand.b"]["self_s"] == pytest.approx(15e-9)
    assert t["hand.launch"]["count"] == 2
    assert t["hand.launch"]["total_s"] == pytest.approx(5e-9)
    assert t["query"]["count"] == 1


def test_one_query_across_pool_threads(on):
    tree = on["pool_tree"]
    (wait,) = tree["children"]
    assert wait["name"] == "run_partitions.wait"
    tasks = wait["children"]
    assert [t["name"] for t in tasks] == ["hand.task"] * 4
    assert {t["query"] for t in tasks} == {on["pool_query"]}
    assert sorted(t["thread"] for t in tasks) == sorted(on["pool_idents"])
    assert on["main_ident"] not in on["pool_idents"]
    # tasks run on other threads: they take nothing off the wait's self time
    assert wait["self_ns"] == wait["end_ns"] - wait["start_ns"]


def test_launches_of_pool_threads_all_counted(on):
    """A launch inside a stage takes no lock (it waits in the thread's own
    table until the stage is left); none is lost and each keeps its stage."""
    assert on["pool_stages"] == {"hand.stage": 1000, "<unstaged>": 4}
    assert on["pool_programs"] == {"hand.stage": {"hand.inside": 1000},
                                   "<unstaged>": {"hand.outside": 4}}


def test_ring_holds_64_queries(on):
    assert on["ring"] == {"held": 64, "limit": 64, "first_gone": True,
                          "seventh_held": True, "last_held": True}


def test_q1_has_one_root_with_plan_and_fetch(on):
    tree = on["q1_tree"]
    assert tree["name"] == "query"
    assert on["q1_delta"]["queries"] == 1
    assert on["q1_delta"]["spans"]["query"]["count"] == 1
    assert len(_named(tree, "query")) == 1
    kids = [c["name"] for c in tree["children"]]
    assert "plan.physical" in kids and "collect.fetch" in kids
    (plan,) = _named(tree, "plan.physical")
    assert [c["name"] for c in plan["children"]] == [
        "plan.optimize", "plan.tag", "plan.convert", "plan.stages"]
    for name in ("CachedExec.acquire", "ShuffleExchangeExec.gather",
                 "collect.concat", "launch.jit", "launch.eager",
                 "launch.device_get"):
        assert _named(tree, name), name
    # the spans of an exchange that moves rows: under the same aggregate
    # over a hash repartition, and nowhere under the gather's tree
    for name in _MOVING_SPANS:
        assert _named(on["hash_tree"], name), name
        assert not _named(tree, name), name
    assert _named(on["hash_tree"], "ShuffleExchangeExec.gather")
    # the root's own time, what no child covers, is a small part of it
    assert tree["self_ns"] < 0.1 * (tree["end_ns"] - tree["start_ns"])


def test_slicing_an_exchanged_batch_is_one_launch(on):
    """The launch fence: a 2-partition batch of q1's partial aggregate
    costs no eager primitive and at most 2 compiled programs (one when
    both partitions share a capacity), in the hand-made call and under
    every ``ShuffleExchangeExec.slice`` of the query that repartitions by
    hash (an aggregate's own exchange is a gather: no slice at all)."""
    d = on["slice"]
    assert d["rows"] == [1, 3]
    assert d["eager_op_calls"] == 0 and d["transfers"] == 0
    assert 1 <= d["jit_calls"] <= 2
    assert "launch.eager" not in d["spans"]
    assert d["spans"]["launch.jit"]["count"] == d["jit_calls"]
    slices = _named(on["hash_tree"], "ShuffleExchangeExec.slice")
    assert slices
    for node in slices:
        kids = {c["name"]: c["count"] for c in node["children"]}
        assert set(kids) == {"launch.jit"} and kids["launch.jit"] <= 2, kids


@pytest.mark.parametrize("batches", [1, 2])
@pytest.mark.parametrize("parts", [2, 4, 8])
@pytest.mark.parametrize("stmt,per_query", [("q1", 6), ("q6", 14)])
def test_gather_launch_fence(on, stmt, per_query, parts, batches):
    """TPC-H q1 and q6 over ``parts`` cached partitions of ``batches``
    batches: no span of an exchange that moves rows, one
    ``ShuffleExchangeExec.gather`` a partition with no launch beneath it,
    and the law of PR 30: ONE launch a cached batch and the final side's
    6 (q1: the coalesce's one fetch of every partition's count, its concat,
    the final group-by and its row count, the sorting chain, the result
    fetch) or 14 (q6) a query, where PR 28 measured 5 a partition and 5 (q1:
    15, 25, 45) and 6 a partition and 14 (q6: 26, 38, 62) at one batch."""
    run = on["tpch"]["%s.%d.%d" % (stmt, parts, batches)]
    tree, d = run["tree"], run["delta"]
    for name in _MOVING_SPANS + ("AdaptiveShuffleReaderExec.next",):
        assert not _named(tree, name), name
        assert name not in d["spans"], name
    gathers = _named(tree, "ShuffleExchangeExec.gather")
    assert len(gathers) == parts
    assert d["spans"]["ShuffleExchangeExec.gather"]["count"] == parts
    for g in gathers:
        assert g["children"] == [], g["children"]
    assert d["dispatch_count"] <= parts * batches + per_query, d
    assert d["queries"] == 1
    # a map task: its pulls of the cache and one jit call a batch, with no
    # eager launch, no transfer (so no wait for the device between a
    # partition's batches) and no concat beneath it
    tasks = _named(tree, "FusedAggregateExec.next")
    assert len(tasks) == parts
    for task in tasks:
        below = [n for n in _walk(task) if n is not task]
        assert {n["name"] for n in below} == {
            "CachedExec.next", "CachedExec.acquire",
            "FusedAggregateExec.step", "launch.jit"}, below
        steps = _named(task, "FusedAggregateExec.step")
        assert len(steps) == batches
        for step in steps:
            (launch,) = step["children"]
            assert (launch["name"], launch["count"]) == ("launch.jit", 1)
    spans = d["spans"]
    assert spans["FusedAggregateExec.step"]["count"] == parts * batches
    for name in ("FusedAggregateExec.chain", "HashAggregateExec.mergeAgg"):
        assert name not in spans, name
    # the final aggregate's one update over the coalesced partials
    assert spans["HashAggregateExec.updateAgg"]["count"] == 1
    # the gather's one block a map task ends with the query
    assert d["counters"] == {"fused_agg.engaged": parts * batches,
                             "exchange.blocks.registered": parts,
                             "exchange.blocks.closed": parts}


def test_sort_path_aggregate_over_joins_keeps_its_launches(on):
    """TPC-H Q3 over three cached tables (lineitem 2 partitions of 3
    batches): its aggregate's partials have the batch's capacity, so every
    batch keeps chain, update and merge, and the query the 63 = 30 + 23 + 10
    launches the parent of PR 30 counts for the same script."""
    d = on["q3"]["delta"]
    assert on["q3"]["rows"] == 10
    assert d["counters"] == {"fused_agg.fallback.inline_build": 1,
                             "fused_agg.fallback.sort_path": 5,
                             "exchange.blocks.registered": 4,
                             "exchange.blocks.closed": 4}
    assert (d["jit_calls"], d["eager_op_calls"], d["transfers"]) == \
        (30, 23, 10), d
    spans = d["spans"]
    assert "FusedAggregateExec.step" not in spans
    assert spans["FusedAggregateExec.chain"]["count"] == 5
    assert spans["HashAggregateExec.mergeAgg"]["count"] == 4
    # both builds are broadcast into the chain: no join exec, no join span;
    # the two gathered partials and the two inlined builds end with the query
    assert not [n for n in spans if n.startswith("HashJoinExec")]
    held, after = on["q3"]["catalog"]
    assert after == held
    # the top-N's wait for the sorted batch and its cut, under its pull
    (limit,) = _named(on["q3"]["tree"], "LocalLimitExec.limit")
    assert spans["LocalLimitExec.limit"]["count"] == 1
    assert {c["name"] for c in limit["children"]} <= {
        "launch.jit", "launch.device_get"}


def test_shuffled_join_spans_and_counters(on):
    """The same statement with orders too large to broadcast, the plan sf 1
    takes: a ``ShuffledHashJoinExec`` over two hash exchanges of 16
    partitions that the adaptive reader coalesces. ``HashJoinExec.build``
    once a join partition, over ``.buildStage`` and ``.buildPrepare`` and
    beside the probe's ``HashJoinExec.inner``; the ``join.*`` counters a
    partition and a stream batch each, ``join.out_rows`` left out (an inner
    join's count stays on the device); the launches the parent of PR 32
    counts for this script (49 + 73 + 18) and one transfer a build, the
    fetch of ``join.probe.rounds`` after the partition's last probe: the
    build's index is made inside ``_build_sorted`` and searched inside
    ``_probe_sorted``, no new program and no eager operation; every block
    of the three exchanges closed."""
    run = on["q3_shuffled"]
    d, tree, spans = run["delta"], run["tree"], run["delta"]["spans"]
    assert run["rows"] == 10
    ((kind, parts),) = run["joins"]
    assert kind == "ShuffledHashJoinExec" and parts >= 1
    builds = _named(tree, "HashJoinExec.build")
    assert len(builds) == parts == spans["HashJoinExec.build"]["count"]
    for b in builds:
        assert [c["name"] for c in b["children"]] == [
            "HashJoinExec.buildStage", "HashJoinExec.buildPrepare"]
        assert not _named(b, "launch.device_get")
    for name in ("HashJoinExec.buildStage", "HashJoinExec.buildPrepare"):
        assert spans[name]["count"] == parts
    probes = spans["HashJoinExec.inner"]["count"]
    assert probes >= parts
    for name in _MOVING_SPANS:
        assert spans[name]["count"] == 8, name    # 2 tables x 2 x 2 batches
    c = d["counters"]
    assert c.pop("join.build_rows") > 0 and c.pop("join.probe_rows") > 0
    registered = c.pop("exchange.blocks.registered")
    assert registered > 8 and c.pop("exchange.blocks.closed") == registered
    # a build each: the halvings its probes made against those of one
    # whole-build search (a capacity of at least 128 rows takes 8)
    rounds, full = c.pop("join.probe.rounds"), c.pop("join.probe.rounds_full")
    assert parts <= rounds < full and full >= 8 * parts, (rounds, full)
    assert c == {"join.build.hash": parts,
                 "fused_agg.fallback.inline_build": 1}, c
    assert (d["jit_calls"], d["eager_op_calls"], d["transfers"]) == \
        (49, 73, 18 + parts), d
    held, after = run["catalog"]
    assert after == held


def test_shuffled_join_fetches_no_rounds_with_the_recorder_off():
    """The same statement over the same tables without ``install()``: the
    18 transfers of the parent of PR 32 and no ``join.probe.*`` counter;
    the round count stays on the device."""
    run = _run(_OFF_SCRIPT)
    assert not run["recording"] and run["rows"] == 10
    c = run["counters"]
    assert c["join.build.hash"] >= 1
    assert not [name for name in c if name.startswith("join.probe.")], c
    assert run["transfers"] == 18, run


def test_q1_table_is_the_tree(on):
    """The window's table and the query's tree are one set of records."""
    tree, spans = on["q1_tree"], on["q1_delta"]["spans"]
    for name, row in spans.items():
        nodes = _named(tree, name)
        assert sum(n["count"] for n in nodes) == row["count"], name
        assert sum(n["self_ns"] for n in nodes) / 1e9 == \
            pytest.approx(row["self_s"], abs=1e-9), name


def test_q1_next_self_times_equal_last_metrics(on):
    """An exec's op_time is its pulls less its children's pulls; both come
    from the clock reads of timed(), so the tree says the same."""
    def pulls_below(node):
        for c in node["children"]:
            if c["name"].endswith(".next"):
                yield c
            else:
                yield from pulls_below(c)

    tree_ms = {}
    for n in _walk(on["q1_tree"]):
        if n["name"].endswith(".next"):
            own = (n["end_ns"] - n["start_ns"]) - sum(
                c["end_ns"] - c["start_ns"] for c in pulls_below(n))
            cls = n["name"][:-len(".next")]
            tree_ms[cls] = tree_ms.get(cls, 0.0) + own / 1e6
    metric_ms = {}
    for key, m in on["q1_metrics"].items():
        cls = key.split("#")[0]
        metric_ms[cls] = metric_ms.get(cls, 0.0) + m["op_time_ms"]
    assert {k for k, v in metric_ms.items() if v} == set(tree_ms)
    for cls, ms in tree_ms.items():
        assert ms == pytest.approx(metric_ms[cls], abs=0.005), cls


def _three_dynamic_slices_counted(d):
    (programs,) = d["programs"].values()
    assert programs["eager:dynamic_slice"] == 3
    assert all(p.startswith("eager:") for p in programs)
    assert d["eager_op_calls"] == sum(programs.values())
    assert (d["jit_calls"], d["transfers"]) == (0, 0)
    assert d["dispatch_count"] == d["eager_op_calls"]
    assert d["spans"]["launch.eager"]["count"] == d["eager_op_calls"]
    assert d["spans"]["launch.eager"]["total_s"] > 0


def test_three_eager_dynamic_slices_count_three(on):
    _three_dynamic_slices_counted(on["eager"])


def test_eager_count_when_install_follows_import_jax():
    _three_dynamic_slices_counted(_run(_AFTER_JAX_SCRIPT))


def test_delta_has_spans_and_queries(on):
    d = on["q1_delta"]
    assert set(d) == {"jit_calls", "eager_op_calls", "transfers",
                      "dispatch_count", "spans", "queries", "counters"}
    # three cached batches whose int key lost its range in the repartition
    # (and the gather's three blocks, registered and closed with the query)
    assert d["counters"] == {"fused_agg.fallback.sort_path": 3,
                             "exchange.blocks.registered": 3,
                             "exchange.blocks.closed": 3}
    assert d["dispatch_count"] == \
        d["jit_calls"] + d["eager_op_calls"] + d["transfers"]
    assert d["eager_op_calls"] > 0
    assert sum(on["q1_stages"].values()) == d["dispatch_count"]
    # the launch rows are the launch counts, timed where they are counted
    assert d["spans"]["launch.jit"]["count"] == d["jit_calls"]
    assert d["spans"]["launch.eager"]["count"] == d["eager_op_calls"]
    assert d["spans"]["launch.device_get"]["count"] == d["transfers"]
    for row in d["spans"].values():
        assert set(row) == {"count", "total_s", "self_s"}
        assert 0 <= row["self_s"] <= row["total_s"] + 1e-12


def test_install_fails_loudly_when_jax_moved_the_hook():
    """A JAX without the call-time lookup is an error at install(), not an
    eager counter that reads 0 (as it did before PR 25)."""
    script = (
        "import sys; sys.path.insert(0, %r)\n"
        "from jax._src import dispatch as jd\n"
        "del jd.xla_primitive_callable\n"
        "from spark_rapids_tpu.utils import dispatch as disp\n"
        "import jax\n"
        "jit = jax.jit\n"
        "try:\n"
        "    disp.install()\n"
        "except RuntimeError as e:\n"
        "    assert 'eager' in str(e), e\n"
        "    assert jax.jit is jit and not disp.installed()\n"
        "    print('refused')\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", script],
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().splitlines()[-1] == "refused"
