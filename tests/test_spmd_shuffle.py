"""SPMD in-program shuffle: differential oracles, the host-path
equivalence contract, fallback-reason recording, and the zero-hidden-
sync plan map.

The tentpole's correctness story is three-way agreement: the SAME
relational work must produce identical results on (a) the multi-device
mesh with in-program ``all_to_all`` exchanges, (b) the single-process
device path, and (c) the pandas CPU oracle — across 1/2/8 shards,
uneven partition sizes, and shards that receive zero rows. CPU CI
provides the 8 virtual devices via ``xla_force_host_platform_device_
count`` (conftest).
"""
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu.api import Session
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.column import col
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.parallel import spmd


def _mesh_session(n_dev, extra=None):
    conf = {"rapids.tpu.mesh.enabled": True,
            "rapids.tpu.mesh.devices": n_dev}
    conf.update(extra or {})
    return Session(conf)


def _normalize(df, sort_cols):
    out = df.sort_values(sort_cols, na_position="last") \
        .reset_index(drop=True)
    return out


def _assert_triple(mesh_df, plain_df, oracle_df, sort_cols):
    """mesh == single-device == CPU oracle, column by column."""
    assert len(mesh_df) == len(plain_df) == len(oracle_df)
    m = _normalize(mesh_df, sort_cols)
    p = _normalize(plain_df, sort_cols)
    o = _normalize(oracle_df, sort_cols)
    for ci in range(len(m.columns)):
        g = m.iloc[:, ci].to_numpy(np.float64)
        for other, tag in ((p, "single-device"), (o, "cpu-oracle")):
            w = other.iloc[:, ci].to_numpy(np.float64)
            np.testing.assert_allclose(
                g, w, rtol=1e-9, equal_nan=True,
                err_msg=f"col {m.columns[ci]} vs {tag}")


# ---------------------------------------------------------------------------
# differential oracles: group-by / hash join / sort across shard counts
# ---------------------------------------------------------------------------

# 997 rows: deliberately not divisible by any mesh size, so every
# shard count exercises uneven per-device partitions
_N = 997


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_groupby_mesh_matches_single_and_cpu(n_dev):
    rng = np.random.default_rng(101 + n_dev)
    df = pd.DataFrame({
        "k": pd.array([None if x == 0 else int(x)
                       for x in rng.integers(0, 37, _N)], dtype="Int64"),
        "v": rng.random(_N),
    })

    def run(sess):
        out = sess.create_dataframe(df).group_by("k").agg(
            F.sum(col("v")).alias("s"), F.count("*").alias("n"))
        return out.collect()

    got = run(_mesh_session(n_dev))
    want = run(Session({}))
    oracle = (df.groupby("k", dropna=False)["v"]
              .agg(["sum", "size"]).reset_index())
    oracle.columns = ["k", "s", "n"]
    _assert_triple(got, want, oracle, ["k"])


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_hash_join_mesh_matches_single_and_cpu(n_dev):
    rng = np.random.default_rng(211 + n_dev)
    left = pd.DataFrame({
        "k": rng.integers(0, 53, _N).astype(np.int64),
        "v": rng.random(_N),
    })
    right = pd.DataFrame({
        "k2": rng.integers(20, 80, 311).astype(np.int64),
        "w": rng.random(311),
    })

    def run(sess):
        return sess.create_dataframe(left).join(
            sess.create_dataframe(right), on=[("k", "k2")],
            how="inner").collect()

    got = run(_mesh_session(n_dev))
    want = run(Session({}))
    oracle = left.merge(right, left_on="k", right_on="k2", how="inner")
    oracle = oracle[["k", "v", "k2", "w"]]
    _assert_triple(got, want, oracle, ["k", "v", "w"])


@pytest.mark.parametrize("n_dev", [1, 2, 8])
def test_sort_mesh_matches_single_and_cpu(n_dev):
    rng = np.random.default_rng(307 + n_dev)
    df = pd.DataFrame({
        "a": rng.integers(0, 60, _N).astype(np.int64),
        "b": rng.random(_N),
    })

    def run(sess):
        return sess.create_dataframe(df).order_by(
            "a", "b", ascending=[True, False]).collect()

    got = run(_mesh_session(n_dev))
    want = run(Session({}))
    oracle = df.sort_values(["a", "b"], ascending=[True, False]) \
        .reset_index(drop=True)
    # ORDER BY compares positionally: no re-sort before comparing
    for c in ("a", "b"):
        np.testing.assert_allclose(got[c].to_numpy(np.float64),
                                   want[c].to_numpy(np.float64),
                                   rtol=1e-9)
        np.testing.assert_allclose(got[c].to_numpy(np.float64),
                                   oracle[c].to_numpy(np.float64),
                                   rtol=1e-9)


def test_spmd_mesh_8_shard_bitexact():
    """Join + group-by + sort of integers on the 8-shard mesh is
    BIT-equal to the single-device run: what happens inside the
    shard_map programs changes nothing observable."""
    r = np.random.default_rng(23)
    fact = pd.DataFrame({
        "k": r.integers(0, 40, _N).astype(np.int64),
        "v": r.integers(0, 1000, _N).astype(np.int64)})
    dim = pd.DataFrame({"k": np.arange(40, dtype=np.int64),
                        "w": (np.arange(40, dtype=np.int64) * 3) % 7})

    def run(s):
        try:
            s.create_temp_view("fact", s.create_dataframe(fact))
            s.create_temp_view("dim", s.create_dataframe(dim))
            return s.sql(
                "SELECT dim.w AS w, SUM(fact.v) AS sv, COUNT(*) AS c "
                "FROM fact JOIN dim ON fact.k = dim.k "
                "GROUP BY dim.w ORDER BY w").to_pandas()
        finally:
            s.stop()

    single, mesh = run(Session()), run(_mesh_session(8))
    oracle = fact.merge(dim, on="k").groupby("w").agg(
        sv=("v", "sum"), c=("v", "count")).reset_index()
    assert list(single.columns) == list(mesh.columns) == ["w", "sv", "c"]
    for c in single.columns:
        np.testing.assert_array_equal(single[c].to_numpy(),
                                      mesh[c].to_numpy(), err_msg=c)
        np.testing.assert_array_equal(single[c].to_numpy(),
                                      oracle[c].to_numpy(), err_msg=c)


def test_empty_partition_shards_match():
    """Fewer rows than devices: most mesh positions receive ZERO rows
    and the collectives must still line up (the all_to_all ships empty
    blocks + zero counts, not ragged shapes)."""
    df = pd.DataFrame({
        "k": np.array([3, 3, 7, 11, 7], dtype=np.int64),
        "v": np.array([0.5, 1.5, 2.5, 3.5, 4.5]),
    })

    def run(sess):
        return sess.create_dataframe(df).group_by("k").agg(
            F.sum(col("v")).alias("s"),
            F.count("*").alias("n")).collect()

    got = run(_mesh_session(8))
    want = run(Session({}))
    oracle = df.groupby("k")["v"].agg(["sum", "size"]).reset_index()
    oracle.columns = ["k", "s", "n"]
    _assert_triple(got, want, oracle, ["k"])


def test_skewed_keys_uneven_shards_match():
    """One hot key: after hash routing one device owns most rows while
    others are near-empty — per-device receive capacities and counts
    must absorb the skew."""
    rng = np.random.default_rng(43)
    k = np.where(rng.random(_N) < 0.8, 5,
                 rng.integers(0, 29, _N)).astype(np.int64)
    df = pd.DataFrame({"k": k, "v": rng.random(_N)})

    def run(sess):
        return sess.create_dataframe(df).group_by("k").agg(
            F.sum(col("v")).alias("s")).collect()

    got = run(_mesh_session(8))
    want = run(Session({}))
    oracle = df.groupby("k")["v"].sum().reset_index()
    oracle.columns = ["k", "s"]
    _assert_triple(got, want, oracle, ["k"])


# ---------------------------------------------------------------------------
# ShuffleExchangeExec: in-program mode is partition-for-partition
# interchangeable with the host path
# ---------------------------------------------------------------------------


def _rows_exec(parts):
    """A leaf exec yielding fixed in-memory batches per partition
    (``parts``: list of (keys, key_valid, vals) per input partition;
    an empty list means that partition produces nothing)."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
    from spark_rapids_tpu.columnar.column import Column
    from spark_rapids_tpu.execs.base import TpuExec

    class _Rows(TpuExec):
        def __init__(self):
            super().__init__([], Schema(["k", "v"],
                                        [dt.INT64, dt.FLOAT64]))

        @property
        def num_partitions(self):
            return len(parts)

        def execute(self, partition=0):
            for keys, kv, vals in parts[partition]:
                yield ColumnarBatch(
                    [Column.from_numpy(keys, dt.INT64, validity=kv),
                     Column.from_numpy(vals, dt.FLOAT64)], len(keys))

    return _Rows()


def _drain_exchange(ex):
    """partition -> multiset of (key_or_None, value) rows."""
    out = {}
    for p in range(ex.num_out_partitions):
        rows = []
        for b in ex.execute(p):
            pdf = b.to_pandas()
            for _, r in pdf.iterrows():
                key = r.iloc[0]
                key = None if pd.isna(key) else int(key)
                rows.append((key, float(r.iloc[1])))
        out[p] = sorted(rows, key=lambda t: (t[0] is None, t[0], t[1]))
    return out


def test_exchange_in_program_matches_host_path():
    """NUM_OUT != n_dev, null keys, an empty input partition: the
    in-program exchange must land every row in EXACTLY the partition
    the host partition kernel picks — the contract that lets one
    sibling of a co-partitioned join flip in-program while the other
    stays on the host path."""
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.parallel.mesh import data_mesh

    rng = np.random.default_rng(59)

    def mk(n):
        keys = rng.integers(-40, 40, n).astype(np.int64)
        kv = rng.random(n) > 0.15  # null keys hash via _NULL_HASH
        vals = rng.random(n)
        return keys, kv, vals

    parts = [[mk(37), mk(23)], [], [mk(41)]]
    num_out = 5  # != 8 devices: pids wrap the mesh axis

    host = ShuffleExchangeExec(("hash", [0]), num_out, _rows_exec(parts))
    want = _drain_exchange(host)

    prog = ShuffleExchangeExec(("hash", [0]), num_out, _rows_exec(parts))
    prog.enable_in_program(data_mesh(8))
    got = _drain_exchange(prog)

    assert prog.in_program
    for p in range(num_out):
        assert got[p] == want[p], f"partition {p} diverged"
    # MapStatus sizes answer from the same blocks on both paths
    assert len(host.map_output_sizes()) == \
        len(prog.map_output_sizes()) == num_out


def test_exchange_in_program_all_rows_one_device():
    """Every key hashes to one pid: 7 of 8 devices receive nothing and
    one receives everything — the receive capacity must hold the full
    input (the _exchange cap covers worst-case skew)."""
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.parallel.mesh import data_mesh

    n = 193
    keys = np.full(n, 12345, dtype=np.int64)
    kv = np.ones(n, dtype=bool)
    vals = np.arange(n, dtype=np.float64)
    parts = [[(keys, kv, vals)]]

    host = ShuffleExchangeExec(("hash", [0]), 4, _rows_exec(parts))
    want = _drain_exchange(host)
    prog = ShuffleExchangeExec(("hash", [0]), 4, _rows_exec(parts))
    prog.enable_in_program(data_mesh(8))
    got = _drain_exchange(prog)
    assert got == want
    total = sum(len(v) for v in got.values())
    assert total == n


# ---------------------------------------------------------------------------
# fallback gates: every "no" is recorded with its reason
# ---------------------------------------------------------------------------


def test_fallback_disabled_knob():
    conf = RapidsConf({cfg.MESH_ENABLED.key: True,
                       cfg.SHUFFLE_IN_PROGRAM.key: False})
    before = spmd.fallback_snapshot()
    assert spmd.in_program_mesh(conf, "join") is None
    delta = spmd.fallback_delta(before)
    assert delta == {
        f"join: disabled by {cfg.SHUFFLE_IN_PROGRAM.key}": 1}


def test_fallback_cluster_mode_dcn():
    conf = RapidsConf({cfg.MESH_ENABLED.key: True,
                       cfg.CLUSTER_ENABLED.key: True})
    before = spmd.fallback_snapshot()
    assert spmd.in_program_mesh(conf, "exchange") is None
    (reason,) = spmd.fallback_delta(before)
    assert reason.startswith("exchange: cross-host DCN")


def test_fallback_non_uniform_reason_passthrough():
    conf = RapidsConf({cfg.MESH_ENABLED.key: True})
    before = spmd.fallback_snapshot()
    assert spmd.in_program_mesh(
        conf, "sort", keyed=False,
        reason_if_unkeyed="range partitioning routes host-side") is None
    (reason,) = spmd.fallback_delta(before)
    assert reason == ("sort: non-uniform: range partitioning routes "
                      "host-side")


def test_fallback_min_rows_floor():
    conf = RapidsConf({cfg.MESH_ENABLED.key: True,
                       cfg.SHUFFLE_IN_PROGRAM_MIN_ROWS.key: 1000})
    before = spmd.fallback_snapshot()
    assert spmd.in_program_mesh(conf, "groupby", est_rows=10) is None
    (reason,) = spmd.fallback_delta(before)
    assert "below" in reason and "10 < 1000" in reason
    # at/above the floor the mesh comes back
    assert spmd.in_program_mesh(conf, "groupby",
                                est_rows=5000) is not None


def test_fallback_mesh_not_requested_is_silent():
    """No mesh, no decision: nothing recorded (a single-device run must
    not spam 'fewer than 2 devices' for every exchange)."""
    before = spmd.fallback_snapshot()
    assert spmd.in_program_mesh(RapidsConf({}), "join") is None
    assert spmd.in_program_mesh(None, "join") is None
    assert spmd.fallback_delta(before) == {}


def test_override_walk_flips_only_eligible_exchanges():
    """plan/overrides._enable_in_program_exchanges: hash+numeric flips,
    string schema records its reason, disabled knob records its reason
    — and with no mesh nothing happens."""
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.plan.overrides import \
        _enable_in_program_exchanges

    def mk_ex():
        return ShuffleExchangeExec(("hash", [0]),
                                   4, _rows_exec([[]]))

    ex = mk_ex()
    _enable_in_program_exchanges(ex, RapidsConf({}))
    assert not ex.in_program  # no mesh requested

    conf = RapidsConf({cfg.MESH_ENABLED.key: True})
    ex = mk_ex()
    _enable_in_program_exchanges(ex, conf)
    assert ex.in_program and ex._in_program_mesh is not None

    before = spmd.fallback_snapshot()
    ex = mk_ex()
    off = RapidsConf({cfg.MESH_ENABLED.key: True,
                      cfg.SHUFFLE_IN_PROGRAM.key: False})
    _enable_in_program_exchanges(ex, off)
    assert not ex.in_program
    (reason,) = spmd.fallback_delta(before)
    assert "disabled" in reason


def test_override_walk_string_schema_falls_back():
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import Schema
    from spark_rapids_tpu.execs.base import TpuExec
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.plan.overrides import \
        _enable_in_program_exchanges

    class _StrLeaf(TpuExec):
        def __init__(self):
            super().__init__([], Schema(["k", "s"],
                                        [dt.INT64, dt.STRING]))

    ex = ShuffleExchangeExec(("hash", [0]), 4, _StrLeaf())
    before = spmd.fallback_snapshot()
    _enable_in_program_exchanges(
        ex, RapidsConf({cfg.MESH_ENABLED.key: True}))
    assert not ex.in_program
    (reason,) = spmd.fallback_delta(before)
    assert "string" in reason


# ---------------------------------------------------------------------------
# telemetry: the distributed stage attributes ONE launch with a program
# label naming the shuffle step
# ---------------------------------------------------------------------------


_TELEMETRY_SNIPPET = r"""
import json
import numpy as np
from spark_rapids_tpu.utils import dispatch as disp
disp.install()  # must precede compute-module imports (wraps jax.jit)
import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.parallel.mesh import data_mesh
from spark_rapids_tpu.parallel.shuffle import (
    distributed_batch_from_host, shuffle_step)

mesh = data_mesh(8)
rng = np.random.default_rng(3)
keys = rng.integers(0, 100, 500).astype(np.int64)
vals = rng.random(500)
datas, valids, counts, _ = distributed_batch_from_host(
    mesh, [keys, vals], [dt.INT64, dt.FLOAT64])
before = disp.stage_programs_snapshot()
step = shuffle_step(mesh, [dt.INT64, dt.FLOAT64], [0], 8)
out = step(datas, valids, counts)
import jax
jax.device_get(out[3])
print(json.dumps(disp.stage_program_delta(before)))
"""


def test_shuffle_step_program_label_attributed():
    import json
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-c", _TELEMETRY_SNIPPET], env=env,
        capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    delta = json.loads(out.stdout.strip().splitlines()[-1])
    labels = [lab for stage in delta.values() for lab in stage]
    assert any("_run_shuffle_step" in lab for lab in labels), delta
    # one compiled launch for the exchange, one device_get to read it
    jit_launches = sum(
        n for stage in delta.values() for lab, n in stage.items()
        if "_run_shuffle_step" in lab)
    assert jit_launches == 1, delta


# ---------------------------------------------------------------------------
# plan-level sync map: the in-program path has ZERO hidden host syncs
# ---------------------------------------------------------------------------


def test_mesh_plan_sync_map_names_every_sync():
    """Every sync a mesh plan pays is a NAMED boundary entry (leaf
    staging / result gather / root fetch); mesh-internal execs — whose
    exchanges run as in-program all_to_all — contribute nothing."""
    from spark_rapids_tpu.analysis.plan_sync import sync_map

    rng = np.random.default_rng(71)
    li = pd.DataFrame({
        "l_orderkey": rng.integers(0, 300, 2000).astype(np.int64),
        "l_quantity": rng.integers(1, 50, 2000).astype(np.int64),
    })
    ords = pd.DataFrame({
        "o_orderkey": np.arange(300, dtype=np.int64),
        "o_pri": rng.integers(0, 3, 300).astype(np.int64),
    })
    sess = _mesh_session(8, {"rapids.tpu.sql.autoBroadcastJoinThreshold": 0})
    sess.create_temp_view("lineitem", sess.create_dataframe(li))
    sess.create_temp_view("orders", sess.create_dataframe(ords))
    root = sess.sql(
        "SELECT o_pri, l_orderkey, SUM(l_quantity) AS q "
        "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
        "GROUP BY o_pri, l_orderkey "
        "ORDER BY q DESC, o_pri, l_orderkey")._exec()
    plan = root.tree_string()
    assert "MeshShuffledJoinExec" in plan, plan
    assert "MeshGroupByExec" in plan, plan

    entries = sync_map(root)
    named = {"duplicate-flag fetch", "result fetch",
             "mesh shard staging (leaf input)", "mesh result gather",
             "mesh exchange map-side staging"}
    for e in entries:
        assert e["kind"] in named, e

    mesh_entries = [e for e in entries if e["op"].startswith("Mesh")]
    # gathers appear EXACTLY at mesh->host boundaries (a mesh exec
    # whose consumer is non-mesh); a mesh exec feeding a mesh parent
    # hands DistributedBatch shards on-device and never gathers
    def walk(node, mesh_parent, out):
        is_mesh = type(node).__name__.startswith("Mesh")
        if is_mesh and not mesh_parent:
            out.append(type(node).__name__)
        for c in node.children:
            walk(c, is_mesh, out)
        return out

    boundary_ops = walk(root, False, [])
    gathers = sorted(e["op"] for e in mesh_entries
                     if e["kind"] == "mesh result gather")
    assert gathers == sorted(boundary_ops), (entries, boundary_ops)
    # the join feeds the mesh groupby directly: mesh-internal, so its
    # exchange is the in-program all_to_all — no gather entry for it
    assert not any(e["op"] == "MeshShuffledJoinExec" and
                   e["kind"] == "mesh result gather"
                   for e in mesh_entries), entries
    assert "MeshShuffledJoinExec" not in boundary_ops, plan
