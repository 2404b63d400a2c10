"""Benchmark-suite smoke tests (TpchLikeSparkSuite analogue: every query
runs on the accelerated path and matches the CPU oracle at tiny SF)."""
import json

import pytest

from spark_rapids_tpu.benchmarks import datagen, tpcds, tpch
from spark_rapids_tpu.benchmarks.runner import BenchmarkRunner
from spark_rapids_tpu.config import RapidsConf

from tests.compare import assert_cpu_and_tpu_equal

SF = 0.001


def _tiered(queries, smoke_pick):
    """One representative query per TPC family stays in the smoke tier;
    the rest of the matrix is the nightly `full` tier (VERDICT r3 #8:
    the 140-query matrix outgrew the per-push window)."""
    return [q if q == smoke_pick else
            pytest.param(q, marks=pytest.mark.full)
            for q in sorted(queries)]


#: tests between two sheddings of jax's in-process caches
_SHED_EVERY = 8
_since_shed = 0


@pytest.fixture(autouse=True)
def _shed_jit_memory():
    """The 140 benchmark queries compile thousands of x64 CPU
    executables; jax's in-process caches retain every one (about 60 MB
    a TPC-DS query) and the suite process once ended in a segfault
    inside XLA compile (memory exhaustion). Clearing every few tests
    keeps the process bounded and still lets neighbouring queries
    share the programs and traces they have in common — reloads come
    from the persistent on-disk cache."""
    global _since_shed
    yield
    _since_shed += 1
    if _since_shed < _SHED_EVERY:
        return
    _since_shed = 0
    import jax

    jax.clear_caches()
    from spark_rapids_tpu.expressions import compiler as _c

    _c._FUSED_CACHE.clear()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpch")
    datagen.write_tables(str(d), SF)
    return str(d)


@pytest.fixture(scope="module")
def tpcds_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("tpcds")
    tpcds.write_tables(str(d), SF)
    return str(d)


@pytest.mark.parametrize("query", _tiered(tpch.QUERIES, "q6"))
def test_query_on_tpu_matches_oracle(data_dir, query):
    plan = tpch.QUERIES[query](data_dir)
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6)


# rank() over FLOAT aggregates: tie-breaks are implementation-defined
# (engines may round same-set sums to different last ulps — the rollup
# levels each re-aggregate the same rows). For these queries the rank
# column is checked SEMANTICALLY per engine (ordering + tie
# consistency vs its own sums) instead of bit-compared across engines;
# the reference documents the same float-agg nondeterminism
# (its variableFloatAgg opt-in exists for exactly this).
_RANK_OVER_FLOAT = {
    "tpcds_q67": {"rk": (["i_category"], "sumsales")},
}


@pytest.mark.parametrize("query", _tiered(tpcds.QUERIES, "q3"))
def test_tpcds_query_on_tpu_matches_oracle(tpcds_dir, query):
    plan = tpcds.QUERIES[query](tpcds_dir)
    # several TPC-DS queries cross-join 1-row aggregate subqueries
    # (q9/q28/q88/q90 buckets, scalar subqueries); the brute-force join
    # is default-off like the reference (GpuOverrides.scala:1837-1856) —
    # the suite opts in exactly as the reference's integration tests do
    conf = RapidsConf({
        "rapids.tpu.sql.test.enabled": True,
        "rapids.tpu.sql.exec.BroadcastNestedLoopJoinExec": True,
        "rapids.tpu.sql.exec.CartesianProductExec": True,
    })
    assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6,
                             rank_over=_RANK_OVER_FLOAT.get(query))


@pytest.fixture(scope="module")
def tpcxbb_dir(tmp_path_factory):
    from spark_rapids_tpu.benchmarks import tpcxbb

    d = tmp_path_factory.mktemp("tpcxbb")
    tpcxbb.write_tables(str(d), SF)
    return str(d)


def _tpcxbb_queries():
    from spark_rapids_tpu.benchmarks import tpcxbb

    return sorted(tpcxbb.QUERIES)


@pytest.mark.parametrize("query", _tiered(_tpcxbb_queries(), "q7"))
def test_tpcxbb_query_on_tpu_matches_oracle(tpcxbb_dir, query):
    from spark_rapids_tpu.benchmarks import tpcxbb

    plan = tpcxbb.QUERIES[query](tpcxbb_dir)
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6)


def test_q1_returns_flag_groups(data_dir):
    from spark_rapids_tpu.execs.base import collect
    from spark_rapids_tpu.plan.overrides import apply_overrides

    df = collect(apply_overrides(tpch.QUERIES["tpch_q1"](data_dir),
                                 RapidsConf()))
    # 3 return flags x 2 line statuses
    assert len(df) == 6
    assert df["count_order"].astype(int).sum() > 0


def test_runner_json_output(data_dir, capsys):
    from spark_rapids_tpu.benchmarks import runner as runner_mod

    runner_mod.main(["--benchmark", "tpch_q6", "--sf", str(SF),
                     "--iterations", "2", "--warmup", "1", "--compare",
                     "--data-dir", data_dir])
    out = capsys.readouterr().out
    result = json.loads(out)
    assert result["benchmark"] == "tpch_q6"
    assert len(result["iterations"]) == 2
    assert result["compare"]["matches_cpu"], result["compare"]["detail"]
    assert "query_plan" in result and "metrics" in result
    assert result["env"]["device_count"] >= 1


def test_mortgage_etl_matches_oracle(tmp_path):
    from spark_rapids_tpu.benchmarks import mortgage

    mortgage.gen_tables(str(tmp_path), sf=0.005)
    plan = mortgage.etl(str(tmp_path))
    conf = RapidsConf({"rapids.tpu.sql.test.enabled": True})
    assert_cpu_and_tpu_equal(plan, conf=conf, approx_float=1e-6,
                             sort=False)


def test_mortgage_through_runner(tmp_path, capsys):
    import json as _json

    from spark_rapids_tpu.benchmarks import runner as runner_mod

    runner_mod.main(["--benchmark", "mortgage_etl", "--sf", "0.003",
                     "--iterations", "1", "--warmup", "0", "--compare",
                     "--data-dir", str(tmp_path / "m")])
    result = _json.loads(capsys.readouterr().out)
    assert result["compare"]["matches_cpu"], result["compare"]["detail"]
    assert result["rows_returned"] >= 1


def test_wide_shuffle_bench_on_mesh():
    """BASELINE config #4 smoke: the wide-shuffle benchmark runs over the
    8-device mesh and the exchanged aggregate is exact."""
    from spark_rapids_tpu.benchmarks.shuffle_bench import run

    result = run(rows=20_000, n_keys=512, n_devices=8, iterations=1,
                 warmup=1)
    assert result["devices"] == 8
    assert result["groups"] == 512
    assert result["sum_ok"]
    assert result["rows_per_sec"] > 0
