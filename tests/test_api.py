"""DataFrame API tests: the user-facing surface, oracle-checked against
pandas directly (not just the CPU engine) so the API semantics themselves
are pinned."""
import numpy as np
import pandas as pd
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import Session, col, functions as F, lit, when
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import Schema

from tests.compare import assert_frames_equal


@pytest.fixture()
def session():
    return Session()


@pytest.fixture()
def pdf():
    rng = np.random.default_rng(0)
    n = 400
    return pd.DataFrame({
        "k": rng.integers(0, 10, n),
        "v": rng.random(n) * 100,
        "s": [f"name{int(i) % 4}" for i in rng.integers(0, 100, n)],
    })


@pytest.fixture()
def df(session, pdf):
    return session.create_dataframe(pdf)


def _sorted(df):
    return df.sort_values(list(df.columns)).reset_index(drop=True)


def test_select_and_arithmetic(df, pdf):
    out = df.select("k", (col("v") * 2 + 1).alias("v2")).collect()
    assert list(out.columns) == ["k", "v2"]
    np.testing.assert_allclose(out["v2"].astype(float),
                               pdf["v"] * 2 + 1, rtol=1e-12)


def test_filter_where(df, pdf):
    out = df.filter((col("v") > 50) & (col("k") != 3)).collect()
    expect = pdf[(pdf.v > 50) & (pdf.k != 3)]
    assert len(out) == len(expect)


def test_group_by_agg(df, pdf):
    out = (df.group_by("k")
             .agg(F.sum(col("v")).alias("sv"),
                  F.count("*").alias("n"),
                  F.avg(col("v")).alias("av"))
             .order_by("k").collect())
    expect = pdf.groupby("k").agg(
        sv=("v", "sum"), n=("v", "size"), av=("v", "mean")).reset_index()
    np.testing.assert_allclose(out["sv"].astype(float), expect["sv"],
                               rtol=1e-9)
    assert list(out["n"].astype(int)) == list(expect["n"])


def test_join(session, pdf):
    left = session.create_dataframe(pdf)
    dim = session.create_dataframe(pd.DataFrame(
        {"k2": range(10), "label": [f"L{i}" for i in range(10)]}))
    out = left.join(dim, on=[("k", "k2")], how="inner").collect()
    assert len(out) == len(pdf)
    assert set(out.columns) == {"k", "v", "s", "k2", "label"}


def test_with_column_and_drop(df, pdf):
    out = (df.with_column("flag", when(col("v") > 50, "hi")
                          .otherwise("lo"))
             .drop("s").collect())
    assert list(out.columns) == ["k", "v", "flag"]
    expect = np.where(pdf.v > 50, "hi", "lo")
    assert list(out["flag"]) == list(expect)


def test_order_by_limit(df, pdf):
    out = df.order_by("v", ascending=False).limit(5).collect()
    expect = pdf.sort_values("v", ascending=False).head(5)
    np.testing.assert_allclose(out["v"].astype(float), expect["v"],
                               rtol=1e-12)


def test_distinct_union_count(session):
    a = session.create_dataframe({"x": [1, 2, 2, 3]})
    b = session.create_dataframe({"x": [3, 4]})
    u = a.union(b)
    assert u.count() == 6
    d = sorted(u.distinct().collect()["x"].astype(int))
    assert d == [1, 2, 3, 4]


def test_string_functions(df, pdf):
    out = df.select(
        F.upper(col("s")).alias("u"),
        F.length(col("s")).alias("ln"),
        col("s").contains("3").alias("c3")).collect()
    assert list(out["u"]) == [s.upper() for s in pdf["s"]]
    assert list(out["ln"].astype(int)) == [len(s) for s in pdf["s"]]
    assert list(out["c3"].astype(bool)) == ["3" in s for s in pdf["s"]]


def test_cast_and_between(df, pdf):
    out = df.select(
        col("v").cast(dt.INT64).alias("vi"),
        col("v").between(25, 75).alias("mid")).collect()
    assert list(out["vi"].astype(int)) == [int(v) for v in pdf["v"]]
    assert list(out["mid"].astype(bool)) == \
        [(25 <= v <= 75) for v in pdf["v"]]


def test_nulls_through_api(session):
    pdf = pd.DataFrame({"a": [1.0, None, 3.0], "b": ["x", None, "z"]})
    df = session.create_dataframe(pdf)
    out = df.select(col("a").is_null().alias("an"),
                    F.coalesce(col("a"), lit(-1.0)).alias("af")).collect()
    assert list(out["an"].astype(bool)) == [False, True, False]
    assert [float(v) for v in out["af"]] == [1.0, -1.0, 3.0]


def test_read_write_roundtrip(session, tmp_path, pdf):
    src = tmp_path / "in.parquet"
    pq.write_table(pa.Table.from_pandas(pdf), src)
    df = session.read.parquet(str(src))
    stats = (df.filter(col("v") > 10).write
             .partition_by("k").parquet(str(tmp_path / "out")))
    assert stats["num_rows"].astype(int).sum() == int((pdf.v > 10).sum())
    back = session.read.parquet(str(tmp_path / "out")).collect()
    assert len(back) == int((pdf.v > 10).sum())


def test_explain_reports_plan(df):
    text = df.filter(col("v") > 0).explain()
    assert "Filter" in text and "Scan" in text
    assert text.lstrip().startswith("*"), "plan should be on TPU"


def test_udf_through_api(session):
    df = session.create_dataframe({"x": list(range(20))})
    triple = F.udf(lambda x: x * 3, dt.INT64)
    out = df.select(triple(col("x")).alias("t")).collect()
    assert list(out["t"].astype(int)) == [3 * i for i in range(20)]


def test_range_and_agg_global(session):
    df = session.range(100)
    out = df.agg(F.sum(col("id")).alias("s"),
                 F.count("*").alias("n")).collect()
    assert int(out["s"].iloc[0]) == 4950
    assert int(out["n"].iloc[0]) == 100


def test_api_matches_cpu_engine(df):
    """Whole-pipeline equality through both engines (the reference's
    golden comparison applied to the API layer)."""
    from spark_rapids_tpu.cpu.engine import execute_cpu

    pipeline = (df.filter(col("v") > 20)
                  .with_column("bucket", col("k") % 3)
                  .group_by("bucket")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.max(col("s")).alias("ms")))
    cpu_df = execute_cpu(pipeline._plan).to_pandas()
    assert_frames_equal(cpu_df, pipeline.collect(), approx_float=1e-9)


def test_cache_materializes_once(session, pdf):
    df = session.create_dataframe(pdf).filter(col("v") > 10).cache()
    a = df.collect()
    # mutate nothing; second collect must serve from the cache holder
    from spark_rapids_tpu.execs.cache import CacheNode

    assert isinstance(df._plan, CacheNode)
    assert df._plan.holder.is_materialized
    b = df.group_by("k").count().collect()
    assert b["count"].astype(int).sum() == len(a)
    df.unpersist()
    assert not df._plan.holder.is_materialized


def test_cache_survives_spill(session, pdf, tmp_path):
    from spark_rapids_tpu.memory.catalog import (BufferCatalog,
                                                 reset_catalog)

    cat = reset_catalog(BufferCatalog(spill_dir=str(tmp_path)))
    try:
        df = session.create_dataframe(pdf).cache()
        a = df.collect()
        assert cat.synchronous_spill(0) > 0   # evict HBM tier entirely
        assert cat.spill_host_to_disk(0) > 0  # and the host tier
        b = df.collect()
        assert_frames_equal(a, b)
    finally:
        reset_catalog(BufferCatalog())


def test_repartition_roundtrip(session, pdf):
    df = session.create_dataframe(pdf)
    r = df.repartition(4, "k")
    out = r.collect()
    assert len(out) == len(pdf)
    rr = df.repartition(3)
    assert len(rr.collect()) == len(pdf)


def test_coalesce_partitions(session, tmp_path, pdf):
    from spark_rapids_tpu.api import Session

    for k in range(6):
        pq.write_table(pa.Table.from_pandas(pdf.iloc[k * 60:(k + 1) * 60]),
                       tmp_path / f"f{k}.parquet")
    # a tiny reader byte target keeps the six small files as six scan
    # partitions (FilePartition packing would fold them into one,
    # leaving coalesce(2) nothing to do)
    s = Session(conf={"rapids.tpu.sql.reader.batchSizeBytes": 1024})
    df = s.read.parquet(str(tmp_path))
    c = df.coalesce(2)
    exec_ = c._exec()
    assert exec_.num_partitions == 2
    out = c.collect()
    assert len(out) == 360


def test_last_metrics_after_collect(df):
    pipe = df.filter(col("v") > 10).group_by("k").count()
    assert pipe.last_metrics() == {}
    pipe.collect()
    m = pipe.last_metrics()
    assert any("Aggregate" in k for k in m)
    agg_key = next(k for k in m if "Aggregate" in k)
    assert m[agg_key]["rows"] > 0


def test_last_metrics_two_execs_of_one_class(pdf):
    """q1's shape without fusion: the partial and the final aggregate are
    both HashAggregateExec; the second gets a suffix, neither is lost."""
    s = Session({"rapids.tpu.sql.fusion.enabled": False})
    pipe = (s.create_dataframe(pdf).repartition(3).group_by("k")
             .agg(F.sum(col("v")).alias("sv")))
    assert len(pipe.collect()) == pdf["k"].nunique()
    m = pipe.last_metrics()
    final, partial = m["HashAggregateExec"], m["HashAggregateExec#2"]
    assert final["rows"] == pdf["k"].nunique()
    assert partial["rows"] >= final["rows"] and partial["batches"] >= 1
    assert "ShuffleExchangeExec#2" in m
    assert len(m) == pipe._last_exec.tree_string().count("\n") + 1


def test_na_functions(session):
    pdf2 = pd.DataFrame({"a": [1.0, None, 3.0, None],
                         "s": ["x", None, "z", "w"],
                         "i": [10, 20, 30, 40]})
    df = session.create_dataframe(pdf2)
    filled = df.fillna(-1.0, subset=["a"]).collect()
    assert [float(v) for v in filled["a"]] == [1.0, -1.0, 3.0, -1.0]
    assert filled["s"][1] is None or pd.isna(filled["s"][1])
    fs = df.fillna("??").collect()
    assert list(fs["s"]) == ["x", "??", "z", "w"]
    assert pd.isna(fs["a"][1])  # numeric untouched by a string fill
    assert df.dropna().count() == 2           # rows 0 and 2
    assert df.dropna(subset=["a"]).count() == 2
    assert df.dropna(how="all").count() == 4  # 'i' is never null


def test_rename_and_todf(df):
    r = df.with_column_renamed("v", "value")
    assert r.columns == ["k", "value", "s"]
    t = df.to_df("c1", "c2", "c3")
    assert t.columns == ["c1", "c2", "c3"]
    assert len(t.collect()) == 400


def test_sample_and_describe(session, pdf):
    s2 = Session({"rapids.tpu.sql.incompatibleOps.enabled": True})
    df = s2.create_dataframe(pdf)
    frac = df.sample(0.3, seed=5).count() / len(pdf)
    assert 0.2 < frac < 0.4
    # deterministic per seed
    assert df.sample(0.3, seed=5).count() == \
        df.sample(0.3, seed=5).count()
    d = session.create_dataframe(pdf).describe("v")
    assert int(d["count(v)"].iloc[0]) == len(pdf)
    assert abs(float(d["mean(v)"].iloc[0]) - pdf.v.mean()) < 1e-9
