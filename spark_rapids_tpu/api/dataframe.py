"""Lazy DataFrame over the engine-neutral plan tree."""
from __future__ import annotations

from typing import List, Optional, Sequence, Union

from spark_rapids_tpu.api.column import Column, _to_col, col
from spark_rapids_tpu.api.functions import AggColumn
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression)
from spark_rapids_tpu.ops.sortkeys import SortKeySpec
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.utils import tracing

ColumnOrName = Union[Column, str]


def _as_col(c: ColumnOrName) -> Column:
    return col(c) if isinstance(c, str) else c


class DataFrame:
    def __init__(self, plan: pn.PlanNode, session):
        self._plan = plan
        self.session = session

    # -- metadata ---------------------------------------------------------

    @property
    def schema(self) -> Schema:
        return self._plan.output_schema()

    @property
    def columns(self) -> List[str]:
        return list(self.schema.names)

    @property
    def dtypes(self):
        s = self.schema
        return [(n, t.name) for n, t in zip(s.names, s.types)]

    def __repr__(self) -> str:  # pragma: no cover
        return f"DataFrame[{', '.join(f'{n}: {t}' for n, t in self.dtypes)}]"

    # -- transformations --------------------------------------------------

    def _df(self, plan: pn.PlanNode) -> "DataFrame":
        return DataFrame(plan, self.session)

    def select(self, *cols: ColumnOrName) -> "DataFrame":
        schema = self.schema
        exprs: List[Expression] = []
        names: List[str] = []
        for i, c in enumerate(cols):
            cc = _as_col(c)
            e = cc.resolve(schema)
            names.append(cc.out_name(f"col{i}"))
            exprs.append(e.children[0] if isinstance(e, Alias) else e)
        return self._df(pn.ProjectNode(exprs, self._plan, names))

    def filter(self, condition: Column) -> "DataFrame":
        return self._df(pn.FilterNode(
            condition.resolve(self.schema), self._plan))

    where = filter

    def with_column(self, name: str, c: Column) -> "DataFrame":
        schema = self.schema
        exprs = [BoundReference(i, t)
                 for i, t in enumerate(schema.types)]
        names = list(schema.names)
        new = c.resolve(schema)
        if name in names:
            exprs[names.index(name)] = new
        else:
            exprs.append(new)
            names.append(name)
        return self._df(pn.ProjectNode(exprs, self._plan, names))

    withColumn = with_column

    def drop(self, *names: str) -> "DataFrame":
        keep = [n for n in self.columns if n not in names]
        return self.select(*keep)

    def create_or_replace_temp_view(self, name: str) -> None:
        """Register this DataFrame in the session catalog for
        Session.sql (Spark's createOrReplaceTempView)."""
        self.session.create_temp_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    def explode(self, *cols: ColumnOrName, value_name: str = "col",
                pos: bool = False, pos_name: str = "pos") -> "DataFrame":
        """explode/posexplode of a per-row array created from ``cols``
        (the GenerateExec surface — the reference supports exactly
        explode(array(...)), GpuGenerateExec.scala). Every original
        column is kept; each input row emits len(cols) rows."""
        schema = self.schema
        exprs = []
        for c in cols:
            e = _as_col(c).resolve(schema)
            exprs.append(e.children[0] if isinstance(e, Alias) else e)
        return self._df(pn.GenerateNode(
            exprs, self._plan, list(range(len(schema.names))),
            value_name=value_name, include_pos=pos, pos_name=pos_name))

    def group_by(self, *cols: ColumnOrName) -> "GroupedData":
        return GroupedData(self, [_as_col(c) for c in cols],
                           [c if isinstance(c, str) else c.out_name(None)
                            for c in cols])

    groupBy = group_by

    def agg(self, *aggs: AggColumn) -> "DataFrame":
        return GroupedData(self, [], []).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner"
             ) -> "DataFrame":
        how = {"leftsemi": "left_semi", "left_semi": "left_semi",
               "leftanti": "left_anti", "left_anti": "left_anti",
               "leftouter": "left", "rightouter": "right",
               "outer": "full", "fullouter": "full",
               "full_outer": "full"}.get(how, how)
        if how == "cross" or on is None:
            return self._df(pn.JoinNode("cross", self._plan, other._plan,
                                        [], []))
        ls, rs = self.schema, other.schema
        if isinstance(on, str):
            on = [on]
        lk, rk = [], []
        for o in on:
            if isinstance(o, tuple):
                lname, rname = o
            else:
                lname = rname = o
            lk.append(ls.index_of(lname))
            rk.append(rs.index_of(rname))
        return self._df(pn.JoinNode(how, self._plan, other._plan, lk, rk))

    def order_by(self, *cols: ColumnOrName,
                 ascending: Union[bool, Sequence[bool]] = True
                 ) -> "DataFrame":
        schema = self.schema
        if isinstance(ascending, bool):
            asc = [ascending] * len(cols)
        else:
            asc = list(ascending)
        specs = []
        for c, a in zip(cols, asc):
            e = _as_col(c).resolve(schema)
            if not isinstance(e, BoundReference):
                raise ValueError(
                    "order_by requires plain columns; project computed "
                    "keys first (with_column)")
            specs.append(SortKeySpec.spark_default(e.ordinal,
                                                   ascending=a))
        return self._df(pn.SortNode(specs, self._plan))

    sort = order_by
    orderBy = order_by

    def limit(self, n: int) -> "DataFrame":
        return self._df(pn.LimitNode(n, self._plan))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._df(pn.UnionNode([self._plan, other._plan]))

    unionAll = union

    def distinct(self) -> "DataFrame":
        schema = self.schema
        grouping = [BoundReference(i, t)
                    for i, t in enumerate(schema.types)]
        return self._df(pn.AggregateNode(
            grouping, [], self._plan,
            grouping_names=list(schema.names)))

    def map_in_pandas(self, fn, schema: Schema) -> "DataFrame":
        from spark_rapids_tpu.execs.python_exec import MapInPandasNode

        return self._df(MapInPandasNode(fn, schema, self._plan))

    mapInPandas = map_in_pandas

    def cache(self) -> "DataFrame":
        """Persist results as spillable device batches (HBM while it
        fits, host/disk under pressure — unlike the reference, which
        routes .cache() through the host-side Spark cache)."""
        from spark_rapids_tpu.execs.cache import CacheNode

        if isinstance(self._plan, CacheNode):
            return self
        return self._df(CacheNode(self._plan))

    persist = cache

    def unpersist(self) -> "DataFrame":
        from spark_rapids_tpu.execs.cache import CacheNode

        if isinstance(self._plan, CacheNode):
            self._plan.holder.unpersist()
        return self

    def with_column_renamed(self, old: str, new: str) -> "DataFrame":
        names = [new if n == old else n for n in self.columns]
        return self.to_df(*names)

    withColumnRenamed = with_column_renamed

    def to_df(self, *names: str) -> "DataFrame":
        schema = self.schema
        assert len(names) == len(schema)
        exprs = [BoundReference(i, t)
                 for i, t in enumerate(schema.types)]
        return self._df(pn.ProjectNode(exprs, self._plan,
                                       names=list(names)))

    toDF = to_df

    def fillna(self, value, subset: Optional[Sequence[str]] = None
               ) -> "DataFrame":
        """Replace NULLs with ``value`` in type-compatible columns
        (pyspark DataFrameNaFunctions.fill)."""
        from spark_rapids_tpu.columnar import dtypes as dt
        from spark_rapids_tpu.expressions.conditional import Coalesce
        from spark_rapids_tpu.expressions.base import Literal

        schema = self.schema
        exprs: List[Expression] = []
        for i, (name, typ) in enumerate(zip(schema.names,
                                            schema.types)):
            e: Expression = BoundReference(i, typ)
            applies = subset is None or name in subset
            compat = (
                (isinstance(value, bool) and typ is dt.BOOLEAN) or
                (isinstance(value, (int, float)) and
                 not isinstance(value, bool) and typ.is_numeric) or
                (isinstance(value, str) and typ is dt.STRING))
            if applies and compat:
                e = Coalesce([e, Literal(
                    typ.np_dtype.type(value).item()
                    if typ.is_numeric and not isinstance(value, bool)
                    else value, typ)])
            exprs.append(e)
        return self._df(pn.ProjectNode(exprs, self._plan,
                                       names=list(schema.names)))

    def dropna(self, how: str = "any",
               subset: Optional[Sequence[str]] = None) -> "DataFrame":
        """Drop rows with NULLs (pyspark DataFrameNaFunctions.drop)."""
        from spark_rapids_tpu.expressions import predicates as pr

        schema = self.schema
        cols = [i for i, n in enumerate(schema.names)
                if subset is None or n in subset]
        if not cols:
            return self
        terms = [pr.IsNotNull(BoundReference(i, schema.types[i]))
                 for i in cols]
        cond = terms[0]
        for t in terms[1:]:
            cond = pr.And(cond, t) if how == "any" else pr.Or(cond, t)
        return self._df(pn.FilterNode(cond, self._plan))

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        """Bernoulli row sample via the counter-based rand stream
        (nondeterministic vs Spark's sampler, so it rides the same
        incompatibleOps gate as rand())."""
        from spark_rapids_tpu.expressions import predicates as pr
        from spark_rapids_tpu.expressions.base import Literal
        from spark_rapids_tpu.expressions.nondeterministic import Rand

        return self._df(pn.FilterNode(
            pr.LessThan(Rand(seed), Literal(float(fraction))),
            self._plan))

    def describe(self, *cols: str):
        """count/mean/min/max summary of numeric columns (collected)."""
        from spark_rapids_tpu.api import functions as F

        schema = self.schema
        targets = [n for n, t in zip(schema.names, schema.types)
                   if t.is_numeric and (not cols or n in cols)]
        aggs = []
        for n in targets:
            aggs += [F.count(col(n)).alias(f"count({n})"),
                     F.avg(col(n)).alias(f"mean({n})"),
                     F.min(col(n)).alias(f"min({n})"),
                     F.max(col(n)).alias(f"max({n})")]
        return self.agg(*aggs).collect()

    def coalesce(self, num_partitions: int) -> "DataFrame":
        """Shrink partition count without a shuffle."""
        return self._df(pn.CoalescePartitionsNode(num_partitions,
                                                  self._plan))

    def repartition(self, num_partitions: int,
                    *cols: ColumnOrName) -> "DataFrame":
        schema = self.schema
        if cols:
            ordinals = []
            for c in cols:
                e = _as_col(c).resolve(schema)
                assert isinstance(e, BoundReference), \
                    "repartition keys must be plain columns"
                ordinals.append(e.ordinal)
            part = ("hash", ordinals)
        else:
            part = ("round_robin",)
        return self._df(pn.ShuffleExchangeNode(part, num_partitions,
                                               self._plan))

    # -- actions ----------------------------------------------------------

    def _exec(self):
        from spark_rapids_tpu.plan.overrides import apply_overrides

        self._last_exec = apply_overrides(self._plan, self.session.conf)
        return self._last_exec

    def _run(self, plan_exec):
        """The root of a query: plan (``plan_exec`` returns the physical
        tree), run every partition, fetch the frame; then, fetched or
        raised, the blocks the tree's exchanges registered for this
        execution end with it (``execs/exchange.close_query_blocks``)."""
        from spark_rapids_tpu.execs.base import collect
        from spark_rapids_tpu.execs.exchange import close_query_blocks

        with tracing.QueryRange() as query:
            exec_ = plan_exec()
            try:
                out = collect(exec_, conf=self.session.conf)
            finally:
                close_query_blocks(exec_)
        self._last_query = query.query_id
        return out

    def collect(self):
        return self._run(self._exec)

    def collect_async(self, tenant: str = "default", priority: int = 0,
                      deadline=None):
        """Submit through the session's QueryService (service/):
        returns a QueryHandle immediately; ``handle.result()`` blocks.
        Many collect_async() calls run concurrently under admission
        control + fair stage scheduling instead of serializing."""
        return self.session.service.submit(
            self, tenant=tenant, priority=priority, deadline=deadline)

    collectAsync = collect_async

    def last_metrics(self) -> dict:
        """Per-operator metrics of the most recent collect() — the SQL-UI
        SQLMetrics view (GpuExec.scala:90-96): rows/batches/self-time."""
        exec_ = getattr(self, "_last_exec", None)
        if exec_ is None:
            return {}
        return {name: {"rows": m.num_output_rows,
                       "batches": m.num_output_batches,
                       "op_time_ms": round(m.op_time_ns / 1e6, 3)}
                for name, m in exec_.all_metrics().items()}

    def last_profile(self) -> dict:
        """Where the host time of the most recent collect()/count() went:
        its span tree from the ``query`` root down (utils/tracing.profile:
        planning, every exec's batch pulls, launches, waits, the result
        fetch), each node with its self time. Empty unless
        ``utils/dispatch.install()`` ran before the engine was imported,
        and once 64 later queries have pushed it out."""
        return tracing.profile(getattr(self, "_last_query", None))

    to_pandas = collect
    toPandas = collect

    def count(self) -> int:
        from spark_rapids_tpu.expressions import aggregates as A

        plan = pn.AggregateNode(
            [], [pn.AggCall(A.Count(None), "count")], self._plan)
        from spark_rapids_tpu.plan.overrides import apply_overrides

        df = self._run(lambda: apply_overrides(plan, self.session.conf))
        return int(df["count"].iloc[0])

    def show(self, n: int = 20) -> None:  # pragma: no cover - console
        print(self.limit(n).collect().to_string(index=False))

    def explain(self) -> str:
        """Tag/convert report (spark.rapids.sql.explain analogue)."""
        from spark_rapids_tpu.plan.overrides import explain

        return explain(self._plan, self.session.conf)

    @property
    def write(self) -> "DataFrameWriter":
        return DataFrameWriter(self)


class GroupedData:
    def __init__(self, df: DataFrame, keys: List[Column],
                 key_names: List[Optional[str]]):
        self.df = df
        self.keys = keys
        self.key_names = key_names

    def agg(self, *aggs: AggColumn) -> DataFrame:
        schema = self.df.schema
        grouping = []
        gnames = []
        for i, (k, nm) in enumerate(zip(self.keys, self.key_names)):
            e = k.resolve(schema)
            grouping.append(e.children[0] if isinstance(e, Alias) else e)
            gnames.append(nm or k.out_name(f"key{i}"))
        calls = []
        for i, a in enumerate(aggs):
            assert isinstance(a, AggColumn), \
                "group_by().agg takes aggregate functions"
            calls.append(pn.AggCall(a.make(schema),
                                    a.out_name(f"agg{i}")))
        return self.df._df(pn.AggregateNode(
            grouping, calls, self.df._plan, grouping_names=gnames))

    def count(self) -> DataFrame:
        from spark_rapids_tpu.api import functions as F

        return self.agg(F.count("*").alias("count"))

    def apply_in_pandas(self, fn, schema: Schema) -> DataFrame:
        """groupBy(keys).applyInPandas: ``fn`` maps each group's pandas
        frame to a frame with ``schema``."""
        from spark_rapids_tpu.execs.python_exec import \
            GroupedMapInPandasNode

        return self.df._df(GroupedMapInPandasNode(
            self._key_ordinals(), fn, schema, self.df._plan))

    applyInPandas = apply_in_pandas

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        return CoGroupedData(self, other)

    def _key_ordinals(self) -> List[int]:
        schema = self.df.schema
        out = []
        for k in self.keys:
            e = k.resolve(schema)
            assert isinstance(e, BoundReference), \
                "grouped/cogrouped pandas keys must be plain columns"
            out.append(e.ordinal)
        return out


class CoGroupedData:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        assert len(left.keys) == len(right.keys)
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema: Schema) -> DataFrame:
        from spark_rapids_tpu.execs.python_exec import \
            CoGroupedMapInPandasNode

        return self.left.df._df(CoGroupedMapInPandasNode(
            self.left.df._plan, self.right.df._plan,
            self.left._key_ordinals(), self.right._key_ordinals(),
            fn, schema))

    applyInPandas = apply_in_pandas

    def _shortcut(self, fn_name: str, *cols: str) -> DataFrame:
        from spark_rapids_tpu.api import functions as F

        fn = getattr(F, fn_name)
        targets = cols or [n for n, t in zip(self.df.schema.names,
                                             self.df.schema.types)
                           if t.is_numeric]
        return self.agg(*[fn(col(c)).alias(f"{fn_name}({c})")
                          for c in targets])

    def sum(self, *cols: str) -> DataFrame:
        return self._shortcut("sum", *cols)

    def min(self, *cols: str) -> DataFrame:
        return self._shortcut("min", *cols)

    def max(self, *cols: str) -> DataFrame:
        return self._shortcut("max", *cols)

    def avg(self, *cols: str) -> DataFrame:
        return self._shortcut("avg", *cols)

    mean = avg


class DataFrameWriter:
    def __init__(self, df: DataFrame):
        self.df = df
        self._mode = "overwrite"
        self._partition_by: List[str] = []

    def mode(self, m: str) -> "DataFrameWriter":
        self._mode = {"overwrite": "overwrite",
                      "error": "error",
                      "errorifexists": "error"}[m]
        return self

    def partition_by(self, *cols: str) -> "DataFrameWriter":
        self._partition_by = list(cols)
        return self

    partitionBy = partition_by

    def _write(self, path: str, fmt: str):
        from spark_rapids_tpu.io.write import WriteFilesNode
        from spark_rapids_tpu.plan.overrides import apply_overrides

        node = WriteFilesNode(self.df._plan, path, format=fmt,
                              partition_by=self._partition_by,
                              mode=self._mode)
        return self.df._run(
            lambda: apply_overrides(node, self.df.session.conf))

    def parquet(self, path: str):
        return self._write(path, "parquet")

    def orc(self, path: str):
        return self._write(path, "orc")
