"""TpuOverrides: the plan-rewrite layer.

Reference: GpuOverrides.scala (rule registry, :536-1932), RapidsMeta.scala
(wrapper tree with tagging reasons, :66-832), GpuTransitionOverrides.scala
(transition/coalesce insertion). Flow (GpuOverrides.scala:1946-1964):

    wrap(plan) -> tag_for_tpu() (children first, with per-op config gates
    and type checks) -> explain -> convert_if_needed() -> coalesce/transition
    insertion.

Subtrees that cannot run on TPU execute on the CPU engine via
CpuFallbackExec; TPU-able children beneath a CPU node still accelerate —
their results cross the device boundary through a precomputed-frame source
(GpuBringBackToHost / HostColumnarToGpu analogues).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Type

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.execs import adaptive as adaptive_exec
from spark_rapids_tpu.execs import aggregate as agg_exec
from spark_rapids_tpu.execs import basic, batching, exchange, joins, sort, \
    window
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.expressions import aggregates as aggfn
from spark_rapids_tpu.expressions import arithmetic, bitwise, cast, \
    conditional, constraints, datetime as dtexpr, math as mathexpr, \
    nondeterministic, predicates, strings
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression, Literal)
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.utils.tracing import TraceRange


def _session_mesh(conf):
    from spark_rapids_tpu.parallel.mesh import session_mesh

    return session_mesh(conf)


def _in_program_mesh(conf, node, op, **kw):
    """The SPMD whole-stage gate (parallel/spmd.py): the mesh when this
    shuffle boundary folds into the compiled program as an in-program
    all_to_all, else None with the fallback reason recorded for run
    telemetry. Row estimates feed the inProgram.minRows floor.

    ``cluster_local=True`` because every caller here lowers a Mesh*Exec
    SUBTREE: in cluster mode the subtree ships to one executor whole and
    its collective spans only that process's local mesh — the DCN gate
    applies to the cross-process exchanges, not to these."""
    from spark_rapids_tpu.parallel import spmd
    from spark_rapids_tpu.plan.optimizer import estimate_rows

    est = None
    try:
        est = estimate_rows(node.children[0]) if node.children else None
    except Exception:  # estimation must never block planning
        est = None
    return spmd.in_program_mesh(conf, op, est_rows=est,
                                cluster_local=True, **kw)


def _cluster_mode(conf) -> bool:
    return conf is not None and conf.get(cfg.CLUSTER_ENABLED)

# ---------------------------------------------------------------------------
# Expression rule registry (ExprRule analogue, GpuOverrides.scala:536-1621)
# ---------------------------------------------------------------------------


class ExprRule:
    def __init__(self, klass: Type[Expression], incompat: bool = False,
                 desc: str = ""):
        self.klass = klass
        self.incompat = incompat
        self.flag = cfg.register_op_flag(
            "expression", klass.__name__,
            desc or f"TPU replacement of {klass.__name__}",
            incompat="TPU approximation differs in ulps from java.lang.Math"
            if incompat else None)

    def tag(self, e: Expression, meta: "NodeMeta", conf: RapidsConf):
        if not conf.get(self.flag) and not (
                self.incompat and conf.get(cfg.INCOMPATIBLE_OPS)):
            if self.incompat:
                meta.will_not_work(
                    f"expression {self.klass.__name__} is incompatible "
                    f"(enable {self.flag.key} or "
                    f"{cfg.INCOMPATIBLE_OPS.key})")
            else:
                meta.will_not_work(
                    f"expression {self.klass.__name__} disabled by "
                    f"{self.flag.key}")
        if isinstance(e, cast.Cast):
            self._tag_cast(e, meta, conf)
        tag_self = getattr(e, "tag_self", None)
        if tag_self is not None:
            # expression-specific gate (e.g. RegExpReplace's regex-free
            # pattern requirement)
            tag_self(meta, conf)

    @staticmethod
    def _tag_cast(e: cast.Cast, meta: "NodeMeta", conf: RapidsConf):
        src = e.children[0].dtype
        if src.is_floating and e.to is dt.STRING and \
                not conf.get(cfg.CAST_FLOAT_TO_STRING):
            meta.will_not_work(
                f"cast float->string needs {cfg.CAST_FLOAT_TO_STRING.key}")
        if src is dt.STRING and e.to.is_floating and \
                not conf.get(cfg.CAST_STRING_TO_FLOAT):
            meta.will_not_work(
                f"cast string->float needs {cfg.CAST_STRING_TO_FLOAT.key}")
        if src is dt.STRING and e.to is dt.TIMESTAMP and \
                not conf.get(cfg.CAST_STRING_TO_TIMESTAMP):
            meta.will_not_work(
                f"cast string->timestamp needs "
                f"{cfg.CAST_STRING_TO_TIMESTAMP.key}")


_EXPR_RULES: Dict[Type[Expression], ExprRule] = {}


def _register_exprs():
    import inspect

    for mod in (arithmetic, bitwise, predicates, conditional, constraints,
                mathexpr, dtexpr, nondeterministic, strings, cast, aggfn):
        for _, klass in inspect.getmembers(mod, inspect.isclass):
            if not issubclass(klass, Expression):
                continue
            if klass.__module__ != mod.__name__:
                continue
            if klass.__name__.startswith("_"):
                continue
            if vars(klass).get("abstract", False):  # own attr only:
                continue  # subclasses of an abstract template register
            incompat = bool(getattr(klass, "incompat", False))
            _EXPR_RULES[klass] = ExprRule(klass, incompat)
    for klass in (BoundReference, Literal, Alias):
        _EXPR_RULES[klass] = ExprRule(klass)


_register_exprs()


def tag_expression(e: Expression, meta: "NodeMeta", conf: RapidsConf):
    rule = _EXPR_RULES.get(type(e))
    if rule is None:
        meta.will_not_work(
            f"expression {type(e).__name__} has no TPU implementation")
        return
    rule.tag(e, meta, conf)
    for c in e.children:
        if c is not None:
            tag_expression(c, meta, conf)


# ---------------------------------------------------------------------------
# Node metas
# ---------------------------------------------------------------------------


class NodeMeta:
    """SparkPlanMeta analogue (RapidsMeta.scala:418): per-node tag state.

    A plan node OBJECT referenced from several tree positions (CTE
    reuse — plan_statement shares each CTE's plan node across its
    references) gets ONE meta and converts to ONE exec: exchanges and
    broadcasts under the shared subtree then materialize once for every
    consumer (Spark's ReuseExchange/ReuseSubquery role)."""

    def __init__(self, node: pn.PlanNode, conf: RapidsConf, _memo=None):
        self.node = node
        self.conf = conf
        _memo = {} if _memo is None else _memo
        self.children = [NodeMeta._shared(c, conf, _memo)
                         for c in node.children]
        self.reasons: List[str] = []
        self.rule = _NODE_RULES.get(type(node))
        self._converted: Optional[TpuExec] = None
        self._tagged = False

    @staticmethod
    def _shared(node: pn.PlanNode, conf: RapidsConf,
                memo: dict) -> "NodeMeta":
        hit = memo.get(id(node))
        if hit is None:
            hit = NodeMeta(node, conf, memo)
            memo[id(node)] = hit  # meta holds node: id stays pinned
        return hit

    def will_not_work(self, reason: str):
        if reason not in self.reasons:
            self.reasons.append(reason)

    @property
    def can_run(self) -> bool:
        return not self.reasons

    def tag_for_tpu(self):
        if self._tagged:
            return
        self._tagged = True
        for c in self.children:
            c.tag_for_tpu()
        if not self.conf.get(cfg.SQL_ENABLED):
            self.will_not_work(f"{cfg.SQL_ENABLED.key} is false")
            return
        if self.rule is None:
            self.will_not_work(
                f"node {self.node.name} has no TPU implementation")
            return
        flag = cfg.register_op_flag("exec", type(self.node).__name__,
                                    f"TPU replacement of {self.node.name}")
        if not self.conf.get(flag):
            self.will_not_work(f"exec disabled by {flag.key}")
            return
        self.rule.tag(self)

    def explain(self, indent: int = 0, only_not_on_tpu: bool = False
                ) -> str:
        mark = "*" if self.can_run else "!"
        line = "  " * indent + f"{mark} {self.node.describe()}"
        if self.reasons:
            line += "  <-- " + "; ".join(self.reasons)
        lines = [] if (only_not_on_tpu and self.can_run) else [line]
        for c in self.children:
            sub = c.explain(indent + 1, only_not_on_tpu)
            if sub:
                lines.append(sub)
        return "\n".join(lines)

    # -- conversion ----------------------------------------------------

    def convert(self) -> TpuExec:
        if self._converted is not None:
            return self._converted
        if self.can_run:
            tpu_children = [c.convert() for c in self.children]
            self._converted = self.rule.convert(self, tpu_children)
        else:
            self._converted = self._convert_fallback()
        return self._converted

    def _convert_fallback(self) -> TpuExec:
        """Run this node on the CPU engine. TPU-able children still
        accelerate: their device output crosses back through a
        precomputed-frame source."""
        tpu_subtrees: List[TpuExec] = []
        new_children: List[pn.PlanNode] = []
        for c in self.children:
            if c.can_run:
                child_exec = insert_coalesce(c.convert())
                tpu_subtrees.append(child_exec)
                new_children.append(pn.ScanNode(_DeferredTpuSource(
                    child_exec, c.node.output_schema())))
            else:
                new_children.append(c._fallback_plan())
        node = self.node.with_children(new_children) if self.children \
            else self.node
        return basic.CpuFallbackExec(node, self.node.output_schema(),
                                     self.reasons, tpu_subtrees)

    def _fallback_plan(self) -> pn.PlanNode:
        """Plan node for CPU execution with TPU-able descendants swapped
        for deferred device sources."""
        if self.can_run:
            child_exec = insert_coalesce(self.convert())
            return pn.ScanNode(_DeferredTpuSource(
                child_exec, self.node.output_schema()))
        if not self.children:
            return self.node
        return self.node.with_children(
            [c._fallback_plan() for c in self.children])


class _DeferredTpuSource(pn.DataSource):
    """DataSource over a TPU exec's (lazily collected) output — the
    GpuBringBackToHost boundary."""

    def __init__(self, exec_: TpuExec, schema: Schema):
        self.exec = exec_
        self._schema = schema

    def schema(self) -> Schema:
        return self._schema

    def read_host(self):
        import numpy as np

        from spark_rapids_tpu.execs.interop import batch_to_frame

        frames = []
        for p in range(self.exec.num_partitions):
            for b in self.exec.execute(p):
                if b.realized_num_rows() == 0:
                    continue
                frames.append(batch_to_frame(b, self._schema))
        data: Dict[str, np.ndarray] = {}
        validity: Dict[str, np.ndarray] = {}
        for i, name in enumerate(self._schema.names):
            typ = self._schema.types[i]
            if frames:
                data[name] = np.concatenate(
                    [f.cols[i].data for f in frames])
                validity[name] = np.concatenate(
                    [f.cols[i].valid_mask() for f in frames])
            else:
                data[name] = np.array(
                    [], dtype=object if typ is dt.STRING else typ.np_dtype)
                validity[name] = np.array([], dtype=bool)
        return data, validity


# ---------------------------------------------------------------------------
# Node rules (ExecRule analogue)
# ---------------------------------------------------------------------------


class NodeRule:
    def tag(self, meta: NodeMeta):
        pass

    def convert(self, meta: NodeMeta, children: List[TpuExec]) -> TpuExec:
        raise NotImplementedError


def _adaptive_read(ex: exchange.ShuffleExchangeExec,
                   conf: RapidsConf) -> TpuExec:
    """Wrap a multi-partition exchange in an adaptive coalescing reader
    (AQE's coalesce-shuffle-partitions applied with exact statistics).
    Works under cluster mode too: statistics come from the exchange's
    ``map_output_sizes`` — the cluster subclass answers from the
    MapOutputTracker's MapStatus sizes instead of an in-process block
    store (GpuShuffleExchangeExec.scala:95-101 map stats future)."""
    if not conf.get(cfg.ADAPTIVE_ENABLED) or ex.num_out_partitions <= 1:
        return ex
    return adaptive_exec.AdaptiveShuffleReaderExec(
        ex, conf.get(cfg.ADVISORY_PARTITION_SIZE))


def _check_types(meta: NodeMeta, types, what: str):
    for t in types:
        if not dt.is_supported(t):
            meta.will_not_work(f"{what}: type {t} not supported")


class _ScanRule(NodeRule):
    def tag(self, meta: NodeMeta):
        _check_types(meta, meta.node.output_schema().types, "scan")
        src = meta.node.source
        from spark_rapids_tpu.io.csv import CsvSource
        from spark_rapids_tpu.io.orc import OrcSource
        from spark_rapids_tpu.io.parquet import ParquetSource

        gates = {
            ParquetSource: (cfg.PARQUET_ENABLED, cfg.PARQUET_READ_ENABLED),
            OrcSource: (cfg.ORC_ENABLED, cfg.ORC_READ_ENABLED),
            CsvSource: (cfg.CSV_ENABLED, cfg.CSV_READ_ENABLED),
        }
        for klass, (fmt_flag, read_flag) in gates.items():
            if isinstance(src, klass):
                for flag in (fmt_flag, read_flag):
                    if not meta.conf.get(flag):
                        meta.will_not_work(
                            f"{klass.__name__} scan disabled by "
                            f"{flag.key}")
        # CSV timestamp compat gate (RapidsConf.scala:482 analogue):
        # timestamp text parses only under the configured formats, so
        # scans producing TIMESTAMP columns need the explicit opt-in
        if isinstance(src, CsvSource) and \
                not meta.conf.get(cfg.CSV_TIMESTAMPS_ENABLED) and \
                any(t is dt.TIMESTAMP
                    for t in meta.node.output_schema().types):
            meta.will_not_work(
                "CSV TIMESTAMP columns disabled by "
                f"{cfg.CSV_TIMESTAMPS_ENABLED.key} (formats gated by "
                f"{cfg.CSV_TIMESTAMP_FORMATS.key})")

    def convert(self, meta, children):
        node: pn.ScanNode = meta.node
        from spark_rapids_tpu.ml.handoff import DeviceBatchesSource

        if isinstance(node.source, DeviceBatchesSource):
            # already on device: serve as-is, no host round trip
            return basic.DeviceBatchesExec(node.source,
                                           node.output_schema())
        rows = meta.conf.get(cfg.MAX_READER_BATCH_SIZE_ROWS)
        # file sources default to DEFAULT_CONF: hand them the session
        # conf so reader knobs (split packing targets, read threads)
        # follow the session, not construction-time defaults. Only
        # before splits are derived — a source already being read
        # keeps the split layout it advertised.
        src = node.source
        if hasattr(src, "conf") and \
                getattr(src, "_splits", None) is None:
            src.conf = meta.conf
        return basic.ScanExec(node.source, node.output_schema(),
                              batch_rows=rows,
                              pack=meta.conf.get(cfg.SCAN_PACK_TRANSFERS))


class _WriteRule(NodeRule):
    def tag(self, meta: NodeMeta):
        from spark_rapids_tpu.io.write import WriteFilesNode

        node: WriteFilesNode = meta.node
        _check_types(meta, node.children[0].output_schema().types, "write")
        gates = {
            "parquet": (cfg.PARQUET_ENABLED, cfg.PARQUET_WRITE_ENABLED),
            "orc": (cfg.ORC_ENABLED, cfg.ORC_WRITE_ENABLED),
        }
        for flag in gates[node.format]:
            if not meta.conf.get(flag):
                meta.will_not_work(
                    f"{node.format} write disabled by {flag.key}")

    def convert(self, meta, children):
        from spark_rapids_tpu.io.write import WriteFilesExec

        return WriteFilesExec(meta.node, children[0])


class _RangeRule(NodeRule):
    def convert(self, meta, children):
        node: pn.RangeNode = meta.node
        return basic.RangeExec(node.start, node.end, node.step,
                               node.output_schema())


class _ProjectRule(NodeRule):
    def tag(self, meta: NodeMeta):
        for e in meta.node.exprs:
            tag_expression(e, meta, meta.conf)

    def convert(self, meta, children):
        node: pn.ProjectNode = meta.node
        return basic.ProjectExec(node.exprs, children[0],
                                 node.output_schema(), meta.conf)


class _FilterRule(NodeRule):
    def tag(self, meta: NodeMeta):
        tag_expression(meta.node.condition, meta, meta.conf)

    def convert(self, meta, children):
        return basic.FilterExec(meta.node.condition, children[0], meta.conf)


_SUPPORTED_AGGS = (aggfn.Min, aggfn.Max, aggfn.Sum, aggfn.Count,
                   aggfn.Average, aggfn.First, aggfn.Last,
                   aggfn.StddevSamp, aggfn.StddevPop,
                   aggfn.VarianceSamp, aggfn.VariancePop)


class _AggregateRule(NodeRule):
    def tag(self, meta: NodeMeta):
        node: pn.AggregateNode = meta.node
        for e in node.grouping:
            tag_expression(e, meta, meta.conf)
        for call in node.aggs:
            if not isinstance(call.fn, _SUPPORTED_AGGS):
                meta.will_not_work(
                    f"aggregate {type(call.fn).__name__} not implemented")
                continue
            if call.fn.distinct:
                meta.will_not_work("distinct aggregates fall back")
            if call.fn.input is not None:
                tag_expression(call.fn.input, meta, meta.conf)

    @staticmethod
    def _fuse_filter(child: TpuExec):
        """Aggregate-over-filter fuses the keep-mask into the groupby
        sort (one fewer compaction executable per batch)."""
        if isinstance(child, basic.FilterExec) and \
                child.filter.fused and \
                child.filter.condition.deterministic:
            return child.children[0], child.filter
        return child, None

    def convert(self, meta, children):
        """One exec over one partition, ``MeshGroupByExec`` where the
        mesh takes the boundary, else partial aggregate, exchange, final
        aggregate. That exchange is by key hash only where the partials
        live apart or the mesh arms it (cluster mode, the mesh conf).
        Everywhere else one process holds every partition on its device,
        where hashing, cutting and copying partials buys nothing: the
        exchange is a gather (``("single",)``, one final partition), as
        an unkeyed aggregate's always was, and what is planned above the
        final aggregate sees one partition. ``shuffle.partitions`` has no
        say here; it governs joins, windows and ``repartition()``."""
        node: pn.AggregateNode = meta.node
        child = children[0]
        out_schema = node.output_schema()
        if node.mode != "complete":
            child, ff = self._fuse_filter(child)
            return agg_exec.HashAggregateExec(
                node.grouping, node.aggs, child, out_schema,
                mode=node.mode, conf=meta.conf, fused_filter=ff)
        mesh = _in_program_mesh(
            meta.conf, node, "groupby", keyed=bool(node.grouping),
            reason_if_unkeyed="ungrouped aggregate funnels to one "
                              "device")
        if mesh is not None:
            # mesh lowering: the partial/exchange/final pipeline becomes
            # one all_to_all + local-groupby program per chip
            from spark_rapids_tpu.parallel.execs import MeshGroupByExec

            return MeshGroupByExec(node.grouping, node.aggs, child,
                                   out_schema, meta.conf, mesh)
        if child.num_partitions == 1:
            child, ff = self._fuse_filter(child)
            return agg_exec.HashAggregateExec(
                node.grouping, node.aggs, child, out_schema,
                mode="complete", conf=meta.conf, fused_filter=ff)
        # distributed: partial -> exchange -> final (the physical split
        # Spark's planner produces, aggregate.scala partial/final modes)
        pnames = list(node.grouping_names)
        ptypes = [e.dtype for e in node.grouping]
        for a in node.aggs:
            for j, pt in enumerate(a.fn.partial_types()):
                pnames.append(f"{a.name}#p{j}")
                ptypes.append(pt)
        partial_schema = Schema(pnames, ptypes)
        child, ff = self._fuse_filter(child)
        partial = agg_exec.HashAggregateExec(
            node.grouping, node.aggs, child, partial_schema,
            mode="partial", conf=meta.conf, fused_filter=ff)
        nkeys = len(node.grouping)
        if nkeys and (_cluster_mode(meta.conf) or
                      meta.conf.get(cfg.MESH_ENABLED)):
            # the partials live apart (a cluster's workers read
            # co-partitioned blocks) or the mesh arms this boundary
            # (_enable_in_program_exchanges): exchange by key
            ex = _adaptive_read(exchange.ShuffleExchangeExec(
                ("hash", list(range(nkeys))),
                min(cfg.resolve_shuffle_partitions(meta.conf),
                    max(child.num_partitions, 1)),
                partial,
                task_threads=meta.conf.get(cfg.TASK_THREADS)),
                meta.conf)
        else:
            # one process holds every partition on its device: a gather
            ex = exchange.ShuffleExchangeExec(
                ("single",), 1, partial,
                task_threads=meta.conf.get(cfg.TASK_THREADS))
        final_grouping = [BoundReference(i, e.dtype)
                          for i, e in enumerate(node.grouping)]
        return agg_exec.HashAggregateExec(
            final_grouping, node.aggs, ex, out_schema, mode="final",
            conf=meta.conf)


class _SortRule(NodeRule):
    def tag(self, meta: NodeMeta):
        _check_types(meta, meta.node.output_schema().types, "sort")

    def convert(self, meta, children):
        node: pn.SortNode = meta.node
        child = children[0]
        # a non-global sort has no exchange to fold — only ORDER BY
        # consults the SPMD gate (so no fallback noise for local sorts)
        mesh = _in_program_mesh(meta.conf, node, "sort") \
            if node.global_sort else None
        if mesh is not None:
            from spark_rapids_tpu.parallel.execs import MeshSortExec

            return MeshSortExec(node.specs, child,
                                node.output_schema(), meta.conf, mesh)
        if node.global_sort and child.num_partitions > 1:
            parts = min(cfg.resolve_shuffle_partitions(meta.conf),
                        child.num_partitions)
            if parts > 1:
                # distributed global sort: range-partition on sampled
                # bounds (full key tuples for multi-key sorts), then
                # sort each range-ordered partition — no
                # single-partition funnel (GpuRangePartitioning +
                # GpuSortExec, avoiding the SURVEY §5.7 cliff)
                child = exchange.ShuffleExchangeExec(
                    ("range", list(node.specs), None), parts, child,
                    task_threads=meta.conf.get(cfg.TASK_THREADS),
                    batch_bytes=meta.conf.get(cfg.BATCH_SIZE_BYTES))
            else:
                child = exchange.ShuffleExchangeExec(
                    ("single",), 1, child,
                    task_threads=meta.conf.get(cfg.TASK_THREADS))
        return sort.SortExec(
            node.specs, child, global_sort=node.global_sort,
            batch_bytes=meta.conf.get(cfg.BATCH_SIZE_BYTES))


class _LimitRule(NodeRule):
    def convert(self, meta, children):
        node: pn.LimitNode = meta.node
        child = children[0]
        limited = basic.LocalLimitExec(node.n, child)
        if node.global_limit and child.num_partitions > 1:
            ex = exchange.ShuffleExchangeExec(
                ("single",), 1, limited,
                task_threads=meta.conf.get(cfg.TASK_THREADS))
            return basic.LocalLimitExec(node.n, ex)
        return limited


class _UnionRule(NodeRule):
    def convert(self, meta, children):
        return basic.UnionExec(children, meta.node.output_schema())


class _ExpandRule(NodeRule):
    def tag(self, meta: NodeMeta):
        for p in meta.node.projections:
            for e in p:
                tag_expression(e, meta, meta.conf)

    def convert(self, meta, children):
        node: pn.ExpandNode = meta.node
        return basic.ExpandExec(node.projections, children[0],
                                node.output_schema(), meta.conf)


class _GenerateRule(NodeRule):
    """GpuGenerateExecSparkPlanMeta analogue: only explode/posexplode of a
    created array is supported (GpuGenerateExec.scala:66-82); lowering
    desugars the generator into Expand projections (one per array slot)
    so the existing ExpandExec kernel runs it."""

    def tag(self, meta: NodeMeta):
        node: pn.GenerateNode = meta.node
        for e in node.exprs:
            tag_expression(e, meta, meta.conf)
        _check_types(meta, node.output_schema().types, "generate")

    def convert(self, meta, children):
        node: pn.GenerateNode = meta.node
        return basic.ExpandExec(node.expand_projections(), children[0],
                                node.output_schema(), meta.conf)


_BNLJ_FLAG = cfg.register_op_flag(
    "exec", "BroadcastNestedLoopJoinExec",
    "Brute-force cross/conditioned join streaming the left side against a "
    "broadcast right side; the full pair grid is materialized per batch "
    "(GpuOverrides.scala:1837-1840 disables it by default for the same "
    "OOM risk)", default_enabled=False)
_CARTESIAN_FLAG = cfg.register_op_flag(
    "exec", "CartesianProductExec",
    "Brute-force cartesian product over the left x right partition grid "
    "(GpuOverrides.scala:1841-1856 disables it by default for the same "
    "OOM risk)", default_enabled=False)


class _JoinRule(NodeRule):
    def tag(self, meta: NodeMeta):
        node: pn.JoinNode = meta.node
        if node.condition is not None and node.kind not in ("inner",
                                                            "cross"):
            meta.will_not_work(
                "conditioned outer joins are post-join-filter unsafe "
                "(GpuHashJoin.scala:285-291 applies the same restriction)")
        if node.kind == "cross" and not (meta.conf.get(_BNLJ_FLAG) or
                                         meta.conf.get(_CARTESIAN_FLAG)):
            meta.will_not_work(
                "cross joins are disabled by default (OOM risk, "
                f"GpuOverrides.scala:1837-1856); set {_BNLJ_FLAG.key} or "
                f"{_CARTESIAN_FLAG.key} to true")
        if node.condition is not None:
            tag_expression(node.condition, meta, meta.conf)
        ls = node.children[0].output_schema()
        rs = node.children[1].output_schema()
        _check_types(meta, ls.types, "join left")
        _check_types(meta, rs.types, "join right")

    def convert(self, meta, children):
        node: pn.JoinNode = meta.node
        left, right = children
        out_schema = node.output_schema()
        kind = node.kind
        lk, rk = node.left_keys, node.right_keys
        cond = node.condition
        if kind == "right":
            # flip: stream the (former) right side, build the left, then
            # reorder output columns (Spark310 buildSide-flip analogue).
            # Conditioned right joins were rejected at tag time.
            inner_schema = _concat_schema(right.schema, left.schema)
            flipped = self._plan(meta, "left", right, left, rk, lk, None,
                                 inner_schema,
                                 build_node=node.children[0])
            nr = len(right.schema)
            reorder = [BoundReference(nr + i, t)
                       for i, t in enumerate(left.schema.types)] + \
                      [BoundReference(i, t)
                       for i, t in enumerate(right.schema.types)]
            reorder = [Alias(e, n)
                       for e, n in zip(reorder, out_schema.names)]
            return basic.ProjectExec(reorder, flipped, out_schema,
                                     meta.conf)
        return self._plan(meta, kind, left, right, lk, rk, cond,
                          out_schema, build_node=node.children[1])

    @staticmethod
    def _plan(meta, kind, left, right, lk, rk, cond, out_schema,
              build_node=None):
        supported = bool(lk) and kind in ("inner", "left", "left_semi",
                                          "left_anti", "full")
        mesh = _in_program_mesh(
            meta.conf, meta.node, "join", keyed=supported,
            reason_if_unkeyed=("no equi-join keys to hash-route" if not lk
                               else f"unsupported join kind '{kind}'"))
        if mesh is not None:
            # right joins arrive here already flipped to "left" (convert()
            # above); "full" composes left + null-extended anti halves with
            # a sharded union (GpuHashJoin.scala:302-318 emits FullOuter
            # from one kernel; the mesh shape is two programs + a union)
            from spark_rapids_tpu.parallel.execs import MeshShuffledJoinExec

            return MeshShuffledJoinExec(kind, left, right, lk, rk,
                                        out_schema, cond, meta.conf, mesh)
        multi = left.num_partitions > 1 or right.num_partitions > 1
        if multi and lk and kind in ("inner", "left", "left_semi",
                                     "left_anti") and \
                build_node is not None:
            # Spark's autoBroadcastJoinThreshold: a small ESTIMATED
            # build side broadcasts instead of shuffling both sides -
            # two exchange pipelines (partition + split + concat
            # dispatches per batch) collapse into one materialize
            from spark_rapids_tpu.plan.optimizer import estimate_rows

            thr = meta.conf.get(cfg.AUTO_BROADCAST_THRESHOLD)
            est = estimate_rows(build_node) if thr > 0 else None
            row_bytes = max(sum(t.byte_width
                                for t in right.schema.types), 1)
            if est is not None and est * row_bytes <= thr:
                build = exchange.BroadcastExchangeExec(right)
                return joins.BroadcastHashJoinExec(
                    kind, left, _ReplayExec(build, left.num_partitions),
                    lk, rk, out_schema, cond, meta.conf)
        if kind == "cross":
            # brute-force joins: nested-loop when the right side is already
            # a single partition (broadcast is then free) or when the
            # partition-grid cartesian isn't enabled; a multi-partition
            # right side with both flags on goes to CartesianProductExec
            # rather than funneling it whole into one device batch
            use_bnlj = meta.conf.get(_BNLJ_FLAG) and (
                right.num_partitions == 1 or
                not meta.conf.get(_CARTESIAN_FLAG))
            if use_bnlj:
                if right.num_partitions > 1:
                    right = exchange.ShuffleExchangeExec(
                        ("single",), 1, right,
                        task_threads=meta.conf.get(cfg.TASK_THREADS))
                build = exchange.BroadcastExchangeExec(right)
                return joins.BroadcastNestedLoopJoinExec(
                    left, _ReplayExec(build, left.num_partitions),
                    out_schema, cond, meta.conf)
            return joins.CartesianProductExec(left, right, out_schema,
                                              cond, meta.conf)
        if multi:
            parts = cfg.resolve_shuffle_partitions(meta.conf)
            tt = meta.conf.get(cfg.TASK_THREADS)
            lex = exchange.ShuffleExchangeExec(("hash", lk), parts, left,
                                               task_threads=tt)
            rex = exchange.ShuffleExchangeExec(("hash", rk), parts, right,
                                               task_threads=tt)
            if meta.conf.get(cfg.ADAPTIVE_ENABLED) and parts > 1:
                # defer the final join strategy to EXECUTE time: once
                # the build-side map stage has materialized, the
                # adaptive exec picks broadcast vs shuffled-hash vs
                # dense-probe from MEASURED sizes, and its paired
                # readers split skewed partitions (one shared group
                # spec keeps the sides partition-aligned; cluster mode
                # included — stats come from the tracker)
                return adaptive_exec.AdaptiveShuffledJoinExec(
                    kind, lex, rex, lk, rk, out_schema, cond, meta.conf)
            return joins.ShuffledHashJoinExec(
                kind, lex, rex, lk, rk, out_schema, cond, meta.conf)
        build = exchange.BroadcastExchangeExec(right)
        # broadcast replays its single partition to every stream partition
        return joins.BroadcastHashJoinExec(
            kind, left, _ReplayExec(build, left.num_partitions), lk, rk,
            out_schema, cond, meta.conf)


class _ReplayExec(TpuExec):
    """Presents a 1-partition child (broadcast) as n identical partitions."""

    def __init__(self, child: TpuExec, n: int):
        super().__init__([child], child.schema)
        self._n = max(n, 1)

    @property
    def num_partitions(self) -> int:
        return self._n

    @property
    def coalesce_after(self):
        return self.children[0].coalesce_after

    def execute(self, partition: int = 0):
        return self.children[0].execute(0)


def _concat_schema(a: Schema, b: Schema) -> Schema:
    return Schema(list(a.names) + list(b.names),
                  list(a.types) + list(b.types))


def _default_coercible(in_t: dt.DType, default) -> bool:
    """Can ``default`` be stored in a column of ``in_t``'s physical dtype?
    (lead/lag fill value; WindowExec materializes it with jnp.asarray)."""
    if isinstance(default, bool):
        return True  # bool coerces into every numeric physical dtype
    if in_t.is_integral or in_t in (dt.DATE, dt.TIMESTAMP):
        return isinstance(default, int)
    if in_t.is_floating:
        return isinstance(default, (int, float))
    if in_t is dt.BOOLEAN:
        return False  # non-bool default over a boolean column
    return False


class _WindowRule(NodeRule):
    def tag(self, meta: NodeMeta):
        node: pn.WindowNode = meta.node
        for c in node.calls:
            if isinstance(c.fn, aggfn.AggregateFunction):
                if not isinstance(c.fn, (aggfn.Sum, aggfn.Count,
                                         aggfn.Average, aggfn.Min,
                                         aggfn.Max, aggfn.First,
                                         aggfn.Last)):
                    meta.will_not_work(
                        f"window aggregate {type(c.fn).__name__} "
                        "not implemented")
                if isinstance(c.fn, (aggfn.First, aggfn.Last)) and \
                        c.fn.ignore_nulls:
                    meta.will_not_work(
                        "first/last(ignoreNulls) windows fall back")
                if c.frame.kind == "range":
                    self._tag_range_frame(c, node, meta)
                elif isinstance(c.fn, (aggfn.Min, aggfn.Max)) and \
                        not (c.frame.lower is None and
                             c.frame.upper in (0, None)):
                    meta.will_not_work(
                        "bounded min/max window frames fall back "
                        "(GpuWindowExpression.scala frame checks analogue)")
                if c.fn.input is not None:
                    tag_expression(c.fn.input, meta, meta.conf)
                if c.fn.input is not None and \
                        c.fn.input.dtype is dt.STRING:
                    meta.will_not_work("string window aggregates fall back")
            elif isinstance(c.fn, tuple):
                kind = c.fn[0]
                if kind not in ("lead", "lag"):
                    meta.will_not_work(f"window shift {kind!r} unknown")
                    continue
                tag_expression(c.fn[1], meta, meta.conf)
                if c.default is not None:
                    in_t = c.fn[1].dtype
                    if in_t is dt.STRING:
                        meta.will_not_work(
                            "lead/lag default over strings falls back")
                    elif not _default_coercible(in_t, c.default):
                        meta.will_not_work(
                            f"lead/lag default {c.default!r} does not "
                            f"coerce to {in_t} column")
            elif c.fn not in ("row_number", "rank", "dense_rank"):
                meta.will_not_work(f"window function {c.fn} unknown")

    @staticmethod
    def _tag_range_frame(c, node: pn.WindowNode, meta: NodeMeta):
        """Device range frames: single ascending order key of an
        orderable numeric/date/timestamp type, sum/count/avg only (the
        reference limits range frames to timestamp keys,
        GpuWindowExpression.scala:208-263 — ours are wider but min/max
        still fall back)."""
        if isinstance(c.fn, (aggfn.Min, aggfn.Max)):
            meta.will_not_work("range-framed min/max windows fall back")
            return
        if len(node.order_specs) != 1:
            meta.will_not_work(
                "range frames need exactly one order key")
            return
        spec = node.order_specs[0]
        if not spec.ascending:
            meta.will_not_work("descending range frames fall back")
        kt = node.children[0].output_schema().types[spec.ordinal]
        if not (kt.is_numeric or kt in (dt.DATE, dt.TIMESTAMP)):
            meta.will_not_work(
                f"range frame over {kt} order key falls back")

    def convert(self, meta, children):
        node: pn.WindowNode = meta.node
        child = children[0]
        mesh = _in_program_mesh(
            meta.conf, node, "window",
            keyed=bool(node.partition_ordinals),
            reason_if_unkeyed="window without PARTITION BY funnels to "
                              "one device")
        if mesh is not None:
            # partition-by windows lower onto the mesh: the hash
            # exchange + per-partition window (GpuWindowExec.scala:92)
            # fuse into one all_to_all + per-chip kernel program
            from spark_rapids_tpu.parallel.execs import MeshWindowExec

            return MeshWindowExec(node.partition_ordinals,
                                  node.order_specs, node.calls, child,
                                  node.output_schema(), meta.conf, mesh)
        if child.num_partitions > 1:
            if node.partition_ordinals:
                parts = cfg.resolve_shuffle_partitions(meta.conf)
                child = _adaptive_read(exchange.ShuffleExchangeExec(
                    ("hash", node.partition_ordinals), parts, child,
                    task_threads=meta.conf.get(cfg.TASK_THREADS)),
                    meta.conf)
            else:
                child = exchange.ShuffleExchangeExec(
                    ("single",), 1, child,
                    task_threads=meta.conf.get(cfg.TASK_THREADS))
        return window.WindowExec(node.partition_ordinals, node.order_specs,
                                 node.calls, child, node.output_schema(),
                                 meta.conf)


class _CoalescePartitionsRule(NodeRule):
    def convert(self, meta, children):
        return basic.CoalescePartitionsExec(meta.node.num_partitions,
                                            children[0])


class _ExchangeRule(NodeRule):
    def convert(self, meta, children):
        node: pn.ShuffleExchangeNode = meta.node
        return exchange.ShuffleExchangeExec(
            node.partitioning, node.num_partitions, children[0],
            task_threads=meta.conf.get(cfg.TASK_THREADS))


class _BroadcastRule(NodeRule):
    def convert(self, meta, children):
        return exchange.BroadcastExchangeExec(children[0])


class _CacheRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.cache import CachedExec

        return CachedExec(meta.node, children[0])


class _FragmentRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.service.cache.fragments import (
            FragmentCaptureExec, FragmentServeExec)

        if children:
            return FragmentCaptureExec(meta.node, children[0])
        return FragmentServeExec(meta.node)


class _MapInPandasRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import MapInPandasExec

        return MapInPandasExec(meta.node, children[0],
                               conf=meta.conf)


class _CoGroupedMapRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import \
            CoGroupedMapInPandasExec

        node = meta.node
        left, right = children
        if left.num_partitions > 1 or right.num_partitions > 1:
            parts = cfg.resolve_shuffle_partitions(meta.conf)
            tt = meta.conf.get(cfg.TASK_THREADS)
            left = exchange.ShuffleExchangeExec(
                ("hash", list(node.left_ordinals)), parts, left,
                task_threads=tt)
            right = exchange.ShuffleExchangeExec(
                ("hash", list(node.right_ordinals)), parts, right,
                task_threads=tt)
        return CoGroupedMapInPandasExec(node, left, right,
                                        conf=meta.conf)


class _GroupedMapRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import \
            GroupedMapInPandasExec

        node = meta.node
        child = children[0]
        if child.num_partitions > 1:
            parts = cfg.resolve_shuffle_partitions(meta.conf)
            child = _adaptive_read(exchange.ShuffleExchangeExec(
                ("hash", list(node.grouping_ordinals)), parts, child),
                meta.conf)
        return GroupedMapInPandasExec(node, child,
                                      conf=meta.conf)


class _ArrowEvalPythonRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import ArrowEvalPythonExec

        return ArrowEvalPythonExec(meta.node, children[0],
                                   conf=meta.conf)


class _AggInPandasRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import AggregateInPandasExec

        node = meta.node
        child = children[0]
        if child.num_partitions > 1:
            parts = cfg.resolve_shuffle_partitions(meta.conf)
            child = _adaptive_read(exchange.ShuffleExchangeExec(
                ("hash", list(node.grouping_ordinals)), parts, child),
                meta.conf)
        return AggregateInPandasExec(node, child,
                                     conf=meta.conf)


class _WindowInPandasRule(NodeRule):
    def convert(self, meta, children):
        from spark_rapids_tpu.execs.python_exec import WindowInPandasExec

        node = meta.node
        child = children[0]
        if child.num_partitions > 1:
            parts = cfg.resolve_shuffle_partitions(meta.conf)
            child = _adaptive_read(exchange.ShuffleExchangeExec(
                ("hash", list(node.partition_ordinals)), parts, child),
                meta.conf)
        return WindowInPandasExec(node, child, conf=meta.conf)


def _register_io_rules():
    from spark_rapids_tpu.execs.cache import CacheNode
    from spark_rapids_tpu.execs.python_exec import MapInPandasNode
    from spark_rapids_tpu.io.write import WriteFilesNode
    # cycle-safe: service/cache/fragments imports execs/memory/plan.nodes
    # only, never this module (the service layer reaches overrides
    # exclusively through function-level imports)
    from spark_rapids_tpu.service.cache.fragments import \
        CachedFragmentNode

    from spark_rapids_tpu.execs.python_exec import (
        AggregateInPandasNode, ArrowEvalPythonNode,
        CoGroupedMapInPandasNode, GroupedMapInPandasNode,
        WindowInPandasNode)

    _NODE_RULES[WriteFilesNode] = _WriteRule()
    _NODE_RULES[MapInPandasNode] = _MapInPandasRule()
    _NODE_RULES[GroupedMapInPandasNode] = _GroupedMapRule()
    _NODE_RULES[CoGroupedMapInPandasNode] = _CoGroupedMapRule()
    _NODE_RULES[WindowInPandasNode] = _WindowInPandasRule()
    _NODE_RULES[ArrowEvalPythonNode] = _ArrowEvalPythonRule()
    _NODE_RULES[AggregateInPandasNode] = _AggInPandasRule()
    _NODE_RULES[CacheNode] = _CacheRule()
    _NODE_RULES[CachedFragmentNode] = _FragmentRule()
    # mirror the reference: pandas execs are off by default because data
    # leaves the accelerator for the Python worker
    # (GpuOverrides.scala:1888-1907)
    cfg.register_op_flag(
        "exec", "MapInPandasNode",
        "Run mapInPandas around the TPU pipeline (device->pandas->device "
        "round trip per batch)", default_enabled=False)
    cfg.register_op_flag(
        "exec", "GroupedMapInPandasNode",
        "Run groupBy().applyInPandas around the TPU pipeline "
        "(co-partitioned device->pandas->device round trip)",
        default_enabled=False)
    cfg.register_op_flag(
        "exec", "CoGroupedMapInPandasNode",
        "Run cogroup().applyInPandas around the TPU pipeline",
        default_enabled=False)
    cfg.register_op_flag(
        "exec", "WindowInPandasNode",
        "Run a pandas window UDF over co-partitioned window partitions "
        "(GpuWindowInPandasExec analogue)", default_enabled=False)
    # scalar pandas UDFs stay enabled by default — the reference likewise
    # keeps GpuArrowEvalPythonExec on (it holds data on the accelerator
    # between the scan and the Python worker, GpuOverrides.scala:1888)
    cfg.register_op_flag(
        "exec", "ArrowEvalPythonNode",
        "Evaluate scalar pandas UDFs per batch and append their columns "
        "(GpuArrowEvalPythonExec analogue)")
    cfg.register_op_flag(
        "exec", "AggregateInPandasNode",
        "Run pandas aggregation UDFs over co-partitioned groups "
        "(GpuAggregateInPandasExec analogue)", default_enabled=False)


_NODE_RULES: Dict[Type[pn.PlanNode], NodeRule] = {
    pn.ScanNode: _ScanRule(),
    pn.RangeNode: _RangeRule(),
    pn.ProjectNode: _ProjectRule(),
    pn.FilterNode: _FilterRule(),
    pn.AggregateNode: _AggregateRule(),
    pn.SortNode: _SortRule(),
    pn.LimitNode: _LimitRule(),
    pn.UnionNode: _UnionRule(),
    pn.ExpandNode: _ExpandRule(),
    pn.GenerateNode: _GenerateRule(),
    pn.JoinNode: _JoinRule(),
    pn.WindowNode: _WindowRule(),
    pn.ShuffleExchangeNode: _ExchangeRule(),
    pn.CoalescePartitionsNode: _CoalescePartitionsRule(),
    pn.BroadcastExchangeNode: _BroadcastRule(),
}

_register_io_rules()


# ---------------------------------------------------------------------------
# File-filter pushdown (GpuParquetScan.scala:228-265 row-group filtering)
# ---------------------------------------------------------------------------

_PUSHDOWN_OPS = {
    predicates.EqualTo: "=",
    predicates.LessThan: "<",
    predicates.LessThanOrEqual: "<=",
    predicates.GreaterThan: ">",
    predicates.GreaterThanOrEqual: ">=",
}

_FLIP = {"=": "=", "<": ">", "<=": ">=", ">": "<", ">=": "<="}


def _split_conjuncts(e: Expression) -> List[Expression]:
    if isinstance(e, predicates.And):
        return (_split_conjuncts(e.children[0])
                + _split_conjuncts(e.children[1]))
    return [e]


def _extract_pushdown(cond: Expression, schema: Schema):
    """-> list of (column, op, value) pruning triples, one per conjunct of
    shape ``col <cmp> literal`` (either side). Literals are already in the
    engine's physical encodings, which is what io/parquet.py _stat_value
    normalizes footer statistics to."""
    out = []
    for c in _split_conjuncts(cond):
        op = _PUSHDOWN_OPS.get(type(c))
        if op is None:
            continue
        left, right = c.children
        if isinstance(left, BoundReference) and isinstance(right, Literal):
            ref, lit, o = left, right, op
        elif isinstance(right, BoundReference) and isinstance(left,
                                                             Literal):
            ref, lit, o = right, left, _FLIP[op]
        else:
            continue
        if lit.value is None:
            continue
        if ref.dtype is dt.STRING and not isinstance(lit.value, str):
            continue
        out.append((schema.names[ref.ordinal], o, lit.value))
    return out


def push_down_file_filters(plan: pn.PlanNode,
                           conf: RapidsConf) -> pn.PlanNode:
    """Rewrite Filter(Scan(file-source)) so the source also receives the
    comparison conjuncts for chunk pruning; the Filter stays (exact
    semantics on device)."""
    from spark_rapids_tpu.io.filesrc import FileSourceBase

    if not conf.get(cfg.FILTER_PUSHDOWN_ENABLED):
        return plan
    new_children = [push_down_file_filters(c, conf)
                    for c in plan.children]
    plan = plan.with_children(new_children) if plan.children else plan
    if isinstance(plan, pn.FilterNode):
        child = plan.children[0]
        if isinstance(child, pn.ScanNode) and \
                isinstance(child.source, FileSourceBase):
            filters = _extract_pushdown(plan.condition,
                                        child.output_schema())
            if filters:
                from spark_rapids_tpu.io import scanpipe

                scanpipe.record_pushdown(len(filters))
                new_scan = pn.ScanNode(child.source.with_filters(filters))
                return plan.with_children([new_scan])
    return plan


# ---------------------------------------------------------------------------
# Transition / coalesce insertion (GpuTransitionOverrides.scala)
# ---------------------------------------------------------------------------


def insert_coalesce(root: TpuExec) -> TpuExec:
    """Insert CoalesceBatchesExec where a child's output doesn't satisfy
    the parent's goal (GpuTransitionOverrides.scala:118-203)."""
    new_children = [insert_coalesce(c) for c in root.children]
    goals = root.children_coalesce_goal
    for i, (child, goal) in enumerate(zip(new_children, goals)):
        if goal is None:
            continue
        produced = child.coalesce_after
        if produced is not None and produced.satisfies(goal):
            continue
        new_children[i] = batching.CoalesceBatchesExec(child, goal)
    root.children = new_children
    return root


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


class PlanOnCpuError(AssertionError):
    """Raised in test mode when part of the plan fell back
    (GpuTransitionOverrides.scala:270-326 assertIsOnTheGpu)."""


def apply_overrides(plan: pn.PlanNode,
                    conf: Optional[RapidsConf] = None) -> TpuExec:
    """Logical plan to physical exec tree, under the span
    ``plan.physical`` and its four children."""
    conf = conf or RapidsConf()
    with TraceRange("plan.physical"):
        with TraceRange("plan.optimize"):
            if conf.get(cfg.UDF_COMPILER_ENABLED):
                from spark_rapids_tpu.udf import compile_udfs_in_plan

                plan = compile_udfs_in_plan(plan)
            if conf.get(cfg.OPTIMIZER_ENABLED):
                from spark_rapids_tpu.plan.optimizer import optimize

                plan = optimize(plan)
            plan = push_down_file_filters(plan, conf)
            pn.gate_split_packing(plan)
        with TraceRange("plan.tag"):
            meta = NodeMeta(plan, conf)
            meta.tag_for_tpu()
            explain_mode = conf.get(cfg.EXPLAIN).upper()
            if explain_mode in ("ALL", "NOT_ON_TPU"):
                print(meta.explain(
                    only_not_on_tpu=explain_mode == "NOT_ON_TPU"))
        # plan-time partition-count queries must see STATIC shuffle
        # counts: without this, a rule asking an adaptive reader for
        # num_partitions materializes (executes!) the whole map stage
        # mid-planning, before fusion/coalesce have rewritten the subtree
        with TraceRange("plan.convert"), adaptive_exec.planning_mode():
            exec_ = meta.convert()
            if conf.get(cfg.FUSION_ENABLED):
                from spark_rapids_tpu.execs.fused import fuse_pipelines

                exec_ = fuse_pipelines(exec_, conf)
            exec_ = insert_coalesce(exec_)
        with TraceRange("plan.stages"):
            if _cluster_mode(conf):
                from spark_rapids_tpu.runtime.cluster import (
                    install_cluster_exchanges, session_cluster)

                runtime = session_cluster(conf)
                if runtime is not None:
                    exec_ = install_cluster_exchanges(exec_, runtime)
            _enable_in_program_exchanges(exec_, conf)
            if conf.get(cfg.TEST_ENABLED):
                allowed = {s.strip() for s in
                           conf.get(cfg.TEST_ALLOWED_NON_TPU).split(",")
                           if s.strip()}
                _assert_on_tpu(exec_, allowed)
            # label every exec with its pipeline stage so dispatch
            # telemetry (and bench output) attributes round trips per
            # stage
            from spark_rapids_tpu.plan.optimizer import cut_stages

            cut_stages(exec_)
    return exec_


def _enable_in_program_exchanges(exec_: TpuExec, conf) -> None:
    """SPMD whole-stage exchange: flip every eligible hash
    ShuffleExchangeExec surviving in the physical plan to the compiled
    all_to_all map side (execs/exchange._materialize_in_program). The
    mesh exec lowering already absorbs most shuffles into chained
    Mesh*Execs; this walk catches the rest — explicit repartitions,
    shuffled-join inputs, partial/final aggregate boundaries. Safe to
    flip one side of a co-partitioned pair: the in-program step
    reproduces the host partition kernel's pid exactly. Every "no" on a
    mesh-enabled session lands in parallel/spmd.py's fallback telemetry
    with a reason."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.execs.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.parallel import spmd

    if conf is None or not conf.get(cfg.MESH_ENABLED):
        return
    skew = spmd.adaptive_skew_spec(conf)
    seen: set = set()

    def walk(e) -> None:
        if id(e) in seen:
            return
        seen.add(id(e))
        if isinstance(e, ShuffleExchangeExec) and not e.in_program \
                and e._blocks is None \
                and e.partitioning[0] != "single":
            kind = e.partitioning[0]
            if kind != "hash":
                mesh = spmd.in_program_mesh(
                    conf, "exchange", keyed=False,
                    reason_if_unkeyed=f"{kind} partitioning routes "
                    "host-side (sampled bounds / row order)")
            elif any(t is dt.STRING for t in e.schema.types):
                mesh = spmd.in_program_mesh(
                    conf, "exchange", keyed=False,
                    reason_if_unkeyed="string columns need host-side "
                    "dictionary unification")
            else:
                mesh = spmd.in_program_mesh(conf, "exchange")
            if mesh is not None:
                e.enable_in_program(mesh, skew=skew)
        for c in e.children:
            walk(c)
        for bx in getattr(e, "builds", ()) or ():
            walk(bx)

    walk(exec_)


def _assert_on_tpu(exec_: TpuExec, allowed: set):
    if isinstance(exec_, basic.CpuFallbackExec):
        name = type(exec_.plan_node).__name__
        if name not in allowed:
            raise PlanOnCpuError(
                f"{name} fell back to CPU: {exec_.reasons}")
    for c in exec_.children:
        _assert_on_tpu(c, allowed)


def explain(plan: pn.PlanNode, conf: Optional[RapidsConf] = None) -> str:
    conf = conf or RapidsConf()
    meta = NodeMeta(plan, conf)
    meta.tag_for_tpu()
    return meta.explain()


# all module-level knobs (including every import-time op flag above)
# are registered by this point; anything added later is a per-node
# apply-time flag that docs generation can never see
cfg.snapshot_docs_registry()
