"""Plan-level optimizer rules applied before TpuOverrides.

The reference inherits Catalyst's optimized plans; standalone, this
engine needs the handful of structural rules with direct dispatch-count
impact (each collapsed node is one fewer jitted executable per batch):

- CollapseProject: Project(Project(x)) -> one Project with the outer
  expressions rewritten over the inner ones (Catalyst's CollapseProject)
- CombineFilters: Filter(Filter(x)) -> one conjunctive Filter
- CollapseFilterProject: Filter(Project(x)) where the condition only
  references projected columns -> Project(Filter'(x)) is NOT generally
  safe (the projection may rename/compute); instead the condition is
  rewritten through the projection so the pair becomes
  Project(..) over Filter(rewritten) — pushing the filter below the
  projection lets scans prune earlier (PushDownPredicate subset for
  deterministic projections).
"""
from __future__ import annotations

import copy
import dataclasses
from typing import List, Optional

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.expressions.base import (Alias, BoundReference,
                                               Expression)
from spark_rapids_tpu.plan import nodes as pn
from spark_rapids_tpu.utils import tracing


def _substitute(e: Expression, inner: List[Expression]) -> Expression:
    """Rewrite ``e``'s bound references as the inner projection's
    expressions (unwrapping aliases)."""
    def fn(node: Expression) -> Expression:
        if isinstance(node, BoundReference):
            repl = inner[node.ordinal]
            while isinstance(repl, Alias):
                repl = repl.children[0]
            return repl
        return node
    return e.transform(fn)


def _all_deterministic(exprs) -> bool:
    return all(e.deterministic for e in exprs)


def _reference_counts(exprs: List[Expression], width: int) -> List[int]:
    counts = [0] * width
    for e in exprs:
        for node in e.collect(lambda n: isinstance(n, BoundReference)):
            counts[node.ordinal] += 1
    return counts


def collapse_project(node: pn.PlanNode, _memo=None) -> pn.PlanNode:
    """Bottom-up single pass collapsing Project/Filter chains.

    ``_memo`` (id -> (node, result), the node ref pins the id) keeps
    SHARED subtrees shared: CTE references reuse one plan node, and a
    rebuild that copied it per reference would make the exec layer
    materialize the common stage once per consumer."""
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(node))
    if hit is not None:
        return hit[1]
    result = _collapse_project_one(node, _memo)
    _memo[id(node)] = (node, result)
    return result


def _collapse_project_one(node: pn.PlanNode, _memo) -> pn.PlanNode:
    new_children = [collapse_project(c, _memo) for c in node.children]
    node = node.with_children(new_children) if node.children else node

    if isinstance(node, pn.ProjectNode) and \
            isinstance(node.children[0], pn.ProjectNode):
        inner: pn.ProjectNode = node.children[0]
        if _all_deterministic(inner.exprs):
            # avoid exploding duplicated non-trivial inner expressions:
            # collapse only when every inner expr used more than once is
            # a bare reference (Catalyst applies a similar cost guard)
            counts = _reference_counts(node.exprs, len(inner.exprs))
            cheap = all(
                c <= 1 or isinstance(
                    inner.exprs[i].children[0]
                    if isinstance(inner.exprs[i], Alias)
                    else inner.exprs[i], BoundReference)
                for i, c in enumerate(counts))
            if cheap:
                exprs = [_substitute(e, inner.exprs)
                         for e in node.exprs]
                return collapse_project(pn.ProjectNode(
                    exprs, inner.children[0], names=list(node.names)),
                    _memo)

    if isinstance(node, pn.FilterNode) and \
            isinstance(node.children[0], pn.FilterNode):
        from spark_rapids_tpu.expressions import predicates as pr

        inner_f: pn.FilterNode = node.children[0]
        return collapse_project(pn.FilterNode(
            pr.And(inner_f.condition, node.condition),
            inner_f.children[0]), _memo)

    if isinstance(node, pn.FilterNode) and \
            isinstance(node.children[0], pn.ProjectNode):
        proj: pn.ProjectNode = node.children[0]
        if _all_deterministic(proj.exprs) and \
                _all_deterministic([node.condition]):
            pushed = _substitute(node.condition, proj.exprs)
            return collapse_project(pn.ProjectNode(
                list(proj.exprs),
                pn.FilterNode(pushed, proj.children[0]),
                names=list(proj.names)), _memo)

    return node


def rewrite_distinct_aggregates(node: pn.PlanNode,
                                _memo=None) -> pn.PlanNode:
    """count/sum(DISTINCT x) -> dedup-then-aggregate: an inner group-by
    over (keys..., x) removes duplicates, then the outer aggregate runs
    the plain (non-distinct) function. This is the planner-level role of
    the reference's distinct handling (aggregate.scala:56-130).

    Mixed distinct + plain aggregates also rewrite when every plain
    aggregate is decomposable (Sum/Count/Min/Max): the inner group-by
    computes the plain aggregate per (keys, x) sub-group and the outer
    re-merges (Count -> Sum of counts; Sum/Min/Max self-merge) — the
    two-phase expand Spark plans for one distinct column. Only
    multi-distinct (different inputs) still falls back, as in the
    reference."""
    from spark_rapids_tpu.expressions import aggregates as aggfn

    if _memo is None:
        _memo = {}
    hit = _memo.get(id(node))
    if hit is not None:
        return hit[1]
    orig = node
    new_children = [rewrite_distinct_aggregates(c, _memo)
                    for c in node.children]
    if node.children and any(n is not o for n, o in
                             zip(new_children, node.children)):
        node = node.with_children(new_children)
    result = _rewrite_distinct_one(node)
    _memo[id(orig)] = (orig, result)
    return result


def _rewrite_distinct_one(node: pn.PlanNode) -> pn.PlanNode:
    from spark_rapids_tpu.expressions import aggregates as aggfn

    if not isinstance(node, pn.AggregateNode) or node.mode != "complete":
        return node
    dist = [a for a in node.aggs if getattr(a.fn, "distinct", False)]
    plain = [a for a in node.aggs if not getattr(a.fn, "distinct", False)]
    if not dist:
        return node
    if not all(isinstance(a.fn, (aggfn.Count, aggfn.Sum))
               for a in dist):
        return node  # (Average has no distinct form to rewrite)
    if not all(isinstance(a.fn, (aggfn.Count, aggfn.Sum, aggfn.Min,
                                 aggfn.Max, aggfn.Average))
               for a in plain):
        return node  # non-decomposable plain aggregate alongside
    # (ungrouped plain Counts merge via Sum whose empty-input default is
    # NULL, not Count's 0 — the final projection coalesces them back)
    inputs = [a.fn.children[0] if a.fn.children else None
              for a in dist]
    if any(i is None for i in inputs):
        return node
    first_key = inputs[0].tree_key()
    if first_key is None or any(i.tree_key() != first_key
                                for i in inputs[1:]):
        return node  # multi-distinct: fall back like the reference

    nkeys = len(node.grouping)
    inner_aggs = []
    inner_ords = {}  # id(plain call) -> inner agg ordinals
    for a in plain:
        fn = a.fn
        i0 = len(inner_aggs)
        if isinstance(fn, aggfn.Average):
            # avg is not avg-of-avgs decomposable: split into sum+count
            # partials, re-divided by a final projection
            inner_aggs.append(pn.AggCall(aggfn.Sum(fn.children[0]),
                                         f"_p{i0}"))
            inner_aggs.append(pn.AggCall(aggfn.Count(fn.children[0]),
                                         f"_p{i0 + 1}"))
            inner_ords[id(a)] = [i0, i0 + 1]
        else:
            clone = type(fn)(*fn.children) if fn.children else type(fn)()
            inner_aggs.append(pn.AggCall(clone, f"_p{i0}"))
            inner_ords[id(a)] = [i0]
    inner = pn.AggregateNode(
        list(node.grouping) + [inputs[0]], inner_aggs, node.children[0],
        grouping_names=list(node.grouping_names) + ["__distinct"])
    x = BoundReference(nkeys, inputs[0].dtype)
    outer_aggs = []
    out_spec = []  # per original agg: ("ref", j) | ("div", j1, j2)
    for a in node.aggs:
        if getattr(a.fn, "distinct", False):
            out_spec.append(("ref", len(outer_aggs)))
            outer_aggs.append(pn.AggCall(type(a.fn)(x), a.name))
            continue
        ords = inner_ords[id(a)]
        if isinstance(a.fn, aggfn.Average):
            j1, j2 = len(outer_aggs), len(outer_aggs) + 1
            for o in ords:
                ref = BoundReference(nkeys + 1 + o,
                                     inner_aggs[o].fn.dtype)
                outer_aggs.append(pn.AggCall(aggfn.Sum(ref),
                                             f"{a.name}_{o}"))
            out_spec.append(("div", j1, j2))
        else:
            o = ords[0]
            ref = BoundReference(nkeys + 1 + o,
                                 inner_aggs[o].fn.dtype)
            merge = aggfn.Sum if isinstance(a.fn, (aggfn.Count,
                                                   aggfn.Sum)) else \
                type(a.fn)
            kind = "coalesce0" if (not node.grouping and
                                   isinstance(a.fn, aggfn.Count)) \
                else "ref"
            out_spec.append((kind, len(outer_aggs)))
            outer_aggs.append(pn.AggCall(merge(ref), a.name))
    outer_keys = [BoundReference(i, e.dtype)
                  for i, e in enumerate(node.grouping)]
    out = pn.AggregateNode(outer_keys, outer_aggs, inner,
                           grouping_names=list(node.grouping_names))
    if all(k == "ref" for k, *_ in out_spec):
        return out
    from spark_rapids_tpu.expressions.arithmetic import Divide

    schema = out.output_schema()
    exprs = [Alias(BoundReference(i, schema.types[i]), schema.names[i])
             for i in range(nkeys)]
    names = list(schema.names[:nkeys])
    for spec, a in zip(out_spec, node.aggs):
        if spec[0] == "ref":
            j = nkeys + spec[1]
            exprs.append(Alias(BoundReference(j, schema.types[j]),
                               a.name))
        elif spec[0] == "coalesce0":
            from spark_rapids_tpu.expressions import conditional as cd_
            from spark_rapids_tpu.expressions.base import Literal
            from spark_rapids_tpu.columnar import dtypes as dt_

            j = nkeys + spec[1]
            exprs.append(Alias(cd_.Coalesce(
                [BoundReference(j, schema.types[j]),
                 Literal(0, dt_.INT64)]), a.name))
        else:
            _, j1, j2 = spec
            exprs.append(Alias(
                Divide(BoundReference(nkeys + j1,
                                      schema.types[nkeys + j1]),
                       BoundReference(nkeys + j2,
                                      schema.types[nkeys + j2])),
                a.name))
        names.append(a.name)
    return pn.ProjectNode(exprs, out, names)


# ---------------------------------------------------------------------------
# Filter pushdown through joins (PushPredicateThroughJoin subset): the
# SQL planner distributes WHERE conjuncts for the implicit-join form,
# but explicit JOIN ... ON and DataFrame .join().filter() leave the
# whole WHERE above the join — severing scan pruning, inflating join
# inputs, and breaking sharded mesh hand-off chains.
# ---------------------------------------------------------------------------


def _expr_conjuncts(e: Expression) -> List[Expression]:
    from spark_rapids_tpu.expressions.predicates import And

    if isinstance(e, And):
        return _expr_conjuncts(e.children[0]) + \
            _expr_conjuncts(e.children[1])
    return [e]


def _and_all(exprs: List[Expression]) -> Expression:
    from spark_rapids_tpu.expressions.predicates import And

    out = exprs[0]
    for e in exprs[1:]:
        out = And(out, e)
    return out


def _shift_refs(e: Expression, delta: int) -> Expression:
    def fn(n):
        if isinstance(n, BoundReference):
            return BoundReference(n.ordinal + delta, n.dtype)
        return n
    return e.transform(fn)


def _split_by_side(cond: Expression, lw: int, push_left: bool,
                   push_right: bool) -> tuple:
    """A condition over a join's left columns then its right ones, cut
    into (conjuncts that stay, conjuncts that may filter the left input,
    conjuncts that may filter the right input, rebased onto it). A
    nondeterministic conjunct, or one that reads no column, stays."""
    keep: List[Expression] = []
    lpush: List[Expression] = []
    rpush: List[Expression] = []
    for c in _expr_conjuncts(cond):
        ords = [r.ordinal for r in
                c.collect(lambda n: isinstance(n, BoundReference))]
        if not c.deterministic or not ords:
            keep.append(c)
        elif max(ords) < lw and push_left:
            lpush.append(c)
        elif min(ords) >= lw and push_right:
            rpush.append(_shift_refs(c, -lw))
        else:
            keep.append(c)
    return keep, lpush, rpush


def push_filters_below_joins(node: pn.PlanNode,
                             _memo=None) -> pn.PlanNode:
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(node))
    if hit is not None:
        return hit[1]
    orig = node
    result = _push_filters_one(node, _memo)
    _memo[id(orig)] = (orig, result)
    return result


def _push_filters_one(node: pn.PlanNode, _memo) -> pn.PlanNode:
    if node.children:
        new_children = [push_filters_below_joins(c, _memo)
                        for c in node.children]
        if any(n is not o for n, o in zip(new_children, node.children)):
            node = node.with_children(new_children)
    if not (isinstance(node, pn.FilterNode) and
            isinstance(node.children[0], pn.JoinNode)):
        return node
    join: pn.JoinNode = node.children[0]
    kind = join.kind
    lw = len(join.children[0].output_schema())
    # which sides may see a pre-join filter without changing results:
    # a LEFT join's right side must NOT pre-filter (a filtered-out
    # match becomes a null-extended row instead of a dropped one);
    # FULL pushes nothing; semi/anti output only left columns
    push_left = kind in ("inner", "cross", "left", "left_semi",
                         "left_anti")
    push_right = kind in ("inner", "cross", "right")
    keep, lpush, rpush = _split_by_side(node.condition, lw, push_left,
                                        push_right)
    if not lpush and not rpush:
        return node
    left, right = join.children
    if lpush:
        left = push_filters_below_joins(
            pn.FilterNode(_and_all(lpush), left), _memo)
    if rpush:
        right = push_filters_below_joins(
            pn.FilterNode(_and_all(rpush), right), _memo)
    out: pn.PlanNode = pn.JoinNode(kind, left, right, join.left_keys,
                                   join.right_keys,
                                   condition=join.condition)
    if keep:
        out = pn.FilterNode(_and_all(keep), out)
    return out


# ---------------------------------------------------------------------------
# A join's own ON conjuncts below it (the part of Catalyst's
# PushPredicateThroughJoin that rewrites join conditions): a deterministic
# conjunct that names one side only becomes a filter on that side's input
# wherever the answer cannot change. An outer join keeps the rows of its
# preserved side whatever its condition says, so only the null-supplying
# side may be filtered; an anti join keeps the left rows its condition
# rejects, so only its right side may. What stays is evaluated as today,
# and a non-inner join that keeps a condition falls back as before.
# ---------------------------------------------------------------------------

#: join kind -> (a left-only conjunct may filter the left input, a
#: right-only conjunct may filter the right input)
_ON_PUSHABLE = {
    "inner": (True, True), "cross": (True, True),
    "left": (False, True), "right": (True, False),
    "left_semi": (True, True), "left_anti": (False, True),
    "full": (False, False),
}


def push_join_conditions(node: pn.PlanNode, _memo=None) -> pn.PlanNode:
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(node))
    if hit is not None:
        return hit[1]
    orig = node
    result = _push_condition_one(node, _memo)
    _memo[id(orig)] = (orig, result)
    return result


def _push_condition_one(node: pn.PlanNode, _memo) -> pn.PlanNode:
    if node.children:
        new_children = [push_join_conditions(c, _memo)
                        for c in node.children]
        if any(n is not o for n, o in zip(new_children, node.children)):
            node = node.with_children(new_children)
    if not isinstance(node, pn.JoinNode) or node.condition is None:
        return node
    push_left, push_right = _ON_PUSHABLE[node.kind]
    # the condition reads the left columns, then the right ones, for
    # every kind (semi and anti joins included)
    lw = len(node.children[0].output_schema())
    keep, lpush, rpush = _split_by_side(node.condition, lw, push_left,
                                        push_right)
    if not lpush and not rpush:
        return node
    left, right = node.children
    if lpush:
        left = pn.FilterNode(_and_all(lpush), left)
    if rpush:
        right = pn.FilterNode(_and_all(rpush), right)
    return pn.JoinNode(node.kind, left, right, node.left_keys,
                       node.right_keys,
                       condition=_and_all(keep) if keep else None)


# ---------------------------------------------------------------------------
# Greedy join reordering (r3 verdict #6). The reference inherits join
# order from Spark's cost-based optimizer upstream; standalone, this
# planner owns the job. Scan-statistics row counts (parquet footer
# metadata / host array lengths) drive a classic greedy heuristic:
# start from the LARGEST relation (the fact table stays the stream
# side) and repeatedly join the smallest connected relation — small
# dimensions become early, cheap build sides and intermediate results
# shrink as early as possible (q64's 17-table chain no longer depends
# on the hand-written query order).
# ---------------------------------------------------------------------------

_FILTER_SELECTIVITY = 0.3


def estimate_key_ndv(node: pn.PlanNode, ordinal: int) -> Optional[int]:
    """Distinct-value estimate for a join key column, derived from file
    footer statistics where the column traces back to a scan: an
    integral key with host-known (lo, hi) bounds has NDV <= hi-lo+1,
    capped by the relation's row estimate. Replaces part of the fixed
    heuristic cardinality model (round-4 weak #5) with data-driven
    numbers when footers provide them."""
    if isinstance(node, pn.FilterNode):
        return estimate_key_ndv(node.children[0], ordinal)
    if isinstance(node, pn.ProjectNode):
        e = node.exprs[ordinal]
        while isinstance(e, Alias):
            e = e.children[0]
        if isinstance(e, BoundReference):
            return estimate_key_ndv(node.children[0], e.ordinal)
        return None
    if isinstance(node, pn.ScanNode):
        src = node.source
        try:
            schema = src.schema()
            t = schema.types[ordinal]
            if not (t.is_integral or t in (dt.DATE, dt.TIMESTAMP)):
                return None
            name = schema.names[ordinal]
            splits = getattr(src, "splits", None)
            if splits is None:
                return None
            lo = hi = None
            for i in range(len(splits())):
                s = src.split_stats(i)
                if not s or name not in s:
                    return None
                slo, shi = s[name]
                lo = slo if lo is None else min(lo, slo)
                hi = shi if hi is None else max(hi, shi)
            if lo is None:
                return None
            span = int(hi) - int(lo) + 1
            rows = src.estimated_row_count()
            return max(min(span, rows) if rows is not None else span, 1)
        except Exception:
            return None
    return None


def estimate_rows(node: pn.PlanNode) -> Optional[int]:
    """Plan-time cardinality estimate; None = unknown (no reordering)."""
    est_fn = getattr(node, "plan_row_estimate", None)
    if est_fn is not None:
        # nodes that carry their own estimate (a cached-fragment leaf
        # knows the cardinality of the subtree it replaced) — without
        # this, a grafted serve leaf would charge default_rows against
        # admission for data that is already materialized
        return est_fn()
    if isinstance(node, pn.ScanNode):
        est = node.source.estimated_row_count()
        if est is not None and isinstance(node.source, pn.DataSource) \
                and getattr(node.source, "filters", None):
            est = max(int(est * _FILTER_SELECTIVITY), 1)
        return est
    if isinstance(node, pn.FilterNode):
        c = estimate_rows(node.children[0])
        return None if c is None else max(int(c * _FILTER_SELECTIVITY), 1)
    if isinstance(node, pn.JoinNode):
        le = estimate_rows(node.children[0])
        if node.kind in ("left_semi", "left_anti"):
            return le
        re = estimate_rows(node.children[1])
        if le is None or re is None:
            return None
        if node.kind == "inner":
            # |A join B| = |A|*|B| / ndv(k), FLOORED at max(le, re):
            # span-based NDV is only an upper bound on true NDV (sparse
            # key domains like lineitem.l_orderkey can make span ~ rows
            # while true NDV is rows/4), so an unfloored estimate would
            # systematically UNDER-estimate and mislead the broadcast
            # threshold. With the floor, the refinement can only detect
            # many-to-many EXPANSION (est above both sides) — the
            # direction span stats CAN bound soundly.
            if node.left_keys:
                cands = []
                for side, ord_ in (
                        (node.children[0], node.left_keys[0]),
                        (node.children[1], node.right_keys[0])):
                    ndv = estimate_key_ndv(side, ord_)
                    if ndv is not None:
                        cands.append(ndv)
                if cands:
                    est = (le * re) // max(max(cands), 1)
                    return max(min(est, le * re), max(le, re), 1)
            return max(le, re)  # FK->PK: output tracks the fact side
        return le if node.kind == "left" else le + re
    if isinstance(node, pn.AggregateNode):
        c = estimate_rows(node.children[0])
        # grouped outputs shrink; keep a conservative fraction
        return None if c is None else max(c // 3, 1)
    if isinstance(node, pn.UnionNode):
        parts = [estimate_rows(c) for c in node.children]
        return None if any(p is None for p in parts) else sum(parts)
    if isinstance(node, pn.LimitNode):
        c = estimate_rows(node.children[0])
        return node.n if c is None else min(node.n, c)
    if len(node.children) == 1:  # project/sort/window/exchange/...
        return estimate_rows(node.children[0])
    return None


def _flatten_inner_joins(node: pn.PlanNode):
    """Maximal chain of condition-free inner equi-joins.
    Returns (rels, colmap, edges): base relations, a map from this
    subtree's output ordinal to (rel_index, rel_ordinal), and key
    equalities as ((ri, ci), (rj, cj)) pairs."""
    if isinstance(node, pn.JoinNode) and node.kind == "inner" and \
            node.condition is None and node.left_keys:
        lrels, lmap, ledges = _flatten_inner_joins(node.children[0])
        rrels, rmap, redges = _flatten_inner_joins(node.children[1])
        off = len(lrels)
        rmap = [(ri + off, ci) for ri, ci in rmap]
        redges = [((a + off, b), (c + off, d))
                  for (a, b), (c, d) in redges]
        edges = ledges + redges
        for lk, rk in zip(node.left_keys, node.right_keys):
            edges.append((lmap[lk], rmap[rk]))
        return lrels + rrels, lmap + rmap, edges
    width = len(node.output_schema())
    return [node], [(0, i) for i in range(width)], []


def _greedy_order(n: int, edges, est) -> Optional[List[int]]:
    adj = {i: set() for i in range(n)}
    for (ri, _), (rj, _) in edges:
        adj[ri].add(rj)
        adj[rj].add(ri)
    start = max(range(n), key=lambda i: est[i])
    order, placed = [start], {start}
    while len(order) < n:
        cand = [i for i in range(n)
                if i not in placed and adj[i] & placed]
        if not cand:
            return None  # disconnected graph: keep the written order
        nxt = min(cand, key=lambda i: est[i])
        order.append(nxt)
        placed.add(nxt)
    return order


def reorder_joins(node: pn.PlanNode, _memo=None) -> pn.PlanNode:
    if _memo is None:
        _memo = {}
    hit = _memo.get(id(node))
    if hit is not None:
        return hit[1]
    orig = node
    result = _reorder_joins_one(node, _memo)
    _memo[id(orig)] = (orig, result)
    return result


def _reorder_joins_one(node: pn.PlanNode, _memo) -> pn.PlanNode:
    # TOP-DOWN: the chain must flatten before any sub-chain wraps
    # itself in a restore-projection (which would hide it)
    if not (isinstance(node, pn.JoinNode) and node.kind == "inner" and
            node.condition is None and node.left_keys):
        if node.children:
            new_children = [reorder_joins(c, _memo)
                            for c in node.children]
            if any(n is not o for n, o in
                   zip(new_children, node.children)):
                return node.with_children(new_children)
        return node

    def keep_written_order():
        new_children = [reorder_joins(c, _memo)
                        for c in node.children]
        if any(n is not o for n, o in zip(new_children, node.children)):
            return node.with_children(new_children)
        return node

    rels, colmap, edges = _flatten_inner_joins(node)
    if len(rels) < 3:
        return keep_written_order()
    est = [estimate_rows(r) for r in rels]
    if any(e is None for e in est):
        return keep_written_order()
    order = _greedy_order(len(rels), edges, est)
    if order is None or order == list(range(len(rels))):
        return keep_written_order()
    rels = [reorder_joins(r, _memo) for r in rels]  # recurse below
    # rebuild left-deep in greedy order; when a relation joins, every
    # key equality linking it to already-placed relations applies (so
    # no edge constraint is ever dropped — an edge activates when its
    # later-placed endpoint arrives)
    offsets = {order[0]: 0}
    cur = rels[order[0]]
    width = len(cur.output_schema())
    placed = {order[0]}
    for idx in order[1:]:
        r = rels[idx]
        pairs = []
        for (ri, ci), (rj, cj) in edges:
            if ri in placed and rj == idx:
                pairs.append((offsets[ri] + ci, cj))
            elif rj in placed and ri == idx:
                pairs.append((offsets[rj] + cj, ci))
        pairs = list(dict.fromkeys(pairs))
        cur = pn.JoinNode("inner", cur, r,
                          [p[0] for p in pairs], [p[1] for p in pairs])
        offsets[idx] = width
        width += len(r.output_schema())
        placed.add(idx)
    # a projection restores the original column order on top
    out_schema = node.output_schema()
    exprs: List[Expression] = []
    for ri, rel in enumerate(rels):
        rtypes = rel.output_schema().types
        for ci in range(len(rtypes)):
            exprs.append(Alias(
                BoundReference(offsets[ri] + ci, rtypes[ci]),
                out_schema.names[len(exprs)]))
    return pn.ProjectNode(exprs, cur, names=list(out_schema.names))


# ---------------------------------------------------------------------------
# Column pruning below joins (the join part of Catalyst's ColumnPruning):
# a join emits every column of both inputs, so each input it is handed is
# gathered, null-extended and concatenated in full, string dictionaries
# included, whatever the plan above reads. Walking down from the root,
# every node asks its child for the columns it reads; a join input that
# outputs more than that is wrapped in a projection of bare references,
# and the ordinals of the join and of the nodes above it, up to the next
# node that re-projects, are rewritten old -> new. A node that reads every
# column of its child (window, union, expand, exchanges, ...) asks for all
# of them, and pruning starts afresh below it. A node several parents
# share is asked for all of its columns, so it is never copied per
# consumer; a projection holding a nondeterministic expression is kept
# whole, with all of its input.
# ---------------------------------------------------------------------------


def _remap(e: Expression, cmap: Optional[dict]) -> Expression:
    """``e`` with its bound references moved through ``cmap`` (old ->
    new ordinal; None: unchanged)."""
    if cmap is None:
        return e

    def fn(n):
        if isinstance(n, BoundReference) and cmap[n.ordinal] != n.ordinal:
            n = copy.copy(n)
            n.ordinal = cmap[n.ordinal]
        return n
    return e.transform(fn)


def _refs(exprs) -> set:
    return {o for e in exprs for o in e.references()}


class _JoinInputPruner:
    """One walk of a plan. ``prune(node, need)`` returns the node asked
    for the output ordinals ``need`` (None: all of them) and the map from
    its old output ordinals to its new ones (None: its output is
    unchanged); ``removed`` counts the columns taken off join inputs."""

    def __init__(self, plan: pn.PlanNode):
        self.parents: dict = {}
        self.memo: dict = {}
        self.removed = 0
        self.has_join = False
        stack = [plan]
        while stack:
            node = stack.pop()
            self.has_join |= isinstance(node, pn.JoinNode)
            for c in node.children:
                self.parents[id(c)] = self.parents.get(id(c), 0) + 1
                if self.parents[id(c)] == 1:
                    stack.append(c)

    def prune(self, node: pn.PlanNode, need: Optional[set]):
        if self.parents.get(id(node), 0) <= 1:
            return self._prune_one(node, need)
        hit = self.memo.get(id(node))
        if hit is None:
            hit = self.memo[id(node)] = (node,
                                         self._prune_one(node, None)[0])
        return hit[1], None

    def _prune_one(self, node: pn.PlanNode, need: Optional[set]):
        if isinstance(node, pn.JoinNode):
            return self._join(node, need)
        if isinstance(node, pn.ProjectNode):
            whole = not _all_deterministic(node.exprs)
            keep = list(range(len(node.exprs))) if need is None or whole \
                else sorted(need) or [0]
            exprs = [node.exprs[i] for i in keep]
            child, cm = self.prune(node.children[0],
                                   None if whole else _refs(exprs))
            if len(keep) == len(node.exprs) and \
                    child is node.children[0]:
                return node, None
            out = pn.ProjectNode([_remap(e, cm) for e in exprs], child,
                                 names=[node.names[i] for i in keep])
            return out, (None if len(keep) == len(node.exprs) else
                         {o: j for j, o in enumerate(keep)})
        if isinstance(node, pn.FilterNode):
            cond = node.condition
            child, cm = self.prune(node.children[0], None if need is None
                                   else need | _refs([cond]))
            if child is node.children[0]:
                return node, None
            return pn.FilterNode(_remap(cond, cm), child), cm
        if isinstance(node, pn.AggregateNode) and node.mode == "complete":
            child, cm = self.prune(node.children[0], _refs(
                list(node.grouping) + [a.fn for a in node.aggs]))
            if child is node.children[0]:
                return node, None
            return pn.AggregateNode(
                [_remap(e, cm) for e in node.grouping],
                [pn.AggCall(_remap(a.fn, cm), a.name) for a in node.aggs],
                child, grouping_names=list(node.grouping_names)), None
        if isinstance(node, pn.SortNode):
            child, cm = self.prune(node.children[0], None if need is None
                                   else need | {s.ordinal
                                                for s in node.specs})
            if child is node.children[0]:
                return node, None
            specs = [dataclasses.replace(s, ordinal=cm[s.ordinal])
                     if cm is not None else s for s in node.specs]
            return pn.SortNode(specs, child, node.global_sort), cm
        if isinstance(node, pn.LimitNode):
            child, cm = self.prune(node.children[0], need)
            if child is node.children[0]:
                return node, None
            return node.with_children([child]), cm
        children = [self.prune(c, None)[0] for c in node.children]
        if any(n is not o for n, o in zip(children, node.children)):
            node = node.with_children(children)
        return node, None

    def _input(self, node: pn.PlanNode, need: Optional[set]):
        """A join input asked for ``need``, narrowed to exactly that (a
        cross join's side that nothing reads keeps its first column)."""
        if need is not None and not need:
            need = {0}
        node, cm = self.prune(node, need)
        if need is None:
            return node, None
        keep = sorted(need)
        schema = node.output_schema()
        if len(schema) == len(keep):
            return node, cm
        self.removed += len(schema) - len(keep)
        at = [o if cm is None else cm[o] for o in keep]
        node = pn.ProjectNode([BoundReference(i, schema.types[i])
                               for i in at], node,
                              names=[schema.names[i] for i in at])
        return node, {o: j for j, o in enumerate(keep)}

    def _join(self, node: pn.JoinNode, need: Optional[set]):
        left, right = node.children
        lw = len(left.output_schema())
        semi = node.kind in ("left_semi", "left_anti")
        cond = node.condition
        read = _refs([cond] if cond is not None else [])
        if need is not None:
            read |= need
        lneed = set(node.left_keys) | {o for o in read if o < lw}
        rneed = set(node.right_keys) | {o - lw for o in read if o >= lw}
        if need is None:
            lneed = None
            if not semi:
                rneed = None
        new_left, lcm = self._input(left, lneed)
        new_right, rcm = self._input(right, rneed)
        if new_left is left and new_right is right:
            return node, None
        nlw = len(new_left.output_schema())
        both = {o: (o if lcm is None else lcm[o]) for o in range(lw)
                if lcm is None or o in lcm}
        both.update({lw + o: nlw + (o if rcm is None else rcm[o])
                     for o in range(len(right.output_schema()))
                     if rcm is None or o in rcm})
        out = pn.JoinNode(
            node.kind, new_left, new_right,
            [both[o] for o in node.left_keys],
            [both[lw + o] - nlw for o in node.right_keys],
            condition=None if cond is None else _remap(cond, both))
        cm = lcm if semi else (None if lcm is None and rcm is None
                               else both)
        return out, cm


def prune_join_inputs(plan: pn.PlanNode) -> tuple:
    """``plan`` with every join input narrowed to the columns read above
    it; returns (plan, columns removed from join inputs). The root's
    schema is unchanged, and a plan without a join comes back as it was."""
    pruner = _JoinInputPruner(plan)
    if not pruner.has_join:
        return plan, 0
    out, cm = pruner.prune(plan, None)
    assert cm is None
    return out, pruner.removed


def optimize(plan: pn.PlanNode) -> pn.PlanNode:
    plan = collapse_project(plan)
    # a join's one-sided ON conjuncts become filters on its inputs, which
    # the passes below move further down like any other filter
    plan = push_join_conditions(plan)
    # collapse first (filters drop through projections), then push
    # through joins, then collapse again (a pushed filter may meet
    # another filter/projection), then push the combined form once more
    plan = push_filters_below_joins(plan)
    plan = collapse_project(plan)
    plan = push_filters_below_joins(plan)
    plan = reorder_joins(plan)
    # the reorder's restore-projection may now collapse with outer ones
    plan = collapse_project(plan)
    plan = rewrite_distinct_aggregates(plan)
    # last: every join input narrowed to what is read above it, and the
    # narrowing projections collapsed with the ones they meet
    plan, removed = prune_join_inputs(plan)
    if removed:
        tracing.count("plan.prune.columns", removed)
        plan = collapse_project(plan)
    return plan


# ---------------------------------------------------------------------------
# Peak-footprint model (round-6, service admission): a static estimate
# of how many device bytes a query may pin at once, from the same
# footer-stat cardinalities the join reorder uses. The admission
# controller charges this against the HBM budget before letting a query
# onto the device (GpuSemaphore bounds WHO may enter; this bounds HOW
# MUCH the admitted set is expected to ask for).
# ---------------------------------------------------------------------------


def _row_width(node: pn.PlanNode) -> int:
    """Estimated device bytes per row of a node's output (kernel lane
    width + validity byte; strings are dictionary codes on device)."""
    schema = node.output_schema()
    return sum(t.byte_width + 1 for t in schema.types) or 1


def estimate_footprint_bytes(plan: pn.PlanNode,
                             default_rows: int = 1 << 20,
                             runtime_rows=None) -> int:
    """Estimated peak device bytes of executing ``plan``: the widest
    single operator's working set (its output plus every input it holds
    live) plus the broadcast/build sides and materialized exchanges that
    stay resident across the pipeline. Nodes without a cardinality
    estimate assume ``default_rows``. Deliberately coarse and
    conservative — admission needs an upper-bound-shaped number, not a
    point estimate; the spill catalog is the real enforcement.

    ``runtime_rows`` (AQE replan rule 3b: node -> rows | None) answers
    for nodes the STATIC estimator cannot — measured cardinalities from
    earlier runs of the same plan shape (execs.adaptive's registry) —
    so admission tightens as the workload repeats."""
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    resident = 0  # exchange/aggregate materializations live across stages

    def bytes_of(node: pn.PlanNode) -> int:
        rows = estimate_rows(node)
        if rows is None and runtime_rows is not None:
            rows = runtime_rows(node)
        rows = max(rows if rows is not None else default_rows, 1)
        # BUCKETED, not raw: device columns are padded to the capacity
        # ladder (ops/buckets), so the bytes a node actually pins are
        # the bucket's, not the row count's — an estimate off by up to
        # a full growth factor would under-admit against real HBM
        return bucket_capacity(rows) * _row_width(node)

    def walk(node: pn.PlanNode, seen) -> int:
        """Peak transient bytes of the subtree rooted at node."""
        nonlocal resident
        if id(node) in seen:  # shared CTE subtree: one materialization
            return 0
        seen.add(id(node))
        own = bytes_of(node)
        if isinstance(node, (pn.JoinNode, pn.AggregateNode, pn.SortNode,
                             pn.ShuffleExchangeNode)):
            # materialization points hold their input batches staged
            # (spillable, but device-first) while producing output
            resident += own
        child_peaks = [walk(c, seen) for c in node.children]
        return own + max(child_peaks, default=0)

    peak = walk(plan, set())
    return peak + resident


# ---------------------------------------------------------------------------
# Plan-cost model (round-5): a static dispatch-count estimate over the
# PHYSICAL tree, so tests can assert optimizer decisions (join reorder,
# broadcast selection) never make a plan costlier than the written
# order — the plan-quality guard the semantics fuzz can't provide.
# Weights are the measured per-exec dispatch shapes from BASELINE.md's
# telemetry, not wall-clock claims.
# ---------------------------------------------------------------------------


def plan_cost(exec_) -> int:
    """Estimated dispatch count of a physical exec tree. Runs under
    planning_mode so adaptive/range partition-count queries never
    materialize anything."""
    from spark_rapids_tpu.execs import adaptive as adaptive_exec

    with adaptive_exec.planning_mode():
        return _cost(exec_)


def _own_cost(e) -> int:
    """Estimated dispatch count of ONE exec (excluding children)."""
    from spark_rapids_tpu.execs import basic, joins
    from spark_rapids_tpu.execs.adaptive import AdaptiveShuffleReaderExec
    from spark_rapids_tpu.execs.aggregate import HashAggregateExec
    from spark_rapids_tpu.execs.batching import CoalesceBatchesExec
    from spark_rapids_tpu.execs.exchange import (BroadcastExchangeExec,
                                                 ShuffleExchangeExec)
    from spark_rapids_tpu.execs.fused import (FusedAggregateExec,
                                              FusedChainExec)
    from spark_rapids_tpu.execs.sort import SortExec

    parts = max(getattr(e, "num_partitions", 1), 1)
    if type(e).__name__.startswith("Mesh"):
        # whole-stage SPMD exec: one compiled shard_map launch plus a
        # staging/gather hop, independent of partition count — the
        # point of folding the shuffle into the program
        return 2
    if isinstance(e, FusedAggregateExec):
        # chain + single-pass groupby per partition; the build prep is
        # inlined into the chain's first launch (in-program build), so
        # builds no longer add their own dispatches
        own = 2 * parts
    elif isinstance(e, FusedChainExec):
        own = 1 * parts
    elif isinstance(e, HashAggregateExec):
        own = 3 * parts
    elif isinstance(e, joins.HashJoinExec):
        own = 6 * parts  # probe/expand/emit chain + count sync
    elif isinstance(e, (joins.BroadcastNestedLoopJoinExec,
                        joins.CartesianProductExec)):
        # full pair-grid materialization: the guard must never score a
        # hash->nested-loop degradation as an improvement
        own = 50 * parts
    elif isinstance(e, AdaptiveShuffleReaderExec):
        own = 0  # a view over its exchange; the exchange carries cost
    elif isinstance(e, ShuffleExchangeExec):
        if getattr(e, "in_program", False):
            # staging gather + ONE all_to_all program + result gather,
            # regardless of batch or partition count
            own = 3
        else:
            own = 2 * max(e.children[0].num_partitions, 1) + parts
    elif isinstance(e, BroadcastExchangeExec):
        own = 2
    elif isinstance(e, basic.FilterExec):
        own = 2 * parts
    elif isinstance(e, (basic.ProjectExec, CoalesceBatchesExec)):
        own = 1 * parts
    elif isinstance(e, SortExec):
        own = 2 * parts
    elif isinstance(e, basic.ScanExec):
        own = 1 * parts
    else:
        own = 2 * parts  # unknown execs are not free
    return own


def _cost(e) -> int:
    return _own_cost(e) + sum(_cost(c) for c in e.children)


# ---------------------------------------------------------------------------
# Stage cutting (round-6): partition the PHYSICAL tree into pipeline
# stages — maximal regions whose per-batch dispatches the fusion pass
# coalesces toward one program — and label every exec with its stage so
# dispatch telemetry attributes round trips per stage. Stage breakers
# are the materialization points: exchanges (a broadcast/shuffle build
# runs to completion before its consumer), aggregates (the merge loop
# drains its input), and sorts (a global sort stages everything).
# ---------------------------------------------------------------------------


def _is_stage_breaker(e) -> bool:
    from spark_rapids_tpu.execs.aggregate import HashAggregateExec
    from spark_rapids_tpu.execs.exchange import (BroadcastExchangeExec,
                                                 ShuffleExchangeExec)
    from spark_rapids_tpu.execs.sort import SortExec

    if isinstance(e, ShuffleExchangeExec) and \
            getattr(e, "in_program", False):
        # the shuffle is a collective inside the enclosing stage's
        # program, not a materialization boundary: child and consumer
        # share one stage (whole-stage SPMD execution)
        return False
    return isinstance(e, (HashAggregateExec, ShuffleExchangeExec,
                          BroadcastExchangeExec, SortExec))


def cut_stages(root) -> List[dict]:
    """Assign ``_stage_label`` to every exec and return the stage list:
    [{stage, ops, est_dispatches, mesh_internal}] in discovery
    (top-down) order. ``mesh_internal`` marks stages whose shuffle is
    an in-program mesh collective rather than a host exchange. A
    stage starts at the root, below every breaker, and at every
    broadcast build subtree (reached via ``.builds`` on fused execs —
    those exchanges are not ``children``). ``est_dispatches`` is the
    static per-stage dispatch estimate from the plan-cost model, so
    bench output can show where a query's round-trip budget sits
    BEFORE running it."""
    from spark_rapids_tpu.execs import adaptive as adaptive_exec

    stages: List[dict] = []
    seen: set = set()

    def new_stage() -> dict:
        s = {"stage": f"stage{len(stages)}", "ops": [],
             "est_dispatches": 0, "mesh_internal": False}
        stages.append(s)
        return s

    def walk(node, stage) -> None:
        if id(node) in seen:
            return
        seen.add(id(node))
        if stage is None:
            stage = new_stage()
        node._stage_label = stage["stage"]
        stage["ops"].append(node.name)
        stage["est_dispatches"] += _own_cost(node)
        if node.name.startswith("Mesh") or \
                getattr(node, "in_program", False):
            # this stage's shuffle rides an in-program collective over
            # the mesh (no host exchange at its boundary)
            stage["mesh_internal"] = True
        breaker = _is_stage_breaker(node)
        for c in node.children:
            walk(c, None if breaker else stage)
        for bx in getattr(node, "builds", ()) or ():
            walk(bx, None)

    with adaptive_exec.planning_mode():
        walk(root, None)
    return stages
