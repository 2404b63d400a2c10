"""One launch schedule for a group-by (PR 29).

ops/groupby.py used to split wide aggregate lists (more than six
columns at capacity >= 32,768 on the sort path) into two launches, a
workaround for a compile crash of a libtpu from 2026-07, behind a
user-set switch. The installed compiler builds the whole program
(tests/test_tpu_compile.py keeps asking it), so one ``_groupby`` launch
is the only schedule. This suite holds that one path to a numpy oracle
at the shapes the loop guarded — bit for bit on integer aggregates, to
the last place on float ones, with and without a fused filter mask —
keeps dense against sort, and covers the exec-level
_COMPACT_WIDE_MIN_CAP pre-pass.
"""
import numpy as np
import pytest

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.ops import groupby as gb
from spark_rapids_tpu.ops.groupby import AggSpec

WIDE_CAP = 1 << 15      # the capacity from which the chunk loop ran

# 9 aggregates over a float and an int column
WIDE_AGGS = [AggSpec("sum", 1), AggSpec("min", 1), AggSpec("max", 1),
             AggSpec("count", 1), AggSpec("sum", 2), AggSpec("min", 2),
             AggSpec("max", 2), AggSpec("count", 2),
             AggSpec("count_star")]

ORDER_INSENSITIVE_WIDE = [AggSpec("min", 1), AggSpec("max", 1),
                          AggSpec("count", 1), AggSpec("sum", 2),
                          AggSpec("min", 2), AggSpec("max", 2),
                          AggSpec("count_star")]       # exact on any
                                                       # kernel


def _wide_batch(rng, n, span, with_stats=False):
    keys = rng.integers(0, span, n).astype(np.int64)
    keys[:min(span, n)] = np.arange(min(span, n))
    f = rng.standard_normal(n)
    f[rng.random(n) < 0.05] = np.nan
    f[rng.random(n) < 0.05] = -0.0
    i = rng.integers(-1000, 1000, n).astype(np.int64)
    kcol = Column.from_numpy(keys)
    if with_stats:
        kcol.stats = (0, span - 1)
    cols = [kcol,
            Column.from_numpy(f, validity=rng.random(n) > 0.1),
            Column.from_numpy(i, validity=rng.random(n) > 0.1)]
    return ColumnarBatch(cols, n), [dt.INT64, dt.FLOAT64, dt.INT64]


def _rows(out, num_aggs):
    """Realized (key -> agg tuple) dict with float BITS for exactness."""
    import jax

    n = out.realized_num_rows()
    cols = []
    for c in out.columns:
        data = np.asarray(jax.device_get(c.data))[:n]
        if data.dtype.kind == "f":
            data = data.view(f"u{data.dtype.itemsize}")
        valid = np.ones(n, bool) if c.validity is None else \
            np.asarray(jax.device_get(c.validity))[:n]
        cols.append((data, valid))
    rows = {}
    for i in range(n):
        key = (cols[0][0][i].item(), bool(cols[0][1][i]))
        rows[key] = tuple(
            (cols[j][0][i].item(), bool(cols[j][1][i]))
            for j in range(1, 1 + num_aggs))
    return rows


def _count_launches(fn):
    """Run ``fn`` counting _groupby invocations."""
    calls = []
    real = gb._groupby

    def spy(*a, **k):
        calls.append(1)
        return real(*a, **k)

    gb._groupby = spy
    try:
        out = fn()
    finally:
        gb._groupby = real
    return out, len(calls)


def _oracle_rows(b, aggs, mask=None):
    """numpy, a group at a time: (key -> agg tuple) as ``_rows`` gives
    it. Floats as (bits, valid) where the answer is order-free (min,
    max), as the float itself for sums."""
    import jax

    n = b.realized_num_rows()
    live = np.ones(n, bool) if mask is None else np.asarray(mask)[:n]
    cols = []
    for c in b.columns:
        d = np.asarray(jax.device_get(c.data))[:n]
        v = np.ones(n, bool) if c.validity is None else \
            np.asarray(jax.device_get(c.validity))[:n]
        cols.append((d, v))
    keys = cols[0][0]
    rows = {}
    for k in np.unique(keys[live]):
        g = live & (keys == k)
        out = []
        for spec in aggs:
            if spec.op == "count_star":
                out.append((int(g.sum()), True))
                continue
            d, v = cols[spec.ordinal]
            x = d[g & v]
            if spec.op == "count":
                out.append((len(x), True))
            elif len(x) == 0:
                out.append((None, False))
            elif spec.op == "sum":
                # pairwise, as good as any order the engine may take
                out.append((x.sum().item(), True))
            elif x.dtype.kind == "f" and np.isnan(x).any():
                out.append((float("nan"), True))
            else:
                out.append(((x.min() if spec.op == "min"
                             else x.max()).item(), True))
        rows[(k.item(), True)] = tuple(out)
    return rows


def _assert_rows_match_oracle(got, want, aggs, types):
    """Integer aggregates and counts bit for bit; float min/max equal
    as values (NaN == NaN, -0.0 == 0.0: either zero of a group is its
    least); float sums to the last place of the group's largest
    partial sum."""
    assert got.keys() == want.keys()
    for key, wrow in want.items():
        grow = got[key]
        for (gv, gok), (wv, wok), spec in zip(grow, wrow, aggs):
            assert gok == wok, (key, spec)
            if not wok:
                continue
            is_float = spec.ordinal >= 0 and \
                types[spec.ordinal] is dt.FLOAT64 and \
                spec.op in ("sum", "min", "max")
            if not is_float:
                assert gv == wv, (key, spec, gv, wv)
                continue
            g = np.array(gv, np.uint64).view(np.float64).item()
            if spec.op == "sum":
                assert np.isnan(g) if np.isnan(wv) else \
                    abs(g - wv) <= 64 * np.spacing(max(abs(wv), 1.0)), \
                    (key, spec, g, wv)
            else:
                assert (np.isnan(g) and np.isnan(wv)) or g == wv, \
                    (key, spec, g, wv)


@pytest.mark.parametrize("masked", [False, True])
def test_wide_groupby_matches_oracle_bit_exact(masked):
    """At the shape the chunk loop guarded (9 aggregates, capacity
    32,768, sort path) the aggregate is ONE launch and equals the numpy
    oracle, with and without a fused filter live_mask."""
    rng = np.random.default_rng(42 + masked)
    b, types = _wide_batch(rng, WIDE_CAP, 1000)
    mask = (rng.random(b.capacity) > 0.3) if masked else None
    out, launches = _count_launches(lambda: gb.groupby_aggregate(
        b, [0], WIDE_AGGS, types, live_mask=mask))
    assert launches == 1
    _assert_rows_match_oracle(_rows(out[0], len(WIDE_AGGS)),
                              _oracle_rows(b, WIDE_AGGS, mask),
                              WIDE_AGGS, types)


@pytest.mark.parametrize("width", [6, 7, 9])
def test_agg_width_boundary(width):
    """Six aggregates, seven, nine: ONE ``_groupby`` launch each, and
    the first six columns of a wider run are the six-wide run's, bit
    for bit — adding an aggregate must not perturb its neighbours."""
    rng = np.random.default_rng(7)
    b, types = _wide_batch(rng, WIDE_CAP, 500)
    out6, n6 = _count_launches(lambda: gb.groupby_aggregate(
        b, [0], WIDE_AGGS[:6], types))
    out, n = _count_launches(lambda: gb.groupby_aggregate(
        b, [0], WIDE_AGGS[:width], types))
    assert n6 == 1 and n == 1
    assert _rows(out6[0], 6) == {
        k: v[:6] for k, v in _rows(out[0], width).items()}


def test_dense_and_sort_paths_agree():
    """Order-insensitive wide aggregate, dense-eligible key span: the
    dense sweep (stats) and the sort kernel (no stats) produce the same
    bits, each in one launch."""
    rng = np.random.default_rng(13)
    n = WIDE_CAP
    b_stats, types = _wide_batch(rng, n, 100, with_stats=True)
    b_plain = ColumnarBatch(list(b_stats.columns), n)
    b_plain.columns[0] = Column(dt.INT64, b_stats.columns[0].data,
                                b_stats.columns[0].validity)  # no stats
    assert gb._dense_layout(types, [0], (gb.key_range_of(
        b_stats.columns[0], dt.INT64),), (False,)) is not None
    na = len(ORDER_INSENSITIVE_WIDE)
    out_d, nd = _count_launches(lambda: gb.groupby_aggregate(
        b_stats, [0], ORDER_INSENSITIVE_WIDE, types))
    out_s, ns = _count_launches(lambda: gb.groupby_aggregate(
        b_plain, [0], ORDER_INSENSITIVE_WIDE, types))
    assert nd == 1 and ns == 1
    assert _rows(out_d[0], na) == _rows(out_s[0], na)


def test_exec_compact_wide_matches_the_cpu_oracle(monkeypatch):
    """Exec level: the _COMPACT_WIDE_MIN_CAP pre-pass (compact filtered
    survivors before a wide sort-path aggregate) — with the boundary
    lowered into range the compaction engages and the answer still
    matches the CPU oracle; at the default boundary (capacity far below
    1<<22) it must NOT engage."""
    from compare import assert_cpu_and_tpu_equal
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.execs.aggregate import HashAggregateExec
    from spark_rapids_tpu.plan import nodes as pn
    from spark_rapids_tpu.sql import parse, plan_statement

    rng = np.random.default_rng(17)
    n = 4096
    src = pn.InMemorySource(
        {"k": rng.integers(0, 1000, n).astype(np.int64),
         "v": rng.standard_normal(n),
         "w": rng.integers(-50, 50, n).astype(np.int64)},
        validity={"v": rng.random(n) > 0.1})
    sql = ("SELECT k, sum(v) AS a1, min(v) AS a2, max(v) AS a3, "
           "count(v) AS a4, sum(w) AS a5, min(w) AS a6, max(w) AS a7 "
           "FROM t WHERE v > 0.2 GROUP BY k ORDER BY k")
    plan = plan_statement(parse(sql), {"t": src})

    compacted = []
    real = HashAggregateExec._maybe_compact_wide

    def spy(self, b, mask):
        nb, nm = real(self, b, mask)
        if mask is not None and nm is None:
            compacted.append(nb.capacity)
        return nb, nm

    monkeypatch.setattr(HashAggregateExec, "_maybe_compact_wide", spy)
    for min_cap in (256, HashAggregateExec._COMPACT_WIDE_MIN_CAP):
        monkeypatch.setattr(HashAggregateExec, "_COMPACT_WIDE_MIN_CAP",
                            min_cap)
        compacted.clear()
        assert_cpu_and_tpu_equal(plan, conf=RapidsConf(), sort=False,
                                 approx_float=1e-9)
        if min_cap == 256:
            assert compacted, \
                "compact-wide pre-pass should engage below the " \
                "lowered boundary"
        else:
            assert not compacted
