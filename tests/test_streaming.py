"""Streaming ingestion & standing queries (service/streaming): the
acceptance suite. The load-bearing fences:

- EQUIVALENCE: a standing query folded over N appended micro-batches —
  including out-of-order / late ones — must match the batch engine run
  over the concatenated input (the batch engine is the oracle; the
  stream table's read_host IS the concatenation).
- RESILIENCE: a fold that trips an injected OOM at its own retry sites
  walks the same spill/halve ladder as a batch aggregation and still
  produces the oracle answer.
- LIFECYCLE: cancel (including cancel MID-FOLD through the test seam)
  releases every owner-tagged catalog buffer — ``owner_refcounts`` must
  come back empty, the same leak fence batch queries have.
"""
import numpy as np
import pandas as pd
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.api import Session
from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.memory import fault_injection as FI
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu.plan.incremental import (IncrementalUnsupported,
                                               analyze)
from spark_rapids_tpu.service import QueryService
from spark_rapids_tpu.service.streaming import stats as sstats
from spark_rapids_tpu.service.streaming.standing import (
    CANCELLED, EMITTING, FAILED, StreamingStateOverflow)

from tests.compare import assert_frames_equal

SCHEMA = Schema(["k", "v", "ev"], [dt.INT64, dt.FLOAT64, dt.INT64])
AGG_SQL = ("SELECT k, SUM(v) AS sv, COUNT(v) AS c "
           "FROM events GROUP BY k")


@pytest.fixture(autouse=True)
def _clean_injector():
    FI.get_injector().disarm()
    yield
    FI.get_injector().disarm()


def _batch(seed, n=200, nk=7, t0=0):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, nk, n).astype(np.int64),
            "v": rng.random(n),
            "ev": (t0 + rng.integers(0, 1000, n)).astype(np.int64)}


def _frame(batches):
    return pd.concat([pd.DataFrame(b) for b in batches],
                     ignore_index=True)


def _session():
    s = Session()
    src = s.create_streaming_table("events", SCHEMA)
    return s, src


def _oracle(frame):
    return frame.groupby("k").agg(
        sv=("v", "sum"), c=("v", "count")).reset_index()


# -- equivalence ------------------------------------------------------------


def test_incremental_matches_batch_over_appends():
    """q5lite-style streaming aggregation over N appended micro-batches
    == the batch engine over the concatenated input, checked at EVERY
    emit point (not just the end)."""
    s, src = _session()
    df = s.sql(AGG_SQL)
    try:
        sq = s.service.register_standing(df)
        seen = []
        for i in range(6):
            b = _batch(seed=i, n=150 + 37 * i, t0=i * 1000)
            seen.append(b)
            s.append_batch("events", b)
            assert sq.state == EMITTING and sq.folds == i + 1
            # the batch engine over the SAME table is the oracle
            assert_frames_equal(_oracle(_frame(seen)), sq.results())
            assert_frames_equal(df.to_pandas(), sq.results())
        assert sq.rows_folded == sum(len(b["k"]) for b in seen)
    finally:
        s.stop()


def test_fold_of_integer_sums_matches_oracle_at_every_emit():
    """A standing aggregation of integer sums and counts folded over
    appended micro-batches of growing size equals the pandas oracle,
    exactly, at every emit point: the fold seam re-enters the same
    fused chain each time."""
    s = Session()
    s.create_streaming_table(
        "events", Schema(["k", "v"], [dt.INT64, dt.INT64]))
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL))
        seen = []
        for i in range(3):
            r = np.random.default_rng(i)
            b = {"k": r.integers(0, 7, 120 + 11 * i).astype(np.int64),
                 "v": r.integers(0, 100,
                                 120 + 11 * i).astype(np.int64)}
            seen.append(b)
            s.append_batch("events", b)
            assert_frames_equal(_oracle(_frame(seen)), sq.results())
    finally:
        s.stop()


def test_catchup_folds_preexisting_deltas():
    """Registering AFTER appends must fold the backlog immediately —
    a standing query never misses data that landed before it."""
    s, src = _session()
    try:
        batches = [_batch(seed=i) for i in range(3)]
        for b in batches:
            src.append(b)
        sq = s.service.register_standing(s.sql(AGG_SQL))
        assert sq.folds == 3
        assert_frames_equal(_oracle(_frame(batches)), sq.results())
    finally:
        s.stop()


def test_out_of_order_late_batches_merge_to_oracle():
    """Late rows (event time at-or-below the watermark on arrival)
    re-merge through the same merge specs: the final answer equals the
    batch oracle over ALL rows, and the late-row counter proves the
    late path actually ran."""
    s, src = _session()
    try:
        sq = s.service.register_standing(
            s.sql(AGG_SQL), event_time_col="ev", watermark_ms=100,
            late_policy="merge")
        on_time = [_batch(seed=i, t0=10_000 * (i + 1))
                   for i in range(3)]
        for b in on_time:
            s.append_batch("events", b)
        assert sq.watermark == max(int(np.max(b["ev"]))
                                   for b in on_time) - 100
        late = _batch(seed=9, t0=0)   # far below the watermark
        s.append_batch("events", late)
        assert sq.late_rows_remerged == len(late["k"])
        assert_frames_equal(_oracle(_frame(on_time + [late])),
                            sq.results())
        # max() watermark never retreats on out-of-order arrival
        assert sq.watermark == max(int(np.max(b["ev"]))
                                   for b in on_time) - 100
    finally:
        s.stop()


def test_late_policy_drop_excludes_late_rows():
    s, src = _session()
    try:
        sq = s.service.register_standing(
            s.sql(AGG_SQL), event_time_col="ev", watermark_ms=0,
            late_policy="drop")
        first = _batch(seed=1, t0=50_000)
        s.append_batch("events", first)
        late = _batch(seed=2, t0=0)
        s.append_batch("events", late)
        assert sq.late_rows_dropped == len(late["k"])
        # oracle over the on-time rows only
        assert_frames_equal(_oracle(_frame([first])), sq.results())
    finally:
        s.stop()


def test_windowed_finalization_under_watermark():
    """Grouping by a window-end column: final_only emits exactly the
    windows the watermark has passed."""
    s = Session()
    s.create_streaming_table(
        "w", Schema(["wend", "v"], [dt.INT64, dt.INT64]))
    try:
        sq = s.service.register_standing(
            s.sql("SELECT wend, SUM(v) AS sv FROM w GROUP BY wend"),
            event_time_col="wend", window_col="wend",
            watermark_ms=500)
        s.append_batch("w", {"wend": np.array([1000, 2000, 3000]),
                             "v": np.array([1, 2, 3])})
        # watermark = 3000 - 500 = 2500: windows 1000 and 2000 final
        fin = sq.results(final_only=True)
        assert sorted(fin["wend"]) == [1000, 2000]
        full = sq.results(final_only=False)
        assert sorted(full["wend"]) == [1000, 2000, 3000]
    finally:
        s.stop()


def test_streaming_join_keeps_dimension_build_across_folds():
    """A streaming fact joined against a non-streaming dimension: the
    per-fold exec reset clears only delta-reachable state, so the
    dimension build materializes ONCE and every fold still matches the
    batch oracle."""
    from spark_rapids_tpu.execs.exchange import BroadcastExchangeExec

    s = Session()
    s.create_streaming_table(
        "fact", Schema(["k", "v"], [dt.INT64, dt.INT64]))
    dim = pd.DataFrame({"k": np.arange(8, dtype=np.int64),
                        "w": np.arange(8, dtype=np.int64) * 10})
    s.create_temp_view("dim", s.create_dataframe(dim))
    q = s.sql("SELECT dim.w AS w, SUM(fact.v) AS sv FROM fact "
              "JOIN dim ON fact.k = dim.k GROUP BY dim.w")
    try:
        sq = s.service.register_standing(q)
        state = sq.agg_state
        builds = [e for e in _walk_execs(state._child_exec)
                  if isinstance(e, BroadcastExchangeExec)]
        seen = []
        cached_after_first = None
        for i in range(4):
            b = {"k": np.random.RandomState(i).randint(0, 8, 100)
                 .astype(np.int64),
                 "v": np.arange(100, dtype=np.int64)}
            seen.append(b)
            s.append_batch("fact", b)
            if builds and not _reaches_delta(state, builds[0]):
                if cached_after_first is None:
                    cached_after_first = builds[0]._cached
                    assert cached_after_first is not None
                else:
                    # the SAME materialized build object, not a rebuild
                    assert builds[0]._cached is cached_after_first
        fact = _frame(seen)
        oracle = fact.merge(dim, on="k").groupby("w").agg(
            sv=("v", "sum")).reset_index()
        assert_frames_equal(oracle, sq.results())
        assert_frames_equal(q.to_pandas(), sq.results())
    finally:
        s.stop()


def _walk_execs(root):
    out, stack = [], [root]
    while stack:
        e = stack.pop()
        out.append(e)
        stack.extend(getattr(e, "children", ()))
        if hasattr(e, "builds"):
            stack.extend(e.builds)
            stack.append(e.fallback)
    return out


def _reaches_delta(state, e):
    return state._reaches_delta(e, {})


def test_pandas_append_with_nulls():
    """Session.append_batch accepts a pandas frame; NaNs become
    validity masks exactly like create_dataframe, and COUNT(v) counts
    only valid rows."""
    s = Session()
    s.create_streaming_table(
        "t", Schema(["k", "v"], [dt.INT64, dt.FLOAT64]))
    try:
        sq = s.service.register_standing(
            s.sql("SELECT k, SUM(v) AS sv, COUNT(v) AS c "
                  "FROM t GROUP BY k"))
        pdf = pd.DataFrame({"k": [0, 0, 1, 1, 1],
                            "v": [1.0, np.nan, 2.0, np.nan, 4.0]})
        s.append_batch("t", pdf)
        res = sq.results().sort_values("k").reset_index(drop=True)
        assert list(res["c"]) == [1, 2]
        assert res["sv"].tolist() == pytest.approx([1.0, 6.0])
    finally:
        s.stop()


# -- resilience -------------------------------------------------------------


def test_injected_oom_fold_walks_retry_ladder():
    """An injected OOM at the fold's own retry sites must not change
    the answer — the ladder spills/halves and the fold completes; the
    per-owner retry ledger records the retries."""
    s, src = _session()
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL))
        b0 = _batch(seed=0)
        s.append_batch("events", b0)
        FI.get_injector().arm(at_call=1, consecutive=1,
                              sites=["streaming.fold"])
        b1 = _batch(seed=1)
        s.append_batch("events", b1)
        FI.get_injector().disarm()
        assert sq.state == EMITTING, sq.error
        assert_frames_equal(_oracle(_frame([b0, b1])), sq.results())
        from spark_rapids_tpu.memory import retry as R
        owner = R.owner_stats(sq.owner_tag)
        assert owner["oom_retries"] >= 1, \
            "the injected fold OOM must be visible in the retry ledger"
        per_site = R.stats()["per_site"]
        assert any(site.startswith("streaming.fold")
                   and d["oom_retries"] >= 1
                   for site, d in per_site.items()), per_site
    finally:
        s.stop()


def test_max_state_bytes_fails_query_and_tears_down():
    s, src = _session()
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL),
                                         max_state_bytes=1)
        s.append_batch("events", _batch(seed=0))
        assert sq.state == FAILED
        assert isinstance(sq.error, StreamingStateOverflow)
        assert get_catalog().owner_refcounts(sq.owner_tag) == {}, \
            "state-overflow teardown leaked owner-tagged buffers"
        with pytest.raises(StreamingStateOverflow):
            sq.results()
        # the append itself survived: batch queries still see the rows
        assert src.total_rows == 200
    finally:
        s.stop()


# -- lifecycle / leak fence -------------------------------------------------


def test_cancel_mid_fold_releases_owner_tags():
    """Cancel landing BETWEEN fold steps (through the deterministic
    test seam): the fold aborts, the standing query is CANCELLED, and
    the catalog holds ZERO buffers under its owner tag."""
    s, src = _session()
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL))
        s.append_batch("events", _batch(seed=0))
        calls = []

        def hook():
            # fire the cancel exactly once, mid-fold
            if not calls:
                calls.append(1)
                sq._cancel_requested = True

        sq._fold_hook = hook
        s.append_batch("events", _batch(seed=1))
        assert calls, "the fold never reached the seam"
        assert sq.state == CANCELLED
        assert get_catalog().owner_refcounts(sq.owner_tag) == {}, \
            "cancel mid-fold leaked owner-tagged catalog buffers"
        from spark_rapids_tpu.service.types import QueryCancelled
        with pytest.raises(QueryCancelled):
            sq.results()
        # later appends land (the table outlives the query) but are
        # not folded by the dead query
        s.append_batch("events", _batch(seed=2))
        assert sq.folds == 1 and src.num_appends == 3
    finally:
        s.stop()


def test_cancel_idle_releases_owner_tags():
    s, src = _session()
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL))
        for i in range(3):
            s.append_batch("events", _batch(seed=i))
        assert sq.agg_state.state_bytes() > 0
        assert sq.cancel() and sq.state == CANCELLED
        assert get_catalog().owner_refcounts(sq.owner_tag) == {}
        assert sq.agg_state.state_bytes() == 0
    finally:
        s.stop()


def test_shutdown_cancels_standing_queries():
    s, src = _session()
    sq = s.service.register_standing(s.sql(AGG_SQL))
    s.append_batch("events", _batch(seed=0))
    tag = sq.owner_tag
    s.stop()
    assert sq.terminal
    assert get_catalog().owner_refcounts(tag) == {}


# -- validation -------------------------------------------------------------


def test_unsupported_shapes_are_rejected():
    s, src = _session()
    try:
        # no aggregate on top
        with pytest.raises(IncrementalUnsupported, match="aggregation"):
            analyze(s.sql("SELECT k, v FROM events"))
        # no streaming source at all
        s.create_temp_view("plain", s.create_dataframe(
            {"k": np.array([1]), "v": np.array([1.0])}))
        with pytest.raises(IncrementalUnsupported,
                           match="no streaming table"):
            analyze(s.sql("SELECT k, SUM(v) AS sv FROM plain "
                          "GROUP BY k"))
        # bad knobs
        with pytest.raises(ValueError, match="late_policy"):
            s.service.register_standing(s.sql(AGG_SQL),
                                        late_policy="teleport")
        with pytest.raises(ValueError, match="event_time_col"):
            s.service.register_standing(s.sql(AGG_SQL),
                                        event_time_col="nope")
        # disabled by conf
        s2 = Session({cfg.STREAMING_ENABLED.key: False})
        src2 = s2.create_streaming_table("events", SCHEMA)
        try:
            with pytest.raises(RuntimeError, match="disabled"):
                s2.service.register_standing(s2.sql(AGG_SQL))
        finally:
            s2.stop()
    finally:
        s.stop()


def test_ragged_and_missing_column_appends_rejected():
    s, src = _session()
    try:
        with pytest.raises(ValueError, match="missing columns"):
            src.append({"k": np.array([1])})
        with pytest.raises(ValueError, match="ragged"):
            src.append({"k": np.array([1, 2]), "v": np.array([1.0]),
                        "ev": np.array([0, 1])})
    finally:
        s.stop()


# -- observability ----------------------------------------------------------


def test_service_stats_streaming_block():
    pre = sstats.snapshot()
    s, src = _session()
    try:
        sq = s.service.register_standing(s.sql(AGG_SQL),
                                         event_time_col="ev")
        for i in range(2):
            s.append_batch("events", _batch(seed=i, t0=i * 10_000))
        sq.results()
        st = s.service.stats().streaming
        for key in ("standing_live", "folds", "state_bytes",
                    "device_resident_bytes", "watermark_lag_ms",
                    "late_rows_remerged", "standing"):
            assert key in st, f"streaming stats block missing {key}"
        assert st["standing_live"] == 1
        d = sstats.delta(pre)
        assert d["appends"] == 2 and d["folds"] == 2
        assert d["emits"] >= 1 and d["rows_appended"] == \
            sq.rows_folded
        mine = [q for q in st["standing"]
                if q["standing_id"] == sq.query_id]
        assert mine and mine[0]["state"] == EMITTING
        assert mine[0]["folds"] == 2
    finally:
        s.stop()
