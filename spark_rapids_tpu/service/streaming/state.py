"""StreamingAggregateState: long-lived partial columns, folded per batch.

The incremental engine in one picture::

    micro-batch ──delta exec tree──> raw rows
        ──update_partials──> delta partials        (1 update launch)
        ──merge_partials(running, delta)──> running' (1 merge launch)

``running`` is the (keys..., partials...) merge-schema batch from
execs/aggregate's update/merge split, held across folds as a
SpillableBatch: owner-tagged in the catalog so it rides the
device->host->disk spill chain between folds, counts against the
service's admission footprint while device-resident, and one
``remove_owner`` call tears it down on cancel. Each fold's cost tracks
the micro-batch — the running state is touched only by the single
merge, never rescanned.

The delta exec tree is planned ONCE (apply_overrides over the delta
subplan from plan/incremental) and re-driven per fold. Exec-side
materializations that read the delta (shuffle blocks, delta-side
broadcast builds) are reset each fold; dimension-side broadcast builds
and fused-chain prepared builds are delta-unreachable and survive — the
PR 13 inline-build tables stay device-resident across folds for free.

Both fold launches run under the OOM retry ladder at their own sites
(``streaming.fold.update`` / ``streaming.fold.merge``): a fold that
trips device pressure spills, retries, and splits exactly like a batch
aggregation — and the fault injector can target a fold without touching
batch queries.
"""
from __future__ import annotations

from typing import Optional

from spark_rapids_tpu.execs.base import all_execs
from spark_rapids_tpu.execs.exchange import (BroadcastExchangeExec,
                                             ShuffleExchangeExec,
                                             close_query_blocks)
from spark_rapids_tpu.memory.catalog import (StorageTier, get_catalog,
                                             set_buffer_owner)
from spark_rapids_tpu.memory.priorities import STREAMING_STATE_PRIORITY
from spark_rapids_tpu.memory.spillable import SpillableBatch
from spark_rapids_tpu.plan import incremental

UPDATE_SITE = "streaming.fold.update"
MERGE_SITE = "streaming.fold.merge"


class StreamingAggregateState:
    """Device-resident incremental aggregate for ONE standing query.
    Not thread-safe: the owning StandingQuery serializes folds under
    its lock."""

    def __init__(self, info: incremental.IncrementalInfo, conf,
                 owner_tag):
        from spark_rapids_tpu.execs.aggregate import HashAggregateExec
        from spark_rapids_tpu.plan.overrides import apply_overrides
        from spark_rapids_tpu.service.streaming.source import \
            DeltaBatchSource

        self.owner_tag = owner_tag
        self.schema = info.aggregate.output_schema()
        #: rename-only projection above the aggregate — applied to the
        #: EMITTED frame only, the running partials never see it
        self.projection = info.projection
        self.output_names = info.output_names()
        self.delta_source = DeltaBatchSource(info.stream_source.schema())
        delta_plan = incremental.substitute_source(
            info.child, info.stream_source, self.delta_source)
        self._child_exec = apply_overrides(delta_plan, conf)
        # the aggregate exec is built directly (not via the planner):
        # its execute() loop is never driven — the state drives the
        # update/merge seam methods so the running partials survive
        # across folds instead of dying with each execute()
        self._agg = HashAggregateExec(
            info.aggregate.grouping, info.aggregate.aggs,
            self._child_exec, self.schema, mode="complete", conf=conf)
        self._running: Optional[SpillableBatch] = None
        self.folds = 0
        self.rows_folded = 0

    # -- fold ----------------------------------------------------------

    def fold(self, data, validity, num_rows: int,
             cancel_check=None) -> int:
        """Fold one micro-batch into the running partials; returns the
        rows folded. ``cancel_check`` (if given) is called at step
        boundaries and may raise to abort the fold — the running state
        is swapped only as the LAST step, so an aborted fold leaves the
        previous state intact."""
        prev_owner = set_buffer_owner(self.owner_tag)
        try:
            self.delta_source.set_delta(data, validity, num_rows)
            self._reset_delta_path()
            try:
                parts = []
                for p in range(self._child_exec.num_partitions):
                    for b in self._child_exec.execute(p):
                        if b.realized_num_rows() == 0:
                            continue
                        parts.append(self._agg.update_partials(
                            b, site=UPDATE_SITE))
                        if cancel_check is not None:
                            cancel_check()
            finally:
                self.delta_source.clear()
            if not parts:
                self.folds += 1
                return 0
            part = parts[0]
            for extra in parts[1:]:
                part = self._agg.merge_partials(part, extra,
                                                site=MERGE_SITE)
            if cancel_check is not None:
                cancel_check()
            if self._running is None:
                merged = part
            else:
                with self._running.acquired() as rb:
                    merged = self._agg.merge_partials(rb, part,
                                                      site=MERGE_SITE)
            old, self._running = self._running, SpillableBatch(
                merged, STREAMING_STATE_PRIORITY)
            if old is not None:
                old.close()
            self.folds += 1
            self.rows_folded += num_rows
            return num_rows
        finally:
            set_buffer_owner(prev_owner)

    # -- emit ----------------------------------------------------------

    def emit(self):
        """Finalize the running partials into a pandas frame (the
        partials are NOT consumed — folding continues)."""
        import pandas as pd

        from spark_rapids_tpu.utils import dispatch as _disp

        if self._running is None:
            return pd.DataFrame({n: pd.Series([], dtype=object)
                                 for n in self.output_names})
        prev_owner = set_buffer_owner(self.owner_tag)
        try:
            with self._running.acquired() as rb:
                out = self._agg.finalize_partials(rb)
            tok = _disp.enter_stage("result_sync")
            try:
                frame = out.to_pandas(self.schema)
            finally:
                _disp.exit_stage(tok)
        finally:
            set_buffer_owner(prev_owner)
        if self.projection is not None:
            frame = pd.DataFrame(
                {name: frame.iloc[:, ordinal]
                 for name, ordinal in self.projection})
        return frame

    # -- checkpoint snapshot / restore (PR 19) -------------------------

    def snapshot_host(self) -> Optional[bytes]:
        """The running (keys..., partials...) batch in the serde wire
        format — the checkpoint payload. None when nothing has folded
        yet. Read-only: the partials keep folding afterwards."""
        from spark_rapids_tpu.columnar import serde

        if self._running is None:
            return None
        prev_owner = set_buffer_owner(self.owner_tag)
        try:
            with self._running.acquired() as rb:
                return serde.serialize_host_batch(serde.to_host_batch(rb))
        finally:
            set_buffer_owner(prev_owner)

    def restore_running(self, payload: Optional[bytes], folds: int,
                        rows_folded: int) -> None:
        """Adopt a checkpointed running state (inverse of
        ``snapshot_host``); only valid before the first fold."""
        from spark_rapids_tpu.columnar import serde

        if self._running is not None:
            raise RuntimeError("restore_running on a state that has "
                               "already folded")
        if payload:
            prev_owner = set_buffer_owner(self.owner_tag)
            try:
                db = serde.to_device_batch(
                    serde.deserialize_host_batch(payload))
                self._running = SpillableBatch(db,
                                               STREAMING_STATE_PRIORITY)
            finally:
                set_buffer_owner(prev_owner)
        self.folds = int(folds)
        self.rows_folded = int(rows_folded)

    # -- accounting / teardown -----------------------------------------

    def state_bytes(self) -> int:
        """Running-state size at device width (the admission and
        maxStateBytes currency, whatever tier it currently sits on)."""
        return self._running.device_memory_size() \
            if self._running is not None else 0

    def device_resident_bytes(self) -> int:
        if self._running is None:
            return 0
        cat = get_catalog()
        try:
            on_device = cat.tier_of(self._running.buffer_id) is \
                StorageTier.DEVICE
        except KeyError:
            return 0
        return self._running.device_memory_size() if on_device else 0

    def close(self) -> None:
        """Drop the running state and every catalog buffer the fold
        machinery registered under this query's owner tag (shuffle
        blocks, delta-side broadcast builds) — the cancel/deadline
        teardown path, same contract as Query finalize."""
        if self._running is not None:
            self._running.close()
            self._running = None
        close_query_blocks(self._child_exec)
        get_catalog().remove_owner(self.owner_tag)

    # -- per-fold exec-state reset -------------------------------------

    def _reaches_delta(self, e, memo) -> bool:
        r = memo.get(id(e))
        if r is None:
            r = getattr(e, "source", None) is self.delta_source or any(
                self._reaches_delta(c, memo)
                for c in getattr(e, "children", ()))
            memo[id(e)] = r
        return r

    def _reset_delta_path(self) -> None:
        """Clear materialize-once exec state that READ the previous
        delta; dimension-side state (delta-unreachable) is left alone
        so build tables stay resident across folds."""
        from spark_rapids_tpu.execs.adaptive import \
            AdaptiveShuffleReaderExec
        from spark_rapids_tpu.execs.fused import FusedChainExec

        memo: dict = {}
        for e in all_execs(self._child_exec):
            if isinstance(e, (ShuffleExchangeExec,
                              BroadcastExchangeExec)) and \
                    self._reaches_delta(e, memo):
                e.close_blocks()
            elif isinstance(e, AdaptiveShuffleReaderExec) and \
                    self._reaches_delta(e, memo):
                e._groups = None
            elif isinstance(e, FusedChainExec) and any(
                    self._reaches_delta(b, memo) for b in e.builds):
                with e._prep_lock:
                    e._preps = None
                    e._preps_ok = None
