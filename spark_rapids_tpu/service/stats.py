"""Service observability: the ServiceStats snapshot.

The multi-tenant wins this surfaces: queue depth + shed counts show
backpressure working, queue/run-time histograms (now with p50/p95/p99)
show fairness AND feed the sustained-QPS SLO harness, the
compile-cache hit rate shows tenants sharing compiled programs — a
repeated plan shape admitted for tenant B reuses tenant A's XLA
executables (utils/progcache) instead of compiling its own — and the
batching block shows the micro-batcher
turning that sharing into coalesced physical launches
(service/batching).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

#: histogram bucket upper bounds in seconds (last bucket is +inf)
HIST_BUCKETS = (0.001, 0.01, 0.1, 1.0, 10.0, 100.0)
HIST_LABELS = tuple(f"le_{b:g}s" for b in HIST_BUCKETS) + ("inf",)


class Histogram:
    """Fixed log-bucket latency histogram plus a bounded sample set for
    percentiles (enough for a snapshot; the service is not a metrics
    pipeline).

    Percentiles need more resolution than 7 log buckets, so raw samples
    are retained up to ``SAMPLE_CAP`` and then deterministically
    THINNED: the set halves (every other sample) and the keep stride
    doubles, so memory stays bounded while the retained set remains an
    unbiased-in-time 1-in-stride systematic sample. Exact until the
    cap; an approximation with bounded memory beyond it."""

    SAMPLE_CAP = 8192

    def __init__(self):
        self.counts = [0] * (len(HIST_BUCKETS) + 1)
        self.total = 0
        self.sum_s = 0.0
        self._samples: List[float] = []
        self._stride = 1
        self._skip = 0

    def add(self, seconds: float) -> None:
        for i, b in enumerate(HIST_BUCKETS):
            if seconds <= b:
                self.counts[i] += 1
                break
        else:
            self.counts[-1] += 1
        self.total += 1
        self.sum_s += seconds
        if self._skip > 0:
            self._skip -= 1
            return
        self._samples.append(seconds)
        self._skip = self._stride - 1
        if len(self._samples) >= self.SAMPLE_CAP:
            self._samples = self._samples[::2]
            self._stride *= 2

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the retained samples
        (q in [0, 100]) — one definition for the whole serving layer
        (service/batching/slo), so harness and histogram numbers can
        never diverge."""
        from spark_rapids_tpu.service.batching.slo import percentile

        return percentile(self._samples, q)

    def snapshot(self) -> dict:
        return {
            "buckets": dict(zip(HIST_LABELS, self.counts)),
            "count": self.total,
            "mean_s": round(self.sum_s / self.total, 6)
            if self.total else 0.0,
            "p50_s": round(self.percentile(50), 6),
            "p95_s": round(self.percentile(95), 6),
            "p99_s": round(self.percentile(99), 6),
        }


@dataclasses.dataclass
class ServiceStats:
    """Point-in-time service snapshot; ``to_dict()`` is what the
    benchmark runner embeds in its JSON."""

    queue_depth: int
    running: int
    admitted_inflight: int
    inflight_bytes: int
    budget_bytes: Optional[int]
    counters: Dict[str, int]           # admitted/shed/done/failed/...
    queue_time_hist: dict
    run_time_hist: dict
    per_query: List[dict]
    progcache: dict
    semaphore: dict
    #: OOM-retry ladder accounting (memory/retry.stats()): totals +
    #: per-call-site retries/splits/bytes-spilled/time-blocked
    retry: dict = dataclasses.field(default_factory=dict)
    #: micro-batcher effectiveness (service/batching): physical
    #: launches, coalesced launches/participants, mean group size
    batching: dict = dataclasses.field(default_factory=dict)
    #: semantic result & fragment cache effectiveness (service/cache):
    #: per-tier hits/misses/bytes, single-flight followers, publishes,
    #: OOM-degraded captures, evictions
    cache: dict = dataclasses.field(default_factory=dict)
    #: streaming ingestion & standing queries (service/streaming):
    #: appends/folds/late-row counters, live standing-query registry,
    #: state bytes (device-resident share), watermark lag
    streaming: dict = dataclasses.field(default_factory=dict)
    #: lineage fault recovery (runtime/recovery.snapshot()): reduce-side
    #: fetch failures, map tasks re-run, workers respawned, executor
    #: slots blacklisted, stage retries spent, SPMD degrades, hosts
    #: added/removed through elastic membership — a query that survived
    #: a worker death shows up here, never silently
    recovery: dict = dataclasses.field(default_factory=dict)
    #: queue-pressure autoscaler (service/autoscaler): scale-ups fired,
    #: thresholds, last reason/executor — pairs with counters.scale_ups
    autoscaler: dict = dataclasses.field(default_factory=dict)

    @property
    def progcache_hit_rate(self) -> float:
        hits = self.progcache.get("hits", 0)
        misses = self.progcache.get("misses", 0)
        return hits / (hits + misses) if hits + misses else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["progcache"]["hit_rate"] = round(self.progcache_hit_rate, 4)
        # the SLO headline numbers, hoisted so harnesses need not dig
        # through the histogram blocks
        d["latency"] = {
            "queue_p99_s": self.queue_time_hist.get("p99_s", 0.0),
            "run_p99_s": self.run_time_hist.get("p99_s", 0.0),
            "queue_p50_s": self.queue_time_hist.get("p50_s", 0.0),
            "run_p50_s": self.run_time_hist.get("p50_s", 0.0),
        }
        return d
