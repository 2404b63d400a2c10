"""``CompileMeter`` and ``assert_on_device``, copied from ``chip_smoke.py``
(PR 24), not imported: that script is the program's smoke test and may
change or go; the benchmark's counts of compilations must not.
"""
from __future__ import annotations


class CompileMeter:
    """Counts what JAX compiled and what it took from the persistent
    cache, from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = 0      # backend compile-or-fetch calls
        self.cache_hits = 0
        self.seconds = 0.0     # spent in those calls, hits included
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.requests, self.cache_hits, self.seconds)

    def delta(self, before):
        r, h, s = (a - b for a, b in zip(self.snapshot(), before))
        return {"programs_compiled": r - h, "cache_hits": h,
                "compile_s": s}


def assert_on_device(name: str, plan_text: str) -> None:
    """Every node of the tagged plan carries '*': on this path a
    reasoned CPU fallback ('!') is a failure, not a degradation."""
    lines = [ln.strip() for ln in plan_text.splitlines() if ln.strip()]
    bad = [ln for ln in lines if not ln.startswith("* ")]
    if not lines or bad:
        raise AssertionError(
            f"{name}: plan nodes not on the device:\n" + "\n".join(bad)
            + "\nfull plan:\n" + plan_text)
