"""Diagnostic codes and the Finding record every pass emits.

Codes are STABLE: allowlist entries and baselines reference them, so a
code is never renumbered or reused. New checks take the next free
number in their family.
"""
from __future__ import annotations

import dataclasses

#: code -> one-line meaning. The authoritative list; docs/static-analysis.md
#: renders this table and tests assert the two never drift.
CODES = {
    # -- TPU1xx: host-sync discipline -----------------------------------
    "TPU101": "np.asarray/np.array on device data without an explicit "
              "jax.device_get (hidden device->host sync)",
    "TPU102": ".item() scalar pull (one full dispatch RTT per call)",
    "TPU103": "block_until_ready outside benchmark/measurement code",
    "TPU104": "implicit __bool__ on a jnp array value (truth test "
              "forces a sync)",
    # -- TPU2xx: recompile hazards --------------------------------------
    "TPU201": "jax.jit created inside a function body (fresh trace "
              "cache per call: recompiles every invocation)",
    "TPU202": "data-dependent shape fed to an array constructor in a "
              "function that never quantizes through ops/buckets",
    "TPU203": "jnp scalar/array literal without an explicit dtype "
              "(weak-type promotion drifts program signatures)",
    "TPU204": "pallas_call (the package keeps no Pallas kernel: none "
              "has compiled for the v5e)",
    # -- TPU3xx: concurrency --------------------------------------------
    "TPU301": "lock acquisition order inverts the declared hierarchy "
              "(utils/lockorder.py)",
    "TPU302": "blocking call (device transfer, socket I/O, sleep, "
              "foreign Condition.wait) while holding a framework lock",
    "TPU303": "lock created outside utils/lockorder factories, or with "
              "an undeclared hierarchy name",
    # -- TPU4xx: robustness / config ------------------------------------
    "TPU401": "except handler can swallow RESOURCE_EXHAUSTED without "
              "re-raising into the retry ladder (memory/retry.py)",
    "TPU402": "rapids.tpu.* knob string not registered in config.py",
    "TPU403": "registered knob missing from docs/configs.md (run "
              "scripts/gen_config_docs.py)",
}


@dataclasses.dataclass(frozen=True)
class Finding:
    """One diagnostic at one site. ``scope`` is the allowlist key for
    the site (``<relpath>::<qualname>`` or just ``<relpath>`` for
    module-level findings)."""

    code: str
    path: str        # path relative to the repo root
    line: int
    qualname: str    # enclosing function/class qualname, "" at module level
    message: str

    @property
    def scope(self) -> str:
        return f"{self.path}::{self.qualname}" if self.qualname else self.path

    def render(self) -> str:
        where = f"{self.path}:{self.line}"
        if self.qualname:
            where += f" ({self.qualname})"
        return f"{self.code} {where}: {self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)
