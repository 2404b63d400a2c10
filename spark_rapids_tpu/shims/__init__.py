"""Host-framework version shims (SURVEY.md §2.13).

The reference adapts to each Spark release through ServiceLoader-discovered
``SparkShimServiceProvider``s that probe the running version and hand back
a ``SparkShims`` implementation (ShimLoader.scala:26,
SparkShimServiceProvider.scala:25), overridable via
``spark.rapids.shims-provider-override`` (RapidsConf.scala:707). Our host
framework is jax, whose public surface also moves between releases
(``shard_map`` graduated from ``jax.experimental.shard_map`` to
``jax.shard_map`` and renamed ``check_rep`` to ``check_vma``;
backend-reset moved into ``jax.extend``). Same design: providers declare
the versions they serve, the loader probes the installed jax exactly
once, and everything version-sensitive in the package goes through the
resolved ``JaxShims``. One provider is registered: the one for the jax
this package is installed and run with. A provider for a jax nobody
installs is code nobody runs.
"""
from __future__ import annotations

import os
import threading
from spark_rapids_tpu.utils import lockorder
from typing import List, Optional, Tuple


def _parse_version(v: str) -> Tuple[int, ...]:
    parts = []
    for p in v.split("."):
        digits = ""
        for ch in p:
            if ch.isdigit():
                digits += ch
            else:
                break
        if digits == "":
            break
        parts.append(int(digits))
    return tuple(parts)


class JaxShims:
    """The version-varying API surface (SparkShims trait analogue,
    SparkShims.scala:62-141) — only entries this package actually calls."""

    def shard_map(self):
        """The shard_map transform."""
        raise NotImplementedError

    def clear_backends(self):
        """Reset backends so device-count flags re-apply."""
        raise NotImplementedError


class JaxShimServiceProvider:
    """SparkShimServiceProvider analogue: version probe + factory."""

    #: inclusive lower bound, exclusive upper bound (None = open)
    VERSION_RANGE: Tuple[Optional[str], Optional[str]] = (None, None)

    @classmethod
    def matches(cls, version: str) -> bool:
        lo, hi = cls.VERSION_RANGE
        v = _parse_version(version)
        if lo is not None and v < _parse_version(lo):
            return False
        if hi is not None and v >= _parse_version(hi):
            return False
        return True

    def build(self) -> JaxShims:
        raise NotImplementedError


class _ModernJaxShims(JaxShims):
    """jax >= 0.7: public top-level shard_map (``check_vma``),
    jax.extend backend API."""

    def shard_map(self):
        from jax import shard_map

        return shard_map

    def clear_backends(self):
        from jax.extend import backend

        backend.clear_backends()


class ModernJaxShimProvider(JaxShimServiceProvider):
    VERSION_RANGE = ("0.7", None)

    def build(self) -> JaxShims:
        return _ModernJaxShims()


#: discovery order — the ServiceLoader registry (ShimLoader.scala:26)
PROVIDERS: List[type] = [ModernJaxShimProvider]

OVERRIDE_ENV = "RAPIDS_TPU_SHIMS_PROVIDER_OVERRIDE"

_lock = lockorder.make_lock("shims.init")
_shims: Optional[JaxShims] = None


def _resolve(version: str) -> JaxShims:
    override = os.environ.get(OVERRIDE_ENV)
    if override:
        # spark.rapids.shims-provider-override analogue: fully qualified
        # provider name trusted over the probe (RapidsConf.scala:707)
        import importlib

        mod, _, name = override.rpartition(".")
        klass = getattr(importlib.import_module(mod), name) if mod else \
            globals()[name]
        return klass().build()
    for p in PROVIDERS:
        if p.matches(version):
            return p().build()
    raise RuntimeError(
        f"Could not find a shim provider for jax {version}; supported "
        f"ranges: {[p.VERSION_RANGE for p in PROVIDERS]} (set "
        f"{OVERRIDE_ENV} to force one)")


def get_shims() -> JaxShims:
    """Probe once, cache forever (ShimLoader semantics)."""
    global _shims
    with _lock:
        if _shims is None:
            import jax

            _shims = _resolve(jax.__version__)
        return _shims
