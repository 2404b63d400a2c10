"""Persistent compile cache: repeated plans over the same schema skip
both XLA compilation and warm-up dispatches.

Two layers cooperate:

- **in-process**: structurally identical fused programs share one
  jitted callable through the chain-key registry in
  ``expressions/compiler.py`` (``_FUSED_CACHE``, keyed by the same
  ``chain_key`` tuples whose CRC tags the program names). A fresh plan
  instance of a repeated query re-traces nothing.
- **cross-process**: JAX's persistent compilation cache keeps the XLA
  *executables* across process restarts. The fused chain programs
  carry STABLE names (the ``fused_chain[...]@crc`` tag derives from
  the chain key, not object identity), which keeps their cache keys
  reproducible across runs — a cold process starts hot.

THE cache-directory rule lives here and nowhere else: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and this
package sets no directory in code; where it is not, the cache is
``<checkout>/.jax_cache`` — a fixed path, because the path is part of
what makes a later process find the entries again. ``configure()``
(called once by the package ``__init__``) applies the rule;
``install()`` additionally persists EVERY executable, not only the
slow-to-compile ones (long-lived deployments, ``bench.py``,
``chip_smoke.py``).
"""
from __future__ import annotations

import os

from spark_rapids_tpu.utils import lockorder

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_installed = False
_lock = lockorder.make_lock("utils.progcache")


def default_dir() -> str:
    """``<checkout>/.jax_cache``: the directory in force when the
    environment names none."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.abspath(os.path.join(here, "..", "..", ".jax_cache"))


def configure() -> None:
    """Apply the directory rule. Executables that took under two
    seconds to compile are not persisted until ``install()`` says so
    (the test suite compiles thousands of tiny programs)."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", default_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)


def cache_dir() -> str:
    """The directory in force (whichever side of the rule set it)."""
    import jax

    return jax.config.jax_compilation_cache_dir


def install() -> bool:
    """Persist every executable compiled from here on into the
    directory in force. Idempotent. False when no directory is in
    force (the cache was opted out of)."""
    global _installed
    with _lock:
        if _installed:
            return True
        import jax

        if not cache_dir():
            return False
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _installed = True
        return True


def installed_dir():
    """The persistent directory once ``install()`` took effect."""
    return cache_dir() if _installed else None


def stats() -> dict:
    """Program-registry effectiveness: in-process chain-key cache size
    and hit/miss counts (a miss = one trace + compile somewhere), the
    persistent directory when active, and the shape-bucket ledger —
    how many distinct (program, bucket-shape) executables the service
    path observed vs reused (service/batching: programs are keyed on
    BUCKETED operand shapes, so concurrent tenants land on the same
    executables by construction)."""
    from spark_rapids_tpu.expressions import compiler as _c

    out = dict(_c._FUSED_CACHE_STATS)
    out["programs"] = len(_c._FUSED_CACHE)
    out["persistent_dir"] = installed_dir()
    try:
        from spark_rapids_tpu.service.batching.buckets import \
            get_registry

        out["buckets"] = get_registry().stats()
    except Exception:  # pragma: no cover - service package unavailable
        pass
    return out
