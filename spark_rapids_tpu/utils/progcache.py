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

What a first call pays is read from JAX's own monitoring events
(``listen()``, once when the package loads): each backend compile or
persistent-cache read counts ``compile.requests`` (and a read also
``compile.cache_hits``), recording or not; with recording on
(``utils/dispatch.install()``) each step's seconds become a launch timer
of ``utils/tracing`` under the span open on the compiling thread:
``compile.trace`` (jaxpr), ``compile.lower`` (MLIR), ``compile.backend``
(compile, or a read from the persistent cache) and ``compile.cache_read``
(the read alone, INSIDE ``compile.backend``: a sum takes one or the
other). A trace nested in another (a jit called while tracing a jit) is
inside the outer one's seconds and adds no timer.
"""
from __future__ import annotations

import os
import threading

from spark_rapids_tpu.utils import lockorder, tracing

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_installed = False
_lock = lockorder.make_lock("utils.progcache")

_BACKEND = "/jax/core/compile/backend_compile_duration"
_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
#: JAX's timed compile steps -> the launch timers they become
_STEPS = {"/jax/core/compile/jaxpr_trace_duration": "compile.trace",
          "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
          _BACKEND: "compile.backend"}
_listening = False
_open = threading.local()   # .depth: compile steps open on this thread


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


def listen() -> None:
    """Register the compile listeners with ``jax.monitoring``. Idempotent;
    called once by the package ``__init__``."""
    global _listening
    with _lock:
        if _listening:
            return
        import jax.monitoring as mon

        mon.register_scalar_listener(_on_step_start)
        mon.register_event_duration_secs_listener(_on_step_end)
        mon.register_event_listener(_on_event)
        _listening = True


def _on_step_start(event, value, **kw) -> None:
    # JAX records a scalar of the same name as each timed step starts
    if event in _STEPS:
        _open.depth = getattr(_open, "depth", 0) + 1


def _on_step_end(event, secs, **kw) -> None:
    if event == _READ:      # the read alone, inside compile.backend
        tracing.covered_leaf("compile.cache_read", int(secs * 1e9))
        return
    name = _STEPS.get(event)
    if name is None:
        return
    if event == _BACKEND:
        tracing.count("compile.requests")
    _open.depth = depth = max(getattr(_open, "depth", 1) - 1, 0)
    if not depth:       # else inside an open step of this thread
        tracing.covered_leaf(name, int(secs * 1e9))


def _on_event(event, **kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        tracing.count("compile.cache_hits")


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
