"""Native Pallas kernel registry: gates, interpret-mode policy, and the
one sanctioned ``pallas_call`` entry point.

The reference accelerator's entire win lives in its native kernel layer
(cuDF's JNI surface); this package is the TPU analogue — hand-written
Pallas kernels for the ops where jit-of-jnp is the measured floor
(BENCH_r08's per-stage program attribution): the hash-join probe and
dictionary-string predicates. Three rules hold the layer together:

1. **Gated, default-off.** Every kernel routes through ``enabled(kind)``
   reading the ``rapids.tpu.native.kernels.{enabled,join,strings}``
   knobs (applied process-wide by ``runtime.device.initialize``, same
   contract as memory/retry). With the gate off, callers run the
   existing jnp implementations unchanged — the differential fences in
   tests/test_kernels.py assert bit-equality between the two.

2. **One interpret-mode decision.** Kernels never call
   ``pl.pallas_call`` directly; they call :func:`pallas_call` here,
   which sets ``interpret=True`` on any non-TPU backend. CPU CI
   therefore executes the *same kernel bodies* that compile for TPU —
   a compiled-only code path would be dead under tier-1. tpulint's
   TPU204 diagnostic fences this rule statically.

3. **Traceable by construction.** Every kernel is jit/shard_map
   composable (interpret mode lowers to XLA ops), so routing a kernel
   inside an existing fused-chain program changes zero dispatch counts
   — the q26 <= 5 dispatch fence holds with kernels on and off.
"""
from __future__ import annotations

from typing import Optional

from spark_rapids_tpu.utils import lockorder

_LOCK = lockorder.make_lock("native.kernels.config")

_DEFAULTS = {"enabled": False, "join": True, "strings": True}
_state = dict(_DEFAULTS)


def configure(enabled: Optional[bool] = None, join: Optional[bool] = None,
              strings: Optional[bool] = None) -> None:
    """Set the process-wide kernel gates (None = leave unchanged)."""
    with _LOCK:
        for key, val in (("enabled", enabled), ("join", join),
                         ("strings", strings)):
            if val is not None:
                _state[key] = bool(val)


def configure_from_conf(conf) -> None:
    from spark_rapids_tpu import config as cfg

    configure(enabled=conf.get(cfg.NATIVE_KERNELS_ENABLED),
              join=conf.get(cfg.NATIVE_KERNELS_JOIN),
              strings=conf.get(cfg.NATIVE_KERNELS_STRINGS))


def reset_config() -> None:
    """Restore defaults (test teardown; runtime.device.shutdown)."""
    with _LOCK:
        _state.update(_DEFAULTS)


def enabled(kind: str) -> bool:
    """Is the ``kind`` kernel ('join' | 'strings') active?"""
    with _LOCK:
        return _state["enabled"] and _state[kind]


def cache_token() -> tuple:
    """Hashable gate state for program/jit cache keys: any compiled
    program whose trace read a gate must key on this, or a mid-process
    knob flip would serve the stale routing."""
    with _LOCK:
        return (_state["enabled"], _state["join"], _state["strings"])


def interpret_mode() -> bool:
    """True when kernels must run through the Pallas interpreter: any
    backend that is not a real TPU (CPU CI, GPU). The decision is made
    once per process — backends don't change under a running query. A
    backend that cannot be asked raises: "no answer" is not "interpret"
    (a chip that failed to attach must not pass for the CPU)."""
    global _interpret
    if _interpret is None:
        import jax

        _interpret = jax.default_backend() != "tpu"
    return _interpret


_interpret: Optional[bool] = None


def pallas_call(kernel, *, out_shape, grid=None, **kwargs):
    """The one sanctioned ``pl.pallas_call`` wrapper: resolves the
    pallas module through the version shims and pins ``interpret`` to
    the process-wide policy. Direct ``pl.pallas_call`` sites elsewhere
    are a TPU204 lint error (they would silently dead-code the CPU CI
    leg or crash a TPU-compiled kernel on the CPU backend)."""
    from spark_rapids_tpu.shims import get_shims

    pl = get_shims().pallas()
    if pl is None:  # pragma: no cover - ancient jax
        raise RuntimeError("pallas unavailable in this jax version")
    if grid is not None:
        kwargs["grid"] = grid
    return pl.pallas_call(kernel, out_shape=out_shape,
                          interpret=interpret_mode(), **kwargs)
