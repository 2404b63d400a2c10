"""Device mesh construction and multi-host topology.

The reference pins one GPU per executor process and scales by adding
executors (GpuDeviceManager.scala:72-118). The TPU analogue is a pod of
hosts: each host (process) owns an N-chip mesh slice with explicit
``data`` x ``model`` axes and runs ONE SPMD program over it — data
parallelism is the shuffle/partition axis, the model axis is reserved
for tensor-parallel operators. Between hosts sits the DCN seam, carried
by the TCP exchange path (shuffle/tcp.py); inside a host, collectives
ride ICI in-program. :class:`HostTopology` is the explicit map of that
layout, and every clamp or downgrade the mesh builder applies is
recorded (``mesh_fallback_snapshot``) so the runner can surface it next
to the shuffle-fallback telemetry instead of silently shrinking.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Sequence

import jax
from jax.sharding import Mesh

from spark_rapids_tpu.utils import lockorder

DATA_AXIS = "data"
MODEL_AXIS = "model"

# {reason: count} — process-wide, snapshot/delta like the spmd fallback
# telemetry so a runner reports only its own run's mesh downgrades.
_mesh_fallbacks: dict = {}
_fb_lock = lockorder.make_lock("parallel.mesh.fallbacks")


def record_mesh_fallback(reason: str) -> None:
    """Count one mesh construction that did not deliver what the conf
    asked for (device clamp, model axis dropped, ...)."""
    with _fb_lock:
        _mesh_fallbacks[reason] = _mesh_fallbacks.get(reason, 0) + 1


def mesh_fallback_snapshot() -> dict:
    with _fb_lock:
        return dict(sorted(_mesh_fallbacks.items()))


def mesh_fallback_delta(before: dict) -> dict:
    """Mesh fallbacks recorded since ``before`` (a snapshot)."""
    now = mesh_fallback_snapshot()
    return {k: n - before.get(k, 0) for k, n in now.items()
            if n - before.get(k, 0)}


def data_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """1-D mesh over ``n_devices`` chips with a single data axis — the
    shuffle/partition axis (the reference's executor set)."""
    import numpy as np

    if devices is None:
        devices = jax.devices()
        if n_devices is not None:
            devices = devices[:n_devices]
    return Mesh(np.asarray(devices), (DATA_AXIS,))


def data_model_mesh(n_data: int, n_model: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
    """2-D ``(data, model)`` mesh over ``n_data * n_model`` chips. With
    ``n_model == 1`` this returns the plain 1-D data mesh so every
    existing shard_map spec (and its compile cache) is untouched."""
    import numpy as np

    if n_model <= 1:
        return data_mesh(n_data, devices)
    if devices is None:
        devices = jax.devices()
    need = n_data * n_model
    assert len(devices) >= need, (
        f"data x model mesh needs {n_data}x{n_model}={need} devices, "
        f"have {len(devices)}")
    grid = np.asarray(devices[:need]).reshape(n_data, n_model)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def mesh_axis_size(mesh: Mesh, axis: str = DATA_AXIS) -> int:
    return mesh.shape[axis]


def mesh_model_size(mesh: Mesh) -> int:
    """Model-axis width of ``mesh`` (1 for 1-D data meshes)."""
    return mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1


class HostTopology(NamedTuple):
    """Explicit multi-host axis layout: ``n_hosts`` processes, each
    owning a ``data x model`` mesh slice of ``devices_per_host`` chips.
    The global data axis is the concatenation of the per-host data
    slices; collectives inside a slice are in-program ICI, anything
    crossing a host boundary is the DCN seam (TCP exchange path)."""

    n_hosts: int
    devices_per_host: int
    model: int = 1

    @property
    def data_per_host(self) -> int:
        """Data-axis width of one host's slice."""
        return max(self.devices_per_host // max(self.model, 1), 1)

    @property
    def global_data(self) -> int:
        """Total data-axis width across the pod."""
        return self.n_hosts * self.data_per_host

    @property
    def total_devices(self) -> int:
        return self.n_hosts * self.devices_per_host

    def host_of(self, global_data_index: int) -> int:
        """Which host owns position ``global_data_index`` of the global
        data axis (hosts hold contiguous slices)."""
        assert 0 <= global_data_index < self.global_data, \
            f"data index {global_data_index} outside {self.global_data}"
        return global_data_index // self.data_per_host

    def seam(self, src_data_index: int, dst_data_index: int) -> str:
        """The link class a transfer between two global data positions
        crosses: ``"ici"`` inside one host's slice, ``"dcn"`` between
        hosts."""
        return ("ici" if self.host_of(src_data_index)
                == self.host_of(dst_data_index) else "dcn")

    def axis_layout(self) -> dict:
        """JSON-friendly layout summary for telemetry/docs."""
        return {"hosts": self.n_hosts,
                "data_per_host": self.data_per_host,
                "model": self.model,
                "global_data": self.global_data,
                "total_devices": self.total_devices}


def session_topology(conf) -> Optional[HostTopology]:
    """The session's host topology, or None when the mesh is off.
    Host count from ``rapids.tpu.mesh.hosts``; 0 infers it from cluster
    membership (driver + workers) when cluster mode is on, else 1. The
    per-host slice is the session mesh of THIS process — every host
    runs the same SPMD program shape over its own devices."""
    from spark_rapids_tpu import config as cfg

    if conf is None or not conf.get(cfg.MESH_ENABLED):
        return None
    hosts = conf.get(cfg.MESH_HOSTS) or 0
    if hosts <= 0:
        hosts = 1
        if conf.get(cfg.CLUSTER_ENABLED):
            hosts += max(conf.get(cfg.CLUSTER_WORKERS) or 0, 0)
    m = session_mesh(conf)
    if m is not None:
        per_host = len(m.devices.flat)
        model = mesh_model_size(m)
    else:
        per_host = len(jax.devices())
        model = 1
    return HostTopology(n_hosts=hosts, devices_per_host=per_host,
                        model=model)


_SESSION_MESH: Optional[Mesh] = None


def session_mesh(conf) -> Optional[Mesh]:
    """The planner-visible mesh: None unless ``rapids.tpu.mesh.enabled``.
    Cached process-wide (meshes are cheap but identity-stable mesh objects
    keep shard_map caches warm). A device count larger than the attached
    backend clamps to what exists — the driver's virtual-CPU dry run sets
    the backend size before planning — and the clamp is RECORDED as a
    mesh fallback, never silent. ``rapids.tpu.mesh.modelDevices`` > 1
    carves a model axis out of the device budget (data = devices //
    model); a model axis that leaves fewer than 2 data devices is
    dropped, with the reason recorded."""
    from spark_rapids_tpu import config as cfg

    if conf is None or not conf.get(cfg.MESH_ENABLED):
        return None
    global _SESSION_MESH
    want = conf.get(cfg.MESH_DEVICES) or 0
    avail = len(jax.devices())
    n = min(want, avail) if want > 0 else avail
    if 0 < avail < want:
        record_mesh_fallback(
            f"{cfg.MESH_DEVICES.key}={want} exceeds the attached "
            f"backend ({avail} devices): clamped to {avail}")
    if n < 2:
        return None  # a 1-chip mesh adds collectives for nothing
    model = max(conf.get(cfg.MESH_MODEL_DEVICES) or 1, 1)
    if model > 1 and n // model < 2:
        record_mesh_fallback(
            f"{cfg.MESH_MODEL_DEVICES.key}={model} leaves fewer than 2 "
            f"data devices out of {n}: model axis dropped")
        model = 1
    n_data = n // model if model > 1 else n
    if _SESSION_MESH is None \
            or _SESSION_MESH.shape[DATA_AXIS] != n_data \
            or mesh_model_size(_SESSION_MESH) != model:
        _SESSION_MESH = data_model_mesh(n_data, model)
    return _SESSION_MESH


_RECONSTRUCTED: dict = {}


def reconstruct_mesh(n: int, model: int = 1) -> Mesh:
    """Worker-side mesh reconstruction from a shipped spec (axis sizes):
    cluster map tasks carry mesh subtrees as specs, never live Device
    handles — the receiving process builds an equivalent mesh over its
    OWN devices (the reference ships GPU ids and re-opens handles
    per-executor the same way, GpuDeviceManager.scala:72-118). Cached
    per (data, model) size: identity-stable meshes keep shard_map
    caches warm."""
    model = max(int(model or 1), 1)
    got = _RECONSTRUCTED.get((n, model))
    if got is not None:
        return got
    devs = jax.devices()
    need = n * model
    assert len(devs) >= need, (
        f"shipped mesh subtree needs {need} devices; this process has "
        f"{len(devs)} — spawn executors with "
        f"xla_force_host_platform_device_count >= {need}")
    m = data_model_mesh(n, model)
    _RECONSTRUCTED[(n, model)] = m
    return m


def force_cpu_mesh(n_devices: int) -> None:
    """Ensure at least ``n_devices`` devices exist, falling back to a
    virtual CPU mesh when the attached backend has fewer (e.g. one real
    TPU chip). Used by multi-chip dry runs and mesh benchmarks."""
    import os

    # set the flag BEFORE the first backend touch: XLA parses XLA_FLAGS
    # once at client creation. Harmless on real accelerators — it only
    # sizes the host platform.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    if len(jax.devices()) >= n_devices:
        return
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.shims import get_shims

    get_shims().clear_backends()
    jax.config.update("jax_num_cpu_devices", n_devices)
    assert len(jax.devices()) >= n_devices, (
        f"need {n_devices} devices, have {jax.devices()} — this jax "
        f"cannot resize an initialized backend; set "
        f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices} "
        f"before process start")
