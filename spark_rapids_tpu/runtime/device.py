"""Device manager + runtime environment singleton."""
from __future__ import annotations

import dataclasses
import threading
from spark_rapids_tpu.utils import lockorder
from typing import Optional

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.memory import semaphore as sem
from spark_rapids_tpu.memory.catalog import (BufferCatalog, get_catalog,
                                             reset_catalog)


class TpuDeviceManager:
    """GpuDeviceManager analogue (GpuDeviceManager.scala:31): owns the
    chosen device and the memory-budget math."""

    def __init__(self, device_ordinal: int = 0):
        self.device_ordinal = device_ordinal
        self._device = None

    @property
    def device(self):
        if self._device is None:
            import jax

            devices = jax.devices()
            if self.device_ordinal >= len(devices):
                raise RuntimeError(
                    f"device ordinal {self.device_ordinal} out of range "
                    f"({len(devices)} devices)")
            self._device = devices[self.device_ordinal]
        return self._device

    def hbm_bytes(self) -> Optional[int]:
        """Total device memory (Cuda.memGetInfo analogue). None on the
        CPU host platform, which reports none; an accelerator that
        reports none raises — a silent None there would turn the HBM
        budget off on the very device it exists for."""
        stats = self.device.memory_stats()
        total = (stats or {}).get("bytes_limit") or (stats or {}).get(
            "bytes_reservable_limit")
        if total is None and self.device.platform != "cpu":
            raise RuntimeError(
                f"{self.device} reports no memory limit "
                f"(memory_stats()={stats!r}): cannot size the HBM budget")
        return total

    def device_budget(self, conf: RapidsConf) -> Optional[int]:
        """allocFraction * hbm - reserve (GpuDeviceManager.scala:159-258
        pool sizing). None = unbounded (no HBM accounting available)."""
        total = self.hbm_bytes()
        if total is None:
            return None
        frac = conf.get(cfg.HBM_POOL_FRACTION)
        reserve = conf.get(cfg.HBM_RESERVE)
        budget = int(total * frac) - reserve
        if budget <= 0:
            raise RuntimeError(
                f"HBM budget non-positive: total={total} frac={frac} "
                f"reserve={reserve}")
        return budget


@dataclasses.dataclass
class RuntimeEnv:
    conf: RapidsConf
    device_manager: TpuDeviceManager
    catalog: BufferCatalog
    semaphore: "sem.TpuSemaphore"
    shuffle_codec: str

    @property
    def device(self):
        return self.device_manager.device


_env: Optional[RuntimeEnv] = None
_lock = lockorder.make_lock("runtime.device")


def initialize(conf: Optional[RapidsConf] = None,
               device_ordinal: int = 0) -> RuntimeEnv:
    """Executor-init analogue (RapidsExecutorPlugin.init,
    Plugin.scala:122-147). Idempotent: re-initializing with a new conf
    replaces the environment."""
    global _env
    conf = conf or RapidsConf()
    with _lock:
        dm = TpuDeviceManager(device_ordinal)
        _ = dm.device  # fail fast if the device is unavailable
        # an explicit configured budget wins over the HBM-derived one —
        # the artificially-small-budget mode the out-of-core fence uses
        budget = conf.get(cfg.DEVICE_BUDGET) or dm.device_budget(conf)
        catalog = BufferCatalog(
            device_budget=budget,
            host_budget=conf.get(cfg.HOST_SPILL_STORAGE_SIZE),
            spill_dir=conf.get(cfg.SPILL_DIR),
            disk_codec=conf.get(cfg.SHUFFLE_COMPRESSION_CODEC)
            if conf.get(cfg.SHUFFLE_COMPRESSION_CODEC) != "none"
            else "lz4",
            async_spill=conf.get(cfg.SPILL_ASYNC_WRITE))
        reset_catalog(catalog)
        semaphore = sem.initialize(conf.get(cfg.CONCURRENT_TPU_TASKS))
        from spark_rapids_tpu.memory import fault_injection, retry
        from spark_rapids_tpu.shuffle import \
            fault_injection as shuffle_fault_injection

        retry.configure_from_conf(conf)
        fault_injection.arm_from_conf(conf)
        shuffle_fault_injection.arm_from_conf(conf)
        from spark_rapids_tpu.shuffle import tcp as shuffle_tcp

        shuffle_tcp.configure_retry_from_conf(conf)
        _env = RuntimeEnv(conf, dm, catalog, semaphore,
                          conf.get(cfg.SHUFFLE_COMPRESSION_CODEC))
        return _env


def get_env() -> Optional[RuntimeEnv]:
    with _lock:
        return _env


def shutdown() -> None:
    """Test teardown: drop the environment and restore defaults."""
    global _env
    with _lock:
        old = _env
        _env = None
        if old is not None:
            old.catalog.close()  # drain + end the spill writer thread
        reset_catalog(BufferCatalog())
        sem.initialize(2)
        from spark_rapids_tpu.memory import fault_injection, retry
        from spark_rapids_tpu.shuffle import \
            fault_injection as shuffle_fault_injection

        retry.reset_config()
        fault_injection.get_injector().disarm()
        shuffle_fault_injection.get_injector().disarm()
