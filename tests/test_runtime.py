"""Runtime bootstrap tests (§2.1 plugin-init analogue)."""
import pytest

from spark_rapids_tpu import config as cfg
from spark_rapids_tpu.config import RapidsConf
from spark_rapids_tpu.memory import semaphore as sem
from spark_rapids_tpu.memory.catalog import get_catalog
from spark_rapids_tpu import runtime
from spark_rapids_tpu.runtime.device import TpuDeviceManager


@pytest.fixture(autouse=True)
def _teardown():
    yield
    runtime.shutdown()


def test_initialize_wires_globals(tmp_path):
    conf = RapidsConf({
        "rapids.tpu.sql.concurrentTpuTasks": 5,
        "rapids.tpu.memory.spillDir": str(tmp_path),
        "rapids.tpu.shuffle.compression.codec": "zlib",
    })
    env = runtime.initialize(conf)
    assert env.semaphore is sem.get()
    assert env.catalog is get_catalog()
    assert env.catalog._spill_dir == str(tmp_path)
    assert env.catalog.disk_codec == "zlib"
    assert env.shuffle_codec == "zlib"
    assert env.device.platform in ("cpu", "tpu")
    # semaphore honors the conf
    for _ in range(5):
        assert env.semaphore.acquire_if_necessary(task_id=_) is True
    assert env.semaphore.holds(task_id=0)


def test_initialize_idempotent_replaces():
    e1 = runtime.initialize(RapidsConf())
    e2 = runtime.initialize(RapidsConf(
        {"rapids.tpu.sql.concurrentTpuTasks": 1}))
    assert runtime.get_env() is e2
    assert e1 is not e2


def test_device_budget_math():
    dm = TpuDeviceManager()
    dm.hbm_bytes = lambda: 16 << 30  # pretend 16 GiB HBM
    conf = RapidsConf({"rapids.tpu.memory.hbm.allocFraction": 0.5,
                       "rapids.tpu.memory.hbm.reserve": 1 << 30})
    assert dm.device_budget(conf) == (8 << 30) - (1 << 30)
    bad = RapidsConf({"rapids.tpu.memory.hbm.allocFraction": 0.01,
                      "rapids.tpu.memory.hbm.reserve": 8 << 30})
    with pytest.raises(RuntimeError, match="non-positive"):
        dm.device_budget(bad)


def test_budget_none_without_memory_stats():
    dm = TpuDeviceManager()
    dm.hbm_bytes = lambda: None
    assert dm.device_budget(RapidsConf()) is None


def test_bad_device_ordinal():
    with pytest.raises(RuntimeError, match="out of range"):
        runtime.initialize(RapidsConf(), device_ordinal=512)


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform = platform
        self._stats = stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("cpu", None, None),
    ("tpu", {"bytes_limit": 16 << 30}, 16 << 30),
    ("tpu", {"bytes_reservable_limit": 15 << 30}, 15 << 30),
])
def test_hbm_bytes_reads_the_device(platform, stats, want):
    dm = TpuDeviceManager()
    dm._device = _FakeDevice(platform, stats)
    assert dm.hbm_bytes() == want


@pytest.mark.parametrize("stats", [None, {}])
def test_hbm_bytes_raises_on_an_accelerator_that_reports_none(stats):
    """A silent None would turn the HBM budget off on the chip."""
    dm = TpuDeviceManager()
    dm._device = _FakeDevice("tpu", stats)
    with pytest.raises(RuntimeError, match="no memory limit"):
        dm.hbm_bytes()


def test_compile_cache_directory_rule(monkeypatch):
    """One rule (utils/progcache): the environment's directory is JAX's
    to read and the package sets none in code; unset, the directory is
    <checkout>/.jax_cache, a fixed path."""
    import os

    import jax

    from spark_rapids_tpu.utils import progcache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setenv(progcache.CACHE_DIR_ENV, "/somewhere/else")
    progcache.configure()
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.delenv(progcache.CACHE_DIR_ENV)
    progcache.configure()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert updates["jax_compilation_cache_dir"] == \
        os.path.join(repo, ".jax_cache")
