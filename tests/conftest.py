"""Test bootstrap: run everything on a virtual 8-device CPU mesh.

The sandbox tests run in has no chip; sharding/collective paths are
validated on XLA's host platform with 8 virtual devices. The chip itself is
reached through ``chip_smoke.py`` (one process per chip), and
``tests/test_tpu_compile.py`` asks the chip's compiler without the chip.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Runtime lock-order assertions for the whole tier-1 run
# (rapids.tpu.debug.lockOrder.enabled). Must be set BEFORE the package
# imports: every lock is wrapped (or not) at creation time. Record mode
# (the default): violations accumulate instead of raising mid-test, and
# pytest_sessionfinish below fails the run if any were observed.
os.environ.setdefault("RAPIDS_TPU_DEBUG_LOCKORDER_ENABLED", "1")

# XLA's C++ log writes straight to fd 2. Loading a CPU executable from
# the compile cache (below) logs two ERROR lines each time, about
# `+prefer-no-gather/-scatter`, tuning hints XLA adds itself and then
# misses among the host's features; a line that a background thread
# writes between two tests would land inside pytest's line of dots. A
# real XLA error still reaches the test as a Python exception.
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import pytest  # noqa: E402

import spark_rapids_tpu  # noqa: E402,F401  (enables x64 before jax use)
from spark_rapids_tpu.utils import lockorder, progcache  # noqa: E402

import jax  # noqa: E402

assert jax.devices()[0].platform == "cpu", \
    "tests must run on the virtual CPU mesh, not the real TPU"
assert len(jax.devices()) >= 8, \
    "xla_force_host_platform_device_count=8 did not take effect"

# The suite's time is XLA's: thousands of small x64 CPU programs, most of
# them compiled again by every run and, where a file sheds jax's caches
# between tests (test_benchmarks.py), again inside one run. Keep every
# executable in the compile cache (utils/progcache's directory rule
# decides where), not only those that took over two seconds: a program
# seen before is then loaded, which costs its lowering and a read.
progcache.install()


@pytest.fixture(scope="session")
def n_virtual_devices():
    return len(jax.devices())


def _count_lines(path: str) -> int:
    try:
        with open(path) as f:
            return sum(1 for _ in f)
    except OSError:  # no /proc: nothing to watch
        return 0


def _max_map_count() -> int:
    try:
        with open("/proc/sys/vm/max_map_count") as f:
            return int(f.read())
    except (OSError, ValueError):
        return 65530


def pytest_runtest_teardown(item, nextitem):
    """Keep the run's one process under the kernel's limit on memory
    mappings. A loaded XLA:CPU executable holds about 12 of them, jax's
    caches keep every program of the run alive, and a process may have
    ``vm.max_map_count`` (65,530): the 1,161 tests of PR 31 ended within
    a few per cent of it and PR 32's 22 more passed it (60,313 mappings
    at test 1,082, then a segmentation fault inside XLA's compile or its
    cache read, in a different test each run). Between two modules, once
    half the limit is in use, shed jax's caches: the executables go and
    their mappings with them (4,166 -> 595 for 300 programs); what a
    later module needs again it reads from the compile cache."""
    if nextitem is not None and nextitem.module is item.module:
        return
    if _count_lines("/proc/self/maps") > _max_map_count() // 2:
        jax.clear_caches()


def pytest_collection_modifyitems(config, items):
    """Two-tier suite: anything not explicitly `full` (the 140-query TPC
    oracle matrices) is the `smoke` tier — `pytest -m smoke` stays under
    the per-push CI window; plain `pytest tests/` is the nightly run."""
    for item in items:
        if "full" not in item.keywords:
            item.add_marker(pytest.mark.smoke)


def pytest_sessionfinish(session, exitstatus):
    """Fail the run if any lock-order inversion was recorded anywhere in
    the suite — the dynamic half of tpulint's TPU301 (the static pass
    only sees nestings it can prove; this catches the interleavings)."""
    viols = lockorder.violations()
    if not viols:
        return
    rep = session.config.pluginmanager.get_plugin("terminalreporter")
    for v in viols:
        msg = ("LOCK-ORDER VIOLATION: acquired %(acquiring)r (rank "
               "%(acquiring_rank)d) while holding %(held)r (rank "
               "%(held_rank)d) on thread %(thread)s\n%(stack)s" % v)
        if rep:
            rep.write_line(msg, red=True)
        else:  # pragma: no cover - no terminal plugin
            print(msg)
    session.exitstatus = 3
