"""Fail when the SPMD mesh path stops landing its programs in progcache.

The in-program shuffle (``parallel/shuffle.py``) funnels every
exchange through ONE module-level jit entry (``_run_shuffle_step``), so
its executable must persist through ``utils/progcache`` — otherwise every fresh worker process
eats the shard_map program's cold compile per plan shape, which is the
regression this fence makes loud. It is a live two-process proof under
``JAX_PLATFORMS=cpu`` with 8 virtual devices.

**Probe 1 (land).** A subprocess is given a throwaway cache directory
through ``JAX_COMPILATION_CACHE_DIR``, runs a real 8-device ``shuffle_step`` over a ``data_mesh``,
and the parent asserts a ``jit__run_shuffle_step-*-cache`` entry
appeared — the mesh-path program key landed in progcache.

**Probe 2 (hit).** A SECOND subprocess replays the same program against
the same directory with actual compilation FORBIDDEN (the
``jax._src.compiler`` backend-compile chokepoint monkeypatched to
raise). Success proves the
persistent entry is keyed reproducibly across processes — a cold worker
starts hot. The parent also asserts no NEW main-program entry was
written: a second key for the identical program would mean the cache key
picked up process-local state.

Both probes run the package's own staging path
(``distributed_batch_from_host``) and check row conservation through the
``all_to_all``, so a probe that "passes" on a broken exchange cannot
happen. The probe env is forced to ``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=8`` by the parent, so the
script works from any shell, TPU-attached or not.
"""
from __future__ import annotations

import argparse
import os
import shutil
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# basename marker of the whole-stage exchange program's cache entries
MAIN_PROGRAM = "_run_shuffle_step"
N_DEV = 8
N_ROWS = 1000


def _probe_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={N_DEV}"
        ).strip()
    return env


def _main_entries(cache_dir: str) -> list:
    if not os.path.isdir(cache_dir):
        return []
    return sorted(e for e in os.listdir(cache_dir)
                  if MAIN_PROGRAM in e and e.endswith("-cache"))


def probe(cache_dir: str, forbid_compile: bool) -> int:
    """Child-process body: run one real in-program exchange with
    progcache installed over ``cache_dir`` (which the parent named in
    ``JAX_COMPILATION_CACHE_DIR``: the package sets no directory in
    code then). With ``forbid_compile`` the executable MUST come from
    the persistent cache."""
    from spark_rapids_tpu.utils import progcache

    import jax

    if not progcache.install() or \
            os.path.abspath(progcache.installed_dir()) != \
            os.path.abspath(cache_dir):
        print("probe: progcache is not serving the directory the "
              "environment names", file=sys.stderr)
        return 2

    import numpy as np

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.parallel.mesh import data_mesh
    from spark_rapids_tpu.parallel.shuffle import (
        distributed_batch_from_host, shuffle_step)

    mesh = data_mesh(N_DEV)
    dtypes = [dt.INT64, dt.FLOAT64]
    step = shuffle_step(mesh, dtypes, [0], N_DEV)
    rng = np.random.default_rng(0)
    arrs = [rng.integers(0, 50, N_ROWS).astype(np.int64),
            rng.random(N_ROWS)]
    datas, valids, counts, _cap = distributed_batch_from_host(
        mesh, arrs, dtypes)

    if forbid_compile:
        from jax._src import compiler

        def _forbid(*a, **k):
            raise RuntimeError(
                "backend_compile reached: the persistent entry did not "
                "serve the mesh program")

        # the actual-XLA-compile chokepoint under compile_or_get_cached
        # (this jax predates backend_compile_and_load)
        name = ("backend_compile_and_load"
                if hasattr(compiler, "backend_compile_and_load")
                else "backend_compile")
        orig = getattr(compiler, name)
        setattr(compiler, name, _forbid)
        try:
            out = step(datas, valids, counts)
            jax.block_until_ready(out[3])
        finally:
            setattr(compiler, name, orig)
    else:
        out = step(datas, valids, counts)
        jax.block_until_ready(out[3])

    total = int(np.asarray(jax.device_get(out[3])).sum())
    if total != N_ROWS:
        print(f"probe: exchange lost rows ({total} != {N_ROWS})",
              file=sys.stderr)
        return 2
    print(f"probe-ok dir={progcache.installed_dir()}")
    return 0


def _run_probe(base_dir: str, forbid: bool):
    cmd = [sys.executable, os.path.abspath(__file__),
           "--_probe", base_dir]
    if forbid:
        cmd.append("--_forbid-compile")
    env = _probe_env()
    env["JAX_COMPILATION_CACHE_DIR"] = base_dir
    r = subprocess.run(cmd, env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    installed = None
    for line in r.stdout.splitlines():
        if line.startswith("probe-ok dir="):
            installed = line.split("=", 1)[1]
    return r, installed


def check() -> int:
    tmp = tempfile.mkdtemp(prefix="mesh_progcache_fence_")
    base = os.path.join(tmp, "cache")
    try:
        cold, installed = _run_probe(base, forbid=False)
        if cold.returncode != 0 or not installed:
            print("FAIL: cold mesh probe did not complete:\n"
                  + cold.stdout + cold.stderr)
            return 1
        entries = _main_entries(installed)
        if not entries:
            print("FAIL: the mesh whole-stage program left NO "
                  f"{MAIN_PROGRAM} entry in progcache ({installed}) — "
                  "every fresh worker will eat the shard_map program's "
                  "cold compile. Did parallel/shuffle.py stop funneling "
                  "exchanges through the module-level jit entry, or did "
                  "progcache.install() stop covering sharded programs?")
            return 1
        warm, _ = _run_probe(base, forbid=True)
        if warm.returncode != 0:
            print("FAIL: warm replay had to COMPILE the mesh program — "
                  "its progcache key is not reproducible across "
                  "processes (process-local state leaked into the "
                  "cache key?):\n" + warm.stdout + warm.stderr)
            return 1
        after = _main_entries(installed)
        if after != entries:
            print("FAIL: the warm replay minted a new program key "
                  f"({entries} -> {after}) — the mesh program's cache "
                  "key is unstable across processes")
            return 1
        print("OK: mesh-path program key lands in progcache and "
              f"serves a fresh process ({entries[0]})")
        return 0
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--_probe", metavar="DIR", default=None,
                   help=argparse.SUPPRESS)
    p.add_argument("--_forbid-compile", action="store_true",
                   dest="_forbid_compile", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args._probe:
        return probe(args._probe, args._forbid_compile)
    return check()


if __name__ == "__main__":
    sys.exit(main())
