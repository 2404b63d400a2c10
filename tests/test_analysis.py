"""tpulint test suite: per-code fixtures, the allowlist contract, the
runtime lock-order tracker, the subprocess CI-gate fence, and the q26
plan-level sync-map exactness check.

The fixture tests write tiny source trees under tmp_path shaped like
the real package (``<root>/spark_rapids_tpu/...``) so path-scoped
rules (device-path TPU401, lockorder self-exemption) apply exactly as
they do on the repo. The gate fence runs ``scripts/lint_check.py`` in
a subprocess against a tree seeded with one violation from EACH of the
four diagnostic families and demands a nonzero exit — proving the gate
cannot be wired out of CI silently.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LINT = os.path.join(ROOT, "scripts", "lint_check.py")


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _tree(tmp_path, files):
    """Write {relpath: source} under tmp_path and return its str path."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return str(tmp_path)


def _codes(findings):
    return [f.code for f in findings]


# ---------------------------------------------------------------------------
# TPU1xx host-sync fixtures
# ---------------------------------------------------------------------------


def test_tpu101_np_coerce_flagged_and_device_get_exempt(tmp_path):
    from spark_rapids_tpu.analysis import host_sync
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        import numpy as np
        import jax

        def bad(x):
            return np.asarray(x)

        def explicit(x):
            return np.asarray(jax.device_get(x))

        def literal():
            return np.asarray([1, 2, 3])
    """})
    fs = host_sync.run(root)
    assert _codes(fs) == ["TPU101"]
    assert fs[0].qualname == "bad"


def test_tpu102_item_flagged(tmp_path):
    from spark_rapids_tpu.analysis import host_sync
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        def bad(x):
            return x.item()

        def indexed(x):
            return x.item(0)   # numpy-style indexed item: host array
    """})
    fs = [f for f in host_sync.run(root) if f.code == "TPU102"]
    assert len(fs) == 1 and fs[0].qualname == "bad"


def test_tpu103_barrier_flagged(tmp_path):
    from spark_rapids_tpu.analysis import host_sync
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        import jax

        def bad(x):
            jax.block_until_ready(x)
    """})
    assert _codes(host_sync.run(root)) == ["TPU103"]


def test_tpu104_truth_tests(tmp_path):
    from spark_rapids_tpu.analysis import host_sync
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        import jax.numpy as jnp

        def direct(x):
            if jnp.any(x > 0):
                return 1

        def via_name(x):
            flag = jnp.all(x)
            while not flag:
                break

        def metadata(dt):
            if jnp.issubdtype(dt, jnp.integer):   # host bool: exempt
                return 1
    """})
    fs = [f for f in host_sync.run(root) if f.code == "TPU104"]
    assert sorted(f.qualname for f in fs) == ["direct", "via_name"]


# ---------------------------------------------------------------------------
# TPU2xx recompile fixtures
# ---------------------------------------------------------------------------


def test_tpu201_jit_in_body_flagged_decorator_exempt(tmp_path):
    from spark_rapids_tpu.analysis import recompile
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        from functools import partial
        import jax

        _STEP = jax.jit(lambda x: x + 1)   # module level: fine

        @partial(jax.jit, static_argnums=0)
        def decorated(n, x):
            return x * n

        def bad(x):
            return jax.jit(lambda v: v * 2)(x)
    """})
    fs = [f for f in recompile.run(root) if f.code == "TPU201"]
    assert len(fs) == 1 and fs[0].qualname == "bad"


def test_tpu202_raw_shape_flagged_bucketed_exempt(tmp_path):
    from spark_rapids_tpu.analysis import recompile
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        import jax.numpy as jnp
        from spark_rapids_tpu.ops.buckets import bucket_capacity

        def bad(xs):
            return jnp.zeros(len(xs))

        def quantized(xs):
            cap = bucket_capacity(len(xs))
            return jnp.zeros(cap)
    """})
    fs = [f for f in recompile.run(root) if f.code == "TPU202"]
    assert len(fs) == 1 and fs[0].qualname == "bad"


def test_tpu203_weak_literal_flagged_dtype_exempt(tmp_path):
    from spark_rapids_tpu.analysis import recompile
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        import jax.numpy as jnp

        def bad():
            return jnp.asarray(1)

        def kw():
            return jnp.asarray(1, dtype=jnp.int32)

        def positional(dt):
            return jnp.asarray(0, dt)
    """})
    fs = [f for f in recompile.run(root) if f.code == "TPU203"]
    assert len(fs) == 1 and fs[0].qualname == "bad"


def test_tpu204_every_pallas_call_flagged_and_the_package_has_none(
        tmp_path):
    """The package keeps no Pallas kernel (PR 29): a site is a finding
    wherever it stands and whatever it goes through, the place of the
    former registry included; over the real package the rule is
    silent."""
    from spark_rapids_tpu.analysis import recompile
    root = _tree(tmp_path, {
        "spark_rapids_tpu/native/kernels/__init__.py": """
            def wrapper(kernel, *, out_shape, **kw):
                from jax.experimental import pallas as pl
                return pl.pallas_call(kernel, out_shape=out_shape,
                                      interpret=True, **kw)
        """,
        "spark_rapids_tpu/execs/bad.py": """
            from jax.experimental.pallas import pallas_call

            def bad(kern, shape):
                return pallas_call(kern, out_shape=shape)
        """,
        "spark_rapids_tpu/ops/fine.py": """
            import jax.numpy as jnp

            def fine(sb_h, h_p):
                return jnp.searchsorted(sb_h, h_p, side="left")
        """})
    fs = [f for f in recompile.run(root) if f.code == "TPU204"]
    assert sorted(f.qualname for f in fs) == ["bad", "wrapper"]
    assert [f for f in recompile.run(ROOT) if f.code == "TPU204"] == []


# ---------------------------------------------------------------------------
# TPU3xx lock fixtures (static)
# ---------------------------------------------------------------------------

_LOCK_SRC = """
    import threading
    import time
    from spark_rapids_tpu.utils import lockorder

    OUTER = lockorder.make_lock("service.query")        # rank 20
    INNER = lockorder.make_lock("memory.semaphore")     # rank 108
    RAW = threading.Lock()

    def ordered():
        with OUTER:
            with INNER:
                pass

    def inverted():
        with INNER:
            with OUTER:
                pass

    def blocking():
        with OUTER:
            time.sleep(0.1)
"""


def test_tpu301_static_inversion(tmp_path):
    from spark_rapids_tpu.analysis import locks
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": _LOCK_SRC})
    fs = locks.run(root)
    inv = [f for f in fs if f.code == "TPU301"]
    assert len(inv) == 1 and inv[0].qualname == "inverted"
    assert "service.query" in inv[0].message


def test_tpu302_blocking_under_lock(tmp_path):
    from spark_rapids_tpu.analysis import locks
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": _LOCK_SRC})
    blk = [f for f in locks.run(root) if f.code == "TPU302"]
    assert len(blk) == 1 and blk[0].qualname == "blocking"


def test_tpu303_raw_lock(tmp_path):
    from spark_rapids_tpu.analysis import locks
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": _LOCK_SRC})
    raw = [f for f in locks.run(root) if f.code == "TPU303"]
    assert len(raw) == 1 and raw[0].line == 8


# ---------------------------------------------------------------------------
# TPU4xx robustness fixtures
# ---------------------------------------------------------------------------


def test_tpu401_broad_except_on_device_path(tmp_path):
    from spark_rapids_tpu.analysis import robustness
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        from spark_rapids_tpu.memory.retry import is_oom_error

        def bad(run):
            try:
                return run()
            except Exception:
                return None

        def gated(run):
            try:
                return run()
            except Exception as e:
                if is_oom_error(e):
                    raise
                return None

        def guard():
            try:
                import cupy
            except Exception:
                cupy = None
    """})
    fs = [f for f in robustness.run(root) if f.code == "TPU401"]
    assert len(fs) == 1 and fs[0].qualname == "bad"


def test_tpu401_only_on_device_path(tmp_path):
    from spark_rapids_tpu.analysis import robustness
    root = _tree(tmp_path, {"spark_rapids_tpu/plan/m.py": """
        def host_side(run):
            try:
                return run()
            except Exception:
                return None
    """})
    assert not [f for f in robustness.run(root) if f.code == "TPU401"]


def test_tpu402_unknown_knob(tmp_path):
    from spark_rapids_tpu.analysis import robustness
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/m.py": """
        BAD = "rapids.tpu.thisKnob.doesNotExist"
        GOOD = "rapids.tpu.debug.lockOrder.enabled"
        FAMILY_PREFIX = "rapids.tpu.sql.exec."   # key family, not a key
    """})
    fs = [f for f in robustness.run(root) if f.code == "TPU402"]
    assert len(fs) == 1
    assert "thisKnob.doesNotExist" in fs[0].message


def test_tpu403_undocumented_knob(tmp_path):
    from spark_rapids_tpu.analysis import robustness
    # a docs/configs.md that documents nothing: every non-internal
    # registered knob is reported; absent docs file -> no TPU403
    root = _tree(tmp_path, {"docs/configs.md": "# empty\n"})
    fs = [f for f in robustness.run(root) if f.code == "TPU403"]
    assert fs, "expected TPU403 for every undocumented registered knob"
    assert not any("rapids.tpu.sql.test.enabled" in f.message
                   for f in fs), "internal knobs are docs-exempt"
    assert not [f for f in robustness.run(str(tmp_path / "nowhere"))
                if f.code == "TPU403"]


# ---------------------------------------------------------------------------
# allowlist contract
# ---------------------------------------------------------------------------


def test_allowlist_justification_mandatory():
    from spark_rapids_tpu.analysis.allowlist import (Allowlist,
                                                     AllowlistError)
    with pytest.raises(AllowlistError, match="justification"):
        Allowlist.parse("TPU101 pkg/m.py::f\n")
    with pytest.raises(AllowlistError, match="unknown diagnostic"):
        Allowlist.parse("TPU999 pkg/m.py::f -- because\n")


def test_allowlist_scopes():
    from spark_rapids_tpu.analysis.allowlist import Allowlist
    from spark_rapids_tpu.analysis.diagnostics import Finding
    al = Allowlist.parse("""
        TPU101 pkg/a.py::C.f -- exact site
        TPU102 pkg/b.py -- whole module
        TPU103 pkg/bench/* -- harness glob
    """)
    hit = Finding("TPU101", "pkg/a.py", 3, "C.f", "m")
    miss_fn = Finding("TPU101", "pkg/a.py", 9, "C.g", "m")
    miss_code = Finding("TPU104", "pkg/a.py", 3, "C.f", "m")
    file_hit = Finding("TPU102", "pkg/b.py", 1, "anything", "m")
    glob_hit = Finding("TPU103", "pkg/bench/x.py", 1, "run", "m")
    assert al.allows(hit) and al.allows(file_hit) and al.allows(glob_hit)
    assert not al.allows(miss_fn) and not al.allows(miss_code)
    assert al.filter([hit, miss_fn]) == [miss_fn]
    assert al.unused_entries([hit]) == [
        ("TPU102", "pkg/b.py", "whole module"),
        ("TPU103", "pkg/bench/*", "harness glob")]


def test_repo_allowlist_loads_and_is_exact():
    """Every entry in the checked-in allowlist parses, matches at least
    one current finding (no stale exemptions), and the filtered set is
    empty — the same invariant lint_check.py gates on."""
    from spark_rapids_tpu import analysis
    from spark_rapids_tpu.analysis.allowlist import Allowlist
    al = Allowlist.load()
    assert al.entries, "repo allowlist should not be empty"
    fs = analysis.run_all()
    assert al.filter(fs) == []
    assert al.unused_entries(fs) == []


# ---------------------------------------------------------------------------
# runtime lock-order tracker
# ---------------------------------------------------------------------------


def test_lockorder_runtime_inversion():
    """A→B passes, B→A raises in raise mode: the runtime complement of
    the static TPU301 pass, over the same declared hierarchy."""
    from spark_rapids_tpu.utils import lockorder
    a = lockorder.make_lock("service.query")       # rank 20
    b = lockorder.make_lock("memory.semaphore")    # rank 108
    if not lockorder.enabled():
        pytest.skip("lock-order tracking disabled in this environment")
    lockorder.set_raise_mode(True)
    try:
        with a:
            with b:
                pass                               # declared order: fine
        with pytest.raises(lockorder.LockOrderViolation):
            with b:
                with a:
                    pass
    finally:
        lockorder.set_raise_mode(False)
        lockorder.reset_violations()


def test_lockorder_group_exemption():
    """planBarrier group members may interleave in any order (the plan
    DAG is acyclic) but still order against locks outside the group."""
    from spark_rapids_tpu.utils import lockorder
    chain = lockorder.make_lock("execs.fused.chainPrep")         # 36
    bcast = lockorder.make_lock("exchange.broadcast.materialize")  # 38
    svc = lockorder.make_lock("service.query")                   # 20
    if not lockorder.enabled():
        pytest.skip("lock-order tracking disabled in this environment")
    lockorder.set_raise_mode(True)
    try:
        with bcast:
            with chain:        # lower rank inside group member: exempt
                pass
        with pytest.raises(lockorder.LockOrderViolation):
            with bcast:
                with svc:      # outside the group: ranks still apply
                    pass
    finally:
        lockorder.set_raise_mode(False)
        lockorder.reset_violations()


def test_lockorder_undeclared_name_rejected():
    from spark_rapids_tpu.utils import lockorder
    if not lockorder.enabled():
        pytest.skip("lock-order tracking disabled in this environment")
    with pytest.raises(lockorder.LockOrderViolation, match="not declared"):
        lockorder.make_lock("no.such.lock")


# ---------------------------------------------------------------------------
# the CI gate, end to end
# ---------------------------------------------------------------------------


def _run_lint(*argv, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, LINT, *argv], env=env,
                          capture_output=True, text=True,
                          timeout=timeout)


def test_gate_clean_on_repo():
    out = _run_lint()
    assert out.returncode == 0, out.stdout + out.stderr
    assert "0 new vs baseline" in out.stdout


def test_gate_fails_on_seeded_violations_all_families(tmp_path):
    """One seeded violation per family; lint_check.py must exit
    nonzero and name all four, or the gate is decorative."""
    root = _tree(tmp_path, {
        "spark_rapids_tpu/execs/seeded.py": """
            import threading
            import numpy as np
            import jax

            _RAW = threading.Lock()                      # TPU303

            def sync(x):
                return np.asarray(x)                     # TPU101

            def retrace(x):
                return jax.jit(lambda v: v)(x)           # TPU201

            def swallow(run):
                try:
                    return run()
                except Exception:                        # TPU401
                    return None
        """})
    out = _run_lint("--root", root)
    assert out.returncode == 1, out.stdout + out.stderr
    for family in ("TPU101", "TPU201", "TPU303", "TPU401"):
        assert family in out.stdout, (family, out.stdout)


def test_gate_json_output(tmp_path):
    root = _tree(tmp_path, {"spark_rapids_tpu/execs/seeded.py": """
        import numpy as np

        def sync(x):
            return np.asarray(x)
    """})
    json_path = tmp_path / "findings.json"
    out = _run_lint("--root", root, "--json", str(json_path))
    assert out.returncode == 1
    data = json.loads(json_path.read_text())
    assert data["total"] == 1 and data["allowlisted"] == 0
    [f] = data["new"]
    assert f["code"] == "TPU101"
    assert f["path"] == "spark_rapids_tpu/execs/seeded.py"


# ---------------------------------------------------------------------------
# q26 plan-level sync map
# ---------------------------------------------------------------------------


def test_q26_sync_map_exact():
    """tpcxbb q26 sf0.1: the compiled plan's sync map is EXACTLY the
    batched duplicate-flag fetch plus the root result fetch — any third
    entry is a new host sync the dispatch fence would pay for.
    Subprocess for the same reason as the dispatch fence: planning
    imports compute modules, and the shared dataset dir is reused."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, LINT, "--sync-map",
         "--data-dir", os.path.join("/tmp", "srt_dispatch_fence")],
        env=env, capture_output=True, text=True, timeout=580)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.strip()]
    kinds = sorted(ln.split(None, 1)[1].rsplit(None, 1)[0].strip()
                   for ln in lines)
    assert kinds == ["duplicate-flag fetch", "result fetch"], out.stdout
