"""Ask the chip's compiler without the chip.

The TPU compiler is installed in the sandbox and compiles for a chip
that is *described*, not attached (on-chip-measurement guide, section
2, rehearsal 3). These tests lower the main path's programs for a
described ``v5e:2x2`` and compile them: what the chip's compiler would
refuse, it refuses here, at no chip time. Nothing runs, so they say
nothing about answers or times.

Rules this file keeps (the guide gives the reasons): the topology is
described only inside a fixture, never at import, in a ``skipif`` or in
a ``parametrize`` argument; compiles happen in the test's own process
(only one process may hold the TPU library); the persistent compilation
cache is off around them (an AOT entry cannot be read back without a
chip); everything lives in this one file. Few compiles: since PR 27 a
sort of one packed lane costs the chip's compiler about 5 s at any
capacity and a sort of a key word with its index 13-25 s (one of those
for any number of key lanes), but a segmented group-by at 65,536 rows
still costs 18 s, and at 2,097,152 rows minutes (its scans, not its
sorts).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    """An executable compiled for a described device is written to the
    persistent cache but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()


def _described(args, sharding):
    """Arrays -> ShapeDtypeStructs placed on the described device;
    everything else (statics, python scalars) passes through."""
    def leaf(x):
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=sharding)
        return x

    return jax.tree_util.tree_map(leaf, args)


class _JitRecorder:
    """Stands in for ``jax.jit`` while a query runs on the CPU: records
    each program built at run time with the arguments of its calls."""

    def __init__(self, real_jit):
        self.real_jit = real_jit
        self.calls = []  # (fn, jit kwargs, args, kwargs)
        # a program built now may outlive the test in a module registry:
        # switched off, its wrapper records (and so holds) nothing more
        self.recording = True

    def __call__(self, fn=None, **kw):
        if fn is None:
            return lambda f: self(f, **kw)
        jitted = self.real_jit(fn, **kw)
        rec = self

        class _Recorded:
            def __call__(self, *a, **k):
                if rec.recording:
                    rec.calls.append((fn, kw, a, k))
                return jitted(*a, **k)

            def __getattr__(self, name):
                return getattr(jitted, name)

        return _Recorded()


def test_entry_step_compiles_for_v5e(one_chip, no_compile_cache):
    """``__graft_entry__.entry()``: filter -> sort-based group-by on
    int64/float64, the smallest program of the hot path, at 65,536
    rows."""
    from __graft_entry__ import entry

    step, _ = entry()
    cap = 65536
    args = (jax.ShapeDtypeStruct((cap,), jnp.int64, sharding=one_chip),
            jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one_chip),
            jax.ShapeDtypeStruct((cap,), jnp.float64, sharding=one_chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    compiled = jax.jit(step).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes > 0
    assert mem.argument_size_in_bytes >= cap * (8 + 1 + 8)


def test_q6_chain_compiles_for_v5e_at_sf1_batch_shape(
        one_chip, no_compile_cache, tmp_path, monkeypatch):
    """TPC-H q6's one launch a scanned batch (since PR 30 the fused
    decode+filter+project chain, the keyless reduction and the merge into
    the running partials are one program, ``fused_agg[decode+...]``),
    caught from a real ``Session.sql`` run over one sf 1 scan split (sf 1
    lineitem is 6.0 M rows in four files of 1.5 M: capacity 2,097,152 per
    batch), then compiled for the described chip at exactly those
    shapes."""
    import pyarrow.parquet as pq

    import chip_smoke
    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.benchmarks import datagen
    from spark_rapids_tpu.expressions import compiler

    n = 1_500_000
    split = datagen._lineitem_chunk(np.random.default_rng(11), n, 1.0, 0.0)
    os.makedirs(tmp_path / "lineitem")
    pq.write_table(split.select(["l_extendedprice", "l_discount",
                                 "l_quantity", "l_shipdate"]),
                   str(tmp_path / "lineitem" / "part-000.parquet"))

    rec = _JitRecorder(jax.jit)
    monkeypatch.setattr(jax, "jit", rec)
    # a chain an earlier test built would be served from the registry
    # and never reach jax.jit
    monkeypatch.setattr(compiler, "_FUSED_CACHE", {})
    s = Session()
    s.register_parquet("lineitem", str(tmp_path / "lineitem"))
    out = s.sql(chip_smoke.Q6).collect()
    rec.recording = False
    monkeypatch.undo()
    assert len(out) == 1 and np.isfinite(out["revenue"].iloc[0])

    chains = [c for c in rec.calls
              if c[0].__name__.startswith("fused_agg[decode+filter")]
    assert chains, [c[0].__name__ for c in rec.calls]
    fn, kw, a, k = chains[0]
    shapes = {x.shape for x in jax.tree_util.tree_leaves((a, k))
              if hasattr(x, "shape")}
    assert (2097152,) in shapes, shapes
    a, k = _described((a, k), one_chip)
    compiled = jax.jit(fn, **kw).lower(*a, **k).compile()
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_q1_fused_step_compiles_for_v5e_at_sf1_batch_shape(
        one_chip, no_compile_cache, tmp_path, monkeypatch):
    """TPC-H q1's one launch a cached batch (PR 30): filter and
    projection, the dense group-by of its eight aggregates and the merge
    into the running partials, caught from a ``Session.sql`` run over a
    small cached lineitem and compiled for the described chip with every
    row-capacity argument at 2,097,152 (the program is one Python function
    at any capacity). 4.7 s and 6 MB of code in this sandbox (PR 30), the
    two 15-slot compaction sorts included; the limit is ten times that: a
    sort-path group-by traced in by mistake takes minutes at this shape
    (493 s, PR 27). Its only results are the running partials."""
    import time

    from spark_rapids_tpu.api import Session
    from spark_rapids_tpu.benchmarks import datagen
    from spark_rapids_tpu.expressions import compiler

    datagen.write_tables(str(tmp_path), 0.002, tables=["lineitem"])
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "queries",
            "q1.sql")) as f:
        q1 = f.read()

    rec = _JitRecorder(jax.jit)
    monkeypatch.setattr(jax, "jit", rec)
    monkeypatch.setattr(compiler, "_FUSED_CACHE", {})
    s = Session()
    s.read.parquet(str(tmp_path / "lineitem")).cache() \
        .create_or_replace_temp_view("lineitem")
    out = s.sql(q1).collect()
    rec.recording = False
    monkeypatch.undo()
    s.stop()
    assert len(out) == 6

    steps = [c for c in rec.calls if c[0].__name__.startswith("fused_agg[")]
    assert steps, [c[0].__name__ for c in rec.calls]
    fn, kw, a, k = steps[0]
    small = max(x.shape[0] for x in jax.tree_util.tree_leaves((a, k))
                if getattr(x, "shape", ()))
    cap = 2097152

    def at_cell_size(x):
        if isinstance(x, (jax.Array, np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(
                (cap,) if x.shape == (small,) else x.shape, x.dtype,
                sharding=one_chip)
        return x

    a, k = jax.tree_util.tree_map(at_cell_size, (a, k))
    t0 = time.perf_counter()
    compiled = jax.jit(fn, **kw).lower(*a, **k).compile()
    seconds = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    assert mem.generated_code_size_in_bytes > 0
    assert mem.argument_size_in_bytes >= cap * 38    # q1's columns alone
    # keys, seven sums and four counts, a validity each, and the count
    results = jax.tree_util.tree_leaves(compiled.out_info)
    assert len(results) == 27 and \
        all(r.shape in ((15,), ()) for r in results), results
    assert seconds < 47.0, f"q1's fused step compiled in {seconds:.1f} s"


def test_mesh_exchange_step_compiles_for_four_v5e_chips(
        topo, no_compile_cache):
    """One four-device ``shard_map`` exchange step (the in-program
    all_to_all shuffle of ``parallel/shuffle.py``) over a ``Mesh`` of
    the described 2x2 topology, at 65,536 rows per device: 6.7 s of the
    chip's compiler since no column rides the exchange's sorts (PR 27;
    7.1 s at 8,192), where it took 191 s, and 75 s at 8,192 (PR 23) —
    what is checked is that the sharded program and its collective
    lower and compile."""
    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_tpu.parallel.shuffle import (_run_shuffle_step,
                                                   shuffle_step)

    n_dev, cap = 4, 65536
    assert len(topo.devices) == n_dev
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    dtypes = [dt.INT64, dt.FLOAT64]
    step = shuffle_step(mesh, dtypes, [0], n_dev)
    rows = NamedSharding(mesh, P(DATA_AXIS))
    datas = [jax.ShapeDtypeStruct((n_dev * cap,), t.np_dtype, sharding=rows)
             for t in dtypes]
    valids = [jax.ShapeDtypeStruct((n_dev * cap,), np.bool_, sharding=rows)
              for _ in dtypes]
    counts = jax.ShapeDtypeStruct((n_dev,), np.int32, sharding=rows)
    compiled = _run_shuffle_step.lower(step, datas, valids,
                                       counts).compile()
    text = compiled.as_text()
    assert "all-to-all" in text, "the exchange lost its collective"
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0


def test_q3_partition_kernel_compiles_for_v5e_at_sf1_batch_shape(
        one_chip, no_compile_cache):
    """TPC-H Q3's widest sort program over the cached tables: the hash
    exchange's split of one lineitem batch, 15 columns with their
    validities at 2,097,152 rows into 16 partitions
    (``ops/partition._partition_kernel``). With the columns carried
    through the sort this took the chip's compiler 107 s at 65,536 rows
    and 352 s at 32,768 (PR 23); as one packed sort lane and a gather a
    column it took 5.8 s in this sandbox, 10.0 s beside two other
    compiles (PR 27). The limit is three times the latter: a sort that
    carries a column again, or an index of 64 bits, breaks it."""
    import time

    from spark_rapids_tpu.ops import partition as part

    cap = 2097152
    lineitem = [jnp.int64] * 4 + [jnp.float64] * 4 + [jnp.int32] * 7

    def rows(t):
        return jax.ShapeDtypeStruct((cap,), t, sharding=one_chip)

    t0 = time.perf_counter()
    compiled = part._partition_kernel.lower(
        [rows(t) for t in lineitem], [rows(jnp.bool_)] * len(lineitem),
        rows(jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        num_partitions=16).compile()
    seconds = time.perf_counter() - t0
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    assert seconds < 30.0, f"_partition_kernel compiled in {seconds:.1f} s"


def test_q3_join_build_and_probe_compile_for_v5e_at_sf1_shapes(
        one_chip, no_compile_cache):
    """TPC-H Q3's shuffled join at sf 1: ``ops/join._build_sorted`` over
    the orders side (its 8 columns, no validity, capacity 524,288: the
    sort of the hashed key, the distinct hashes compacted by a one-bit
    ``stable_order``, the directory by a scatter-add and a prefix sum)
    and ``_probe_sorted`` of a 1,048,576-row stream batch against it (a
    ``while`` of one gather of four stacked int32 lanes a round). The
    parent's two programs (a sort and a gather a dtype; two
    ``searchsorted`` loops) took 20.4 s and 0.4 s in this sandbox, these
    28.5 s and 1.2 s (PR 32; the scatter-add is the build's difference).
    The limits are three times that: a cumulative operation over the
    whole capacity (65 s at 1 M rows, ``_prefix_sum``), a sort that
    carries the hashes, a second two-operand sort in the index or an
    unrolled search breaks them."""
    import time

    from spark_rapids_tpu.ops import join as J

    b_cap, s_cap = 524288, 1048576
    orders = [jnp.int64] * 2 + [jnp.int32] + [jnp.float64] + [jnp.int32] * 4

    def arr(n, t):
        shape = n if isinstance(n, tuple) else (n,)
        return jax.ShapeDtypeStruct(shape, t, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    t0 = time.perf_counter()
    build = J._build_sorted.lower(
        [arr(b_cap, t) for t in orders], [None] * len(orders),
        arr(b_cap, jnp.int64), scalar).compile()
    t1 = time.perf_counter()
    bits = J._directory_bits(b_cap)
    index = J.HashIndex(arr((4, b_cap + 1), jnp.int32),
                        arr((2, 1 << bits), jnp.int32), scalar)
    probe = J._probe_sorted.lower(
        index, arr(s_cap, jnp.int64), scalar).compile()
    t2 = time.perf_counter()
    for compiled in (build, probe):
        assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    assert t1 - t0 < 90.0, f"_build_sorted compiled in {t1 - t0:.1f} s"
    assert t2 - t1 < 4.0, f"_probe_sorted compiled in {t2 - t1:.1f} s"


def test_wide_sort_path_groupby_compiles_for_v5e(one_chip,
                                                 no_compile_cache):
    """The sort-path ``_groupby`` (an int64 key with no host-known
    range, so no dense layout) with nine aggregate columns at capacity
    32,768: the shape at which a libtpu of 2026-07 crashed its compile
    helper, and for which a chunked launch loop existed until PR 29.
    One whole program is the only schedule now; this case is the fence
    that would see the crash come back."""
    import time

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.ops import groupby as gb
    from spark_rapids_tpu.ops.groupby import AggSpec

    cap = 32768
    dtypes = (dt.INT64, dt.FLOAT64, dt.INT64)
    aggs = (AggSpec("sum", 1), AggSpec("min", 1), AggSpec("max", 1),
            AggSpec("count", 1), AggSpec("sum", 2), AggSpec("min", 2),
            AggSpec("max", 2), AggSpec("count", 2),
            AggSpec("count_star"))
    assert gb._dense_layout(list(dtypes), [0], (None,), (False,)) is None

    def rows(t):
        return jax.ShapeDtypeStruct((cap,), t, sharding=one_chip)

    cols = [(rows(jnp.int64), None), (rows(jnp.float64), rows(jnp.bool_)),
            (rows(jnp.int64), rows(jnp.bool_))]
    t0 = time.perf_counter()
    compiled = gb._groupby.lower(
        cols, dtypes, (0,), aggs,
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        live_mask=rows(jnp.bool_), key_ranges=(None,),
        dense_ok=True).compile()
    seconds = time.perf_counter() - t0
    assert compiled.memory_analysis().generated_code_size_in_bytes > 0
    assert seconds < 120.0, f"_groupby compiled in {seconds:.1f} s"
