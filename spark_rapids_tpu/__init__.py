"""spark_rapids_tpu: a TPU-native columnar SQL acceleration framework.

A from-scratch, TPU-first re-design of the capabilities of the RAPIDS
Accelerator for Apache Spark (reference surveyed in SURVEY.md):

- a columnar batch representation backed by JAX device arrays with
  validity masks and *bucketed static capacities* (the TPU/XLA answer to
  cuDF's dynamically-sized device buffers),
- a kernel surface (filter/sort/groupby/join/partition/concat/cast/...)
  implemented as jit-compiled XLA computations with bounded recompilation,
- an expression layer whose projections fuse into single XLA executables,
- a tiered device->host->disk spill catalog and chip admission control,
- a plan-override planner with per-op config gates, tagging reasons and
  CPU fallback (pandas engine doubles as the golden-comparison oracle),
- a device-resident shuffle whose intra-slice path rides ICI collectives
  (jax.lax.all_to_all under shard_map) instead of UCX/RDMA.

Reference architecture citations throughout use ``path:line`` into
/root/reference (vorktanamobay/spark-rapids).
"""
from __future__ import annotations

import os

# Spark SQL semantics require 64-bit longs/doubles (LongType/DoubleType are
# pervasive in TPC-* schemas). JAX defaults to 32-bit; opt into x64 before any
# array is created. Set SPARK_RAPIDS_TPU_NO_X64=1 to opt out (perf experiments).
if not os.environ.get("SPARK_RAPIDS_TPU_NO_X64"):
    import jax

    jax.config.update("jax_enable_x64", True)

# Persistent executable cache: the fused relational programs are LARGE
# (sorts + scans over x64-rewritten graphs) and compile for a long time;
# caching makes every process after the first start hot. The directory
# rule is utils/progcache's.
if not os.environ.get("SPARK_RAPIDS_TPU_NO_COMPILE_CACHE"):
    from spark_rapids_tpu.utils import progcache as _progcache

    _progcache.configure()

__version__ = "0.1.0"

from spark_rapids_tpu.config import RapidsConf  # noqa: E402,F401
