"""Standalone shuffle-server process: one executor's catalog over TCP.

The reference's shuffle peers are separate executor JVMs, each serving
its cached blocks through the UCX transport
(RapidsShuffleInternalManager.scala:249-269, UCX.scala:70-155). This
module is the process entry point for the TPU build's equivalent: spawn
``python -m spark_rapids_tpu.shuffle.remote_worker`` with a JSON config
on stdin and it

1. builds an executor (BufferCatalog + ShuffleBufferCatalog),
2. registers the configured deterministic blocks (a map task's output),
3. serves them over a real listening socket (shuffle/tcp.py),
4. prints ``READY <host> <port>`` on stdout,
5. exits when stdin closes (parent-death binding, like Spark executor
   processes dying with their worker).

Config JSON::

    {"executor_id": "exec-remote",
     "blocks": [[shuffle_id, map_id, partition, lo, n], ...],
     "hangup_after_chunks": -1}   # >=0: raise Hangup after N chunk reqs

Blocks hold ``int64 arange(lo, lo+n)`` with every ``v % 7 == 3`` row
null — the same deterministic recipe the in-process shuffle tests use,
so both processes can compute the expected result independently.
"""
from __future__ import annotations

import json
import sys


def make_block_batch(lo: int, n: int):
    """Deterministic batch: int64 arange(lo, lo+n), v%7==3 -> null."""
    import numpy as np

    from spark_rapids_tpu.columnar import dtypes as dt
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column

    vals = np.arange(lo, lo + n, dtype=np.int64)
    valid = (vals % 7) != 3
    return ColumnarBatch(
        [Column.from_numpy(vals, dtype=dt.INT64, validity=valid)], n)


def run_task_loop(ex, ts) -> None:
    """Task-server mode: the worker EXECUTES map tasks shipped as pickled
    closures (the cluster runtime's remote executors — Spark's
    serialized-lineage model), registers the partitioned output in its
    own catalog, and serves it through the already-listening TCP server.
    Nested shuffle reads in the closure fetch from peer executors via
    this process's own transport client (ExecutorContext)."""
    import base64
    import pickle
    import traceback

    from spark_rapids_tpu.runtime.cluster import (ExecutorContext,
                                                  run_map_partitions,
                                                  set_executor_context)
    from spark_rapids_tpu.shuffle.meta import BlockId
    from spark_rapids_tpu.shuffle.tcp import TcpTransport

    transport = TcpTransport()
    set_executor_context(ExecutorContext(ex, transport))
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        cmd = json.loads(line)
        if cmd.get("cmd") == "exit":
            break
        try:
            assert cmd.get("cmd") == "run_map", cmd
            payload = pickle.loads(
                base64.b64decode(cmd["payload_b64"]))
            for eid, addr in payload["addresses"].items():
                if eid != ex.executor_id:
                    transport.register_remote(eid, *addr)
            subtree = payload["subtree"]
            if payload.get("mode") == "sample":
                # range-bounds sampling pass: run the subtree, return a
                # host row sample (the driver aggregates into bounds)
                from spark_rapids_tpu.runtime.cluster import \
                    sample_rows_host

                sample = sample_rows_host(
                    subtree.execute(payload["map_id"]),
                    subtree.schema, payload["sample_rows"])
                print(json.dumps({
                    "ok": True, "map_id": payload["map_id"],
                    "sample_b64": base64.b64encode(
                        pickle.dumps(sample)).decode()}), flush=True)
                continue
            parts = run_map_partitions(
                subtree.execute(payload["map_id"]),
                payload["partitioning"], payload["types"],
                payload["num_out"])
            for p, batch in parts.items():
                ex.shuffle_catalog.register(
                    BlockId(payload["shuffle_id"], payload["map_id"], p),
                    batch)
            print(json.dumps({"ok": True,
                              "map_id": payload["map_id"],
                              # MapStatus sizes ride back with the ids
                              # (AQE coalesced reads need them)
                              "partitions": {
                                  str(p): b.device_memory_size()
                                  for p, b in parts.items()}}),
                  flush=True)
        except Exception:
            print(json.dumps({"ok": False,
                              "error": traceback.format_exc()}),
                  flush=True)


def main() -> None:
    import os

    import spark_rapids_tpu  # noqa: F401

    # workers run on the CPU whatever the parent holds (one process per
    # chip: the driver keeps it); shipped mesh subtrees additionally
    # need the session's mesh width in virtual CPU devices
    import jax

    # FIRST pin the CPU backend (before any device probe): workers must
    # never compute on — or even initialize — the shared attached TPU
    jax.config.update("jax_platforms", "cpu")
    mesh_n = int(os.environ.get("SRT_WORKER_MESH_DEVICES", "0") or 0)
    if mesh_n >= 2:
        from spark_rapids_tpu.parallel.mesh import force_cpu_mesh

        force_cpu_mesh(mesh_n)
    from spark_rapids_tpu.shuffle.cluster import Executor
    from spark_rapids_tpu.shuffle.meta import BlockId
    from spark_rapids_tpu.shuffle.tcp import Hangup, TcpShuffleServer

    config = json.loads(sys.stdin.readline())
    ex = Executor(config.get("executor_id", "exec-remote"))
    if config.get("mode") == "task":
        ts = TcpShuffleServer(ex.server)
        print(f"READY {ts.host} {ts.port}", flush=True)
        run_task_loop(ex, ts)
        ts.close()
        return
    for sid, mid, part, lo, n in config.get("blocks", []):
        ex.shuffle_catalog.register(BlockId(sid, mid, part),
                                    make_block_batch(lo, n))

    hangup_after = int(config.get("hangup_after_chunks", -1))
    if hangup_after >= 0:
        state = {"served": 0}

        def chunk_hook(block, offset, length):
            if state["served"] >= hangup_after:
                raise Hangup()
            state["served"] += 1

        ex.server.on_chunk = chunk_hook

    ts = TcpShuffleServer(ex.server)
    print(f"READY {ts.host} {ts.port}", flush=True)

    # serve until the parent closes our stdin (or kills us)
    sys.stdin.read()
    ts.close()


if __name__ == "__main__":
    main()
