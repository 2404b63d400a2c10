"""Batch concatenation (cuDF ``Table.concatenate`` analogue).

Feeds the coalescing engine (GpuCoalesceBatches.scala:129-490). Row counts
are realized host-side here — concatenation IS the batch boundary where the
reference also materializes sizes. Output capacity is the bucket of the total
row count; each input's live prefix is placed with ``dynamic_update_slice``.
String columns are first re-encoded onto a unified dictionary.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn, unify_dictionaries
from spark_rapids_tpu.ops.buckets import bucket_capacity


def concat_batches(batches: List[ColumnarBatch]) -> ColumnarBatch:
    batches = [b for b in batches if b is not None]
    assert batches, "concat of zero batches"
    if len(batches) == 1:
        return batches[0]
    ncols = batches[0].num_columns
    counts = ColumnarBatch.realize_counts(batches)  # one sync, not N
    total = sum(counts)
    out_cap = bucket_capacity(total)

    # strings first: dictionary unification is host-side and replaces
    # the code arrays the kernel consumes
    per_col_cols: List[List[Column]] = []
    dictionaries: List = []
    for ci in range(ncols):
        cols = [b.columns[ci] for b in batches]
        if isinstance(cols[0], StringColumn):
            cols = unify_dictionaries(cols)  # type: ignore[arg-type]
            dictionaries.append(cols[0].dictionary)
        else:
            dictionaries.append(None)
        per_col_cols.append(cols)

    # ONE jitted program assembles every column (the per-placement
    # eager dispatches - capacity slices + dynamic_update_slices - each
    # paid a device round trip; offsets/counts ride as traced scalars
    # so one compilation serves every count pattern at this signature)
    datas = tuple(tuple(c.data for c in cols) for cols in per_col_cols)
    valids = tuple(tuple(c.validity for c in cols)
                   for cols in per_col_cols)
    import numpy as np

    offs = np.zeros(len(batches), dtype=np.int64)
    np.cumsum(counts[:-1], out=offs[1:])
    out_d, out_v = _concat_kernel(datas, valids,
                                  jnp.asarray(offs),
                                  jnp.asarray(np.asarray(counts,
                                                         dtype=np.int64)),
                                  out_cap)
    out_cols: List[Column] = []
    for ci in range(ncols):
        if dictionaries[ci] is not None:
            out_cols.append(StringColumn(out_d[ci], dictionaries[ci],
                                         out_v[ci]))
        else:
            out_cols.append(Column(per_col_cols[ci][0].dtype, out_d[ci],
                                   out_v[ci]))
    return ColumnarBatch(out_cols, total)


@partial(jax.jit, static_argnames=("out_cap",))
def _concat_kernel(datas, valids, offs, ns, out_cap: int):
    live = jnp.arange(out_cap, dtype=jnp.int64) < offs[-1] + ns[-1]
    out_d, out_v = [], []
    for col_datas, col_valids in zip(datas, valids):
        out_d.append(_place_all(col_datas, offs, live))
        if any(v is not None for v in col_valids):
            out_v.append(_place_all(
                [jnp.ones(d.shape[0], dtype=bool) if v is None else v
                 for d, v in zip(col_datas, col_valids)], offs, live))
        else:
            out_v.append(None)
    return out_d, out_v


def _place_all(pieces, offs, live) -> jax.Array:
    """The pieces' live rows one after another, zero from the last live
    row on. Each piece is written whole at its traced offset, in order, so
    the next piece overwrites its padding; the buffer is a piece longer
    than the output, so no write is clamped. Slice writes and not a
    gather a piece: a gather would read the whole output capacity for
    every piece of every column. Runs INSIDE _concat_kernel's trace."""
    out_cap = live.shape[0]
    acc = jnp.zeros(out_cap + max(p.shape[0] for p in pieces),
                    dtype=pieces[0].dtype)
    for bi, p in enumerate(pieces):
        acc = jax.lax.dynamic_update_slice(acc, p.astype(acc.dtype),
                                           (offs[bi],))
    return jnp.where(live, acc[:out_cap], jnp.zeros((), acc.dtype))


def interleave_batches(batches: List[ColumnarBatch]) -> ColumnarBatch:
    """Row-major interleave of same-schema, same-num_rows batches: output
    row i*k+j comes from batches[j] row i. This is Spark's ExpandExec /
    explode emission order (one output row per (input row, projection)
    pair, projections adjacent). A stack+reshape keeps live rows in the
    prefix [0, n*k): with every input's live rows in [0, n), output slot
    i*k+j < n*k iff i < n."""
    assert batches, "interleave of zero batches"
    if len(batches) == 1:
        return batches[0]
    k = len(batches)
    ncols = batches[0].num_columns
    n = batches[0].realized_num_rows()
    assert all(b.realized_num_rows() == n for b in batches), \
        "interleave requires equal row counts"
    cap = max(b.capacity for b in batches)

    out_cols: List[Column] = []
    for ci in range(ncols):
        cols = [b.columns[ci].with_capacity(cap) for b in batches]
        if isinstance(cols[0], StringColumn):
            cols = unify_dictionaries(cols)  # type: ignore[arg-type]
            dictionary = cols[0].dictionary
        else:
            dictionary = None
        data = _interleave([c.data for c in cols])
        if any(c.validity is not None for c in cols):
            validity = _interleave(
                [c.validity if c.validity is not None
                 else jnp.ones(cap, dtype=bool) for c in cols])
        else:
            validity = None
        if dictionary is not None:
            out_cols.append(StringColumn(data, dictionary, validity))
        else:
            out_cols.append(Column(cols[0].dtype, data, validity))
    return ColumnarBatch(out_cols, n * k)


@jax.jit
def _interleave(arrs: List[jax.Array]) -> jax.Array:
    return jnp.stack(arrs, axis=1).reshape(-1)
