"""Columnar batches: the unit of execution.

TPU-native analogue of Spark's ``ColumnarBatch`` carrying ``GpuColumnVector``s
(GpuColumnVector.java:252-276 from/to batch conversions). Key differences:

- ``num_rows`` may be a **device scalar** (0-d int32 array): kernels like
  filter and groupby produce data-dependent row counts; we leave the count
  on device until a consumer genuinely needs the Python int (coalescing
  decisions, shuffle sizing, host materialization). That keeps chains of
  jitted kernels free of host syncs — the TPU version of cuDF's
  "row count comes back with the table" behavior without blocking.
- all columns share one bucketed capacity >= num_rows.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column, StringColumn

RowCount = Union[int, jax.Array]

#: most row ranges one launch of ``_slice_rows`` cuts: a program's size
#: (and its compile time) grows with ranges x arrays, so a batch cut into
#: more ranges of one capacity takes several launches of 16
_MAX_SLICE_CHUNK = 16


@partial(jax.jit, static_argnames=("cap",))
def _slice_rows(datas, validities, starts, cap: int):
    """Rows ``[starts[i], starts[i] + cap)`` of every array, each read as
    if zero-padded past its capacity: ``[(datas_i, validities_i), ...]``,
    one pair a start. The pad to ``capacity + cap`` is static, so no
    ``dynamic_slice`` is left to clamp a start that lies within the
    capacity. Keyed on the arrays' shapes and dtypes, ``starts.shape`` and
    ``cap``, never on the starts themselves."""
    k = starts.shape[0]

    def windows(x):
        if x is None:
            return [None] * k
        padded = jnp.concatenate([x, jnp.zeros(cap, dtype=x.dtype)])
        return [jax.lax.dynamic_slice_in_dim(padded, starts[i], cap)
                for i in range(k)]

    d = [windows(x) for x in datas]
    v = [windows(x) for x in validities]
    return [([c[i] for c in d], [c[i] for c in v]) for i in range(k)]


class Schema:
    """Ordered (name, DType) pairs. Plan attributes reference columns by
    ordinal after binding (GpuBoundReference analogue), names matter at the
    API/IO boundary."""

    __slots__ = ("names", "types")

    def __init__(self, names: Sequence[str], types: Sequence[dt.DType]):
        assert len(names) == len(types)
        self.names = list(names)
        self.types = list(types)

    def __len__(self) -> int:
        return len(self.names)

    def index_of(self, name: str) -> int:
        return self.names.index(name)

    def field(self, i: int):
        return self.names[i], self.types[i]

    def __repr__(self) -> str:  # pragma: no cover
        return "Schema(" + ", ".join(
            f"{n}:{t}" for n, t in zip(self.names, self.types)) + ")"


class ColumnarBatch:
    __slots__ = ("columns", "_num_rows", "origin")

    def __init__(self, columns: List[Column], num_rows: RowCount,
                 origin=None):
        self.columns = columns
        self._num_rows = num_rows
        #: (file_path, block_start, block_length) when this batch came
        #: straight from one file split (input_file_name support,
        #: GpuInputFileBlock.scala); transforms drop it — Spark's
        #: input_file_name is likewise only defined directly above scans
        self.origin = origin
        if columns:
            cap = columns[0].capacity
            assert all(c.capacity == cap for c in columns), \
                "all columns in a batch must share one capacity"

    # -- shape ------------------------------------------------------------

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    @property
    def capacity(self) -> int:
        return self.columns[0].capacity if self.columns else 0

    @property
    def num_rows(self) -> RowCount:
        """May be a device scalar; prefer this in jitted code."""
        return self._num_rows

    def num_rows_device(self) -> jax.Array:
        if isinstance(self._num_rows, int):
            return jnp.asarray(self._num_rows, dtype=jnp.int32)
        return self._num_rows

    def realized_num_rows(self) -> int:
        """Force the row count to the host (sync point — use sparingly,
        at batch boundaries only)."""
        if not isinstance(self._num_rows, int):
            self._num_rows = int(jax.device_get(self._num_rows))
        return self._num_rows

    @staticmethod
    def realize_counts(batches: "List[ColumnarBatch]") -> List[int]:
        """Realize MANY batches' lazy counts in ONE device_get instead
        of N separate syncs."""
        lazy = [b for b in batches
                if not isinstance(b._num_rows, int)]
        if lazy:
            vals = jax.device_get([b._num_rows for b in lazy])
            for b, v in zip(lazy, vals):
                b._num_rows = int(v)
        return [b._num_rows for b in batches]

    def row_mask(self) -> jax.Array:
        """lane-mask of live rows: iota < num_rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < \
            self.num_rows_device()

    def device_memory_size(self) -> int:
        return sum(c.device_memory_size() for c in self.columns)

    # -- construction -----------------------------------------------------

    @staticmethod
    def empty(schema: Schema) -> "ColumnarBatch":
        cols: List[Column] = []
        from spark_rapids_tpu.ops.buckets import MIN_CAPACITY
        for t in schema.types:
            if t is dt.STRING:
                cols.append(StringColumn(
                    jnp.zeros(MIN_CAPACITY, dtype=jnp.int32),
                    np.array([], dtype=object)))
            else:
                cols.append(Column(
                    t, jnp.zeros(MIN_CAPACITY, dtype=t.kernel_dtype)))
        return ColumnarBatch(cols, 0)

    @staticmethod
    def rows_only(num_rows: int) -> "ColumnarBatch":
        """Degenerate batch: rows but no columns (the reference round-trips
        these through shuffle as metadata-only, MetaUtils.scala:144)."""
        return ColumnarBatch([], num_rows)

    def select(self, ordinals: Sequence[int]) -> "ColumnarBatch":
        return ColumnarBatch([self.columns[i] for i in ordinals],
                             self._num_rows)

    def with_columns(self, columns: List[Column]) -> "ColumnarBatch":
        return ColumnarBatch(columns, self._num_rows)

    def slice(self, start: int, length: int) -> "ColumnarBatch":
        """Row range view (SlicedGpuColumnVector analogue). Result is
        re-bucketed to the smallest capacity holding ``length``."""
        return self.slices([start], [length])[0]

    def slices(self, starts: Sequence[int], lengths: Sequence[int]
               ) -> "List[ColumnarBatch]":
        """Many row ranges of this batch at once, each re-bucketed to the
        smallest capacity holding its length; rows past the batch's own
        capacity read as zeros. The ranges that share an output capacity
        go through ``_slice_rows`` in power-of-two chunks (13 = 8 + 4 +
        1), so the programs compiled follow the ladder's rungs and not
        the lengths."""
        from spark_rapids_tpu.ops.buckets import bucket_capacity
        n = self.realized_num_rows()
        starts = np.clip(starts, 0, n).astype(np.int64)
        lengths = np.clip(lengths, 0, n - starts).astype(np.int64)
        if not self.columns:
            return [ColumnarBatch([], int(length)) for length in lengths]
        by_cap = {}
        for i, length in enumerate(lengths):
            by_cap.setdefault(bucket_capacity(int(length)), []).append(i)
        datas = [c.data for c in self.columns]
        validities = [c.validity for c in self.columns]
        out: List[Optional[ColumnarBatch]] = [None] * len(lengths)
        for cap, members in by_cap.items():
            while members:
                k = min(1 << (len(members).bit_length() - 1),
                        _MAX_SLICE_CHUNK)
                chunk, members = members[:k], members[k:]
                cut = _slice_rows(datas, validities,
                                  starts[chunk].astype(np.int32), cap=cap)
                for i, (d, v) in zip(chunk, cut):
                    out[i] = ColumnarBatch(
                        [c._like(cd, cv)
                         for c, cd, cv in zip(self.columns, d, v)],
                        int(lengths[i]))
        return out

    # -- host materialization --------------------------------------------

    def to_pandas(self, schema: Optional[Schema] = None):
        import pandas as pd

        # ONE device->host transfer for the whole batch: every column's
        # data + validity and the (possibly lazy) row count ride a
        # single device_get instead of one fetch per column
        import jax

        fetched = jax.device_get((
            [c.data for c in self.columns],
            [c.validity for c in self.columns],
            None if isinstance(self._num_rows, int) else self._num_rows))
        datas, valids, n_dev = fetched
        if n_dev is not None:
            self._num_rows = int(n_dev)
        n = self._num_rows
        data = {}
        for i, c in enumerate(self.columns):
            name = schema.names[i] if schema else f"c{i}"
            values, validity = c._decode_host(datas[i], valids[i], n)
            if validity is not None and not isinstance(c, StringColumn):
                # preserve SQL NULLs: use pandas nullable / object via mask
                values = values.astype(object)
                values[~validity] = None
            if values.dtype == object:
                # explicit object Series: pandas 3's frame constructor
                # infers a string dtype from object arrays and coerces
                # None->NaN, losing SQL NULL-ness
                data[name] = pd.Series(values, dtype=object)
            else:
                data[name] = values
        df = pd.DataFrame(data)
        return df

    def __repr__(self) -> str:  # pragma: no cover
        nr = self._num_rows if isinstance(self._num_rows, int) else "<device>"
        return f"ColumnarBatch(cols={self.num_columns}, rows={nr}, cap={self.capacity})"
