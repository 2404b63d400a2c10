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

from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.column import Column, StringColumn

RowCount = Union[int, jax.Array]


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
        """Zero-copy-ish row range view (SlicedGpuColumnVector analogue).
        Result is re-bucketed to the smallest capacity holding ``length``."""
        from spark_rapids_tpu.ops.buckets import bucket_capacity
        n = self.realized_num_rows()
        start = max(0, min(start, n))
        length = max(0, min(length, n - start))
        cap = bucket_capacity(length)
        cols = []
        for c in self.columns:
            grown = c.with_capacity(max(cap + start, c.capacity))
            data = jax.lax.dynamic_slice_in_dim(grown.data, start, cap)
            validity = None
            if grown.validity is not None:
                validity = jax.lax.dynamic_slice_in_dim(
                    grown.validity, start, cap)
            cols.append(c._like(data, validity))
        return ColumnarBatch(cols, length)

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
