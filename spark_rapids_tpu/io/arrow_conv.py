"""pyarrow <-> host-columnar conversion shared by all file sources.

Host representation (what DataSource.read_host returns): numpy arrays in the
engine's physical encodings — int32 days for DATE, int64 UTC microseconds for
TIMESTAMP, object arrays (None = null) for STRING — plus bool validity masks.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import Schema
from spark_rapids_tpu.io.hoststrings import HostStrings
from spark_rapids_tpu.utils.tracing import TraceRange, count


def schema_from_arrow(arrow_schema, columns: Optional[List[str]] = None
                      ) -> Schema:
    names, types = [], []
    for field in arrow_schema:
        if columns is not None and field.name not in columns:
            continue
        names.append(field.name)
        types.append(dt.from_arrow(field.type))
    if columns is not None:
        order = {n: i for i, n in enumerate(names)}
        missing = [c for c in columns if c not in order]
        if missing:
            raise KeyError(f"columns not in file schema: {missing}")
        names = list(columns)
        types = [types[order[c]] for c in columns]
    return Schema(names, types)


def column_to_host(col, typ: dt.DType) -> Tuple[np.ndarray, np.ndarray]:
    """One arrow ChunkedArray/Array -> (data ndarray, validity ndarray
    or None when the column has no nulls — skipping the is_valid pass,
    the fill_null pass, AND the validity upload)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if col.null_count == 0:
        valid = None
    else:
        valid = pc.is_valid(col).to_numpy(
            zero_copy_only=False).astype(bool)
    if typ is dt.STRING:
        # stay dictionary-encoded end to end: arrow's C++ encode gives
        # codes + unique values; sort the (small) dictionary and remap
        # so code order == string order (StringColumn's invariant).
        # Only the dictionary ever becomes Python objects.
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if not pa.types.is_dictionary(col.type):
            col = pc.dictionary_encode(col)
        if col.dictionary.null_count > 0:
            # a null INSIDE the dictionary is legal arrow (never
            # produced by parquet dictionary pages); is_valid/null_count
            # above only see index-level nulls, so rows referencing the
            # null slot would otherwise surface as the literal string
            # 'None'. Fold them into the validity mask and repoint
            # their codes at slot 0.
            dict_valid = pc.is_valid(col.dictionary).to_numpy(
                zero_copy_only=False).astype(bool)
            idx0 = pc.fill_null(col.indices, 0).to_numpy(
                zero_copy_only=False).astype(np.int64, copy=False)
            row_hits_null = ~dict_valid[idx0]
            if valid is None:
                valid = np.ones(len(col), dtype=bool)
            valid = valid & ~row_hits_null
            col = pa.DictionaryArray.from_arrays(
                pa.array(np.where(row_hits_null, 0, idx0),
                         type=col.indices.type),
                pc.fill_null(col.dictionary, ""))
        idx = col.indices if valid is None else pc.fill_null(col.indices, 0)
        codes = idx.to_numpy(zero_copy_only=False).astype(
            np.int32, copy=False)
        dvals = col.dictionary.to_numpy(zero_copy_only=False)
        if len(dvals):
            ds = dvals.astype(str)
            with TraceRange("strings.dictionary"):
                order = np.argsort(ds, kind="stable")
            count("strings.dictionary_values", len(ds))
            rank = np.empty(len(order), dtype=np.int32)
            rank[order] = np.arange(len(order), dtype=np.int32)
            codes = rank[codes]
            dictionary = np.asarray(ds[order], dtype=object)
        else:
            dictionary = np.array([], dtype=object)
        return HostStrings(codes, dictionary), valid
    if typ is dt.DATE:
        ints = col.cast(pa.int32())
        if valid is not None:
            ints = pc.fill_null(ints, 0)
        return ints.to_numpy(zero_copy_only=False).astype(
            np.int32, copy=False), valid
    if typ is dt.TIMESTAMP:
        # normalize to UTC microseconds (the engine is UTC-only, like the
        # reference: GpuOverrides.scala:341)
        ts = col
        if isinstance(ts, pa.ChunkedArray):
            ts = ts.combine_chunks()
        ts = ts.cast(pa.timestamp("us", tz="UTC")) \
            if ts.type.tz is not None else ts.cast(pa.timestamp("us"))
        ints = ts.cast(pa.int64())
        if valid is not None:
            ints = pc.fill_null(ints, 0)
        return ints.to_numpy(zero_copy_only=False).astype(
            np.int64, copy=False), valid
    if typ is dt.BOOLEAN:
        filled = col if valid is None else pc.fill_null(col, False)
        return (filled.to_numpy(zero_copy_only=False).astype(
            bool, copy=False), valid)
    filled = col if valid is None else pc.fill_null(col, 0)
    arr = filled.to_numpy(zero_copy_only=False).astype(
        typ.np_dtype, copy=False)
    return arr, valid


def table_to_host(table, schema: Schema
                  ) -> Tuple[Dict[str, np.ndarray], Dict[str, np.ndarray]]:
    data: Dict[str, np.ndarray] = {}
    validity: Dict[str, np.ndarray] = {}
    for name, typ in zip(schema.names, schema.types):
        col = table.column(name)
        if str(col.type).startswith("dictionary"):
            col = col.cast("string")
        data[name], validity[name] = column_to_host(col, typ)
    return data, validity


def empty_host(schema: Schema):
    data, validity = {}, {}
    for name, typ in zip(schema.names, schema.types):
        data[name] = np.array(
            [], dtype=object if typ is dt.STRING else typ.np_dtype)
        validity[name] = np.array([], dtype=bool)
    return data, validity


def concat_host(parts, schema: Schema):
    """Concatenate per-split host dicts in order."""
    parts = [p for p in parts if p is not None]
    if not parts:
        return empty_host(schema)
    data, validity = {}, {}
    for name in schema.names:
        vals = [p[0][name] for p in parts]
        if any(isinstance(v, HostStrings) for v in vals):
            data[name] = HostStrings.concat(
                [v if isinstance(v, HostStrings)
                 else HostStrings.from_objects(v) for v in vals])
        else:
            data[name] = np.concatenate(vals)
        vparts = [p[1][name] for p in parts]
        if all(v is None for v in vparts):
            validity[name] = None
        else:
            validity[name] = np.concatenate(
                [v if v is not None else np.ones(len(d), dtype=bool)
                 for v, d in zip(vparts, vals)])
    return data, validity


def batch_to_arrow(batch, schema: Schema):
    """Device ColumnarBatch -> pyarrow Table (the write path's device ->
    host handoff; ColumnarOutputWriter analogue)."""
    import pyarrow as pa

    n = batch.realized_num_rows()
    arrays = []
    for c, typ in zip(batch.columns, schema.types):
        data, valid = c.to_numpy(n)
        mask = None if valid is None else ~np.asarray(valid, dtype=bool)
        if typ is dt.STRING:
            vals = list(data)
            if mask is not None:
                vals = [None if m else v for v, m in zip(vals, mask)]
            arrays.append(pa.array(vals, type=pa.string()))
        elif typ is dt.DATE:
            arrays.append(pa.array(np.asarray(data, dtype=np.int32),
                                   mask=mask).cast(pa.date32()))
        elif typ is dt.TIMESTAMP:
            arrays.append(pa.array(np.asarray(data, dtype=np.int64),
                                   mask=mask).cast(
                pa.timestamp("us", tz="UTC")))
        else:
            arrays.append(pa.array(np.asarray(data, dtype=typ.np_dtype),
                                   mask=mask, type=dt.to_arrow(typ)))
    return pa.Table.from_arrays(arrays, names=list(schema.names))
