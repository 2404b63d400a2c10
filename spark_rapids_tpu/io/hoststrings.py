"""Dictionary-encoded host string columns.

The scan path used to hand string columns around as numpy object arrays
(one Python ``str`` per row). For a 600k-row TPC-H lineitem scan that
meant two full Python-object passes — ``Array.to_pylist`` and
``np.unique`` over objects — costing ~2s of the 4s scan wall while the
device did 0.5s of work. Arrow already HAS the dictionary encoding the
engine wants (columnar/column.py StringColumn: int32 codes + sorted
dictionary), so the host representation keeps it: codes + dictionary,
produced by arrow's C++ ``dictionary_encode`` with only the (small)
dictionary ever touching Python.

The reference's scan path likewise never materializes row-wise strings:
cuDF keeps device string columns and the plugin copies arrow buffers
straight across (GpuColumnVector / HostColumnarToGpu.scala). This module
is numpy-only so the jax-free CPU oracle may import it.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from spark_rapids_tpu.utils.tracing import TraceRange, count


class HostStrings:
    """One host string column: ``codes`` (int32, one per row; invalid
    rows hold 0) indexing ``dictionary`` (object ndarray of unique
    strings, sorted ascending). Supports ``len`` and slice-indexing so
    the scan/upload path can treat it like the object ndarray it
    replaces. Row validity travels separately (the scan's validity
    dict), exactly as for numeric columns."""

    __slots__ = ("codes", "dictionary")

    def __init__(self, codes: np.ndarray, dictionary: np.ndarray):
        self.codes = np.asarray(codes, dtype=np.int32)
        self.dictionary = np.asarray(dictionary, dtype=object)

    def __len__(self) -> int:
        return len(self.codes)

    def __getitem__(self, sl) -> "HostStrings":
        if not isinstance(sl, slice):
            raise TypeError("HostStrings supports slice indexing only")
        return HostStrings(self.codes[sl], self.dictionary)

    def to_objects(self, validity: Optional[np.ndarray] = None
                   ) -> np.ndarray:
        """Decode to the legacy object-ndarray form (None = null) for
        consumers that want row-wise strings (CPU oracle, UDF rows)."""
        if len(self.dictionary):
            out = self.dictionary[
                np.clip(self.codes, 0, len(self.dictionary) - 1)]
            out = np.asarray(out, dtype=object)
        else:
            out = np.full(len(self.codes), None, dtype=object)
        if validity is not None:
            out = out.copy()
            out[~np.asarray(validity, dtype=bool)] = None
        return out

    @staticmethod
    def from_objects(arr: np.ndarray) -> "HostStrings":
        """Object ndarray (None = null) -> HostStrings. Vectorized
        except for the None scan; used for legacy producers (CSV rows,
        UDF outputs) entering the fast path."""
        arr = np.asarray(arr, dtype=object)
        null = np.array([x is None for x in arr], dtype=bool)
        non_null = arr[~null].astype(str) if (~null).any() \
            else np.array([], dtype=str)
        if len(non_null):
            with TraceRange("strings.dictionary"):
                dictionary, inv = np.unique(non_null, return_inverse=True)
            count("strings.dictionary_values", len(non_null))
        else:
            dictionary = np.array([], dtype=object)
            inv = np.array([], dtype=np.int64)
        codes = np.zeros(len(arr), dtype=np.int32)
        codes[~null] = inv.astype(np.int32)
        return HostStrings(codes, np.asarray(dictionary, dtype=object))

    @staticmethod
    def concat(parts: List["HostStrings"]) -> "HostStrings":
        """Concatenate columns onto ONE merged sorted dictionary (the
        host mirror of columnar.column.unify_dictionaries)."""
        dicts = [p.dictionary.astype(str) for p in parts
                 if len(p.dictionary)]
        if not dicts:
            return HostStrings(
                np.concatenate([p.codes for p in parts])
                if parts else np.array([], dtype=np.int32),
                np.array([], dtype=object))
        values = np.concatenate(dicts)
        with TraceRange("strings.dictionary"):
            merged = np.unique(values)
        count("strings.dictionary_values", len(values))
        out_codes = []
        for p in parts:
            if len(p.dictionary):
                remap = np.searchsorted(
                    merged, p.dictionary.astype(str)).astype(np.int32)
                out_codes.append(remap[p.codes])
            else:
                out_codes.append(p.codes)
        return HostStrings(np.concatenate(out_codes),
                           np.asarray(merged, dtype=object))
