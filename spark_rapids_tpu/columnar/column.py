"""Device columns: JAX-array-backed columnar vectors with validity.

The TPU-native replacement for ``GpuColumnVector`` over cuDF columns
(sql-plugin/src/main/java/.../GpuColumnVector.java:39). Differences driven by
XLA:

- **Bucketed capacity**: ``data`` always has a power-of-two length >= the
  logical row count (see ops/buckets.py); the row count lives on the owning
  batch. cuDF columns are exact-sized; ours are padded so jitted kernels
  compile a bounded number of shape variants.
- **Validity**: a boolean mask array (True = valid) instead of a packed
  bitmask; XLA fuses mask math into the consuming kernels for free. ``None``
  means all-valid.
- **Strings**: cuDF has native offset+bytes string columns; XLA has no
  ragged type. ``StringColumn`` dictionary-encodes: int32 codes into a
  *sorted* host-side dictionary, making code order == lexicographic order,
  so every relational kernel (sort/join/groupby/compare) stays numeric and
  on-device. Cross-column string ops first unify dictionaries host-side.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.ops.buckets import bucket_capacity


class Scalar:
    """A typed scalar (GpuScalar analogue). ``value`` is a host Python value;
    None means a typed NULL."""

    __slots__ = ("dtype", "value")

    def __init__(self, dtype: dt.DType, value):
        self.dtype = dtype
        self.value = value

    @property
    def is_null(self) -> bool:
        return self.value is None

    def __repr__(self) -> str:  # pragma: no cover
        return f"Scalar({self.dtype}, {self.value})"


class Column:
    """A device column: ``data`` (capacity,) + optional validity mask.

    ``stats`` optionally holds host-known (min, max) value bounds (from
    file footer statistics or an upload-time pass). Kernels use them to
    pick narrow packed-key paths (ops/groupby); transforms drop them —
    they are never propagated through expressions."""

    __slots__ = ("dtype", "data", "validity", "stats")

    def __init__(self, dtype: dt.DType, data: jax.Array,
                 validity: Optional[jax.Array] = None,
                 stats=None):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.stats = stats

    # -- construction -----------------------------------------------------

    @staticmethod
    def host_buffer(values: np.ndarray,
                    dtype: Optional[dt.DType] = None,
                    validity: Optional[np.ndarray] = None,
                    capacity: Optional[int] = None):
        """The host half of from_numpy: (np_buf, np_vmask|None, dtype).
        Callers with many columns batch the buffers into ONE
        device_put."""
        values = np.asarray(values)
        if dtype is None:
            dtype = _infer_dtype(values.dtype)
        n = len(values)
        cap = capacity or bucket_capacity(n)
        kd = dtype.np_dtype
        buf = np.zeros(cap, dtype=kd)
        buf[:n] = values.astype(kd, copy=False)
        vmask = None
        if validity is not None:
            vm = np.zeros(cap, dtype=bool)
            vm[:n] = validity
            # normalize null slots to the sentinel so padded garbage can't
            # leak through kernels that forget to mask (defense in depth)
            buf[:n][~np.asarray(validity, dtype=bool)] = dt.null_sentinel(dtype)
            vmask = vm
        return buf, vmask, dtype

    @staticmethod
    def from_numpy(values: np.ndarray, dtype: Optional[dt.DType] = None,
                   validity: Optional[np.ndarray] = None,
                   capacity: Optional[int] = None) -> "Column":
        buf, vmask, dtype = Column.host_buffer(values, dtype, validity,
                                               capacity)
        return Column(dtype, jnp.asarray(buf),
                      None if vmask is None else jnp.asarray(vmask))

    @staticmethod
    def all_null(dtype: dt.DType, capacity: int) -> "Column":
        data = jnp.zeros(capacity, dtype=dtype.kernel_dtype)
        if dtype is dt.STRING:
            import numpy as _np

            return StringColumn(data.astype(jnp.int32),
                                _np.array([], dtype=object),
                                jnp.zeros(capacity, dtype=bool))
        return Column(dtype, data, jnp.zeros(capacity, dtype=bool))

    @staticmethod
    def from_scalar(scalar: Scalar, capacity: int) -> "Column":
        if scalar.is_null:
            return Column.all_null(scalar.dtype, capacity)
        data = jnp.full(capacity, scalar.value,
                        dtype=scalar.dtype.kernel_dtype)
        return Column(scalar.dtype, data)

    # -- properties -------------------------------------------------------

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def has_nulls_possible(self) -> bool:
        return self.validity is not None

    def device_memory_size(self) -> int:
        """Bytes on device (GpuColumnVector.getTotalDeviceMemoryUsed
        analogue, GpuColumnVector.java:410)."""
        sz = self.capacity * self.dtype.byte_width
        if self.validity is not None:
            sz += self.capacity  # bool mask, 1B/lane
        return sz

    def validity_or_true(self) -> jax.Array:
        if self.validity is None:
            return jnp.ones(self.capacity, dtype=bool)
        return self.validity

    # -- basic transforms (host-orchestrated; heavy lifting in ops/) ------

    def gather(self, indices: jax.Array,
               in_bounds_mask: Optional[jax.Array] = None) -> "Column":
        """Row gather; rows where ``in_bounds_mask`` is False become null."""
        data = jnp.take(self.data, indices, mode="clip")
        validity = None
        if self.validity is not None:
            validity = jnp.take(self.validity, indices, mode="fill",
                                fill_value=False)
        if in_bounds_mask is not None:
            validity = in_bounds_mask if validity is None \
                else (validity & in_bounds_mask)
        return self._like(data, validity)

    def with_capacity(self, new_capacity: int) -> "Column":
        cap = self.capacity
        if new_capacity == cap:
            return self
        if new_capacity < cap:
            data = self.data[:new_capacity]
            validity = None if self.validity is None \
                else self.validity[:new_capacity]
        else:
            pad = new_capacity - cap
            data = jnp.concatenate(
                [self.data, jnp.zeros(pad, dtype=self.data.dtype)])
            validity = None
            if self.validity is not None:
                validity = jnp.concatenate(
                    [self.validity, jnp.zeros(pad, dtype=bool)])
        return self._like(data, validity)

    def _like(self, data, validity) -> "Column":
        """Rebuild preserving subclass payload (dictionary for strings)."""
        if isinstance(self, StringColumn):
            return StringColumn(data, self.dictionary, validity)
        return Column(self.dtype, data, validity)

    # -- host materialization --------------------------------------------

    def to_numpy(self, num_rows: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Returns (values, validity) trimmed to num_rows; validity None if
        all-valid. String columns return an object array of str/None."""
        data, validity = jax.device_get((self.data, self.validity))
        return self._decode_host(data, validity, num_rows)

    def _decode_host(self, data, validity, num_rows: int
                     ) -> Tuple[np.ndarray, Optional[np.ndarray]]:
        """Host-side tail of to_numpy over ALREADY-FETCHED arrays —
        batch.to_pandas prefetches every column in ONE device_get."""
        data = np.asarray(data)[:num_rows]
        if validity is not None:
            validity = np.asarray(validity)[:num_rows]
            if bool(validity.all()):
                validity = None
        return data, validity

    def __repr__(self) -> str:  # pragma: no cover
        return (f"{type(self).__name__}({self.dtype}, cap={self.capacity}, "
                f"nulls={'?' if self.validity is not None else 'no'})")


class StringColumn(Column):
    """Dictionary-encoded string column.

    ``data`` holds int32 codes; ``dictionary`` is a host-side numpy object
    array of unique strings sorted ascending, so ``code_a < code_b`` iff
    ``str_a < str_b`` whenever two columns share a dictionary. This is the
    TPU stand-in for cuDF native string columns (SURVEY.md §7 "Strings").
    """

    # _dict_hashes: per-dictionary-entry content hashes, lazily filled by
    # ops.hashing.dict_hashes (without the slot the cache write silently
    # failed and every join/partition re-hashed the dictionary)
    __slots__ = ("dictionary", "_dict_hashes")

    def __init__(self, codes: jax.Array, dictionary: np.ndarray,
                 validity: Optional[jax.Array] = None):
        super().__init__(dt.STRING, codes, validity)
        self.dictionary = dictionary
        self._dict_hashes = None

    @staticmethod
    def host_codes(values: Sequence[Optional[str]],
                   capacity: Optional[int] = None):
        """Host half of from_strings: (codes_np, vmask_np|None,
        dictionary) for batched uploads."""
        n = len(values)
        cap = capacity or bucket_capacity(n)
        arr = np.asarray(values, dtype=object)
        null_mask = np.array([v is None for v in arr], dtype=bool)
        non_null = arr[~null_mask].astype(str) if (~null_mask).any() \
            else np.array([], dtype=str)
        dictionary, inv = (np.unique(non_null, return_inverse=True)
                           if len(non_null) else
                           (np.array([], dtype=object), np.array([], int)))
        codes = np.zeros(cap, dtype=np.int32)
        codes_valid = np.zeros(n, dtype=np.int32)
        codes_valid[~null_mask] = inv.astype(np.int32)
        codes[:n] = codes_valid
        vmask = None
        if null_mask.any():
            vmask = np.zeros(cap, dtype=bool)
            vmask[:n] = ~null_mask
        return codes, vmask, np.asarray(dictionary, dtype=object)

    @staticmethod
    def from_strings(values: Sequence[Optional[str]],
                     capacity: Optional[int] = None) -> "StringColumn":
        codes, vmask, dictionary = StringColumn.host_codes(values,
                                                           capacity)
        validity = None if vmask is None else jnp.asarray(vmask)
        return StringColumn(jnp.asarray(codes),
                            dictionary.astype(object), validity)

    def _decode_host(self, data, validity, num_rows: int):
        codes, validity = Column._decode_host(self, data, validity,
                                              num_rows)
        if len(self.dictionary):
            out = self.dictionary[np.clip(codes, 0, len(self.dictionary) - 1)]
        else:
            out = np.full(num_rows, None, dtype=object)
        out = np.asarray(out, dtype=object)
        if validity is not None:
            out[~validity] = None
        return out, validity

    def device_memory_size(self) -> int:
        # codes + validity only; dictionary lives host-side
        return super().device_memory_size()


def unify_dictionaries(cols: List[StringColumn]) -> List[StringColumn]:
    """Re-encode string columns onto one shared sorted dictionary.

    Needed before any cross-column string comparison/join/concat/groupby,
    analogous to how the reference re-serializes cuDF string columns for
    cross-batch ops. Host-side merge of (typically small) dictionaries; the
    per-row remap is a device gather.
    """
    if not cols:
        return cols
    merged = np.unique(np.concatenate([c.dictionary.astype(str)
                                       if len(c.dictionary) else
                                       np.array([], dtype=str)
                                       for c in cols]))
    merged_obj = merged.astype(object)
    out = []
    for c in cols:
        if len(c.dictionary) == len(merged) and (
                len(merged) == 0 or bool((c.dictionary == merged_obj).all())):
            out.append(StringColumn(c.data, merged_obj, c.validity))
            continue
        if len(c.dictionary):
            remap = np.searchsorted(merged, c.dictionary.astype(str))
        else:
            remap = np.array([0], dtype=np.int64)  # dummy, codes all masked
        remap_dev = jnp.asarray(remap.astype(np.int32))
        new_codes = jnp.take(remap_dev, c.data, mode="clip")
        out.append(StringColumn(new_codes, merged_obj, c.validity))
    return out


def _infer_dtype(np_dtype) -> dt.DType:
    np_dtype = np.dtype(np_dtype)
    mapping = {
        np.dtype(np.bool_): dt.BOOLEAN,
        np.dtype(np.int8): dt.INT8,
        np.dtype(np.int16): dt.INT16,
        np.dtype(np.int32): dt.INT32,
        np.dtype(np.int64): dt.INT64,
        np.dtype(np.float32): dt.FLOAT32,
        np.dtype(np.float64): dt.FLOAT64,
    }
    if np_dtype in mapping:
        return mapping[np_dtype]
    raise TypeError(f"cannot infer DType from numpy dtype {np_dtype}")
