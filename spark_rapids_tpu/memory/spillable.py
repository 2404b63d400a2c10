"""SpillableBatch: hold a batch logically while letting it spill physically.

Analogue of SpillableColumnarBatch (SpillableColumnarBatch.scala:165): an
operator registers a batch it is not actively computing on, keeps a handle,
and re-acquires (possibly unspilling) when needed. Used by the coalesce
iterator's accumulation list, join build sides, and the shuffle write cache.
"""
from __future__ import annotations

from typing import Optional

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.memory.catalog import BufferCatalog, get_catalog


class SpillableBatch:
    """Context-manager-friendly handle over a catalog-registered batch."""

    def __init__(self, batch: ColumnarBatch, priority: int,
                 catalog: Optional[BufferCatalog] = None,
                 defer_count: bool = False):
        # explicit None-check: BufferCatalog defines __len__, so an EMPTY
        # catalog is falsy and `catalog or get_catalog()` would silently
        # route buffers to the global catalog
        self._catalog = catalog if catalog is not None else get_catalog()
        # row count: realized up front by default (host metadata must
        # survive tier changes — the reference stores it in TableMeta).
        # ``defer_count`` keeps only the 0-d device scalar instead: no
        # host sync on the register path; consumers that truly need the
        # int pay it via the property (and a device->host spill realizes
        # it anyway inside its own sync, serde.batch_to_host)
        if defer_count:
            nr = batch.num_rows
            self._rows: Optional[int] = nr if isinstance(nr, int) \
                else None
            self._rows_dev = None if isinstance(nr, int) else nr
        else:
            self._rows = batch.realized_num_rows()
            self._rows_dev = None
        self._size = batch.device_memory_size()
        self._id = self._catalog.register(batch, priority)
        self._closed = False

    @property
    def num_rows(self) -> int:
        if self._rows is None:
            import jax

            self._rows = int(jax.device_get(self._rows_dev))
            self._rows_dev = None
        return self._rows

    @staticmethod
    def realize_counts(handles: "list[SpillableBatch]") -> None:
        """Realize MANY deferred counts in ONE device_get (each lazy
        ``num_rows`` access would otherwise pay a full round trip)."""
        import jax

        lazy = [sb for sb in handles if sb._rows is None]
        if not lazy:
            return
        vals = jax.device_get([sb._rows_dev for sb in lazy])
        for sb, v in zip(lazy, vals):
            sb._rows = int(v)
            sb._rows_dev = None

    @property
    def buffer_id(self) -> int:
        return self._id

    def device_memory_size(self) -> int:
        return self._size

    def get_batch(self) -> ColumnarBatch:
        """Acquire the batch on device. Caller must call ``release()`` (or
        use ``with spillable.acquired() as b:``) when done computing."""
        return self._catalog.acquire(self._id)

    def release(self) -> None:
        self._catalog.release(self._id)

    def acquired(self):
        return _Acquired(self)

    def update_priority(self, priority: int) -> None:
        self._catalog.update_priority(self._id, priority)

    def close(self) -> bool:
        """Remove the registration; True for the call that did it."""
        if self._closed:
            return False
        self._catalog.remove(self._id)
        self._closed = True
        return True

    def __enter__(self) -> "SpillableBatch":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class _Acquired:
    __slots__ = ("_sb", "_batch")

    def __init__(self, sb: SpillableBatch):
        self._sb = sb

    def __enter__(self) -> ColumnarBatch:
        self._batch = self._sb.get_batch()
        return self._batch

    def __exit__(self, *exc) -> None:
        self._sb.release()
