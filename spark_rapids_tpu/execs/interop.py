"""Host <-> device columnar conversions: the transition layer
(GpuRowToColumnarExec.scala / GpuColumnarToRowExec.scala /
HostColumnarToGpu.scala analogues). Host-side data is numpy (+validity);
device side is the bucketed ColumnarBatch."""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import numpy as np

from spark_rapids_tpu.columnar import dtypes as dt
from spark_rapids_tpu.columnar.batch import ColumnarBatch, Schema
from spark_rapids_tpu.columnar.column import Column, StringColumn


# --------------------------------------------------------------------------
# transfer packing: ship fewer bytes through the host->device pipe
#
# A 6M-row TPC-H q1 scan is ~264 MB full-width. The reference
# faces the same wall on the PCIe/network edge and ships nvcomp-
# compressed buffers (GpuCompressedColumnVector, shuffle/spill); a TPU
# cannot LZ4-decode on device, but it CAN widen/gather, so the TPU-native
# compression is structural: string dictionary codes at the dictionary's
# width, integers offset-narrowed to their footer-stat span, cents-exact
# doubles as scaled-decimal integers, validity bitmasks bit-packed 8x.
# One jitted program per batch undoes it all on device (a single extra
# dispatch, only paid when something actually packed).
# --------------------------------------------------------------------------

_PACK_MIN_ROWS = 1 << 16      # below this the decode dispatch isn't worth it
_FDICT_MAX_VALUES = 60_000    # value-table ceiling (u16 codes + slack)


def _narrow_uint(span: float):
    if span < 0 or (isinstance(span, float) and not np.isfinite(span)):
        return None
    if span <= 0xFF:
        return np.uint8
    if span <= 0xFFFF:
        return np.uint16
    if span <= 0xFFFFFFFF:
        return np.uint32
    return None


def _pack_fdict(arr: np.ndarray, v) -> Optional[tuple]:
    """f64 -> (narrow code buf, f64 value table) when the column has few
    distinct values (TPC discount/tax/quantity shapes). Decode is ONE
    table gather — pure data movement, the only bit-exact way to
    reproduce arbitrary f64 on this backend: measured, every TPU f64
    ARITHMETIC op (convert, add, mul, div) rounds at float-float
    ~2^-49 precision, and u64 bitcasts are rejected by the x64
    rewriter, so a fraction like 0.07 (full 52-bit mantissa) can never
    be COMPUTED on device — only moved. The round trip is verified
    bit-exactly host-side before the encoding is chosen (this also
    rejects mixed -0.0/0.0 and multi-payload NaN columns, which a
    value table would collapse)."""
    step = max(1, len(arr) // 16384)
    if len(np.unique(arr[::step][:16384])) > 4096:
        return None
    import pandas as pd  # hash-based factorize: no 6M-row sort

    codes, vals = pd.factorize(arr, use_na_sentinel=False)
    vals = np.asarray(vals, dtype=np.float64)
    if len(vals) > _FDICT_MAX_VALUES:
        return None
    width = _narrow_uint(len(vals) - 1)
    if width is None or width().itemsize >= arr.dtype.itemsize:
        return None
    if not (vals[codes].view(np.uint64) == arr.view(np.uint64)).all():
        return None
    enc = codes.astype(width)
    if v is not None:
        enc[~v] = 0
    return enc, vals


def unpack_arrays(bufs, bases, spec, cap):
    """TRACEABLE decode core shared by the standalone unpack program and
    fused chain programs that inline the decode as their first steps
    (the scan->filter->... stage then starts from the packed buffers
    and pays zero decode dispatch)."""
    return _unpack_program(bufs, bases, spec=spec, cap=cap)


def _unpack_program(bufs, bases, *, spec, cap):
    """One jitted device decode for a whole packed batch: widen + offset
    (ints — exact: integer ops are true 32-bit-pair arithmetic), f64
    value-table gather (exact: data movement), narrow string codes to
    i32, validity bit-unpack. bases ride as traced scalar operands so
    one compilation serves every batch at this (spec, shapes)
    signature. Spec entries carry the column's validity-buffer index
    (or -1) so null slots decode to the dtype's sentinel, preserving
    Column.host_buffer's defense-in-depth normalization, plus the
    value-table buffer index for fdict columns."""
    import jax.numpy as jnp

    def unmask(i):
        mbuf, (mkind, _o, _m, _t) = bufs[i], spec[i]
        if mkind != "bits":
            return mbuf
        shifts = jnp.arange(8, dtype=jnp.uint8)
        bits = (mbuf[:, None] >> shifts[None, :]) & jnp.uint8(1)
        return bits.astype(jnp.bool_).reshape(-1)[:cap]

    outs = []
    for buf, base, (kind, out_name, mi, ti) in zip(bufs, bases, spec):
        if kind == "raw":
            outs.append(buf)
        elif kind == "widen":
            out_dt = np.dtype(out_name)
            out = buf.astype(out_dt) + jnp.asarray(base).astype(out_dt)
            if mi >= 0:
                # integral sentinel is 0 (dtypes.null_sentinel)
                out = jnp.where(unmask(mi), out, jnp.asarray(0, out_dt))
            outs.append(out)
        elif kind == "fdict":
            out = jnp.take(bufs[ti], buf.astype(jnp.int32))
            if mi >= 0:
                out = jnp.where(unmask(mi), out, jnp.float64(jnp.nan))
            outs.append(out)
        elif kind == "codes":
            outs.append(buf.astype(jnp.int32))
        elif kind == "bits":
            outs.append(unmask(len(outs)))
        else:  # pragma: no cover - spec is engine-built
            raise AssertionError(kind)
    return tuple(outs)


_UNPACK_JIT = None


def _get_unpack_jit():
    """The jitted decode, created once (a fresh jax.jit wrapper per call
    would key a fresh trace cache and recompile every batch)."""
    global _UNPACK_JIT
    if _UNPACK_JIT is None:
        import jax

        _UNPACK_JIT = partial(jax.jit,
                              static_argnames=("spec", "cap"))(
            _unpack_program)
    return _UNPACK_JIT


class PackedHost:
    """Host-side result of ``pack_host``: everything needed to upload
    and decode one batch, with NO device interaction yet. Produced on
    scan worker threads so the (pure-CPU) encode overlaps the previous
    batch's transfer and device compute."""

    __slots__ = ("host_bufs", "dec_specs", "dec_bases", "col_specs",
                 "cap", "n")

    def __init__(self, host_bufs, dec_specs, dec_bases, col_specs,
                 cap, n):
        self.host_bufs = host_bufs
        self.dec_specs = dec_specs
        self.dec_bases = dec_bases
        self.col_specs = col_specs
        self.cap = cap
        self.n = n

    def nbytes(self) -> int:
        """Host bytes staged for upload — what a queued slice charges
        the admission budget while it waits in the scan pipeline."""
        return int(sum(b.nbytes for b in self.host_bufs))


class PackedBatch:
    """Device-resident but still PACKED scan batch: the upload happened
    (one device_put) and the decode is deferred into the consumer's own
    compiled program — a fused chain inlines ``unpack_arrays`` as its
    first traced steps, so scan-decode + filter + join + project run as
    ONE dispatch. Only fusion-aware consumers understand this type;
    everything else must call ``decode()`` (one unpack dispatch, the
    exact program the eager path would have run)."""

    __slots__ = ("bufs", "dec_specs", "dec_bases", "col_specs",
                 "capacity", "num_rows", "origin")

    def __init__(self, bufs, dec_specs, dec_bases, col_specs, cap, n):
        self.bufs = list(bufs)
        self.dec_specs = tuple(dec_specs)
        self.dec_bases = tuple(dec_bases)
        self.col_specs = list(col_specs)
        self.capacity = cap
        self.num_rows = n
        self.origin = None

    @property
    def num_columns(self) -> int:
        return len(self.col_specs)

    def realized_num_rows(self) -> int:
        return self.num_rows

    def num_rows_device(self):
        import jax.numpy as jnp

        return jnp.asarray(self.num_rows, dtype=jnp.int32)

    def decode_key(self):
        """Static program-cache key component: which buffer decodes how
        and which decoded slots form each output column."""
        return (self.dec_specs,
                tuple((kind, bi, -1 if vi is None else vi)
                      for kind, bi, vi, _t, _d, _s in self.col_specs),
                self.capacity)

    def ghost_info(self):
        """Per output column (dtype, dictionary, stats) — the host
        mirror a fused chain's ghost walk starts from."""
        return [(typ, dictionary, col_stats)
                for _k, _bi, _vi, typ, dictionary, col_stats
                in self.col_specs]

    def column_arrays(self, decoded):
        """Map decoded flat buffers to per-column (data, validity)
        pairs, in col_specs order."""
        out = []
        for _kind, bi, vi, _typ, _d, _s in self.col_specs:
            out.append((decoded[bi],
                        None if vi is None else decoded[vi]))
        return out

    def decode(self) -> ColumnarBatch:
        """Standalone decode (one dispatch) — the safety valve for any
        consumer that is not fusion-aware."""
        decoded = list(_get_unpack_jit()(
            tuple(self.bufs), tuple(self.dec_bases),
            spec=self.dec_specs, cap=self.capacity))
        b = _wrap_uploaded(decoded, self.col_specs, self.num_rows)
        b.origin = self.origin
        return b


def _wrap_uploaded(uploaded, col_specs, n) -> ColumnarBatch:
    cols = []
    for kind, bi, vi, typ, dictionary, col_stats in col_specs:
        valid = None if vi is None else uploaded[vi]
        if kind == "str":
            cols.append(StringColumn(uploaded[bi], dictionary, valid))
        else:
            cols.append(Column(typ, uploaded[bi], valid,
                               stats=col_stats))
    return ColumnarBatch(cols, n)


def pack_host(data: Dict[str, np.ndarray],
              validity: Dict[str, Optional[np.ndarray]],
              schema: Schema, start: int = 0,
              end: Optional[int] = None,
              stats: Optional[Dict[str, tuple]] = None,
              pack: bool = True) -> PackedHost:
    """Host half of the upload: slice, encode and (when it pays) pack
    every column into flat transfer buffers. Pure CPU work — safe on a
    worker thread, touches no device state."""
    from spark_rapids_tpu.io.hoststrings import HostStrings
    from spark_rapids_tpu.ops.buckets import bucket_capacity

    host_bufs = []   # flat upload list (possibly packed)
    dec_specs = []   # per buf: (kind, out_dtype_name, mask_idx, tbl_idx)
    dec_bases = []   # per buf: traced scalar operand
    specs = []       # (kind, buf_idx, vmask_idx|None, dtype, dict, stats)
    n = None
    cap = None

    def push(buf, kind, out_name, base=0, mi=-1, ti=-1):
        host_bufs.append(buf)
        dec_specs.append((kind, out_name, mi, ti))
        dec_bases.append(base)
        return len(host_bufs) - 1

    def push_vmask(v):
        """Pad + (when packing pays) bit-pack a validity mask."""
        vm = np.zeros(cap, dtype=bool)
        vm[:n] = v
        if do_pack:
            return push(np.packbits(vm, bitorder="little"), "bits", "")
        return push(vm, "raw", "")

    for name, typ in zip(schema.names, schema.types):
        raw = data[name]
        arr = raw if isinstance(raw, HostStrings) else np.asarray(raw)
        v = validity.get(name)
        sl = slice(start, end)
        arr = arr[sl]
        v = None if v is None else np.asarray(v, dtype=bool)[sl]
        if n is None:
            n = len(arr)
            cap = bucket_capacity(n)
            do_pack = pack and n >= _PACK_MIN_ROWS
        if typ is dt.STRING:
            if isinstance(arr, HostStrings):
                # already dictionary-encoded by the scan: pad + upload,
                # zero host passes over row-wise Python strings
                codes_n = np.where(v, arr.codes, 0) \
                    if v is not None else arr.codes
                dictionary = arr.dictionary
                v_eff = v if (v is not None and not v.all()) else None
            else:
                vals = [None
                        if (v is not None and not v[i]) or arr[i] is None
                        else str(arr[i]) for i in range(n)]
                c32, vm32, dictionary = StringColumn.host_codes(vals)
                codes_n = c32[:n]
                # host_codes derives nulls from the None values too —
                # its mask, not the caller's, is authoritative here
                v_eff = vm32[:n] if vm32 is not None else None
            # max code is len(dictionary)-1 (same convention as
            # _pack_fdict), so exactly-256/65536-entry dictionaries
            # still pack as u8/u16
            width = _narrow_uint(max(len(dictionary) - 1, 0)) \
                if do_pack else None
            if width is not None and width().itemsize < 4:
                codes = np.zeros(cap, dtype=width)
                codes[:n] = codes_n.astype(width)
                bi = push(codes, "codes", "")
            else:
                codes = np.zeros(cap, dtype=np.int32)
                codes[:n] = codes_n
                bi = push(codes, "raw", "")
            vi = None
            if v_eff is not None:
                vi = push_vmask(v_eff)
            specs.append(("str", bi, vi, typ, dictionary, None))
        else:
            if arr.dtype.kind == "M":
                unit = np.datetime_data(arr.dtype)[0]
                arr = (arr.astype("datetime64[D]").astype(np.int32)
                       if typ is dt.DATE else
                       arr.astype("datetime64[us]").astype(np.int64))
            arr = arr.astype(typ.np_dtype, copy=False)
            col_stats = None
            if typ.is_integral or typ in (dt.DATE, dt.TIMESTAMP):
                s = stats.get(name) if stats is not None else None
                if s is not None:
                    # footer statistics: free bounds, no host pass
                    col_stats = (int(s[0]), int(s[1]))
                else:
                    # upload-time (min, max): one vectorized host pass
                    # that lets the groupby kernel pick its packed-key
                    # sort lane (Column.stats). Also the per-column
                    # fallback when a footer omitted this column's stats
                    sv = arr if v is None else arr[v]
                    if len(sv):
                        col_stats = (int(sv.min()), int(sv.max()))
            kname = np.dtype(typ.kernel_dtype).name
            # mask first: packed data columns reference it to decode
            # null slots to the dtype sentinel
            vi = push_vmask(v) if v is not None else None
            mi = -1 if vi is None else vi
            bi = None
            if do_pack and col_stats is not None and \
                    typ is not dt.BOOLEAN:
                lo, hi = col_stats
                width = _narrow_uint(hi - lo)
                if width is not None and \
                        width().itemsize < arr.dtype.itemsize:
                    t = arr.astype(np.int64, copy=False) - lo
                    if v is not None:
                        t[~v] = 0  # t is fresh (the subtract allocates)
                    enc = np.zeros(cap, dtype=width)
                    enc[:n] = t.astype(width)
                    bi = push(enc, "widen", kname, base=int(lo), mi=mi)
            if bi is None and do_pack and typ is dt.FLOAT64:
                packed = _pack_fdict(arr, v)
                if packed is not None:
                    encv, table = packed
                    enc = np.zeros(cap, dtype=encv.dtype)
                    enc[:n] = encv
                    # pad the value table to a power-of-two length so
                    # table-size wobble between batches doesn't key a
                    # fresh decode compilation
                    tlen = max(1, len(table))
                    tcap = 1 << (tlen - 1).bit_length()
                    tbuf = np.zeros(tcap, dtype=np.float64)
                    tbuf[:tlen] = table
                    ti = push(tbuf, "raw", kname)
                    bi = push(enc, "fdict", kname, mi=mi, ti=ti)
            if bi is None:
                buf, _vm, typ = Column.host_buffer(arr, typ, v,
                                                   capacity=cap)
                bi = push(buf, "raw", kname)
            specs.append(("num", bi, vi, typ, None, col_stats))

    return PackedHost(host_bufs, tuple(dec_specs), tuple(dec_bases),
                      specs, cap or 0, n or 0)


def upload_packed(packed: PackedHost, defer_decode: bool = False):
    """Device half of the upload: ONE device_put for the whole batch's
    buffers (instead of one transfer per column), then the jitted decode
    — or, with ``defer_decode``, a PackedBatch that hands the decode to
    a fusion-aware consumer's own program (zero decode dispatch)."""
    import jax

    uploaded = jax.device_put(packed.host_bufs)
    if any(s[0] != "raw" for s in packed.dec_specs):
        if defer_decode:
            return PackedBatch(uploaded, packed.dec_specs,
                               packed.dec_bases, packed.col_specs,
                               packed.cap, packed.n)
        uploaded = list(_get_unpack_jit()(
            tuple(uploaded), tuple(packed.dec_bases),
            spec=packed.dec_specs, cap=packed.cap))
    return _wrap_uploaded(uploaded, packed.col_specs, packed.n)


def host_to_batch(data: Dict[str, np.ndarray],
                  validity: Dict[str, Optional[np.ndarray]],
                  schema: Schema, start: int = 0,
                  end: Optional[int] = None,
                  stats: Optional[Dict[str, tuple]] = None,
                  pack: bool = True, defer_decode: bool = False):
    """Upload a row range of host columns (the device-upload half of the
    reference's scan path, GpuParquetScan.scala host buffer ->
    readParquet). ``stats``: footer-derived {col: (min, max)} — when
    provided the upload-time host min/max pass is skipped entirely (the
    footer already paid for those numbers during pruning). ``pack``:
    transfer packing (see module comment above); packed buffers decode
    on device in one jitted program per batch — or inside the consuming
    fused chain's program when ``defer_decode``."""
    return upload_packed(
        pack_host(data, validity, schema, start, end, stats, pack),
        defer_decode=defer_decode)


def frame_to_batch(frame) -> ColumnarBatch:
    """CpuFrame (cpu/engine.py) -> device batch: the HostColumnarToGpu
    boundary when a CPU-fallback subtree feeds a TPU subtree."""
    cols = []
    for c in frame.cols:
        valid = c.valid_mask()
        if c.dtype is dt.STRING:
            vals = [c.data[i] if valid[i] else None
                    for i in range(frame.num_rows)]
            cols.append(StringColumn.from_strings(vals))
        else:
            v = None if c.validity is None else valid
            cols.append(Column.from_numpy(
                np.asarray(c.data, dtype=c.dtype.np_dtype),
                dtype=c.dtype, validity=v))
    return ColumnarBatch(cols, frame.num_rows)


def batch_to_frame(batch: ColumnarBatch, schema: Schema):
    """Device batch -> CpuFrame: the GpuBringBackToHost boundary when a TPU
    subtree feeds a CPU-fallback operator."""
    from spark_rapids_tpu.cpu.engine import CpuFrame
    from spark_rapids_tpu.cpu.evaluator import CV

    n = batch.realized_num_rows()
    cols = []
    for c, typ in zip(batch.columns, schema.types):
        data, validity = c.to_numpy(n)
        if typ is dt.STRING:
            data = np.asarray(data, dtype=object)
            if validity is None:
                validity = np.array([x is not None for x in data],
                                    dtype=bool)
        cols.append(CV(typ, data, validity))
    return CpuFrame(schema, cols, n)
