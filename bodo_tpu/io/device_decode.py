"""Device-side Parquet decode: ship raw pages, decode on-chip.

The host/device inversion the engine applies to relational kernels,
applied to ingest (ROADMAP item 3): instead of pyarrow decoding every
page on host before ``device_put``, the I/O layer ships **raw column
chunk byte ranges** (offsets straight from the cached footer, PR 4) and
jitted XLA programs decode the common encodings directly into padded
device buffers:

  * PLAIN fixed-width (INT32/INT64/FLOAT/DOUBLE): little-endian byte
    assembly via shifts + same-width bitcast,
  * dictionary pages + RLE_DICTIONARY index streams: the host walks the
    RLE/bit-packed hybrid *run headers* (a handful of varints per page),
    the device expands runs and extracts bit-packed values with a
    searchsorted-over-run-starts gather, then maps codes through the
    dictionary (numeric gather on device; string dictionaries stay host
    arrays, codes remap through the sorted-rank LUT exactly like
    ``arrow_bridge``),
  * RLE/bit-packed booleans and PLAIN bit-packed booleans,
  * definition levels -> validity masks, with densely-packed non-null
    values scattered to row positions via a cumsum of the mask.

Exotic encodings (DELTA_BINARY_PACKED, BYTE_STREAM_SPLIT, non-dict
BYTE_ARRAY, INT96, FLBA, nested columns) transparently fall back to the
host pyarrow decode — per column, so one delta-encoded column does not
drag a whole row group back to host.

Work split (who runs where):

  * io_pool worker threads: raw range read, thrift page-header parse,
    per-page host decompression (snappy/gzip/zstd release the GIL in
    arrow), hybrid run-header walk. All O(pages), not O(values).
  * device: everything O(values) — bit unpack, run expansion, byte
    assembly, null scatter, dictionary gather — one jitted program per
    (encoding, dtype, page-shape bucket, page-count bucket, output
    length) cached in ``kernel_cache.DecodeProgramCache``. A column
    chunk's consecutive pages of one kind decode in ONE dispatch: their
    operands stacked along a page axis and sent once, the page body
    under ``vmap``, every page's values written straight to the column's
    slots. Shapes bucket to powers of two (page counts past 16 and value
    slots past 16,384 to four steps an octave) to bound the program
    population (XLA:CPU segfaults after thousands of pinned executables;
    see utils/kernel_cache.py).

Decode kernels are jitted ``jnp`` bodies rather than raw Pallas: the
decode is gather/cumsum/bitwise-bound (no MXU work), XLA lowers it well
on both CPU and TPU backends, and tier-1 runs on the CPU backend where
Pallas needs interpret mode. The bodies are decorated ``fusion_stage``
— they run inside compiled programs where host sync is illegal, and the
shardcheck fusion-host-call lint audits them like any fused stage.

Bit-identical parity with ``arrow_bridge._arrow_column`` is the
contract (tests/test_device_decode.py sweeps every encoding): float
nulls become NaN with no mask, int/bool/timestamp/date nulls become
0/False + mask, string nulls carry raw code 0 *before* the sorted-rank
remap, timestamps scale to ns ticks.
"""

from __future__ import annotations

import os
import struct
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from bodo_tpu.analysis import progcheck
from bodo_tpu.config import config
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import Column, REP, Table, round_capacity
from bodo_tpu.utils import tracing

# ---------------------------------------------------------------------------
# format constants
# ---------------------------------------------------------------------------

# page types (parquet.thrift PageType)
_DATA_PAGE, _INDEX_PAGE, _DICT_PAGE, _DATA_PAGE_V2 = 0, 1, 2, 3
# encodings (parquet.thrift Encoding)
_PLAIN = 0
_PLAIN_DICTIONARY = 2
_RLE = 3
_BIT_PACKED = 4
_DELTA_BINARY_PACKED = 5
_DELTA_LENGTH_BYTE_ARRAY = 6
_DELTA_BYTE_ARRAY = 7
_RLE_DICTIONARY = 8
_BYTE_STREAM_SPLIT = 9

_DICT_ENCODINGS = (_PLAIN_DICTIONARY, _RLE_DICTIONARY)

# physical type -> (itemsize, assembled uint dtype)
_PHYS_WIDTH = {"INT32": 4, "INT64": 8, "FLOAT": 4, "DOUBLE": 8}

_MAX_BITWIDTH = 24  # 4-byte gather window in the bit extractor


class Unsupported(Exception):
    """Internal control flow: this chunk/page/file cannot decode on
    device — the caller falls back to the host pyarrow path. Never
    escapes this module."""


# ---------------------------------------------------------------------------
# thrift compact protocol (page headers only)
# ---------------------------------------------------------------------------
# Page headers are tiny (tens of bytes) TCompactProtocol structs; a
# minimal pure-python reader keeps the raw-page path dependency-free.
# Only the fields the decoder routes on are kept; everything else
# (statistics, crc, bloom offsets) is skipped structurally.

_CT_STOP = 0
_CT_TRUE, _CT_FALSE = 1, 2
_CT_BYTE, _CT_I16, _CT_I32, _CT_I64 = 3, 4, 5, 6
_CT_DOUBLE, _CT_BINARY, _CT_LIST, _CT_SET, _CT_MAP, _CT_STRUCT = \
    7, 8, 9, 10, 11, 12


def _uvarint(buf: bytes, off: int):
    out = shift = 0
    while True:
        b = buf[off]
        off += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, off
        shift += 7
        if shift > 63:
            raise Unsupported("varint overflow in page header")


def _zigzag(v: int) -> int:
    return (v >> 1) ^ -(v & 1)


def _skip_field(buf: bytes, off: int, ftype: int) -> int:
    if ftype in (_CT_TRUE, _CT_FALSE):
        return off
    if ftype == _CT_BYTE:
        return off + 1
    if ftype in (_CT_I16, _CT_I32, _CT_I64):
        return _uvarint(buf, off)[1]
    if ftype == _CT_DOUBLE:
        return off + 8
    if ftype == _CT_BINARY:
        n, off = _uvarint(buf, off)
        return off + n
    if ftype == _CT_STRUCT:
        return _skip_struct(buf, off)
    if ftype in (_CT_LIST, _CT_SET):
        head = buf[off]
        off += 1
        n = head >> 4
        if n == 15:
            n, off = _uvarint(buf, off)
        et = head & 0x0F
        for _ in range(n):
            off = _skip_field(buf, off, et)
        return off
    if ftype == _CT_MAP:
        n, off = _uvarint(buf, off)
        if n:
            kt, vt = buf[off] >> 4, buf[off] & 0x0F
            off += 1
            for _ in range(n):
                off = _skip_field(buf, off, kt)
                off = _skip_field(buf, off, vt)
        return off
    raise Unsupported(f"thrift compact type {ftype}")


def _field_header(buf: bytes, off: int, fid: int):
    """Read one compact-protocol field header. Returns
    (fid, ftype, off, stop)."""
    head = buf[off]
    off += 1
    if head == _CT_STOP:
        return fid, _CT_STOP, off, True
    delta = head >> 4
    ftype = head & 0x0F
    if delta:
        fid += delta
    else:
        z, off = _uvarint(buf, off)
        fid = _zigzag(z)
    return fid, ftype, off, False


def _skip_struct(buf: bytes, off: int) -> int:
    fid = 0
    while True:
        fid, ftype, off, stop = _field_header(buf, off, fid)
        if stop:
            return off
        off = _skip_field(buf, off, ftype)


@dataclass
class _PageHeader:
    type: int
    uncompressed_size: int
    compressed_size: int
    num_values: int = 0
    encoding: int = _PLAIN
    def_level_encoding: int = _RLE
    # DataPageHeaderV2 extras
    num_nulls: int = -1           # v2 records it; v1 = -1 (unknown)
    def_levels_byte_len: int = 0  # v2: uncompressed levels at page front
    v2_compressed: bool = True
    header_len: int = 0           # bytes consumed by the thrift header


def _parse_sub(buf, off, hdr, *, v2: bool) -> int:
    """DataPageHeader / DataPageHeaderV2 / DictionaryPageHeader."""
    fid = 0
    while True:
        fid, ftype, off, stop = _field_header(buf, off, fid)
        if stop:
            return off
        if ftype in (_CT_I16, _CT_I32, _CT_I64):
            z, off = _uvarint(buf, off)
            val = _zigzag(z)
        elif ftype in (_CT_TRUE, _CT_FALSE):
            val = ftype == _CT_TRUE
        else:
            off = _skip_field(buf, off, ftype)
            continue
        if fid == 1:
            hdr.num_values = val
        elif not v2:
            if fid == 2:
                hdr.encoding = val
            elif fid == 3:
                hdr.def_level_encoding = val
        else:
            if fid == 2:
                hdr.num_nulls = val
            elif fid == 4:
                hdr.encoding = val
            elif fid == 5:
                hdr.def_levels_byte_len = val
            elif fid == 6 and val != 0:
                raise Unsupported("repetition levels in v2 page")
            elif fid == 7:
                hdr.v2_compressed = bool(val)


def _parse_page_header(buf: bytes, off: int) -> _PageHeader:
    start = off
    hdr = _PageHeader(type=-1, uncompressed_size=0, compressed_size=0)
    fid = 0
    while True:
        fid, ftype, off, stop = _field_header(buf, off, fid)
        if stop:
            break
        if ftype in (_CT_I16, _CT_I32, _CT_I64):
            z, off = _uvarint(buf, off)
            val = _zigzag(z)
            if fid == 1:
                hdr.type = val
            elif fid == 2:
                hdr.uncompressed_size = val
            elif fid == 3:
                hdr.compressed_size = val
        elif ftype == _CT_STRUCT and fid in (5, 7):
            off = _parse_sub(buf, off, hdr, v2=False)
        elif ftype == _CT_STRUCT and fid == 8:
            hdr.v2_compressed = True
            off = _parse_sub(buf, off, hdr, v2=True)
        elif ftype in (_CT_TRUE, _CT_FALSE):
            pass
        else:
            off = _skip_field(buf, off, ftype)
    if hdr.type < 0 or hdr.compressed_size < 0:
        raise Unsupported("malformed page header")
    hdr.header_len = off - start
    return hdr


# ---------------------------------------------------------------------------
# decompression (host, per page — arrow codecs release the GIL)
# ---------------------------------------------------------------------------

_codec_cache: dict = {}
_codec_lock = threading.Lock()


def _codec(name: str):
    name = (name or "UNCOMPRESSED").lower()
    if name == "uncompressed":
        return None
    # parquet "LZ4" is the raw block format in every modern writer (the
    # frame-format legacy is what got LZ4 deprecated in the spec);
    # pa.Codec("lz4") is the FRAME codec, so map to lz4_raw. A true
    # legacy frame file fails decompress -> Unsupported -> host decode.
    if name == "lz4":
        name = "lz4_raw"
    with _codec_lock:
        c = _codec_cache.get(name)
    if c is None:
        import pyarrow as pa
        try:
            c = pa.Codec(name)
        except Exception as e:
            raise Unsupported(f"codec {name}: {e}") from e
        with _codec_lock:
            _codec_cache[name] = c
    return c


def _decompress(codec, raw: bytes, out_size: int) -> bytes:
    if codec is None:
        return raw
    try:
        return codec.decompress(raw,
                                decompressed_size=out_size).to_pybytes()
    except Exception as e:
        # wrong codec flavor / malformed page: demote to host decode,
        # which re-reads from the file through pyarrow (true corruption
        # still surfaces there as a real error)
        raise Unsupported(f"decompress: {e}") from e


# ---------------------------------------------------------------------------
# RLE / bit-packed hybrid: host run-header walk -> device run tables
# ---------------------------------------------------------------------------

@dataclass
class _RunTable:
    """Host-parsed hybrid runs. ``starts[i]`` is the output index where
    run i begins; RLE runs carry ``vals[i]``, bit-packed runs carry the
    absolute bit offset ``bits[i]`` of their first value in the page."""
    starts: np.ndarray   # int32 [n_runs]
    is_rle: np.ndarray   # bool  [n_runs]
    vals: np.ndarray     # int32 [n_runs]
    bits: np.ndarray     # int32 [n_runs]


def _parse_hybrid(buf: bytes, off: int, end: int, bw: int,
                  n: int, exact: bool = True) -> _RunTable:
    """Walk RLE/bit-packed hybrid run headers in buf[off:end] until n
    output values are covered. O(runs), not O(values) — the value work
    happens on device. ``exact=False`` tolerates a stream that ends
    early: dictionary-index and RLE-bool value streams store only the
    NON-NULL entries, so ``n`` (the page's row count) is an upper bound
    there and the stream simply runs out at the stored count."""
    starts: List[int] = []
    is_rle: List[int] = []
    vals: List[int] = []
    bits: List[int] = []
    vbw = (bw + 7) // 8
    count = 0
    while count < n:
        if off >= end:
            if exact:
                raise Unsupported("hybrid run stream truncated")
            break
        header, off = _uvarint(buf, off)
        if header & 1:  # bit-packed: (header >> 1) groups of 8 values
            groups = header >> 1
            if groups <= 0:
                raise Unsupported("empty bit-packed run")
            starts.append(count)
            is_rle.append(False)
            vals.append(0)
            bits.append(off * 8)
            off += groups * bw
            count += groups * 8
        else:  # RLE run: value in ceil(bw/8) LE bytes
            run = header >> 1
            if run <= 0:
                raise Unsupported("empty RLE run")
            v = int.from_bytes(buf[off:off + vbw], "little") if vbw else 0
            off += vbw
            starts.append(count)
            is_rle.append(True)
            vals.append(v)
            bits.append(0)
            count += run
        if off > end:
            raise Unsupported("hybrid run overruns page")
    return _RunTable(np.asarray(starts, np.int32),
                     np.asarray(is_rle, bool),
                     np.asarray(vals, np.int32),
                     np.asarray(bits, np.int32))


def _bucket(n: int, lo: int = 16) -> int:
    """Next power of two >= max(n, lo) — the shape-bucketing that keeps
    the decode-program population bounded."""
    b = lo
    while b < n:
        b <<= 1
    return b


# ---------------------------------------------------------------------------
# jitted decode programs (cached per shape/encoding/dtype bucket)
# ---------------------------------------------------------------------------

from bodo_tpu.runtime import xla_observatory as xobs  # noqa: E402
from bodo_tpu.utils.kernel_cache import DecodeProgramCache  # noqa: E402


def _describe_spec(spec):
    """Facet split of a _PageSpec for the program registry: shape
    buckets are the churn-prone facet (a drifting page size shows up
    as shape-bucket-churn in retrace attribution)."""
    return f"device_decode:{spec.kind}", {
        "dtype": spec.out_dtype,
        "shape": (spec.byte_bucket, spec.n_bucket, spec.def_runs,
                  spec.val_runs, spec.dict_bucket, spec.n_pages, spec.out_len),
        "static": (spec.itemsize, spec.bit_width, spec.has_defs,
                   spec.masked, spec.scale, spec.want_valid,
                   spec.accumulate)}


_programs = DecodeProgramCache(subsystem="device_decode",
                               describe=_describe_spec)
_programs_lock = threading.Lock()

# XLA:CPU's JIT crashes once a process pins thousands of distinct
# executables (same failure mode the fusion compile budget guards).
# Decode programs draw from that pool too — shape bucketing keeps the
# signature count small in real scans, but a full single-process test
# run reads hundreds of tiny files with drifting shapes, so new-spec
# compiles stop after a process-wide budget; later pages decode on the
# host, which is always correct. <0 disables the budget.
_max_compiles = int(os.environ.get(
    "BODO_TPU_DEVICE_DECODE_MAX_COMPILES", "64"))
_n_compiles = 0


def decode_program_stats() -> dict:
    out = _programs.stats()
    left_local = (max(0, _max_compiles - _n_compiles)
                  if _max_compiles >= 0 else -1)
    left_pool = xobs.subsystem_budget_left("device_decode")
    lefts = [x for x in (left_local, left_pool) if x >= 0]
    out["budget_left"] = min(lefts) if lefts else -1
    return out


def clear_programs() -> None:
    """Drop every cached decode program and return the compile budget:
    releasing the program references is what frees the executables, so
    a caller starting clean gets the full budget back."""
    global _n_compiles
    with _programs_lock:
        _programs.clear()
        _n_compiles = 0
    xobs.reset_budget("device_decode")


@dataclass(frozen=True)
class _PageSpec:
    """Static configuration of one jitted decode program for a run of
    consecutive same-kind pages of a column chunk — the decode-program
    cache key (encoding kind, output dtype, the element-wise largest of
    the run's pages' power-of-two shape buckets, the page-count bucket
    and the output length)."""
    kind: str            # 'plain' | 'dict' | 'boolplain' | 'boolrle'
    out_dtype: str       # numpy dtype name of the decoded values
    itemsize: int        # physical width for 'plain' (0 otherwise)
    bit_width: int       # widest bit width the run's gather window holds
    has_defs: bool       # definition levels present (optional column)
    masked: bool         # produce a validity mask + null scatter
    byte_bucket: int     # padded page-byte length
    n_bucket: int        # padded output value count of one page
    def_runs: int        # padded def-level run count
    val_runs: int        # padded value-stream run count (hybrid kinds)
    dict_bucket: int     # padded dictionary length (numeric dict gather)
    scale: int           # timestamp unit -> ns multiplier (1 otherwise)
    n_pages: int         # page-count bucket of the run
    out_len: int         # output length: the capacity's power of two
    want_valid: bool     # return the validity mask beside the values
    accumulate: bool     # take the chunk's earlier runs' output as input


def _hybrid_expand_body(jnp, data, starts, is_rle, vals, bits, bw,
                        n_bucket, width=None):
    """Device run expansion: RLE runs broadcast their value, bit-packed
    runs extract bw bits at bits[run] + (i - start)*bw through a
    little-endian gather window just wide enough for the bit width.
    ``width``, where given, is the page's own traced bit width and ``bw``
    the largest in its run, which sizes the window: a dictionary's index
    width grows with the dictionary from page to page of one chunk.

    The output-index -> owning-run map exploits that the output domain
    is SORTED: scatter each run's index at its start position and take a
    running max. XLA:CPU lowers the obvious `searchsorted` to a
    per-element binary search (~60% of warm page-decode time at 256k
    values); the scatter+cummax is one cheap scan. Sentinel-padded runs
    scatter out of range and drop; duplicate starts (empty runs) resolve
    to the later run, matching searchsorted's 'right' side."""
    from jax import lax
    i = jnp.arange(n_bucket, dtype=jnp.int32)
    n_runs = starts.shape[0]
    r = lax.cummax(
        jnp.zeros(n_bucket, jnp.int32).at[starts].max(
            jnp.arange(n_runs, dtype=jnp.int32), mode="drop"))
    # per-run fields folded so the expansion gathers TWO run-table
    # columns, not four (each n_bucket-sized gather is ~0.35ms on the
    # CPU fallback): base = the run's bit offset rebased to i=0, and
    # rv = the RLE value or -1 for bit-packed runs (values are always
    # non-negative, so -1 is a free "take the unpacked bits" sentinel)
    rv = jnp.where(is_rle, vals, -1)[r]
    if bw > 0:
        pw = bw if width is None else width
        bp = (bits - starts * pw)[r] + i * pw
        byte0 = bp >> 3
        nb = data.shape[0]
        # ceil((7 + bw) / 8) bytes cover any bit phase: 1 byte for the
        # def-level/bool bw=1 case, 2 for dict indexes up to 9 bits
        w = data[jnp.clip(byte0, 0, nb - 1)].astype(jnp.uint32)
        for k in range(1, (bw + 14) // 8):
            w = w | (data[jnp.clip(byte0 + k, 0, nb - 1)]
                     .astype(jnp.uint32) << (8 * k))
        mask = (1 << bw) - 1 if width is None else \
            (jnp.uint32(1) << pw.astype(jnp.uint32)) - 1
        packed = ((w >> (bp & 7).astype(jnp.uint32))
                  & mask).astype(jnp.int32)
    else:
        packed = jnp.zeros(n_bucket, jnp.int32)
    return jnp.where(rv >= 0, rv, packed)


def _assemble_plain_body(jnp, lax, data, val_off, itemsize, out_dtype,
                         n_bucket):
    """PLAIN fixed-width: dynamic-slice the dense value region, assemble
    little-endian uints via shifts, bitcast to the physical dtype."""
    window = lax.dynamic_slice(data, (val_off,), (n_bucket * itemsize,))
    b = window.reshape(n_bucket, itemsize)
    if itemsize == 4:
        u = (b[:, 0].astype(jnp.uint32)
             | (b[:, 1].astype(jnp.uint32) << 8)
             | (b[:, 2].astype(jnp.uint32) << 16)
             | (b[:, 3].astype(jnp.uint32) << 24))
        phys = {"int32": jnp.int32, "uint32": jnp.uint32,
                "float32": jnp.float32}
    else:
        u = b[:, 0].astype(jnp.uint64)
        for k in range(1, 8):
            u = u | (b[:, k].astype(jnp.uint64) << (8 * k))
        phys = {"int64": jnp.int64, "uint64": jnp.uint64,
                "float64": jnp.float64}
    target = phys.get(out_dtype)
    if target is None:
        # narrow logical ints (int8/16, uint8/16) ride in INT32
        base = jnp.int32 if itemsize == 4 else jnp.int64
        return lax.bitcast_convert_type(u, base).astype(out_dtype)
    return lax.bitcast_convert_type(u, target)


def _build_page_program(spec: _PageSpec):
    """One jitted program decoding a run of pages: the page body
    (def-level expansion, value decode, null scatter, dtype conversion)
    under ``vmap`` over the stacked pages, then every page's live values
    written to its slots of the column — a single dispatch per run, no
    host round-trip. Traced-body rules apply (fusion_stage)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bodo_tpu.plan.fusion import fusion_stage

    out_np = np.dtype(spec.out_dtype)
    fill_nan = out_np.kind == "f"
    has_vruns = spec.kind in _VAL_RUN_KINDS

    @fusion_stage
    def _one_page(data, n_values, druns, vruns, val_off, bit_width,
                  dictvals):
        i = jnp.arange(spec.n_bucket, dtype=jnp.int32)
        in_rows = i < n_values
        if spec.has_defs:
            levels = _hybrid_expand_body(
                jnp, data, druns[0], druns[1] > 0, druns[2], druns[3], 1,
                spec.n_bucket)
            valid = (levels == 1) & in_rows
        else:
            valid = in_rows
        # densely-packed non-null values: row i reads packed slot
        # cumsum(valid)-1 (identity when no nulls)
        if spec.masked or spec.has_defs:
            pos = jnp.cumsum(valid.astype(jnp.int32)) - 1
            pos = jnp.clip(pos, 0, spec.n_bucket - 1)
        else:
            pos = i
        if spec.kind == "plain":
            dense = _assemble_plain_body(jnp, lax, data, val_off,
                                         spec.itemsize, spec.out_dtype,
                                         spec.n_bucket)
            vals_at = dense[pos]
        elif spec.kind == "dict":
            codes = _hybrid_expand_body(
                jnp, data, vruns[0], vruns[1] > 0, vruns[2], vruns[3],
                spec.bit_width, spec.n_bucket, width=bit_width)
            codes = codes[pos]
            # null rows carry raw code 0 (matches arrow_bridge's NaN->0
            # before the rank remap)
            codes = jnp.where(valid, codes, 0)
            if spec.dict_bucket:
                vals_at = dictvals[
                    jnp.clip(codes, 0, spec.dict_bucket - 1)]
            else:
                vals_at = codes.astype(jnp.int32)
        elif spec.kind == "boolplain":
            bits_i = val_off.astype(jnp.int32) * 8 + pos
            byte0 = bits_i >> 3
            nb = data.shape[0]
            vals_at = ((data[jnp.clip(byte0, 0, nb - 1)]
                        >> (bits_i & 7).astype(jnp.uint8)) & 1) > 0
        elif spec.kind == "boolrle":
            dense = _hybrid_expand_body(
                jnp, data, vruns[0], vruns[1] > 0, vruns[2], vruns[3], 1,
                spec.n_bucket)
            vals_at = dense[pos] > 0
        else:  # pragma: no cover - spec construction guards this
            raise AssertionError(spec.kind)
        if spec.scale != 1:
            vals_at = vals_at * spec.scale
        vals_at = vals_at.astype(out_np)
        if fill_nan:
            # float nulls: NaN carries the null inside the row range,
            # zeros pad beyond it (mirrors _pad + NaN densification)
            out = jnp.where(valid, vals_at, jnp.asarray(np.nan, out_np))
            out = jnp.where(in_rows, out, jnp.zeros((), out_np))
        else:
            out = jnp.where(valid, vals_at, jnp.zeros((), out_np))
        n_nulls = jnp.sum(in_rows & ~valid).astype(jnp.int32)
        return out, valid, n_nulls

    @fusion_stage
    def _page_decode(data, meta, *rest):
        # operands: stacked page bytes [P, B], per-page (n_values,
        # val_off, bit_width, first output slot) [P, 4], then the stacked
        # run tables [P, 4, R] the kind reads, the chunk's dictionary,
        # and the earlier runs' (values, nulls[, validity])
        rest = list(rest)
        druns = rest.pop(0) if spec.has_defs else None
        vruns = rest.pop(0) if has_vruns else None
        dictvals = rest.pop(0) if spec.dict_bucket else None
        vals, valid, n_nulls = jax.vmap(
            lambda d, m, dr, vr: _one_page(d, m[0], dr, vr, m[1], m[2],
                                           dictvals),
            in_axes=(0, 0, 0 if druns is not None else None,
                     0 if vruns is not None else None))(
            data, meta, druns, vruns)
        # compaction: page p's value i lands in slot meta[p, 3] + i. A
        # page's n_bucket-wide write spills its zero padding past its
        # rows, which the next page's write covers; the buffer runs
        # n_bucket past the output so no write is ever clamped, and
        # padded pages (n_values 0) write zeros at the run's end
        if spec.accumulate:
            acc_vals, acc_nulls = rest[0], rest[1]
        else:
            acc_vals = jnp.zeros(spec.out_len, out_np)
            acc_nulls = jnp.zeros((), jnp.int32)
        tail = jnp.zeros(spec.n_bucket, out_np)
        bufs = [jnp.concatenate([acc_vals, tail])]
        pages = [vals]
        if spec.want_valid:
            acc_valid = rest[2] if spec.accumulate else \
                jnp.zeros(spec.out_len, bool)
            bufs.append(jnp.concatenate(
                [acc_valid, jnp.zeros(spec.n_bucket, bool)]))
            pages.append(valid)

        def put(p, bufs):
            return [lax.dynamic_update_slice(b, x[p], (meta[p, 3],))
                    for b, x in zip(bufs, pages)]

        bufs = lax.fori_loop(0, spec.n_pages, put, bufs)
        # (values, nulls[, validity]): the order a later run takes them in
        return ((bufs[0][:spec.out_len], acc_nulls + jnp.sum(n_nulls))
                + tuple(b[:spec.out_len] for b in bufs[1:]))

    # stored into _programs (DecodeProgramCache) by _page_program
    # under its lock  # shardcheck: ignore[unregistered-jit]
    return jax.jit(_page_decode)


def _page_program(spec: _PageSpec):
    global _n_compiles
    with _programs_lock:
        fn = _programs.lookup(spec)
        if fn is None:
            if _n_compiles >= _max_compiles >= 0 \
                    or not xobs.try_spend("device_decode"):
                raise Unsupported("decode compile budget spent")
            _n_compiles += 1
    if fn is not None:
        return fn, False
    fn = _build_page_program(spec)
    with _programs_lock:
        _programs[spec] = fn
    return fn, True


# bucket floors of the small operands: run tables and a numeric
# dictionary cost little device work past their live entries (a scatter
# of the run starts, a gather from the table), so a high floor lets one
# program serve files whose null patterns and dictionary sizes differ
_RUNS_FLOOR = 64
_DICT_FLOOR = 4096
_VAL_RUN_KINDS = ("dict", "boolrle")


def _fine_bucket(n: int, lo: int, fine_from: int) -> int:
    """Powers of two up to ``fine_from``, then four steps an octave: for
    the sizes whose padding the device decodes, a run's page count (53
    pages -> 56) and a page's value slots (20,000 -> 20,480). Past
    ``fine_from`` the padding is device work worth saving, and rounding
    up by at most a quarter keeps it small; below, one program serves
    twice the sizes. The population stays logarithmic either way."""
    b = _bucket(n, lo)
    if b <= fine_from:
        return b
    step = b >> 3
    return -(-n // step) * step


def _stack_runs(tables, n_pages: int, bucket: int,
                sentinel: int) -> np.ndarray:
    """Stack pages' run tables as int32 [n_pages, 4, bucket] rows
    (starts, is_rle, vals, bits); sentinel starts never win the
    run lookup, so padded runs and padded pages are inert."""
    out = np.zeros((n_pages, 4, bucket), np.int32)
    out[:, 0, :] = sentinel
    for p, rt in enumerate(tables):
        if rt is None:
            continue
        k = len(rt.starts)
        out[p, 0, :k] = rt.starts
        out[p, 1, :k] = rt.is_rle
        out[p, 2, :k] = rt.vals
        out[p, 3, :k] = rt.bits
    return out


def _run_page_program(spec: _PageSpec, pages: Sequence["_Page"],
                      first_slot: int, dict_dev, acc: tuple) -> tuple:
    """Dispatch one run of pages through its cached program: stack each
    operand along a leading page axis on the host, one transfer an
    operand. Returns device (values[out_len], nulls[, validity[out_len]])
    after the chunk's runs so far."""
    n = len(pages)
    data = np.zeros((spec.n_pages, spec.byte_bucket), np.uint8)
    meta = np.zeros((spec.n_pages, 4), np.int32)
    slot = first_slot
    for p, pg in enumerate(pages):
        data[p, :len(pg.data)] = np.frombuffer(pg.data, np.uint8)
        meta[p] = (pg.num_values, pg.val_off, pg.bit_width, slot)
        slot += pg.num_values
    meta[n:, 3] = slot
    sentinel = spec.n_bucket + 1
    args_in = [data, meta]
    if spec.has_defs:
        args_in.append(_stack_runs([pg.def_runs for pg in pages],
                                   spec.n_pages, spec.def_runs, sentinel))
    if spec.kind in _VAL_RUN_KINDS:
        args_in.append(_stack_runs([pg.val_runs for pg in pages],
                                   spec.n_pages, spec.val_runs, sentinel))
    if spec.dict_bucket:
        args_in.append(dict_dev)
    args_in.extend(acc)
    fn, compiled = _page_program(spec)
    t0 = time.perf_counter()
    out = fn(*args_in)
    if compiled:
        h = _programs.handle_for(spec)
        progcheck.check_jit(fn, tuple(args_in),
                            program=f"device_decode:{spec.kind}",
                            subsystem="device_decode", obs_handle=h)
        progcheck.mark_checked(h)
        with _programs_lock:
            _programs.record_compile(f"device_decode:{spec.kind}",
                                     time.perf_counter() - t0,
                                     handle=_programs.handle_for(spec))
    for x in (out[0],) + out[2:]:
        xobs.track_buffer(x, "device_decode")
    return out


# ---------------------------------------------------------------------------
# chunk planning (footer + arrow schema -> device route or fallback)
# ---------------------------------------------------------------------------

@dataclass
class _ColPlan:
    """Per-column decode plan derived from footer metadata alone (no
    data bytes touched yet)."""
    name: str
    leaf: int                 # leaf column index in the parquet schema
    phys: str                 # physical type
    codec_name: str
    max_def: int
    num_values: int
    start: int                # chunk byte range [start, start+size)
    size: int
    null_count: Optional[int]  # from chunk statistics (None = unknown)
    out_dtype: str            # numpy dtype of decoded values
    col_dtype: dt.DType       # logical table dtype
    scale: int = 1            # timestamp -> ns multiplier
    is_string: bool = False


def _arrow_out(field_type, phys: str):
    """Map an arrow field type to (np dtype name, table DType, ns scale,
    is_string) or raise Unsupported. Mirrors _arrow_column exactly."""
    import pyarrow as pa
    t = field_type
    if pa.types.is_dictionary(t):
        t = t.value_type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        if phys != "BYTE_ARRAY":
            raise Unsupported(f"string stored as {phys}")
        return "int32", dt.STRING, 1, True
    if phys == "BYTE_ARRAY":
        raise Unsupported("non-string BYTE_ARRAY")
    if pa.types.is_timestamp(t):
        scale = {"ns": 1, "us": 1000, "ms": 1_000_000,
                 "s": 1_000_000_000}.get(t.unit)
        if scale is None or phys != "INT64":
            raise Unsupported(f"timestamp unit {t.unit} phys {phys}")
        return "int64", dt.DATETIME, scale, False
    if pa.types.is_date32(t):
        return "int32", dt.DATE, 1, False
    if pa.types.is_boolean(t):
        return "bool", dt.BOOL, 1, False
    if pa.types.is_integer(t) or pa.types.is_floating(t):
        np_name = t.to_pandas_dtype().__name__
        return np_name, dt.from_numpy(np.dtype(np_name)), 1, False
    raise Unsupported(f"arrow type {t}")


def _plan_chunk(md, arrow_schema, rg: int, name: str) -> _ColPlan:
    """Decide whether one column chunk can decode on device; raises
    Unsupported to route it to the host fallback."""
    schema = md.schema
    leaf = None
    for i in range(md.num_columns):
        if schema.column(i).path == name:
            leaf = i
            break
    if leaf is None:
        raise Unsupported(f"no flat leaf for column {name!r} (nested?)")
    cs = schema.column(leaf)
    if cs.max_repetition_level > 0:
        raise Unsupported("repeated (nested) column")
    if cs.max_definition_level > 1:
        raise Unsupported("definition depth > 1 (nested optional)")
    col = md.row_group(rg).column(leaf)
    phys = col.physical_type
    if phys not in ("INT32", "INT64", "FLOAT", "DOUBLE", "BOOLEAN",
                    "BYTE_ARRAY"):
        raise Unsupported(f"physical type {phys}")
    for enc in col.encodings:
        if enc in ("DELTA_BINARY_PACKED", "DELTA_LENGTH_BYTE_ARRAY",
                   "DELTA_BYTE_ARRAY", "BYTE_STREAM_SPLIT"):
            raise Unsupported(f"encoding {enc}")
    try:
        field_type = arrow_schema.field(name).type
    except KeyError as e:
        raise Unsupported(f"no arrow field for {name!r}") from e
    out_dtype, col_dtype, scale, is_str = _arrow_out(field_type, phys)
    _codec(col.compression)  # raises Unsupported for unavailable codecs
    dpo = col.dictionary_page_offset
    start = col.data_page_offset
    if dpo is not None and 0 < dpo < start:
        start = dpo
    stats = col.statistics
    null_count = None
    if stats is not None and stats.has_null_count:
        null_count = int(stats.null_count)
    return _ColPlan(name=name, leaf=leaf, phys=phys,
                    codec_name=col.compression,
                    max_def=cs.max_definition_level,
                    num_values=col.num_values, start=start,
                    size=col.total_compressed_size,
                    null_count=null_count, out_dtype=out_dtype,
                    col_dtype=col_dtype, scale=scale, is_string=is_str)


# ---------------------------------------------------------------------------
# raw bundles: what the io_pool ships (bytes + parsed page descriptors)
# ---------------------------------------------------------------------------

@dataclass
class _Page:
    kind: str                 # 'plain' | 'dict' | 'boolplain' | 'boolrle'
    num_values: int
    data: bytes               # decompressed page payload
    def_runs: Optional[_RunTable]
    val_runs: Optional[_RunTable]
    val_off: int              # byte offset of dense PLAIN/bool values
    bit_width: int            # dict-index bit width
    has_defs: bool
    num_nulls: int            # -1 = unknown (v1 page, stats absent)


@dataclass
class _RawColumn:
    plan: _ColPlan
    pages: List[_Page] = field(default_factory=list)
    dictionary: Optional[np.ndarray] = None   # dict-page values (host)
    raw_bytes: int = 0


@dataclass
class RawRowGroup:
    """One row group's shipped payload: per-column raw pages for the
    device route plus pyarrow columns for host-fallback ones. ``nbytes``
    charges prefetch admission at compressed + decoded size."""
    file: str
    rg: int
    nrows: int
    device_cols: Dict[str, _RawColumn]
    host_cols: List[str]
    names: List[str]          # output column order
    compressed_bytes: int = 0
    decoded_bytes: int = 0
    host_table = None         # pa.Table for host_cols (set by fetch)

    @property
    def nbytes(self) -> int:
        return int(self.compressed_bytes + self.decoded_bytes)


def enabled() -> bool:
    """Device decode on? (config.device_decode / BODO_TPU_DEVICE_DECODE;
    default on — exotic shapes fall back per column.)"""
    try:
        return bool(config.device_decode)
    except Exception:
        return False


def _parse_string_dict(buf: bytes, n: int) -> np.ndarray:
    out = []
    off = 0
    for _ in range(n):
        (ln,) = struct.unpack_from("<I", buf, off)
        off += 4
        out.append(buf[off:off + ln].decode("utf-8"))
        off += ln
    return np.asarray(out, dtype=str) if out else np.array([], dtype=str)


def _split_chunk_pages(plan: _ColPlan, raw: bytes) -> _RawColumn:
    """Walk a column chunk's pages: parse headers, decompress payloads,
    pre-parse run tables. Raises Unsupported on any page the device
    programs can't decode (caller falls back to host for the column)."""
    codec = _codec(plan.codec_name)
    rc = _RawColumn(plan=plan, raw_bytes=len(raw))
    off = 0
    values_seen = 0
    while values_seen < plan.num_values:
        if off >= len(raw):
            raise Unsupported("chunk ended before all values")
        hdr = _parse_page_header(raw, off)
        off += hdr.header_len
        payload = raw[off:off + hdr.compressed_size]
        if len(payload) != hdr.compressed_size:
            raise Unsupported("page payload truncated")
        off += hdr.compressed_size
        if hdr.type == _DICT_PAGE:
            if rc.dictionary is not None:
                raise Unsupported("multiple dictionary pages")
            data = _decompress(codec, payload, hdr.uncompressed_size)
            if plan.is_string:
                rc.dictionary = _parse_string_dict(data, hdr.num_values)
            else:
                if plan.phys not in _PHYS_WIDTH:
                    raise Unsupported(f"dict of {plan.phys}")
                rc.dictionary = np.frombuffer(
                    data, dtype=_phys_np(plan.phys),
                    count=hdr.num_values)
            continue
        if hdr.type == _INDEX_PAGE:
            continue
        if hdr.type not in (_DATA_PAGE, _DATA_PAGE_V2):
            raise Unsupported(f"page type {hdr.type}")
        v2 = hdr.type == _DATA_PAGE_V2
        if v2:
            lvl_len = hdr.def_levels_byte_len
            levels = payload[:lvl_len]
            body = payload[lvl_len:]
            if hdr.v2_compressed:
                body = _decompress(codec, body,
                                   hdr.uncompressed_size - lvl_len)
            data = levels + body
            lvl_off, lvl_end = 0, lvl_len
            val_off = lvl_len
        else:
            data = _decompress(codec, payload, hdr.uncompressed_size)
            if plan.max_def > 0:
                if hdr.def_level_encoding != _RLE:
                    raise Unsupported("non-RLE definition levels")
                (lvl_len,) = struct.unpack_from("<I", data, 0)
                lvl_off, lvl_end = 4, 4 + lvl_len
                val_off = 4 + lvl_len
            else:
                lvl_off = lvl_end = val_off = 0
        def_runs = None
        if plan.max_def > 0:
            def_runs = _parse_hybrid(data, lvl_off, lvl_end, 1,
                                     hdr.num_values)
        page = _make_page(plan, hdr, data, val_off, def_runs)
        rc.pages.append(page)
        values_seen += hdr.num_values
    if values_seen != plan.num_values:
        raise Unsupported("page value counts disagree with footer")
    return rc


def _phys_np(phys: str) -> str:
    return {"INT32": "<i4", "INT64": "<i8", "FLOAT": "<f4",
            "DOUBLE": "<f8"}[phys]


def _make_page(plan: _ColPlan, hdr: _PageHeader, data: bytes,
               val_off: int, def_runs: Optional[_RunTable]) -> _Page:
    enc = hdr.encoding
    nn = hdr.num_values
    if enc in _DICT_ENCODINGS:
        bw = data[val_off] if val_off < len(data) else 0
        if bw > _MAX_BITWIDTH:
            raise Unsupported(f"dict index bit width {bw}")
        # n is an upper bound: with nulls the index stream stores only
        # the non-null entries (exact=False lets it run out early)
        val_runs = _parse_hybrid(data, val_off + 1, len(data), bw, nn,
                                 exact=False) \
            if nn else _RunTable(*(np.zeros(0, t) for t in
                                   (np.int32, bool, np.int32, np.int32)))
        return _Page("dict", nn, data, def_runs, val_runs, 0, bw,
                     plan.max_def > 0, hdr.num_nulls)
    if enc == _PLAIN:
        if plan.phys == "BOOLEAN":
            return _Page("boolplain", nn, data, def_runs, None, val_off,
                         1, plan.max_def > 0, hdr.num_nulls)
        if plan.is_string or plan.phys not in _PHYS_WIDTH:
            raise Unsupported("PLAIN variable-width values")
        return _Page("plain", nn, data, def_runs, None, val_off, 0,
                     plan.max_def > 0, hdr.num_nulls)
    if enc == _RLE and plan.phys == "BOOLEAN":
        (ln,) = struct.unpack_from("<I", data, val_off)
        val_runs = _parse_hybrid(data, val_off + 4, val_off + 4 + ln, 1,
                                 nn, exact=False) if nn else None
        return _Page("boolrle", nn, data, def_runs, val_runs, 0, 1,
                     plan.max_def > 0, hdr.num_nulls)
    raise Unsupported(f"data page encoding {enc}")


# ---------------------------------------------------------------------------
# fetch (pool side): raw ranges in, page bundles out
# ---------------------------------------------------------------------------

@tracing.event("scan.fetch")  # a fresh span each call (ContextDecorator)
def fetch_row_group(f: str, rg: int, columns: Optional[Sequence[str]],
                    *, inject: bool = True) -> RawRowGroup:
    """Pool task: ship one row group as raw pages. Device-decodable
    columns carry decompressed page payloads + run tables; the rest are
    host-read via pyarrow right here (still on the pool thread). IO
    errors and armed ``io.read`` faults propagate to the caller's retry
    envelope."""
    from bodo_tpu.io.parquet import _raw_range, footer_metadata
    from bodo_tpu.runtime import io_pool, resilience

    if inject:
        resilience.maybe_inject("io.read")
    md = footer_metadata(f)
    arrow_schema = _arrow_schema_of(md)
    g = md.row_group(rg)
    names = list(columns) if columns else list(arrow_schema.names)
    bundle = RawRowGroup(file=f, rg=rg, nrows=g.num_rows,
                         device_cols={}, host_cols=[], names=names)
    for name in names:
        try:
            plan = _plan_chunk(md, arrow_schema, rg, name)
            raw = _raw_range(f, plan.start, plan.size)
            with tracing.event("scan.split", bytes=plan.size):
                rc = _split_chunk_pages(plan, raw)
            if plan.is_string and rc.dictionary is None and \
                    plan.num_values > 0:
                raise Unsupported("string chunk without dictionary page")
            bundle.device_cols[name] = rc
            bundle.compressed_bytes += plan.size
            bundle.decoded_bytes += plan.num_values * \
                max(np.dtype(plan.out_dtype).itemsize, 1)
        except Unsupported:
            bundle.host_cols.append(name)
    if bundle.host_cols:
        import pyarrow.parquet as pq

        from bodo_tpu.io.parquet import _opened
        with _opened(f) as src:
            pf = pq.ParquetFile(src, metadata=md)
            bundle.host_table = pf.read_row_group(rg,
                                                  columns=bundle.host_cols)
        bundle.decoded_bytes += bundle.host_table.nbytes
        io_pool.count("host_decode_bytes", int(bundle.host_table.nbytes))
    io_pool.count("raw_bytes", int(bundle.compressed_bytes))
    return bundle


_arrow_schema_cache: dict = {}
_arrow_schema_lock = threading.Lock()


def _arrow_schema_of(md):
    # keyed by id(md), so each entry must PIN its metadata object: a
    # footer evicted from parquet._footer_cache can be freed and a new
    # file's FileMetaData allocated at the same address, which would
    # silently serve the old file's schema (wrong column set) here
    key = id(md)
    with _arrow_schema_lock:
        ent = _arrow_schema_cache.get(key)
    if ent is not None and ent[0] is md:
        return ent[1]
    sch = md.schema.to_arrow_schema()
    with _arrow_schema_lock:
        if len(_arrow_schema_cache) > 64:
            _arrow_schema_cache.clear()
        _arrow_schema_cache[key] = (md, sch)
    return sch


# ---------------------------------------------------------------------------
# decode (consumer side): bundles -> device Tables
# ---------------------------------------------------------------------------

def _kind_runs(pages: List[_Page]) -> List[List[_Page]]:
    """Consecutive pages of one kind: a chunk's dispatch units (one run,
    or two where dictionary pages give way to PLAIN mid-chunk)."""
    runs: List[List[_Page]] = []
    for pg in pages:
        if runs and runs[-1][0].kind == pg.kind:
            runs[-1].append(pg)
        else:
            runs.append([pg])
    return runs


def _run_spec(rc: _RawColumn, run: List[_Page], cap: int,
              accumulate: bool) -> _PageSpec:
    """One run's program spec: the element-wise largest of its pages'
    buckets (a page padded to a larger bucket decodes the same: run
    starts past its rows are sentinels, values past n_values are
    masked)."""
    plan = rc.plan
    stats_clean = plan.null_count == 0
    masked = plan.max_def > 0 and not stats_clean
    out_dtype = "int32" if plan.is_string else plan.out_dtype
    kind = run[0].kind
    n_bucket = max(_fine_bucket(pg.num_values, 128, 16384) for pg in run)
    if kind == "plain":
        itemsize = _PHYS_WIDTH[plan.phys]
        # the value window, n_bucket * itemsize bytes from val_off, is
        # most of the bytes a run sends: round up to a multiple of 4 KiB,
        # not to a power of two
        byte_need = max(max(len(pg.data), pg.val_off + n_bucket * itemsize)
                        for pg in run)
        byte_bucket = -(-byte_need // 4096) * 4096
    elif kind == "dict":
        itemsize = 0
        byte_bucket = _bucket(max(len(pg.data) for pg in run) + 4, 4096)
    else:
        itemsize = 0
        byte_bucket = _bucket(
            max(max(len(pg.data), pg.val_off + n_bucket // 8 + 8)
                for pg in run), 4096)
    dict_bucket = 0
    if kind == "dict" and rc.dictionary is not None and not plan.is_string:
        dict_bucket = _bucket(len(rc.dictionary), _DICT_FLOOR)
    # stats prove zero nulls -> every def level is 1, so the level
    # expansion and the dense-position cumsum are identities: decode as
    # if the pages had no def levels (the same stats trust that drops
    # the validity mask)
    has_defs = run[0].has_defs and not stats_clean

    def runs_bucket(tables):
        return max([_bucket(len(t.starts), _RUNS_FLOOR)
                    for t in tables if t is not None] + [_RUNS_FLOOR])

    bw = max(pg.bit_width for pg in run)
    if kind == "dict" and bw:
        # each page's own width is an operand: the program only needs the
        # gather window's byte count, ceil((7 + bw) / 8), so key it by the
        # widest width that many bytes hold (9, 17, 24)
        bw = min(8 * ((bw + 14) // 8) - 7, _MAX_BITWIDTH)
    return _PageSpec(
        kind=kind, out_dtype=out_dtype, itemsize=itemsize, bit_width=bw,
        has_defs=has_defs, masked=masked,
        byte_bucket=byte_bucket, n_bucket=n_bucket,
        def_runs=runs_bucket([pg.def_runs for pg in run])
        if has_defs else _RUNS_FLOOR,
        val_runs=runs_bucket([pg.val_runs for pg in run]),
        dict_bucket=dict_bucket, scale=plan.scale,
        n_pages=_fine_bucket(len(run), 1, 16),
        out_len=_bucket(cap, 1024),
        # mask presence must match arrow_bridge: floats never carry one
        # (NaN is the null), others only when the chunk has nulls
        want_valid=masked and np.dtype(out_dtype).kind != "f",
        accumulate=accumulate)


def _decode_column(rc: _RawColumn, cap: int) -> Tuple[Column, int]:
    """Decode one column chunk's pages on device into the padded column:
    one program dispatch per run of same-kind pages, each writing its
    pages' values straight into the column's slots (after the chunk's
    earlier runs). Returns the column and the number of dispatches."""
    import jax.numpy as jnp

    plan = rc.plan
    out_np = np.dtype("int32" if plan.is_string else plan.out_dtype)
    runs = _kind_runs(rc.pages)
    acc: tuple = ()
    dict_dev = None
    slot = 0
    for run in runs:
        spec = _run_spec(rc, run, cap, accumulate=bool(acc))
        if spec.dict_bucket and dict_dev is None:
            # the chunk's numeric dictionary goes to the device once
            dpad = np.zeros(spec.dict_bucket, rc.dictionary.dtype)
            dpad[:len(rc.dictionary)] = rc.dictionary
            dict_dev = jnp.asarray(dpad)
        acc = _run_page_program(spec, run, slot, dict_dev, acc)
        slot += sum(pg.num_values for pg in run)
    valid = None
    if not acc:
        data = jnp.zeros(cap, out_np)
    else:
        data, n_nulls = acc[0], acc[1]
        valid = acc[2] if spec.want_valid else None
        if data.shape[0] != cap:
            # the programs' output is the capacity's power of two, so
            # that row groups of other sizes share them: the column is
            # its head
            data = data[:cap]
            valid = None if valid is None else valid[:cap]
    valid_out = None
    if valid is not None:
        if plan.null_count is not None and plan.null_count > 0:
            valid_out = valid
        elif int(tracing.host_read("decode_null_count", n_nulls)) > 0:
            valid_out = valid
    dictionary = None
    if plan.is_string:
        raw_dict = rc.dictionary if rc.dictionary is not None \
            else np.array([], dtype=str)
        order = np.argsort(raw_dict, kind="stable")
        rank = np.empty_like(order)
        rank[order] = np.arange(len(order))
        dictionary = raw_dict[order] if len(raw_dict) else raw_dict
        if len(raw_dict):
            # rank remap applies to live rows only; the pad region stays
            # raw zero, matching arrow_bridge's _pad(np.zeros)
            lut = jnp.asarray(rank.astype(np.int32))
            clipped = jnp.clip(data, 0, len(raw_dict) - 1)
            # string-dict index gather: Pallas dictionary kernel when
            # the gate is open (ranks < dict length — always f32-exact)
            from bodo_tpu.ops import pallas_kernels as PK
            from bodo_tpu.runtime import io_pool
            remapped = PK.dict_gather(clipped, lut)
            if remapped is not None:
                io_pool.count("pallas_dict_gather")
            else:
                remapped = lut[clipped]
            live = jnp.arange(cap, dtype=jnp.int32) < plan.num_values
            data = jnp.where(live, remapped, 0).astype(jnp.int32)
    return Column(data, valid_out, plan.col_dtype, dictionary), len(runs)


def decode_row_group(bundle: RawRowGroup,
                     capacity: Optional[int] = None) -> Table:
    """Decode one shipped row group into a REP Table: device programs
    for planned columns, ``arrow_bridge`` for host-fallback ones (same
    capacity, so the merged table is indistinguishable from a host
    read)."""
    from bodo_tpu.io.arrow_bridge import _arrow_column
    from bodo_tpu.runtime import io_pool

    t0 = time.perf_counter()
    cap = capacity if capacity is not None else round_capacity(
        bundle.nrows)
    cols: Dict[str, Column] = {}
    n_pages = 0
    n_dispatches = 0
    dev_bytes = 0
    for name in bundle.names:
        rc = bundle.device_cols.get(name)
        if rc is not None:
            try:
                with tracing.event("scan.column", pages=len(rc.pages),
                                   bytes=rc.raw_bytes):
                    cols[name], n = _decode_column(rc, cap)
                n_pages += len(rc.pages)
                n_dispatches += n
                dev_bytes += rc.plan.num_values * \
                    max(np.dtype(rc.plan.out_dtype).itemsize, 1)
                continue
            except Exception:
                # decode surprise: demote this column to host (the raw
                # chunk bytes aren't a pyarrow input, so re-read it)
                bundle.host_cols.append(name)
                io_pool.count("device_decode_errors")
        cols[name] = None  # host-filled below
    missing = [n for n, c in cols.items() if c is None]
    if missing:
        with tracing.event("scan.host_fallback", columns=len(missing)):
            at = bundle.host_table
            have = set() if at is None else set(at.column_names)
            need = [n for n in missing if n not in have]
            if need:
                import pyarrow.parquet as pq

                from bodo_tpu.io.parquet import _opened, footer_metadata
                with _opened(bundle.file) as src:
                    pf = pq.ParquetFile(
                        src, metadata=footer_metadata(bundle.file))
                    extra = pf.read_row_group(bundle.rg, columns=need)
                io_pool.count("host_decode_bytes", int(extra.nbytes))
                at = extra if at is None else _merge_tables(at, extra)
            for n in missing:
                cols[n] = _arrow_column(at.column(n), cap)
    t = Table(cols, bundle.nrows, REP, None)
    t._device_decoded = bool(bundle.device_cols)
    io_pool.count("device_decode_pages", n_pages)
    io_pool.count("device_decode_dispatches", n_dispatches)
    io_pool.count("device_decode_bytes", dev_bytes)
    io_pool.count("device_decode_cols", len(bundle.device_cols))
    io_pool.count("device_fallback_cols", len(set(bundle.host_cols)))
    io_pool.add_time("device_decode_s", time.perf_counter() - t0)
    return t


def _merge_tables(a, b):
    import pyarrow as pa
    arrays = {n: a.column(n) for n in a.column_names}
    arrays.update({n: b.column(n) for n in b.column_names})
    return pa.table(arrays)


# ---------------------------------------------------------------------------
# REP-table concat with dictionary unification
# ---------------------------------------------------------------------------

def concat_tables_rep(tables: List[Table]) -> Table:
    """Concatenate per-row-group REP tables on device, unioning string
    dictionaries (host LUT, device gather — the streaming DictTracker's
    remap, applied once at assembly)."""
    import jax.numpy as jnp

    if len(tables) == 1:
        return tables[0]
    n_total = sum(t.nrows for t in tables)
    cap = round_capacity(n_total)
    names = list(tables[0].columns)
    cols: Dict[str, Column] = {}
    for name in names:
        parts = [t.columns[name] for t in tables]
        dtype = parts[0].dtype
        if any(p.dtype is not dtype for p in parts):
            raise Unsupported(f"dtype drift across row groups: {name}")
        union = None
        if dtype is dt.STRING:
            dicts = [p.dictionary if p.dictionary is not None
                     else np.array([], str) for p in parts]
            union = dicts[0]
            for d in dicts[1:]:
                if d is not union and (len(union) != len(d)
                                       or not np.array_equal(union, d)):
                    union = np.union1d(union, d)
        datas, valids = [], []
        any_valid = any(p.valid is not None for p in parts)
        for t, p in zip(tables, parts):
            d = p.data[:t.nrows]
            if union is not None and p.dictionary is not None and \
                    union is not p.dictionary and len(p.dictionary):
                lut = np.searchsorted(
                    union, p.dictionary).astype(np.int32)
                d = jnp.asarray(lut)[jnp.clip(
                    d, 0, len(p.dictionary) - 1)]
            datas.append(d)
            if any_valid:
                valids.append(p.valid[:t.nrows] if p.valid is not None
                              else jnp.ones(t.nrows, bool))
        data = jnp.concatenate(datas) if len(datas) > 1 else datas[0]
        pad = cap - data.shape[0]
        if pad > 0:
            data = jnp.concatenate(
                [data, jnp.zeros(pad, data.dtype)])
        valid = None
        if any_valid:
            valid = jnp.concatenate(valids) if len(valids) > 1 \
                else valids[0]
            if pad > 0:
                valid = jnp.concatenate([valid, jnp.zeros(pad, bool)])
            if dtype is dt.STRING and union is not None and len(union):
                # arrow's oracle convention: null slots carry the code
                # of the column's FIRST non-null value (encounter-order
                # dictionary[0]); per-chunk decode filled rank(chunk's
                # own first value) instead, which only matches for the
                # first row group. Recover the global fill from the
                # first live row so multi-row-group reads stay
                # bit-identical to a host read.
                null_code = data[jnp.argmax(valid)]
                live = jnp.arange(cap, dtype=jnp.int32) < n_total
                data = jnp.where(valid | ~live, data, null_code)
        cols[name] = Column(data, valid, dtype, union)
    out = Table(cols, n_total, REP, None)
    out._device_decoded = any(getattr(t, "_device_decoded", False)
                              for t in tables)
    return out


# ---------------------------------------------------------------------------
# read-path entry points
# ---------------------------------------------------------------------------

def worth_device_decode(units) -> bool:
    """Size gate for the device route: estimated decoded bytes (footer
    row-group totals) must clear config.device_decode_min_bytes. Small
    reads stay on host — dispatch overhead dominates, and each novel
    page shape would pin another XLA executable for nothing."""
    from bodo_tpu.io.parquet import footer_metadata

    min_b = int(getattr(config, "device_decode_min_bytes", 0))
    if min_b <= 0:
        return True
    est = 0
    for unit in units:
        f, rg = unit[0], unit[1]
        est += footer_metadata(f).row_group(rg).total_byte_size
        if est >= min_b:
            return True
    return False


def read_units_table(units, columns) -> Optional[Table]:
    """Device route for io/parquet._read_parquet_once: pool workers ship
    raw page bundles (ordered), the consumer decodes on device. Returns
    None when the dataset can't take the device route at all (caller
    re-reads via the classic host path); IO/injection errors propagate."""
    from bodo_tpu.runtime import io_pool

    if not worth_device_decode(units):
        return None

    def fetch(unit):
        f, rg, _w = unit
        return fetch_row_group(f, rg, columns)

    try:
        if len(units) > 1 and io_pool.io_thread_count() > 1:
            io_pool.count("parallel_reads")
            bundles = list(io_pool.pool_map_ordered(fetch, units))
        else:
            bundles = [fetch(u) for u in units]
        tables = [decode_row_group(b) for b in bundles]
        return concat_tables_rep(tables)
    except Unsupported:
        return None


def raw_bundles(path, columns, units=None):
    """Generator of RawRowGroup bundles for the streaming source; each
    fetch runs under the shared retry envelope. ``nbytes`` on each item
    charges prefetch admission at compressed + decoded size."""
    from bodo_tpu.io.parquet import _dataset_files, footer_metadata
    from bodo_tpu.runtime import resilience

    if units is None:
        units = []
        for f in _dataset_files(path):
            md = footer_metadata(f)
            units.extend((f, rg) for rg in range(md.num_row_groups))
    # label matches the host streaming route's per-pull envelope: the
    # "streaming parquet reads retry" contract is route-independent
    for f, rg in units:
        yield resilience.retry_call(
            lambda f=f, rg=rg: fetch_row_group(f, rg, columns),
            label="parquet_batch", point="io.read")


def decoded_batches(bundles, batch_rows: int):
    """Decode shipped bundles and re-slice to fixed-capacity batches
    (one compiled shape downstream). Row-group remainders carry over
    into the next group, preserving the parquet_batches contract that
    every batch except the stream's last holds exactly batch_rows rows.
    Dictionary drift across row groups is the streaming DictTracker's
    job — batches keep their chunk dictionary here."""
    from bodo_tpu.plan.streaming import table_batches

    carry = None
    for bundle in bundles:
        t = decode_row_group(bundle)
        if carry is not None:
            t = concat_tables_rep([carry, t])
            carry = None
        flag = getattr(t, "_device_decoded", False)
        out = list(table_batches(t, batch_rows))
        for b in out:
            b._device_decoded = flag
        if out and out[-1].nrows < batch_rows:
            carry = out.pop()
        yield from out
    if carry is not None:
        yield carry
