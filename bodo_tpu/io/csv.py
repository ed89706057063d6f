"""CSV reader (Arrow-based).

Analogue of the reference's chunked parallel CSV reader
(bodo/io/_csv_json_reader.cpp, bodo/ir/csv_ext.py:49). pyarrow's
multithreaded C++ parser does the heavy lifting on host; parse_dates
mirrors the pandas read_csv option used by the benchmarks.
"""

from __future__ import annotations

from typing import Optional, Sequence

import pyarrow as pa
import pyarrow.csv as pacsv

from bodo_tpu.io.arrow_bridge import arrow_to_table
from bodo_tpu.runtime import resilience
from bodo_tpu.table.table import Table
from bodo_tpu.utils import tracing


@tracing.traced_table_op
@tracing.event("scan.host_fallback")  # CSV is parsed on the host
def read_csv(path: str, columns: Optional[Sequence[str]] = None,
             parse_dates: Optional[Sequence[str]] = None) -> Table:
    convert = {}
    if parse_dates:
        convert = {c: pa.timestamp("ns") for c in parse_dates}
    at = resilience.retry_call(
        lambda: pacsv.read_csv(
            path,
            convert_options=pacsv.ConvertOptions(
                column_types=convert,
                include_columns=list(columns) if columns else None,
            ),
        ),
        label="read_csv", point="io.read")
    t = arrow_to_table(at)
    _attach_host_ranges(t, at)
    return t


def _attach_host_ranges(t: Table, at: pa.Table) -> None:
    """Column.vrange from one arrow min/max pass at ingest (CSV has no
    footer statistics; a host pass here spares the dense-path planners a
    device reduce + sync later)."""
    import pyarrow.compute as pc

    from bodo_tpu.table import dtypes as dt
    for name, col in t.columns.items():
        if col.dtype.kind not in ("i", "u", "dt", "date"):
            continue
        arr = at.column(name)
        try:
            mm = pc.min_max(arr)
            lo, hi = mm["min"].as_py(), mm["max"].as_py()
        except Exception:
            continue
        if lo is None or hi is None:
            continue
        import datetime as _dtm

        import numpy as np
        if isinstance(lo, _dtm.datetime):
            lo = int(np.datetime64(lo, "ns").astype(np.int64))
            hi = int(np.datetime64(hi, "ns").astype(np.int64))
        elif isinstance(lo, _dtm.date):
            lo = int(np.datetime64(lo, "D").astype(np.int64))
            hi = int(np.datetime64(hi, "D").astype(np.int64))
        elif not isinstance(lo, (int, np.integer)):
            continue
        col.vrange = (int(lo), int(hi), True)


# ---------------------------------------------------------------------------
# chunked / parallel byte-range reader
# ---------------------------------------------------------------------------

# default byte-range chunk for the streaming reader
CHUNK_BYTES = 32 << 20


def _newline_bounds(path: str, chunk_bytes: int,
                    split_header: bool = True):
    """(header_bytes, offsets): byte-range chunk boundaries aligned to
    row starts by scanning forward to the next newline from each nominal
    split point — the reference's offset-search scheme
    (bodo/io/_csv_json_reader.cpp). Like the reference's scanner this
    assumes the row delimiter does not appear inside quoted fields.
    `split_header=False` (JSON-lines: the first line is data) returns
    header=b"" with bounds starting at byte 0."""
    import os
    size = os.path.getsize(path)
    with open(path, "rb") as f:
        if split_header:
            header = f.readline()
        else:
            header = b""
        start = f.tell()
        bounds = [start]
        pos = start + chunk_bytes
        while pos < size:
            f.seek(pos)
            f.readline()
            pos2 = f.tell()
            if pos2 >= size:
                break
            bounds.append(pos2)
            pos = pos2 + chunk_bytes
        bounds.append(size)
    return header, bounds


def iter_csv_arrow(path: str, columns: Optional[Sequence[str]] = None,
                   parse_dates: Optional[Sequence[str]] = None,
                   chunk_bytes: int = CHUNK_BYTES):
    """Yield one arrow Table per newline-aligned byte-range chunk.

    The first chunk parses synchronously and its inferred schema is
    pinned for every later chunk so dtypes cannot drift mid-file (a
    chunk whose values no longer parse under the pinned schema raises
    instead of silently widening). Remaining chunks parse on the shared
    I/O pool with ordered reassembly (runtime/io_pool.py) — output is
    identical to the serial parse; host memory stays bounded by the
    pool's in-flight window (~(threads+1) x chunk_bytes). Each task
    opens its own file handle, so no seek races across threads."""
    import io as _io

    header, bounds = _newline_bounds(path, chunk_bytes)
    column_types = {c: pa.timestamp("ns") for c in (parse_dates or [])}

    def parse_range(span, types):
        s, e = span

        def _once():
            with open(path, "rb") as f:
                f.seek(s)
                buf = f.read(e - s)
            return pacsv.read_csv(
                _io.BytesIO(header + buf),
                convert_options=pacsv.ConvertOptions(
                    column_types=dict(types),
                    include_columns=list(columns) if columns else None,
                ))
        return resilience.retry_call(_once, label="read_csv_chunk",
                                     point="io.read")

    spans = list(zip(bounds, bounds[1:]))
    if not spans:
        return
    first = parse_range(spans[0], column_types)
    for fld in first.schema:
        column_types.setdefault(fld.name, fld.type)
    yield first
    rest = spans[1:]
    if not rest:
        return
    from bodo_tpu.runtime import io_pool
    pinned = dict(column_types)
    if len(rest) > 1 and io_pool.io_thread_count() > 1:
        io_pool.count("parallel_reads")
        yield from io_pool.pool_map_ordered(
            lambda span: parse_range(span, pinned), rest)
    else:
        for span in rest:
            yield parse_range(span, pinned)


def slice_arrow_batches(src, chunksize: int):
    """Re-slice a stream of arrow Tables into exactly-`chunksize` arrow
    Tables (last may be short). Linear: the pending tail concatenates
    once per INPUT chunk, and all output slices cut from that one
    concatenation (not re-concatenated per yield)."""
    if chunksize < 1:
        raise ValueError(f"chunksize must be >= 1, got {chunksize}")
    pending = []
    pending_rows = 0
    for at in src:
        pending.append(at)
        pending_rows += at.num_rows
        if pending_rows < chunksize:
            continue
        whole = pa.concat_tables(pending)
        off = 0
        while pending_rows - off >= chunksize:
            yield whole.slice(off, chunksize)
            off += chunksize
        pending = [whole.slice(off)] if pending_rows > off else []
        pending_rows -= off
    if pending_rows:
        yield pa.concat_tables(pending)


def read_csv_chunked(path: str, chunksize: int,
                     columns: Optional[Sequence[str]] = None,
                     parse_dates: Optional[Sequence[str]] = None,
                     chunk_bytes: int = CHUNK_BYTES):
    """pandas read_csv(chunksize=N) analogue: an iterator of pandas
    DataFrames of exactly `chunksize` rows (last may be short), parsed
    chunk-at-a-time with bounded host memory (reference:
    bodo/io/csv_iterator_ext.py)."""
    for at in slice_arrow_batches(
            iter_csv_arrow(path, columns, parse_dates, chunk_bytes),
            chunksize):
        yield at.to_pandas()
