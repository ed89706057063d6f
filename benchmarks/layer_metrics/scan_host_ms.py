"""Host milliseconds per query inside the scan's spans of
`io/device_decode.py`: `bodo:scan.fetch` (file read; on `io_pool` threads
when a read has more than one row group), inside it `bodo:scan.split`
(page-header walk, decompress, run tables: one span a column chunk),
`bodo:scan.column` (padding, one dispatch a page, concat) and
`bodo:scan.host_fallback` (columns pyarrow reads; `io/csv.py`). The union
over all threads: while it runs the device mostly waits."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "scan"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:scan\."


def read(run):
    return per_query(run, span_seconds(run.trace, SPANS), 1e3)
