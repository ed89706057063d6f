"""Device milliseconds per query in the parquet page-decode programs."""

from harness.readers import per_query

LAYER = "scan"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "device_trace"
# `device_decode.py` jits one `_page_decode` per page shape (trace of PR 26)
PATTERNS = [r"_page_decode", r"device_decode"]


def read(run):
    return per_query(run, run.trace.family_seconds(PATTERNS), 1e3)
