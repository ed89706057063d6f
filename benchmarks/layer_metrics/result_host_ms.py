"""Host milliseconds per query inside `bodo:to_pandas` (`Table.to_pandas`:
on several chips the gather, then `bodo:result.d2h`, the copy of every
column to the host, and `bodo:result.frame`, the pandas frame). What the
query's `collect` does to the frame afterwards (taxi's sort of the
answer) is the benchmark's and not in here."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "entry"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:to_pandas$"


def read(run):
    return per_query(run, span_seconds(run.trace, SPANS), 1e3)
