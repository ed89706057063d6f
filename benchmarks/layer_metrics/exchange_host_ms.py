"""Host milliseconds per query inside `bodo:exchange.*`: what the host
does to carry a join's rows across chips. `bodo:exchange.shuffle` holds
the two `shuffle_by_key` of a join of sharded sides (dispatch, the wait
for the received counts, the slice to fit), `bodo:exchange.broadcast`
the replication of a build side (`Table.gather`: every column to the
host, a numpy repack, back to the device; that gather is also in
`dist_host_ms`, and the two are not to be added). A build side that was
replicated as it came opens a span with nothing inside. A program that
writes no such span (a commit before them) gives nothing to read."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "distribution"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:exchange\."


def read(run):
    return per_query(run, span_seconds(run.trace, SPANS), 1e3)
