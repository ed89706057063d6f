"""Host milliseconds per query inside `bodo:dist.shard` (`Table.shard`:
rows scattered from the default device over the mesh) and
`bodo:dist.gather` (`Table.gather`: every shard copied to the host and
repacked). A gather inside `to_pandas` is counted here and in
`result_host_ms`: the two are not to be added. Nothing to read on one
chip."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "distribution"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:dist\."


def read(run):
    return per_query(run, span_seconds(run.trace, SPANS), 1e3)
