"""Host milliseconds per query inside the engine's planning spans:
`bodo:plan.sql` (`BodoSQLContext.sql`: parse, plan cache, planner) and,
inside `physical.execute`, `bodo:plan.optimize`, `bodo:plan.validate`
and `bodo:plan.fusion`. `frontend_ms` times the first from outside and
does not see the other three."""

from harness.readers import per_query
from harness.spans import span_seconds

LAYER = "plan"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"
SPANS = r"bodo:plan\."


def read(run):
    return per_query(run, span_seconds(run.trace, SPANS), 1e3)
