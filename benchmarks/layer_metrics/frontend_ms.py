"""Host milliseconds a query spends before execution starts: building the
lazy frame, or `ctx.sql(text)` (parse, plan cache, planner). The
benchmark's own span on the host clock, mean over the window's queries."""

LAYER = "entry"
UNIT = "ms"
MOVES = "query_s"
SOURCE = "program_span"


def read(run):
    if not run.frontend:
        return None
    return 1e3 * sum(run.frontend) / len(run.frontend)
