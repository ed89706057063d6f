"""Programs dispatched to the device per query, from
`xla_observatory.stats()["dispatches"]` over the whole window."""

LAYER = "plan"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_counter"


def read(run):
    if not run.queries:
        return None
    return run.counters["dispatches"] / run.queries
