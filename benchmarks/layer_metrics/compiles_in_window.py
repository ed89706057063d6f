"""Programs the engine compiled plus backend compiles JAX reported inside
the window. Set-up warms every query up, so this should read 0; a count
is a count, so 0 is a reading here."""

LAYER = "plan"
UNIT = "count"
MOVES = "query_s"
SOURCE = "program_counter"


def read(run):
    return run.counters["programs"] + run.counters["backend_compiles"]
