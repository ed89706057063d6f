"""Device seconds per query in the eager aggregate programs, which the
engine names for their operator: `jit_groupby_dense`,
`jit__groupby_hashed_*`, `jit_groupby_sharded_partial` / `_combine`,
`jit_groupby_local`. The fused aggregate stage (`jit_fusedagg`) is
`agg_device_s`'s."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "s"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"groupby"]


def read(run):
    return per_query(run, run.trace.family_seconds(PATTERNS))
