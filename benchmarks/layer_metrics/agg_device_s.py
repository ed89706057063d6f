"""Device seconds per query in the aggregate programs.

The fused aggregate stage (`plan/fusion.py`, registered as
`fusion:fusedagg`) is compiled as `jit_fused`, a name every fused stage
shares, and the eager groupby programs of `relational.py` are all
`jit_body`. So the metric lists only cells in which `jit_fused` can be
nothing but the aggregate stage: the engine's registry shows no other
fused program there (PERF.md, section 3). Names that say groupby are
counted wherever they appear."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "s"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"^jit_fused$", r"fusedagg", r"groupby", r"_hashed_"]


def read(run):
    return per_query(run, run.trace.family_seconds(PATTERNS))
