"""Device seconds per query in the fused join stage of
`plan/fusion_join.py` (chain below, probe of the cached build, chain
above: one program, `jit_fusedjoin`, replicated or sharded)."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "s"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"fusedjoin"]


def read(run):
    return per_query(run, run.trace.family_seconds(PATTERNS))
