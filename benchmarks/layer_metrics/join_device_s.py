"""Device seconds per query in the programs whose XLA module name says
join: `jit_join_local`, `jit_join_count` and the build side `jit_bbody`
(`relational.py`'s dense-LUT and hash builds), as the trace of PR 26
names them. The fused join stage of `plan/fusion_join.py` is compiled as
`jit_fused`, a name it shares with every other fused stage, so it cannot
be counted here: the metric lists only cells whose joins run as
`join_local` (PERF.md, section 3)."""

from harness.readers import per_query

LAYER = "operators"
UNIT = "s"
MOVES = "query_s"
SOURCE = "device_trace"
PATTERNS = [r"join", r"^jit_bbody$"]


def read(run):
    return per_query(run, run.trace.family_seconds(PATTERNS))
