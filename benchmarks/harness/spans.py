"""Host seconds inside the engine's own spans. `bodo_tpu/utils/tracing.py
event()` writes every span it opens into the profiler's trace as a
TraceAnnotation `bodo:<name>`, on the device events' clock; a program
that writes none (a commit before them) gives nothing to read."""

import re

import numpy as np

from harness.trace import union_ns


def span_seconds(trace, pattern):
    """Seconds of the traced window covered by host spans whose name
    matches `pattern` (`re.match`): the union of their intervals over
    every host thread, so nested spans and spans that run side by side
    on pool threads count once. None where no such span was written."""
    names, starts, ends = trace.host
    rx = re.compile(pattern)
    hit = np.flatnonzero([bool(rx.match(str(n))) for n in names])
    if not len(hit):
        return None
    return union_ns(*trace._clip(starts[hit], ends[hit]))[0] / 1e9
