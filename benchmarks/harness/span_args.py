"""The arguments the engine writes on its own `bodo:<name>` spans
(`utils/tracing.py event()`: TraceMe metadata such as `rows`), which
`harness/trace.py` keeps no copy of: read from the same `.xplane.pb`
once a reduction, and kept on it for every reader after the first.
A program that writes no such argument gives nothing to read."""

SHUFFLE = "bodo:shuffle_by_key"
JOIN_SHUFFLE = "bodo:exchange.shuffle"
# what `relational.shuffle_by_key` records on its span besides `rows`
RECORDS = ("rows_crossed", "a2a_bytes", "rows_max", "rows_mean", "columns")


def _read(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                continue
            for ev in line.events:
                if ev.name.startswith("bodo:"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def spans(trace, name):
    """[(start_ns, end_ns, {argument: value})] of every host span called
    `name`, in or out of the window."""
    every = getattr(trace, "span_args", None)
    if every is None:
        every = trace.span_args = _read(trace.path)
    return [(s, e, a) for n, s, e, a in every if n == name]


def join_shuffles(trace):
    """The arguments of every `shuffle_by_key` that starts inside the
    traced window within a join's `bodo:exchange.shuffle` span and
    carries the engine's records; None where there is none."""
    w0, w1 = trace.window_ns
    joins = [(s, e) for s, e, _ in spans(trace, JOIN_SHUFFLE)]
    out = [a for s, e, a in spans(trace, SHUFFLE)
           if w0 <= s < w1 and all(k in a for k in RECORDS)
           and any(s0 <= s and e <= e0 for s0, e0 in joins)]
    return out or None
