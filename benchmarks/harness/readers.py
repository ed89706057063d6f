"""What the readers under layer_metrics/ share."""


def per_query(run, seconds, scale=1.0):
    """Seconds of the traced part as a reading per traced query; None
    where the trace had nothing to read."""
    if seconds is None or not run.traced_queries:
        return None
    return scale * seconds / run.traced_queries
