"""bodo_tpu.views — materialized views & continuous queries.

Thin façade over ``runtime/views.py``: named materialized views that
compose into a DAG and are maintained incrementally on the serving
path. A view's materialization lives in the semantic result cache;
downstream views scan it like a table, and a base-table change
propagates topologically — appends splice a delta scan, partition-level
mutates re-merge only the affected source file's contribution, anything
ambiguous falls back to a full recompute (never a stale partial).

    import bodo_tpu
    daily = df.groupby("day").agg(s=("v", "sum"))
    bodo_tpu.views.create_view("daily", daily)
    weekly = bodo_tpu.views.read("daily").groupby("week")...
    bodo_tpu.views.create_view("weekly", weekly)

    out = bodo_tpu.views.read("weekly").to_pandas()   # serves cached

Continuous queries ride the serving layer: a tenant session registers
``session.subscribe("weekly", max_staleness_s=5.0)`` and receives every
refresh through ``Subscription.next()``; the scheduler's idle workers
poll base signatures between queue drains and run refreshes as
weighted-fair work on the system maintenance session (tenants are not
billed for shared maintenance).

Knobs: ``BODO_TPU_VIEW_*`` (see config.py) — watcher poll interval,
maintenance session weight.
"""

from __future__ import annotations

from bodo_tpu.runtime.views import (  # noqa: F401 - public re-exports
    MAINTENANCE_SESSION,
    Subscription,
    ViewError,
    base_sources,
    create_view,
    drop_view,
    list_views,
    materialized_table,
    read,
    refresh,
    reset,
    scan_node,
    stats,
    subscribe,
)

__all__ = [
    "create_view", "drop_view", "list_views", "read", "refresh",
    "materialized_table", "scan_node", "base_sources", "subscribe",
    "stats", "reset", "Subscription", "ViewError",
    "MAINTENANCE_SESSION",
]
