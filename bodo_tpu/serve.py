"""bodo_tpu.serve — the multi-tenant query-serving client surface.

Thin façade over ``runtime/scheduler.py``: one resident SPMD gang, many
concurrent logical sessions. A client opens a :func:`session`, submits
plan thunks (any callable that runs engine work — a
``df.to_pandas`` lambda, a ``ctx.sql(...)`` call) and gets Futures
back; the scheduler multiplexes them onto the warm gang with fair-share
queueing, admission control from the live health/metrics signals, and
typed backpressure instead of OOM.

    import bodo_tpu
    srv = bodo_tpu.serve.start()
    a = bodo_tpu.serve.session("tenant-a", priority=2.0)
    fut = a.submit(lambda: df.groupby("k").agg(s=("v", "sum")).to_pandas())
    try:
        out = fut.result()
    except bodo_tpu.serve.Overloaded as e:
        time.sleep(e.retry_after_s)   # typed backpressure contract

Sessions share every warm layer the engine has — the fusion/compile
program caches, the SQL plan cache, the persistent AQE stats store and
the semantic result cache — with per-session accounting underneath so
one tenant's huge join cannot evict another tenant's working set.

Knobs: ``BODO_TPU_SERVE_*`` (see config.py) — worker count, queue
bounds, admission thresholds, aging rate, retry-after base.
"""

from __future__ import annotations

from typing import Callable, Optional

from bodo_tpu.runtime.scheduler import (  # noqa: F401 - public re-exports
    AdmissionController,
    AdmissionSignals,
    BackOff,
    Decision,
    Degraded,
    Overloaded,
    QueryFailed,
    Scheduler,
    ServeRejection,
    Session,
    current_session,
    local_signals,
    scheduler,
    session_scope,
    signals_from_health,
    signals_from_metrics,
)
from bodo_tpu.runtime.views import (  # noqa: F401 - continuous queries
    MAINTENANCE_SESSION,
    Subscription,
)

__all__ = [
    "start", "stop", "drain", "session", "submit", "stats",
    "Session", "Scheduler", "ServeRejection", "Overloaded", "Degraded",
    "BackOff", "QueryFailed", "AdmissionSignals", "AdmissionController",
    "Decision", "current_session", "session_scope", "local_signals",
    "signals_from_health", "signals_from_metrics", "scheduler",
    "Subscription", "MAINTENANCE_SESSION",
]


def start(*, telemetry_port: Optional[int] = None) -> Scheduler:
    """Bring the serving layer up on the current (warm) runtime: start
    the scheduler's worker pool and — when a port is given — the
    telemetry HTTP endpoint the admission controller's remote twins
    scrape. Idempotent; returns the scheduler."""
    sched = scheduler()
    sched._ensure_workers()
    if telemetry_port is not None:
        from bodo_tpu.runtime import telemetry
        telemetry.serve(telemetry_port)
    return sched


def stop(*, drain_s: float = 0.0) -> None:
    """Stop the worker pool, optionally draining in-flight work first.
    Queued work survives and resumes on the next start()/submit."""
    sched = scheduler()
    if drain_s > 0:
        sched.drain(timeout=drain_s)
    sched.stop()


def drain(timeout: float = 30.0) -> bool:
    """Block until all queued/running queries finish (True) or the
    timeout expires (False)."""
    return scheduler().drain(timeout=timeout)


def session(session_id: Optional[str] = None, *, priority: float = 1.0,
            allow_degraded: bool = False,
            slo: str = "throughput") -> Session:
    """Open a logical session on the resident gang. ``priority`` is the
    fair-share weight (2.0 gets twice the gang of 1.0 under
    contention); ``allow_degraded`` opts into service while the gang
    has unhealthy ranks; ``slo`` is the service class — ``"latency"``
    ages SERVE_LATENCY_BOOST× faster under contention,
    ``"throughput"`` (default) takes the plain fair share."""
    return scheduler().session(session_id, priority=priority,
                               allow_degraded=allow_degraded, slo=slo)


def submit(fn: Callable, session_id: str = "default"):
    """One-shot convenience: submit a thunk on a named (default)
    session; returns its Future."""
    return session(session_id).submit(fn)


def stats() -> dict:
    """Scheduler snapshot (sessions, queue depths, decision counters)."""
    return scheduler().stats()
