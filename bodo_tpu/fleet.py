"""bodo_tpu.fleet — fleet serving: one controller, many gangs.

Thin façade over ``runtime/fleet.py``: a single controller in this
process spawns N **gang processes** (each a full PR 14 serving stack —
scheduler, result cache, telemetry endpoint) and multiplexes logical
sessions over them. Queries route to gangs by consistent hashing of
the plan/routing key so repeat traffic lands on a warm result cache;
the controller scrapes every gang's ``/metrics`` + ``/healthz`` and
routes around shed/degraded/dead gangs with the same typed
backpressure contract as single-gang serving. On a cache miss the
owning gang peers with the key's previous owner before recomputing,
and dataset mutations broadcast invalidations fleet-wide.

    import bodo_tpu.fleet as fleet
    ctl = fleet.start(gangs=4)
    s = fleet.session("tenant-a", priority=2.0, slo="latency")
    fut = s.submit(lambda: run_query())     # returns a host value
    try:
        out = fut.result()
    except fleet.Overloaded as e:
        time.sleep(e.retry_after_s)         # typed backpressure
    fleet.stop()

Thunks submitted through the fleet execute in a gang process and their
return value crosses a process boundary — return HOST values (pandas
DataFrames, scalars, lists), not device-resident Tables.

Knobs: ``BODO_TPU_FLEET_*`` (see config.py) — gang count, frame-size
bound, per-session quota, optional client-listener port.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional

from bodo_tpu.runtime.fleet import (  # noqa: F401 - public re-exports
    BackOff,
    Degraded,
    FleetController,
    FleetSession,
    Overloaded,
    ProtocolError,
    QueryFailed,
    RemoteFleet,
    ServeRejection,
    connect,
    controller,
    controller_stats,
    gang_main,
)
from bodo_tpu.runtime import fleet as _impl

__all__ = [
    "start", "stop", "session", "submit", "stats", "gang_stats",
    "connect", "controller", "controller_stats",
    "FleetController", "FleetSession", "RemoteFleet",
    "ProtocolError", "ServeRejection", "Overloaded", "Degraded",
    "BackOff", "QueryFailed",
]


def start(gangs: Optional[int] = None, *,
          gang_env: Optional[Dict[int, Dict[str, str]]] = None,
          timeout: float = 120.0) -> FleetController:
    """Spawn the gang processes and start the controller (idempotent
    while a fleet is running). ``gangs`` defaults to
    ``config.fleet_gangs``; ``gang_env`` overlays extra environment
    onto individual gangs by index (e.g. fault injection for chaos
    tests)."""
    return _impl.start(gangs, gang_env=gang_env, timeout=timeout)


def stop() -> None:
    """Shut the fleet down: polite ``shutdown`` op per gang, then
    stdin-close + kill for stragglers."""
    _impl.stop()


def session(session_id: Optional[str] = None, *, priority: float = 1.0,
            slo: str = "throughput",
            allow_degraded: bool = False) -> FleetSession:
    """Open (or re-open) a logical fleet session. ``slo`` is
    ``"latency"`` (aged ``SERVE_LATENCY_BOOST``× faster on every gang)
    or ``"throughput"``; ``priority`` is the fair-share weight."""
    ctl = _impl.controller()
    if ctl is None or not ctl._started:
        ctl = _impl.start()
    return ctl.session(session_id, priority=priority, slo=slo,
                       allow_degraded=allow_degraded)


def submit(fn: Callable, session_id: str = "default", *,
           key: Optional[str] = None):
    """One-shot convenience: submit on a named session."""
    return session(session_id).submit(fn, key=key)


def stats() -> Optional[dict]:
    """Controller-level fleet stats (gang states, reroutes, peering,
    invalidations) — None when no fleet is running."""
    return _impl.controller_stats()


def gang_stats(gang_id: str) -> Optional[dict]:
    """A single gang's own scheduler/result-cache counters, fetched
    over the wire."""
    ctl = _impl.controller()
    if ctl is None:
        return None
    return ctl.gang_stats(gang_id)
