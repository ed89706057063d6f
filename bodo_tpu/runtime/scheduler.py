"""Multi-tenant query scheduler: one resident gang, many sessions.

The Pathways design point (PAPERS §2): a centralized controller
multiplexes many logical plans onto ONE warm SPMD gang instead of every
client paying gang spawn + jax.distributed init. Clients hold a
:class:`Session` (thin handles minted by ``bodo_tpu.serve``) and submit
plan thunks; a small worker pool drains the per-session queues through
``plan/physical.execute`` with the session pinned in a contextvar so
every layer underneath (result cache, sql plan cache, EXPLAIN, governor
grants) attributes its work to the right tenant.

Three mechanisms, in dispatch order:

1. ADMISSION — every submit is screened against the observability the
   engine already exports, via :class:`AdmissionSignals`:
     * governor occupancy >= ``serve_shed_occupancy`` (or an OOM retry /
       result-cache pressure shed since the last decision) → shed the
       request with a typed :class:`Overloaded`;
     * ``unhealthy_ranks`` on /healthz → :class:`Degraded` rejection
       unless the session opted into degraded service;
     * an ``xla_recompile_storm`` whose signature this session's own
       queries compiled under → :class:`BackOff` (shape-bucket churn
       must not evict other tenants' executables);
     * a comm-skewed gang (``comm.wait_frac`` head) → :class:`BackOff`
       for sessions whose own recent queries are comm-wait dominated.
   ``signals_from_health`` / ``signals_from_metrics`` parse remote
   /healthz JSON and /metrics Prometheus text into the same structure
   ``local_signals()`` builds in-process, so a fleet controller makes
   the identical decision from a scrape.

2. FAIR SHARE — per-session FIFO queues drained by weighted virtual
   time: each session accrues ``wall / weight`` as it is served and the
   lowest accrued time runs next, with priority aging (head-of-queue
   wait discounts virtual time at 1/``serve_aging_s`` per second) so a
   starved low-weight session eventually wins the gang.

3. BACKPRESSURE — queues are bounded (``serve_queue_depth`` per
   session, ``serve_max_pending`` total); overflow raises
   :class:`Overloaded` with a measured ``retry_after_s`` hint (queue
   length x the session's EWMA query wall) instead of buffering until
   the device OOMs. A query failure is delivered to that session's
   future as a typed :class:`QueryFailed` — the worker, the gang, and
   every other session keep serving (stage-not-task isolation, the Ray
   contrast of PAPERS §5).

Like telemetry, this module never *forces* an engine subsystem in:
every signal read goes through ``sys.modules.get`` (a subsystem that
was never imported simply contributes no signal), and the plan thunks
themselves pull in the engine on the worker thread.
"""

from __future__ import annotations

import itertools
import re
import sys
import threading
import time
from collections import deque
from concurrent.futures import Future
from contextvars import ContextVar
from dataclasses import dataclass, fields as _dc_fields
from typing import Callable, Dict, List, Optional

from bodo_tpu.config import config
from bodo_tpu.utils.logging import log

_STORM_SIGS_MAX = 8       # storm signatures remembered per session
_EWMA_ALPHA = 0.5         # weight of the newest query in session EWMAs
_SIGNAL_TTL_S = 0.2       # local_signals() snapshot reuse window
# Latency-bound SLO class: priority aging runs this many times faster
# for slo="latency" sessions, so their queued requests overtake
# throughput-bound traffic without starving it.
SERVE_LATENCY_BOOST = 4.0


# --------------------------------------------------------------------------
# typed backpressure contract
# --------------------------------------------------------------------------

class ServeRejection(RuntimeError):
    """Base of every admission rejection: carries the machine-readable
    reason and a retry-after hint (seconds) for the client's backoff."""

    kind = "rejected"

    def __init__(self, msg: str, *, retry_after_s: float = 0.0,
                 reason: str = ""):
        super().__init__(msg)
        self.retry_after_s = max(float(retry_after_s), 0.0)
        self.reason = reason or self.kind


class Overloaded(ServeRejection):
    """Shed: the gang cannot take more work right now (governor
    pressure, cache pressure, or a full queue). Retry after the hint."""

    kind = "overloaded"


class Degraded(ServeRejection):
    """The gang is unhealthy (dead/hung ranks). Sessions that did not
    opt into degraded service are rejected until it recovers."""

    kind = "degraded"


class BackOff(ServeRejection):
    """This session specifically should slow down (its shape churn is
    storming the compile cache, or it is comm-dominated on a skewed
    gang) — other sessions are still being admitted."""

    kind = "backoff"


class QueryFailed(RuntimeError):
    """A submitted query raised: delivered to THAT session's future with
    the original error chained, never to the worker or other sessions."""

    def __init__(self, session_id: str, query_id: Optional[str],
                 cause: BaseException):
        super().__init__(
            f"session {session_id!r} query {query_id or '-'} failed: "
            f"{type(cause).__name__}: {cause}")
        self.session_id = session_id
        self.query_id = query_id
        self.__cause__ = cause


# --------------------------------------------------------------------------
# admission signals: one structure, three producers
# --------------------------------------------------------------------------

@dataclass
class AdmissionSignals:
    """Normalized admission inputs. Every field is Optional — a parser
    fills what its payload carries and ``merged()`` overlays sources
    (e.g. /healthz gang state + /metrics governor occupancy)."""

    gang_status: Optional[str] = None
    unhealthy_ranks: Optional[tuple] = None
    governor_budget_bytes: Optional[int] = None
    governor_granted_bytes: Optional[int] = None
    governor_occupancy: Optional[float] = None
    oom_retries: Optional[int] = None
    comm_wait_frac: Optional[float] = None
    comm_max_wait_site: Optional[str] = None
    storm_signature: Optional[str] = None
    storm_compiles: Optional[int] = None
    storm_window_s: Optional[float] = None
    xla_budget_remaining: Optional[int] = None
    result_cache_occupancy: Optional[float] = None
    result_cache_pressure_sheds: Optional[int] = None
    # static HBM peak (progcheck liveness sweep) of the largest verified
    # program: admission sheds BEFORE trace when even the biggest known
    # program wouldn't fit the governor's remaining headroom
    progcheck_hbm_peak_bytes: Optional[int] = None
    # elastic capacity: <1.0 when the gang shrank after a rank loss —
    # the fleet admission twin scales the per-gang session quota (and
    # routing weight) by this instead of rejecting outright
    gang_capacity_frac: Optional[float] = None
    elastic_epoch: Optional[int] = None
    source: str = "local"

    def merged(self, other: "AdmissionSignals") -> "AdmissionSignals":
        """New signals with ``other``'s non-None fields overlaid."""
        out = AdmissionSignals(**{f.name: getattr(self, f.name)
                                  for f in _dc_fields(AdmissionSignals)})
        for f in _dc_fields(AdmissionSignals):
            v = getattr(other, f.name)
            if v is not None and f.name != "source":
                setattr(out, f.name, v)
        out.source = f"{self.source}+{other.source}"
        return out


def signals_from_health(doc: dict) -> AdmissionSignals:
    """Parse a /healthz JSON document (telemetry.health()) into
    admission signals: gang status + unhealthy ranks, the comm skew
    head, the recompile-storm flag, and the result-cache pressure block
    this PR adds to the document."""
    sig = AdmissionSignals(source="healthz")
    sig.gang_status = doc.get("status")
    bad = doc.get("unhealthy_ranks")
    if bad:
        sig.unhealthy_ranks = tuple(int(r) for r in bad)
    cm = doc.get("comm") or {}
    if "wait_frac" in cm:
        sig.comm_wait_frac = float(cm["wait_frac"])
        sig.comm_max_wait_site = cm.get("max_wait_site")
    st = doc.get("xla_recompile_storm") or {}
    if st.get("signature"):
        sig.storm_signature = str(st["signature"])
        sig.storm_compiles = int(st.get("compiles_in_window", 0))
        sig.storm_window_s = float(st.get("window_s", 0.0))
    rc = doc.get("result_cache") or {}
    if rc:
        if "occupancy_frac" in rc:
            sig.result_cache_occupancy = float(rc["occupancy_frac"])
        if "pressure_sheds" in rc:
            sig.result_cache_pressure_sheds = int(rc["pressure_sheds"])
    el = doc.get("elastic") or {}
    if "capacity_frac" in el:
        sig.gang_capacity_frac = float(el["capacity_frac"])
        sig.elastic_epoch = int(el.get("epoch", 0))
    return sig


_PROM_LINE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:]*)(?:\{(.*)\})?\s+(\S+)\s*$")
_PROM_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _prom_samples(text: str):
    """Yield (name, labels, value) from Prometheus exposition text."""
    for line in text.splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _PROM_LINE.match(line)
        if m is None:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        labels = dict(_PROM_LABEL.findall(m.group(2) or ""))
        yield m.group(1), labels, value


def signals_from_metrics(text: str) -> AdmissionSignals:
    """Parse /metrics Prometheus text into admission signals: governor
    occupancy (granted / derived budget) and OOM retries, the comm wait
    fraction, compile-budget headroom, and result-cache occupancy from
    the cache byte/budget gauges + pressure-shed counter."""
    sig = AdmissionSignals(source="metrics")
    granted = 0
    saw_granted = False
    rc_dev = rc_budget = None
    for name, labels, value in _prom_samples(text):
        if name == "bodo_tpu_mem_derived_budget_bytes":
            sig.governor_budget_bytes = int(value)
        elif name == "bodo_tpu_mem_operator_bytes" \
                and labels.get("kind") == "granted":
            granted += int(value)
            saw_granted = True
        elif name == "bodo_tpu_mem_oom_retries_total":
            sig.oom_retries = int(value)
        elif name == "bodo_tpu_comm_wait_frac":
            sig.comm_wait_frac = float(value)
        elif name == "bodo_tpu_xla_budget_remaining":
            sig.xla_budget_remaining = int(value)
        elif name == "bodo_tpu_result_cache_bytes" \
                and labels.get("tier") == "device":
            rc_dev = int(value)
        elif name == "bodo_tpu_result_cache_budget_bytes":
            rc_budget = int(value)
        elif name == "bodo_tpu_result_cache_events_total" \
                and labels.get("event") == "pressure_sheds":
            sig.result_cache_pressure_sheds = int(value)
    if saw_granted:
        sig.governor_granted_bytes = granted
    if sig.governor_budget_bytes and saw_granted:
        sig.governor_occupancy = granted / sig.governor_budget_bytes
    if rc_dev is not None and rc_budget:
        sig.result_cache_occupancy = rc_dev / rc_budget
    return sig


def _mod(name: str):
    return sys.modules.get(name)


def _maintenance_due() -> bool:
    """True when the view signature watcher wants a poll slot. Called
    by idle workers while HOLDING the scheduler lock, so it must stay
    lock-free (plain attribute reads in runtime/views.py) and cheap."""
    vw = _mod("bodo_tpu.runtime.views")
    if vw is None:
        return False
    try:
        return vw.maintenance_due()
    except Exception:  # noqa: BLE001 - a broken watcher must not wedge
        return False


def _run_maintenance_tick(sched) -> None:
    """One watcher poll: detect changed base tables and schedule view
    refreshes as weighted-fair work on the system maintenance session."""
    vw = _mod("bodo_tpu.runtime.views")
    if vw is not None:
        try:
            vw.maintenance_tick(sched)
        except Exception:  # noqa: BLE001
            pass


def local_signals() -> AdmissionSignals:
    """In-process signals: the same document /healthz serves, plus a
    direct governor read (occupancy without a /metrics scrape). Every
    subsystem is read via sys.modules.get — an admission check never
    forces a jax import."""
    sig = AdmissionSignals(source="local")
    tl = _mod("bodo_tpu.runtime.telemetry")
    if tl is not None:
        try:
            sig = signals_from_health(tl.health())
            sig.source = "local"
        except Exception:  # noqa: BLE001 - admission reads best-effort
            pass
    mg = _mod("bodo_tpu.runtime.memory_governor")
    if mg is not None:
        try:
            st = mg.governor().stats()
            budget = int(st.get("derived_budget_bytes", 0))
            granted = int(sum(m.get("granted", 0)
                              for m in st.get("operators", {}).values()))
            sig.governor_budget_bytes = budget
            sig.governor_granted_bytes = granted
            if budget > 0:
                sig.governor_occupancy = granted / budget
            sig.oom_retries = int(st.get("n_oom_retries", 0))
        except Exception:  # noqa: BLE001
            pass
    pc = _mod("bodo_tpu.analysis.progcheck")
    if pc is not None:
        try:
            est = int(pc.max_hbm_estimate())
            if est > 0:
                sig.progcheck_hbm_peak_bytes = est
        except Exception:  # noqa: BLE001
            pass
    rc = _mod("bodo_tpu.runtime.result_cache")
    if rc is not None and sig.result_cache_occupancy is None:
        try:
            rs = rc.stats()
            budget = int(rs.get("budget_bytes", 0))
            if budget > 0:
                sig.result_cache_occupancy = \
                    int(rs.get("device_bytes", 0)) / budget
            sig.result_cache_pressure_sheds = \
                int(rs.get("pressure_sheds", 0))
        except Exception:  # noqa: BLE001
            pass
    return sig


# --------------------------------------------------------------------------
# admission controller
# --------------------------------------------------------------------------

@dataclass
class Decision:
    action: str                    # "admit" | "shed" | "degrade" | "backoff"
    reason: str = ""
    retry_after_s: float = 0.0


class AdmissionController:
    """Stateless-per-session decision function over AdmissionSignals,
    with one piece of memory: the last-seen OOM-retry / pressure-shed
    counters, so a NEW retry or shed since the previous decision reads
    as live memory pressure (the counters themselves are cumulative)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._last_oom: Optional[int] = None
        self._last_sheds: Optional[int] = None

    def _pressure_event(self, sig: AdmissionSignals) -> Optional[str]:
        with self._mu:
            out = None
            if sig.oom_retries is not None:
                if self._last_oom is not None \
                        and sig.oom_retries > self._last_oom:
                    out = "oom_retry"
                self._last_oom = sig.oom_retries
            if sig.result_cache_pressure_sheds is not None:
                if self._last_sheds is not None \
                        and sig.result_cache_pressure_sheds > \
                        self._last_sheds:
                    out = out or "cache_pressure_shed"
                self._last_sheds = sig.result_cache_pressure_sheds
            return out

    def decide(self, sig: AdmissionSignals,
               session: Optional["Session"] = None) -> Decision:
        base = max(float(config.serve_retry_after_s), 0.05)
        if not config.serve_admission:
            return Decision("admit", "admission_disabled")
        # 1) shed on memory pressure: the whole point of admission is
        #    that overload turns into a typed rejection, never an OOM
        occ = sig.governor_occupancy
        if occ is not None and occ >= float(config.serve_shed_occupancy):
            return Decision("shed", f"governor_occupancy={occ:.2f}",
                            retry_after_s=base * 4)
        pressure = self._pressure_event(sig)
        if pressure is not None:
            return Decision("shed", pressure, retry_after_s=base * 4)
        # 1b) shed BEFORE trace when the statically-estimated peak of
        #     the gang's largest verified program exceeds the governor's
        #     remaining headroom: the query would compile, dispatch and
        #     only then discover the pressure mid-flight
        est = sig.progcheck_hbm_peak_bytes
        if est and sig.governor_budget_bytes:
            headroom = sig.governor_budget_bytes \
                - int(sig.governor_granted_bytes or 0)
            if est > headroom > 0 or headroom <= 0:
                return Decision(
                    "shed",
                    f"progcheck_hbm_estimate={est}>headroom={headroom}",
                    retry_after_s=base * 4)
        # 2) degrade on gang health: dead/hung ranks mean sharded
        #    results are at risk — only opted-in sessions proceed
        if sig.unhealthy_ranks:
            if session is None or not session.allow_degraded:
                return Decision(
                    "degrade",
                    f"unhealthy_ranks={list(sig.unhealthy_ranks)}",
                    retry_after_s=base * 2)
        # 3) back off the storm owner: a session whose shape churn is
        #    recompiling every dispatch must not evict other tenants'
        #    executables (attribution: the session saw compiles land
        #    under this signature during its own queries)
        if sig.storm_signature and session is not None \
                and session.owns_storm(sig.storm_signature):
            return Decision(
                "backoff", f"recompile_storm={sig.storm_signature}",
                retry_after_s=max(base * 2,
                                  float(sig.storm_window_s or 0.0)))
        # 4) back off comm-dominated sessions on a skewed gang: more of
        #    their queries just means more peer-wait for everyone
        thresh = float(config.serve_comm_wait_frac)
        if sig.comm_wait_frac is not None \
                and sig.comm_wait_frac >= thresh \
                and session is not None \
                and session.ewma_comm_wait_frac >= thresh:
            return Decision(
                "backoff",
                f"comm_skew={sig.comm_wait_frac:.2f}"
                f"@{sig.comm_max_wait_site or '-'}",
                retry_after_s=base * 2)
        return Decision("admit", "ok")


# --------------------------------------------------------------------------
# sessions
# --------------------------------------------------------------------------

class _Request:
    __slots__ = ("session", "fn", "future", "enq_ts", "query_id")

    def __init__(self, session: "Session", fn: Callable):
        self.session = session
        self.fn = fn
        self.future: Future = Future()
        self.enq_ts = time.monotonic()
        self.query_id: Optional[str] = None


class Session:
    """One tenant's handle on the resident gang. Mutable state is
    guarded by the owning scheduler's lock; the EWMA/storm fields are
    only written by worker threads between queries."""

    def __init__(self, sched: "Scheduler", sid: str, *,
                 priority: float = 1.0, allow_degraded: bool = False,
                 slo: str = "throughput"):
        self._sched = sched
        self.sid = sid
        self.weight = max(float(priority), 0.01)
        self.allow_degraded = bool(allow_degraded)
        # SLO class: "latency" sessions age faster in _rank_locked so
        # their queued head overtakes throughput-bound traffic.
        self.slo = slo if slo in ("latency", "throughput") \
            else "throughput"
        self.queue: deque = deque()
        self.vtime = 0.0              # served seconds / weight
        self.served_s = 0.0
        self.ewma_query_s = 0.0
        self.ewma_comm_wait_frac = 0.0
        self._storm_sigs: deque = deque(maxlen=_STORM_SIGS_MAX)
        self.counters: Dict[str, int] = {}
        self.closed = False

    # -- client surface ----------------------------------------------------

    def submit(self, fn: Callable) -> Future:
        """Queue a plan thunk; returns a Future resolving to its result
        (or raising QueryFailed / a typed rejection synchronously)."""
        return self._sched.submit(self, fn)

    def run(self, fn: Callable, timeout: Optional[float] = None):
        """Submit and block for the result."""
        return self.submit(fn).result(timeout=timeout)

    def subscribe(self, view: str,
                  max_staleness_s: Optional[float] = None):
        """Register a standing query against a materialized view
        (runtime/views.py): returns a Subscription whose ``next()``
        delivers every refreshed result through an ordinary serve
        future. The refresh work itself runs on the system maintenance
        session, not billed to this tenant; ``max_staleness_s`` bounds
        how far behind a base-table change the delivered result may be
        (it tightens the scheduler's signature poll interval)."""
        from bodo_tpu.runtime import views as _views
        return _views.subscribe(view, session=self,
                                max_staleness_s=max_staleness_s)

    def close(self) -> None:
        self._sched.close_session(self)

    def stats(self) -> dict:
        with self._sched._cv:
            return {
                "session": self.sid,
                "weight": self.weight,
                "slo": self.slo,
                "allow_degraded": self.allow_degraded,
                "queued": len(self.queue),
                "vtime_s": round(self.vtime, 6),
                "served_s": round(self.served_s, 6),
                "ewma_query_s": round(self.ewma_query_s, 6),
                "ewma_comm_wait_frac":
                    round(self.ewma_comm_wait_frac, 4),
                "storm_signatures": list(self._storm_sigs),
                "counters": dict(self.counters),
                "closed": self.closed,
            }

    # -- scheduler-side helpers -------------------------------------------

    def owns_storm(self, signature: str) -> bool:
        return signature in self._storm_sigs

    def note_storm(self, signature: str) -> None:
        if signature and signature not in self._storm_sigs:
            self._storm_sigs.append(signature)

    def _count(self, name: str, n: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + n


# --------------------------------------------------------------------------
# the scheduler
# --------------------------------------------------------------------------

class Scheduler:
    """Weighted fair queueing + admission over a worker pool that is
    the only thing actually executing plans on the gang."""

    def __init__(self):
        self._cv = threading.Condition()
        self._sessions: Dict[str, Session] = {}
        self._workers: List[threading.Thread] = []
        self._stop = threading.Event()
        self._pending = 0
        self._running = 0
        self._decisions: Dict[str, int] = {}
        self._completed = 0
        self._failed = 0
        self._resumed = 0
        self._sig_cache: Optional[AdmissionSignals] = None
        self._sig_at = 0.0
        self._seq = itertools.count(1)
        self.admission = AdmissionController()

    # -- sessions ----------------------------------------------------------

    def session(self, session_id: Optional[str] = None, *,
                priority: float = 1.0,
                allow_degraded: bool = False,
                slo: str = "throughput") -> Session:
        """Open (or re-open) a session. Re-opening an existing id keeps
        its queue/accounting but re-applies priority/degraded/SLO."""
        with self._cv:
            sid = session_id or f"s{next(self._seq)}"
            s = self._sessions.get(sid)
            if s is None:
                s = Session(self, sid, priority=priority,
                            allow_degraded=allow_degraded, slo=slo)
                self._sessions[sid] = s
            else:
                s.weight = max(float(priority), 0.01)
                s.allow_degraded = bool(allow_degraded)
                s.slo = slo if slo in ("latency", "throughput") \
                    else "throughput"
                s.closed = False
            return s

    def close_session(self, session: Session) -> None:
        """Refuse new submits and drop queued (not yet running) work;
        queued futures get a typed rejection."""
        with self._cv:
            session.closed = True
            dropped = list(session.queue)
            session.queue.clear()
            self._pending -= len(dropped)
        for req in dropped:
            req.future.set_exception(Overloaded(
                f"session {session.sid!r} closed with queued work",
                reason="session_closed"))

    # -- submission / admission -------------------------------------------

    def _signals(self) -> AdmissionSignals:
        now = time.monotonic()
        with self._cv:
            if self._sig_cache is not None \
                    and now - self._sig_at < _SIGNAL_TTL_S:
                return self._sig_cache
        sig = local_signals()
        with self._cv:
            self._sig_cache, self._sig_at = sig, time.monotonic()
        return sig

    def _reject(self, session: Session, exc: ServeRejection):
        with self._cv:
            self._decisions[exc.kind] = \
                self._decisions.get(exc.kind, 0) + 1
            session._count(f"rejected_{exc.kind}")
        try:
            import os as _os

            from bodo_tpu.utils import metrics
            names = ("kind", "session")
            labels = {"kind": exc.kind, "session": session.sid}
            gid = _os.environ.get("BODO_TPU_GANG_ID", "")
            if gid:
                # fleet gang: per-gang attribution on the scraped
                # series (env is process-constant, so the label set
                # never flips mid-registry)
                names += ("gang",)
                labels["gang"] = gid
            metrics.counter(
                "bodo_tpu_serve_rejections_total",
                "admission/backpressure rejections by kind",
                names).labels(**labels).inc()
        except Exception:  # noqa: BLE001
            pass
        raise exc

    def submit(self, session: Session, fn: Callable) -> Future:
        if session.closed:
            self._reject(session, Overloaded(
                f"session {session.sid!r} is closed",
                reason="session_closed"))
        decision = self.admission.decide(self._signals(), session)
        if decision.action != "admit":
            exc_type = {"shed": Overloaded, "degrade": Degraded,
                        "backoff": BackOff}[decision.action]
            self._reject(session, exc_type(
                f"{decision.action}: {decision.reason}",
                retry_after_s=decision.retry_after_s,
                reason=decision.reason))
        ewma = max(session.ewma_query_s, 0.01)
        with self._cv:
            depth = max(int(config.serve_queue_depth), 1)
            if len(session.queue) >= depth:
                hint = ewma * (len(session.queue) + 1)
            elif self._pending >= max(int(config.serve_max_pending), 1):
                hint = ewma * (self._pending + 1) \
                    / max(len(self._workers), 1)
            else:
                hint = None
                self._decisions["admit"] = \
                    self._decisions.get("admit", 0) + 1
                if not session.queue:
                    # a session returning from idle rejoins at the
                    # backlog's minimum virtual time: it competes
                    # fairly from now on instead of replaying the
                    # service it never consumed while away
                    floor = [t.vtime for t in self._sessions.values()
                             if t.queue]
                    if floor:
                        session.vtime = max(session.vtime, min(floor))
                req = _Request(session, fn)
                session.queue.append(req)
                self._pending += 1
                session._count("submitted")
                self._cv.notify()
        if hint is not None:
            self._reject(session, Overloaded(
                f"session {session.sid!r} queue full "
                f"({len(session.queue)} queued)",
                retry_after_s=hint, reason="queue_full"))
        self._ensure_workers()
        return req.future

    # -- fair-share pick ---------------------------------------------------

    def _rank_locked(self, s: Session, now: float) -> float:
        """Virtual-time rank with priority aging: every serve_aging_s
        seconds the head request has waited discounts one second of
        accrued virtual time, so starvation is bounded. Latency-class
        sessions age SERVE_LATENCY_BOOST× faster — their head overtakes
        queued throughput traffic without zeroing its progress."""
        aging = max(float(config.serve_aging_s), 0.01)
        if s.slo == "latency":
            aging /= SERVE_LATENCY_BOOST
        waited = now - s.queue[0].enq_ts
        return s.vtime - waited / aging

    def _pick_locked(self) -> Optional[_Request]:
        now = time.monotonic()
        best = None
        for s in self._sessions.values():
            if not s.queue:
                continue
            r = self._rank_locked(s, now)
            if best is None or r < best[0] \
                    or (r == best[0] and s.sid < best[1].sid):
                best = (r, s)
        if best is None:
            return None
        s = best[1]
        req = s.queue.popleft()
        self._pending -= 1
        return req

    # -- workers -----------------------------------------------------------

    def _ensure_workers(self) -> None:
        with self._cv:
            want = max(int(config.serve_workers), 1)
            alive = [t for t in self._workers if t.is_alive()]
            self._workers = alive
            if self._stop.is_set():
                self._stop = threading.Event()
            stop = self._stop
            n_new = want - len(alive)
            new = []
            for _ in range(max(n_new, 0)):
                t = threading.Thread(
                    target=self._worker, args=(stop,),
                    name=f"bodo-tpu-serve-{len(self._workers) + len(new)}",
                    daemon=True)
                new.append(t)
                self._workers.append(t)
        for t in new:
            t.start()

    def _worker(self, stop: threading.Event) -> None:
        while True:
            tick = False
            with self._cv:
                req = None
                while not stop.is_set():
                    req = self._pick_locked()
                    if req is not None:
                        break
                    # between queue drains: the view signature watcher
                    # gets a poll slot. maintenance_due() is lock-free
                    # attribute reads — it must never block under _cv.
                    if _maintenance_due():
                        tick = True
                        break
                    self._cv.wait(0.1)
                if req is None and not tick:
                    return
                if req is not None:
                    self._running += 1
            if req is None:
                # the tick runs OUTSIDE the lock: view maintenance
                # submits refresh work back into this scheduler, which
                # re-acquires _cv
                _run_maintenance_tick(self)
                continue
            try:
                self._execute(req)
            finally:
                with self._cv:
                    self._running -= 1
                    self._cv.notify_all()

    # -- execution + per-session attribution ------------------------------

    def _execute(self, req: _Request) -> None:
        s = req.session
        token = _session_ctx.set(s.sid)
        grant = None
        comm0 = xla0 = None
        cm = _mod("bodo_tpu.parallel.comm")
        ob = _mod("bodo_tpu.runtime.xla_observatory")
        try:
            if comm0 is None and cm is not None:
                try:
                    comm0 = cm.stats()
                except Exception:  # noqa: BLE001
                    comm0 = None
            if ob is not None:
                try:
                    xla0 = ob.head()
                except Exception:  # noqa: BLE001
                    xla0 = None
            grant = self._session_grant(s)
            t0 = time.perf_counter()
            try:
                out, qid = self._run_in_span(req)
            except BaseException as e:  # noqa: BLE001 - typed delivery
                # the scheduler fails nothing it can resume: a rank
                # loss under an elastic gang re-runs the thunk ONCE on
                # the shrunk mesh — completed stages hit the result
                # cache, so only the plan suffix past the last stage
                # checkpoint actually executes again. Other sessions
                # never see the loss at all.
                out = None
                resumed = False
                el = _mod("bodo_tpu.runtime.elastic")
                if el is not None and config.elastic and \
                        el.is_resumable(e):
                    try:
                        out, qid = self._run_in_span(req)
                        resumed = True
                    except BaseException as e2:  # noqa: BLE001
                        e = e2
                if not resumed:
                    wall = time.perf_counter() - t0
                    self._account(s, wall, cm, comm0, ob, xla0)
                    with self._cv:
                        self._failed += 1
                        s._count("failed")
                    req.future.set_exception(
                        QueryFailed(s.sid, req.query_id, e))
                    return
                el.note_resume()
                with self._cv:
                    self._resumed += 1
                    s._count("resumed")
            wall = time.perf_counter() - t0
            self._account(s, wall, cm, comm0, ob, xla0)
            with self._cv:
                self._completed += 1
                s._count("completed")
            req.future.set_result(out)
            # background grow: a shrunk gang re-admits replacement
            # capacity at the next query boundary
            el = _mod("bodo_tpu.runtime.elastic")
            if el is not None:
                try:
                    el.note_query_boundary()
                except Exception:  # noqa: BLE001
                    pass
        finally:
            if grant is not None:
                try:
                    grant.release()
                except Exception:  # noqa: BLE001
                    pass
            _session_ctx.reset(token)

    def _run_in_span(self, req: _Request):
        """Execute the thunk under a tracing query span (when tracing is
        on) so EXPLAIN/trace records carry the query id the session tag
        attaches to."""
        tr = _mod("bodo_tpu.utils.tracing")
        if tr is not None:
            try:
                if tr.is_tracing() and tr.current_query_id() is None:
                    with tr.query_span() as qid:
                        req.query_id = qid
                        return req.fn(), qid
            except ServeRejection:
                raise
            except Exception:  # noqa: BLE001 - span plumbing only
                pass
        return req.fn(), req.query_id

    def _session_grant(self, s: Session):
        """Partitioned governor accounting: while a session's query
        runs it holds a small named grant (``session:<sid>``) so the
        governor's operator table shows who is on the gang; enforcement
        stays with the per-operator grants and the cache's fair share
        (a large reservation here would double-charge the same bytes)."""
        if not config.mem_governor:
            return None
        mg = _mod("bodo_tpu.runtime.memory_governor")
        if mg is None:
            return None
        try:
            return mg.governor().admit(f"session:{s.sid}", want=1,
                                       wait=False)
        except Exception:  # noqa: BLE001 - accounting is best-effort
            return None

    def _account(self, s: Session, wall: float, cm, comm0, ob,
                 xla0) -> None:
        """Post-query attribution: virtual time for fair share, EWMAs
        for the backoff rules, storm-signature ownership."""
        wall = max(wall, 0.0)
        frac = None
        if cm is not None and comm0 is not None:
            try:
                after = cm.stats()
                wait = after["wait_s"] - comm0["wait_s"]
                frac = min(max(wait / wall, 0.0), 1.0) if wall > 1e-9 \
                    else 0.0
            except Exception:  # noqa: BLE001
                frac = None
        storm_sig = None
        if ob is not None and xla0 is not None:
            try:
                head = ob.head()
                if head["compiles"] - xla0["compiles"] > 0:
                    st = ob.storm()
                    if st["storming"]:
                        storm_sig = st["signature"]
            except Exception:  # noqa: BLE001
                storm_sig = None
        with self._cv:
            s.vtime += wall / s.weight
            s.served_s += wall
            a = _EWMA_ALPHA
            s.ewma_query_s = wall if s.ewma_query_s == 0.0 \
                else (1 - a) * s.ewma_query_s + a * wall
            if frac is not None:
                s.ewma_comm_wait_frac = \
                    (1 - a) * s.ewma_comm_wait_frac + a * frac
            if storm_sig:
                s.note_storm(storm_sig)

    # -- lifecycle / introspection ----------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every queued/running request finished (True) or
        the timeout expired (False)."""
        deadline = time.monotonic() + timeout
        with self._cv:
            while self._pending > 0 or self._running > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.1))
        return True

    def stop(self) -> None:
        """Stop the worker pool; queued work stays queued and resumes
        on the next submit (which restarts workers)."""
        with self._cv:
            stop = self._stop
            workers = list(self._workers)
            self._workers = []
        stop.set()
        with self._cv:
            self._cv.notify_all()
        for t in workers:
            if t.is_alive():
                t.join(timeout=2.0)

    def reset(self) -> None:
        """Tests: stop workers, fail queued futures, drop sessions."""
        self.stop()
        with self._cv:
            sessions = list(self._sessions.values())
            self._sessions.clear()
            self._pending = 0
            self._decisions.clear()
            self._completed = 0
            self._failed = 0
            self._resumed = 0
            self._sig_cache = None
        for s in sessions:
            for req in s.queue:
                req.future.set_exception(Overloaded(
                    "scheduler reset", reason="reset"))
            s.queue.clear()

    def reconfigure(self) -> None:
        """config.set_config hook: re-size the worker pool and drop the
        signal snapshot so new thresholds apply to the next submit."""
        with self._cv:
            self._sig_cache = None
        if self._workers:
            self._ensure_workers()

    def stats(self) -> dict:
        with self._cv:
            return {
                "sessions": len(self._sessions),
                "queued": self._pending,
                "running": self._running,
                "workers": len([t for t in self._workers
                                if t.is_alive()]),
                "completed": self._completed,
                "failed": self._failed,
                "resumed": self._resumed,
                "decisions": dict(self._decisions),
                "by_session": {sid: {
                    "queued": len(s.queue),
                    "weight": s.weight,
                    "vtime_s": round(s.vtime, 6),
                    "served_s": round(s.served_s, 6),
                    "ewma_query_s": round(s.ewma_query_s, 6),
                    "ewma_comm_wait_frac":
                        round(s.ewma_comm_wait_frac, 4),
                    "counters": dict(s.counters),
                } for sid, s in sorted(self._sessions.items())},
            }


# --------------------------------------------------------------------------
# module singleton + session context
# --------------------------------------------------------------------------

# the executing query's session id; worker threads set it around the
# thunk, so everything under plan/physical.execute can attribute
_session_ctx: ContextVar = ContextVar("bodo_tpu_session", default=None)

_scheduler: Optional[Scheduler] = None
_sched_mu = threading.Lock()


def scheduler() -> Scheduler:
    global _scheduler
    with _sched_mu:
        if _scheduler is None:
            _scheduler = Scheduler()
        return _scheduler


def current_session() -> Optional[str]:
    """Session id of the executing query, or None outside the serving
    layer (single-tenant callers behave exactly as before). Lower
    layers read this via sys.modules.get — never import-forcing."""
    return _session_ctx.get()


class session_scope:
    """Attribute work on the CALLING thread to a session (tests and
    clients that bypass the worker pool)."""

    def __init__(self, sid: str):
        self.sid = sid
        self._token = None

    def __enter__(self):
        self._token = _session_ctx.set(self.sid)
        return self.sid

    def __exit__(self, *exc):
        _session_ctx.reset(self._token)
        return False


def reconfigure() -> None:
    """config.set_config hook (serve_* keys)."""
    with _sched_mu:
        sched = _scheduler
    if sched is not None:
        sched.reconfigure()


def reset() -> None:
    """Tests: tear down the singleton scheduler."""
    global _scheduler
    with _sched_mu:
        sched, _scheduler = _scheduler, None
    if sched is not None:
        sched.reset()


def stats() -> Optional[dict]:
    """Live scheduler stats, or None when no scheduler was created —
    telemetry/metrics read through this (lazily, via sys.modules.get)."""
    with _sched_mu:
        sched = _scheduler
    return sched.stats() if sched is not None else None
