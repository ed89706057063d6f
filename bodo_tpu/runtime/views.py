"""Materialized views & continuous queries: an incremental view DAG on
the serving path.

Named views register a logical plan whose materialization lives in the
semantic result cache (runtime/result_cache.py) under the plan's own
query key. Views reference other views as scan sources (plan.logical
ViewScan), so a cached daily aggregate feeds coarser rollups; a view
scan signs with the view's BASE source signatures, which makes every
dependent's cache key roll over exactly when the underlying data
changes — maintenance then propagates topologically:

  * append to a base table   -> the leaf view's entry splices a delta
                                scan (PR 13 machinery: classify_change /
                                _try_incremental), including in-place
                                grown files (#rg= fragments);
  * mutate of SOME files     -> partition-level invalidation: the
                                entry's per-source-file contribution map
                                re-runs only the affected files' delta
                                plans (_try_partition_refresh);
  * anything ambiguous       -> full invalidation, full recompute —
                                never a stale partial;
  * interior views           -> re-aggregate from their parents' cached
                                materializations (a plain execute whose
                                leaf scans serve at cache speed).

Continuous queries: sessions register standing queries
(``session.subscribe(view, max_staleness_s=)``); idle scheduler workers
poll ``maintenance_due()`` between queue drains, and a detected change
schedules refreshes as ordinary weighted-fair work on the system
maintenance session (tenants are not billed for shared refreshes).
Refreshed results are delivered to subscribers through the same serve
futures every query uses, with per-view staleness tracking.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

from bodo_tpu.config import config
from bodo_tpu.utils.logging import log

#: session id every view refresh is attributed to (result-cache
#: by_session rows, scheduler accounting) — tenants are not billed
MAINTENANCE_SESSION = "__maintenance__"

_STALENESS_SAMPLES = 256   # per-view staleness history for the p99
# Per-source-file contribution maps (partition-level invalidation) are
# built only for datasets of at most this many files — the map costs one
# extra pass over the dataset per materialization.
VIEW_MAX_PARTS = 64


class ViewError(ValueError):
    """Typed registry error (unknown/duplicate view, live dependents)."""


class _View:
    def __init__(self, name: str, plan, root, deps: Tuple[str, ...]):
        self.name = name
        self.plan = plan            # user's logical root (pre-optimize)
        self.root = root            # optimized exec root (stable fp)
        self.schema = dict(root.schema)
        self.deps = deps            # direct parent view names
        self.dependents: set = set()
        self.version = 0            # bumps when a refresh changed data
        self.fp = None              # result-cache plan fingerprint
        self.last_sig_digest = None
        self.base_sigs = None       # qi.sigs snapshot at materialize
        self.lock = threading.RLock()
        self.subs: List["Subscription"] = []
        self.stale_since: Optional[float] = None  # monotonic, watcher
        self.inflight = False
        self.staleness = deque(maxlen=_STALENESS_SAMPLES)
        self.refreshes_full = 0
        self.refreshes_incremental = 0
        self.full_wall_s = 0.0
        self.refresh_wall_s = 0.0


class Subscription:
    """A standing query on one view. ``next(timeout)`` blocks for the
    next refresh and returns the refreshed Table (the underlying
    delivery is the maintenance query's serve Future)."""

    def __init__(self, view_name: str, session_id: str,
                 max_staleness_s: Optional[float]):
        self.view = view_name
        self.session_id = session_id
        self.max_staleness_s = max_staleness_s
        self._cv = threading.Condition()
        self._futures: deque = deque()
        self.cancelled = False

    def _deliver(self, fut) -> None:
        with self._cv:
            if self.cancelled:
                return
            self._futures.append(fut)
            self._cv.notify_all()

    def next(self, timeout: Optional[float] = None):
        """Block until the next refresh lands; returns the refreshed
        Table. Raises TimeoutError when nothing arrives in time."""
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        with self._cv:
            while not self._futures:
                if self.cancelled:
                    raise ViewError(
                        f"subscription on {self.view!r} cancelled")
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    raise TimeoutError(
                        f"no refresh of view {self.view!r} within "
                        f"{timeout}s")
                self._cv.wait(left if left is not None else 0.5)
            fut = self._futures.popleft()
        left = None if deadline is None else \
            max(deadline - time.monotonic(), 0.01)
        return fut.result(timeout=left)

    def cancel(self) -> None:
        with self._cv:
            self.cancelled = True
            self._cv.notify_all()
        _unsubscribe(self)


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

_mu = threading.RLock()
_views: Dict[str, _View] = {}
_c: Dict[str, int] = {}

# watcher state: read LOCK-FREE by idle scheduler workers holding the
# scheduler condition (maintenance_due below) — plain attribute writes
# only, never guarded reads
_next_poll_at = 0.0
_n_subs = 0
_tick_mu = threading.Lock()


def _count(name: str, n: int = 1) -> None:
    with _mu:
        _c[name] = _c.get(name, 0) + n


def _get(name: str) -> _View:
    with _mu:
        v = _views.get(name)
    if v is None:
        raise ViewError(f"unknown view {name!r}")
    return v


def _view_scans(node, out=None):
    from bodo_tpu.plan import logical as L
    if out is None:
        out = []
    if isinstance(node, L.ViewScan):
        out.append(node)
    for c in node.children:
        _view_scans(c, out)
    return out


def _clear_cached(node) -> None:
    """Drop plan-collapse memoization across a held plan tree: a view's
    root is executed repeatedly over CHANGING data, so node._cached
    tables from the previous generation must never short-circuit."""
    node._cached = None
    for c in node.children:
        _clear_cached(c)


def _as_plan(plan):
    """Accept a logical Node or anything carrying one (BodoDataFrame)."""
    from bodo_tpu.plan import logical as L
    if isinstance(plan, L.Node):
        return plan
    inner = getattr(plan, "_plan", None)
    if isinstance(inner, L.Node):
        return inner
    raise TypeError(f"create_view needs a logical plan or a lazy "
                    f"frame, got {type(plan).__name__}")


def create_view(name: str, plan) -> None:
    """Register a named materialized view over ``plan`` (a logical plan
    root or a lazy BodoDataFrame). The plan may scan other views
    (``views.read(name)``); every referenced view must already exist, so
    the registry is a DAG by construction. Materialization is lazy —
    the first read (or the first maintenance refresh) pays it."""
    from bodo_tpu.plan.optimizer import optimize
    root = _as_plan(plan)
    parents = tuple(dict.fromkeys(s.name for s in _view_scans(root)))
    with _mu:
        if name in _views:
            raise ViewError(f"view {name!r} already exists")
        for p in parents:
            if p not in _views:
                raise ViewError(f"view {name!r} references unknown "
                                f"view {p!r}")
        v = _View(name, root, optimize(root), deps=parents)
        _views[name] = v
        for p in parents:
            _views[p].dependents.add(name)
    for p in parents:
        _sync_pin(p)
    _count("created")
    log(1, f"views: created {name!r} over "
           f"{parents or 'base tables'}")


def drop_view(name: str) -> None:
    """Unregister a view; refuses while downstream views depend on it.
    Live subscriptions are cancelled."""
    with _mu:
        v = _views.get(name)
        if v is None:
            raise ViewError(f"unknown view {name!r}")
        if v.dependents:
            raise ViewError(f"view {name!r} has dependents "
                            f"{sorted(v.dependents)}")
        del _views[name]
        for p in v.deps:
            pv = _views.get(p)
            if pv is not None:
                pv.dependents.discard(name)
        subs = list(v.subs)
        v.subs.clear()
    for s in subs:
        with s._cv:
            s.cancelled = True
            s._cv.notify_all()
    _recount_subs()
    for p in v.deps:
        _sync_pin(p)
    if v.fp is not None:
        _rcache().set_view_pin(v.fp, 0)


def list_views() -> List[str]:
    with _mu:
        return sorted(_views)


def scan_node(name: str):
    """A fresh ViewScan leaf for composing this view into a plan."""
    from bodo_tpu.plan import logical as L
    v = _get(name)
    return L.ViewScan(name, v.schema, version=v.version)


def read(name: str):
    """Lazy frame over the view — compose/filter/aggregate like any
    table; execution serves the materialization from the result cache."""
    from bodo_tpu.pandas_api.frame import BodoDataFrame
    return BodoDataFrame(scan_node(name))


def base_sources(name: str):
    """The view's transitive BASE sources in result-cache form
    (tuple of ("pq"|"csv"|"mem", ident)) — what a ViewScan signs as.
    None when any leaf is unsignable."""
    from bodo_tpu.runtime import result_cache as rcache
    v = _get(name)
    out, seen = [], set()

    def walk(view: _View) -> bool:
        srcs = rcache._sources_of(view.root)
        if srcs is None:
            return False
        for s in srcs:
            if s not in seen:
                seen.add(s)
                out.append(s)
        return True

    # _sources_of resolves nested ViewScans back through this function,
    # so walking the root alone already covers the transitive closure
    return tuple(out) if walk(v) else None


# --------------------------------------------------------------------------
# materialization / maintenance
# --------------------------------------------------------------------------

def _rcache():
    from bodo_tpu.runtime import result_cache
    return result_cache.cache()


def _sync_pin(name: str) -> None:
    """Benefit-eviction pin: weight the view's cache entry by its live
    dependent count (downstream views + subscriptions)."""
    with _mu:
        v = _views.get(name)
        if v is None or v.fp is None:
            return
        deps = len(v.dependents) + len(v.subs)
        fp = v.fp
    _rcache().set_view_pin(fp, deps)


def materialized_table(name: str):
    """Current materialization of a view as a Table — the ViewScan
    execution hook (plan/physical.py). Always goes through the cached
    execute boundary: unchanged data is a device cache hit, an append
    splices, a partition mutate re-merges, anything else recomputes."""
    return _materialize(_get(name))


def refresh(name: str):
    """Synchronously bring one view (and its ancestors) up to date."""
    return _materialize(_get(name))


def _materialize(v: _View):
    from bodo_tpu.plan import physical
    from bodo_tpu.runtime import result_cache as rcache
    with v.lock:
        # parents first: this view's execution reads their
        # materializations through ViewScan leaves
        for p in v.deps:
            _materialize(_get(p))
        cache = _rcache()
        before = cache.stats()
        detected = v.stale_since
        _clear_cached(v.root)
        t0 = time.perf_counter()
        t = physical.execute(v.root, optimize_first=False)
        wall = time.perf_counter() - t0
        after = cache.stats()
        with rcache.signature_epoch():
            try:
                qi = cache._query_info(v.root)
            except Exception:  # noqa: BLE001
                qi = None
        changed = qi is not None and \
            qi.key[3] != v.last_sig_digest
        # hit-detection rides the sig digest, NOT q_hits deltas: this
        # view's execute re-enters its parents' ViewScans, and their
        # (expected) cache hits would read as ours
        hit = qi is not None and not changed
        incremental = (after["q_incremental"] >
                       before["q_incremental"]) or \
            (after["partition_refresh"] > before["partition_refresh"])
        if qi is not None:
            if v.fp is None:
                v.fp = qi.fp
            v.base_sigs = qi.sigs
            if changed:
                v.version += 1
                v.last_sig_digest = qi.key[3]
            if not hit:
                if incremental:
                    v.refreshes_incremental += 1
                    v.refresh_wall_s += wall
                else:
                    v.refreshes_full += 1
                    v.full_wall_s += wall
                # contribution map for partition-level invalidation,
                # rebuilt per generation (bounded by VIEW_MAX_PARTS)
                try:
                    cache.build_parts(
                        qi.key, physical._exec,
                        max_parts=VIEW_MAX_PARTS)
                except Exception:  # noqa: BLE001
                    pass
        if changed or v.stale_since is not None:
            v.stale_since = None
            if detected is not None:
                v.staleness.append(
                    max(time.monotonic() - detected, 0.0))
        _sync_pin(v.name)
        return t


# --------------------------------------------------------------------------
# continuous queries: subscriptions + the signature watcher
# --------------------------------------------------------------------------

def subscribe(view: str, *, session=None,
              max_staleness_s: Optional[float] = None) -> Subscription:
    """Register a standing query; used via ``Session.subscribe``. The
    subscriber receives every subsequent refresh of the view through
    ``Subscription.next()``."""
    v = _get(view)
    sid = getattr(session, "sid", None) or "-"
    sub = Subscription(view, sid, max_staleness_s)
    with _mu:
        v.subs.append(sub)
    _recount_subs()
    _sync_pin(view)
    _wake_watcher()   # poll promptly for tight staleness bounds
    return sub


def _unsubscribe(sub: Subscription) -> None:
    with _mu:
        v = _views.get(sub.view)
        if v is not None and sub in v.subs:
            v.subs.remove(sub)
    _recount_subs()
    if v is not None:
        _sync_pin(v.name)


def _recount_subs() -> None:
    global _n_subs
    with _mu:
        _n_subs = sum(len(v.subs) for v in _views.values())


def note_invalidated_paths(paths) -> int:
    """Result-cache invalidation hook (local mutate or a fleet
    ``invalidate`` broadcast): flag every view whose base sources
    intersect ``paths`` as stale, so the next watcher tick (or read)
    refreshes it. Returns views flagged."""
    pset = {str(p) for p in paths}
    flagged = 0
    now = time.monotonic()
    with _mu:
        views = list(_views.values())
    for v in views:
        try:
            srcs = base_sources(v.name)
        except Exception:  # noqa: BLE001
            srcs = None
        if srcs is None:
            continue
        idents = {str(s[1]) for s in srcs}
        # dataset idents are dirs/globs; broadcast paths are files —
        # prefix/containment matches both directions
        hit = bool(idents & pset) or any(
            p.startswith(i.rstrip("/*") + "/") or i in p
            for p in pset for i in idents)
        if hit and v.stale_since is None:
            v.stale_since = now
            flagged += 1
    if flagged:
        _count("flagged_stale", flagged)
        _wake_watcher()
    return flagged


def _wake_watcher() -> None:
    """Writers take _mu; maintenance_due() stays a lock-free read (it
    runs holding the scheduler condition — see scheduler._worker)."""
    global _next_poll_at
    with _mu:
        _next_poll_at = 0.0


def _arm_next_poll() -> None:
    global _next_poll_at
    nxt = time.monotonic() + _poll_interval_s()
    with _mu:
        _next_poll_at = nxt


def _poll_interval_s() -> float:
    base = max(float(config.view_poll_s), 0.05)
    with _mu:
        bounds = [s.max_staleness_s for v in _views.values()
                  for s in v.subs if s.max_staleness_s]
    if bounds:
        base = min(base, max(min(bounds) / 4.0, 0.05))
    return base


def maintenance_due() -> bool:
    """Lock-free check idle scheduler workers run while holding the
    scheduler condition: is it time for a watcher poll?"""
    return _n_subs > 0 and time.monotonic() >= _next_poll_at


def maintenance_tick(sched) -> None:
    """One watcher poll (outside every lock the scheduler holds):
    detect changed base signatures, then schedule a refresh of each
    stale subscribed view as weighted-fair work on the system
    maintenance session. Rejections (queue full, degraded) leave the
    view flagged — the next tick retries."""
    if not _tick_mu.acquire(blocking=False):
        return  # another idle worker is already polling
    try:
        _arm_next_poll()
        _count("ticks")
        from bodo_tpu.runtime import result_cache as rcache
        now = time.monotonic()
        with _mu:
            views = [v for v in _views.values() if v.subs]
        for v in views:
            if v.stale_since is None and v.base_sigs is not None:
                # signature watcher: one stat pass per source
                with rcache.signature_epoch():
                    for kind, ident, _old in v.base_sigs:
                        if rcache._source_sig(kind, ident) != _old:
                            v.stale_since = now
                            _count("detected_stale")
                            break
            if v.stale_since is None or v.inflight:
                continue
            self_v = v

            def job(v=self_v):
                try:
                    return _materialize(v)
                finally:
                    v.inflight = False

            try:
                sess = sched.session(
                    MAINTENANCE_SESSION,
                    priority=float(config.view_maintenance_weight))
                v.inflight = True
                fut = sess.submit(job)
            except Exception:  # noqa: BLE001 - typed rejection: retry
                v.inflight = False
                _count("refresh_rejected")
                continue
            _count("refresh_scheduled")
            with _mu:
                subs = list(v.subs)
            for sub in subs:
                sub._deliver(fut)
    finally:
        _tick_mu.release()


# --------------------------------------------------------------------------
# observability / lifecycle
# --------------------------------------------------------------------------

def _depth(v: _View, memo: Dict[str, int]) -> int:
    got = memo.get(v.name)
    if got is not None:
        return got
    d = 1 + max((_depth(_views[p], memo) for p in v.deps
                 if p in _views), default=0)
    memo[v.name] = d
    return d


def stats() -> dict:
    """Registry + maintenance stats (telemetry/doctor/metrics read
    through this; lazy-module rule applies on their side)."""
    with _mu:
        memo: Dict[str, int] = {}
        by = {}
        lagging, lag_p99 = None, -1.0
        ref_wall = full_wall = 0.0
        n_inc = n_full = 0
        for name, v in sorted(_views.items()):
            hist = sorted(v.staleness)
            p99 = hist[min(int(len(hist) * 0.99),
                           len(hist) - 1)] if hist else 0.0
            cur = (time.monotonic() - v.stale_since) \
                if v.stale_since is not None else 0.0
            worst = max(p99, cur)
            if worst > lag_p99:
                lagging, lag_p99 = name, worst
            ref_wall += v.refresh_wall_s
            full_wall += v.full_wall_s
            n_inc += v.refreshes_incremental
            n_full += v.refreshes_full
            by[name] = {
                "version": v.version,
                "depth": _depth(v, memo),
                "deps": sorted(v.deps),
                "dependents": sorted(v.dependents),
                "subscriptions": len(v.subs),
                "stale": v.stale_since is not None,
                "staleness_p99_s": round(p99, 6),
                "refreshes_incremental": v.refreshes_incremental,
                "refreshes_full": v.refreshes_full,
            }
        out = {k: int(n) for k, n in _c.items()}
        n_ref = n_inc + max(n_full - len(by), 0)  # first fulls excluded
        out.update(
            n_views=len(by),
            dag_depth=max(memo.values(), default=0),
            subscriptions=_n_subs,
            refreshes_incremental=n_inc,
            refreshes_full=n_full,
            # refresh cost relative to full recompute cost; 0.0 until a
            # refresh has happened
            refresh_ratio=round(ref_wall / full_wall, 6)
            if full_wall > 0 and n_ref > 0 else 0.0,
            staleness_p99_s=round(max(lag_p99, 0.0), 6),
            lagging_view=lagging,
            by_view=by,
        )
        return out


def reset() -> None:
    """Tests: drop every view, subscription, pin and counter."""
    global _next_poll_at, _n_subs
    with _mu:
        views = list(_views.values())
        _views.clear()
        _c.clear()
        _n_subs = 0
        _next_poll_at = 0.0
    for v in views:
        for s in v.subs:
            with s._cv:
                s.cancelled = True
                s._cv.notify_all()
    try:
        _rcache().clear_view_pins()
    except Exception:  # noqa: BLE001
        pass
