"""Compile & device-memory observatory: the engine's ledger of XLA
executables and device buffers.

Three subsystems grew independent compile budgets (whole-stage fusion,
device-side parquet decode, bounded_jit) because the XLA:CPU
many-executables cliff was hit blind; device memory was attributed to
nothing. This module unifies both resources behind one registry:

* **Program registry** — every jit entry point (bounded_jit wrappers,
  FusionProgramCache, DecodeProgramCache, the host-level jax.jit sites
  in relational.py/ops/) registers each compiled executable here with a
  structural signature split into named *facets* (mesh, dtype, shape,
  donation flag, ...), its source subsystem, compile wall, dispatch
  count and last-used stamp.

* **Retrace attribution** — a registration whose (subsystem, base)
  was seen before is a retrace; diffing the facet dicts names the
  cause (shape-bucket-churn, dtype-churn, mesh-change, donation-flag,
  weak-type-promotion, ...). A sliding-window storm detector flags a
  signature compiling repeatedly (telemetry sampler, /healthz, doctor).

* **Unified compile budget** — `BODO_TPU_XLA_MAX_EXECUTABLES` caps
  process-wide compiles; the legacy per-subsystem knobs
  (`BODO_TPU_FUSION_MAX_COMPILES`, `BODO_TPU_DEVICE_DECODE_MAX_COMPILES`)
  remain as sub-caps. Fusion and decode spend through `try_spend()`.

* **Device-buffer ledger** — `track_buffer`/`track_table` hook buffer
  creation (arrow ingest, fused-stage outputs, device decode) and a
  `weakref.finalize` per buffer hooks the free, attributing live device
  bytes to (query_id, operator). `verify_donation` proves a donated
  input was actually freed by the dispatch; `finish_query` runs the
  leak check at tracing.query_span() exit.

Import rules: stdlib only at module level — this module must be
importable from a /metrics scrape without dragging in jax. Consumers
that must never force *this* module to load read it via
`sys.modules.get` (metrics/telemetry/tracing); the jit call sites
import it directly (cheap).
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
import weakref
from collections import OrderedDict, deque
from typing import Any, Dict, Optional, Tuple

# RLock: buffer finalizers can fire during gc triggered while this
# module already holds the lock on the same thread.
_lock = threading.RLock()


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, str(default)))
    except (TypeError, ValueError):
        return default


# ---------------------------------------------------------------------------
# enable toggle

_enabled = os.environ.get("BODO_TPU_XLA_OBSERVATORY", "1").lower() \
    not in ("0", "false", "off")


def enabled() -> bool:
    return _enabled


def set_enabled(on: bool) -> None:
    """Toggle registry + ledger accounting (budgets stay enforced)."""
    global _enabled
    with _lock:
        _enabled = bool(on)


# ---------------------------------------------------------------------------
# unified compile budget

# Legacy per-subsystem knobs survive as sub-caps; the unified pool
# defaults to their sum so default behavior is unchanged. <0 disables.
_SUB_CAPS: Dict[str, int] = {
    "fusion": _env_int("BODO_TPU_FUSION_MAX_COMPILES", 128),
    "device_decode": _env_int("BODO_TPU_DEVICE_DECODE_MAX_COMPILES", 64),
}


def _default_pool() -> int:
    caps = [c for c in _SUB_CAPS.values()]
    if any(c < 0 for c in caps):
        return -1  # any uncapped subsystem => pool uncapped by default
    return sum(caps)


_pool_cap = _env_int("BODO_TPU_XLA_MAX_EXECUTABLES", _default_pool())
_spent: Dict[str, int] = {}
_budget_denials: Dict[str, int] = {}


def try_spend(subsystem: str) -> bool:
    """Consume one unit of the unified compile budget for `subsystem`.

    Returns False when either the subsystem's legacy sub-cap or the
    unified `BODO_TPU_XLA_MAX_EXECUTABLES` pool is exhausted; the
    caller falls back (fusion -> unfused, decode -> host decode)."""
    with _lock:
        sub_cap = _SUB_CAPS.get(subsystem, -1)
        used = _spent.get(subsystem, 0)
        if sub_cap >= 0 and used >= sub_cap:
            _budget_denials[subsystem] = \
                _budget_denials.get(subsystem, 0) + 1
            return False
        if _pool_cap >= 0 and sum(_spent.values()) >= _pool_cap:
            _budget_denials[subsystem] = \
                _budget_denials.get(subsystem, 0) + 1
            return False
        _spent[subsystem] = used + 1
        return True


def reset_budget(subsystem: Optional[str] = None) -> None:
    """Return a subsystem's spend to the pool (its program cache was
    cleared, so its executables were released); None resets all."""
    with _lock:
        if subsystem is None:
            _spent.clear()
            _budget_denials.clear()
        else:
            _spent.pop(subsystem, None)
            _budget_denials.pop(subsystem, None)


def budget() -> dict:
    with _lock:
        spent = sum(_spent.values())
        return {
            "pool_cap": _pool_cap,
            "spent": spent,
            "remaining": (_pool_cap - spent) if _pool_cap >= 0 else -1,
            "per_subsystem": dict(_spent),
            "sub_caps": dict(_SUB_CAPS),
            "denials": dict(_budget_denials),
        }


def subsystem_budget_left(subsystem: str) -> int:
    """Units the subsystem could still spend (min of sub-cap and pool
    headroom); -1 when unlimited. Feeds legacy `budget_left` stats."""
    with _lock:
        sub_cap = _SUB_CAPS.get(subsystem, -1)
        used = _spent.get(subsystem, 0)
        heads = []
        if sub_cap >= 0:
            heads.append(max(0, sub_cap - used))
        if _pool_cap >= 0:
            heads.append(max(0, _pool_cap - sum(_spent.values())))
        return min(heads) if heads else -1


# ---------------------------------------------------------------------------
# program registry

_MAX_RECORDS = _env_int("BODO_TPU_XLA_MAX_RECORDS", 4096)

# retrace-cause taxonomy, checked in priority order: the first facet
# that differs names the cause.
_CAUSE_BY_FACET = (
    ("mesh", "mesh-change"),
    ("donate", "donation-flag"),
    ("weak_type", "weak-type-promotion"),
    ("dtype", "dtype-churn"),
    ("shape", "shape-bucket-churn"),
    ("dist", "distribution-change"),
    ("schema", "schema-change"),
    ("steps", "plan-change"),
    ("static", "static-arg-churn"),
    ("tree", "pytree-structure-change"),
)


class ProgramRecord:
    __slots__ = ("handle", "subsystem", "base", "facets", "compile_s",
                 "flops", "bytes_accessed", "dispatches", "created",
                 "last_used", "donated", "retrace_cause", "alive",
                 "progcheck")

    def __init__(self, handle: int, subsystem: str, base: str,
                 facets: Dict[str, Any], donated: bool,
                 retrace_cause: Optional[str]):
        self.handle = handle
        self.subsystem = subsystem
        self.base = base
        self.facets = facets
        self.compile_s = 0.0
        self.flops = 0.0
        self.bytes_accessed = 0.0
        self.dispatches = 0
        self.created = time.time()
        self.last_used = self.created
        self.donated = donated
        self.retrace_cause = retrace_cause
        self.alive = True
        self.progcheck = None  # verifier verdict (note_progcheck)

    def to_dict(self) -> dict:
        out = {
            "handle": self.handle, "subsystem": self.subsystem,
            "base": self.base,
            "facets": {k: repr(v)[:120] for k, v in self.facets.items()},
            "compile_s": round(self.compile_s, 6),
            "flops": self.flops, "bytes_accessed": self.bytes_accessed,
            "dispatches": self.dispatches,
            "created": self.created, "last_used": self.last_used,
            "donated": self.donated,
            "retrace_cause": self.retrace_cause, "alive": self.alive,
        }
        if self.progcheck is not None:
            out["progcheck"] = self.progcheck
        return out


_records: "OrderedDict[int, ProgramRecord]" = OrderedDict()
_latest_by_base: Dict[Tuple[str, str], int] = {}
_next_handle = 0
_retraces: Dict[str, int] = {}
_last_cause: Optional[str] = None
_totals = {"compiles": 0, "dispatches": 0, "evicted": 0,
           "compile_s": 0.0}

# recompile-storm detector: sliding window of compile events
_STORM_WINDOW_S = float(os.environ.get("BODO_TPU_XLA_STORM_WINDOW_S",
                                       "60"))
_STORM_THRESHOLD = _env_int("BODO_TPU_XLA_STORM_THRESHOLD", 8)
_compile_events: "deque[Tuple[float, Tuple[str, str]]]" = deque(
    maxlen=1024)


def _diff_cause(old: Dict[str, Any], new: Dict[str, Any]) -> str:
    for facet, cause in _CAUSE_BY_FACET:
        if old.get(facet) != new.get(facet):
            return cause
    for k in set(old) | set(new):
        if old.get(k) != new.get(k):
            return f"{k}-change"
    return "evicted-recompile"  # identical facets: prior was evicted


def register(subsystem: str, base: str,
             facets: Optional[Dict[str, Any]] = None, *,
             donated: bool = False) -> int:
    """Record one freshly compiled executable; returns a handle for
    touch()/note_compile()/mark_evicted(). Handle 0 = disabled."""
    global _next_handle, _last_cause
    if not _enabled:
        return 0
    facets = facets or {}
    with _lock:
        _next_handle += 1
        handle = _next_handle
        cause = None
        prev = _latest_by_base.get((subsystem, base))
        if prev is not None:
            prev_rec = _records.get(prev)
            if prev_rec is not None:
                cause = _diff_cause(prev_rec.facets, facets)
            else:
                cause = "evicted-recompile"
            _retraces[cause] = _retraces.get(cause, 0) + 1
            _last_cause = cause
        rec = ProgramRecord(handle, subsystem, base, facets, donated,
                            cause)
        _records[handle] = rec
        _latest_by_base[(subsystem, base)] = handle
        _totals["compiles"] += 1
        _compile_events.append((time.monotonic(), (subsystem, base)))
        while len(_records) > _MAX_RECORDS:
            _records.popitem(last=False)
        return handle


def touch(handle: int) -> None:
    """One dispatch of an already-registered executable."""
    if not handle or not _enabled:
        return
    with _lock:
        rec = _records.get(handle)
        if rec is not None:
            rec.dispatches += 1
            rec.last_used = time.time()
        _totals["dispatches"] += 1


def note_compile(handle: int, seconds: float) -> None:
    """Attribute measured compile wall to a registered executable."""
    with _lock:
        _totals["compile_s"] += float(seconds)
        rec = _records.get(handle)
        if rec is not None:
            rec.compile_s += float(seconds)


def note_progcheck(handle: int, info: dict) -> None:
    """Attach the static verifier's verdict (analysis/progcheck.py) to
    a registered executable: collective manifest, rank-invariance,
    static HBM peak, violations. Flows into registry dumps and
    flight-recorder bundles, where doctor's triage reads it."""
    if not handle:
        return
    with _lock:
        rec = _records.get(handle)
        if rec is not None:
            rec.progcheck = dict(info)


def note_cost(handle: int, flops: float = 0.0,
              bytes_accessed: float = 0.0) -> None:
    """Attach XLA cost-analysis numbers (best-effort; callers only
    compute them when BODO_TPU_XLA_COST_ANALYSIS is on)."""
    with _lock:
        rec = _records.get(handle)
        if rec is not None:
            rec.flops = float(flops)
            rec.bytes_accessed = float(bytes_accessed)


_COST_ANALYSIS = os.environ.get("BODO_TPU_XLA_COST_ANALYSIS", "0") \
    .lower() in ("1", "true", "on")


def cost_analysis_enabled() -> bool:
    return _COST_ANALYSIS


def mark_evicted(handle: int) -> None:
    """The owning cache dropped this executable (LRU/clear)."""
    if not handle:
        return
    with _lock:
        rec = _records.get(handle)
        if rec is not None and rec.alive:
            rec.alive = False
            _totals["evicted"] += 1


def storm() -> dict:
    """Sliding-window recompile-storm check: the hottest (subsystem,
    base) signature and whether it crossed the threshold."""
    now = time.monotonic()
    with _lock:
        while _compile_events and \
                now - _compile_events[0][0] > _STORM_WINDOW_S:
            _compile_events.popleft()
        counts: Dict[Tuple[str, str], int] = {}
        for _, sig in _compile_events:
            counts[sig] = counts.get(sig, 0) + 1
    if not counts:
        return {"storming": False, "signature": None,
                "compiles_in_window": 0,
                "window_s": _STORM_WINDOW_S,
                "threshold": _STORM_THRESHOLD}
    sig, n = max(counts.items(), key=lambda kv: kv[1])
    return {"storming": n >= _STORM_THRESHOLD,
            "signature": f"{sig[0]}:{sig[1]}", "compiles_in_window": n,
            "window_s": _STORM_WINDOW_S, "threshold": _STORM_THRESHOLD}


# ---------------------------------------------------------------------------
# facet extraction helpers (callers pass raw cache keys)

def _short(obj: Any) -> str:
    """Stable short fingerprint for a facet value too bulky to keep."""
    try:
        h = hash(obj)
    except TypeError:
        h = hash(repr(obj))
    return f"{h & 0xffffffff:08x}"


def _looks_schema(part: Any) -> bool:
    return (isinstance(part, tuple) and len(part) > 0
            and all(isinstance(p, tuple) and len(p) == 4
                    and isinstance(p[0], str) for p in part))


def _looks_mesh(part: Any) -> bool:
    return (isinstance(part, tuple) and len(part) == 2
            and isinstance(part[0], tuple) and len(part[0]) > 0
            and all(isinstance(d, int) for d in part[0])
            and isinstance(part[1], tuple)
            and all(isinstance(a, str) for a in part[1]))


def facets_from_sig(key: Any) -> Dict[str, Any]:
    """Best-effort facet split for a relational-style cache key: a
    tuple whose first element is the kind string, followed by schema
    tuples, "1D"/"REP" distribution markers, mesh keys and opaque
    static parts."""
    f: Dict[str, Any] = {}
    extras = []
    parts = key[1:] if isinstance(key, tuple) and key else ()
    for part in parts:
        if part in ("1D", "REP") and "dist" not in f:
            f["dist"] = part
        elif _looks_mesh(part) and "mesh" not in f:
            f["mesh"] = _short(part)
        elif _looks_schema(part) and "schema" not in f:
            f["schema"] = _short(part)
            f["dtype"] = tuple(p[1] for p in part)
        elif isinstance(part, bool) and "donate" not in f:
            f["donate"] = part
        else:
            extras.append(_short(part))
    if extras:
        f["static"] = tuple(extras)
    return f


def facets_from_leaves(struct: Any, leaf_keys: Tuple) -> Dict[str, Any]:
    """Facets for a bounded_jit key: ("a", shape, dtype) array leaves
    and ("v", value) static leaves."""
    shapes, dtypes, static = [], [], []
    for lk in leaf_keys:
        if lk and lk[0] == "a":
            shapes.append(lk[1])
            dtypes.append(lk[2])
        else:
            static.append(_short(lk[1:]))
    return {"shape": tuple(shapes), "dtype": tuple(dtypes),
            "static": tuple(static), "tree": _short(struct)}


# ---------------------------------------------------------------------------
# device-buffer ledger

_live: Dict[int, Tuple[int, Optional[str], str]] = {}  # id -> (nbytes, qid, op)
_ledger = {"created_bytes": 0, "freed_bytes": 0,
           "created_buffers": 0, "freed_buffers": 0,
           # high-water mark of live tracked bytes — what progcheck's
           # static HBM estimates are judged against
           "peak_live_bytes": 0}
_by_op: Dict[str, Dict[str, int]] = {}
_MAX_QUERY_REPORTS = 256
_by_query: "OrderedDict[str, Dict[str, Any]]" = OrderedDict()
_donation = {"verified": 0, "copied": 0}


def _query_entry(qid: Optional[str]) -> Dict[str, Any]:
    # callers hold _lock (track_buffer / finish_query critical sections)
    key = qid or "-"
    ent = _by_query.get(key)
    if ent is None:
        ent = {"created_bytes": 0, "freed_bytes": 0, "buffers": 0,
               "by_op": {}, "finished": False}
        # shardcheck: ignore[unlocked-shared-state]
        _by_query[key] = ent
        while len(_by_query) > _MAX_QUERY_REPORTS:
            # shardcheck: ignore[unlocked-shared-state]
            _by_query.popitem(last=False)
    return ent


def _current_qid() -> Optional[str]:
    tr = sys.modules.get("bodo_tpu.utils.tracing")
    if tr is not None:
        try:
            return tr.current_query_id()
        except Exception:
            return None
    return None


def _on_free(key: int) -> None:
    with _lock:
        ent = _live.pop(key, None)
        if ent is None:
            return
        nbytes, qid, op = ent
        _ledger["freed_bytes"] += nbytes
        _ledger["freed_buffers"] += 1
        ops = _by_op.get(op)
        if ops is not None:
            ops["freed_bytes"] += nbytes
            ops["live_buffers"] -= 1
        q = _by_query.get(qid or "-")
        if q is not None:
            q["freed_bytes"] += nbytes
            qo = q["by_op"].get(op)
            if qo is not None:
                qo["freed"] += nbytes


def track_buffer(arr: Any, op: str,
                 query_id: Optional[str] = None) -> bool:
    """Account one device buffer's creation to (query, operator); a
    weakref finalizer accounts the free. Tracers and non-weakrefable
    values are skipped. Returns True when tracked."""
    if not _enabled or arr is None:
        return False
    nbytes = getattr(arr, "nbytes", 0)
    if not isinstance(nbytes, int) or nbytes <= 0:
        return False
    # concrete device arrays only: tracers lack is_deleted
    if not hasattr(arr, "is_deleted"):
        return False
    key = id(arr)
    with _lock:
        if key in _live:
            return False
    try:
        weakref.finalize(arr, _on_free, key)
    except TypeError:
        return False
    qid = query_id if query_id is not None else _current_qid()
    with _lock:
        _live[key] = (nbytes, qid, op)
        _ledger["created_bytes"] += nbytes
        _ledger["created_buffers"] += 1
        live = _ledger["created_bytes"] - _ledger["freed_bytes"]
        if live > _ledger["peak_live_bytes"]:
            _ledger["peak_live_bytes"] = live
        ops = _by_op.setdefault(op, {"created_bytes": 0,
                                     "freed_bytes": 0,
                                     "live_buffers": 0})
        ops["created_bytes"] += nbytes
        ops["live_buffers"] += 1
        q = _query_entry(qid)
        q["created_bytes"] += nbytes
        q["buffers"] += 1
        q["by_op"].setdefault(op, {"created": 0, "freed": 0})
        q["by_op"][op]["created"] += nbytes
    return True


def track_table(t: Any, op: str,
                query_id: Optional[str] = None) -> int:
    """Track every column buffer (data + validity) of a Table."""
    if not _enabled or t is None:
        return 0
    n = 0
    try:
        cols = t.columns.values()
    except AttributeError:
        return 0
    for col in cols:
        if track_buffer(getattr(col, "data", None), op, query_id):
            n += 1
        if track_buffer(getattr(col, "valid", None), op, query_id):
            n += 1
    return n


def mark_deleted(arr: Any) -> None:
    """A dispatch donated this buffer: its device memory is gone even
    though the Python object survives. Accounts the free now; the
    later weakref finalizer becomes a no-op."""
    _on_free(id(arr))


def verify_donation(t: Any) -> bool:
    """After a donated dispatch, check the donated input's buffers were
    actually consumed by XLA (`is_deleted()`). Freed buffers are
    released from the ledger immediately; a False return means the
    runtime silently copied instead of donating."""
    deleted, total = 0, 0
    try:
        cols = list(t.columns.values())
    except AttributeError:
        cols = []
    for col in cols:
        for arr in (getattr(col, "data", None),
                    getattr(col, "valid", None)):
            if arr is None or not hasattr(arr, "is_deleted"):
                continue
            total += 1
            try:
                if arr.is_deleted():
                    deleted += 1
                    mark_deleted(arr)
            except Exception:
                pass
    ok = total > 0 and deleted == total
    with _lock:
        if ok:
            _donation["verified"] += 1
        else:
            _donation["copied"] += 1
    return ok


def live_bytes() -> int:
    with _lock:
        return _ledger["created_bytes"] - _ledger["freed_bytes"]


def finish_query(qid: Optional[str]) -> dict:
    """Leak check at query_span exit: per-query created/freed/live
    device bytes. `live` > 0 is *occupancy* (results the caller still
    holds), not necessarily a leak — the caller decides."""
    with _lock:
        ent = _by_query.get(qid or "-")
        if ent is None:
            return {"query_id": qid, "created_bytes": 0,
                    "freed_bytes": 0, "live_bytes": 0, "buffers": 0}
        ent["finished"] = True
        return {"query_id": qid,
                "created_bytes": ent["created_bytes"],
                "freed_bytes": ent["freed_bytes"],
                "live_bytes": ent["created_bytes"] - ent["freed_bytes"],
                "buffers": ent["buffers"],
                "by_op": {k: dict(v) for k, v in ent["by_op"].items()}}


def query_report(qid: Optional[str] = None) -> dict:
    return finish_query(qid) if qid else ledger_stats()


def leak_check(collect: bool = True) -> dict:
    """Force a gc pass (finalizers fire) and report what stayed live,
    grouped by op — the flight recorder's leak triage reads this."""
    if collect:
        gc.collect()
    with _lock:
        by_op: Dict[str, int] = {}
        for nbytes, _qid, op in _live.values():
            by_op[op] = by_op.get(op, 0) + nbytes
        return {"live_bytes": _ledger["created_bytes"]
                - _ledger["freed_bytes"],
                "live_buffers": len(_live),
                "by_op": dict(sorted(by_op.items(),
                                     key=lambda kv: -kv[1]))}


def ledger_stats() -> dict:
    with _lock:
        return {
            "created_bytes": _ledger["created_bytes"],
            "freed_bytes": _ledger["freed_bytes"],
            "live_bytes": _ledger["created_bytes"]
            - _ledger["freed_bytes"],
            "created_buffers": _ledger["created_buffers"],
            "freed_buffers": _ledger["freed_buffers"],
            "peak_live_bytes": _ledger["peak_live_bytes"],
            "live_buffers": len(_live),
            "by_op": {k: dict(v) for k, v in _by_op.items()},
            "donation": dict(_donation),
        }


# ---------------------------------------------------------------------------
# snapshots & dumps

def head() -> dict:
    """Cheap snapshot for per-node deltas (physical executor)."""
    with _lock:
        return {"compiles": _totals["compiles"],
                "dispatches": _totals["dispatches"],
                "retraces": sum(_retraces.values()),
                "last_cause": _last_cause,
                "live_bytes": _ledger["created_bytes"]
                - _ledger["freed_bytes"]}


def stats() -> dict:
    """Full summary: registry counts, retrace taxonomy, budget, storm
    state and the device ledger — what telemetry.sample() embeds."""
    with _lock:
        alive = sum(1 for r in _records.values() if r.alive)
        by_sub: Dict[str, Dict[str, Any]] = {}
        for r in _records.values():
            s = by_sub.setdefault(r.subsystem,
                                  {"executables": 0, "alive": 0,
                                   "compile_s": 0.0, "dispatches": 0})
            s["executables"] += 1
            s["alive"] += 1 if r.alive else 0
            s["compile_s"] += r.compile_s
            s["dispatches"] += r.dispatches
        pc_programs = pc_violations = 0
        for r in _records.values():
            if r.progcheck is not None:
                pc_programs += 1
                pc_violations += len(r.progcheck.get("violations", ()))
        summary = {
            "executables": len(_records), "alive": alive,
            "progcheck": {"programs": pc_programs,
                          "violations": pc_violations},
            "compiles": _totals["compiles"],
            "dispatches": _totals["dispatches"],
            "evicted": _totals["evicted"],
            "compile_s": round(_totals["compile_s"], 6),
            "retraces": dict(_retraces),
            "retraces_total": sum(_retraces.values()),
            "by_subsystem": {k: {**v,
                                 "compile_s": round(v["compile_s"], 6)}
                             for k, v in by_sub.items()},
        }
    summary["budget"] = budget()
    summary["storm"] = storm()
    summary["ledger"] = ledger_stats()
    return summary


def registry_dump(limit: Optional[int] = None) -> list:
    """Per-program records, most recent first (flight-recorder bundles
    embed this as xla_registry.json)."""
    with _lock:
        recs = [r.to_dict() for r in reversed(_records.values())]
    return recs[:limit] if limit else recs


def top_programs(n: int = 5, key: str = "compile_s") -> list:
    with _lock:
        recs = sorted(_records.values(),
                      key=lambda r: -getattr(r, key, 0.0))
        return [r.to_dict() for r in recs[:n]]


def reset() -> None:
    """Full teardown (runtests.py group teardown + test isolation)."""
    global _next_handle, _last_cause
    with _lock:
        _last_cause = None
        _records.clear()
        _latest_by_base.clear()
        _retraces.clear()
        _compile_events.clear()
        _next_handle = 0
        for k in _totals:
            _totals[k] = 0.0 if k == "compile_s" else 0
        _spent.clear()
        _budget_denials.clear()
        _live.clear()
        for k in _ledger:
            _ledger[k] = 0
        _by_op.clear()
        _by_query.clear()
        for k in _donation:
            _donation[k] = 0
