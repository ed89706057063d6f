"""Chrome-trace event tracing + per-operator / per-query profile.

Analogue of the reference's tracing/profiling stack
(bodo/utils/tracing.pyx Event/dump — Chrome trace JSON;
bodo/libs/_query_profile_collector.h per-operator TIMER/STAT metrics).
Enabled via BODO_TPU_TRACING_LEVEL >= 1 (config.tracing_level); the plan
executor wraps every physical operator in an event, so `dump()` yields a
chrome://tracing-loadable timeline and `profile()` the per-operator
aggregate table.

Device traces: whatever the tracing level, `event()` also enters a
`jax.profiler.TraceAnnotation` named `bodo:<name>` while a profiler
session listens (and costs one inactive-check while none does), so any
`jax.profiler` trace shows the engine's spans on the device events'
clock, nested by thread. `event()` is the one span entry point: new
host timings belong in it, not in another clock beside it.

Query scoping: a `query_span()` context assigns every event inside it a
query id (contextvar; exported as BODO_TPU_QUERY_ID so spawned gang
workers inherit the same identity), and the per-operator aggregates are
additionally keyed per query — `profile(query_id=...)` / `top_ops()`
answer "where did THIS query's time go", the accounting unit the
multi-tenant serving layer (ROADMAP item 2) schedules by.

Clock discipline: every event derives BOTH its timestamp and duration
from `time.perf_counter()` against one per-process wall-clock anchor
captured at import — timestamps are epoch-comparable across the ranks
of a gang (for `merge_trace_shards`) while durations stay monotonic.
Thread ids are mapped through a stable small-int table (raw
`threading.get_ident()` values are reused by the OS and collide when
truncated).

The event list is a ring buffer (BODO_TPU_TRACE_EVENTS_MAX, drop-oldest)
so long-running sessions cannot leak; dropped events are counted and
reported in `dump()`.

Counter-valued profile rows (`mem:`/`resil:`/`aqe:`/`io:`/`lint:`/
`lockstep:`/`cache:`) are read from the unified metrics registry
(utils/metrics.py `sync_engine_metrics`), which is also what the
Prometheus exposition serves.
"""

from __future__ import annotations

import contextlib
import contextvars
import glob as _glob
import json
import os
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from bodo_tpu.config import config

_lock = threading.Lock()

# one clock anchor per process: ts AND dur derive from perf_counter so a
# ts is never skewed against its own duration; the wall part makes ts
# epoch-comparable across the ranks of a gang
_ANCHOR_WALL = time.time()
_ANCHOR_PERF = time.perf_counter()


def _ts_us(perf_t: float) -> float:
    return (_ANCHOR_WALL + (perf_t - _ANCHOR_PERF)) * 1e6


def _new_events() -> deque:
    n = max(int(config.trace_events_max), 1)
    return deque(maxlen=n)


_events: deque = _new_events()
_dropped = 0
# per-(query, operator) aggregates; query None = outside any span
_agg: Dict[Tuple[Optional[str], str], dict] = {}
# stable small-int thread ids (get_ident values are reused/collide)
_tids: Dict[int, int] = {}
# completed query spans: qid -> {"wall_s": ...} (insertion-ordered)
_query_meta: "OrderedDict[str, dict]" = OrderedDict()
_MAX_QUERY_META = 256


def is_tracing() -> bool:
    return config.tracing_level >= 1


# ---------------------------------------------------------------------------
# query identity
# ---------------------------------------------------------------------------

_QID_ENV = "BODO_TPU_QUERY_ID"
_query_ctx: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("bodo_tpu_query_id", default=None)
_qid_counter = [0]


def new_query_id(prefix: str = "q") -> str:
    with _lock:
        _qid_counter[0] += 1
        n = _qid_counter[0]
    return f"{prefix}{os.getpid()}-{n}"


def current_query_id() -> Optional[str]:
    """The active query id: the innermost `query_span` on this thread,
    else the gang-inherited BODO_TPU_QUERY_ID (set by the spawner so
    worker-side events carry the parent query's identity)."""
    q = _query_ctx.get()
    if q is not None:
        return q
    return os.environ.get(_QID_ENV) or None


@contextlib.contextmanager
def query_span(query_id: Optional[str] = None, env_export: bool = True):
    """Scope everything inside to one query id. Nested spans shadow the
    outer id (contextvar semantics); `env_export` additionally publishes
    the id to the environment so gangs spawned inside the span inherit
    it. Yields the query id."""
    qid = query_id or new_query_id()
    tok = _query_ctx.set(qid)
    prev_env = os.environ.get(_QID_ENV)
    if env_export:
        os.environ[_QID_ENV] = qid
    t0 = time.perf_counter()
    try:
        yield qid
    finally:
        _query_ctx.reset(tok)
        if env_export:
            if prev_env is None:
                os.environ.pop(_QID_ENV, None)
            else:
                os.environ[_QID_ENV] = prev_env
        wall = time.perf_counter() - t0
        # device-buffer leak check: the observatory closes this query's
        # HBM ledger entry (created/freed/live bytes) at span exit
        dev = None
        ob = sys.modules.get("bodo_tpu.runtime.xla_observatory")
        if ob is not None:
            try:
                dev = ob.finish_query(qid)
            except Exception:
                dev = None
        with _lock:
            meta = _query_meta.setdefault(qid, {"wall_s": 0.0})
            meta["wall_s"] += wall
            if dev is not None and dev.get("buffers"):
                meta["device_bytes"] = {
                    "created": dev["created_bytes"],
                    "freed": dev["freed_bytes"],
                    "live": dev["live_bytes"]}
            while len(_query_meta) > _MAX_QUERY_META:
                _query_meta.popitem(last=False)


def query_ids() -> List[str]:
    """Query ids seen by completed spans, oldest first."""
    with _lock:
        return list(_query_meta)


def _seen_query_ids_locked() -> List[str]:
    """All query ids this process traced under: completed spans first,
    then ids only seen via inherited context (a gang worker tagging
    events with the spawner's exported id never opens its own span)."""
    seen = list(_query_meta)
    extra = sorted({q for q, _ in _agg
                    if q is not None and q not in _query_meta})
    return seen + extra


def query_wall_s(qid: str) -> Optional[float]:
    with _lock:
        m = _query_meta.get(qid)
        return m["wall_s"] if m else None


# ---------------------------------------------------------------------------
# events
# ---------------------------------------------------------------------------

_NO_SPAN = contextlib.nullcontext()


def profiler_listening() -> bool:
    """True while a `jax.profiler` session records host spans. jax is
    never imported from here (gang workers load this module before jax):
    where it is not loaded yet, nothing can be listening."""
    prof = sys.modules.get("jax.profiler")
    return prof is not None and prof.TraceAnnotation.is_enabled()


def _profiler_span(name: str, args: dict):
    """The span as the profiler's trace holds it: a TraceAnnotation
    `bodo:<name>` on the device events' clock, its parent given by
    nesting on its thread, `args` and the query id as its arguments.
    Nothing is built or formatted unless a session listens."""
    if not profiler_listening():
        return _NO_SPAN
    qid = current_query_id()
    if qid is not None:
        args = {**args, "query_id": qid}
    return sys.modules["jax.profiler"].TraceAnnotation("bodo:" + name,
                                                       **args)


# info dicts of the events open on this thread, innermost last
_open = threading.local()


@contextlib.contextmanager
def event(name: str, **args):
    """Trace one operator/phase: always as a `bodo:<name>` span of any
    listening `jax.profiler` session, and with tracing on (level >= 1)
    also as a ring-buffer event, where the active query id (if any) is
    attached and keys the per-query aggregate row. Yields a dict for
    arguments only known inside the span (`rows`, and what `annotate`
    adds from deeper frames): they land beside `args` in both places.
    Yields None, at the cost of one inactive-check, when neither
    listens."""
    with _profiler_span(name, args) as span:
        if span is None and not is_tracing():
            yield None
            return
        info: dict = {}
        stack = _open.__dict__.setdefault("stack", [])
        stack.append(info)
        try:
            if is_tracing():
                yield from _ring_event(name, args, info)
            else:
                yield info
        finally:
            stack.pop()
            if span is not None and info:
                span.set_metadata(**info)


# host_read's calls by site since the process started (read as deltas)
_sync_counts: Dict[str, int] = {}
_sync_lock = threading.Lock()


def host_read(site: str, x):
    """`jax.device_get(x)` for any pytree, as one counted host sync: the
    entry point for every read of a device value the engine makes to
    decide its next step (a count, a flag, a key range). It drains the
    device's queue, so the chip idles until the host dispatches again.
    While a profiler session listens or tracing is on, the read is the
    span `bodo:sync.<site>` (`event()`); always, it adds one to `site`'s
    count in `sync_counts()`. With neither listening it formats no name
    and builds no dict. The answer's own copy in `Table.to_pandas` is not
    a sync and does not come through here."""
    if is_tracing() or profiler_listening():
        with event("sync." + site):
            out = sys.modules["jax"].device_get(x)
    else:
        out = sys.modules["jax"].device_get(x)
    with _sync_lock:
        _sync_counts[site] = _sync_counts.get(site, 0) + 1
    return out


def sync_counts() -> Dict[str, int]:
    """host_read's calls by site since the process started: take two
    copies and subtract for the syncs of a span of work."""
    with _sync_lock:
        return dict(_sync_counts)


def annotate(**args) -> None:
    """Add arguments to the innermost event open on this thread, from a
    frame that does not hold it (the route a kernel took, decided far
    below the operator's span). No-op when no event is open."""
    stack = getattr(_open, "stack", None)
    if stack:
        stack[-1].update(args)


def _ring_event(name: str, args: dict, info: dict):
    t0 = time.perf_counter()
    qid = current_query_id()
    try:
        yield info
    finally:
        t1 = time.perf_counter()
        dur = t1 - t0
        global _dropped
        ev_args = {**args, **info}
        if qid is not None:
            ev_args["query_id"] = qid
        with _lock:
            ident = threading.get_ident()
            tid = _tids.get(ident)
            if tid is None:
                tid = _tids[ident] = len(_tids)
            if _events.maxlen is not None and \
                    len(_events) == _events.maxlen:
                _dropped += 1
            _events.append({
                "name": name, "ph": "X", "ts": _ts_us(t0),
                "dur": dur * 1e6, "pid": os.getpid(), "tid": tid,
                "args": ev_args,
            })
            a = _agg.get((qid, name))
            if a is None:
                a = _agg[(qid, name)] = {"count": 0, "total_s": 0.0,
                                         "max_s": 0.0, "rows": 0}
            a["count"] += 1
            a["total_s"] += dur
            a["max_s"] = max(a["max_s"], dur)
            a["rows"] += int(ev_args.get("rows", 0))


def reset() -> None:
    global _dropped
    with _lock:
        _events.clear()
        _agg.clear()
        _tids.clear()
        _query_meta.clear()
        _dropped = 0


def resize_events_buffer() -> None:
    """Rebuild the ring buffer at the current config.trace_events_max
    (keeps the newest events; called by set_config)."""
    global _events
    with _lock:
        old = list(_events)
        _events = _new_events()
        _events.extend(old[-_events.maxlen:])


def has_events() -> bool:
    with _lock:
        return bool(_events)


def dropped_events() -> int:
    with _lock:
        return _dropped


def query_agg() -> Dict[Tuple[Optional[str], str], dict]:
    """Copy of the per-(query, operator) aggregates (metrics registry
    sync reads this to publish per-query-labelled counters)."""
    with _lock:
        return {k: dict(v) for k, v in _agg.items()}


# ---------------------------------------------------------------------------
# dump + cross-rank merge
# ---------------------------------------------------------------------------

def dump(path: Optional[str] = None) -> str:
    """Write chrome-trace JSON (load in chrome://tracing / Perfetto).
    Includes a `memory` section with the governor's derived budget and
    per-operator granted/peak/spilled bytes, a `resilience` section with
    fault/retry/degradation counters, an `aqe` section with adaptive
    decision counters + q-error summary, an `io` section with prefetch
    decode/stall/overlap and footer-cache counters, an `analysis`
    section with the shardcheck plan-validator/lint/lockstep counters,
    a `metrics` section with the unified registry snapshot
    (utils/metrics.py), `compile_cache` hit/miss counts when the
    persistent jit cache is active, plus ring-buffer accounting
    (`dropped_events`) and the query ids the events belong to."""
    from bodo_tpu.utils import metrics
    with _lock:
        events = list(_events)
        dropped = _dropped
        qids = _seen_query_ids_locked()
    out = {"traceEvents": events, "displayTimeUnit": "ms",
           "memory": memory_stats(), "resilience": resilience_stats(),
           "aqe": aqe_stats(), "io": io_stats(),
           "analysis": analysis_stats(),
           "metrics": metrics.snapshot(),
           "dropped_events": dropped,
           "query_ids": qids}
    cc = compile_cache_stats()
    if cc["hits"] or cc["misses"]:
        out["compile_cache"] = cc
    text = json.dumps(out)
    if path:
        with open(path, "w") as f:
            f.write(text)
    return text


def _shard_rank() -> int:
    v = os.environ.get("BODO_TPU_PROC_ID")
    if v not in (None, ""):
        return int(v)
    return 0


def dump_shard(dirpath: str, rank: Optional[int] = None) -> str:
    """Write this process's raw trace shard into a gang-shared directory
    (spawn.py points workers at the gang temp dir). Shards carry the
    clock anchor + rank so `merge_trace_shards` can build one multi-rank
    timeline. Returns the shard path."""
    if rank is None:
        rank = _shard_rank()
    with _lock:
        events = list(_events)
        dropped = _dropped
        qids = _seen_query_ids_locked()
    payload = {"rank": int(rank), "pid": os.getpid(),
               "anchor_wall": _ANCHOR_WALL, "dropped_events": dropped,
               "query_ids": qids, "traceEvents": events}
    path = os.path.join(dirpath, f"trace_shard_{int(rank)}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)
    return path


def merge_trace_shards(dirpath: str,
                       out_path: Optional[str] = None) -> Optional[dict]:
    """Merge per-rank `trace_shard_*.json` files into ONE Perfetto
    timeline: each rank becomes a process lane (pid = rank, with
    process_name/process_sort_index metadata), and all timestamps are
    normalized to the earliest event across the gang so the ranks line
    up on a common zero. Deterministic: shards are read in rank order
    and events sorted by (ts, rank, tid, name). Returns the merged dict
    (written to `out_path` when given), or None when no shards exist."""
    paths = sorted(_glob.glob(os.path.join(dirpath, "trace_shard_*.json")))
    if not paths:
        return None
    shards = []
    for p in paths:
        try:
            with open(p) as f:
                shards.append(json.load(f))
        except (OSError, ValueError):  # truncated shard: skip, keep rest
            continue
    if not shards:
        return None
    shards.sort(key=lambda s: s.get("rank", 0))
    origin = min((e["ts"] for s in shards for e in s["traceEvents"]),
                 default=0.0)
    merged: List[dict] = []
    qids: List[str] = []
    dropped = 0
    for s in shards:
        rank = int(s.get("rank", 0))
        dropped += int(s.get("dropped_events", 0))
        for q in s.get("query_ids", []):
            if q not in qids:
                qids.append(q)
        merged.append({"name": "process_name", "ph": "M", "pid": rank,
                       "tid": 0,
                       "args": {"name": f"rank {rank} "
                                        f"(pid {s.get('pid')})"}})
        merged.append({"name": "process_sort_index", "ph": "M",
                       "pid": rank, "tid": 0,
                       "args": {"sort_index": rank}})
        for e in s["traceEvents"]:
            e = dict(e)
            e["pid"] = rank
            e["ts"] = round(e["ts"] - origin, 3)
            merged.append(e)
    meta = [e for e in merged if e["ph"] == "M"]
    rest = sorted((e for e in merged if e["ph"] != "M"),
                  key=lambda e: (e["ts"], e["pid"], e.get("tid", 0),
                                 e["name"]))
    out = {"traceEvents": meta + rest, "displayTimeUnit": "ms",
           "ranks": len(shards), "origin_us": origin,
           "query_ids": qids, "dropped_events": dropped}
    if out_path:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(out, f)
        os.replace(tmp, out_path)
    return out


# ---------------------------------------------------------------------------
# subsystem snapshots (legacy dict shapes; the metrics registry is the
# canonical consumer-facing surface)
# ---------------------------------------------------------------------------

def memory_stats() -> dict:
    """Memory-governor snapshot (derived budget + per-operator bytes)."""
    from bodo_tpu.runtime.memory_governor import governor
    return governor().stats()


def resilience_stats() -> dict:
    """Fault-injection / retry / degradation counter snapshot."""
    from bodo_tpu.runtime import resilience
    return resilience.stats()


def aqe_stats() -> dict:
    """Adaptive-execution snapshot: decision counters + q-error summary."""
    from bodo_tpu.plan import adaptive
    return adaptive.stats()


def io_stats() -> dict:
    """Pipelined-I/O snapshot: prefetch decode/stall seconds, hit and
    depth counters, footer-cache hits, parallel decode units, and the
    derived overlap ratio (runtime/io_pool.py)."""
    from bodo_tpu.runtime import io_pool
    return io_pool.io_stats()


def analysis_stats() -> dict:
    """Shardcheck snapshot: plan-validator plans/nodes/violations,
    lint run counters, and lockstep dispatch/wait/divergence counters
    (analysis/)."""
    from bodo_tpu.analysis import lint, lockstep, plan_validator
    return {"plan_validator": plan_validator.stats(),
            "lint": lint.stats(), "lockstep": lockstep.stats()}


# persistent-compile-cache observability: jax's monitoring module emits
# /jax/compilation_cache/cache_hits|cache_misses events when
# jax_compilation_cache_dir is set; we fold them into hit/miss counters
_cc_lock = threading.Lock()
_cc_counts = {"hits": 0, "misses": 0}
_cc_installed = False


def install_compile_cache_listener() -> None:
    """Idempotently subscribe to jax's compilation-cache events so the
    profile can report persistent jit-cache hits/misses. Safe to call on
    jax builds without the monitoring hooks (silently does nothing)."""
    global _cc_installed
    # check-and-set under the lock: two racing installers would
    # register two listeners and double-count every cache event
    with _cc_lock:
        if _cc_installed:
            return
        _cc_installed = True
    try:
        from jax._src import monitoring

        def _listen(event: str, *a, **kw) -> None:
            if event.endswith("/cache_hits"):
                with _cc_lock:
                    _cc_counts["hits"] += 1
            elif event.endswith("/cache_misses"):
                with _cc_lock:
                    _cc_counts["misses"] += 1

        monitoring.register_event_listener(_listen)
    except Exception:
        pass


def compile_cache_stats() -> dict:
    with _cc_lock:
        return dict(_cc_counts)


# ---------------------------------------------------------------------------
# profile
# ---------------------------------------------------------------------------

def profile(query_id: Optional[str] = None) -> Dict[str, dict]:
    """Per-operator aggregate metrics (query-profile-collector analogue).
    With `query_id`, only that query's operator rows are returned (the
    counter rows below are process-wide either way). Operators the
    memory governor tracked additionally carry granted/peak/spilled
    bytes under a `mem:<operator>` key; resilience counters (fired
    faults, retries, degraded stages, gang retries) appear under
    `resil:<counter>` keys; the pipelined-I/O layer contributes `io:*`
    counter rows plus time-valued `io:decode`, `io:stall`, and
    `io:overlap` rows (overlap = decode hidden behind consumer
    compute); shardcheck contributes `lint:*` counters (plans
    validated/violations, lint findings) and a time-valued
    `lockstep:check` row (dispatches fingerprinted + peer-wait seconds)
    plus `lockstep:mismatches`/`lockstep:timeouts`; the static program
    verifier contributes a time-valued `progcheck:check` row (programs
    verified + verification seconds + the largest static HBM peak
    estimate) and a `progcheck:violations` counter; whole-stage fusion
    contributes `fusion:*` counter rows plus `fusion:cache`
    (hit/miss) and a time-valued `fusion:compile` row; the comm
    observatory contributes per-collective `comm:<op>` rows carrying
    bytes in/out and the host-wall vs peer-wait split. All counter
    rows are sourced from the unified metrics registry."""
    from bodo_tpu.utils import metrics
    out: Dict[str, dict] = {}
    with _lock:
        for (qid, name), v in _agg.items():
            if query_id is not None and qid != query_id:
                continue
            a = out.get(name)
            if a is None:
                out[name] = dict(v)
            else:
                a["count"] += v["count"]
                a["total_s"] += v["total_s"]
                a["max_s"] = max(a["max_s"], v["max_s"])
                a["rows"] += v["rows"]
    metrics.sync_engine_metrics()

    def series(name: str) -> Dict[Tuple[str, ...], float]:
        m = metrics.registry().get(name)
        return m.series() if m is not None else {}

    mem_bytes = series("bodo_tpu_mem_operator_bytes")
    mem_events = series("bodo_tpu_mem_operator_events")
    for (op, kind), v in mem_bytes.items():
        row = out.setdefault(f"mem:{op}", {
            "count": 0, "total_s": 0.0, "max_s": 0.0, "rows": 0,
            "granted_bytes": 0, "peak_bytes": 0, "spilled_bytes": 0,
            "n_spills": 0})
        row[f"{kind}_bytes"] = int(v)
    for (op, kind), v in mem_events.items():
        row = out.get(f"mem:{op}")
        if row is not None:
            row["count" if kind == "count" else "n_spills"] = int(v)
    counters: Dict[str, float] = {}
    for (point,), n in series("bodo_tpu_resil_faults_fired_total").items():
        counters[f"resil:fault:{point}"] = n
    for (label,), n in series("bodo_tpu_resil_retries_total").items():
        counters[f"resil:retry:{label}"] = n
    for (stage,), n in \
            series("bodo_tpu_resil_degraded_stages_total").items():
        counters[f"resil:degraded:{stage}"] = n
    gr = series("bodo_tpu_resil_gang_retries_total").get((), 0)
    if gr:
        counters["resil:gang_retries"] = gr
    for (decision,), n in series("bodo_tpu_aqe_decisions_total").items():
        counters[f"aqe:{decision}"] = n
    ios = series("bodo_tpu_io_events_total")
    for key in ("prefetch_hits", "prefetch_streams", "prefetch_depth",
                "stalls", "footer_hits", "footer_misses",
                "parallel_units", "parallel_reads", "decode_batches",
                "device_decode_pages", "device_decode_dispatches",
                "device_decode_cols", "device_fallback_cols",
                "device_decode_errors"):
        counters[f"io:{key}"] = ios.get((key,), 0)
    # time-valued io rows: decode seconds (worker-side), consumer stall
    # seconds, and the decode time hidden behind compute
    io_s = series("bodo_tpu_io_seconds")
    if ios.get(("decode_batches",)):
        out["io:decode"] = {"count": int(ios[("decode_batches",)]),
                            "total_s": io_s.get(("decode",), 0.0),
                            "max_s": 0.0, "rows": 0,
                            "bytes": int(ios.get(("decode_bytes",), 0))}
        out["io:stall"] = {"count": int(ios.get(("stalls",), 0)),
                           "total_s": io_s.get(("stall",), 0.0),
                           "max_s": 0.0, "rows": 0}
        ratio = series("bodo_tpu_io_overlap_ratio").get((), 0.0)
        out["io:overlap"] = {"count": int(ios[("decode_batches",)]),
                             "total_s": io_s.get(("overlap",), 0.0),
                             "max_s": 0.0, "rows": 0,
                             "ratio": round(ratio, 4)}
    # device-side parquet decode: pages decoded, the programs dispatched
    # for them (one a run of same-kind pages), on-chip decode seconds,
    # decoded bytes, and the device fraction of all decoded scan output
    if ios.get(("device_decode_pages",)) or \
            ios.get(("device_fallback_cols",)):
        frac = series("bodo_tpu_scan_device_decode_frac").get((), 0.0)
        out["io:device_decode"] = {
            "count": int(ios.get(("device_decode_pages",), 0)),
            "dispatches": int(ios.get(("device_decode_dispatches",), 0)),
            "total_s": io_s.get(("device_decode",), 0.0),
            "max_s": 0.0, "rows": 0,
            "bytes": int(ios.get(("device_decode_bytes",), 0)),
            "frac": round(frac, 4)}
    pv = series("bodo_tpu_plans_validated_total").get((), 0)
    if pv:
        counters["lint:plan_validated"] = pv
        counters["lint:plan_violations"] = \
            series("bodo_tpu_plan_violations_total").get((), 0)
    lf = series("bodo_tpu_lint_findings_total").get((), 0)
    if lf:
        counters["lint:findings"] = lf
    for key in ("mismatches", "timeouts"):
        n = series(f"bodo_tpu_lockstep_{key}_total").get((), 0)
        if n:
            counters[f"lockstep:{key}"] = n
    for key, n in counters.items():
        if n:
            out[key] = {"count": int(n), "total_s": 0.0, "max_s": 0.0,
                        "rows": 0}
    # whole-stage fusion: per-kind counters plus a time-valued
    # fusion:compile row (fused programs built + compile wall seconds)
    fus = series("bodo_tpu_fusion_events_total")
    if any(fus.values()):
        for key in ("groups_planned", "groups_executed", "stream_chains",
                    "partial_agg", "fallbacks", "donated",
                    "device_scan_batches"):
            n = fus.get((key,), 0)
            if n:
                out[f"fusion:{key}"] = {"count": int(n), "total_s": 0.0,
                                        "max_s": 0.0, "rows": 0}
        out["fusion:cache"] = {
            "count": int(fus.get(("hits",), 0)
                         + fus.get(("misses",), 0)),
            "total_s": 0.0, "max_s": 0.0, "rows": 0,
            "hits": int(fus.get(("hits",), 0)),
            "misses": int(fus.get(("misses",), 0))}
        out["fusion:compile"] = {
            "count": int(fus.get(("compiles",), 0)),
            "total_s": series("bodo_tpu_fusion_compile_seconds").get(
                (), 0.0),
            "max_s": 0.0, "rows": 0}
    # compile & device-memory observatory: per-subsystem executable
    # populations with compile wall (time-valued), retrace causes, and
    # the live device-byte ledger
    xe = series("bodo_tpu_xla_executables")
    xc = series("bodo_tpu_xla_compile_seconds")
    xd = series("bodo_tpu_xla_dispatches_total")
    for (sub,), n in xe.items():
        if n:
            out[f"xla:{sub}"] = {
                "count": int(n), "total_s": xc.get((sub,), 0.0),
                "max_s": 0.0, "rows": 0,
                "dispatches": int(xd.get((sub,), 0))}
    for (cause,), n in series("bodo_tpu_xla_retraces_total").items():
        if n:
            out[f"xla:retrace:{cause}"] = {
                "count": int(n), "total_s": 0.0, "max_s": 0.0,
                "rows": 0}
    created = series("bodo_tpu_device_bytes_created_total").get((), 0)
    if created:
        freed = series("bodo_tpu_device_bytes_freed_total").get((), 0)
        out["xla:device_bytes"] = {
            "count": int(series("bodo_tpu_device_buffers_live")
                         .get((), 0)),
            "total_s": 0.0, "max_s": 0.0, "rows": 0,
            "created_bytes": int(created), "freed_bytes": int(freed),
            "live_bytes": int(created - freed)}
    # time-valued lockstep row: dispatches checked + peer-wait seconds
    lc = series("bodo_tpu_lockstep_collectives_total").get((), 0)
    if lc:
        out["lockstep:check"] = {
            "count": int(lc),
            "total_s": series("bodo_tpu_lockstep_wait_seconds").get(
                (), 0.0),
            "max_s": series("bodo_tpu_lockstep_max_wait_seconds").get(
                (), 0.0),
            "rows": 0}
    # time-valued progcheck row: programs statically verified at
    # registration + verification wall seconds, and the violation
    # counter when any invariant failed
    pcn = series("bodo_tpu_progcheck_programs_total").get((), 0)
    if pcn:
        out["progcheck:check"] = {
            "count": int(pcn),
            "total_s": series("bodo_tpu_progcheck_check_seconds").get(
                (), 0.0),
            "max_s": series(
                "bodo_tpu_progcheck_max_check_seconds").get((), 0.0),
            "rows": 0,
            "hbm_peak_bytes_max": int(series(
                "bodo_tpu_progcheck_hbm_peak_bytes_max").get((), 0))}
        pcv = series("bodo_tpu_progcheck_violations_total").get((), 0)
        if pcv:
            out["progcheck:violations"] = {
                "count": int(pcv), "total_s": 0.0, "max_s": 0.0,
                "rows": 0}
    # comm observatory: one row per collective op with the bytes moved
    # and the wall/peer-wait split (parallel/comm.py accounting)
    cd = series("bodo_tpu_comm_dispatches_total")
    if cd:
        cb = series("bodo_tpu_comm_bytes_total")
        cw = series("bodo_tpu_comm_seconds_total")
        for (op,), n in sorted(cd.items()):
            out[f"comm:{op}"] = {
                "count": int(n),
                "total_s": cw.get((op, "wall"), 0.0),
                "max_s": 0.0, "rows": 0,
                "bytes_in": int(cb.get((op, "in"), 0)),
                "bytes_out": int(cb.get((op, "out"), 0)),
                "wait_s": round(cw.get((op, "wait"), 0.0), 6)}
    qn = series("bodo_tpu_aqe_q_error_count").get((), 0)
    if qn:
        qe = {k: series(f"bodo_tpu_aqe_q_error_{k}").get((), 0.0)
              for k in ("mean", "p50", "p90", "max")}
        out["aqe:q_error"] = {
            "count": int(qn), "total_s": 0.0, "max_s": 0.0,
            "rows": 0, "mean": qe["mean"], "p50": qe["p50"],
            "p90": qe["p90"], "max": qe["max"]}
    cc = series("bodo_tpu_compile_cache_total")
    hits, misses = cc.get(("hit",), 0), cc.get(("miss",), 0)
    if hits or misses:
        out["cache:compile"] = {
            "count": int(hits + misses), "total_s": 0.0,
            "max_s": 0.0, "rows": 0, "hits": int(hits),
            "misses": int(misses)}
    # semantic result cache: query-level hits/misses/incremental
    # refreshes, with the wall seconds serving from cache saved
    rce = series("bodo_tpu_result_cache_events_total")
    rqh = rce.get(("q_hits",), 0)
    rqm = rce.get(("q_misses",), 0)
    if rqh or rqm:
        out["cache:result"] = {
            "count": int(rqh + rqm),
            "total_s": series("bodo_tpu_result_cache_saved_seconds"
                              ).get((), 0.0),
            "max_s": 0.0, "rows": 0, "hits": int(rqh),
            "misses": int(rqm),
            "incremental": int(rce.get(("q_incremental",), 0)),
            "evictions": int(rce.get(("evictions",), 0))}
    return out


def top_ops(query_id: Optional[str] = None, n: int = 5) -> List[dict]:
    """Top-n operators by wall seconds for one query (or overall):
    the "where did the time go" rows."""
    with _lock:
        rows: Dict[str, dict] = {}
        for (qid, name), v in _agg.items():
            if query_id is not None and qid != query_id:
                continue
            a = rows.get(name)
            if a is None:
                rows[name] = dict(v)
            else:
                a["count"] += v["count"]
                a["total_s"] += v["total_s"]
                a["rows"] += v["rows"]
    out = [{"op": name, "total_s": round(v["total_s"], 4),
            "count": v["count"], "rows": v["rows"]}
           for name, v in rows.items()]
    out.sort(key=lambda r: (-r["total_s"], r["op"]))
    return out[:n]


_op_depth = threading.local()


def traced_table_op(fn):
    """Wrap a Table-returning operator so every call (through ANY entry
    point — executor, streaming, or direct relational calls) lands in
    the per-operator profile with a rows count. Only the OUTERMOST
    traced frame records (operators re-enter each other — distributed
    groupby calls local groupby, windows call sort — and double-counting
    would make profile totals exceed wall time). No-op when tracing is
    off and no profiler session listens (two predicate checks)."""
    import functools

    @functools.wraps(fn)
    def wrapper(*a, **k):
        if not (is_tracing() or profiler_listening()) \
                or getattr(_op_depth, "d", 0):
            return fn(*a, **k)
        _op_depth.d = 1
        try:
            with event(fn.__name__) as ev:
                t = fn(*a, **k)
                rows = getattr(t, "nrows", None)
                if rows is not None and ev is not None:
                    ev["rows"] = rows
                return t
        finally:
            _op_depth.d = 0
    return wrapper
