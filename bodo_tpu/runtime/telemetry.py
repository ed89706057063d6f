"""Always-on telemetry: sampler, health endpoints, flight recorder.

PR 6 made single queries observable (spans, cross-rank trace merge,
EXPLAIN ANALYZE, the metrics registry); this module covers the gaps
*between* queries and *after* failures — the observability contract the
future serving layer (runtime/scheduler.py, ROADMAP item 2) scrapes per
tenant, and the Pathways-style controller function of watching a
gang-scheduled fleet centrally (PAPERS §2: health monitoring is a
first-class controller concern; §4: TPU rank loss and wedged hosts
are routine fleet events, so the diagnostic artifact must be produced
by default).

Three parts:

1. SAMPLER — one daemon thread (config.telemetry_interval_s period)
   snapshots every subsystem's cheap stats into a bounded in-memory
   ring (config.telemetry_ring samples): memory-governor occupancy and
   spill, io_pool prefetch depth / stalls / overlap, fusion-cache
   hits/budget, lockstep sequence head, spawn heartbeat age, process
   RSS. Each sample also lands in the metrics registry as
   ``bodo_tpu_process_rss_bytes`` / ``bodo_tpu_heartbeat_age_seconds``
   / ``bodo_tpu_lockstep_sequence_head`` gauges. Subsystem modules are
   read via ``sys.modules.get`` — a sample never forces a jax import.

2. HTTP ENDPOINT — a stdlib ThreadingHTTPServer (``serve()``) bound on
   127.0.0.1 serving:
       /metrics                Prometheus text exposition
       /healthz                JSON gang health (per-rank alive / hb
                               age / last collective when a gang is
                               running, else the local process view)
       /debug/flightrecorder   trigger a bundle dump, return its path

3. FLIGHT RECORDER — ``dump_bundle(reason)`` writes a self-contained
   timestamped diagnostic directory: manifest (config + BODO_TPU_*/
   JAX_* env + armed faults + per-rank diagnostics), the telemetry
   ring, a metrics snapshot, the slowest-N EXPLAIN ANALYZE records,
   faulthandler stacks of every thread, the merged multi-rank trace
   and the lockstep side-channel logs when a gang dir is given.
   Triggered automatically by spawn.py on gang failure, by
   analysis/lockstep.py on LockstepError, and by SIGUSR1
   (``install_signal_trigger()``). ``python -m bodo_tpu.doctor
   <bundle>`` triages the result.
"""

from __future__ import annotations

import faulthandler
import http.server
import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from bodo_tpu.config import config
from bodo_tpu.utils import metrics

_lock = threading.Lock()

# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

_PAGE = 4096
try:
    _PAGE = os.sysconf("SC_PAGE_SIZE")
except (ValueError, OSError, AttributeError):  # pragma: no cover
    pass


def rss_bytes() -> int:
    """Resident set size of this process; /proc on Linux, getrusage
    peak-RSS fallback elsewhere (0 when neither is available)."""
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * _PAGE
    except (OSError, ValueError, IndexError):
        pass
    try:  # pragma: no cover - non-Linux fallback
        import resource
        return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    except Exception:  # pragma: no cover
        return 0


def _mod(name: str):
    """Already-imported subsystem module or None — a telemetry sample
    must never force an import (several of these pull in jax)."""
    return sys.modules.get(name)


def sample() -> dict:
    """One JSON-safe snapshot of the engine's live state. Every
    subsystem read is best-effort: a sampler tick must never raise."""
    s: dict = {"ts": round(time.time(), 3),
               "rss_bytes": rss_bytes()}
    resil = _mod("bodo_tpu.runtime.resilience")
    if resil is not None:
        try:
            age = resil.last_heartbeat_age()
            if age is not None:
                s["heartbeat_age_s"] = round(age, 3)
        except Exception:
            pass
    mg = _mod("bodo_tpu.runtime.memory_governor")
    if mg is not None:
        try:
            st = mg.governor().stats()
            ops = st.get("operators", {})
            s["mem"] = {
                "budget_bytes": int(st.get("derived_budget_bytes", 0)),
                "granted_bytes": int(sum(m.get("granted", 0)
                                         for m in ops.values())),
                "peak_bytes": int(sum(m.get("peak", 0)
                                      for m in ops.values())),
                "spilled_bytes": int(sum(m.get("spilled_bytes", 0)
                                         for m in ops.values())),
                "n_spills": int(sum(m.get("n_spills", 0)
                                    for m in ops.values())),
                "n_queued": int(st.get("n_queued", 0)),
                "oom_retries": int(st.get("n_oom_retries", 0)),
            }
        except Exception:
            pass
    iop = _mod("bodo_tpu.runtime.io_pool")
    if iop is not None:
        try:
            ios = iop.io_stats()
            s["io"] = {
                "prefetch_depth": int(ios.get("prefetch_depth", 0)),
                "prefetch_streams": int(ios.get("prefetch_streams", 0)),
                "stalls": int(ios.get("stalls", 0)),
                "decode_batches": int(ios.get("decode_batches", 0)),
                "overlap_ratio": round(float(
                    ios.get("overlap_ratio", 0.0)), 4),
            }
        except Exception:
            pass
    rc = _mod("bodo_tpu.runtime.result_cache")
    if rc is not None:
        try:
            rs = rc.stats()
            budget = int(rs.get("budget_bytes", 0))
            dev = int(rs.get("device_bytes", 0))
            s["result_cache"] = {
                "entries": int(rs.get("entries", 0)),
                "device_bytes": dev,
                "host_bytes": int(rs.get("host_bytes", 0)),
                "budget_bytes": budget,
                # occupancy + shed/eviction pressure: the admission
                # controller (runtime/scheduler.py) reads cache
                # pressure here without a full /metrics scrape
                "occupancy_frac": round(dev / budget, 4) if budget
                else 0.0,
                "evictions": int(rs.get("evictions", 0)),
                "pressure_sheds": int(rs.get("pressure_sheds", 0)),
                "rejected": int(rs.get("rejected", 0)),
                "spills": int(rs.get("spills", 0)),
                "q_hits": int(rs.get("q_hits", 0)),
                "q_misses": int(rs.get("q_misses", 0)),
                "q_incremental": int(rs.get("q_incremental", 0)),
                "hit_rate": round(float(rs.get("q_hit_rate", 0.0)), 4),
                "saved_wall_s": round(float(
                    rs.get("saved_wall_s", 0.0)), 3),
            }
        except Exception:
            pass
    sch = _mod("bodo_tpu.runtime.scheduler")
    if sch is not None:
        try:
            ss = sch.stats()
            if ss is not None:
                s["scheduler"] = {
                    "sessions": int(ss.get("sessions", 0)),
                    "queued": int(ss.get("queued", 0)),
                    "running": int(ss.get("running", 0)),
                    "completed": int(ss.get("completed", 0)),
                    "failed": int(ss.get("failed", 0)),
                    "decisions": {k: int(v) for k, v in
                                  ss.get("decisions", {}).items()},
                }
        except Exception:
            pass
    vw = _mod("bodo_tpu.runtime.views")
    if vw is not None:
        try:
            vs = vw.stats()
            if vs.get("n_views"):
                s["views"] = {
                    "n_views": int(vs.get("n_views", 0)),
                    "dag_depth": int(vs.get("dag_depth", 0)),
                    "subscriptions": int(vs.get("subscriptions", 0)),
                    "refreshes_incremental":
                        int(vs.get("refreshes_incremental", 0)),
                    "refreshes_full": int(vs.get("refreshes_full", 0)),
                    "refresh_ratio":
                        round(float(vs.get("refresh_ratio", 0.0)), 4),
                    "staleness_p99_s":
                        round(float(vs.get("staleness_p99_s", 0.0)), 4),
                    "lagging_view": vs.get("lagging_view"),
                }
        except Exception:
            pass
    fz = _mod("bodo_tpu.plan.fusion")
    if fz is not None:
        try:
            fs = fz.stats()
            s["fusion"] = {
                "cache_hits": int(fs.get("hits", 0)),
                "cache_misses": int(fs.get("misses", 0)),
                "programs_cached": int(fs.get("size", 0)),
                "budget_spent": float(fs.get("budget_spent",
                                             fs.get("compile_s", 0.0))),
            }
        except Exception:
            pass
    ob = _mod("bodo_tpu.runtime.xla_observatory")
    if ob is not None:
        try:
            st = ob.storm()
            led = ob.ledger_stats()
            bud = ob.budget()
            s["xla"] = {
                "live_device_bytes": int(led["live_bytes"]),
                "live_buffers": int(led["live_buffers"]),
                "budget_remaining": int(bud["remaining"]),
                "storming": bool(st["storming"]),
            }
            if st["storming"]:
                s["xla"]["storm_signature"] = st["signature"]
                s["xla"]["storm_compiles"] = st["compiles_in_window"]
        except Exception:
            pass
    ls = _mod("bodo_tpu.analysis.lockstep")
    if ls is not None:
        try:
            s["lockstep_seq"] = int(ls.sequence_head())
        except Exception:
            pass
    cm = _mod("bodo_tpu.parallel.comm")
    if cm is not None:
        try:
            sk = cm.skew_head()
            if sk.get("dispatches"):
                s["comm"] = sk
        except Exception:
            pass
    gid = os.environ.get("BODO_TPU_GANG_ID", "")
    if gid:
        s["gang_id"] = gid
    fl = _mod("bodo_tpu.runtime.fleet")
    if fl is not None:
        try:
            fs = fl.controller_stats()
            if fs is not None:
                s["fleet"] = fs
        except Exception:
            pass
    el = _mod("bodo_tpu.runtime.elastic")
    if el is not None:
        try:
            eh = el.head()
            # only worth a ring slot once recovery state exists — an
            # epoch-0 full-capacity gang is the default
            if eh.get("epoch") or eh.get("shrinks") or eh.get("grows") \
                    or eh.get("resumes"):
                s["elastic"] = eh
        except Exception:
            pass
    return s


def _update_gauges(s: dict) -> None:
    metrics.gauge("bodo_tpu_process_rss_bytes",
                  "resident set size of this engine process").set(
        s.get("rss_bytes", 0))
    if "heartbeat_age_s" in s:
        metrics.gauge("bodo_tpu_heartbeat_age_seconds",
                      "seconds since this worker's last heartbeat").set(
            s["heartbeat_age_s"])
    if "lockstep_seq" in s:
        metrics.gauge("bodo_tpu_lockstep_sequence_head",
                      "sequence number of the last fingerprinted "
                      "collective dispatch").set(s["lockstep_seq"])
    with _lock:
        n = len(_ring)
    metrics.gauge("bodo_tpu_telemetry_ring_samples",
                  "samples currently held in the telemetry ring").set(n)


def sync_gauges() -> None:
    """Refresh the telemetry gauges from a fresh (registry-free)
    sample. Called by metrics.sync_engine_metrics() so a /metrics
    scrape always sees current RSS even between sampler ticks."""
    _update_gauges(sample())


# ---------------------------------------------------------------------------
# ring + sampler thread
# ---------------------------------------------------------------------------

_ring: deque = deque(maxlen=600)
_sampler_stop: Optional[threading.Event] = None
_sampler_thread: Optional[threading.Thread] = None
_samples_total = 0


def record_sample() -> dict:
    """Take one sample, append it to the ring, refresh the gauges."""
    global _samples_total
    s = sample()
    with _lock:
        if _ring.maxlen != int(config.telemetry_ring):
            _resize_ring_locked()
        _ring.append(s)
        _samples_total += 1
    try:
        _update_gauges(s)
        metrics.counter("bodo_tpu_telemetry_samples_total",
                        "telemetry sampler ticks").inc()
    except Exception:
        pass
    return s


def _resize_ring_locked() -> None:
    # _locked suffix contract: every caller already holds _lock
    global _ring
    _ring = deque(_ring,  # shardcheck: ignore[unlocked-shared-state]
                  maxlen=max(1, int(config.telemetry_ring)))


def ring_snapshot() -> List[dict]:
    with _lock:
        return [dict(s) for s in _ring]


def samples_total() -> int:
    with _lock:
        return _samples_total


def _run_sampler(stop: threading.Event) -> None:
    while not stop.wait(max(0.01, float(config.telemetry_interval_s))):
        try:
            record_sample()
        except Exception:  # noqa: BLE001 - the sampler must survive
            pass


def ensure_sampler() -> bool:
    """Start the background sampler if config.telemetry allows and it
    is not already running. Returns True when a sampler is live."""
    global _sampler_stop, _sampler_thread
    if not config.telemetry:
        return False
    with _lock:
        if _sampler_thread is not None and _sampler_thread.is_alive():
            return True
        stop = threading.Event()
        t = threading.Thread(target=_run_sampler, args=(stop,),
                             name="bodo-tpu-telemetry", daemon=True)
        _sampler_stop = stop
        _sampler_thread = t
    t.start()
    return True


def stop_sampler() -> None:
    global _sampler_stop, _sampler_thread
    with _lock:
        stop, t = _sampler_stop, _sampler_thread
        _sampler_stop = None
        _sampler_thread = None
    if stop is not None:
        stop.set()
    if t is not None and t.is_alive():
        t.join(timeout=2.0)


def sampler_running() -> bool:
    with _lock:
        return _sampler_thread is not None and _sampler_thread.is_alive()


def reconfigure() -> None:
    """Apply config changes to a live sampler: stop it when telemetry
    was disabled; resize the ring. Called by set_config."""
    if not config.telemetry:
        stop_sampler()
    with _lock:
        if _ring.maxlen != int(config.telemetry_ring):
            _resize_ring_locked()


def reset() -> None:
    """Stop the sampler and clear the ring (tests)."""
    global _samples_total
    stop_sampler()
    with _lock:
        _ring.clear()
        _samples_total = 0


# ---------------------------------------------------------------------------
# gang health
# ---------------------------------------------------------------------------

# the spawner registers a provider while a gang is live: a zero-arg
# callable returning {rank: {"alive", "returncode", "hb_age_s",
# "last_collective"}}
_gang_provider: Optional[Callable[[], Dict[int, dict]]] = None


def set_gang_health_provider(fn: Optional[Callable[[], Dict[int, dict]]]
                             ) -> None:
    global _gang_provider
    with _lock:
        _gang_provider = fn


def lockstep_log_tail(dirpath: str, rank: int) -> Optional[str]:
    """Last dispatch recorded in a rank's lockstep side-channel log
    ("#seq op@site"), or None when the rank never dispatched."""
    path = os.path.join(dirpath, f"lockstep_{rank}.log")
    try:
        with open(path, "r") as f:
            last = None
            for line in f:
                if "\t" in line:
                    last = line.rstrip("\n")
            if last is None:
                return None
            # seq \t fingerprint [\t arrival-ts]
            parts = last.split("\t")
            return f"#{parts[0]} {parts[1]}"
    except OSError:
        return None


def health() -> dict:
    """Aggregated health document served at /healthz."""
    with _lock:
        provider = _gang_provider
    doc: dict = {
        "status": "ok",
        "time": round(time.time(), 3),
        "pid": os.getpid(),
    }
    gid = os.environ.get("BODO_TPU_GANG_ID", "")
    if gid:
        # stable fleet identity: the controller's scrapes (and doctor
        # triage) name gangs by this, not by pid/port
        doc["gang_id"] = gid
    resil = _mod("bodo_tpu.runtime.resilience")
    if resil is not None:
        try:
            doc["rank"] = resil.current_rank()
            age = resil.last_heartbeat_age()
            if age is not None:
                doc["heartbeat_age_s"] = round(age, 3)
        except Exception:
            pass
    if provider is not None:
        try:
            ranks = provider()
            doc["gang"] = {str(r): d for r, d in sorted(ranks.items())}
            hb_timeout = float(getattr(config, "spawn_hb_timeout_s",
                                       15.0))
            # shrink-evicted ranks left the mesh on purpose: they read
            # as reduced capacity (the elastic block below), never as
            # an unhealthy gang that needs a restart
            bad = [r for r, d in ranks.items()
                   if not d.get("evicted", False)
                   and (not d.get("alive", False)
                        or d.get("hb_age_s", 0.0) > hb_timeout)]
            if bad:
                doc["status"] = "degraded"
                doc["unhealthy_ranks"] = sorted(bad)
            evicted = sorted(r for r, d in ranks.items()
                             if d.get("evicted", False))
            if evicted:
                doc["evicted_ranks"] = evicted
        except Exception as e:
            doc["status"] = "unknown"
            doc["gang_error"] = f"{type(e).__name__}: {e}"
    el = _mod("bodo_tpu.runtime.elastic")
    if el is not None:
        try:
            eh = el.head()
            # always present once the elastic module is loaded: the
            # fleet admission twin rescales quotas/routing from
            # capacity_frac, so "1.0" (full width) is signal too
            doc["elastic"] = eh
        except Exception:
            pass
    cm = _mod("bodo_tpu.parallel.comm")
    if cm is not None:
        try:
            sk = cm.skew_head()
            if sk.get("dispatches"):
                # arrival-skew head for /healthz consumers (the future
                # scheduler's admission signal, ROADMAP item 2)
                doc["comm"] = sk
        except Exception:
            pass
    ob = _mod("bodo_tpu.runtime.xla_observatory")
    if ob is not None:
        try:
            st = ob.storm()
            if st["storming"]:
                # a signature recompiling every dispatch burns wall on
                # compiles — surfaced for admission to back the session
                # off, but it does NOT flip "status": storms are normal
                # during warm-up / test suites, and gang liveness (the
                # thing "degraded" gates restarts on) is unaffected
                doc["xla_recompile_storm"] = {
                    "signature": st["signature"],
                    "compiles_in_window": st["compiles_in_window"],
                    "window_s": st["window_s"],
                }
            doc["xla_live_device_bytes"] = int(
                ob.ledger_stats()["live_bytes"])
        except Exception:
            pass
    rc = _mod("bodo_tpu.runtime.result_cache")
    if rc is not None:
        try:
            rs = rc.stats()
            budget = int(rs.get("budget_bytes", 0))
            dev = int(rs.get("device_bytes", 0))
            # occupancy/shed block: cache pressure for the admission
            # controller without a full /metrics scrape. Like the storm
            # flag it does NOT flip "status" — a full cache is load,
            # not ill health
            doc["result_cache"] = {
                "device_bytes": dev,
                "budget_bytes": budget,
                "occupancy_frac": round(dev / budget, 4) if budget
                else 0.0,
                "entries": int(rs.get("entries", 0)),
                "evictions": int(rs.get("evictions", 0)),
                "pressure_sheds": int(rs.get("pressure_sheds", 0)),
                "rejected": int(rs.get("rejected", 0)),
            }
        except Exception:
            pass
    sch = _mod("bodo_tpu.runtime.scheduler")
    if sch is not None:
        try:
            ss = sch.stats()
            if ss is not None:
                doc["scheduler"] = {
                    "sessions": int(ss.get("sessions", 0)),
                    "queued": int(ss.get("queued", 0)),
                    "running": int(ss.get("running", 0)),
                    "decisions": {k: int(v) for k, v in
                                  ss.get("decisions", {}).items()},
                }
        except Exception:
            pass
    vw = _mod("bodo_tpu.runtime.views")
    if vw is not None:
        try:
            vs = vw.stats()
            if vs.get("n_views"):
                # like result_cache: a lagging view is maintenance
                # load, not ill health — doctor triage names the view
                doc["views"] = {
                    "n_views": int(vs.get("n_views", 0)),
                    "dag_depth": int(vs.get("dag_depth", 0)),
                    "subscriptions": int(vs.get("subscriptions", 0)),
                    "refresh_ratio":
                        round(float(vs.get("refresh_ratio", 0.0)), 4),
                    "staleness_p99_s":
                        round(float(vs.get("staleness_p99_s", 0.0)), 4),
                    "lagging_view": vs.get("lagging_view"),
                    "refresh_rejected":
                        int(vs.get("refresh_rejected", 0)),
                }
        except Exception:
            pass
    fl = _mod("bodo_tpu.runtime.fleet")
    if fl is not None:
        try:
            fs = fl.controller_stats()
            if fs is not None:
                # per-gang attribution: which gangs this controller is
                # fronting and what state each is in (ok/shed/degraded/
                # backoff/dead) — doctor triage names gangs from here
                doc["fleet"] = fs
        except Exception:
            pass
    with _lock:
        doc["telemetry"] = {
            "sampler_running": _sampler_thread is not None
            and _sampler_thread.is_alive(),
            "ring_samples": len(_ring),
            "samples_total": _samples_total,
        }
    bundle = last_bundle_path()
    if bundle:
        doc["last_flight_bundle"] = bundle
    return doc


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

_last_bundle: Optional[str] = None
_bundle_lock = threading.Lock()

_ENV_PREFIXES = ("BODO_TPU_", "JAX_", "XLA_")


def flight_dir() -> str:
    return config.flight_dir or os.path.join(tempfile.gettempdir(),
                                             "bodo_tpu_flightrec")


def last_bundle_path() -> Optional[str]:
    with _bundle_lock:
        return _last_bundle


def _sanitize(reason: str) -> str:
    return "".join(c if c.isalnum() or c in "-_" else "_"
                   for c in reason)[:60]


def _write_json(path: str, obj) -> None:
    with open(path, "w") as f:
        json.dump(obj, f, indent=2, sort_keys=True, default=str)


def dump_bundle(reason: str, *, gang_dir: Optional[str] = None,
                ranks: Optional[Dict[int, dict]] = None,
                out_dir: Optional[str] = None) -> Optional[str]:
    """Write a self-contained diagnostic bundle; returns its path (None
    when the flight recorder is disabled). Never raises — diagnostics
    must not compound the failure being diagnosed.

    Layout:
        manifest.json       reason, timestamps, pid/rank, config, env
                            (BODO_TPU_*/JAX_*/XLA_*), armed faults,
                            per-rank diagnostics when given
        telemetry.json      the sampler ring + one final fresh sample
        metrics.prom        Prometheus exposition snapshot
        slow_queries.json   slowest-N EXPLAIN ANALYZE records
        stacks.txt          faulthandler dump of every local thread
        trace_merged.json   multi-rank timeline (gang bundles)
        trace_local.json    this process's trace (non-gang bundles)
        lockstep_<r>.log    copied side-channel dispatch logs
        err_<r>.log         copied worker stderr
        stacks_<r>.txt      per-rank faulthandler stacks (SIGUSR1 path)
    """
    global _last_bundle
    try:
        if not config.flight_recorder:
            return None
        base = out_dir or flight_dir()
        ts = time.strftime("%Y%m%d_%H%M%S")
        d = os.path.join(
            base, f"bundle_{ts}_{os.getpid()}_{_sanitize(reason)}")
        os.makedirs(d, exist_ok=True)
        _write_manifest(d, reason, ranks)
        _write_telemetry(d)
        _write_metrics(d)
        _write_xla(d)
        _write_progcheck(d)
        _write_slow_queries(d)
        _write_stacks(d)
        _write_traces(d, gang_dir)
        if gang_dir:
            _copy_gang_artifacts(d, gang_dir)
        with _bundle_lock:
            _last_bundle = d
        try:
            metrics.counter("bodo_tpu_flight_bundles_total",
                            "flight-recorder bundles dumped",
                            ("reason",)).labels(
                reason=_sanitize(reason)).inc()
        except Exception:
            pass
        sys.stderr.write(
            f"bodo_tpu.telemetry: flight-recorder bundle ({reason}) "
            f"-> {d}\n")
        return d
    except Exception as e:  # noqa: BLE001 - never compound the failure
        sys.stderr.write(
            f"bodo_tpu.telemetry: bundle dump failed: "
            f"{type(e).__name__}: {e}\n")
        return None


def _write_manifest(d: str, reason: str,
                    ranks: Optional[Dict[int, dict]]) -> None:
    from dataclasses import fields as _dc_fields
    resil = _mod("bodo_tpu.runtime.resilience")
    man = {
        "reason": reason,
        "ts": round(time.time(), 3),
        "iso_time": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "pid": os.getpid(),
        "gang_id": os.environ.get("BODO_TPU_GANG_ID", ""),
        "rank": resil.current_rank() if resil is not None else None,
        "config": {f.name: getattr(config, f.name)
                   for f in _dc_fields(type(config))},
        "env": {k: v for k, v in sorted(os.environ.items())
                if k.startswith(_ENV_PREFIXES)},
    }
    if resil is not None:
        try:
            man["faults_armed"] = resil.armed()
            man["resilience"] = resil.stats()
        except Exception:
            pass
    if ranks is not None:
        man["ranks"] = {str(r): dict(diag)
                        for r, diag in sorted(ranks.items())}
    _write_json(os.path.join(d, "manifest.json"), man)


def _write_telemetry(d: str) -> None:
    try:
        samples = ring_snapshot()
        samples.append(sample())  # the moment of failure itself
        _write_json(os.path.join(d, "telemetry.json"),
                    {"interval_s": float(config.telemetry_interval_s),
                     "samples": samples})
    except Exception:
        pass


def _write_metrics(d: str) -> None:
    try:
        with open(os.path.join(d, "metrics.prom"), "w") as f:
            f.write(metrics.expose_text())
    except Exception:
        pass


def _write_xla(d: str) -> None:
    """Embed the program registry + device-buffer ledger in the bundle
    (doctor's storm/leak triage reads xla_registry.json)."""
    ob = _mod("bodo_tpu.runtime.xla_observatory")
    if ob is None:
        return
    try:
        _write_json(os.path.join(d, "xla_registry.json"),
                    {"summary": ob.stats(),
                     "programs": ob.registry_dump(limit=200),
                     "leaks": ob.leak_check(collect=False)})
    except Exception:
        pass


def _write_progcheck(d: str) -> None:
    """Embed the static verifier's collective manifests + violations in
    the bundle (doctor's progcheck triage section reads this; the
    per-program verdicts ride in xla_registry.json too)."""
    pc = _mod("bodo_tpu.analysis.progcheck")
    if pc is None:
        return
    try:
        _write_json(os.path.join(d, "progcheck.json"),
                    {"stats": pc.stats(),
                     "manifests": pc.reports(),
                     "violations": pc.violations()})
    except Exception:
        pass


# Slowest-N EXPLAIN ANALYZE records embedded per bundle.
FLIGHT_SLOW_QUERIES = 5


def _write_slow_queries(d: str) -> None:
    ex = _mod("bodo_tpu.plan.explain")
    if ex is None:
        return
    try:
        _write_json(os.path.join(d, "slow_queries.json"),
                    ex.slow_queries(FLIGHT_SLOW_QUERIES))
    except Exception:
        pass


def _write_stacks(d: str) -> None:
    try:
        with open(os.path.join(d, "stacks.txt"), "w") as f:
            faulthandler.dump_traceback(file=f)
    except Exception:
        pass


def _write_traces(d: str, gang_dir: Optional[str]) -> None:
    tr = _mod("bodo_tpu.utils.tracing")
    if tr is None:
        return
    try:
        if gang_dir:
            tr.merge_trace_shards(gang_dir,
                                  os.path.join(d, "trace_merged.json"))
        elif tr.has_events():
            tr.dump(os.path.join(d, "trace_local.json"))
    except Exception:
        pass


def _copy_gang_artifacts(d: str, gang_dir: str) -> None:
    """Carry the gang temp dir's side channels into the bundle before
    the TemporaryDirectory is cleaned up: lockstep dispatch logs,
    worker stderr, per-rank SIGUSR1 stack dumps, raw trace shards."""
    try:
        names = os.listdir(gang_dir)
    except OSError:
        return
    for name in names:
        if not (name.startswith(("lockstep_", "err_", "stacks_"))
                or name.startswith("trace_shard_")
                or name == "remesh.json"):
            continue
        try:
            shutil.copy2(os.path.join(gang_dir, name),
                         os.path.join(d, name))
        except OSError:
            pass


# ---------------------------------------------------------------------------
# SIGUSR1 trigger + worker integration
# ---------------------------------------------------------------------------

_signal_installed = False
_prev_usr1_handler = None


def install_signal_trigger() -> bool:
    """SIGUSR1 -> dump a flight-recorder bundle (and, in a spawned
    worker, leave the trace shard + stacks in the gang dir for the
    spawner's merge). Main-thread only — returns False elsewhere."""
    global _signal_installed, _prev_usr1_handler
    with _lock:
        if _signal_installed:
            return True
    try:
        prev = signal.signal(signal.SIGUSR1, _on_sigusr1)
    except (ValueError, OSError, AttributeError):
        # ValueError: not the main thread; AttributeError: no SIGUSR1
        return False
    with _lock:
        _signal_installed = True
        _prev_usr1_handler = prev
    return True


def _on_sigusr1(signum, frame) -> None:  # noqa: ARG001
    try:
        _dump_worker_side_channel()
        dump_bundle("sigusr1")
    except Exception:  # noqa: BLE001 - a signal handler must not raise
        pass


def _dump_worker_side_channel() -> None:
    """In a spawned worker: write this rank's trace shard and thread
    stacks into the gang's shared dir, then a done-marker the spawner's
    grace window polls for before the hard kill."""
    d = os.environ.get("BODO_TPU_TRACE_SHARD_DIR")
    if not d:
        return
    rank = os.environ.get("BODO_TPU_PROC_ID", "0")
    tr = _mod("bodo_tpu.utils.tracing")
    if tr is not None:
        try:
            if tr.has_events():
                tr.dump_shard(d)
        except Exception:
            pass
    try:
        with open(os.path.join(d, f"stacks_{rank}.txt"), "w") as f:
            faulthandler.dump_traceback(file=f)
    except Exception:
        pass
    try:
        with open(os.path.join(d, f"usr1_done_{rank}"), "w") as f:
            f.write(str(time.time()))
    except OSError:
        pass


def worker_init() -> None:
    """Called by the spawn worker bootstrap after the jax import:
    starts the config-gated sampler and arms the SIGUSR1 side-channel
    dump so the spawner's teardown grace can collect this rank's shard
    and stacks even when the rank is about to be killed."""
    try:
        ensure_sampler()
    except Exception:
        pass
    install_signal_trigger()
    port = int(os.environ.get("BODO_TPU_TELEMETRY_RANK_PORT", "-1"))
    if port >= 0:
        try:
            addr = serve(port)
            d = os.environ.get("BODO_TPU_TRACE_SHARD_DIR")
            rank = os.environ.get("BODO_TPU_PROC_ID", "0")
            if d and addr:
                with open(os.path.join(d, f"telemetry_{rank}.addr"),
                          "w") as f:
                    f.write(addr)
        except Exception:
            pass


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

_server: Optional[http.server.ThreadingHTTPServer] = None
_server_thread: Optional[threading.Thread] = None


class _Handler(http.server.BaseHTTPRequestHandler):
    # never chat on stderr per request
    def log_message(self, format, *args):  # noqa: A002,ARG002
        pass

    def _send(self, code: int, body: str, ctype: str) -> None:
        data = body.encode("utf-8")
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):  # noqa: N802 - BaseHTTPRequestHandler contract
        path = self.path.split("?", 1)[0]
        try:
            if path == "/metrics":
                self._send(200, metrics.expose_text(),
                           "text/plain; version=0.0.4; charset=utf-8")
            elif path == "/healthz":
                self._send(200, json.dumps(health(), indent=2,
                                           sort_keys=True, default=str),
                           "application/json")
            elif path == "/debug/flightrecorder":
                p = dump_bundle("http_request")
                self._send(200 if p else 503,
                           json.dumps({"bundle": p}),
                           "application/json")
            else:
                self._send(404, json.dumps(
                    {"error": "not found", "endpoints": [
                        "/metrics", "/healthz",
                        "/debug/flightrecorder"]}),
                    "application/json")
        except Exception as e:  # noqa: BLE001 - a scrape must not kill
            try:
                self._send(500, f"{type(e).__name__}: {e}",
                           "text/plain")
            except Exception:
                pass


def serve(port: Optional[int] = None) -> Optional[str]:
    """Start the telemetry HTTP server on 127.0.0.1 (idempotent).
    `port` defaults to config.telemetry_port; negative disables and
    returns None, 0 binds an ephemeral port. Returns "host:port".
    Also starts the sampler — an endpoint with a stale ring is a trap."""
    global _server, _server_thread
    if port is None:
        port = int(config.telemetry_port)
    if port < 0:
        return None
    with _lock:
        if _server is not None:
            srv = _server
            return f"127.0.0.1:{srv.server_address[1]}"
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), _Handler)
    srv.daemon_threads = True
    t = threading.Thread(target=srv.serve_forever,
                         name="bodo-tpu-telemetry-http", daemon=True)
    with _lock:
        _server = srv
        _server_thread = t
    t.start()
    ensure_sampler()
    return f"127.0.0.1:{srv.server_address[1]}"


def endpoint_address() -> Optional[str]:
    with _lock:
        if _server is None:
            return None
        return f"127.0.0.1:{_server.server_address[1]}"


def shutdown_server() -> None:
    global _server, _server_thread
    with _lock:
        srv, t = _server, _server_thread
        _server = None
        _server_thread = None
    if srv is not None:
        srv.shutdown()
        srv.server_close()
    if t is not None and t.is_alive():
        t.join(timeout=2.0)
