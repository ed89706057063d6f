"""Per-collective communication accounting (the observatory's core).

Every host-level collective dispatch site — the relational dispatchers
and `shuffle_by_key`, the host scatter/gather helpers in
parallel/collectives.py, the 1D→REP `Table.gather`, and the streaming
executors' per-batch shuffle steps — reports here: bytes in/out, wall
seconds of the dispatch, and the peer-wait seconds the lockstep checker
measured before the op could proceed (the arrival-skew signal: the rank
everyone waits FOR is the straggler, and it is the rank whose own wait
is smallest).

Rows are keyed ``(op, site)`` where `site` is the first user-level call
frame (same convention as the lockstep fingerprint), so `doctor` can
name the dominant collective site, not just the op. Each span
additionally lands in the trace ring as a ``comm:*`` event (per-rank
lanes in the merged gang trace feed the critical-path analyzer) and the
byte/latency distributions go to the ``bodo_tpu_comm_*`` histograms
push-side; cumulative gauges are synced pull-side by
``metrics.sync_engine_metrics``.

Stdlib-only on purpose: importable from a /metrics scrape or the
telemetry sampler without forcing a jax import.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
from typing import Dict, Optional, Tuple

from bodo_tpu.config import config

_lock = threading.Lock()
# (op, site) -> accounting row
_sites: Dict[Tuple[str, str], dict] = {}
_last = {"op": "", "site": "", "wait_s": 0.0, "wall_s": 0.0, "seq": 0}
_seq = 0

# dispatch-size / dispatch-latency histogram buckets: collectives range
# from KB control payloads to multi-GB shuffles, 100us to seconds
_BYTE_BUCKETS = (1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9)
_TIME_BUCKETS = (1e-4, 1e-3, 5e-3, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0)

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _call_site() -> str:
    """First stack frame OUTSIDE the bodo_tpu package, as
    basename:lineno — same convention as the lockstep fingerprint so a
    comm row and a lockstep log line for one dispatch agree."""
    f = sys._getframe(2)
    while f is not None:
        fname = f.f_code.co_filename
        # collective_span reaches here through contextlib's __enter__ —
        # skip stdlib contextmanager frames along with package frames
        if not fname.startswith(_PKG_DIR) \
                and not fname.endswith("contextlib.py"):
            return f"{os.path.basename(fname)}:{f.f_lineno}"
        f = f.f_back
    return "<internal>"


def record(op: str, *, site: Optional[str] = None, bytes_in: int = 0,
           bytes_out: int = 0, wall_s: float = 0.0,
           wait_s: float = 0.0) -> None:
    """Account one dispatched collective. `wall_s` is the host dispatch
    wall (for async dispatches: enqueue time, not completion); `wait_s`
    is the lockstep peer-wait before the dispatch could proceed."""
    global _seq
    if not config.comm_accounting:
        return
    site = site or _call_site()
    with _lock:
        _seq += 1
        r = _sites.get((op, site))
        if r is None:
            r = _sites[(op, site)] = {
                "count": 0, "bytes_in": 0, "bytes_out": 0,
                "wall_s": 0.0, "max_wall_s": 0.0,
                "wait_s": 0.0, "max_wait_s": 0.0}
        r["count"] += 1
        r["bytes_in"] += int(bytes_in)
        r["bytes_out"] += int(bytes_out)
        r["wall_s"] += float(wall_s)
        r["max_wall_s"] = max(r["max_wall_s"], float(wall_s))
        r["wait_s"] += float(wait_s)
        r["max_wait_s"] = max(r["max_wait_s"], float(wait_s))
        _last.update(op=op, site=site, wait_s=float(wait_s),
                     wall_s=float(wall_s), seq=_seq)
    try:  # push-side distributions (metrics.py is stdlib — no jax pull)
        from bodo_tpu.utils import metrics
        nb = int(bytes_out) or int(bytes_in)
        if nb:
            metrics.histogram(
                "bodo_tpu_comm_dispatch_bytes",
                "bytes moved per collective dispatch", ("op",),
                buckets=_BYTE_BUCKETS).labels(op=op).observe(nb)
        metrics.histogram(
            "bodo_tpu_comm_dispatch_seconds",
            "host wall seconds per collective dispatch", ("op",),
            buckets=_TIME_BUCKETS).labels(op=op).observe(
            float(wall_s) if wall_s else float(wait_s))
    except Exception:  # pragma: no cover - metrics must not break comm
        pass


def record_in_program(group_fp: str, *, bytes_in: int = 0,
                      bytes_out: int = 0, wall_s: float = 0.0,
                      wait_s: float = 0.0) -> int:
    """Attribute the in-program collectives of one fused-group dispatch.

    Collectives traced INSIDE a compiled fusion body (all_to_all /
    psum inside a shard_map program) never hit the per-op dispatch
    hooks, so the usual ``record`` call sites cannot see them. The
    fused dispatcher calls this instead: the group's lockstep manifest
    (``register_fusion_manifest(..., in_program=...)``) declares which
    collective ops the program subsumes, and one accounting row per
    declared op is recorded at site ``fused[<fp>]`` — so ``doctor``
    still sees an all_to_all row for a shuffle that now lives inside a
    compiled stage. Group wall/wait is attributed to
    the FIRST declared op (the program is one dispatch; splitting the
    wall across members would double-count). Returns the number of
    in-program collectives attributed (0 when the manifest declares
    none or does not exist)."""
    if not config.comm_accounting:
        return 0
    from bodo_tpu.analysis import lockstep
    m = lockstep.fusion_manifest(group_fp)
    ops = tuple(m.get("in_program", ())) if m else ()
    if not ops:
        return 0
    site = f"fused[{group_fp}]"
    for i, op in enumerate(ops):
        record(op, site=site,
               bytes_in=int(bytes_in) if i == 0 else 0,
               bytes_out=int(bytes_out) if i == 0 else 0,
               wall_s=float(wall_s) if i == 0 else 0.0,
               wait_s=float(wait_s) if i == 0 else 0.0)
    return len(ops)


@contextlib.contextmanager
def collective_span(op: str, *, bytes_in: int = 0, wait_s: float = 0.0,
                    site: Optional[str] = None):
    """Time one host-level collective dispatch, emit a ``comm:<op>``
    trace event, and account it. Yields a mutable dict: set
    ``bytes_out`` (and adjust ``wait_s``) before the block exits."""
    if not config.comm_accounting:
        yield {}
        return
    site = site or _call_site()
    sp = {"bytes_out": 0, "wait_s": float(wait_s)}
    from bodo_tpu.utils import tracing
    t0 = time.perf_counter()
    try:
        with tracing.event(f"comm:{op}", site=site,
                           bytes_in=int(bytes_in)) as ev:
            yield sp
            if ev is not None:
                ev["bytes_out"] = int(sp.get("bytes_out", 0))
                ev["wait_s"] = round(float(sp.get("wait_s", 0.0)), 6)
    finally:
        record(op, site=site, bytes_in=bytes_in,
               bytes_out=int(sp.get("bytes_out", 0)),
               wall_s=time.perf_counter() - t0,
               wait_s=float(sp.get("wait_s", 0.0)))


def table_bytes(t) -> int:
    """Device bytes of a Table (best-effort input/output sizing for the
    accounting rows; 0 when the governor's sizer is unavailable)."""
    try:
        from bodo_tpu.runtime.memory_governor import table_device_bytes
        return int(table_device_bytes(t))
    except Exception:
        return 0


def stats() -> dict:
    """Full accounting snapshot: process-wide totals + per-(op@site)
    rows. JSON-safe; spawned gang workers return this from run_spmd so
    the parent can compare per-rank skew."""
    with _lock:
        sites = {f"{op}@{site}": dict(r)
                 for (op, site), r in _sites.items()}
        last = dict(_last)
    tot = {"dispatches": 0, "bytes_in": 0, "bytes_out": 0,
           "wall_s": 0.0, "wait_s": 0.0, "max_wait_s": 0.0}
    for r in sites.values():
        tot["dispatches"] += r["count"]
        tot["bytes_in"] += r["bytes_in"]
        tot["bytes_out"] += r["bytes_out"]
        tot["wall_s"] += r["wall_s"]
        tot["wait_s"] += r["wait_s"]
        tot["max_wait_s"] = max(tot["max_wait_s"], r["max_wait_s"])
    tot["sites"] = sites
    tot["last"] = last
    return tot


def per_op() -> Dict[str, dict]:
    """Accounting rows aggregated by op (site collapsed) — what
    tracing.profile's ``comm:*`` rows report."""
    out: Dict[str, dict] = {}
    with _lock:
        items = [(op, dict(r)) for (op, _site), r in _sites.items()]
    for op, r in items:
        a = out.get(op)
        if a is None:
            out[op] = r
            continue
        a["count"] += r["count"]
        a["bytes_in"] += r["bytes_in"]
        a["bytes_out"] += r["bytes_out"]
        a["wall_s"] += r["wall_s"]
        a["max_wall_s"] = max(a["max_wall_s"], r["max_wall_s"])
        a["wait_s"] += r["wait_s"]
        a["max_wait_s"] = max(a["max_wait_s"], r["max_wait_s"])
    return out


def skew_head() -> dict:
    """Small JSON-safe skew snapshot for the telemetry sampler and
    /healthz (the future scheduler's admission input, ROADMAP item 2):
    total dispatches, cumulative/worst peer-wait, the worst-wait site,
    and the wait share of total comm wall."""
    with _lock:
        worst_site, worst = "", 0.0
        wall = wait = 0.0
        n = 0
        for (op, site), r in _sites.items():
            n += r["count"]
            wall += r["wall_s"]
            wait += r["wait_s"]
            if r["max_wait_s"] > worst:
                worst = r["max_wait_s"]
                worst_site = f"{op}@{site}"
        last = dict(_last)
    return {
        "dispatches": n,
        "wait_s": round(wait, 6),
        "max_wait_s": round(worst, 6),
        "max_wait_site": worst_site,
        "wait_frac": round(wait / (wall + wait), 4) if wall + wait
        else 0.0,
        "last_op": last["op"],
        "last_seq": last["seq"],
    }


def straggler_from_logs(dirpath: str, nprocs: int,
                        epoch: int = 0) -> Optional[int]:
    """Straggler attribution from the lockstep arrival stamps: for each
    sequence number every rank reached, the rank whose wall-clock
    arrival stamp is LATEST is the one its peers waited for; the rank
    that is latest most often is the straggler. This is the signal the
    elastic layer's eviction policy uses to drop the rank the gang is
    *waiting for*, not only the one that crashed. Returns the mesh rank
    (epoch-local numbering) or None when the logs carry no comparable
    stamps (lockstep off, single rank, or no common sequence)."""
    from bodo_tpu.analysis.lockstep import _log_name
    arrivals: Dict[int, Dict[int, float]] = {}
    for rank in range(int(nprocs)):
        path = os.path.join(dirpath, _log_name(int(epoch), rank))
        try:
            with open(path) as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        stamps: Dict[int, float] = {}
        for line in lines:
            parts = line.split("\t")
            if len(parts) < 3:
                continue
            try:
                stamps[int(parts[0])] = float(parts[2])
            except ValueError:
                continue
        if stamps:
            arrivals[rank] = stamps
    if len(arrivals) < 2:
        return None
    common = set.intersection(*(set(s) for s in arrivals.values()))
    if not common:
        return None
    late: Dict[int, int] = {}
    for seq in common:
        worst = max(arrivals, key=lambda r: arrivals[r][seq])
        late[worst] = late.get(worst, 0) + 1
    return max(late, key=lambda r: late[r])


def reset() -> None:
    global _seq
    with _lock:
        _sites.clear()
        _seq = 0
        _last.update(op="", site="", wait_s=0.0, wall_s=0.0, seq=0)
    try:
        from bodo_tpu.utils import metrics
        for name in ("bodo_tpu_comm_dispatch_bytes",
                     "bodo_tpu_comm_dispatch_seconds"):
            m = metrics.registry().get(name)
            if m is not None:
                m.clear()
    except Exception:  # pragma: no cover
        pass
