"""Resilience layer: fault injection, retry envelope, error taxonomy.

The engine's recovery machinery (OOM-retry at stage boundaries,
partitioned spill, pandas fallbacks) existed but was only exercisable by
real failures. This module makes faults first-class, the analogue of the
reference engine treating worker supervision as part of the runtime
rather than something MPI does for you (reference: bodo/spawn/spawner.py
spawner/worker model, bodo/libs/memory_budget.py threshold enforcement).

Three parts:

1. FAULT-INJECTION REGISTRY — named points that production code calls
   via `maybe_inject(point)`:

       collective           distributed-op dispatch (shuffle/psum paths)
       device_put           host->device scatter (shard_host_array)
       io.read              parquet/csv/json readers (per attempt)
       io.write             parquet writers (per attempt)
       spawn.worker_start   spawned worker, BEFORE the jax import
       stage.boundary       plan-executor stage entry (both executors)
       fleet.serve          fleet controller, per routed submission
       elastic.checkpoint   elastic worker, at every stage-boundary
                            checkpoint registration (kill here is the
                            canonical mid-pipeline rank loss)
       elastic.remesh       elastic worker, on adopting a new mesh
                            epoch (before renumbering) — recovery of
                            recovery; a fault here must fall back to
                            the gang-level retry, never wedge
       elastic.resume       elastic worker, after renumbering/lockstep
                            re-namespacing, before the recovery
                            reshard of the last checkpoint

   Tests and chaos runs arm them with a spec string, either in-process
   (`set_config(faults=...)`) or via `BODO_TPU_FAULTS=<spec>` in the
   environment so spawned workers inherit them:

       spec   := entry ("," entry)*
       entry  := point ["@" rank] "=" action
       action := "raise:" NAME [":" nth [":" times]]
               | "latency:" SECONDS [":" nth [":" times]]
               | "kill" [":" nth]

   `NAME` resolves against builtins (OSError, TimeoutError, ...); any
   other name raises `FaultInjected` with the name in the message (so
   `raise:RESOURCE_EXHAUSTED` exercises the governor's OOM envelope).
   `nth` is the 1-based call at which the fault starts firing (default
   1); `times` is how many consecutive calls fire (default 1; 0 =
   every call from `nth` on). `@rank` restricts the entry to one
   spawned rank (matched against BODO_TPU_PROC_ID).

2. RETRY ENVELOPE — `retry_call(fn, ...)`: exponential backoff +
   jitter + deadline over a transient-error taxonomy:

       resource_exhausted   XLA RESOURCE_EXHAUSTED / out-of-memory
                            (unified with the memory governor's
                            `is_oom`, which delegates here)
       coordination         jax.distributed / coordination-service
                            flake (DEADLINE_EXCEEDED, UNAVAILABLE,
                            failed-to-connect, barrier timeout)
       filesystem           OSError flake that is NOT a deterministic
                            error (missing file, permissions)

3. COUNTERS — every injected fault, retry, degraded stage, and gang
   retry lands in `stats()`, which the tracing profile and the
   chrome-trace dump embed, so a degraded artifact says WHY it
   degraded.

IMPORTANT: this module must stay importable WITHOUT the bodo_tpu
package (stdlib imports only at module scope). Spawned workers load it
straight from its file path before importing jax, so a `kill` armed at
`spawn.worker_start` costs ~0.2s, not a full jax import. When the
package IS imported, knobs come from `bodo_tpu.config`; standalone they
come from environment variables.
"""

from __future__ import annotations

import os
import random
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

# ---------------------------------------------------------------------------
# config access (lazy: works standalone AND inside the package)
# ---------------------------------------------------------------------------


def _cfg(name: str, env: str, default, cast):
    """Read a knob from bodo_tpu.config when the package is already
    imported (never triggers the package import — that would pull jax
    into a pre-import worker), else from the environment."""
    m = sys.modules.get("bodo_tpu.config")
    c = getattr(m, "config", None) if m is not None else None
    if c is not None and hasattr(c, name):
        return getattr(c, name)
    v = os.environ.get(env)
    if v in (None, ""):
        return default
    return cast(v)


# ---------------------------------------------------------------------------
# fault-injection registry
# ---------------------------------------------------------------------------

POINTS = ("collective", "device_put", "io.read", "io.write",
          "spawn.worker_start", "stage.boundary", "fleet.serve",
          "elastic.checkpoint", "elastic.remesh", "elastic.resume")


class FaultInjected(RuntimeError):
    """Raised by an armed injection point whose exception name does not
    resolve to a builtin exception class. The chosen name is embedded in
    the message so substring-matching recovery layers (e.g. the memory
    governor's RESOURCE_EXHAUSTED check) treat it like the real thing."""

    def __init__(self, point: str, name: str, call_no: int):
        self.point = point
        self.fault_name = name
        super().__init__(
            f"injected fault at {point} (call {call_no}): {name}")


class _Fault:
    __slots__ = ("point", "rank", "kind", "arg", "nth", "times")

    def __init__(self, point, rank, kind, arg, nth, times):
        self.point = point
        self.rank = rank      # None = every rank
        self.kind = kind      # "raise" | "latency" | "kill"
        self.arg = arg        # exception name | latency seconds
        self.nth = nth        # 1-based first firing call
        self.times = times    # firings from nth on; 0 = unlimited

    def spec(self) -> str:
        at = f"@{self.rank}" if self.rank is not None else ""
        if self.kind == "kill":
            return f"{self.point}{at}=kill:{self.nth}"
        return (f"{self.point}{at}={self.kind}:{self.arg}"
                f":{self.nth}:{self.times}")


_lock = threading.Lock()
_armed: Optional[List[_Fault]] = None   # None = not yet armed from env
_calls: Dict[str, int] = {}

_STATS_ZERO = lambda: {  # noqa: E731 - tiny factory
    "faults_fired": {}, "retries": {}, "retries_by_category": {},
    "degraded_stages": {}, "gang_retries": 0,
}
_stats = _STATS_ZERO()


def parse_faults(spec: str) -> List[_Fault]:
    """Parse a fault spec string (see module docstring for the grammar).
    Raises ValueError on malformed entries or unknown points."""
    out: List[_Fault] = []
    for entry in filter(None, (e.strip() for e in spec.split(","))):
        if "=" not in entry:
            raise ValueError(f"fault entry {entry!r}: expected point=action")
        target, action = entry.split("=", 1)
        rank: Optional[int] = None
        if "@" in target:
            target, r = target.split("@", 1)
            rank = int(r)
        if target not in POINTS:
            raise ValueError(
                f"unknown fault point {target!r} (valid: {POINTS})")
        parts = action.split(":")
        kind = parts[0]
        if kind == "kill":
            nth = int(parts[1]) if len(parts) > 1 else 1
            out.append(_Fault(target, rank, "kill", None, nth, 1))
        elif kind in ("raise", "latency"):
            if len(parts) < 2:
                raise ValueError(
                    f"fault entry {entry!r}: {kind} needs an argument")
            arg = parts[1] if kind == "raise" else float(parts[1])
            nth = int(parts[2]) if len(parts) > 2 else 1
            times = int(parts[3]) if len(parts) > 3 else 1
            out.append(_Fault(target, rank, kind, arg, nth, times))
        else:
            raise ValueError(
                f"fault entry {entry!r}: unknown action {kind!r} "
                f"(raise/latency/kill)")
        if out[-1].nth < 1:
            raise ValueError(f"fault entry {entry!r}: nth must be >= 1")
    return out


def arm(spec: str) -> None:
    """Arm the registry from a spec string (empty disarms). Per-point
    call counters reset so `nth` is deterministic from this moment."""
    global _armed
    faults = parse_faults(spec or "")
    with _lock:
        _armed = faults
        _calls.clear()


def disarm() -> None:
    arm("")


def armed() -> List[str]:
    """Spec strings of the currently armed faults (diagnostics)."""
    with _lock:
        return [f.spec() for f in (_armed or [])]


def current_rank() -> Optional[int]:
    """Rank for @rank fault filters: the spawned worker's
    BODO_TPU_PROC_ID, else the jax process index when jax is already
    imported (never imports jax itself)."""
    v = os.environ.get("BODO_TPU_PROC_ID")
    if v not in (None, ""):
        return int(v)
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            return None
    return None


def _ensure_armed() -> List[_Fault]:
    global _armed
    if _armed is None:
        spec = _cfg("faults", "BODO_TPU_FAULTS", "", str)
        try:
            faults = parse_faults(spec)
        except ValueError:
            faults = []
            sys.stderr.write(
                f"bodo_tpu.resilience: ignoring malformed "
                f"BODO_TPU_FAULTS={spec!r}\n")
        # publish under the lock: a concurrent arm()/disarm() must
        # never lose its spec to this lazy env-arming racing it
        with _lock:
            if _armed is None:
                _armed = faults
    return _armed


def maybe_inject(point: str) -> None:
    """Fire any armed faults for `point`. Near-free when nothing is
    armed (one attribute read + truthiness check)."""
    faults = _armed
    if faults is None:
        faults = _ensure_armed()
    if not faults:
        return
    with _lock:
        n = _calls.get(point, 0) + 1
        _calls[point] = n
        live = [f for f in faults if f.point == point]
    if not live:
        return
    rank = current_rank()
    for f in live:
        if f.rank is not None and f.rank != rank:
            continue
        if n < f.nth or (f.times and n >= f.nth + f.times):
            continue
        with _lock:
            _stats["faults_fired"][point] = \
                _stats["faults_fired"].get(point, 0) + 1
        if f.kind == "latency":
            time.sleep(float(f.arg))
            continue
        if f.kind == "kill":
            sys.stderr.write(
                f"bodo_tpu.resilience: injected kill at {point} "
                f"(call {n}, rank {rank})\n")
            sys.stderr.flush()
            # the dying rank is the one whose timeline the post-mortem
            # needs most: leave its trace shard in the gang side channel
            # before os._exit skips every atexit/finally path
            _dump_trace_shard_best_effort()
            os._exit(137)
        # kind == "raise"
        import builtins
        cls = getattr(builtins, str(f.arg), None)
        if isinstance(cls, type) and issubclass(cls, BaseException):
            raise cls(f"injected fault at {point} (call {n})")
        raise FaultInjected(point, str(f.arg), n)


def _dump_trace_shard_best_effort() -> None:
    """Write this process's trace shard into the gang's shared dir (the
    spawner merges shards into the flight-recorder bundle). Uses
    sys.modules.get so the stdlib-only import rule holds: a pre-import
    worker (no tracing module loaded) simply has nothing to dump."""
    tr = sys.modules.get("bodo_tpu.utils.tracing")
    d = os.environ.get("BODO_TPU_TRACE_SHARD_DIR")
    if tr is None or not d:
        return
    try:
        if tr.has_events():
            tr.dump_shard(d)
    except Exception:
        pass


# ---------------------------------------------------------------------------
# transient-error taxonomy
# ---------------------------------------------------------------------------

# shardcheck analysis errors (by class name — this module must stay
# stdlib-only and cannot import bodo_tpu.analysis): correctness bugs
# whose messages mention collectives, so substring taxonomies below
# would otherwise retry or degrade them away instead of surfacing them
_ANALYSIS_ERRORS = ("LockstepError", "PlanInvariantError")


def _is_analysis_error(exc: BaseException) -> bool:
    return type(exc).__name__ in _ANALYSIS_ERRORS


_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")
_COORD_MARKERS = (
    "DEADLINE_EXCEEDED", "UNAVAILABLE", "failed to connect",
    "Connection reset", "connection attempts failed", "Socket closed",
    "Barrier timed out", "coordination service", "Address already in use",
    "heartbeat", "ConnectionResetError", "ConnectionRefusedError",
)
# OSError subclasses that are deterministic, not flake — never retried
_FS_PERMANENT = (FileNotFoundError, PermissionError, IsADirectoryError,
                 NotADirectoryError, FileExistsError)


def is_resource_exhausted(exc: BaseException) -> bool:
    """XLA RESOURCE_EXHAUSTED / allocator OOM (the memory governor's
    `is_oom` delegates here — one taxonomy for the whole engine)."""
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in _OOM_MARKERS)


def classify_transient(exc: BaseException) -> Optional[str]:
    """Category name when `exc` looks transient (worth retrying), else
    None. Injected `FaultInjected` faults are NOT transient — to test
    the retry path, inject a real transient class (e.g. OSError).
    Shardcheck analysis errors (LockstepError/PlanInvariantError) are
    never transient: they report divergence bugs, not flake."""
    if isinstance(exc, FaultInjected) or _is_analysis_error(exc):
        return None
    if is_resource_exhausted(exc):
        return "resource_exhausted"
    msg = f"{type(exc).__name__}: {exc}"
    if any(m in msg for m in _COORD_MARKERS):
        return "coordination"
    if isinstance(exc, (ConnectionError, TimeoutError)):
        return "coordination"
    if isinstance(exc, OSError) and not isinstance(exc, _FS_PERMANENT):
        return "filesystem"
    return None


def classify_transient_text(text: str) -> Optional[str]:
    """Taxonomy over captured stderr (the spawner classifies dead
    workers from their output, not a live exception object)."""
    if not text:
        return None
    if any(m in text for m in _OOM_MARKERS):
        return "resource_exhausted"
    if any(m in text for m in _COORD_MARKERS):
        return "coordination"
    if "terminate called without an active exception" in text \
            and "Traceback" not in text:
        # a bare C++ std::terminate with NO Python traceback: the worker
        # died inside native thread machinery (TSL/XLA startup or
        # teardown under load), never reaching user code — retry the
        # gang like a coordination flake; a deterministic native bug
        # still fails the bounded retry
        return "native_abort"
    return None


def is_degradable(exc: BaseException) -> bool:
    """True when a stage failure should trigger replicated re-execution:
    an injected `collective` fault, or a non-OOM internal/collective
    runtime error from a sharded computation. Shardcheck analysis
    errors are excluded by class name BEFORE the marker matching: a
    LockstepError's message names the diverging collective, and
    degrading it to a replicated re-run would mask the divergence bug
    it exists to surface."""
    if _is_analysis_error(exc):
        return False
    if isinstance(exc, FaultInjected):
        return exc.point == "collective"
    if is_resource_exhausted(exc):
        return False  # the OOM envelope owns this
    msg = f"{type(exc).__name__}: {exc}"
    return any(m in msg for m in (
        "INTERNAL:", "all-reduce", "all-to-all", "all_gather",
        "AllReduce", "AllToAll", "CollectivePermute", "collective",
        "ppermute"))


# ---------------------------------------------------------------------------
# retry envelope
# ---------------------------------------------------------------------------


class RetryPolicy:
    """Exponential backoff + jitter + deadline. Defaults come from
    BODO_TPU_RETRY_ATTEMPTS / _RETRY_BASE_S / _RETRY_DEADLINE_S (or the
    same-named config fields when the package is imported)."""

    def __init__(self, max_attempts: Optional[int] = None,
                 base_s: Optional[float] = None,
                 factor: float = 2.0,
                 max_backoff_s: float = 10.0,
                 deadline_s: Optional[float] = None):
        self.max_attempts = int(max_attempts if max_attempts is not None
                                else _cfg("retry_attempts",
                                          "BODO_TPU_RETRY_ATTEMPTS", 3,
                                          int))
        self.base_s = float(base_s if base_s is not None
                            else _cfg("retry_base_s",
                                      "BODO_TPU_RETRY_BASE_S", 0.05,
                                      float))
        self.factor = float(factor)
        self.max_backoff_s = float(max_backoff_s)
        self.deadline_s = float(deadline_s if deadline_s is not None
                                else _cfg("retry_deadline_s",
                                          "BODO_TPU_RETRY_DEADLINE_S",
                                          30.0, float))

    def backoff(self, attempt: int) -> float:
        """Backoff before attempt `attempt`+1 (attempt is 1-based), with
        +/-50% jitter so gang-wide retries don't synchronize."""
        raw = min(self.base_s * (self.factor ** (attempt - 1)),
                  self.max_backoff_s)
        return raw * (0.5 + random.random())


def retry_call(fn: Callable[[], object], *, label: str,
               point: Optional[str] = None,
               policy: Optional[RetryPolicy] = None,
               classify: Callable[[BaseException], Optional[str]]
               = classify_transient,
               on_retry: Optional[Callable[[BaseException, int], None]]
               = None):
    """Call `fn()` under the retry envelope.

    `point` (optional) names a fault-injection point fired before EVERY
    attempt — an armed one-shot flake is absorbed by the retry, which is
    exactly the behavior chaos tests assert. Non-transient errors (per
    `classify`) raise immediately; transient ones retry with backoff
    until attempts or the deadline run out. Every retry is counted in
    `stats()["retries"][label]`.
    """
    p = policy or RetryPolicy()
    t0 = time.monotonic()
    attempt = 0
    while True:
        attempt += 1
        try:
            if point:
                maybe_inject(point)
            return fn()
        except Exception as e:
            cat = classify(e)
            elapsed = time.monotonic() - t0
            if (cat is None or attempt >= p.max_attempts
                    or elapsed >= p.deadline_s):
                raise
            delay = min(p.backoff(attempt),
                        max(p.deadline_s - elapsed, 0.0))
            with _lock:
                _stats["retries"][label] = \
                    _stats["retries"].get(label, 0) + 1
                _stats["retries_by_category"][cat] = \
                    _stats["retries_by_category"].get(cat, 0) + 1
            sys.stderr.write(
                f"bodo_tpu.resilience: {label} attempt {attempt} failed "
                f"({cat}: {type(e).__name__}: {str(e)[:160]}); retrying "
                f"in {delay:.2f}s\n")
            if on_retry is not None:
                on_retry(e, attempt)
            time.sleep(delay)


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def count_degradation(stage: str) -> None:
    with _lock:
        _stats["degraded_stages"][stage] = \
            _stats["degraded_stages"].get(stage, 0) + 1


def count_gang_retry() -> None:
    with _lock:
        _stats["gang_retries"] += 1


def stats() -> dict:
    """JSON-safe snapshot of all resilience counters plus the armed
    fault specs (embedded in tracing dumps)."""
    with _lock:
        return {
            "faults_armed": [f.spec() for f in (_armed or [])],
            "point_calls": dict(_calls),
            "faults_fired": dict(_stats["faults_fired"]),
            "retries": dict(_stats["retries"]),
            "retries_by_category": dict(_stats["retries_by_category"]),
            "degraded_stages": dict(_stats["degraded_stages"]),
            "gang_retries": _stats["gang_retries"],
        }


def reset_stats() -> None:
    """Zero the counters (tests); armed faults are untouched."""
    global _stats
    with _lock:
        _stats = _STATS_ZERO()
        _calls.clear()


# ---------------------------------------------------------------------------
# heartbeat (spawn worker side)
# ---------------------------------------------------------------------------


_hb_stop: Optional[threading.Event] = None
_hb_last: Optional[float] = None


def last_heartbeat_age() -> Optional[float]:
    """Seconds since this process's own heartbeat thread last beat, or
    None when no heartbeat ever ran (telemetry sampler input: a large
    age in a live process means the beat thread is starved/stopped)."""
    with _lock:
        t = _hb_last
    if t is None:
        return None
    return max(0.0, time.time() - t)


def start_heartbeat(path: str, interval_s: Optional[float] = None
                    ) -> threading.Event:
    """Touch `path` every `interval_s` from a daemon thread. The spawner
    watches the file's mtime: a wedged worker (no beat for the
    supervision window) gets its whole gang torn down with diagnostics
    instead of stalling everyone until the gang timeout. Returns the
    stop event."""
    global _hb_stop
    if interval_s is None:
        interval_s = _cfg("spawn_hb_interval_s",
                          "BODO_TPU_SPAWN_HB_INTERVAL", 0.5, float)
    stop = threading.Event()
    with _lock:
        _hb_stop = stop

    def _beat():
        global _hb_last
        while not stop.is_set():
            try:
                with open(path, "w") as f:
                    f.write(str(time.time()))
                with _lock:
                    _hb_last = time.time()
            except OSError:
                pass
            stop.wait(interval_s)

    t = threading.Thread(target=_beat, name="bodo-tpu-heartbeat",
                         daemon=True)
    t.start()
    return stop


def stop_heartbeat() -> None:
    """Silence this process's heartbeat thread. Chaos-test hook: a
    worker that stops beating AFTER its first beat landed simulates a
    process wedged mid-computation (the hb file exists but its mtime
    goes stale), exercising the supervisor's mtime-age path rather than
    the no-file startup-grace fallback."""
    if _hb_stop is not None:
        _hb_stop.set()
