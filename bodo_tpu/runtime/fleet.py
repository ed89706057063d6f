"""Fleet serving: one controller, many gang processes, peered caches.

PR 14 (runtime/scheduler.py) made the engine a serving system but caps
it at exactly one in-process gang. This module is the Pathways-style
single-controller shape over N of them (PAPERS §2; Ray's control plane
fronting many workers, PAPERS §5): a controller in the client process
spawns N **gang processes**, each running the PR 14 scheduler behind
its telemetry endpoint, and multiplexes many logical sessions over the
fleet through a small length-prefixed wire protocol.

WIRE PROTOCOL (stdlib sockets): every frame is a 5-byte header
``struct.pack(">IB", len(body), kind)`` followed by the body. Kind
``J`` is a UTF-8 JSON object (control plane), kind ``P`` is a pickle
(cloudpickle for thunks, plain payloads for results — the data plane).
A header whose length exceeds ``config.fleet_frame_max`` is a typed
:class:`ProtocolError` before any allocation; EOF mid-frame is a typed
``truncated frame``. One TCP connection carries one request/response
exchange. Ops: ``ping``, ``open``, ``submit`` (header frame + pickled
thunk frame; the gang streams back an ``ack`` frame at enqueue and a
``result`` frame — + pickled payload on success — at completion, so a
gang dying mid-query is an observable mid-stream EOF, not a hang),
``close``, ``peer_get`` (+ pickled cache key), ``invalidate``,
``stats``, ``shutdown``.

ROUTING: plan/routing keys map to gangs by consistent hashing (64
virtual nodes per gang) so result/plan-cache locality survives
scale-out and a gang join/leave moves only ~1/N of the keyspace. The
routing key defaults to a digest of the cloudpickled thunk — a
repeat-issued query template lands on the same gang every time; callers
with a real plan fingerprint can pass it explicitly.

ADMISSION: a scrape thread GETs each gang's ``/metrics`` + ``/healthz``
every ``FLEET_SCRAPE_S`` and runs the SAME admission decision
the gang would make locally (``signals_from_health`` merged with
``signals_from_metrics`` — built for exactly this remote-twin use).
Submits route around shed/degraded/backed-off gangs to the next ring
successor; a gang failing ``FLEET_DEAD_SCRAPES`` consecutive
scrapes (or observed dead at submit time) is evicted from the ring.
When no gang is serviceable the client gets the healthiest gang's typed
rejection with its retry hint — never a hang.

CACHE PEERING: on a local result-cache q-miss the owning gang asks the
routing key's PREVIOUS owner (the previous ring's owner after a
membership change, else the ring successor) for its copy over
``peer_get`` before recomputing (result_cache.set_peer_hooks). Dataset
mutations invalidate fleet-wide: when a gang's cache drops a stale
entry, the mutated source paths ride the submit response back to the
controller, which broadcasts ``invalidate`` to every other gang — no
peer ever serves a pre-mutation result.

SLO CLASSES + QUOTAS: sessions carry ``slo="latency"|"throughput"``
end-to-end (the gang scheduler ages latency-class queues
``scheduler.SERVE_LATENCY_BOOST``× faster) and the controller enforces a
per-session in-flight quota (``config.fleet_session_quota``) as a typed
``Overloaded(reason="session_quota")``.

Everything here is stdlib-only at import time (sockets, json, struct,
urllib); jax lives in the gang processes. ``bodo_tpu.fleet`` is the
client façade.
"""

from __future__ import annotations

import hashlib
import io
import json
import os
import pickle
import socket
import struct
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request
from bisect import bisect_right
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Dict, List, Optional, Tuple

from bodo_tpu.config import config
from bodo_tpu.runtime.scheduler import (
    AdmissionController,
    BackOff,
    Degraded,
    Overloaded,
    QueryFailed,
    ServeRejection,
    signals_from_health,
    signals_from_metrics,
)
from bodo_tpu.utils.logging import log

__all__ = [
    "ProtocolError", "FleetController", "FleetSession", "RemoteFleet",
    "start", "stop", "controller", "controller_stats", "connect",
    "gang_main",
]


class ProtocolError(RuntimeError):
    """Malformed wire traffic: truncated frame, oversized header, bad
    kind byte, or a JSON/pickle body that does not decode."""


# ---------------------------------------------------------------------------
# framing
# ---------------------------------------------------------------------------

# Controller scrape cadence of each gang's /metrics + /healthz.
FLEET_SCRAPE_S = 0.5
# Consecutive failed scrapes before a gang is declared dead and evicted
# from the routing ring.
FLEET_DEAD_SCRAPES = 3

_HDR = struct.Struct(">IB")
_KIND_JSON = ord("J")
_KIND_PICKLE = ord("P")


def _frame_max() -> int:
    try:
        return max(int(config.fleet_frame_max), 1 << 16)
    except Exception:  # noqa: BLE001
        return 64 << 20


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = io.BytesIO()
    got = 0
    while got < n:
        chunk = sock.recv(min(n - got, 1 << 20))
        if not chunk:
            raise ProtocolError(
                f"truncated frame: peer closed after {got}/{n} bytes")
        buf.write(chunk)
        got += len(chunk)
    return buf.getvalue()


def _send_frame(sock: socket.socket, kind: int, body: bytes) -> None:
    sock.sendall(_HDR.pack(len(body), kind) + body)


def _recv_frame(sock: socket.socket) -> Tuple[int, bytes]:
    hdr = _recv_exact(sock, _HDR.size)
    length, kind = _HDR.unpack(hdr)
    if kind not in (_KIND_JSON, _KIND_PICKLE):
        raise ProtocolError(f"unknown frame kind {kind:#x}")
    if length > _frame_max():
        # reject BEFORE allocating: an adversarial header must not be
        # able to balloon the receiver
        raise ProtocolError(
            f"oversized frame: {length} bytes > fleet_frame_max "
            f"{_frame_max()}")
    return kind, _recv_exact(sock, length)


def _send_json(sock: socket.socket, obj: dict) -> None:
    _send_frame(sock, _KIND_JSON,
                json.dumps(obj, default=str).encode("utf-8"))


def _recv_json(sock: socket.socket) -> dict:
    kind, body = _recv_frame(sock)
    if kind != _KIND_JSON:
        raise ProtocolError("expected a JSON frame")
    try:
        out = json.loads(body.decode("utf-8"))
    except Exception as e:  # noqa: BLE001
        raise ProtocolError(f"bad JSON frame: {e}") from None
    if not isinstance(out, dict):
        raise ProtocolError("JSON frame is not an object")
    return out


def _send_pickle(sock: socket.socket, obj) -> None:
    import cloudpickle
    _send_frame(sock, _KIND_PICKLE, cloudpickle.dumps(obj))


def _recv_pickle(sock: socket.socket):
    kind, body = _recv_frame(sock)
    if kind != _KIND_PICKLE:
        raise ProtocolError("expected a pickle frame")
    try:
        return pickle.loads(body)
    except Exception as e:  # noqa: BLE001
        raise ProtocolError(f"bad pickle frame: {e}") from None


def _connect(addr: str, timeout: float = 10.0) -> socket.socket:
    host, port = addr.rsplit(":", 1)
    s = socket.create_connection((host, int(port)), timeout=timeout)
    # multi-frame exchanges (submit = header + thunk) must not sit in
    # Nagle's buffer waiting for a delayed ACK
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    return s


# typed-rejection transport: exceptions cross the wire as
# {"etype", "msg", "reason", "retry_after_s"} and are reconstructed as
# the SAME types client-side, so the PR 14 backpressure contract holds
# end-to-end across the fleet.
_ETYPES = {"Overloaded": Overloaded, "Degraded": Degraded,
           "BackOff": BackOff, "ServeRejection": ServeRejection}


def _exc_to_wire(e: BaseException) -> dict:
    if isinstance(e, ServeRejection):
        return {"ok": False, "etype": type(e).__name__, "msg": str(e),
                "reason": e.reason, "retry_after_s": e.retry_after_s}
    if isinstance(e, QueryFailed):
        return {"ok": False, "etype": "QueryFailed", "msg": str(e),
                "session": e.session_id, "qid": e.query_id,
                "cause": f"{type(e.__cause__).__name__}: {e.__cause__}"
                if e.__cause__ else ""}
    return {"ok": False, "etype": "QueryFailed", "msg": str(e),
            "cause": f"{type(e).__name__}: {e}"}


def _exc_from_wire(d: dict, *, sid: str = "-",
                   qid: Optional[str] = None) -> BaseException:
    et = d.get("etype", "")
    if et in _ETYPES:
        return _ETYPES[et](d.get("msg", et),
                           retry_after_s=float(d.get("retry_after_s",
                                                     0.0)),
                           reason=d.get("reason", ""))
    cause = RuntimeError(d.get("cause") or d.get("msg", "remote error"))
    return QueryFailed(d.get("session", sid), d.get("qid", qid), cause)


# ---------------------------------------------------------------------------
# consistent-hash ring
# ---------------------------------------------------------------------------

class _Ring:
    """Consistent-hash ring with virtual nodes. Membership changes
    snapshot the previous point list so the fingerprint's PREVIOUS
    owner (the peer most likely to hold a migrated key's cache entry)
    stays derivable for one generation."""

    def __init__(self, vnodes: int = 64):
        self._vnodes = max(int(vnodes), 1)
        self._points: List[Tuple[int, str]] = []
        self._prev: Optional[List[Tuple[int, str]]] = None
        self._members: List[str] = []

    @staticmethod
    def _h(s: str) -> int:
        return int.from_bytes(
            hashlib.sha256(s.encode("utf-8")).digest()[:8], "big")

    def members(self) -> List[str]:
        return list(self._members)

    def add(self, gid: str) -> None:
        if gid in self._members:
            return
        self._prev = list(self._points)
        self._members.append(gid)
        self._points.extend((self._h(f"{gid}#{i}"), gid)
                            for i in range(self._vnodes))
        self._points.sort()

    def remove(self, gid: str) -> None:
        if gid not in self._members:
            return
        self._prev = list(self._points)
        self._members.remove(gid)
        self._points = [p for p in self._points if p[1] != gid]

    @staticmethod
    def _owner_in(points: List[Tuple[int, str]], h: int) -> Optional[str]:
        if not points:
            return None
        i = bisect_right(points, (h, "￿")) % len(points)
        return points[i][1]

    def owner(self, key: str) -> Optional[str]:
        return self._owner_in(self._points, self._h(key))

    def successors(self, key: str) -> List[str]:
        """Distinct gangs in ring order starting at the key's owner —
        the routing preference list."""
        if not self._points:
            return []
        h = self._h(key)
        i = bisect_right(self._points, (h, "￿"))
        seen: List[str] = []
        n = len(self._points)
        for j in range(n):
            gid = self._points[(i + j) % n][1]
            if gid not in seen:
                seen.append(gid)
        return seen

    def prev_owner(self, key: str) -> Optional[str]:
        """Designated peering target: the previous ring generation's
        owner when it differs from the current one (the gang that held
        the key before a join/leave), else the current ring successor."""
        cur = self.owner(key)
        if self._prev is not None:
            old = self._owner_in(self._prev, self._h(key))
            if old is not None and old != cur and old in self._members:
                return old
        succ = self.successors(key)
        for gid in succ[1:]:
            return gid
        return None


# ---------------------------------------------------------------------------
# gang process side
# ---------------------------------------------------------------------------

_tls = threading.local()


def _gang_peer_fetch(key):
    """result_cache fetch hook (runs on the gang's scheduler worker
    thread): ask the controller-designated peer for its copy of this
    cache key. The hint is per-query, set by the submit wrapper."""
    addr = getattr(_tls, "peer_addr", None)
    if not addr:
        return None
    try:
        with _connect(addr, timeout=10.0) as s:
            _send_json(s, {"op": "peer_get"})
            _send_pickle(s, key)
            head = _recv_json(s)
            if not head.get("found"):
                return None
            return _recv_pickle(s)
    except Exception as e:  # noqa: BLE001 - peering is best-effort
        log(2, f"fleet: peer_get from {addr} failed: "
               f"{type(e).__name__}: {e}")
        return None


def _gang_peer_notify(paths) -> None:
    """result_cache notify hook: collect mutation-invalidated source
    paths into the per-query box; they ride the submit response back to
    the controller for fleet-wide broadcast."""
    box = getattr(_tls, "inval_box", None)
    if box is not None:
        for p in paths:
            if p not in box:
                box.append(p)


def _wrap_thunk(fn: Callable, peer_addr: Optional[str],
                inval_box: list) -> Callable:
    def wrapped():
        _tls.peer_addr = peer_addr
        _tls.inval_box = inval_box
        try:
            return fn()
        finally:
            _tls.peer_addr = None
            _tls.inval_box = None
    return wrapped


def _gang_handle(conn: socket.socket, gang_id: str) -> None:
    """One request/response exchange on an accepted connection."""
    from bodo_tpu.runtime import result_cache as rcache
    from bodo_tpu.runtime import scheduler as sched_mod
    try:
        req = _recv_json(conn)
    except ProtocolError as e:
        # hostile/truncated input: answer typed when the socket still
        # works, then drop the connection — never take the gang down
        try:
            _send_json(conn, {"ok": False, "etype": "ProtocolError",
                              "msg": str(e)})
        except Exception:  # noqa: BLE001
            pass
        return
    op = req.get("op")
    if op == "ping":
        _send_json(conn, {"ok": True, "gang_id": gang_id,
                          "pid": os.getpid()})
    elif op == "open":
        sched_mod.scheduler().session(
            req.get("sid"), priority=float(req.get("weight", 1.0)),
            allow_degraded=bool(req.get("allow_degraded", False)),
            slo=req.get("slo", "throughput"))
        _send_json(conn, {"ok": True, "gang_id": gang_id})
    elif op == "close":
        sch = sched_mod.scheduler()
        s = sch._sessions.get(req.get("sid"))
        if s is not None:
            sch.close_session(s)
        _send_json(conn, {"ok": True})
    elif op == "submit":
        _gang_handle_submit(conn, req, gang_id)
    elif op == "peer_get":
        key = _recv_pickle(conn)
        payload = None
        try:
            payload = rcache.peer_export(key)
        except Exception:  # noqa: BLE001
            payload = None
        if payload is None:
            _send_json(conn, {"found": False})
        else:
            _send_json(conn, {"found": True})
            _send_pickle(conn, payload)
    elif op == "invalidate":
        n = 0
        try:
            n = rcache.invalidate_paths(req.get("paths") or [])
        except Exception:  # noqa: BLE001
            pass
        _send_json(conn, {"ok": True, "dropped": int(n)})
    elif op == "stats":
        out = {"ok": True, "gang_id": gang_id, "pid": os.getpid()}
        try:
            out["scheduler"] = sched_mod.scheduler().stats()
        except Exception:  # noqa: BLE001
            pass
        try:
            out["result_cache"] = {
                k: v for k, v in rcache.stats().items()
                if isinstance(v, (int, float, str, bool))}
        except Exception:  # noqa: BLE001
            pass
        _send_json(conn, out)
    elif op == "shutdown":
        _send_json(conn, {"ok": True})
        try:
            conn.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        os._exit(0)
    else:
        _send_json(conn, {"ok": False, "etype": "ProtocolError",
                          "msg": f"unknown op {op!r}"})


def _gang_handle_submit(conn: socket.socket, req: dict,
                        gang_id: str) -> None:
    from bodo_tpu.runtime import resilience
    from bodo_tpu.runtime import scheduler as sched_mod
    sid = req.get("sid") or "default"
    qid = req.get("qid")
    try:
        fn = _recv_pickle(conn)
    except ProtocolError as e:
        _send_json(conn, {"ok": False, "etype": "ProtocolError",
                          "msg": str(e)})
        return
    inval_box: list = []
    sch = sched_mod.scheduler()
    session = sch.session(
        sid, priority=float(req.get("weight", 1.0)),
        allow_degraded=bool(req.get("allow_degraded", False)),
        slo=req.get("slo", "throughput"))
    try:
        fut = session.submit(
            _wrap_thunk(fn, req.get("peer"), inval_box))
    except (ServeRejection, QueryFailed) as e:
        _send_json(conn, _exc_to_wire(e))
        return
    # enqueue acknowledged: from here on the client is mid-stream, so
    # a dying gang is an observable EOF instead of a silent hang. The
    # chaos injection point sits exactly here — after the ack, before
    # the result — to exercise that path.
    _send_json(conn, {"ev": "ack", "qid": qid, "gang_id": gang_id})
    resilience.maybe_inject("fleet.serve")
    try:
        result = fut.result(timeout=600.0)
    except (ServeRejection, QueryFailed) as e:
        _send_json(conn, dict(_exc_to_wire(e), ev="result",
                              invalidated=inval_box))
        return
    except Exception as e:  # noqa: BLE001
        _send_json(conn, dict(_exc_to_wire(e), ev="result",
                              invalidated=inval_box))
        return
    _send_json(conn, {"ev": "result", "ok": True, "qid": qid,
                      "invalidated": inval_box})
    _send_pickle(conn, result)


def _watch_parent() -> None:
    """Exit when the controller goes away: stdin is the controller's
    pipe; EOF means the parent died or dropped us."""
    try:
        while True:
            b = sys.stdin.buffer.read(1)
            if not b:
                break
    except Exception:  # noqa: BLE001
        pass
    os._exit(0)


def gang_main() -> None:
    """Entry point of a fleet gang process (spawned by the controller):
    bring up the local scheduler + telemetry endpoint + peering hooks,
    write the ready file, then serve the wire protocol forever."""
    gang_id = os.environ.get("BODO_TPU_GANG_ID") \
        or f"gang-{os.getpid()}"
    os.environ["BODO_TPU_GANG_ID"] = gang_id
    ready_path = os.environ.get("BODO_TPU_FLEET_READY", "")

    from bodo_tpu.runtime import result_cache as rcache
    from bodo_tpu.runtime import scheduler as sched_mod
    from bodo_tpu.runtime import telemetry
    rcache.set_peer_hooks(fetch=_gang_peer_fetch,
                          notify=_gang_peer_notify)
    sched_mod.scheduler()._ensure_workers()
    telem_addr = telemetry.serve(0)

    srv = socket.create_server(("127.0.0.1", 0))
    srv.listen(64)
    port = srv.getsockname()[1]

    threading.Thread(target=_watch_parent, daemon=True,
                     name="fleet-parent-watch").start()

    if ready_path:
        tmp = ready_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"gang_id": gang_id, "pid": os.getpid(),
                       "serve_addr": f"127.0.0.1:{port}",
                       "telemetry_addr": telem_addr}, f)
        os.replace(tmp, ready_path)
    log(1, f"fleet gang {gang_id} serving on 127.0.0.1:{port} "
           f"(telemetry {telem_addr})")

    while True:
        try:
            conn, _ = srv.accept()
        except OSError:
            break
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

        def _run(c=conn):
            try:
                _gang_handle(c, gang_id)
            except Exception as e:  # noqa: BLE001 - one bad conn only
                log(2, f"fleet gang {gang_id}: connection error: "
                       f"{type(e).__name__}: {e}")
            finally:
                try:
                    c.close()
                except OSError:
                    pass

        threading.Thread(target=_run, daemon=True).start()


# ---------------------------------------------------------------------------
# controller side
# ---------------------------------------------------------------------------

import re as _re

_PEER_HITS_RE = _re.compile(
    r'bodo_tpu_result_cache_events_total\{[^}]*event="peer_hits"'
    r'[^}]*\}\s+([0-9.eE+-]+)')


class _GangState:
    __slots__ = ("gang_id", "proc", "serve_addr", "telemetry_addr",
                 "state", "reason", "retry_after_s", "fail_scrapes",
                 "admission", "stdin", "peer_hits", "capacity_frac")

    def __init__(self, gang_id: str):
        self.gang_id = gang_id
        self.proc: Optional[subprocess.Popen] = None
        self.serve_addr = ""
        self.telemetry_addr = ""
        self.state = "ok"           # ok|shed|degraded|backoff|dead
        self.reason = ""
        self.retry_after_s = 0.0
        self.fail_scrapes = 0
        self.peer_hits = 0
        # surviving-rank fraction scraped from /healthz["elastic"]; a
        # shrunk gang (< 1.0) keeps serving but at reduced throughput,
        # so quota and routing scale by it rather than evicting.
        self.capacity_frac = 1.0
        # one admission twin PER GANG: the pressure-event memory (last
        # OOM/shed counters) is per-scrape-target state
        self.admission = AdmissionController()
        self.stdin = None


class FleetSession:
    """One logical tenant session fanned over the fleet. Thread-safe;
    futures resolve on the controller's worker pool."""

    def __init__(self, ctl: "FleetController", sid: str, *,
                 priority: float = 1.0, slo: str = "throughput",
                 allow_degraded: bool = False):
        self._ctl = ctl
        self.sid = sid
        self.weight = max(float(priority), 0.01)
        self.slo = slo if slo in ("latency", "throughput") \
            else "throughput"
        self.allow_degraded = bool(allow_degraded)
        self._mu = threading.Lock()
        self._inflight = 0
        self._qseq = 0
        self.closed = False

    def submit(self, fn: Callable, *, key: Optional[str] = None) -> Future:
        """Queue a thunk on the fleet; returns a Future. ``key`` is the
        routing key (defaults to a digest of the pickled thunk, so a
        verbatim-repeated template routes to the same gang and its warm
        result cache). Raises typed rejections synchronously when the
        session is closed, over quota, or no gang is serviceable."""
        return self._ctl._submit(self, fn, key)

    def run(self, fn: Callable, *, key: Optional[str] = None,
            timeout: Optional[float] = None):
        return self.submit(fn, key=key).result(timeout=timeout)

    def close(self) -> None:
        self.closed = True
        self._ctl._close_session(self)


class FleetController:
    """Single controller fronting N gang processes."""

    def __init__(self, gangs: Optional[int] = None, *,
                 gang_env: Optional[Dict[int, Dict[str, str]]] = None):
        self.n_gangs = int(gangs if gangs is not None
                           else config.fleet_gangs)
        if self.n_gangs < 1:
            raise ValueError("fleet needs at least one gang")
        self._gang_env = gang_env or {}
        self._mu = threading.Lock()
        self._gangs: Dict[str, _GangState] = {}
        self._ring = _Ring()
        self._sessions: Dict[str, FleetSession] = {}
        self._pool = ThreadPoolExecutor(
            max_workers=16, thread_name_prefix="fleet-rt")
        self._stop_ev = threading.Event()
        self._scrape_thread: Optional[threading.Thread] = None
        self._listener: Optional[socket.socket] = None
        self._tmpdir: Optional[str] = None
        self._c: Dict[str, int] = {}
        self._started = False
        self._next_idx = 0

    # -- lifecycle ---------------------------------------------------------

    def _spawn_gang(self, i: int) -> Tuple[_GangState, str]:
        gid = f"gang-{i}"
        ready = os.path.join(self._tmpdir, f"ready_{i}.json")
        pkg_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env.update({
            "BODO_TPU_GANG_ID": gid,
            "BODO_TPU_FLEET_READY": ready,
            "PYTHONPATH": pkg_root + os.pathsep +
            env.get("PYTHONPATH", ""),
        })
        # CPU by default: N gangs sharing one host must not fight
        # over an accelerator unless the caller says so explicitly
        env.setdefault("JAX_PLATFORMS", "cpu")
        env.update(self._gang_env.get(i, {}))
        g = _GangState(gid)
        ef = open(os.path.join(self._tmpdir, f"stderr_{i}.log"), "wb")
        of = open(os.path.join(self._tmpdir, f"stdout_{i}.log"), "wb")
        g.proc = subprocess.Popen(
            [sys.executable, "-c",
             "from bodo_tpu.runtime.fleet import gang_main; "
             "gang_main()"],
            env=env, stdin=subprocess.PIPE, stdout=of, stderr=ef,
            cwd=pkg_root)
        g.stdin = g.proc.stdin
        return g, ready

    def _await_ready(self, g: _GangState, ready: str,
                     deadline: float) -> None:
        while not os.path.exists(ready):
            if g.proc.poll() is not None:
                raise RuntimeError(
                    f"fleet gang {g.gang_id} died during startup "
                    f"(rc={g.proc.returncode}); stderr: "
                    f"{self._tail(g.gang_id)}")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"fleet gang {g.gang_id} not ready in time")
            time.sleep(0.05)
        with open(ready) as f:
            info = json.load(f)
        g.serve_addr = info["serve_addr"]
        g.telemetry_addr = info.get("telemetry_addr") or ""

    def start(self, *, timeout: float = 120.0) -> "FleetController":
        if self._started:
            return self
        self._tmpdir = tempfile.mkdtemp(prefix="bodo_tpu_fleet_")
        ready_paths = {}
        for i in range(self.n_gangs):
            g, ready = self._spawn_gang(i)
            self._gangs[g.gang_id] = g
            ready_paths[g.gang_id] = ready
        self._next_idx = self.n_gangs
        deadline = time.monotonic() + timeout
        for gid, ready in ready_paths.items():
            g = self._gangs[gid]
            try:
                self._await_ready(g, ready, deadline)
            except TimeoutError:
                self.stop()
                raise
            self._ring.add(gid)
        self._started = True
        self._stop_ev.clear()
        self._scrape_thread = threading.Thread(
            target=self._scrape_loop, daemon=True, name="fleet-scrape")
        self._scrape_thread.start()
        port = int(config.fleet_port)
        if port >= 0:
            self.listen(port)
        log(1, f"fleet controller up: {self.n_gangs} gangs "
               f"({', '.join(g.serve_addr for g in self._gangs.values())})")
        return self

    def add_gang(self, *, timeout: float = 120.0,
                 env: Optional[Dict[str, str]] = None) -> str:
        """Scale out: spawn one more gang and join it to the ring.
        Only ~1/N of the keyspace moves to it; moved keys peer-fetch
        their cache entries from the previous owner on first miss, so
        locality survives the join. Returns the new gang id."""
        if not self._started:
            raise RuntimeError("fleet is not running")
        with self._mu:
            i = self._next_idx
            self._next_idx += 1
        if env:
            self._gang_env[i] = dict(env)
        g, ready = self._spawn_gang(i)
        self._await_ready(g, ready, time.monotonic() + timeout)
        with self._mu:
            self._gangs[g.gang_id] = g
            self._ring.add(g.gang_id)
            self.n_gangs = len(self._ring.members())
        log(1, f"fleet: gang {g.gang_id} joined "
               f"({g.serve_addr}); ring is now {self._ring.members()}")
        return g.gang_id

    def _tail(self, gid: str, n: int = 2000) -> str:
        try:
            i = gid.rsplit("-", 1)[1]
            with open(os.path.join(self._tmpdir, f"stderr_{i}.log"),
                      "rb") as f:
                return f.read()[-n:].decode("utf-8", "replace")
        except Exception:  # noqa: BLE001
            return ""

    def stop(self, *, timeout: float = 10.0) -> None:
        self._stop_ev.set()
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        for g in self._gangs.values():
            if g.proc is None or g.proc.poll() is not None:
                continue
            try:
                with _connect(g.serve_addr, timeout=2.0) as s:
                    _send_json(s, {"op": "shutdown"})
                    _recv_json(s)
            except Exception:  # noqa: BLE001
                pass
        deadline = time.monotonic() + timeout
        for g in self._gangs.values():
            if g.proc is None:
                continue
            try:
                if g.stdin is not None:
                    g.stdin.close()
            except OSError:
                pass
            try:
                g.proc.wait(timeout=max(deadline - time.monotonic(),
                                        0.1))
            except subprocess.TimeoutExpired:
                g.proc.kill()
                g.proc.wait(timeout=5.0)
        self._pool.shutdown(wait=False)
        self._started = False

    # -- scraping / admission ---------------------------------------------

    def _scrape_one(self, g: _GangState) -> None:
        if not g.telemetry_addr:
            return
        try:
            with urllib.request.urlopen(
                    f"http://{g.telemetry_addr}/healthz",
                    timeout=3.0) as r:
                health = json.loads(r.read().decode("utf-8"))
            with urllib.request.urlopen(
                    f"http://{g.telemetry_addr}/metrics",
                    timeout=3.0) as r:
                met = r.read().decode("utf-8")
        except Exception:  # noqa: BLE001
            with self._mu:
                g.fail_scrapes += 1
                self._c["scrape_failures"] = \
                    self._c.get("scrape_failures", 0) + 1
                if g.fail_scrapes >= FLEET_DEAD_SCRAPES \
                        and g.state != "dead":
                    self._mark_dead_locked(
                        g, f"{g.fail_scrapes} consecutive scrape "
                           f"failures")
            return
        sig = signals_from_health(health).merged(
            signals_from_metrics(met))
        m = _PEER_HITS_RE.search(met)
        if m is not None:
            try:
                g.peer_hits = int(float(m.group(1)))
            except ValueError:
                pass
        d = g.admission.decide(sig, None)
        with self._mu:
            g.fail_scrapes = 0
            if g.state == "dead":
                return  # eviction is one-way; restart is out of scope
            state = {"admit": "ok", "shed": "shed",
                     "degrade": "degraded",
                     "backoff": "backoff"}.get(d.action, "ok")
            if state != g.state:
                log(1, f"fleet: gang {g.gang_id} {g.state} -> {state}"
                       f" ({d.reason})")
            g.state = state
            g.reason = d.reason
            g.retry_after_s = d.retry_after_s
            cap = sig.gang_capacity_frac
            cap = 1.0 if cap is None else min(max(float(cap), 0.0), 1.0)
            if cap != g.capacity_frac:
                log(1, f"fleet: gang {g.gang_id} capacity "
                       f"{g.capacity_frac:.2f} -> {cap:.2f} "
                       f"(elastic epoch {sig.elastic_epoch})")
            g.capacity_frac = cap

    def _mark_dead_locked(self, g: _GangState, why: str) -> None:
        g.state = "dead"
        g.reason = why
        self._ring.remove(g.gang_id)
        self._c["gangs_evicted"] = self._c.get("gangs_evicted", 0) + 1
        log(0, f"fleet: gang {g.gang_id} declared dead ({why}); "
               f"evicted from ring — keyspace reroutes to "
               f"{self._ring.members()}")

    def _scrape_loop(self) -> None:
        while not self._stop_ev.is_set():
            for g in list(self._gangs.values()):
                if self._stop_ev.is_set():
                    return
                if g.state == "dead":
                    continue
                if g.proc is not None and g.proc.poll() is not None:
                    with self._mu:
                        if g.state != "dead":
                            self._mark_dead_locked(
                                g, f"process exited "
                                   f"rc={g.proc.returncode}")
                    continue
                self._scrape_one(g)
            self._push_metrics()
            self._stop_ev.wait(FLEET_SCRAPE_S)

    def _push_metrics(self) -> None:
        try:
            from bodo_tpu.utils import metrics
            gs = metrics.gauge("bodo_tpu_fleet_gangs",
                               "fleet gangs by controller-visible "
                               "state", ("state",))
            by: Dict[str, int] = {}
            with self._mu:
                for g in self._gangs.values():
                    by[g.state] = by.get(g.state, 0) + 1
                c = dict(self._c)
                c["peer_hits"] = sum(g.peer_hits
                                     for g in self._gangs.values())
                n_sessions = len(self._sessions)
            for st in ("ok", "shed", "degraded", "backoff", "dead"):
                gs.labels(state=st).set(by.get(st, 0))
            metrics.gauge("bodo_tpu_fleet_sessions",
                          "open fleet sessions").set(n_sessions)
            for name, help_ in (
                    ("rerouted", "submits routed around an "
                                 "unhealthy/dead gang"),
                    ("scrape_failures", "failed gang scrapes"),
                    ("gangs_evicted", "gangs evicted from the ring"),
                    ("invalidations_broadcast",
                     "fleet-wide cache invalidation broadcasts"),
                    ("quota_rejections",
                     "session-quota typed rejections"),
                    ("peer_hits", "peered cache fills observed in "
                                  "submit responses")):
                metrics.gauge(f"bodo_tpu_fleet_{name}_total",
                              help_).set(c.get(name, 0))
        except Exception:  # noqa: BLE001 - metrics must never hurt
            pass

    # -- sessions / submission --------------------------------------------

    def session(self, session_id: Optional[str] = None, *,
                priority: float = 1.0, slo: str = "throughput",
                allow_degraded: bool = False) -> FleetSession:
        with self._mu:
            sid = session_id or f"fs{len(self._sessions) + 1}"
            s = self._sessions.get(sid)
            if s is None:
                s = FleetSession(self, sid, priority=priority, slo=slo,
                                 allow_degraded=allow_degraded)
                self._sessions[sid] = s
            else:
                s.weight = max(float(priority), 0.01)
                s.slo = slo if slo in ("latency", "throughput") \
                    else "throughput"
                s.allow_degraded = bool(allow_degraded)
                s.closed = False
            return s

    def _close_session(self, s: FleetSession) -> None:
        for g in self._gangs.values():
            if g.state == "dead" or not g.serve_addr:
                continue
            try:
                with _connect(g.serve_addr, timeout=3.0) as sock:
                    _send_json(sock, {"op": "close", "sid": s.sid})
                    _recv_json(sock)
            except Exception:  # noqa: BLE001
                pass

    @staticmethod
    def _routing_key(fn: Callable, key: Optional[str]) -> str:
        if key:
            return str(key)
        try:
            import cloudpickle
            return hashlib.sha256(
                cloudpickle.dumps(fn)).hexdigest()[:24]
        except Exception:  # noqa: BLE001 - unroutable ≠ unservable
            return f"anon-{id(fn)}"

    def _route(self, rkey: str) -> _GangState:
        """Owner gang for a routing key, walking ring successors around
        non-ok gangs. All-bad ⇒ the healthiest gang's typed rejection
        (with its retry hint) so clients back off instead of hanging."""
        with self._mu:
            order = self._ring.successors(rkey)
            cands = [self._gangs[gid] for gid in order
                     if gid in self._gangs]
            if not cands:
                raise Overloaded(
                    "fleet has no live gangs (all evicted)",
                    retry_after_s=max(
                        float(config.serve_retry_after_s), 0.25) * 4,
                    reason="no_gangs")
            ok = [(i, g) for i, g in enumerate(cands)
                  if g.state == "ok"]
            if ok:
                # affinity first — but when the ring owner is a shrunk
                # (elastic) gang and a full-capacity gang is also ok,
                # spill the key to the full gang: the shrunk gang keeps
                # its warm keys only while no better host exists
                i, g = ok[0]
                if g.capacity_frac < 1.0:
                    full = [(j, h) for j, h in ok
                            if h.capacity_frac >= 1.0]
                    if full:
                        i, g = full[0]
                        self._c["capacity_rerouted"] = \
                            self._c.get("capacity_rerouted", 0) + 1
                if i > 0:
                    self._c["rerouted"] = \
                        self._c.get("rerouted", 0) + 1
                return g
            # no healthy gang: surface the least-bad state typed
            sev = {"backoff": 0, "shed": 1, "degraded": 2, "dead": 3}
            best = min(cands, key=lambda g: sev.get(g.state, 3))
            exc_cls = {"shed": Overloaded, "backoff": BackOff,
                       "degraded": Degraded}.get(best.state, Overloaded)
            raise exc_cls(
                f"no serviceable gang: best is {best.gang_id} "
                f"({best.state}: {best.reason})",
                retry_after_s=best.retry_after_s
                or FLEET_SCRAPE_S * 2,
                reason=f"fleet_{best.state}")

    def _capacity_frac(self) -> float:
        """Mean surviving-rank fraction across live gangs (1.0 for an
        unshrunk fleet; dead gangs don't count — the ring already
        rerouted their keyspace)."""
        with self._mu:
            caps = [g.capacity_frac for g in self._gangs.values()
                    if g.state != "dead"]
        if not caps:
            return 1.0
        return min(max(sum(caps) / len(caps), 0.0), 1.0)

    def _submit(self, s: FleetSession, fn: Callable,
                key: Optional[str]) -> Future:
        if s.closed:
            raise Overloaded(f"fleet session {s.sid!r} is closed",
                             reason="session_closed")
        # a shrunk fleet admits proportionally less: quota scales by the
        # mean surviving-rank fraction of live gangs, so an elastic
        # N->N-1 shrink sheds load instead of queueing it onto fewer
        # ranks (capacity restores to 1.0 once the gang grows back)
        cap = self._capacity_frac()
        quota = max(int(round(int(config.fleet_session_quota) * cap)), 1)
        with s._mu:
            if s._inflight >= quota:
                self._c["quota_rejections"] = \
                    self._c.get("quota_rejections", 0) + 1
                raise Overloaded(
                    f"session {s.sid!r} has {s._inflight} queries in "
                    f"flight (quota {quota})",
                    retry_after_s=max(
                        float(config.serve_retry_after_s), 0.25),
                    reason="session_quota")
            s._inflight += 1
            s._qseq += 1
            qid = f"{s.sid}-q{s._qseq}"
        rkey = self._routing_key(fn, key)
        fut = self._pool.submit(self._roundtrip, s, fn, rkey, qid)

        def _done(_):
            with s._mu:
                s._inflight -= 1
        fut.add_done_callback(_done)
        return fut

    def _roundtrip(self, s: FleetSession, fn: Callable, rkey: str,
                   qid: str):
        """Blocking submit exchange with the owner gang (runs on the
        controller pool). Mid-stream gang death becomes a typed
        QueryFailed AND an immediate eviction — queued work re-routes,
        the in-flight query is NOT silently retried."""
        g = self._route(rkey)
        with self._mu:
            peer = self._ring.prev_owner(rkey)
            peer_addr = None
            if peer is not None and peer != g.gang_id:
                pg = self._gangs.get(peer)
                if pg is not None and pg.state != "dead":
                    peer_addr = pg.serve_addr
        try:
            sock = _connect(g.serve_addr, timeout=10.0)
        except OSError as e:
            # never reached the gang: routing again is safe (nothing
            # ran). Mark it and take the next ring successor.
            self._note_gang_failure(g, f"connect failed: {e}")
            g2 = self._route(rkey)
            if g2.gang_id == g.gang_id:
                raise QueryFailed(s.sid, qid, e) from None
            return self._roundtrip_on(g2, s, fn, rkey, qid, peer_addr)
        with sock:
            return self._exchange(sock, g, s, fn, qid, peer_addr)

    def _roundtrip_on(self, g: _GangState, s: FleetSession,
                      fn: Callable, rkey: str, qid: str,
                      peer_addr: Optional[str]):
        try:
            sock = _connect(g.serve_addr, timeout=10.0)
        except OSError as e:
            self._note_gang_failure(g, f"connect failed: {e}")
            raise QueryFailed(s.sid, qid, e) from None
        with sock:
            return self._exchange(sock, g, s, fn, qid, peer_addr)

    def _exchange(self, sock: socket.socket, g: _GangState,
                  s: FleetSession, fn: Callable, qid: str,
                  peer_addr: Optional[str]):
        sock.settimeout(600.0)
        try:
            _send_json(sock, {"op": "submit", "sid": s.sid, "qid": qid,
                              "weight": s.weight, "slo": s.slo,
                              "allow_degraded": s.allow_degraded,
                              "peer": peer_addr})
            _send_pickle(sock, fn)
            head = _recv_json(sock)
        except (ProtocolError, OSError) as e:
            self._note_gang_failure(g, f"died before ack: {e}")
            self._count_req(g, "failed")
            raise QueryFailed(s.sid, qid, ProtocolError(
                f"gang {g.gang_id} failed before acknowledging: "
                f"{e}")) from None
        if head.get("ev") != "ack":
            self._count_req(g, "rejected")
            raise _exc_from_wire(head, sid=s.sid, qid=qid)
        try:
            res = _recv_json(sock)
        except (ProtocolError, OSError) as e:
            # mid-stream death: the query was in flight on that gang —
            # typed failure to THIS client, eviction + reroute for
            # everything queued behind it
            self._note_gang_failure(
                g, f"died mid-stream on {qid}: {e}", force_dead=True)
            self._count_req(g, "died_midstream")
            raise QueryFailed(s.sid, qid, ProtocolError(
                f"gang {g.gang_id} died mid-stream (after ack, before "
                f"result)")) from None
        self._broadcast_invalidations(g, res.get("invalidated") or [])
        if not res.get("ok"):
            self._count_req(g, "failed")
            raise _exc_from_wire(res, sid=s.sid, qid=qid)
        try:
            out = _recv_pickle(sock)
        except (ProtocolError, OSError) as e:
            self._note_gang_failure(
                g, f"died sending payload for {qid}: {e}",
                force_dead=True)
            self._count_req(g, "died_midstream")
            raise QueryFailed(s.sid, qid, ProtocolError(
                f"gang {g.gang_id} died sending the result payload"))\
                from None
        self._count_req(g, "ok")
        return out

    def _count_req(self, g: _GangState, outcome: str) -> None:
        with self._mu:
            k = f"req_{outcome}"
            self._c[k] = self._c.get(k, 0) + 1
        try:
            from bodo_tpu.utils import metrics
            metrics.counter("bodo_tpu_fleet_requests_total",
                            "fleet submits by gang and outcome",
                            ("gang", "outcome")).labels(
                gang=g.gang_id, outcome=outcome).inc()
        except Exception:  # noqa: BLE001
            pass

    def _note_gang_failure(self, g: _GangState, why: str,
                           force_dead: bool = False) -> None:
        with self._mu:
            if g.state == "dead":
                return
            dead = force_dead or (g.proc is not None
                                  and g.proc.poll() is not None)
            if dead:
                self._mark_dead_locked(g, why)
            else:
                g.state = "backoff"
                g.reason = why

    def _broadcast_invalidations(self, origin: _GangState,
                                 paths: list) -> None:
        """Fan a gang's mutation-invalidated source paths to every
        OTHER gang (the origin already dropped its stale entry and
        recorded the fresh one — hitting it again would drop the fresh
        entry)."""
        if not paths:
            return
        with self._mu:
            self._c["invalidations_broadcast"] = \
                self._c.get("invalidations_broadcast", 0) + 1
            targets = [g for g in self._gangs.values()
                       if g.gang_id != origin.gang_id
                       and g.state != "dead" and g.serve_addr]
        for g in targets:
            try:
                with _connect(g.serve_addr, timeout=5.0) as sock:
                    _send_json(sock, {"op": "invalidate",
                                      "paths": list(paths)})
                    _recv_json(sock)
            except Exception as e:  # noqa: BLE001
                # an unreachable gang is (or is about to be) evicted;
                # its cache dies with the process, so staleness cannot
                # leak through this miss
                log(2, f"fleet: invalidate to {g.gang_id} failed: "
                       f"{type(e).__name__}: {e}")

    # -- introspection -----------------------------------------------------

    def gang_stats(self, gang_id: str) -> Optional[dict]:
        """The gang's own scheduler/result-cache counters over the
        wire (None when unreachable)."""
        g = self._gangs.get(gang_id)
        if g is None or not g.serve_addr:
            return None
        try:
            with _connect(g.serve_addr, timeout=5.0) as sock:
                _send_json(sock, {"op": "stats"})
                return _recv_json(sock)
        except Exception:  # noqa: BLE001
            return None

    def stats(self) -> dict:
        with self._mu:
            peer_hits = sum(g.peer_hits for g in self._gangs.values())
            gangs = {
                g.gang_id: {
                    "state": g.state, "reason": g.reason,
                    "addr": g.serve_addr,
                    "telemetry": g.telemetry_addr,
                    "pid": g.proc.pid if g.proc is not None else None,
                    "capacity_frac": g.capacity_frac,
                } for g in self._gangs.values()}
            out = {
                "gangs": gangs,
                "ring_members": self._ring.members(),
                "sessions": len(self._sessions),
                "rerouted": self._c.get("rerouted", 0),
                "capacity_rerouted":
                    self._c.get("capacity_rerouted", 0),
                "scrape_failures": self._c.get("scrape_failures", 0),
                "gangs_evicted": self._c.get("gangs_evicted", 0),
                "invalidations_broadcast":
                    self._c.get("invalidations_broadcast", 0),
                "quota_rejections": self._c.get("quota_rejections", 0),
                "peer_hits": peer_hits,
                "requests": {k[4:]: v for k, v in self._c.items()
                             if k.startswith("req_")},
            }
        return out

    # -- optional client listener (BODO_TPU_FLEET_PORT) --------------------

    def listen(self, port: int) -> str:
        """Serve the wire protocol to REMOTE clients: open/submit/
        close/stats against this controller (connect() is the client).
        Returns the bound address."""
        if self._listener is not None:
            return self._listen_addr
        srv = socket.create_server(("127.0.0.1", max(port, 0)))
        srv.listen(32)
        self._listener = srv
        self._listen_addr = f"127.0.0.1:{srv.getsockname()[1]}"
        threading.Thread(target=self._listen_loop, daemon=True,
                         name="fleet-listen").start()
        log(1, f"fleet controller listening on {self._listen_addr}")
        return self._listen_addr

    def _listen_loop(self) -> None:
        while not self._stop_ev.is_set():
            try:
                conn, _ = self._listener.accept()
            except OSError:
                break
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            threading.Thread(target=self._client_conn, args=(conn,),
                             daemon=True).start()

    def _client_conn(self, conn: socket.socket) -> None:
        try:
            with conn:
                req = _recv_json(conn)
                op = req.get("op")
                if op == "ping":
                    _send_json(conn, {"ok": True, "role": "controller",
                                      "gangs": self.n_gangs})
                elif op == "open":
                    self.session(
                        req.get("sid"),
                        priority=float(req.get("weight", 1.0)),
                        slo=req.get("slo", "throughput"),
                        allow_degraded=bool(
                            req.get("allow_degraded", False)))
                    _send_json(conn, {"ok": True})
                elif op == "close":
                    s = self._sessions.get(req.get("sid") or "")
                    if s is not None:
                        s.close()
                    _send_json(conn, {"ok": True})
                elif op == "stats":
                    _send_json(conn, {"ok": True,
                                      "fleet": self.stats()})
                elif op == "submit":
                    fn = _recv_pickle(conn)
                    s = self.session(req.get("sid") or "remote")
                    try:
                        fut = s.submit(fn, key=req.get("key"))
                    except (ServeRejection, QueryFailed) as e:
                        _send_json(conn, _exc_to_wire(e))
                        return
                    _send_json(conn, {"ev": "ack",
                                      "qid": req.get("qid")})
                    try:
                        out = fut.result(timeout=600.0)
                    except (ServeRejection, QueryFailed) as e:
                        _send_json(conn, dict(_exc_to_wire(e),
                                              ev="result"))
                        return
                    except Exception as e:  # noqa: BLE001
                        _send_json(conn, dict(_exc_to_wire(e),
                                              ev="result"))
                        return
                    _send_json(conn, {"ev": "result", "ok": True})
                    _send_pickle(conn, out)
                else:
                    _send_json(conn, {"ok": False,
                                      "etype": "ProtocolError",
                                      "msg": f"unknown op {op!r}"})
        except Exception as e:  # noqa: BLE001 - one bad client only
            log(2, f"fleet listener: connection error: "
                   f"{type(e).__name__}: {e}")


class RemoteFleet:
    """Client of a controller's listener (``fleet.connect(addr)``)."""

    def __init__(self, addr: str):
        self.addr = addr

    def ping(self) -> dict:
        with _connect(self.addr, timeout=5.0) as s:
            _send_json(s, {"op": "ping"})
            return _recv_json(s)

    def open(self, sid: str, *, priority: float = 1.0,
             slo: str = "throughput",
             allow_degraded: bool = False) -> None:
        with _connect(self.addr, timeout=5.0) as s:
            _send_json(s, {"op": "open", "sid": sid, "weight": priority,
                           "slo": slo, "allow_degraded": allow_degraded})
            _recv_json(s)

    def run(self, fn: Callable, *, sid: str = "remote",
            key: Optional[str] = None, timeout: float = 600.0):
        with _connect(self.addr, timeout=timeout) as s:
            s.settimeout(timeout)
            _send_json(s, {"op": "submit", "sid": sid, "key": key})
            _send_pickle(s, fn)
            head = _recv_json(s)
            if head.get("ev") != "ack":
                raise _exc_from_wire(head, sid=sid)
            res = _recv_json(s)
            if not res.get("ok"):
                raise _exc_from_wire(res, sid=sid)
            return _recv_pickle(s)

    def close(self, sid: str) -> None:
        with _connect(self.addr, timeout=5.0) as s:
            _send_json(s, {"op": "close", "sid": sid})
            _recv_json(s)

    def stats(self) -> dict:
        with _connect(self.addr, timeout=5.0) as s:
            _send_json(s, {"op": "stats"})
            return _recv_json(s).get("fleet", {})


# ---------------------------------------------------------------------------
# module singleton + façade
# ---------------------------------------------------------------------------

_controller: Optional[FleetController] = None
_ctl_mu = threading.Lock()


def start(gangs: Optional[int] = None, *,
          gang_env: Optional[Dict[int, Dict[str, str]]] = None,
          timeout: float = 120.0) -> FleetController:
    """Bring a fleet up (idempotent while one is running)."""
    global _controller
    with _ctl_mu:
        if _controller is not None and _controller._started:
            return _controller
        _controller = FleetController(gangs, gang_env=gang_env)
    return _controller.start(timeout=timeout)


def stop() -> None:
    global _controller
    with _ctl_mu:
        ctl, _controller = _controller, None
    if ctl is not None:
        ctl.stop()


def controller() -> Optional[FleetController]:
    return _controller


def controller_stats() -> Optional[dict]:
    """Telemetry hook: the live controller's fleet block (None when no
    controller is running in this process)."""
    ctl = _controller
    if ctl is None or not ctl._started:
        return None
    try:
        return ctl.stats()
    except Exception:  # noqa: BLE001
        return None


def connect(addr: str) -> RemoteFleet:
    """Client handle on a controller's listener address."""
    return RemoteFleet(addr)
