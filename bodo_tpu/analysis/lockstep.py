"""Runtime SPMD lockstep checker (shardcheck layer 3).

Debug mode (`BODO_TPU_LOCKSTEP=1` / `set_config(lockstep=True)`): every
host-level collective dispatch in relational.py's dispatchers
(`_inject_collective`, the PR-2 fault-injection plumbing) is
fingerprinted as `op@file:line` and assigned a per-process sequence
number. Each process appends its (seq, fingerprint) stream to an
append-only side-channel file in the gang's shared temp directory (the
same directory that carries the spawn heartbeats), and cross-checks its
peers' streams before proceeding:

  * a peer that dispatched a DIFFERENT collective at the same sequence
    number -> immediate :class:`LockstepError` naming both ranks and
    both call sites (divergent control flow through a gang-scheduled
    op — the Pathways failure class that otherwise hangs the gang);
  * a peer that has NOT reached this sequence number within
    `config.lockstep_timeout_s` -> :class:`LockstepError` naming the
    lagging rank and its last-seen dispatch (a skipped collective or a
    wedged process), in seconds instead of the 180s gang timeout.

Single-process runs (or runs without a shared directory) still count
and fingerprint dispatches but have no peers to check.

The checker is ~free when disabled: one config attribute read per
dispatch. spawn.py exports BODO_TPU_LOCKSTEP_DIR pointing at each
gang's fresh temp dir so seq numbers never collide with a previous
gang's logs.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Dict, Optional

from bodo_tpu.config import config

_POLL_S = 0.02


class LockstepError(RuntimeError):
    """SPMD lockstep violation: a rank diverged at a host-level
    collective dispatch. Carries the sequence number, this rank, the
    peer rank, and both fingerprints (op@file:line).

    NOTE: messages deliberately avoid the resilience layer's transient/
    degradable marker strings — divergence is a correctness bug that
    must surface, never be retried or degraded away (resilience.py
    additionally excludes this class by name)."""

    def __init__(self, message: str, seq: int = 0, rank: int = 0,
                 peer: Optional[int] = None, site: str = "",
                 peer_site: str = ""):
        self.seq = seq
        self.rank = rank
        self.peer = peer
        self.site = site
        self.peer_site = peer_site
        super().__init__(message)


_lock = threading.Lock()
_checker = None       # Checker | False (disabled after warning) | None
_stats = {"collectives": 0, "wait_s": 0.0, "max_wait_s": 0.0,
          "mismatches": 0, "timeouts": 0, "fused_dispatches": 0,
          "prevalidations": 0, "prevalidation_issues": 0}
# mesh epoch: bumped by the elastic layer on every re-mesh (shrink or
# grow). Sequence numbers and fingerprints are namespaced per epoch —
# survivors of a shrink restart from seq 1 in fresh per-epoch logs, so
# post-recovery dispatches can never be cross-checked against the old
# mesh's stream (which would false-positive as divergence).
_mesh_epoch = 0

# Whole-stage fusion moves member collectives INSIDE one compiled
# program, where per-op pre_collective hooks can no longer fire at
# dispatch (they would fire at trace time only). Instead plan/fusion.py
# registers a per-group manifest at compile time (the member op
# fingerprints + collective count the program subsumes) and the group
# dispatch is sequence-numbered as ONE composite collective via
# pre_fused() — peers must dispatch the same group at the same seq.
_manifests: Dict[str, dict] = {}

# Static per-program collective manifests extracted by the jaxpr
# verifier (analysis/progcheck.py) at registration time: program name
# -> ordered collective primitive names + rank-invariance verdict.
# These are what pre_validate_programs() checks BEFORE a gang's first
# dispatch — a rank-variant program is a guaranteed future divergence,
# so it is reported while the gang is still idle and debuggable.
_program_manifests: Dict[str, dict] = {}


def stats() -> dict:
    with _lock:
        return dict(_stats)


def sequence_head() -> int:
    """Sequence number of this process's last fingerprinted collective
    dispatch (0 before any dispatch / when the checker is off). The
    telemetry sampler records it so a wedged gang's bundle shows how
    far each rank got."""
    c = _checker
    if not c:  # None (unbound) or False (disabled)
        return 0
    with c._mu:
        return c.seq


def _flight_record(err: "LockstepError") -> None:
    """Best-effort flight-recorder bundle at the moment of divergence
    (the raise may be swallowed by user code; the bundle survives).
    Lazy: never pulls the telemetry module in just for this."""
    tl = sys.modules.get("bodo_tpu.runtime.telemetry")
    if tl is None:
        try:
            from bodo_tpu.runtime import telemetry as tl
        except Exception:
            return
    try:
        tl.dump_bundle(f"lockstep_seq{err.seq}_rank{err.rank}",
                       gang_dir=config.lockstep_dir or None)
    except Exception:
        pass


def reset() -> None:
    """Drop the active checker and zero counters (tests; also called by
    set_config when any lockstep knob changes so the next dispatch
    rebinds to the new settings)."""
    global _checker, _mesh_epoch
    with _lock:
        if _checker:
            _checker.close()
        _checker = None
        _mesh_epoch = 0
        for k in _stats:
            _stats[k] = 0 if k != "wait_s" and k != "max_wait_s" else 0.0


def mesh_epoch() -> int:
    return _mesh_epoch


def set_mesh_epoch(epoch: int, rank: Optional[int] = None,
                   nprocs: Optional[int] = None) -> None:
    """Enter a new mesh epoch after an elastic re-mesh: drop the
    current checker so the next dispatch rebinds under the (renumbered)
    rank/nprocs the caller has already published to the environment,
    with a fresh sequence counter, an epoch-suffixed log file, and
    epoch-prefixed fingerprints. Cumulative stats are preserved — a
    re-mesh is recovery, not a test reset."""
    global _checker, _mesh_epoch
    with _lock:
        if _checker:
            _checker.close()
        _checker = None
        _mesh_epoch = int(epoch)
    if rank is not None:
        os.environ["BODO_TPU_PROC_ID"] = str(int(rank))
    if nprocs is not None:
        os.environ["BODO_TPU_NPROCS"] = str(int(nprocs))


def _log_name(epoch: int, rank: int) -> str:
    # epoch 0 keeps the historical name: telemetry's log tail, doctor's
    # skew triage and existing gangs all parse lockstep_<rank>.log
    if epoch:
        return f"lockstep_e{epoch}_{rank}.log"
    return f"lockstep_{rank}.log"


def _rank() -> int:
    v = os.environ.get("BODO_TPU_PROC_ID")
    if v not in (None, ""):
        return int(v)
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_index())
        except Exception:
            return 0
    return 0


def _nprocs() -> int:
    v = os.environ.get("BODO_TPU_NPROCS")
    if v not in (None, ""):
        return int(v)
    jax = sys.modules.get("jax")
    if jax is not None:
        try:
            return int(jax.process_count())
        except Exception:
            return 1
    return 1


_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _call_site() -> str:
    """First stack frame OUTSIDE the bodo_tpu package (the user-level
    call that led to this collective), as basename:lineno — stable
    across ranks regardless of checkout path or cwd."""
    f = sys._getframe(2)
    while f is not None:
        fname = f.f_code.co_filename
        if not fname.startswith(_PKG_DIR):
            return f"{os.path.basename(fname)}:{f.f_lineno}"
        f = f.f_back
    return "<internal>"


def pre_collective(op: str) -> float:
    """Record + cross-check one host-level collective dispatch. Called
    by relational._inject_collective / shuffle_by_key and the streaming
    executors' per-batch steps right before the sharded kernel
    dispatches. Returns the seconds this rank spent waiting for its
    peers to arrive (0.0 without peers or with the checker off) — the
    arrival-skew signal the comm observatory records per dispatch."""
    if not config.lockstep:
        return 0.0
    c = _get_checker()
    if c is None:
        return 0.0
    return c.check(op, _call_site())


def register_fusion_manifest(group_fp: str, ops, collectives: int,
                             in_program=()) -> None:
    """Register the collective manifest of one compiled fusion group:
    the member-op fingerprints the fused program subsumes, how many
    host count syncs a dispatch implies, and — new with the fused-join
    work — the NAMES of the collectives traced INSIDE the compiled body
    (``in_program``, e.g. ``("all_to_all", "psum")``). Those collectives
    never pass through the host dispatch hooks, so the manifest is the
    only record of them: the comm observatory resolves it via
    ``comm.record_in_program`` to attribute bytes/latency, and lockstep
    divergence reports can name what a fused[...] fingerprint subsumes.
    Called at group compile time (once per distinct group signature);
    cheap enough to call unconditionally so manifests exist when
    lockstep is enabled later."""
    with _lock:
        _manifests[group_fp] = {"ops": tuple(ops),
                                "collectives": int(collectives),
                                "in_program": tuple(in_program)}


def fusion_manifest(group_fp: str) -> Optional[dict]:
    with _lock:
        m = _manifests.get(group_fp)
        return dict(m) if m is not None else None


def fusion_manifests() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _manifests.items()}


def register_program_manifest(program: str, *, collectives=(),
                              rank_invariant: bool = True,
                              subsystem: str = "", hbm_bytes: int = 0,
                              violations: int = 0) -> None:
    """Register the STATIC collective manifest of one verified program
    (called by progcheck at trace time): the ordered collective
    primitive names the compiled body dispatches, whether the schedule
    is provably rank-invariant, and the static HBM peak estimate.
    Unconditional and cheap, like register_fusion_manifest — manifests
    must exist before lockstep is ever enabled."""
    with _lock:
        _program_manifests[program] = {
            "collectives": tuple(collectives),
            "rank_invariant": bool(rank_invariant),
            "subsystem": subsystem,
            "hbm_bytes": int(hbm_bytes),
            "violations": int(violations),
        }


def program_manifest(program: str) -> Optional[dict]:
    with _lock:
        m = _program_manifests.get(program)
        return dict(m) if m is not None else None


def program_manifests() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _program_manifests.items()}


def clear_program_manifests() -> None:
    with _lock:
        _program_manifests.clear()


def pre_validate_programs() -> list:
    """Validate the gang's registered program set BEFORE first
    dispatch: (1) no program's static manifest is rank-variant (a
    guaranteed divergence once dispatched); (2) every fused group that
    declared in-program collectives agrees with the verifier's
    extracted manifest for its compiled program. Returns the issue
    strings (also counted in stats); called when the checker binds."""
    issues = []
    with _lock:
        progs = {k: dict(v) for k, v in _program_manifests.items()}
        groups = {k: dict(v) for k, v in _manifests.items()}
    for name, m in sorted(progs.items()):
        if not m["rank_invariant"]:
            issues.append(
                f"program {name!r} has a rank-VARIANT collective "
                f"schedule (collectives under axis_index-derived "
                f"control flow): dispatching it will diverge the gang")
    for fp, g in sorted(groups.items()):
        declared = set(g.get("in_program") or ())
        if not declared:
            continue
        pm = progs.get(f"fused:{fp}")
        if pm is None:
            continue
        got = set(pm["collectives"])
        if not declared <= got:
            issues.append(
                f"fused group {fp!r} declares in-program collectives "
                f"{sorted(declared)} but its verified program traced "
                f"only {sorted(got)}: the manifest lies to the "
                f"runtime checker")
    with _lock:
        _stats["prevalidations"] += 1
        _stats["prevalidation_issues"] += len(issues)
    return issues


def pre_fused(group_fp: str) -> float:
    """Sequence-number one fused-group dispatch as a composite
    collective. The fingerprint is the group fp alone (derived from the
    group's structural signature, so identical across ranks even when a
    rank registered its manifest in a different order); the manifest
    resolves the fp back to member ops for diagnostics/profiling.
    Returns the peer-wait seconds like pre_collective."""
    if not config.lockstep:
        return 0.0
    c = _get_checker()
    if c is None:
        return 0.0
    with _lock:
        _stats["fused_dispatches"] += 1
    return c.check(f"fused[{group_fp}]", _call_site())


def _get_checker() -> Optional["Checker"]:
    global _checker
    c = _checker
    if c is not None:
        return c or None  # False sentinel -> disabled
    with _lock:
        if _checker is not None:
            return _checker or None
        d = config.lockstep_dir
        nprocs = _nprocs()
        if nprocs > 1 and not d:
            sys.stderr.write(
                "bodo_tpu.lockstep: BODO_TPU_LOCKSTEP=1 in a multi-"
                "process run but no BODO_TPU_LOCKSTEP_DIR shared "
                "directory; lockstep checking disabled\n")
            _checker = False
            return None
        _checker = Checker(d or None, _rank(), nprocs,
                           epoch=_mesh_epoch)
        c = _checker
    # pre-validate the program set before this gang's FIRST dispatch
    # (outside _lock: pre_validate_programs takes it)
    for issue in pre_validate_programs():
        sys.stderr.write(f"bodo_tpu.lockstep: pre-validation: "
                         f"{issue}\n")
    return c


class _PeerLog:
    """Incremental reader of one peer's append-only dispatch log."""

    def __init__(self, path: str):
        self.path = path
        self._pos = 0
        self._buf = ""
        self._entries: Dict[int, str] = {}
        self._last = 0

    def _refresh(self) -> None:
        try:
            with open(self.path, "r") as f:
                f.seek(self._pos)
                data = f.read()
                self._pos = f.tell()
        except OSError:
            return
        if not data:
            return
        self._buf += data
        lines = self._buf.split("\n")
        self._buf = lines.pop()  # partial trailing line (if any)
        for line in lines:
            if "\t" not in line:
                continue
            # seq \t fingerprint [\t arrival-ts] — the third field is
            # the wall-clock arrival stamp doctor's skew triage reads;
            # the cross-check compares fingerprints only
            parts = line.split("\t")
            try:
                seq = int(parts[0])
            except ValueError:
                continue
            self._entries[seq] = parts[1]
            self._last = max(self._last, seq)

    def entry(self, seq: int) -> Optional[str]:
        if seq not in self._entries:
            self._refresh()
        return self._entries.get(seq)

    def last(self) -> str:
        self._refresh()
        if not self._last:
            return "nothing (no collective dispatched yet)"
        return f"#{self._last} {self._entries[self._last]}"


class Checker:
    """Per-process lockstep state: own sequence counter + log writer,
    plus incremental readers over every peer's log."""

    def __init__(self, dirpath: Optional[str], rank: int, nprocs: int,
                 epoch: int = 0):
        self.dir = dirpath
        self.rank = int(rank)
        self.nprocs = int(nprocs)
        self.epoch = int(epoch)
        self.seq = 0
        self._mu = threading.Lock()
        self._f = None
        if dirpath:
            try:
                os.makedirs(dirpath, exist_ok=True)
                self._f = open(
                    os.path.join(dirpath,
                                 _log_name(self.epoch, self.rank)),
                    "a")
            except OSError as e:  # unusable dir: record-only mode
                sys.stderr.write(
                    f"bodo_tpu.lockstep: cannot open log in "
                    f"{dirpath!r} ({e}); peer checking disabled\n")
        self._peers: Dict[int, _PeerLog] = {}

    def close(self) -> None:
        if self._f is not None:
            try:
                self._f.close()
            except OSError:
                pass
            self._f = None

    def check(self, op: str, site: str) -> float:
        # the mesh-epoch field in the fingerprint makes a stale peer
        # (still dispatching under the old mesh) an immediate, named
        # mismatch instead of a confusing op-level divergence
        fingerprint = f"e{self.epoch}:{op}@{site}" if self.epoch \
            else f"{op}@{site}"
        with self._mu:
            self.seq += 1
            seq = self.seq
            if self._f is not None:
                # third field: wall-clock arrival stamp — per-seq skew
                # across ranks is reconstructed from these by doctor's
                # comm triage (the rank arriving LAST is the straggler)
                self._f.write(f"{seq}\t{fingerprint}\t{time.time():.6f}\n")
                self._f.flush()
        with _lock:
            _stats["collectives"] += 1
        if self.nprocs <= 1 or self._f is None:
            return 0.0
        t0 = time.monotonic()
        deadline = t0 + float(config.lockstep_timeout_s)
        for peer in range(self.nprocs):
            if peer == self.rank:
                continue
            plog = self._peers.get(peer)
            if plog is None:
                plog = self._peers[peer] = _PeerLog(os.path.join(
                    self.dir, _log_name(self.epoch, peer)))
            while True:
                got = plog.entry(seq)
                if got is not None:
                    if got != fingerprint:
                        with _lock:
                            _stats["mismatches"] += 1
                        err = LockstepError(
                            f"SPMD lockstep divergence at dispatch "
                            f"#{seq}: rank {self.rank} issued "
                            f"{fingerprint} but rank {peer} issued "
                            f"{got} — the ranks took different "
                            f"control-flow paths into a gang-scheduled "
                            f"op (this would have wedged the gang)",
                            seq=seq, rank=self.rank, peer=peer,
                            site=fingerprint, peer_site=got)
                        _flight_record(err)
                        raise err
                    break
                if time.monotonic() >= deadline:
                    with _lock:
                        _stats["timeouts"] += 1
                    err = LockstepError(
                        f"SPMD lockstep divergence at dispatch #{seq} "
                        f"({fingerprint}): rank {peer} did not reach "
                        f"dispatch #{seq} within "
                        f"{float(config.lockstep_timeout_s):.1f}s; its "
                        f"last dispatch was {plog.last()} — rank "
                        f"{peer} skipped the op or is wedged",
                        seq=seq, rank=self.rank, peer=peer,
                        site=fingerprint)
                    _flight_record(err)
                    raise err
                time.sleep(_POLL_S)
        wait = time.monotonic() - t0
        with _lock:
            _stats["wait_s"] += wait
            _stats["max_wait_s"] = max(_stats["max_wait_s"], wait)
        return wait
