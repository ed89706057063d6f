"""Elastic gangs: stage-checkpointed shrink-grow recovery.

The all-or-nothing fault story (retry the whole gang, or degrade one
stage to replicated) is intolerable once the gang is a long-lived
shared service: one lost rank kills every tenant's in-flight query and
cold-starts every cache. TPU fleet data treats rank loss and wedged
hosts as routine, and the SPMD answer to per-task lineage
recovery is recovery at the *stage*: checkpoint pipeline state at
stage boundaries, re-mesh onto the survivors, and resume the plan
suffix on the smaller mesh.

Three layers live here:

* :class:`CheckpointStore` — two-phase (register -> commit) per-rank
  stage snapshots. File tier: shards are pickled into the shared gang
  directory (``ckpt_e{epoch}_s{stage}_w{worker}.pkl``), written as
  ``.tmp`` and atomically renamed on commit, so a shard is either
  absent or complete — and the *dead* rank's last committed shard
  survives on shared storage, which is what makes N -> N-1 resharding
  possible without talking to the dead rank. Bounded: shards below the
  gang-wide committed frontier are pruned after every commit and the
  resident bytes are charged to the memory governor through one
  advisory grant. Metadata tier (no directory): in-process stage
  anchors for the serving path, where the semantic result cache
  already owns the bytes (its host-spill tier is the storage; the
  store tracks registration/commit accounting).

* :class:`StageRunner` + :func:`run_elastic` — the elastic gang.
  ``run_elastic(stages, n)`` launches n supervised workers (same
  machinery as ``spawn.run_spmd``); each worker checkpoints its state
  at every stage boundary, then barriers on its peers' checkpoints.
  When the parent detects a rank loss (returncode, stale heartbeat, or
  straggler attribution from the checkpoint frontier / lockstep
  arrival stamps) it writes a new mesh epoch to ``remesh.json``:
  survivors adopt contiguous new ranks, namespace their lockstep
  sequence numbers by the epoch, reshard the last *complete*
  checkpoint from N to N-1 shards, and resume the remaining stages on
  the smaller mesh. The recovery shuffle moves state through the
  shared gang directory, never through collectives — the CPU backend
  has no cross-process collectives, and a recovery path must not
  depend on the thing that just failed. A fresh ``jax.distributed``
  rendezvous on the new mesh is available behind
  ``config.elastic_remesh_distributed`` for real pods. A background
  grow path re-admits a replacement worker at the next stage boundary
  (and the serving layer restores full capacity at the next query
  boundary). If recovery *itself* fails — chaos-testable via the
  ``elastic.remesh`` / ``elastic.resume`` fault points — the gang
  falls back to the existing gang-level retry; it never wedges.

* Serving state — :func:`head` feeds the /healthz ``elastic`` block
  (mesh epoch, evicted workers, ``capacity_frac``) so the fleet
  admission twin can rescale quotas and routing for a shrunk gang;
  :func:`observe_stage` is the plan executor's stage-boundary hook;
  :class:`RankLost` + :func:`is_resumable` are the scheduler's
  resume-once contract (a resumed query re-runs only the plan suffix:
  completed stages come back from the result cache).
"""

from __future__ import annotations

import json
import os
import pickle
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import cloudpickle

from bodo_tpu.config import config
from bodo_tpu.runtime import resilience

_POLL_S = 0.05
_CKPT_RE = re.compile(r"^ckpt_e(\d+)_s(\d+)_w(\d+)\.pkl$")
REMESH_FILE = "remesh.json"
_EVICTED_SENTINEL = "__bodo_tpu_evicted__"


class RankLost(RuntimeError):
    """A gang rank was lost under an in-flight query. The scheduler
    treats this as resumable: the query thunk is re-run once, and the
    plan suffix past the last stage checkpoint is the only part that
    executes again (completed stages hit the result cache)."""

    def __init__(self, message: str = "gang rank lost mid-query",
                 evicted: Sequence[int] = (), epoch: int = 0):
        self.evicted = list(evicted)
        self.epoch = int(epoch)
        super().__init__(message)


class ElasticError(RuntimeError):
    """An elastic gang run failed beyond recovery. ``ranks`` carries
    the per-worker diagnostics (state "ok" / "dead" / "hung" /
    "evicted" / "killed"); ``recovery_failed`` is True when a re-mesh
    had been initiated (the failure happened during or after recovery)
    — the caller falls back to a whole-gang retry in that case."""

    def __init__(self, reason: str, ranks: Dict[int, dict],
                 transient: bool = False, recovery_failed: bool = False):
        self.reason = reason
        self.ranks = ranks
        self.transient = transient
        self.recovery_failed = recovery_failed
        lines = [f"elastic gang failed ({reason}):"]
        for i in sorted(ranks):
            d = ranks[i]
            line = f"  worker {i}: {d.get('state')}"
            if d.get("returncode") is not None:
                line += f" rc={d['returncode']}"
            lines.append(line)
        super().__init__("\n".join(lines))


def is_resumable(exc: BaseException) -> bool:
    """True when the scheduler may transparently re-run the query once
    (rank loss under an elastic gang, not a correctness error)."""
    if isinstance(exc, RankLost):
        return True
    # never resume lockstep divergence: that is a correctness bug
    if type(exc).__name__ == "LockstepError":
        return False
    return bool(getattr(exc, "rank_lost", False))


# --------------------------------------------------------------------
# checkpoint store
# --------------------------------------------------------------------

def default_merge(shards: List[object]) -> object:
    """Deterministic N-shard combine for the recovery shuffle (and for
    comparing a shrunk run against a clean one). Supports the shard
    shapes the executors move: pandas DataFrames (row concat), lists
    (concat), None."""
    if all(s is None for s in shards):
        return None
    try:
        import pandas as pd
    except Exception:  # pragma: no cover
        pd = None
    if pd is not None and all(isinstance(s, pd.DataFrame) for s in shards):
        return pd.concat(list(shards), ignore_index=True)
    if all(isinstance(s, list) for s in shards):
        return [x for s in shards for x in s]
    raise TypeError(
        "elastic.default_merge: unsupported shard type "
        f"{type(shards[0]).__name__}; pass merge=/split= to run_elastic")


def default_split(whole: object, k: int) -> List[object]:
    """Contiguous split of a merged state into k shards (inverse of
    :func:`default_merge` up to shard boundaries)."""
    if whole is None:
        return [None] * k
    try:
        import pandas as pd
    except Exception:  # pragma: no cover
        pd = None
    if pd is not None and isinstance(whole, pd.DataFrame):
        n = len(whole)
        bounds = [round(i * n / k) for i in range(k + 1)]
        return [whole.iloc[bounds[i]:bounds[i + 1]].reset_index(drop=True)
                for i in range(k)]
    if isinstance(whole, list):
        n = len(whole)
        bounds = [round(i * n / k) for i in range(k + 1)]
        return [whole[bounds[i]:bounds[i + 1]] for i in range(k)]
    raise TypeError(
        "elastic.default_split: unsupported state type "
        f"{type(whole).__name__}; pass merge=/split= to run_elastic")


class CheckpointStore:
    """Two-phase stage-checkpoint store (see module docstring).

    ``register`` stages the snapshot (a ``.tmp`` write in the file
    tier); ``commit`` makes it visible atomically. Nothing
    side-effecting belongs between the two — a resumed suffix would
    replay it (the ``checkpoint-non-idempotent`` shardcheck rule
    enforces this package-wide)."""

    def __init__(self, dirpath: Optional[str] = None,
                 budget_bytes: Optional[int] = None):
        self.dir = dirpath or None
        self.budget_bytes = int(budget_bytes if budget_bytes is not None
                                else (256 << 20))
        self._mu = threading.Lock()
        self._bytes = 0
        self._grant = None
        self._stats = {"registered": 0, "committed": 0, "pruned": 0,
                       "over_budget": 0}

    # -- two-phase write ----------------------------------------------
    def register(self, stage: int, epoch: int, worker: int,
                 state: object = None, meta: Optional[dict] = None) -> dict:
        """Stage a checkpoint of `state` entering `stage`. Returns the
        token `commit` consumes. File tier: pickles to ``.tmp`` now, so
        commit is a pure rename."""
        tok = {"stage": int(stage), "epoch": int(epoch),
               "worker": int(worker), "meta": meta or {}, "bytes": 0}
        if self.dir:
            final = os.path.join(
                self.dir, f"ckpt_e{epoch}_s{stage}_w{worker}.pkl")
            tmp = final + ".tmp"
            with open(tmp, "wb") as f:
                pickle.dump(state, f)
            tok["path"], tok["tmp"] = final, tmp
            tok["bytes"] = os.path.getsize(tmp)
        else:
            tok["bytes"] = int((meta or {}).get("bytes", 0))
        with self._mu:
            self._stats["registered"] += 1
        return tok

    def commit(self, token: dict) -> Optional[str]:
        """Atomically publish a registered checkpoint."""
        path = None
        if self.dir and "tmp" in token:
            os.replace(token["tmp"], token["path"])
            path = token["path"]
        with self._mu:
            self._stats["committed"] += 1
            self._bytes += int(token.get("bytes", 0))
            if self._bytes > self.budget_bytes:
                self._stats["over_budget"] += 1
        self._sync_grant()
        return path

    def _sync_grant(self) -> None:
        # one advisory governor grant sized to the resident checkpoint
        # bytes — same pattern as the result cache's persistent grant.
        # Metadata-only stores (no file tier) never hold bytes of their
        # own — the result cache already charged the governor for the
        # anchored stage outputs — so charging again here would
        # double-count every stage boundary of every query.
        try:
            if not self.dir or not config.mem_governor:
                return
            from bodo_tpu.runtime import memory_governor as mg
            gov = mg.governor()
            with self._mu:
                nbytes = self._bytes
                if self._grant is None:
                    self._grant = gov.admit("elastic_ckpt", want=nbytes,
                                            wait=False)
            gov.resize_grant(self._grant, nbytes)
        except Exception:  # noqa: BLE001 - accounting never fails a ckpt
            pass

    # -- reads ---------------------------------------------------------
    def scan(self) -> Dict[tuple, set]:
        """Committed shards on disk: ``{(epoch, worker): {stages}}``."""
        out: Dict[tuple, set] = {}
        if not self.dir:
            return out
        try:
            names = os.listdir(self.dir)
        except OSError:
            return out
        for name in names:
            m = _CKPT_RE.match(name)
            if m:
                e, s, w = int(m.group(1)), int(m.group(2)), int(m.group(3))
                out.setdefault((e, w), set()).add(s)
        return out

    def complete_stage(self, epoch: int,
                       workers: Sequence[int]) -> Optional[int]:
        """Highest stage committed by EVERY worker of `epoch` (the
        resume point a re-mesh reshards from), or None."""
        sc = self.scan()
        common = None
        for w in workers:
            stages = sc.get((int(epoch), int(w)), set())
            common = stages if common is None else (common & stages)
            if not common:
                return None
        return max(common) if common else None

    def load(self, epoch: int, stage: int, worker: int) -> object:
        path = os.path.join(
            self.dir, f"ckpt_e{epoch}_s{stage}_w{worker}.pkl")
        with open(path, "rb") as f:
            return pickle.load(f)

    def reshard(self, epoch: int, stage: int,
                workers_in_rank_order: Sequence[int], new_n: int,
                merge: Callable, split: Callable) -> List[object]:
        """The recovery shuffle: read every old-mesh shard of one
        complete checkpoint (the dead rank's included — its file is on
        shared storage) in old mesh-rank order, combine, and re-split
        contiguously into `new_n` shards."""
        shards = [self.load(epoch, stage, w) for w in workers_in_rank_order]
        return split(merge(shards), new_n)

    # -- retention -----------------------------------------------------
    def prune(self, epoch: int, worker: int, keep_from_stage: int) -> None:
        """Drop this worker's shards of `epoch` below the gang-wide
        committed frontier. Never called with a frontier above the last
        complete stage, so the resume point always survives."""
        if not self.dir:
            return
        sc = self.scan()
        for s in sorted(sc.get((int(epoch), int(worker)), set())):
            if s < int(keep_from_stage):
                self._drop(epoch, s, worker)

    def prune_epochs_below(self, epoch: int, worker: int) -> None:
        """Drop this worker's shards of superseded mesh epochs (called
        once the current epoch has a complete checkpoint)."""
        if not self.dir:
            return
        sc = self.scan()
        for (e, w), stages in sc.items():
            if w == int(worker) and e < int(epoch):
                for s in stages:
                    self._drop(e, s, w)

    def _drop(self, epoch: int, stage: int, worker: int) -> None:
        path = os.path.join(
            self.dir, f"ckpt_e{epoch}_s{stage}_w{worker}.pkl")
        try:
            nbytes = os.path.getsize(path)
            os.remove(path)
        except OSError:
            return
        with self._mu:
            self._stats["pruned"] += 1
            self._bytes = max(0, self._bytes - nbytes)
        self._sync_grant()

    def stats(self) -> dict:
        with self._mu:
            d = dict(self._stats)
            d["bytes"] = self._bytes
            d["budget_bytes"] = self.budget_bytes
        return d


# --------------------------------------------------------------------
# worker side
# --------------------------------------------------------------------

class _Remesh(Exception):
    def __init__(self, doc: dict):
        self.doc = doc


class _Evicted(Exception):
    pass


class _Ctx:
    """Per-stage execution context handed to stage callables."""

    def __init__(self, rank, nprocs, stage, epoch, worker):
        self.rank = rank
        self.nprocs = nprocs
        self.stage = stage
        self.epoch = epoch
        self.worker = worker


def _read_remesh(d: str) -> Optional[dict]:
    try:
        with open(os.path.join(d, REMESH_FILE)) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _write_remesh(d: str, doc: dict) -> None:
    tmp = os.path.join(d, REMESH_FILE + ".tmp")
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, os.path.join(d, REMESH_FILE))


class StageRunner:
    """Worker half of an elastic gang: runs the stage list, snapshots
    state at every stage boundary, barriers on peers' checkpoints, and
    adopts mesh-epoch bumps (shrink, grow, or its own eviction) posted
    by the supervising parent."""

    def __init__(self, stages: Sequence[Callable], init=None, merge=None,
                 split=None, timeout: float = 180.0):
        self.stages = list(stages)
        self.init = init
        self.merge = merge or default_merge
        self.split = split or default_split
        self.dir = os.environ.get("BODO_TPU_ELASTIC_DIR") or \
            config.elastic_dir
        if not self.dir:
            raise RuntimeError("StageRunner needs a shared elastic dir "
                               "(BODO_TPU_ELASTIC_DIR)")
        self.worker = int(os.environ.get(
            "BODO_TPU_ELASTIC_WORKER",
            os.environ.get("BODO_TPU_PROC_ID", "0")))
        self.joiner = os.environ.get("BODO_TPU_ELASTIC_JOINER") == "1"
        self.deadline = time.monotonic() + float(timeout)
        self.store = CheckpointStore(
            self.dir, budget_bytes=config.elastic_ckpt_bytes)
        self.epoch = 0
        self.rank = int(os.environ.get("BODO_TPU_PROC_ID", "0"))
        self.nprocs = int(os.environ.get("BODO_TPU_NPROCS", "1"))
        # worker ids active in the current epoch, in mesh-rank order
        self.workers = list(range(self.nprocs))

    # -- protocol ------------------------------------------------------
    def run(self) -> object:
        try:
            if self.joiner:
                state, s = self._join()
            else:
                state = self.init(self.rank, self.nprocs) \
                    if self.init is not None else None
                s = 0
            while s < len(self.stages):
                try:
                    self._checkpoint(s, state)
                    self._await_stage(s)
                    state = self.stages[s](
                        state, _Ctx(self.rank, self.nprocs, s, self.epoch,
                                    self.worker))
                    s += 1
                except _Remesh as rm:
                    state = self._adopt(rm.doc)
                    s = int(rm.doc["resume_stage"])
            return state
        except _Evicted:
            self._mark_evicted()
            return _EVICTED_SENTINEL

    def _checkpoint(self, s: int, state: object) -> None:
        resilience.maybe_inject("elastic.checkpoint")
        self._poll_remesh()
        tok = self.store.register(stage=s, epoch=self.epoch,
                                  worker=self.worker, state=state)
        self.store.commit(tok)
        # retention: prune below the gang-wide committed frontier (the
        # slowest peer's newest stage), and superseded epochs once the
        # new mesh has a complete checkpoint of its own
        frontier = self.store.complete_stage(self.epoch, self.workers)
        if frontier is not None:
            self.store.prune(self.epoch, self.worker, frontier)
            if self.epoch > 0:
                self.store.prune_epochs_below(self.epoch, self.worker)

    def _await_stage(self, s: int) -> None:
        """Barrier: every current-epoch peer has committed stage `s`
        (or a re-mesh supersedes the wait)."""
        sc = self.store.scan()
        while not all(s in sc.get((self.epoch, w), ())
                      for w in self.workers):
            self._poll_remesh()
            if time.monotonic() > self.deadline:
                raise RuntimeError(
                    f"elastic: stage {s} barrier timed out at epoch "
                    f"{self.epoch} (worker {self.worker})")
            time.sleep(_POLL_S)
            sc = self.store.scan()

    def _poll_remesh(self) -> None:
        doc = _read_remesh(self.dir)
        if doc is None or int(doc.get("epoch", 0)) <= self.epoch:
            return
        if self.worker in [int(w) for w in doc.get("evicted", [])] or \
                str(self.worker) not in doc.get("workers", {}):
            raise _Evicted()
        raise _Remesh(doc)

    def _adopt(self, doc: dict) -> object:
        """Re-mesh: adopt the new epoch's contiguous rank, namespace
        lockstep by the epoch, optionally rendezvous a fresh
        jax.distributed cluster, and reshard the last complete
        checkpoint onto the new mesh."""
        # fault points fire under the OLD identity so `@rank` targeting
        # in BODO_TPU_FAULTS refers to pre-shrink ranks
        resilience.maybe_inject("elastic.remesh")
        self.epoch = int(doc["epoch"])
        ranks = {int(w): int(r) for w, r in doc["workers"].items()}
        self.workers = sorted(ranks, key=lambda w: ranks[w])
        self.rank = ranks[self.worker]
        self.nprocs = len(self.workers)
        os.environ["BODO_TPU_PROC_ID"] = str(self.rank)
        os.environ["BODO_TPU_NPROCS"] = str(self.nprocs)
        try:
            from bodo_tpu.analysis import lockstep
            lockstep.set_mesh_epoch(self.epoch, rank=self.rank,
                                    nprocs=self.nprocs)
        except Exception:  # pragma: no cover
            pass
        if config.elastic_remesh_distributed and doc.get("coord"):
            self._reinit_distributed(doc["coord"])
        resilience.maybe_inject("elastic.resume")
        prev_workers = [int(w) for w in doc["prev_workers"]]
        state = self.store.reshard(
            int(doc["prev_epoch"]), int(doc["resume_stage"]), prev_workers,
            self.nprocs, self.merge, self.split)[self.rank]
        return state

    def _reinit_distributed(self, coord: str) -> None:
        # best-effort: the host-file recovery path above is the one the
        # chaos bar depends on; a real pod re-forms the jax cluster
        # here so post-recovery collectives run on the new mesh
        try:
            import jax
            try:
                jax.distributed.shutdown()
            except Exception:
                pass
            jax.distributed.initialize(
                coordinator_address=coord, num_processes=self.nprocs,
                process_id=self.rank)
        except Exception as e:  # noqa: BLE001
            sys.stderr.write(
                f"bodo_tpu.elastic: jax.distributed re-init skipped "
                f"({e})\n")

    def _join(self):
        """Grow path: a replacement worker waits for the mesh epoch
        that includes it, then enters through the same adoption/reshard
        path as a surviving rank."""
        while True:
            doc = _read_remesh(self.dir)
            if doc is not None and str(self.worker) in \
                    doc.get("workers", {}):
                return self._adopt(doc), int(doc["resume_stage"])
            if time.monotonic() > self.deadline:
                raise RuntimeError(
                    f"elastic: joiner {self.worker} never saw its mesh "
                    f"epoch")
            time.sleep(_POLL_S)

    def _mark_evicted(self) -> None:
        # clean shrink-eviction exit: the marker is how spawn
        # supervision and /healthz distinguish "evicted" from "died"
        path = os.path.join(self.dir, f"evicted_{self.worker}")
        try:
            with open(path, "w") as f:
                json.dump({"worker": self.worker, "epoch": self.epoch,
                           "ts": time.time()}, f)
        except OSError:  # pragma: no cover
            pass


def _elastic_entry(stages, init, merge, split, timeout):
    def entry(_process_index: int) -> object:
        runner = StageRunner(stages, init=init, merge=merge, split=split,
                             timeout=timeout)
        return runner.run()
    return entry


# --------------------------------------------------------------------
# parent side
# --------------------------------------------------------------------

class ElasticRun:
    """Result of :func:`run_elastic`: per-rank final states (final
    mesh-rank order) + a recovery report (epochs, evictions, MTTR)."""

    def __init__(self, results: List[object], report: dict):
        self.results = results
        self.report = report


def run_elastic(stages: Sequence[Callable], n_processes: int = 2, *,
                init: Optional[Callable] = None,
                merge: Optional[Callable] = None,
                split: Optional[Callable] = None,
                timeout: float = 180.0,
                grow: Optional[bool] = None) -> ElasticRun:
    """Run a stage pipeline across an elastic gang of `n_processes`.

    `stages` is a list of picklable ``fn(state, ctx) -> state`` shard
    transforms; `init(rank, nprocs)` builds each rank's initial shard.
    On rank loss the gang shrinks and resumes from the last complete
    stage checkpoint instead of failing (see module docstring); when
    elastic recovery itself cannot proceed, falls back to the
    gang-level retry (``config.elastic_gang_retries``) and raises
    :class:`ElasticError` only after that."""
    retries = max(0, int(config.elastic_gang_retries))
    attempt = 0
    while True:
        try:
            return _run_elastic_gang(stages, n_processes, init, merge,
                                     split, timeout, grow)
        except ElasticError as e:
            if attempt >= retries or \
                    not (e.recovery_failed or e.transient):
                raise
            attempt += 1
            resilience.count_gang_retry()
            sys.stderr.write(
                f"bodo_tpu.elastic: recovery failed ({e.reason}); "
                f"falling back to gang-level retry {attempt}\n")


class _Worker:
    def __init__(self, wid, proc, out, err, hb):
        self.wid = wid
        self.proc = proc
        self.out = out
        self.err = err
        self.hb = hb
        self.evicted = False


def _run_elastic_gang(stages, n_processes, init, merge, split, timeout,
                      grow) -> ElasticRun:
    from bodo_tpu import spawn

    hb_timeout = resilience._cfg("spawn_hb_timeout_s",
                                 "BODO_TPU_SPAWN_HB_TIMEOUT", 15.0, float)
    grow = config.elastic_grow if grow is None else bool(grow)
    max_shrinks = max(0, int(config.elastic_max_shrinks))
    min_ranks = max(1, int(config.elastic_min_ranks))
    straggler_s = float(config.elastic_straggler_s)
    resil_path = os.path.join(
        os.path.dirname(os.path.abspath(spawn.__file__)),
        "runtime", "resilience.py")
    pkg_root = os.path.dirname(os.path.dirname(
        os.path.abspath(spawn.__file__)))
    entry = _elastic_entry(list(stages), init, merge, split, timeout)

    with tempfile.TemporaryDirectory(prefix="bodo_tpu_elastic_") as d:
        payload = os.path.join(d, "fn.pkl")
        with open(payload, "wb") as f:
            cloudpickle.dump(entry, f)
        worker_py = os.path.join(d, "worker.py")
        with open(worker_py, "w") as f:
            f.write(spawn._WORKER_CODE)
        coord = f"127.0.0.1:{spawn._free_port()}"
        store = CheckpointStore(d)
        workers: Dict[int, _Worker] = {}
        handles: List[object] = []

        def launch(wid: int, env_extra: Dict[str, str],
                   nprocs_env: int, proc_id: int) -> None:
            out = os.path.join(d, f"out_{wid}.pkl")
            err = os.path.join(d, f"err_{wid}.log")
            hb = os.path.join(d, f"hb_{wid}")
            env = spawn._worker_env(d, proc_id, nprocs_env, coord,
                                    resil_path, pkg_root, hb)
            env.update({"BODO_TPU_ELASTIC_DIR": d,
                        "BODO_TPU_ELASTIC_WORKER": str(wid)})
            if not config.elastic_remesh_distributed:
                # host-file recovery: each worker runs local jax; a
                # shared coordination service would fatally terminate
                # survivors ~100s after the very rank loss we recover
                # from (see spawn._WORKER_CODE)
                env["BODO_TPU_NO_JAX_DIST"] = "1"
            env.update(env_extra)
            ef = open(err, "wb")
            of = open(os.path.join(d, f"stdout_{wid}.log"), "wb")
            handles.extend([ef, of])
            proc = subprocess.Popen(
                [sys.executable, worker_py, payload, out],
                env=env, stdout=of, stderr=ef)
            workers[wid] = _Worker(wid, proc, out, err, hb)

        rank_of = {w: w for w in range(n_processes)}
        epoch = 0
        shrinks = grows = 0
        detect_ts: Optional[float] = None
        evicted_info: Dict[int, str] = {}
        recovery_initiated = False
        frontier_seen: Dict[int, tuple] = {}
        start = time.monotonic()
        deadline = start + float(timeout)

        def active() -> List[int]:
            return [w for w in sorted(workers) if not workers[w].evicted]

        def diag(reason: Optional[str], failing: set) -> Dict[int, dict]:
            out: Dict[int, dict] = {}
            for wid in sorted(workers):
                w = workers[wid]
                rc = w.proc.poll()
                if w.evicted or os.path.exists(
                        os.path.join(d, f"evicted_{wid}")):
                    state = "evicted"
                elif wid in failing:
                    state = ("hung" if reason == "hung worker" else
                             "timeout" if reason == "gang timeout" else
                             "dead")
                elif rc == 0:
                    state = "ok"
                elif rc is None:
                    state = "running"
                else:
                    state = "killed"
                e = {"state": state, "returncode": rc}
                if state == "evicted" and wid in evicted_info:
                    e["evicted_reason"] = evicted_info[wid]
                if state in ("dead", "hung", "timeout", "killed"):
                    try:
                        with open(w.err, "rb") as f:
                            e["stderr"] = f.read()[-spawn._STDERR_TAIL:] \
                                .decode("utf-8", "replace").strip()
                    except OSError:
                        e["stderr"] = ""
                out[wid] = e
            return out

        def fail(reason: str, failing: set) -> None:
            ranks = diag(reason, failing)
            transient = bool(failing) and all(
                resilience.classify_transient_text(
                    ranks[w].get("stderr", "")) for w in failing)
            spawn._merge_gang_trace(d)
            spawn._dump_flight_bundle("elastic_" + reason.replace(" ", "_"),
                                      ranks, d)
            raise ElasticError(reason, ranks, transient=transient,
                               recovery_failed=recovery_initiated)

        def evict(victims: List[int], reason: str) -> None:
            nonlocal epoch, shrinks, detect_ts, recovery_initiated
            survivors = [w for w in active() if w not in victims]
            # the resume point must be complete across the OLD mesh —
            # the victims' last committed shards included
            resume = store.complete_stage(epoch, active())
            if resume is None or len(survivors) < min_ranks or \
                    shrinks >= max_shrinks:
                fail("worker death" if reason == "dead" else "hung worker",
                     set(victims))
            if detect_ts is None:
                detect_ts = time.monotonic()
            recovery_initiated = True
            prev_workers = sorted(active(), key=lambda w: rank_of[w])
            prev_epoch = epoch
            epoch += 1
            shrinks += 1
            for i, w in enumerate(sorted(survivors,
                                         key=lambda w: rank_of[w])):
                rank_of[w] = i
            doc = {"epoch": epoch, "prev_epoch": prev_epoch,
                   "prev_workers": prev_workers,
                   "workers": {str(w): rank_of[w] for w in survivors},
                   "evicted": sorted(set(evicted_info) | set(victims)),
                   "resume_stage": resume, "reason": reason,
                   "coord": f"127.0.0.1:{spawn._free_port()}",
                   "ts": time.time()}
            _write_remesh(d, doc)
            for v in victims:
                evicted_info[v] = reason
                workers[v].evicted = True
                _teardown_victim(d, workers[v])
            _note_shrink(sorted(victims), len(prev_workers),
                         len(survivors))
            spawn._dump_flight_bundle(f"elastic_shrink_e{epoch}",
                                      diag(None, set()), d)

        try:
            for i in range(n_processes):
                launch(i, {}, n_processes, i)
            spawn._register_gang_health(
                d, [workers[w].proc for w in sorted(workers)],
                [workers[w].hb for w in sorted(workers)], start,
                evicted=lambda: {w for w in workers
                                 if workers[w].evicted or os.path.exists(
                                     os.path.join(d, f"evicted_{w}"))})
            while True:
                now = time.monotonic()
                if now >= deadline:
                    fail("gang timeout",
                         {w for w in active()
                          if workers[w].proc.poll() is None})
                order = active()
                reason, failing_idx = spawn._supervise(
                    [workers[w].proc for w in order],
                    [workers[w].hb for w in order],
                    now, min(1.0, deadline - now), hb_timeout)
                failing = {order[i] for i in failing_idx}
                if reason is None:
                    results = _collect(d, workers, order, rank_of)
                    spawn._merge_gang_trace(d)
                    wall = time.monotonic() - start
                    mttr = (time.monotonic() - detect_ts) \
                        if detect_ts is not None else None
                    if mttr is not None:
                        note_mttr(mttr)
                    report = {"epochs": epoch, "shrinks": shrinks,
                              "grows": grows,
                              "evicted": dict(evicted_info),
                              "final_nprocs": len(order),
                              "mttr_s": mttr, "wall_s": wall,
                              "ckpt": store.stats()}
                    return ElasticRun(results, report)
                if reason == "worker death":
                    evict(sorted(failing), "dead")
                elif reason == "hung worker":
                    evict(sorted(failing), "hung")
                else:  # slice expired: housekeeping
                    straggler = _find_straggler(d, store, epoch, active(),
                                                rank_of, frontier_seen,
                                                straggler_s)
                    if straggler is not None and \
                            len(active()) > min_ranks and \
                            shrinks < max_shrinks:
                        evict([straggler], "straggler")
                    elif grow and shrinks > grows and \
                            len(active()) < n_processes:
                        wid = _try_grow(d, store, workers, rank_of,
                                        evicted_info, epoch, stages,
                                        launch)
                        if wid is not None:
                            epoch += 1
                            grows += 1
                            _note_grow()
        finally:
            spawn._clear_gang_health()
            for w in workers.values():
                if w.proc.poll() is None:
                    w.proc.kill()
            for w in workers.values():
                try:
                    w.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:  # pragma: no cover
                    pass
            for h in handles:
                h.close()


def _teardown_victim(d: str, w: _Worker) -> None:
    """Give an evicted-but-alive rank (straggler eviction) a grace
    window to exit clean before force-killing it; either way its
    diagnostic state is "evicted", not "dead"."""
    grace = time.monotonic() + float(config.elastic_evict_grace_s)
    while w.proc.poll() is None and time.monotonic() < grace:
        if os.path.exists(os.path.join(d, f"evicted_{w.wid}")):
            break
        time.sleep(_POLL_S)
    if w.proc.poll() is None:
        try:
            w.proc.send_signal(signal.SIGUSR1)
        except OSError:  # pragma: no cover
            pass
        dump_grace = time.monotonic() + 2.0
        while w.proc.poll() is None and time.monotonic() < dump_grace:
            time.sleep(_POLL_S)
        if w.proc.poll() is None:
            w.proc.kill()
    # the parent records the eviction even when the worker could not
    # (wedged rank): the marker is what /healthz and doctor read
    path = os.path.join(d, f"evicted_{w.wid}")
    if not os.path.exists(path):
        try:
            with open(path, "w") as f:
                json.dump({"worker": w.wid, "by": "parent",
                           "ts": time.time()}, f)
        except OSError:  # pragma: no cover
            pass


def _collect(d, workers, order, rank_of) -> List[object]:
    outs = []
    for wid in sorted(order, key=lambda w: rank_of[w]):
        path = workers[wid].out
        if not os.path.exists(path):
            raise ElasticError("missing result",
                               {wid: {"state": "dead", "returncode":
                                      workers[wid].proc.poll()}})
        with open(path, "rb") as f:
            outs.append(pickle.load(f))
    # sentinel test must be type-guarded: `!=` on a DataFrame shard is
    # elementwise, not a scalar
    return [o for o in outs
            if not (isinstance(o, str) and o == _EVICTED_SENTINEL)]


def _find_straggler(d, store, epoch, active, rank_of, frontier_seen,
                    straggler_s) -> Optional[int]:
    """Straggler-eviction policy: a rank the gang is *waiting for* —
    its checkpoint frontier is behind its peers' and has not advanced
    within `straggler_s` — is evicted like a dead one. Attribution
    prefers the comm observatory's lockstep arrival stamps when
    lockstep logs exist; the checkpoint frontier is the fallback
    signal. Disabled when `straggler_s` is 0."""
    if straggler_s <= 0 or len(active) < 2:
        return None
    sc = store.scan()
    tops = {w: max(sc.get((epoch, w), {-1})) for w in active}
    lo, hi = min(tops.values()), max(tops.values())
    if hi <= lo:  # nobody is behind
        frontier_seen.clear()
        return None
    laggards = [w for w in active if tops[w] == lo]
    now = time.monotonic()
    for w in active:
        prev = frontier_seen.get(w)
        if prev is None or prev[0] != tops[w]:
            frontier_seen[w] = (tops[w], now)
    stuck = [w for w in laggards
             if now - frontier_seen[w][1] >= straggler_s]
    if not stuck:
        return None
    try:
        from bodo_tpu.parallel import comm
        rk = comm.straggler_from_logs(d, len(active), epoch=epoch)
        if rk is not None:
            cand = [w for w in active if rank_of[w] == rk]
            if cand and cand[0] in stuck:
                return cand[0]
    except Exception:  # noqa: BLE001 - attribution is advisory
        pass
    return stuck[0]


def _try_grow(d, store, workers, rank_of, evicted_info, epoch, stages,
              launch) -> Optional[int]:
    """Grow path: once the shrunk mesh has a complete checkpoint of its
    own and stages remain, admit a replacement worker at the next
    stage boundary via one more epoch bump (reshard N-1 -> N)."""
    active = [w for w in sorted(workers) if not workers[w].evicted]
    resume = store.complete_stage(epoch, active)
    if resume is None or resume >= len(stages):
        return None
    wid = max(workers) + 1
    prev_workers = sorted(active, key=lambda w: rank_of[w])
    new_workers = prev_workers + [wid]
    for i, w in enumerate(new_workers):
        rank_of[w] = i
    doc = {"epoch": epoch + 1, "prev_epoch": epoch,
           "prev_workers": prev_workers,
           "workers": {str(w): rank_of[w] for w in new_workers},
           "evicted": sorted(evicted_info),
           "resume_stage": resume, "reason": "grow",
           "coord": f"127.0.0.1:{_free_port_late()}",
           "ts": time.time()}
    _write_remesh(d, doc)
    # the joiner forms its own single-process jax cluster on the FRESH
    # coordinator port from the remesh doc — never the original gang's,
    # which rank 0's still-running coordinator owns (the shared mesh
    # state rides host files); it adopts the posted epoch on entry
    launch(wid, {"BODO_TPU_ELASTIC_JOINER": "1",
                 "BODO_TPU_COORD": doc["coord"]}, 1, 0)
    return wid


def _free_port_late() -> int:
    from bodo_tpu import spawn
    return spawn._free_port()


# --------------------------------------------------------------------
# serving state (/healthz, scheduler, fleet)
# --------------------------------------------------------------------

_mu = threading.Lock()
_STATE = {"epoch": 0, "nprocs_full": None, "nprocs": None,
          "evicted": [], "capacity_frac": 1.0, "grow_pending": False,
          "shrinks": 0, "grows": 0, "resumes": 0, "last_mttr_s": None}
_QSTORE = CheckpointStore(None)
_qseq = 0


def _note_shrink(evicted: List[int], before: int, after: int) -> None:
    with _mu:
        _STATE["epoch"] += 1
        _STATE["shrinks"] += 1
        _STATE["evicted"] = sorted(set(_STATE["evicted"]) | set(evicted))
        if _STATE["nprocs_full"] is None:
            _STATE["nprocs_full"] = before
        _STATE["nprocs"] = after
        _STATE["capacity_frac"] = round(
            after / max(1, _STATE["nprocs_full"]), 4)
        _STATE["grow_pending"] = True


def _note_grow() -> None:
    with _mu:
        _STATE["epoch"] += 1
        _STATE["grows"] += 1
        full = _STATE["nprocs_full"] or 1
        _STATE["nprocs"] = min(full, (_STATE["nprocs"] or full) + 1)
        _STATE["capacity_frac"] = round(_STATE["nprocs"] / full, 4)
        if _STATE["nprocs"] >= full:
            _STATE["evicted"] = []
            _STATE["grow_pending"] = False


def note_resume() -> None:
    with _mu:
        _STATE["resumes"] += 1


def note_mttr(seconds: float) -> None:
    with _mu:
        _STATE["last_mttr_s"] = round(float(seconds), 4)


def note_query_boundary() -> bool:
    """Scheduler hook, called between queries: the background grow path
    re-admits replacement capacity at the next query boundary (the
    next gang launch runs at full width again). Returns True when
    capacity was restored."""
    if not config.elastic or not config.elastic_grow:
        return False
    with _mu:
        if not _STATE["grow_pending"]:
            return False
        _STATE["grows"] += 1
        _STATE["nprocs"] = _STATE["nprocs_full"]
        _STATE["capacity_frac"] = 1.0
        _STATE["evicted"] = []
        _STATE["grow_pending"] = False
    return True


def observe_stage(node, seconds: float = 0.0) -> None:
    """Plan-executor hook at every AQE stage boundary (physical._exec,
    right after adaptive.observe_stage): register the materialized
    stage output as an in-process checkpoint anchor. The semantic
    result cache owns the bytes (its host-spill tier is the durable
    copy a resumed suffix reads back); the store tracks the two-phase
    registration and byte accounting for /healthz."""
    global _qseq
    if not config.elastic:
        return
    try:
        nbytes = 0
        t = getattr(node, "_cached", None)
        if t is not None:
            from bodo_tpu.runtime.memory_governor import table_device_bytes
            nbytes = table_device_bytes(t)
        with _mu:
            _qseq += 1
            seq = _qseq
        tok = _QSTORE.register(stage=seq, epoch=_STATE["epoch"], worker=0,
                               meta={"bytes": nbytes,
                                     "wall_s": float(seconds)})
        _QSTORE.commit(tok)
    except Exception:  # noqa: BLE001 - accounting never fails a query
        pass


def head() -> dict:
    """Elastic block for /healthz: mesh epoch, evicted workers, the
    reduced capacity the fleet admission twin rescales by, and the
    checkpoint-store counters."""
    with _mu:
        out = dict(_STATE)
        out["evicted"] = list(out["evicted"])
    out["checkpoints"] = _QSTORE.stats()
    return out


def reset() -> None:
    global _QSTORE, _qseq
    with _mu:
        _STATE.update({"epoch": 0, "nprocs_full": None, "nprocs": None,
                       "evicted": [], "capacity_frac": 1.0,
                       "grow_pending": False, "shrinks": 0, "grows": 0,
                       "resumes": 0, "last_mttr_s": None})
        _qseq = 0
        _QSTORE = CheckpointStore(None)
