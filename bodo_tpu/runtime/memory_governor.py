"""Memory governor: auto-derived device budgets + operator admission.

Default-on analogue of the reference's OperatorComptroller over a
budget-enforcing BufferPool (reference: bodo/libs/_memory.h:632 BufferPool
with a real size limit, bodo/libs/memory_budget.py OperatorComptroller
negotiating per-operator budgets). Where the port previously activated
its spill machinery only when `stream_device_budget_mb` was hand-set
(default 0 = unbounded), the governor

  1. DERIVES a real device budget at mesh init: probe
     `device.memory_stats()` (`bytes_limit` - `bytes_in_use`) when the
     backend reports it, else a platform table (TPU HBM per chip by
     device_kind; CPU = a fraction of host RAM via os.sysconf), minus a
     configurable headroom fraction;
  2. runs ADMISSION CONTROL: state-materializing operators request a
     reservation (`admit()`) before allocating. The governor grants up
     to `mem_op_fraction` of the derived budget; when concurrent grants
     oversubscribe the budget it first QUEUES the request briefly
     (waiting for a release), then grants a reduced slice — which
     forces the operator into its partitioned/spill mode, the same
     paths that used to be opt-in;
  3. provides the OOM-RETRY envelope primitives: `is_oom()` recognizes
     XLA RESOURCE_EXHAUSTED, `handle_oom()` halves the fattest active
     grant and spills the largest parked state via the comptroller —
     the plan executor re-runs the failed stage against the shrunken
     grant (plan/physical.py);
  4. exposes OBSERVABILITY: per-operator granted/peak/spilled bytes for
     the tracing profile and the chrome-trace `memory` section.

The legacy `stream_device_budget_mb` knob still wins when set (tests and
users that pin an explicit budget keep exact behavior); the governor is
what happens when nobody set it.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, List, Optional

from bodo_tpu.config import config
from bodo_tpu.utils.logging import log

# TPU HBM per chip, bytes — used when memory_stats() is unavailable.
# Keyed by device_kind prefix.
_TPU_HBM_BYTES = {
    "TPU v2": 8 << 30,
    "TPU v3": 16 << 30,
    "TPU v4": 32 << 30,
    "TPU v5 lite": 16 << 30,
    "TPU v5e": 16 << 30,
    "TPU v5": 95 << 30,    # v5p
    "TPU v6 lite": 32 << 30,
    "TPU v6e": 32 << 30,
}
_CPU_RAM_FRACTION = 0.25   # treat a quarter of host RAM as "device" memory
_ADMIT_TIMEOUT_S = 5.0     # max time a request queues before a forced grant
_MIN_GRANT = 16 << 20      # grants never shrink below this (forward progress)


def _host_ram_bytes() -> Optional[int]:
    try:
        return os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    except (ValueError, OSError, AttributeError):
        return None


def _probe_device_budget() -> int:
    """Free bytes on one local device (the mesh is symmetric), 0 if
    nothing can be determined."""
    import jax
    try:
        dev = jax.local_devices()[0]
    except Exception:
        return 0
    stats = None
    try:
        stats = dev.memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("bytes_limit"):
        return max(0, int(stats["bytes_limit"])
                   - int(stats.get("bytes_in_use", 0)))
    kind = getattr(dev, "device_kind", "") or ""
    if dev.platform == "tpu":
        for prefix, hbm in sorted(_TPU_HBM_BYTES.items(),
                                  key=lambda kv: -len(kv[0])):
            if kind.startswith(prefix):
                return hbm
        raise ValueError(f"no HBM size known for TPU kind {kind!r}: "
                         f"add it to _TPU_HBM_BYTES")
    # CPU (and unknown platforms): a fraction of host RAM, split across
    # the virtual devices sharing it
    ram = _host_ram_bytes()
    if not ram:
        return 0
    n_local = max(len(jax.local_devices()), 1)
    return int(ram * _CPU_RAM_FRACTION / n_local)


class OperatorGrant:
    """One operator's memory reservation. The operator treats `.budget`
    exactly like the old `stream_device_budget_mb` bytes: accumulate
    device state until it exceeds the grant, then park/spill."""

    def __init__(self, gov: "MemoryGovernor", name: str, budget: int):
        self.gov = gov
        self.name = name
        self.budget = int(budget)
        self.granted = int(budget)
        self.used = 0
        self.peak = 0
        self.spilled_bytes = 0
        self.n_spills = 0
        self._released = False

    def update(self, nbytes: int) -> None:
        """Record current device-resident state size."""
        self.used = int(nbytes)
        if self.used > self.peak:
            self.peak = self.used

    def over_budget(self, nbytes: int) -> bool:
        """True when `nbytes` of state exceeds this grant — the caller
        must park/spill (its governed response). Also tracks peak."""
        self.update(nbytes)
        return bool(self.budget) and nbytes > self.budget

    def record_spill(self, nbytes: int) -> None:
        self.spilled_bytes += int(nbytes)
        self.n_spills += 1
        self.used = 0

    def shrink(self) -> int:
        """Halve the grant (OOM response); returns the new budget."""
        self.budget = max(_MIN_GRANT, self.budget // 2)
        return self.budget

    def release(self) -> None:
        if not self._released:
            self._released = True
            self.gov._release(self)

    # context-manager form for whole-table reservations
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class MemoryGovernor:
    """Arbitrates the derived device budget across operators."""

    def __init__(self):
        self._mu = threading.Condition(threading.Lock())
        self._derived = 0          # post-headroom device budget, bytes
        self._derived_key = None   # (platform, n_local) the probe ran on
        self._probe_override: Optional[int] = None  # test hook
        self._grants: List[OperatorGrant] = []
        self.n_queued = 0
        self.n_oom_retries = 0

    # -- derivation ----------------------------------------------------------

    def set_probe_for_testing(self, nbytes: Optional[int]) -> None:
        """Test hook: pretend the device probe returned `nbytes` (None
        restores the real probe). Forces re-derivation."""
        with self._mu:
            self._probe_override = nbytes
            self._derived_key = None

    def derived_budget(self) -> int:
        """Per-device budget after headroom; re-derives when the local
        device set changes (mesh re-init)."""
        import jax
        try:
            key = (jax.default_backend(), len(jax.local_devices()))
        except Exception:
            key = None
        with self._mu:
            if key != self._derived_key:
                raw = (self._probe_override if self._probe_override
                       is not None else _probe_device_budget())
                headroom = min(max(config.mem_headroom_frac, 0.0), 0.9)
                self._derived = max(0, int(raw * (1.0 - headroom)))
                self._derived_key = key
                if self._derived:
                    log(1, f"memory governor: derived device budget "
                           f"{self._derived >> 20} MiB "
                           f"(probe {raw >> 20} MiB, headroom "
                           f"{headroom:.0%})")
            return self._derived

    def operator_budget(self) -> int:
        """Default per-operator slice of the derived budget."""
        frac = min(max(config.mem_op_fraction, 0.05), 1.0)
        return int(self.derived_budget() * frac)

    # -- admission -----------------------------------------------------------

    def admit(self, name: str, want: int = 0,
              wait: bool = True) -> OperatorGrant:
        """Reserve memory for an operator that materializes state.

        Grants min(want or the default per-operator slice, what's left
        unreserved). When active grants oversubscribe the budget the
        request queues (bounded wait for a release), then receives a
        reduced slice — small grants are how the governor forces an
        operator into partitioned/spill mode.

        ``wait=False`` never queues: an oversubscribed request gets the
        minimal grant immediately. I/O prefetch workers use this — a
        derated lookahead depth is the right pressure response there,
        not a stalled stream.
        """
        # explicit legacy budget wins: exact old behavior
        legacy = int(config.stream_device_budget_mb) << 20
        if legacy:
            g = OperatorGrant(self, name, legacy)
            with self._mu:
                self._grants.append(g)
            return g
        if not config.mem_governor:
            g = OperatorGrant(self, name, 0)  # 0 = unbounded (old default)
            with self._mu:
                self._grants.append(g)
            return g
        total = self.derived_budget()
        if not total:
            g = OperatorGrant(self, name, 0)
            with self._mu:
                self._grants.append(g)
            return g
        ask = min(int(want) or self.operator_budget(),
                  self.operator_budget())
        ask = max(ask, _MIN_GRANT)
        deadline = None
        with self._mu:
            while True:
                free = total - sum(g.budget for g in self._grants)
                if free >= ask or not self._grants:
                    budget = min(ask, max(free, _MIN_GRANT))
                    break
                if free >= _MIN_GRANT:
                    # reduced grant: operator runs, but parks/spills
                    # earlier — the governed response to pressure
                    budget = free
                    break
                if not wait:
                    budget = _MIN_GRANT
                    break
                import time as _time
                if deadline is None:
                    deadline = _time.monotonic() + _ADMIT_TIMEOUT_S
                    self.n_queued += 1
                    log(1, f"memory governor: {name} queued "
                           f"({ask >> 20} MiB asked, {free >> 20} MiB "
                           f"free)")
                remaining = deadline - _time.monotonic()
                if remaining <= 0:
                    budget = _MIN_GRANT  # forced minimal grant: spill mode
                    break
                self._mu.wait(timeout=remaining)
            g = OperatorGrant(self, name, budget)
            self._grants.append(g)
        return g

    def resize_grant(self, grant: OperatorGrant, nbytes: int) -> None:
        """Retarget a persistent grant to its holder's current footprint
        (the result cache holds one long-lived grant sized to its device
        tier). Shrinking wakes queued admits — freed budget is real."""
        with self._mu:
            grant.budget = int(nbytes)
            if grant.budget > grant.granted:
                grant.granted = grant.budget
            grant.update(int(nbytes))
            self._mu.notify_all()

    def _release(self, grant: OperatorGrant) -> None:
        with self._mu:
            if grant in self._grants:
                self._grants.remove(grant)
            self._retired = getattr(self, "_retired", {})
            r = self._retired.setdefault(
                grant.name, {"granted": 0, "peak": 0, "spilled_bytes": 0,
                             "n_spills": 0, "count": 0})
            r["granted"] = max(r["granted"], grant.granted)
            r["peak"] = max(r["peak"], grant.peak)
            r["spilled_bytes"] += grant.spilled_bytes
            r["n_spills"] += grant.n_spills
            r["count"] += 1
            self._mu.notify_all()

    # -- OOM envelope --------------------------------------------------------

    @staticmethod
    def is_oom(exc: BaseException) -> bool:
        # taxonomy lives in the resilience layer so injected
        # RESOURCE_EXHAUSTED faults and real XLA OOMs classify the same
        from bodo_tpu.runtime.resilience import is_resource_exhausted
        return is_resource_exhausted(exc)

    def handle_oom(self, exc: BaseException) -> bool:
        """Shrink the fattest active grant and spill parked state so a
        stage re-run has room. Returns False when there is nothing left
        to shrink (re-raise)."""
        with self._mu:
            active = [g for g in self._grants if g.budget > _MIN_GRANT]
            victim = max(active, key=lambda g: g.budget, default=None)
        progress = False
        if victim is not None:
            old = victim.budget
            new = victim.shrink()
            log(1, f"memory governor: OOM — {victim.name} grant "
                   f"{old >> 20} -> {new >> 20} MiB")
            progress = True
        # shed the result cache's device tier (outside _mu — the cache
        # takes its own lock, then calls back into resize_grant): cached
        # results must never OOM a live query
        try:
            import sys as _sys
            rc = _sys.modules.get("bodo_tpu.runtime.result_cache")
            if rc is not None and rc.shed_for_pressure() > 0:
                progress = True
        except Exception:  # noqa: BLE001 - shedding is best-effort
            pass
        from bodo_tpu.runtime.comptroller import default_comptroller
        comp = default_comptroller()
        before = comp.n_spills
        try:
            comp.ensure_room(comp.limit)  # spill everything spillable
        except Exception:
            pass
        if comp.n_spills > before:
            progress = True
        if progress:
            self.n_oom_retries += 1
        return progress

    # -- observability -------------------------------------------------------

    def stats(self) -> dict:
        derived = self.derived_budget() if config.mem_governor \
            else self._derived
        with self._mu:
            ops: Dict[str, dict] = {}
            for name, r in getattr(self, "_retired", {}).items():
                ops[name] = dict(r)
            for g in self._grants:
                r = ops.setdefault(
                    g.name, {"granted": 0, "peak": 0, "spilled_bytes": 0,
                             "n_spills": 0, "count": 0})
                r["granted"] = max(r["granted"], g.granted)
                r["peak"] = max(r["peak"], g.peak)
                r["spilled_bytes"] += g.spilled_bytes
                r["n_spills"] += g.n_spills
                r["count"] += 1
            return {
                "derived_budget_bytes": derived,
                "enabled": bool(config.mem_governor),
                "n_queued": self.n_queued,
                "n_oom_retries": self.n_oom_retries,
                "operators": ops,
            }


_governor: Optional[MemoryGovernor] = None
_gov_lock = threading.Lock()


def governor() -> MemoryGovernor:
    global _governor
    with _gov_lock:
        if _governor is None:
            _governor = MemoryGovernor()
        return _governor


def reset_governor() -> None:
    """Drop all state (tests)."""
    global _governor
    with _gov_lock:
        _governor = None


_res_depth = threading.local()


def reserve(name: str, nbytes: int):
    """Admission for a whole-table operator (join/sort/groupby in
    relational.py): reserve `nbytes` of the derived budget for the
    duration of the op. Outermost frame only — these operators re-enter
    each other (packed sort calls sort, right join calls left join) and
    nested reservations would double-count. Usable as a context
    manager; a no-op (yields None) when the governor is off, a legacy
    budget is pinned, or we're already inside a reservation."""
    import contextlib

    @contextlib.contextmanager
    def _cm():
        if (not config.mem_governor
                or int(config.stream_device_budget_mb)
                or getattr(_res_depth, "d", 0)):
            yield None
            return
        _res_depth.d = 1
        try:
            g = governor().admit(name, want=int(nbytes))
            g.update(int(nbytes))
            try:
                yield g
            finally:
                g.release()
        finally:
            _res_depth.d = 0
    return _cm()


def preadmission_charge(program: str):
    """Charge a compiled program's STATIC HBM peak estimate (the
    progcheck liveness sweep) against the governor for the duration of
    its dispatch. Pre-admission: when the budget is oversubscribed the
    dispatch queues (or runs under a reduced grant and the stage's
    OOM-retry envelope fires earlier) instead of discovering pressure
    via RESOURCE_EXHAUSTED mid-flight. A no-op context when progcheck
    has no estimate for the program, estimates are tiny, or the
    governor is off — and re-entrancy-safe like reserve()."""
    import contextlib
    import sys

    pc = sys.modules.get("bodo_tpu.analysis.progcheck")
    est = pc.hbm_estimate(program) if pc is not None else None
    if not est or est < _MIN_GRANT:
        return contextlib.nullcontext()
    return reserve(f"progcheck:{program}", int(est))


def table_device_bytes(t) -> int:
    """Device bytes of a Table's columns (data + validity)."""
    n = 0
    for c in t.columns.values():
        n += c.data.size * c.data.dtype.itemsize
        if c.valid is not None:
            n += c.valid.size
    return n
