"""progcheck (shardcheck layer 3) — jaxpr-level SPMD program verifier.

shardcheck's AST lint (analysis/lint.py) sees source; the lockstep
checker (analysis/lockstep.py) sees dispatches after they happen. This
module sits between them: every program the engine registers with the
program registry (bounded_jit, the fusion/decode program caches,
cached_builder products, the relational dispatchers) is traced to its
jaxpr and verified BEFORE it can wedge or corrupt a gang. Three passes
per program:

  static lockstep
      Extract the ordered collective primitives (all_to_all,
      all_gather, psum, ppermute, ...) with axis/shape/dtype facets
      into a per-program collective manifest; verify the schedule is
      rank-invariant — no collective under value-dependent control
      flow (cond/while) whose predicate derives from `axis_index`.
      Manifests are registered with analysis/lockstep so a gang's
      program set can be pre-validated before first dispatch, and
      cross-checked against the in-program collectives fused groups
      declare (`register_fusion_manifest(..., in_program=(...))`).

  donation / aliasing audit
      For every `donate_argnums` program, prove no donated input
      escapes to an output through an alias-only chain (reshape /
      transpose / squeeze / expand_dims) — a donated buffer aliased
      into a cached output is read after XLA reuses its pages — and
      that every donated input is actually consumed. Program families
      that cache their outputs across dispatches (the join-build LUT)
      register with `forbid_donation=True`, turning the "never donate
      the build side" comment into a checked contract.

  static HBM peak estimation
      A liveness sweep over the jaxpr computing peak live bytes
      (inputs + outputs + maximal concurrent intermediates,
      donation-aware: a donated input dies at its last use). The
      estimate is recorded per program in the observatory, charged by
      the memory governor before dispatch (preadmission_charge) and
      read by the serve admission controller to shed before trace.

Violations are typed `ProgramInvariantError`s naming the program and
the offending eqn path. `BODO_TPU_PROGCHECK` (default on) gates the
checks; `BODO_TPU_PROGCHECK_ENFORCE` turns warn-and-record into
raise-at-registration.

Module level stays stdlib-only (jax is imported inside functions) so
metrics/tracing/doctor can read `stats()` through the lazy-module rule
without dragging in a backend.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from bodo_tpu.config import config

__all__ = [
    "ProgramInvariantError", "check_jit", "check_jaxpr", "wrap_program",
    "manifests", "manifest_for", "reports", "violations", "stats",
    "hbm_estimate", "max_hbm_estimate", "reset", "main",
]


class ProgramInvariantError(RuntimeError):
    """A statically-provable SPMD invariant violation in a registered
    program: rule, program name and the offending eqn path ride on the
    exception (doctor and the CLI render them)."""

    def __init__(self, program: str, rule: str, message: str,
                 eqn_path: str = ""):
        self.program = program
        self.rule = rule
        self.eqn_path = eqn_path
        where = f" (at {eqn_path})" if eqn_path else ""
        super().__init__(
            f"progcheck[{rule}] program {program!r}: {message}{where}")


# collective primitives whose dispatch order IS the gang's lockstep
# schedule (jax.lax level — what jaxprs contain after tracing)
_COLLECTIVE_PRIMS = {
    "all_to_all", "all_gather", "psum", "pmax", "pmin", "ppermute",
    "pshuffle", "psum_scatter", "reduce_scatter", "all_reduce",
    "pbroadcast",
}

# primitives that alias (or may alias) their operand's buffer rather
# than copying — a donated input reaching an output through ONLY these
# means the "output" is the donated buffer itself
_ALIAS_PRIMS = {"reshape", "transpose", "squeeze", "expand_dims",
                "rev", "copy"}

# control-flow primitives whose predicate selects which eqns run
_BRANCHY_PRIMS = {"cond", "while"}

_mu = threading.RLock()
_reports: Dict[str, dict] = {}          # program -> report
_checked_handles: set = set()           # observatory handles verified
_warned: set = set()                    # programs already warn-logged
_stats = {
    "programs": 0,          # programs verified
    "violations": 0,        # violations recorded (warn or enforce)
    "skipped": 0,           # trace failures / disabled at call time
    "check_s": 0.0,         # total verification wall
    "max_check_s": 0.0,     # slowest single verification
    "manifests": 0,         # collective manifests registered
}


def enabled() -> bool:
    return bool(config.progcheck)


def enforcing() -> bool:
    return bool(config.progcheck_enforce)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _src(eqn) -> str:
    """`file.py:line` of the eqn's user frame ("" when unavailable)."""
    try:
        from jax._src import source_info_util
        fr = source_info_util.user_frame(eqn.source_info)
        if fr is not None:
            import os
            return f"{os.path.basename(fr.file_name)}:{fr.start_line}"
    except Exception:  # noqa: BLE001 - source info is best-effort
        pass
    return ""


def _sub_jaxprs(params: dict) -> List[Tuple[str, Any]]:
    """(param_key, Jaxpr) for every sub-jaxpr hiding in an
    eqn's params (jaxpr / closed jaxpr / tuples of either)."""
    from jax.extend import core as jcore
    out: List[Tuple[str, Any]] = []

    def _coerce(v):
        if isinstance(v, jcore.ClosedJaxpr):
            return v.jaxpr
        if isinstance(v, jcore.Jaxpr):
            return v
        return None

    for k, v in params.items():
        j = _coerce(v)
        if j is not None:
            out.append((k, j))
        elif isinstance(v, (tuple, list)):
            for i, item in enumerate(v):
                j = _coerce(item)
                if j is not None:
                    out.append((f"{k}[{i}]", j))
    return out


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except Exception:  # noqa: BLE001 - abstract tokens etc.
        return 0


def _is_literal(v) -> bool:
    from jax.extend import core as jcore
    return isinstance(v, jcore.Literal)


def _collective_facets(eqn, path: str) -> dict:
    p = eqn.params
    axis = p.get("axis_name", p.get("axes", p.get("axis_index_groups")))
    shape = dtype = None
    for ov in eqn.outvars:
        aval = getattr(ov, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            shape, dtype = tuple(aval.shape), str(aval.dtype)
            break
    return {"prim": eqn.primitive.name, "axis": str(axis),
            "shape": shape, "dtype": dtype, "eqn": path,
            "line": _src(eqn)}


def _scan_jaxpr(jaxpr, tainted: set, ambient_divergent: bool,
                path: str, collectives: List[dict],
                violations: List[dict], program: str) -> None:
    """One pass: collect collectives in dispatch order, propagate
    axis-index taint, and flag any collective reachable only through
    control flow whose predicate carries that taint."""
    for i, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        epath = f"{path}eqns[{i}]:{name}"
        in_tainted = any((not _is_literal(v)) and v in tainted
                         for v in eqn.invars)
        if name in _COLLECTIVE_PRIMS:
            collectives.append(_collective_facets(eqn, epath))
            if ambient_divergent:
                violations.append({
                    "rule": "rank-divergent-collective",
                    "program": program, "eqn": epath,
                    "line": _src(eqn),
                    "message": f"collective {name!r} under control flow "
                               f"whose predicate derives from "
                               f"axis_index: ranks where the predicate "
                               f"differs skip the collective and the "
                               f"gang hangs"})
        pred_tainted = False
        if name == "cond":
            pv = eqn.invars[0]
            pred_tainted = (not _is_literal(pv)) and pv in tainted
        elif name == "while":
            # the carry feeds cond_jaxpr: tainted carry => tainted
            # trip count (conservative)
            pred_tainted = in_tainted
        child_divergent = ambient_divergent or \
            (name in _BRANCHY_PRIMS and pred_tainted)
        subs = _sub_jaxprs(eqn.params)
        if subs:
            ops = eqn.invars[1:] if name == "cond" else eqn.invars
            for key, sub in subs:
                sub_tainted: set = set()
                if len(sub.invars) == len(ops):
                    for sv, ov in zip(sub.invars, ops):
                        if (not _is_literal(ov)) and ov in tainted:
                            sub_tainted.add(sv)
                elif in_tainted:
                    # unknown calling convention: taint everything
                    sub_tainted.update(sub.invars)
                _scan_jaxpr(sub, sub_tainted, child_divergent,
                            f"{epath}/{key}/", collectives, violations,
                            program)
        if name == "axis_index" or in_tainted:
            tainted.update(eqn.outvars)


def _peak_live_bytes(jaxpr, donated: set) -> int:
    """Delta-sweep liveness: peak concurrent bytes across eqn steps.
    Non-donated inputs and constvars live for the whole program;
    donated inputs die at their last contributing use; every value
    feeding a program output lives to the end. Sub-jaxprs contribute
    max(0, sub_peak - sub_io) as transient extra at their eqn."""
    n = len(jaxpr.eqns)
    last_use: Dict[Any, int] = {}
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not _is_literal(v):
                last_use[v] = i
    birth: Dict[Any, int] = {}
    death: Dict[Any, int] = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        birth[v] = 0
        death[v] = last_use.get(v, 0) if v in donated else n
    for i, eqn in enumerate(jaxpr.eqns):
        for o in eqn.outvars:
            birth[o] = i
            death[o] = last_use.get(o, i)
    for v in jaxpr.outvars:
        if not _is_literal(v):
            death[v] = n
    deltas = [0] * (n + 2)
    for v, b in birth.items():
        nb = _aval_bytes(getattr(v, "aval", None))
        if nb <= 0:
            continue
        deltas[b] += nb
        deltas[death.get(v, b) + 1] -= nb
    extras = [0] * (n + 1)
    for i, eqn in enumerate(jaxpr.eqns):
        for _, sub in _sub_jaxprs(eqn.params):
            sub_peak = _peak_live_bytes(sub, set())
            io = sum(_aval_bytes(getattr(v, "aval", None))
                     for v in list(sub.invars) + list(sub.outvars)
                     if not _is_literal(v))
            extras[i] += max(0, sub_peak - io)
    peak = running = 0
    for i in range(n + 1):
        running += deltas[i]
        peak = max(peak, running + (extras[i] if i < n else 0))
    return peak


def _audit_donation(jaxpr, donated: set, program: str,
                    forbid_donation: bool,
                    violations: List[dict]) -> None:
    if not donated:
        return
    if forbid_donation:
        idxs = sorted(i for i, v in enumerate(jaxpr.invars)
                      if v in donated)
        violations.append({
            "rule": "forbidden-donation", "program": program,
            "eqn": f"invars{idxs}", "line": "",
            "message": f"program family registers with "
                       f"forbid_donation=True (outputs are cached "
                       f"across dispatches) but donates inputs "
                       f"{idxs}: a later dispatch would read pages "
                       f"XLA already reused"})
    used: set = set()
    for eqn in jaxpr.eqns:
        used.update(v for v in eqn.invars if not _is_literal(v))
    used.update(v for v in jaxpr.outvars if not _is_literal(v))
    # alias-only reachability from each donated input to an output
    alias_of: Dict[Any, Any] = {v: v for v in donated}
    for i, eqn in enumerate(jaxpr.eqns):
        if eqn.primitive.name in _ALIAS_PRIMS and eqn.invars and \
                not _is_literal(eqn.invars[0]) and \
                eqn.invars[0] in alias_of:
            for o in eqn.outvars:
                alias_of[o] = alias_of[eqn.invars[0]]
    out_set = {v for v in jaxpr.outvars if not _is_literal(v)}
    for i, v in enumerate(jaxpr.invars):
        if v not in donated:
            continue
        if v not in used:
            violations.append({
                "rule": "unused-donation", "program": program,
                "eqn": f"invars[{i}]", "line": "",
                "message": f"donated input {i} is never consumed: the "
                           f"donation frees nothing and masks a stale "
                           f"donate_argnums"})
        hit = next((o for o in out_set
                    if alias_of.get(o) is v), None)
        if hit is not None:
            oi = next(j for j, o in enumerate(jaxpr.outvars)
                      if o is hit)
            violations.append({
                "rule": "read-after-donation", "program": program,
                "eqn": f"invars[{i}]->outvars[{oi}]", "line": "",
                "message": f"donated input {i} reaches output {oi} "
                           f"through an alias-only chain: the caller "
                           f"holds (or caches) a view of a buffer XLA "
                           f"is free to reuse — reading it after "
                           f"dispatch is use-after-free"})


# ---------------------------------------------------------------------------
# verification entry points
# ---------------------------------------------------------------------------

def check_jaxpr(closed, *, program: str, subsystem: str = "",
                donated_argnums: Tuple[int, ...] = (),
                declared_collectives: Optional[Tuple[str, ...]] = None,
                forbid_donation: bool = False) -> dict:
    """Run the three passes over one ClosedJaxpr; returns the report
    (never raises — enforcement is the caller's job)."""
    jaxpr = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    donated = {jaxpr.invars[i] for i in donated_argnums
               if 0 <= i < len(jaxpr.invars)}
    collectives: List[dict] = []
    viols: List[dict] = []
    _scan_jaxpr(jaxpr, set(), False, "", collectives, viols, program)
    _audit_donation(jaxpr, donated, program, forbid_donation, viols)
    if declared_collectives is not None:
        got = {c["prim"] for c in collectives}
        want = set(declared_collectives)
        # subset, not equality: incidental collectives (count gathers
        # inside a shuffle helper) extract into the manifest without
        # being declared — only a DECLARED collective missing from the
        # traced program is a lie the lockstep checker would act on
        if not want <= got:
            viols.append({
                "rule": "manifest-mismatch", "program": program,
                "eqn": "", "line": "",
                "message": f"fused group declares in-program "
                           f"collectives {sorted(want)} but the traced "
                           f"program contains {sorted(got)}: the "
                           f"lockstep pre-validation manifest would "
                           f"lie to the runtime checker"})
    return {
        "program": program,
        "subsystem": subsystem,
        "collectives": collectives,
        "rank_invariant": not any(v["rule"] == "rank-divergent-collective"
                                  for v in viols),
        "violations": viols,
        "hbm_bytes": int(_peak_live_bytes(jaxpr, donated)),
        "donated": len(donated),
        "declared": list(declared_collectives)
        if declared_collectives is not None else None,
    }


def _record(report: dict, obs_handle: int, check_s: float,
            enforce: Optional[bool]) -> dict:
    program = report["program"]
    report["check_s"] = check_s
    report["obs_handle"] = obs_handle
    with _mu:
        _stats["programs"] += 1
        _stats["violations"] += len(report["violations"])
        _stats["check_s"] += check_s
        _stats["max_check_s"] = max(_stats["max_check_s"], check_s)
        _stats["manifests"] += 1
        _reports[program] = report
        if obs_handle:
            _checked_handles.add(obs_handle)
        warn_new = program not in _warned
        _warned.add(program)
    # lockstep pre-validation manifest (collective prim order)
    try:
        from bodo_tpu.analysis import lockstep
        lockstep.register_program_manifest(
            program,
            collectives=tuple(c["prim"] for c in report["collectives"]),
            rank_invariant=report["rank_invariant"],
            subsystem=report["subsystem"],
            hbm_bytes=report["hbm_bytes"],
            violations=len(report["violations"]))
    except Exception:  # noqa: BLE001 - manifest registry best-effort
        pass
    # observatory: per-program row -> registry dumps -> flight bundles
    obs = sys.modules.get("bodo_tpu.runtime.xla_observatory")
    if obs is not None and obs_handle:
        try:
            obs.note_progcheck(obs_handle, {
                "collectives": [c["prim"]
                                for c in report["collectives"]],
                "rank_invariant": report["rank_invariant"],
                "hbm_bytes": report["hbm_bytes"],
                "violations": [
                    {"rule": v["rule"], "eqn": v["eqn"],
                     "line": v["line"]}
                    for v in report["violations"]],
            })
        except Exception:  # noqa: BLE001
            pass
    if report["violations"]:
        v0 = report["violations"][0]
        do_enforce = enforcing() if enforce is None else enforce
        if do_enforce:
            raise ProgramInvariantError(program, v0["rule"],
                                        v0["message"], v0["eqn"])
        if warn_new:
            from bodo_tpu.utils.logging import log
            log(1, f"progcheck: program {program!r}: "
                   f"{len(report['violations'])} violation(s), first: "
                   f"[{v0['rule']}] {v0['message']} (at {v0['eqn']}) "
                   f"— set BODO_TPU_PROGCHECK_ENFORCE=1 to reject at "
                   f"registration")
    return report


def check_jit(fn, args: tuple = (), kwargs: Optional[dict] = None, *,
              program: str, subsystem: str = "",
              declared_collectives: Optional[Tuple[str, ...]] = None,
              forbid_donation: bool = False, obs_handle: int = 0,
              enforce: Optional[bool] = None) -> Optional[dict]:
    """Trace a jitted callable with the given call args and verify it.
    Returns the report, or None when disabled / already verified /
    untraceable. Raises ProgramInvariantError only in enforce mode."""
    if not enabled():
        return None
    with _mu:
        if obs_handle and obs_handle in _checked_handles:
            return _reports.get(program)
        if not obs_handle and program in _reports:
            return _reports[program]
    t0 = time.perf_counter()
    try:
        traced = fn.trace(*args, **(kwargs or {}))
        closed = traced.jaxpr
        import jax
        leaves = jax.tree_util.tree_leaves(traced.args_info)
        donated_argnums = tuple(
            i for i, lf in enumerate(leaves)
            if bool(getattr(lf, "donated", False)))
    except ProgramInvariantError:
        raise
    except Exception:  # noqa: BLE001 - never break dispatch on a
        with _mu:      # trace we cannot reproduce statically
            _stats["skipped"] += 1
        return None
    report = check_jaxpr(
        closed, program=program, subsystem=subsystem,
        donated_argnums=donated_argnums,
        declared_collectives=declared_collectives,
        forbid_donation=forbid_donation)
    return _record(report, obs_handle, time.perf_counter() - t0,
                   enforce)


def mark_checked(handle: int) -> None:
    """Skip-list an observatory handle whose program was already
    verified under another name (e.g. fusion checks `fused:<fp>`
    explicitly before the FusionProgramCache store wraps the same
    executable under its cache handle)."""
    if handle:
        with _mu:
            _checked_handles.add(handle)


class _CheckedProgram:
    """Transparent callable proxy: verifies the wrapped program on its
    first dispatch (when real call args exist to trace against), then
    delegates forever. Attribute access falls through to the program,
    so `.lower`, `.trace`, jit internals all keep working."""

    __slots__ = ("_fn", "_ck", "_done", "__weakref__")

    def __init__(self, fn, ck: dict):
        self._fn = fn
        self._ck = ck
        self._done = False

    def __call__(self, *args, **kwargs):
        if not self._done and enabled():
            self._done = True
            check_jit(self._fn, args, kwargs, **self._ck)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)

    def __repr__(self):
        return f"progcheck({self._fn!r})"


def wrap_program(fn, *, program: str, subsystem: str = "",
                 declared_collectives=None, forbid_donation: bool = False,
                 obs_handle: int = 0):
    """Wrap a jitted callable for first-dispatch verification. Returns
    ``fn`` unchanged when it isn't traceable (no `.trace`) or is
    already wrapped."""
    if isinstance(fn, _CheckedProgram) or not hasattr(fn, "trace") \
            or not callable(fn):
        return fn
    return _CheckedProgram(fn, dict(
        program=program, subsystem=subsystem,
        declared_collectives=declared_collectives,
        forbid_donation=forbid_donation, obs_handle=obs_handle))


# ---------------------------------------------------------------------------
# introspection
# ---------------------------------------------------------------------------

def reports() -> Dict[str, dict]:
    with _mu:
        return {k: dict(v) for k, v in _reports.items()}


def manifests() -> Dict[str, list]:
    with _mu:
        return {k: list(v["collectives"]) for k, v in _reports.items()}


def manifest_for(program: str) -> Optional[list]:
    with _mu:
        r = _reports.get(program)
        return list(r["collectives"]) if r is not None else None


def violations() -> List[dict]:
    with _mu:
        return [dict(v) for r in _reports.values()
                for v in r["violations"]]


def hbm_estimate(program: str) -> Optional[int]:
    with _mu:
        r = _reports.get(program)
        return int(r["hbm_bytes"]) if r is not None else None


def max_hbm_estimate() -> int:
    with _mu:
        return max((int(r["hbm_bytes"]) for r in _reports.values()),
                   default=0)


def stats() -> dict:
    with _mu:
        out = dict(_stats)
        out["hbm_peak_bytes_max"] = max(
            (int(r["hbm_bytes"]) for r in _reports.values()), default=0)
        out["rank_variant_programs"] = sum(
            1 for r in _reports.values() if not r["rank_invariant"])
    out["enforce"] = 1 if enforcing() else 0
    return out


def reset() -> None:
    with _mu:
        _reports.clear()
        _checked_handles.clear()
        _warned.clear()
        for k in _stats:
            _stats[k] = 0.0 if k in ("check_s", "max_check_s") else 0
    ls = sys.modules.get("bodo_tpu.analysis.lockstep")
    if ls is not None:
        ls.clear_program_manifests()


# ---------------------------------------------------------------------------
# CLI: `python -m bodo_tpu.analysis --programs`
# ---------------------------------------------------------------------------

def _self_check_programs():
    """Representative tiny programs, one per verification concern —
    traced fresh in this process so the CLI is meaningful without a
    prior workload."""
    import jax
    import jax.numpy as jnp

    # throwaway CLI-only programs: never dispatched, never cached —
    # the registry bypass is the point (we verify them directly)
    progs = []
    progs.append(("selfcheck:elementwise",
                  jax.jit(lambda x: x * 2 + 1),  # shardcheck: ignore[unregistered-jit]
                  (jnp.arange(8, dtype=jnp.float32),), {}))
    progs.append(("selfcheck:donated",
                  jax.jit(lambda x: jnp.cumsum(x), donate_argnums=(0,)),  # shardcheck: ignore[unregistered-jit]
                  (jnp.arange(8, dtype=jnp.float32),), {}))

    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(jax.devices()[:1], ("x",))

    def body(x):
        return jax.lax.psum(x, "x")

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),  # shardcheck: ignore[unregistered-jit]
                               out_specs=P(), check_vma=False))
    progs.append(("selfcheck:collective", fn,
                  (jnp.arange(4, dtype=jnp.float32),), {}))
    return progs


def main(argv: Optional[List[str]] = None) -> int:
    """`--programs` CLI mode: verify the self-check program set (plus
    anything already registered in this process) and print manifests;
    exit 1 on any violation."""
    import argparse
    import json

    ap = argparse.ArgumentParser(
        prog="python -m bodo_tpu.analysis --programs",
        description="progcheck: jaxpr-level SPMD program verification")
    ap.add_argument("--programs", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report dump")
    ap.add_argument("--enforce", action="store_true",
                    help="raise on first violation instead of listing")
    ap.parse_known_args(argv)
    args = ap.parse_args(argv)

    for name, fn, a, kw in _self_check_programs():
        check_jit(fn, a, kw, program=name, subsystem="selfcheck",
                  enforce=args.enforce)
    reps = reports()
    if args.json:
        print(json.dumps(reps, indent=1, sort_keys=True, default=str))
    else:
        for name in sorted(reps):
            r = reps[name]
            sched = " -> ".join(c["prim"] for c in r["collectives"]) \
                or "(no collectives)"
            flag = "RANK-VARIANT" if not r["rank_invariant"] else "ok"
            print(f"{name}: {sched} | hbm~{r['hbm_bytes']}B | "
                  f"donated={r['donated']} | {flag}")
            for v in r["violations"]:
                print(f"  VIOLATION [{v['rule']}] {v['message']} "
                      f"(at {v['eqn']})")
    bad = violations()
    print(f"progcheck: {len(reps)} programs, {len(bad)} violations")
    return 1 if bad else 0
