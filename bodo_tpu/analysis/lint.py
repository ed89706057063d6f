"""shardcheck codebase lint (shardcheck layer 2) — stdlib-`ast` rules
for SPMD safety over the bodo_tpu package itself.

Rules:

  rank-divergent-collective
      A collective call lexically inside control flow whose condition
      depends on the process/shard identity (rank, process_index,
      BODO_TPU_PROC_ID, axis_index). In gang-scheduled SPMD one rank
      skipping a collective hangs every other rank (Pathways,
      arXiv:2203.12533) — divergent ranks must never reach a
      collective.

  trace-time-side-effect
      A host side effect (I/O, environ, time, random, fault injection)
      inside a function that is traced by jax (contains lax collectives
      or is passed to smap/shard_map). Traced bodies run ONCE at trace
      time and never again from the compiled-kernel cache, so the side
      effect silently stops firing — the PR-2 trace-time-vs-
      dispatch-time distinction as a checked rule.

  retry-non-idempotent
      A non-idempotent operation (write/send/append) inside a callable
      passed to `resilience.retry_call`. A transient failure AFTER the
      effect lands re-runs the effect (duplicate rows / double
      writes) — the ParquetWriter class of bug from the PR-2 review.

  checkpoint-non-idempotent
      A non-idempotent operation (write/send/append) between a
      checkpoint store's two-phase `.register(...)` and its
      `.commit(tok)`. The window is exactly the span a crash discards:
      the snapshot is not yet visible to recovery, so an effect landed
      there replays when the elastic suffix resumes from the PREVIOUS
      checkpoint (duplicate write) — keep the register->commit window
      effect-free.

  unlocked-shared-state
      A write to module-level mutable state outside any `with <lock>:`
      block, in modules that define threading locks (i.e. modules whose
      state is demonstrably shared across threads — the io_pool/pool
      worker-thread model). Modules with no locks are single-threaded
      by design and out of scope.

  fusion-host-call
      A host-sync call (`jax.device_get`, `tracing.host_read`,
      `.to_pandas()`, `device_put`, `.block_until_ready()`) inside a
      function marked `@fusion_stage` (plan/fusion.py). Fusion stages
      run INSIDE one compiled whole-stage program; a host round-trip
      there either fails to trace or silently splits the fused program
      at an unsharded boundary — the exact materialization fusion exists
      to eliminate.

  rank-divergent-rng-seed
      An RNG seeded from process/shard identity (np.random.seed /
      default_rng / PRNGKey over rank, process_index,
      BODO_TPU_PROC_ID, ...). Rank-variant seeds silently diverge
      REPLICATED state: every rank holds "the same" table, fills nulls
      or samples with "the same" RNG, and ends up with different
      bytes — the gang then disagrees at the next content-keyed
      collective or cache lookup. Shard-local sampling must derive
      from a rank-INVARIANT seed plus an explicit fold
      (jax.random.fold_in), never from seeding with the rank itself.

  divergent-host-sync
      A host sync (`jax.device_get` / `tracing.host_read` /
      `.block_until_ready()`) under control flow conditioned on
      process/shard identity. Fetching a SHARDED array is a cross-host
      transfer on multi-host backends — ranks that skipped the branch
      never enter it, so the fetching rank wedges exactly like a skipped
      collective (the rank-divergent-collective rule's host-side twin).

  stream-sync-unannotated
      A host sync (`jax.device_get` / `tracing.host_read` /
      `.block_until_ready()`) inside a streaming accumulator module
      (plan/streaming*.py), a fused-join dispatch body
      (plan/fusion_join.py), or a view step/maintenance body
      (runtime/views.py functions whose name carries step/
      maintenance/tick/refresh/materialize) without a
      `# dispatch-boundary` comment on the call or an adjacent line.
      Streaming steps — and the view-maintenance path that rides the
      same executors — are dispatch-free by design — syncs per stage
      must stay O(1)-O(log batches), so every deliberate sync site is
      annotated and counted in `stream_stats` (through `host_read`); an
      unannotated sync is either an accidental pipeline stall
      (O(batches) regression) or an uncounted one no test can regress
      on.

Suppressions: `# shardcheck: ignore[rule]` (or bare
`# shardcheck: ignore` for all rules) on the finding's line or the
line directly above. Grandfathered findings live in
`analysis/baseline.json`, matched line-number-insensitively on
(rule, file, enclosing function, source text) so unrelated edits don't
resurrect them; `python -m bodo_tpu.analysis --write-baseline`
regenerates it, and `--prune-baseline` drops DEAD entries (ones no
current finding matches) without touching live ones.

Exit status (CLI): 0 when every finding is suppressed or baselined,
1 otherwise — `runtests.py lint` gates on this. A full-package run
also fails (exit 1) on dead baseline entries, so the baseline can only
shrink as findings are fixed.
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys
import tokenize
from dataclasses import dataclass
from typing import Dict, List, Optional, Set

RULES = {
    "rank-divergent-collective":
        "collective dispatched under rank-dependent control flow",
    "trace-time-side-effect":
        "host side effect inside a jax-traced function body",
    "retry-non-idempotent":
        "non-idempotent operation inside the retry envelope",
    "checkpoint-non-idempotent":
        "side effect inside the checkpoint register->commit window",
    "unlocked-shared-state":
        "module-global state written without holding a lock",
    "fusion-host-call":
        "host sync inside a @fusion_stage-decorated traced body",
    "swallowed-collective":
        "collective inside a try whose handler swallows divergence",
    "unregistered-jit":
        "jit/pallas_call site bypassing the program registry",
    "rank-divergent-rng-seed":
        "RNG seeded from process/shard identity",
    "divergent-host-sync":
        "host sync of device arrays under rank-dependent control flow",
    "stream-sync-unannotated":
        "host sync in a streaming step body without a "
        "dispatch-boundary annotation",
}

# names that identify process/shard identity in a branch condition
_RANK_NAMES = {"rank", "process_index", "process_id", "proc_id",
               "current_rank", "axis_index"}
_RANK_ENV = {"BODO_TPU_PROC_ID"}

# axis-context collectives (lax + this package's wrappers) and the
# host-level dispatch helpers: calling any of these from one rank only
# wedges the gang
_COLLECTIVE_NAMES = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "pshuffle", "psum_scatter",
    "dist_sum", "dist_max", "dist_min", "dist_exscan_sum",
    "all_gather_rows", "all_to_all_rows", "ring_shift", "bcast_from",
    "shuffle_rows", "shuffle_by_key",
}
# lax-only subset used to classify a function as jax-traced
_LAX_COLLECTIVES = {"psum", "pmax", "pmin", "all_gather", "all_to_all",
                    "ppermute", "pshuffle", "psum_scatter",
                    "axis_index"}

_SIDE_EFFECT_NAMES = {"open", "print", "maybe_inject", "_inject",
                      "input"}
_SIDE_EFFECT_MODULES = {"os", "time", "random"}
# pure/trace-safe exceptions within those modules
_SIDE_EFFECT_OK = {"time.monotonic", "time.perf_counter", "time.time",
                   "os.path", "random.Random"}

_NONIDEMPOTENT = {"write", "writelines", "write_table", "send",
                  "sendall", "appendleft", "append_row"}

# receivers that look like a two-phase checkpoint store: their
# .register(...) opens an uncommitted-snapshot window that .commit(tok)
# closes (runtime/elastic.py CheckpointStore is the canonical one)
_CKPT_RECV_RE = re.compile(r"ckpt|checkpoint|store", re.IGNORECASE)

# host-sync calls illegal inside a @fusion_stage body (whole-stage
# fusion: the body runs inside ONE compiled program)
_HOST_SYNC_NAMES = {"device_get", "host_read", "to_pandas", "device_put",
                    "block_until_ready"}

# host syncs that are cross-host transfers for sharded arrays — under
# rank-divergent control flow they wedge like a skipped collective
_DIVERGENT_SYNC_NAMES = {"device_get", "host_read", "block_until_ready"}

# streaming accumulator modules: every host sync in a step body must be
# a deliberate, annotated dispatch boundary (plan/streaming.py's
# host-sync accounting contract). plan/fusion_join.py rides the same
# contract whole-module (its group dispatch is the one budgeted sync);
# runtime/views.py only in step/maintenance bodies (the serving-path
# refresh loop), matched by enclosing-function name.
_STREAMING_FILE_RE = re.compile(r"(^|[/\\])plan[/\\]streaming[^/\\]*\.py$")
_STREAM_WHOLE_FILE_RE = re.compile(r"(^|[/\\])plan[/\\]fusion_join\.py$")
_STREAM_SCOPED_FILE_RE = re.compile(r"(^|[/\\])runtime[/\\]views\.py$")
_STREAM_SCOPED_FUNC_RE = re.compile(
    r"step|maintenance|tick|refresh|materialize")
_DISPATCH_BOUNDARY_RE = re.compile(r"#\s*dispatch-boundary")

# RNG seeding entry points (numpy + jax.random)
_RNG_SEED_NAMES = {"seed", "default_rng", "PRNGKey", "RandomState"}

_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}
_LOCKISH_RE = re.compile(r"(lock|_mu$|mutex|cv$|cond)", re.IGNORECASE)
_MUTATORS = {"append", "extend", "add", "update", "pop", "popitem",
             "clear", "remove", "discard", "insert", "setdefault",
             "appendleft"}

_SUPPRESS_RE = re.compile(
    r"#\s*shardcheck:\s*ignore(?:\[([\w\-, ]+)\])?")


@dataclass
class Finding:
    rule: str
    path: str          # repo-relative
    line: int
    col: int
    func: str          # enclosing function qualname ("" = module)
    text: str          # source line, stripped
    message: str

    def key(self):
        """Line-number-insensitive identity for baseline matching."""
        return (self.rule, self.path, self.func, self.text)

    def render(self) -> str:
        where = f" (in {self.func})" if self.func else ""
        return (f"{self.path}:{self.line}:{self.col}: [{self.rule}] "
                f"{self.message}{where}\n    {self.text}")


_stats = {"runs": 0, "files": 0, "findings": 0, "suppressed": 0,
          "baselined": 0}


def stats() -> dict:
    return dict(_stats)


def _terminal(func) -> str:
    """Rightmost name of a call target (foo / mod.foo / a.b.foo)."""
    if isinstance(func, ast.Attribute):
        return func.attr
    return func.id if isinstance(func, ast.Name) else ""


def _root(node) -> str:
    """Leftmost name of an attribute chain (os.environ.get -> os)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else ""


def _dotted(node) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _test_is_rank_divergent(test: ast.AST) -> bool:
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and n.id in _RANK_NAMES:
            return True
        if isinstance(n, ast.Attribute) and n.attr in _RANK_NAMES:
            return True
        if isinstance(n, ast.Call) and _terminal(n.func) in _RANK_NAMES:
            return True
        if isinstance(n, ast.Constant) and n.value in _RANK_ENV:
            return True
    return False


class _ModuleInfo(ast.NodeVisitor):
    """Pre-pass: module-level names, locks, traced functions, and
    retry_call targets."""

    def __init__(self):
        self.globals: Set[str] = set()        # module-level bindings
        self.mutables: Set[str] = set()       # dict/list/set/deque/...
        self.locks: Set[str] = set()          # Lock()/RLock()/...
        self.smap_fn_names: Set[str] = set()  # passed to smap/shard_map

    def visit_Module(self, node: ast.Module):
        for stmt in node.body:
            targets = []
            if isinstance(stmt, ast.Assign):
                targets = [t for t in stmt.targets
                           if isinstance(t, ast.Name)]
                value = stmt.value
            elif isinstance(stmt, ast.AnnAssign) and \
                    isinstance(stmt.target, ast.Name):
                targets = [stmt.target]
                value = stmt.value
            else:
                continue
            for t in targets:
                self.globals.add(t.id)
                if isinstance(value, ast.Call):
                    name = _terminal(value.func)
                    if name in _LOCK_FACTORIES:
                        self.locks.add(t.id)
                    elif name in ("dict", "list", "set", "deque",
                                  "defaultdict", "OrderedDict",
                                  "Counter"):
                        self.mutables.add(t.id)
                elif isinstance(value, (ast.Dict, ast.List, ast.Set,
                                        ast.DictComp, ast.ListComp,
                                        ast.SetComp)):
                    self.mutables.add(t.id)
        # whole-tree scan for smap/shard_map(fn, ...) first args
        for n in ast.walk(node):
            if isinstance(n, ast.Call) and \
                    _terminal(n.func) in ("smap", "shard_map") and \
                    n.args and isinstance(n.args[0], ast.Name):
                self.smap_fn_names.add(n.args[0].id)


# a store like `cache[key] = fn` / `_programs[sig] = fn` / `_jit_cache
# [key] = fn` marks the enclosing function as registering its compiled
# programs with a kernel cache (which reports to the program registry)
_CACHE_NAME_HINTS = ("cache", "program")


def _stores_into_kernel_cache(fn: ast.AST) -> bool:
    for n in ast.walk(fn):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                if isinstance(t, ast.Subscript):
                    name = (_terminal(t.value)
                            if isinstance(t.value, ast.Attribute)
                            else getattr(t.value, "id", ""))
                    low = name.lower()
                    if any(h in low for h in _CACHE_NAME_HINTS):
                        return True
    return False


def _has_registering_decorator(fn: ast.AST) -> bool:
    """@cached_builder("sub") / @bounded_jit memoize the function's
    compiled programs in a registered KernelCache."""
    for d in getattr(fn, "decorator_list", []):
        t = _terminal(d.func) if isinstance(d, ast.Call) else _terminal(d)
        if t in ("cached_builder", "bounded_jit"):
            return True
    return False


def _is_jit_decorator(d: ast.AST) -> bool:
    """@jax.jit, or @partial(jax.jit, ...) / @functools.partial(...),
    likewise around kernel_cache.named_jit."""
    if _dotted(d) == "jax.jit":
        return True
    if isinstance(d, ast.Call) and _terminal(d.func) == "jit" \
            and _root(d.func) == "jax":
        return True
    if isinstance(d, ast.Call) and _terminal(d.func) == "partial":
        for a in d.args:
            if _dotted(a) in ("jax.jit", "named_jit"):
                return True
    return False


def _contains_lax_collective(fn: ast.AST) -> bool:
    for n in ast.walk(fn):
        if isinstance(n, ast.Call) and \
                _terminal(n.func) in _LAX_COLLECTIVES:
            return True
    return False


def _calls_in_order(fn: ast.AST) -> List[ast.Call]:
    """Call nodes lexically inside ``fn``'s own body — nested
    function/lambda bodies excluded (they execute at their OWN call
    time, not inside this function's checkpoint window) — in source
    order."""
    out: List[ast.Call] = []

    def rec(n: ast.AST) -> None:
        for c in ast.iter_child_nodes(n):
            if isinstance(c, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)):
                continue
            if isinstance(c, ast.Call):
                out.append(c)
            rec(c)

    rec(fn)
    out.sort(key=lambda c: (getattr(c, "lineno", 0),
                            getattr(c, "col_offset", 0)))
    return out


class _Checker(ast.NodeVisitor):
    def __init__(self, path: str, rel: str, src_lines: List[str],
                 info: _ModuleInfo,
                 dispatch_lines: Optional[Set[int]] = None):
        self.rel = rel
        self.lines = src_lines
        self.info = info
        self.dispatch_lines = dispatch_lines or set()
        rel_posix = rel.replace(os.sep, "/")
        self._stream_mod = bool(
            _STREAMING_FILE_RE.search(rel_posix)
            or _STREAM_WHOLE_FILE_RE.search(rel_posix))
        self._stream_scoped = bool(
            _STREAM_SCOPED_FILE_RE.search(rel_posix))
        self.findings: List[Finding] = []
        self._func: List[str] = []       # qualname stack
        self._div_depth = 0              # rank-divergent control flow
        self._locks_held = 0             # `with <lock>:` nesting
        self._traced_depth = 0           # inside a jax-traced function
        self._fusion_depth = 0           # inside a @fusion_stage body
        self._reg_depth = 0              # fn stores into a kernel cache
        self._local_defs: List[Dict[str, ast.AST]] = [{}]

    # -- helpers ----------------------------------------------------------

    def _add(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        text = self.lines[line - 1].strip() if \
            0 < line <= len(self.lines) else ""
        self.findings.append(Finding(
            rule=rule, path=self.rel, line=line,
            col=getattr(node, "col_offset", 0),
            func=".".join(self._func), text=text, message=message))

    def _qual(self, name: str) -> str:
        return ".".join(self._func + [name])

    # -- scopes -----------------------------------------------------------

    def _visit_func(self, node):
        self._local_defs[-1][node.name] = node
        traced = (node.name in self.info.smap_fn_names or
                  _contains_lax_collective(node))
        fused = any(_terminal(d) == "fusion_stage"
                    for d in node.decorator_list)
        registers = (_stores_into_kernel_cache(node) or
                     _has_registering_decorator(node))
        for d in node.decorator_list:
            # a @jax.jit on a local function whose enclosing scope
            # stores it into a kernel cache IS registered
            if not self._reg_depth and not registers \
                    and _is_jit_decorator(d):
                self._add(
                    "unregistered-jit", d,
                    "module-lifetime @jit decorator: pins one "
                    "executable per signature forever, invisible to "
                    "the program registry and its compile budget — "
                    "route through bounded_jit or a registered "
                    "KernelCache")
        self._func.append(node.name)
        self._check_checkpoint_windows(node)
        self._local_defs.append({})
        if traced:
            self._traced_depth += 1
        if fused:
            self._fusion_depth += 1
        if registers:
            self._reg_depth += 1
        # a lock held at the call site does not cover the function body
        saved_locks, self._locks_held = self._locks_held, 0
        self.generic_visit(node)
        self._locks_held = saved_locks
        if registers:
            self._reg_depth -= 1
        if fused:
            self._fusion_depth -= 1
        if traced:
            self._traced_depth -= 1
        self._local_defs.pop()
        self._func.pop()

    visit_FunctionDef = _visit_func
    visit_AsyncFunctionDef = _visit_func

    # -- rank-divergent control flow --------------------------------------

    def _visit_branch(self, node):
        divergent = _test_is_rank_divergent(node.test)
        if divergent:
            self._div_depth += 1
        self.generic_visit(node)
        if divergent:
            self._div_depth -= 1

    visit_If = _visit_branch
    visit_While = _visit_branch
    visit_IfExp = _visit_branch

    # -- with <lock>: -----------------------------------------------------

    def visit_With(self, node: ast.With):
        lockish = 0
        for item in node.items:
            expr = item.context_expr
            if isinstance(expr, ast.Call):
                expr = expr.func  # reserve(...), lock() factories
            name = _dotted(expr)
            leaf = name.rsplit(".", 1)[-1] if name else ""
            if leaf in self.info.locks or _LOCKISH_RE.search(leaf or ""):
                lockish += 1
        self._locks_held += lockish
        self.generic_visit(node)
        self._locks_held -= lockish

    # -- try/except around collectives ------------------------------------

    # exception names wide enough to catch a lockstep divergence (or
    # any gang-consistency error) — swallowing one desynchronizes the
    # swallowing rank from peers still inside (or dead at) the op
    _BROAD_EXC = {"Exception", "BaseException", "LockstepError"}

    def _handler_swallows(self, h: ast.ExceptHandler) -> bool:
        names: Set[str] = set()
        if h.type is None:
            names.add("BaseException")  # bare except
        else:
            types = h.type.elts if isinstance(h.type, ast.Tuple) \
                else [h.type]
            for tnode in types:
                names.add(_terminal(tnode) if
                          isinstance(tnode, ast.Call) else
                          _dotted(tnode).rsplit(".", 1)[-1])
        if not names & self._BROAD_EXC:
            return False
        # a handler that re-raises (or exits the process) propagates
        # the divergence instead of swallowing it
        for n in ast.walk(h):
            if isinstance(n, ast.Raise):
                return False
            if isinstance(n, ast.Call) and \
                    _terminal(n.func) in ("_exit", "exit", "abort"):
                return False
        return True

    def visit_Try(self, node: ast.Try):
        swallowing = [h for h in node.handlers
                      if self._handler_swallows(h)]
        if swallowing:
            for n in ast.walk(ast.Module(body=node.body,
                                         type_ignores=[])):
                if isinstance(n, ast.Call) and \
                        _terminal(n.func) in _COLLECTIVE_NAMES:
                    t = _terminal(n.func)
                    self._add(
                        "swallowed-collective", n,
                        f"collective {t!r} inside a try whose handler "
                        f"catches broadly without re-raising: a "
                        f"divergence error (LockstepError) raised here "
                        f"is swallowed on THIS rank while peers wedge "
                        f"in (or die at) the op — catch narrowly or "
                        f"re-raise")
        self.generic_visit(node)

    # -- calls ------------------------------------------------------------

    def visit_Call(self, node: ast.Call):
        t = _terminal(node.func)
        if self._div_depth and t in _COLLECTIVE_NAMES:
            self._add(
                "rank-divergent-collective", node,
                f"collective {t!r} dispatched under rank-dependent "
                f"control flow: ranks taking the other branch never "
                f"enter the collective and the gang hangs")
        if self._div_depth and t in _DIVERGENT_SYNC_NAMES:
            self._add(
                "divergent-host-sync", node,
                f"{t!r} under rank-dependent control flow: fetching a "
                f"sharded array is a cross-host transfer — ranks that "
                f"took the other branch never participate, wedging "
                f"this rank like a skipped collective")
        in_stream_body = self._stream_mod or (
            self._stream_scoped and any(
                _STREAM_SCOPED_FUNC_RE.search(fn) for fn in self._func))
        if in_stream_body and self._func and \
                t in _DIVERGENT_SYNC_NAMES:
            lo = getattr(node, "lineno", 1) - 1
            hi = getattr(node, "end_lineno", lo + 1) + 1
            if not any(ln in self.dispatch_lines
                       for ln in range(lo, hi + 1)):
                self._add(
                    "stream-sync-unannotated", node,
                    f"{t!r} in a streaming step body without a "
                    f"`# dispatch-boundary` annotation: streaming "
                    f"stages budget O(1)-O(log batches) syncs — mark "
                    f"the site deliberate (read it through "
                    f"tracing.host_read) or "
                    f"hoist the fetch out of the per-batch path")
        if t in _RNG_SEED_NAMES and (node.args or node.keywords) and \
                any(_test_is_rank_divergent(a)
                    for a in list(node.args) +
                    [k.value for k in node.keywords]):
            self._add(
                "rank-divergent-rng-seed", node,
                f"{t!r} seeded from process/shard identity: replicated "
                f"state sampled from it silently diverges across "
                f"ranks — derive shard-local streams from a "
                f"rank-invariant seed via jax.random.fold_in instead")
        if self._traced_depth:
            dotted = _dotted(node.func)
            if (t in _SIDE_EFFECT_NAMES or
                (_root(node.func) in _SIDE_EFFECT_MODULES and
                 not any(dotted.startswith(ok)
                         for ok in _SIDE_EFFECT_OK))):
                self._add(
                    "trace-time-side-effect", node,
                    f"{dotted or t!r} inside a jax-traced body fires "
                    f"at TRACE time only (compiled kernels are cached "
                    f"and replay without it)")
        if self._fusion_depth and t in _HOST_SYNC_NAMES:
            self._add(
                "fusion-host-call", node,
                f"{t!r} inside a @fusion_stage body: fusion stages "
                f"trace into ONE compiled program — a host sync here "
                f"splits the fused pipeline (or fails to trace)")
        if not self._reg_depth and \
                ((t == "jit" and _root(node.func) == "jax")
                 or t in ("named_jit", "pallas_call")):
            self._add(
                "unregistered-jit", node,
                f"direct {_dotted(node.func) or t!r} call outside a "
                f"registering cache: the executable bypasses the "
                f"program registry (no retrace attribution, no "
                f"compile budget, unbounded pinning) — store it in a "
                f"subsystem-tagged KernelCache or use bounded_jit")
        if t == "retry_call" and node.args:
            self._check_retry_target(node)
        # dict.setdefault-style mutations via call are handled in the
        # mutation visitors below; nothing else to do here
        self.generic_visit(node)

    def _check_retry_target(self, node: ast.Call) -> None:
        target = node.args[0]
        body: Optional[ast.AST] = None
        if isinstance(target, ast.Lambda):
            body = target
        elif isinstance(target, ast.Name):
            for scope in reversed(self._local_defs):
                if target.id in scope:
                    body = scope[target.id]
                    break
        if body is None:
            return
        for n in ast.walk(body):
            if isinstance(n, ast.Call):
                meth = _terminal(n.func)
                if meth in _NONIDEMPOTENT and \
                        isinstance(n.func, ast.Attribute):
                    self._add(
                        "retry-non-idempotent", node,
                        f"retry envelope wraps non-idempotent "
                        f"`.{meth}(...)`: a transient failure after "
                        f"the effect lands replays it (duplicate "
                        f"write)")
                    return

    def _check_checkpoint_windows(self, fn) -> None:
        """Linear source-order scan of this function's calls: a
        ``<ckpt-store>.register(...)`` opens an uncommitted-snapshot
        window that the matching ``<ckpt-store>.commit(...)`` closes;
        any non-idempotent effect inside the window replays on elastic
        resume (the snapshot it rode with was never committed)."""
        open_regs: Dict[str, ast.Call] = {}
        for c in _calls_in_order(fn):
            if not isinstance(c.func, ast.Attribute):
                continue
            t = c.func.attr
            recv = _dotted(c.func.value)
            if t == "register" and recv and _CKPT_RECV_RE.search(recv):
                open_regs[recv] = c
                continue
            if t == "commit" and recv in open_regs:
                del open_regs[recv]
                continue
            if open_regs and t in _NONIDEMPOTENT:
                stores = ", ".join(sorted(open_regs))
                self._add(
                    "checkpoint-non-idempotent", c,
                    f"non-idempotent `.{t}(...)` between "
                    f"{stores!r}.register() and its commit: a crash "
                    f"here discards the registered snapshot, so the "
                    f"resumed suffix replays this effect (duplicate "
                    f"write) — move it after commit or make it "
                    f"idempotent")

    # -- shared-state mutation --------------------------------------------

    def _mutation(self, node, name: str, how: str) -> None:
        if not self.info.locks:           # module has no threads/locks
            return
        if not self._func:                # module top level: init time
            return
        if self._locks_held:
            return
        self._add(
            "unlocked-shared-state", node,
            f"module-global {name!r} {how} without holding any of "
            f"this module's locks "
            f"({', '.join(sorted(self.info.locks))})")

    def visit_Global(self, node: ast.Global):
        # remember rebindable globals for this function scope
        self._global_decls = getattr(self, "_global_decls", {})
        self._global_decls.setdefault(".".join(self._func),
                                      set()).update(node.names)
        self.generic_visit(node)

    def _rebinds_global(self, name: str) -> bool:
        decls = getattr(self, "_global_decls", {})
        return name in decls.get(".".join(self._func), set())

    def visit_Assign(self, node: ast.Assign):
        for t in node.targets:
            self._check_store(t, node)
        self.generic_visit(node)

    def visit_AugAssign(self, node: ast.AugAssign):
        self._check_store(node.target, node)
        self.generic_visit(node)

    def _check_store(self, target, node) -> None:
        if isinstance(target, ast.Name):
            if target.id in self.info.globals and \
                    self._rebinds_global(target.id):
                self._mutation(node, target.id, "rebound")
        elif isinstance(target, ast.Subscript):
            base = target.value
            if isinstance(base, ast.Name) and \
                    base.id in self.info.mutables:
                self._mutation(node, base.id, "item-assigned")

    def visit_Expr(self, node: ast.Expr):
        # `_cache.update(...)`-style mutator method calls
        v = node.value
        if isinstance(v, ast.Call) and isinstance(v.func, ast.Attribute):
            base = v.func.value
            if isinstance(base, ast.Name) and \
                    base.id in self.info.mutables and \
                    v.func.attr in _MUTATORS:
                self._mutation(node, base.id,
                               f"mutated via .{v.func.attr}()")
        self.generic_visit(node)


# ---------------------------------------------------------------------------
# suppressions / baseline
# ---------------------------------------------------------------------------

def _dispatch_boundary_lines(source: str) -> Set[int]:
    """Lines carrying a `# dispatch-boundary` comment (tokenize-based,
    so the marker inside a string/docstring does not count)."""
    out: Set[int] = set()
    try:
        tokens = tokenize.generate_tokens(
            iter(source.splitlines(True)).__next__)
        for tok in tokens:
            if tok.type == tokenize.COMMENT and \
                    _DISPATCH_BOUNDARY_RE.search(tok.string):
                out.add(tok.start[0])
    except tokenize.TokenError:
        pass
    return out


def _suppressions(source: str) -> Dict[int, Optional[Set[str]]]:
    """line -> suppressed rule set (None = all rules). A comment
    suppresses its own line and the line below it."""
    out: Dict[int, Optional[Set[str]]] = {}
    try:
        tokens = tokenize.generate_tokens(
            iter(source.splitlines(True)).__next__)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if not m:
                continue
            rules = None
            if m.group(1):
                rules = {r.strip() for r in m.group(1).split(",")}
            for line in (tok.start[0], tok.start[0] + 1):
                prev = out.get(line, set())
                out[line] = None if rules is None or prev is None \
                    else prev | rules
    except tokenize.TokenError:
        pass
    return out


def _is_suppressed(f: Finding,
                   supp: Dict[int, Optional[Set[str]]]) -> bool:
    if f.line not in supp:
        return False
    rules = supp[f.line]
    return rules is None or f.rule in rules


def load_baseline(path: str) -> List[tuple]:
    try:
        with open(path) as fh:
            raw = json.load(fh)
    except (OSError, ValueError):
        return []
    return [(e["rule"], e["file"], e.get("func", ""), e["text"])
            for e in raw if isinstance(e, dict)]


def write_baseline(path: str, findings: List[Finding]) -> None:
    _write_baseline_keys(path, [f.key() for f in findings])


def _write_baseline_keys(path: str, keys: List[tuple]) -> None:
    entries = [{"rule": rule, "file": file, "func": func, "text": text}
               for rule, file, func, text in keys]
    with open(path, "w") as fh:
        json.dump(entries, fh, indent=1, sort_keys=True)
        fh.write("\n")


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BASELINE = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "baseline.json")


def lint_file(path: str, root: Optional[str] = None) -> List[Finding]:
    """Lint one file; findings suppressed inline are dropped (counted
    in stats)."""
    root = root or os.path.dirname(path)
    rel = os.path.relpath(path, root)
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(rule="parse-error", path=rel,
                        line=e.lineno or 1, col=0, func="",
                        text="", message=str(e))]
    info = _ModuleInfo()
    info.visit_Module(tree)
    checker = _Checker(path, rel, source.splitlines(), info,
                       dispatch_lines=_dispatch_boundary_lines(source))
    checker.visit(tree)
    supp = _suppressions(source)
    kept = []
    for f in checker.findings:
        if _is_suppressed(f, supp):
            _stats["suppressed"] += 1
        else:
            kept.append(f)
    _stats["files"] += 1
    return kept


def lint_paths(paths, root: Optional[str] = None) -> List[Finding]:
    files: List[str] = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                files += [os.path.join(dirpath, fn)
                          for fn in filenames if fn.endswith(".py")]
        elif p.endswith(".py"):
            files.append(p)
    out: List[Finding] = []
    for f in sorted(files):
        out += lint_file(f, root=root)
    return out


def lint_package() -> List[Finding]:
    """Lint the installed bodo_tpu package (what the CI gate runs)."""
    return lint_paths([_PKG_DIR], root=os.path.dirname(_PKG_DIR))


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m bodo_tpu.analysis",
        description="shardcheck: SPMD safety lint over bodo_tpu/")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to lint (default: the package)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON of grandfathered findings")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings too")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline from current findings")
    ap.add_argument("--prune-baseline", action="store_true",
                    help="drop baseline entries no current finding "
                         "matches (keeps live ones untouched)")
    args = ap.parse_args(argv)
    _stats["runs"] += 1
    if args.paths:
        findings = lint_paths(args.paths, root=os.getcwd())
    else:
        findings = lint_package()
    _stats["findings"] += len(findings)
    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(f"shardcheck: wrote {len(findings)} baseline entries to "
              f"{args.baseline}")
        return 0
    live_keys = {f.key() for f in findings}
    if args.prune_baseline:
        if args.paths:
            # a partial scan would read unscanned files' entries as
            # falsely dead and silently delete them
            print("shardcheck: --prune-baseline requires a full-package "
                  "run (no explicit paths)")
            return 1
        entries = load_baseline(args.baseline)
        kept = [e for e in entries if e in live_keys]
        _write_baseline_keys(args.baseline, kept)
        print(f"shardcheck: pruned {len(entries) - len(kept)} dead "
              f"baseline entries ({len(kept)} kept) in {args.baseline}")
        return 0
    baseline = set() if args.no_baseline else \
        set(load_baseline(args.baseline))
    fresh = []
    for f in findings:
        if f.key() in baseline:
            _stats["baselined"] += 1
        else:
            fresh.append(f)
    for f in fresh:
        print(f.render())
    # full-package runs also gate on DEAD baseline entries: a fixed
    # finding must leave the baseline (--prune-baseline removes it),
    # otherwise the grandfather list silently grows stale and can
    # resurrect a regression unnoticed. Partial-path runs skip this —
    # entries for unscanned files would read as falsely dead.
    dead: List[tuple] = []
    if not args.paths and not args.no_baseline:
        dead = sorted(baseline - live_keys)
        for rule, file, func, text in dead:
            where = f" (in {func})" if func else ""
            print(f"{file}: [{rule}] DEAD baseline entry — the finding "
                  f"no longer fires{where}; run --prune-baseline"
                  f"\n    {text}")
    n_base = len(findings) - len(fresh)
    print(f"shardcheck: {_stats['files']} files, "
          f"{len(findings)} findings "
          f"({n_base} baselined, {_stats['suppressed']} suppressed "
          f"inline, {len(fresh)} new"
          + (f", {len(dead)} dead baseline entries" if dead else "")
          + ")")
    return 1 if fresh or dead else 0
