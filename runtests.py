#!/usr/bin/env python
"""Run the test suite in a few grouped subprocesses.

XLA:CPU's JIT compiler segfaults after pinning thousands of distinct
compiled kernels in one process; the engine bounds its own caches
(utils/kernel_cache.py), but a single-process run of the FULL suite
still accumulates every module's distinct shapes at once. The reference
engine contains the same class of leak by running test files in
separate processes (reference: bodo/runtests.py:58-100 — "Run each test
file in a separate process to avoid out-of-memory issues in CI").

One subprocess per module (53 processes) re-pays jax import + kernel
compile per module and pushes the suite past 20 minutes; a handful of
grouped subprocesses keeps the per-process kernel count bounded while
amortizing startup. test_tpch.py stays isolated: it compiles the widest
kernel set (22 queries) and is the likeliest segfault source.

Each group runs under a watchdog (BODO_TPU_TEST_TIMEOUT seconds,
default 900): the child installs faulthandler.dump_traceback_later so a
hung module dumps every thread's stack to stderr BEFORE the parent's
kill lands, and the kill is reported as TIMEOUT(module) instead of a
bare non-zero rc.

The full-suite run also gates on the shardcheck SPMD lint
(`python -m bodo_tpu.analysis`): any finding that is neither suppressed
inline nor in analysis/baseline.json fails the run — as do DEAD
baseline entries (prune with `--prune-baseline`). It additionally
gates on the progcheck self-check
(`python -m bodo_tpu.analysis --programs`): one representative program
per family is traced and its collective manifest / donation / HBM
passes must verify clean.

Usage:
    python runtests.py              # whole suite + shardcheck lint
    python runtests.py lint         # shardcheck lint only
    python runtests.py -k pattern   # forwarded to pytest
    python runtests.py tests/test_sql.py tests/test_groupby.py
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import time

_REPO = os.path.dirname(os.path.abspath(__file__))

# Modules that run alone: widest kernel sets / heaviest compile load —
# and test_io_pipeline.py, whose chaos cases (mid-stream Prefetcher
# close, armed io.read faults, thread-leak assertions) must not share a
# process with modules that leave streams open. test_query_profiler.py
# arms global tracing / resizes the event ring buffer / spawns a traced
# gang, so it must not interleave with modules asserting on the same
# globals. test_comm_observatory.py arms comm accounting / lockstep /
# the telemetry server and spawns a latency-fault gang, for the same
# reason. test_fused_join.py compiles a wide set of fused join/shuffle
# programs and asserts on process-wide lockstep manifests, comm sites
# and the build cache, so it runs alone like test_fusion.py.
# test_result_cache.py mutates parquet datasets on disk, pins tiny
# cache/governor budgets and asserts on the process-wide result-cache
# counters, so it must not share a process with modules that execute
# plans concurrently. test_scheduler.py owns the process-wide serving
# scheduler singleton (worker threads, serve_* config, per-session
# cache counters, an armed chaos fault), so it runs alone too.
# test_fleet.py owns real subprocess gangs (ports, the fleet
# controller singleton, fault-injected gang deaths, process-wide
# result-cache ownership env), so it runs alone; wall time is bounded
# by the same per-group watchdog as every other group.
# test_elastic.py spawns real elastic gangs with armed kill/raise
# faults and asserts on the process-wide elastic serving state,
# lockstep mesh epochs and resilience counters, so it runs alone too.
# test_views.py owns the process-wide materialized-view registry,
# mutates datasets on disk, starts/stops the serving scheduler for the
# continuous-query paths and asserts on process-wide cache counters
# (partition_refresh / parts_reused / view_pins), so it runs alone.
_ISOLATED = ("test_tpch.py", "test_adaptive.py", "test_io_pipeline.py",
             "test_query_profiler.py", "test_fusion.py",
             "test_telemetry.py", "test_device_decode.py",
             "test_comm_observatory.py", "test_fused_join.py",
             "test_result_cache.py", "test_scheduler.py",
             "test_fleet.py", "test_elastic.py", "test_views.py")
_N_GROUPS = 4

# Per-group watchdog. pytest's builtin faulthandler plugin installs
# faulthandler.dump_traceback_later per test (against the REAL stderr
# fd, immune to output capture), so a wedged test dumps every thread's
# stack before the parent's kill lands at the group deadline.
_WATCHDOG_S = float(os.environ.get("BODO_TPU_TEST_TIMEOUT", "1200"))
_DUMP_S = _WATCHDOG_S * 0.8  # dump fires comfortably before the kill


def _group_modules(modules: list[str]) -> list[list[str]]:
    """Split modules into ~_N_GROUPS similar-sized groups (round-robin
    over a size-sorted list balances compile-heavy modules), with
    _ISOLATED modules each in their own group."""
    iso, rest = [], []
    for m in modules:
        (iso if os.path.basename(m) in _ISOLATED else rest).append(m)
    groups: list[list[str]] = [[m] for m in iso]
    if rest:
        n = min(_N_GROUPS, len(rest))
        buckets: list[list[str]] = [[] for _ in range(n)]
        by_size = sorted(rest, key=lambda m: -os.path.getsize(m))
        for i, m in enumerate(by_size):
            buckets[i % n].append(m)
        groups.extend(sorted(b) for b in buckets)
    return groups


def _run_lint() -> int:
    """Shardcheck SPMD lint over the package; exit 0 only when every
    finding is suppressed inline or baselined (analysis/baseline.json)."""
    print("[lint] python -m bodo_tpu.analysis ... ", end="", flush=True)
    t1 = time.time()
    r = subprocess.run([sys.executable, "-m", "bodo_tpu.analysis"],
                       cwd=_REPO, capture_output=True, text=True,
                       timeout=300)
    tail = (r.stdout.strip().splitlines() or [""])[-1]
    print(f"{tail}  ({time.time() - t1:.0f}s)")
    if r.returncode != 0:
        sys.stdout.write(r.stdout[-4000:] + r.stderr[-2000:] + "\n")
    return r.returncode


def _run_progcheck() -> int:
    """Static program verification self-check: trace one representative
    program per family, extract collective manifests, and fail on any
    invariant violation (analysis/progcheck.py)."""
    print("[progcheck] python -m bodo_tpu.analysis --programs ... ",
          end="", flush=True)
    t1 = time.time()
    r = subprocess.run([sys.executable, "-m", "bodo_tpu.analysis",
                        "--programs"],
                       cwd=_REPO, capture_output=True, text=True,
                       timeout=300,
                       env={**os.environ, "JAX_PLATFORMS":
                            os.environ.get("JAX_PLATFORMS", "cpu")})
    tail = (r.stdout.strip().splitlines() or [""])[-1]
    print(f"{tail}  ({time.time() - t1:.0f}s)")
    if r.returncode != 0:
        sys.stdout.write(r.stdout[-4000:] + r.stderr[-2000:] + "\n")
    return r.returncode


def main(argv: list[str]) -> int:
    want_lint = "lint" in argv
    argv = [a for a in argv if a != "lint"]
    # a non-flag arg is a test module only if it points at a file; other
    # bare words (e.g. the pattern value after -k) pass through to pytest
    modules = [a for a in argv
               if not a.startswith("-") and os.path.exists(a)]
    passthrough = [a for a in argv if a not in modules]
    if want_lint and not modules and not passthrough:
        return 1 if _run_lint() else 0
    full_suite = not modules
    if not modules:
        modules = sorted(glob.glob(os.path.join(_REPO, "tests",
                                                "test_*.py")))
    groups = _group_modules(modules)
    t0 = time.time()
    failed: list[str] = []
    total = 0
    if full_suite or want_lint:
        if _run_lint() != 0:
            failed.append("lint")
    if full_suite:
        if _run_progcheck() != 0:
            failed.append("progcheck")
    for i, group in enumerate(groups):
        names = " ".join(os.path.relpath(m, _REPO) for m in group)
        label = names if len(group) == 1 else \
            f"{len(group)} modules ({names})"
        print(f"[{i + 1}/{len(groups)}] {label} ... ", end="", flush=True)
        t1 = time.time()
        try:
            r = subprocess.run(
                [sys.executable, "-m", "pytest", *group, "-q",
                 "--no-header",
                 "-o", f"faulthandler_timeout={_DUMP_S:.0f}",
                 *passthrough],
                cwd=_REPO, capture_output=True, text=True,
                # per-module program-registry teardown (tests/conftest.py):
                # grouped modules share one process, so evicting each
                # module's compiled programs keeps the live-executable
                # census bounded and the observatory's numbers per-module
                env={**os.environ, "BODO_TPU_XLA_TEARDOWN": "1"},
                timeout=_WATCHDOG_S)
        except subprocess.TimeoutExpired as e:
            dt = time.time() - t1
            print(f"TIMEOUT after {dt:.0f}s")
            failed.append(f"TIMEOUT({names})")
            # the faulthandler dump (all thread stacks at the watchdog
            # deadline) is in the captured stderr — surface it
            for s in (e.stdout, e.stderr):
                if s:
                    if isinstance(s, bytes):
                        s = s.decode("utf-8", "replace")
                    sys.stdout.write(s[-6000:] + "\n")
            continue
        dt = time.time() - t1
        tail = (r.stdout.strip().splitlines() or [""])[-1]
        print(f"{tail}  ({dt:.0f}s)")
        # count only "N passed" — warnings/failed/deselected parts of the
        # summary line must not inflate the headline test count
        for part in tail.split(","):
            words = part.strip().split()
            if len(words) >= 2 and words[0].isdigit() \
                    and words[1].startswith("passed"):
                total += int(words[0])
        if r.returncode == 5:  # no tests collected (e.g. -k filter)
            continue
        if r.returncode != 0:
            failed.append(names)
            sys.stdout.write(r.stdout[-4000:] + r.stderr[-2000:] + "\n")
    dt = time.time() - t0
    if failed:
        print(f"\nFAILED groups ({len(failed)}/{len(groups)}): "
              f"{' | '.join(failed)}  [{dt:.0f}s]")
        return 1
    print(f"\nall {len(groups)} groups green, {total} tests "
          f"[{dt:.0f}s]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
