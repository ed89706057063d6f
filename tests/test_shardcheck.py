"""shardcheck SPMD safety analyzer: plan validator, codebase lint,
and runtime lockstep checker (bodo_tpu/analysis/).

Covers the three layers end to end: mis-typed plans raise structured
PlanInvariantErrors BEFORE execution; the ast lint catches the four
SPMD hazard classes on fixture files and runs clean over the package
itself; the lockstep checker converts collective divergence between
processes into a structured LockstepError in seconds instead of a
gang hang. Plus regression tests for the race-lint true positives
fixed in this change (pool.default_pool, adaptive.set_estimate_injector)
and the resilience-layer exclusions for analysis errors.
"""

import textwrap
import threading
import time

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.analysis import lint, lockstep, plan_validator
from bodo_tpu.analysis.lockstep import LockstepError
from bodo_tpu.analysis.plan_validator import (DIST, REP, PlanInvariantError,
                                              check_kernel_result, dist_of,
                                              validate_plan,
                                              validate_rewrite)
from bodo_tpu.config import config
from bodo_tpu.plan import logical as L
from bodo_tpu.plan.expr import BinOp, ColRef, Lit


def _src(n=16):
    return L.FromPandas(pd.DataFrame({
        "k": np.arange(n, dtype=np.int64) % 4,
        "v": np.arange(n, dtype=np.float64),
        "s": [f"s{i % 3}" for i in range(n)]}))


# ---------------------------------------------------------------------------
# layer 1: plan validator
# ---------------------------------------------------------------------------

class TestPlanValidator:
    def test_valid_plan_returns_dist(self, mesh8):
        agg = L.Aggregate(_src(), ["k"], [("v", "sum", "vs")])
        assert validate_plan(agg) == DIST
        assert validate_plan(L.Limit(agg, 3)) == REP
        assert dist_of(L.Reduce(_src(), [("v", "sum", "t")])) == REP

    def test_mutated_aggregate_keys(self, mesh8):
        agg = L.Aggregate(_src(), ["k"], [("v", "sum", "vs")])
        agg.keys = ["nope"]  # simulate a buggy planner rewrite
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(agg)
        assert ei.value.rule == "unknown-column"
        assert "nope" in str(ei.value)
        assert "Aggregate" in ei.value.path

    def test_mutated_projection_expr(self, mesh8):
        proj = L.Projection(_src(), [("out", ColRef("v"))])
        proj.exprs = [("out", ColRef("gone"))]
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(proj)
        assert ei.value.rule == "unknown-column"

    def test_filter_schema_drift(self, mesh8):
        f = L.Filter(_src(), BinOp(">", ColRef("v"), Lit(1.0)))
        f.schema = {"v": f.schema["v"]}  # filters must not project
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(f)
        assert ei.value.rule == "schema-drift"

    def test_empty_aggregate_keys(self, mesh8):
        agg = L.Aggregate(_src(), ["k"], [("v", "sum", "vs")])
        agg.keys = []
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(agg)
        assert ei.value.rule == "empty-keys"

    def test_sort_spec_mismatch(self, mesh8):
        srt = L.Sort(_src(), ["k"], [True])
        srt.ascending = [True, False]
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(srt)
        assert ei.value.rule == "sort-spec"

    def test_limit_negative(self, mesh8):
        lim = L.Limit(_src(), 5)
        lim.n = -1
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(lim)
        assert ei.value.rule == "limit-n"

    def test_join_key_dtype_mismatch(self, mesh8):
        j = L.Join(_src(), _src(), ["k"], ["k"])
        j.left_on, j.right_on = ["s"], ["k"]  # string vs int64
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(j)
        assert ei.value.rule == "join-key-dtype"

    def test_join_empty_keys(self, mesh8):
        j = L.Join(_src(), _src(), ["k"], ["k"])
        j.left_on = []
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(j)
        assert ei.value.rule == "join-keys"

    def test_union_schema_mismatch(self, mesh8):
        a, b = _src(), _src()
        u = L.Union([a, b])
        b.schema = {"other": b.schema["k"]}
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(u)
        assert ei.value.rule == "union-schema"

    def test_cycle_detection(self, mesh8):
        f = L.Filter(_src(), BinOp(">", ColRef("v"), Lit(1.0)))
        f.children = [f]  # corrupt graph must not hang the walk
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(f)
        assert ei.value.rule == "cycle"

    def test_shared_subtree_validates_once(self, mesh8):
        plan_validator.reset_stats()
        src = _src()
        j = L.Join(src, src, ["k"], ["k"])  # diamond DAG, not a cycle
        assert validate_plan(j) == DIST
        assert plan_validator.stats()["nodes"] == 2  # src memoized

    def test_kernel_result_dist_check(self):
        plan_validator.reset_stats()
        check_kernel_result("union", "REP")        # declared REP: ok
        check_kernel_result("undeclared_op", "1D")  # not declared: ok
        with pytest.raises(PlanInvariantError) as ei:
            check_kernel_result("union", "1D")
        assert ei.value.rule == "kernel-result-dist"
        assert "RUNTIME_RESULT_DIST" in str(ei.value)
        assert plan_validator.stats()["kernel_checks"] == 3

    def test_validate_rewrite_schema_and_dist(self, mesh8):
        src = _src()
        agg = L.Aggregate(src, ["k"], [("v", "sum", "vs")])
        other = L.Aggregate(src, ["k"], [("v", "mean", "vm")])
        with pytest.raises(PlanInvariantError) as ei:
            validate_rewrite(agg, other)
        assert ei.value.rule == "rewrite-schema"
        # widening a replicated subtree to a possibly-sharded one:
        # Limit(src, n) is REP with src's schema; src itself is DIST
        lim = L.Limit(src, 4)
        with pytest.raises(PlanInvariantError) as ei:
            validate_rewrite(lim, src)
        assert ei.value.rule == "rewrite-dist"
        validate_rewrite(agg, agg)  # identity rewrite always passes

    def test_execute_validates_by_default(self, mesh8):
        from bodo_tpu.plan.physical import execute
        assert config.plan_validate  # on by default
        plan_validator.reset_stats()
        out = execute(L.Aggregate(_src(), ["k"], [("v", "sum", "vs")]))
        assert out.nrows == 4
        assert plan_validator.stats()["plans"] >= 1

    def test_execute_rejects_broken_plan_before_running(self, mesh8):
        from bodo_tpu.plan.physical import execute
        agg = L.Aggregate(_src(), ["k"], [("v", "sum", "vs")])
        agg.keys = ["nope"]
        with pytest.raises(PlanInvariantError):
            execute(agg, optimize_first=False)

    def test_execute_validation_togglable(self, mesh8, monkeypatch):
        from bodo_tpu.plan.physical import execute
        monkeypatch.setattr(config, "plan_validate", False)
        plan_validator.reset_stats()
        execute(L.Limit(_src(), 2))
        assert plan_validator.stats()["plans"] == 0

    def test_shuffle_rep_guard(self, mesh8):
        from bodo_tpu import relational
        from bodo_tpu.table.table import Table
        t = Table.from_pandas(pd.DataFrame({"k": np.arange(8)}))
        assert t.distribution == "REP"
        with pytest.raises(PlanInvariantError) as ei:
            relational.shuffle_by_key(t, ["k"])
        assert ei.value.rule == "shuffle-needs-1d"


class TestValidatorSweep:
    def test_distribution_sweep_validates_clean(self, mesh8):
        """Property: every plan produced by a representative
        groupby+join+sort pipeline across ALL distribution modes
        type-checks with zero violations (check_func runs each mode
        through physical.execute, which validates by default)."""
        from tests.utils import check_func
        plan_validator.reset_stats()

        left = pd.DataFrame({"k": [0, 1, 2, 3] * 6,
                             "v": np.arange(24, dtype=np.float64)})
        right = pd.DataFrame({"k": [0, 1, 2, 3],
                              "w": [10.0, 20.0, 30.0, 40.0]})

        def fn(a, b):
            m = a.merge(b, on="k")
            g = m.groupby("k", as_index=False).agg({"v": "sum",
                                                    "w": "max"})
            return g.sort_values("k")

        check_func(fn, [left, right])
        st = plan_validator.stats()
        assert st["plans"] >= 3  # at least one plan per mode
        assert st["violations"] == 0

    def test_tpch_plans_validate(self, mesh8):
        """Every supported TPC-H query's plan (raw and optimized)
        passes validation — the validator never false-positives on
        real planner output."""
        from bodo_tpu.plan.optimizer import optimize
        from bodo_tpu.sql import BodoSQLContext
        from bodo_tpu.workloads.tpch import QUERIES, UNSUPPORTED, gen_tpch
        ctx = BodoSQLContext(gen_tpch(n_orders=120, seed=7))
        plan_validator.reset_stats()
        checked = 0
        for qnum in sorted(QUERIES):
            if qnum in UNSUPPORTED:
                continue
            plan = ctx.sql(QUERIES[qnum])._plan
            validate_plan(plan)
            validate_plan(optimize(plan))
            checked += 1
        assert checked >= 15
        assert plan_validator.stats()["violations"] == 0


class TestViewScanValidator:
    """ViewScan leaf rules: signed transitive sources, schema/dist
    consistency with the parent view's materialization."""

    @pytest.fixture
    def view(self, mesh8):
        from bodo_tpu.runtime import views
        views.create_view("pv_daily",
                          L.Aggregate(_src(), ["k"],
                                      [("v", "sum", "vs")]))
        yield views
        for name in list(views.list_views()):
            if name.startswith("pv_"):
                views.drop_view(name)

    def test_valid_view_scan(self, view):
        scan = view.scan_node("pv_daily")
        assert validate_plan(scan) == DIST
        # composes like any leaf
        assert validate_plan(L.Limit(scan, 3)) == REP

    def test_unknown_view(self, view):
        bad = L.ViewScan("pv_nope", {"k": None})
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(bad)
        assert ei.value.rule == "unknown-view"
        assert "pv_nope" in str(ei.value)

    def test_non_leaf_rejected(self, view):
        scan = view.scan_node("pv_daily")
        scan.children = [view.scan_node("pv_daily")]
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(scan)
        assert ei.value.rule == "arity"

    def test_schema_drift_after_redefine(self, view):
        """A scan minted before the view was redefined carries a stale
        schema: downstream column refs were checked against it."""
        scan = view.scan_node("pv_daily")
        view.drop_view("pv_daily")
        view.create_view("pv_daily",
                         L.Aggregate(_src(), ["k"],
                                     [("v", "mean", "vm")]))
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(scan)
        assert ei.value.rule == "view-schema-drift"

    def test_unsigned_sources_rejected(self, view, monkeypatch):
        scan = view.scan_node("pv_daily")
        monkeypatch.setattr(view, "base_sources", lambda name: None)
        with pytest.raises(PlanInvariantError) as ei:
            validate_plan(scan)
        assert ei.value.rule == "unsigned-view-sources"

    def test_materialization_dist_consistency(self, view):
        """A sharded materialization under an abstractly-REP defining
        root is the fusion-input-dist failure class at the view edge."""
        from types import SimpleNamespace
        view.create_view("pv_rep", L.Limit(_src(), 4))  # root is REP
        scan = view.scan_node("pv_rep")
        assert validate_plan(scan) == DIST  # no materialization yet
        v = view._get("pv_rep")
        v.root._cached = SimpleNamespace(distribution="1D")
        try:
            with pytest.raises(PlanInvariantError) as ei:
                validate_plan(scan)
            assert ei.value.rule == "view-dist"
            # a REP materialization is consistent
            v.root._cached = SimpleNamespace(distribution="REP")
            assert validate_plan(scan) == DIST
        finally:
            v.root._cached = None


# ---------------------------------------------------------------------------
# layer 2: codebase lint
# ---------------------------------------------------------------------------

def _lint_src(tmp_path, source: str):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(source))
    return lint.lint_file(str(p), root=str(tmp_path))


class TestLint:
    def test_rank_divergent_collective(self, tmp_path):
        got = _lint_src(tmp_path, """
            def f(x, rank):
                if rank == 0:
                    return psum(x, "d")
                return x
        """)
        assert [f.rule for f in got] == ["rank-divergent-collective"]
        assert got[0].func == "f"

    def test_rank_divergent_via_process_index(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax
            def f(x):
                if jax.process_index() == 0:
                    return all_gather_rows(x)
                return x
        """)
        assert [f.rule for f in got] == ["rank-divergent-collective"]

    def test_collective_outside_divergence_ok(self, tmp_path):
        got = _lint_src(tmp_path, """
            def f(x, n):
                if n > 3:          # data-dependent, not rank-dependent
                    return psum(x, "d")
                return x
        """)
        assert got == []

    def test_trace_time_side_effect(self, tmp_path):
        got = _lint_src(tmp_path, """
            def body(x):
                print("tracing")
                return psum(x, "ax")
        """)
        assert [f.rule for f in got] == ["trace-time-side-effect"]

    def test_smap_body_side_effect(self, tmp_path):
        got = _lint_src(tmp_path, """
            def body(x):
                open("/tmp/marker", "w")
                return x
            out = smap(body, None, None)
        """)
        assert [f.rule for f in got] == ["trace-time-side-effect"]

    def test_trace_safe_time_ok(self, tmp_path):
        got = _lint_src(tmp_path, """
            import time
            def body(x):
                t = time.monotonic()   # pure read, trace-safe
                return psum(x, "ax")
        """)
        assert got == []

    def test_retry_non_idempotent(self, tmp_path):
        got = _lint_src(tmp_path, """
            def save(f, data):
                retry_call(lambda: f.write(data), label="save")
        """)
        assert [f.rule for f in got] == ["retry-non-idempotent"]

    def test_retry_idempotent_ok(self, tmp_path):
        got = _lint_src(tmp_path, """
            def load(path):
                return retry_call(lambda: read_file(path), label="load")
        """)
        assert got == []

    def test_unlocked_shared_state(self, tmp_path):
        got = _lint_src(tmp_path, """
            import threading
            _lock = threading.Lock()
            _cache = {}

            def put(k, v):
                _cache[k] = v

            def put_locked(k, v):
                with _lock:
                    _cache[k] = v

            def rebind():
                global _cache
                _cache = {}
        """)
        assert sorted((f.rule, f.func) for f in got) == [
            ("unlocked-shared-state", "put"),
            ("unlocked-shared-state", "rebind")]

    def test_lockless_module_out_of_scope(self, tmp_path):
        # no locks defined -> module is single-threaded by design
        got = _lint_src(tmp_path, """
            _cache = {}
            def put(k, v):
                _cache[k] = v
        """)
        assert got == []

    def test_suppression_comment(self, tmp_path):
        got = _lint_src(tmp_path, """
            import threading
            _lock = threading.Lock()
            _cache = {}
            def put(k, v):
                # shardcheck: ignore[unlocked-shared-state]
                _cache[k] = v
        """)
        assert got == []

    def test_suppression_wrong_rule_does_not_apply(self, tmp_path):
        got = _lint_src(tmp_path, """
            import threading
            _lock = threading.Lock()
            _cache = {}
            def put(k, v):
                # shardcheck: ignore[retry-non-idempotent]
                _cache[k] = v
        """)
        assert [f.rule for f in got] == ["unlocked-shared-state"]

    def test_unregistered_jit_direct_call(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax
            def build(spec):
                return jax.jit(lambda x: x + 1)
        """)
        assert [f.rule for f in got] == ["unregistered-jit"]
        assert got[0].func == "build"

    def test_unregistered_pallas_call(self, tmp_path):
        got = _lint_src(tmp_path, """
            def kernel(x):
                return pl.pallas_call(body, grid=(4,))(x)
        """)
        assert [f.rule for f in got] == ["unregistered-jit"]

    def test_unregistered_jit_decorator(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax
            from functools import partial

            @jax.jit
            def f(x):
                return x

            @partial(jax.jit, static_argnames=("k",))
            def g(x, k):
                return x
        """)
        assert sorted(f.rule for f in got) == ["unregistered-jit"] * 2

    def test_jit_registered_via_cache_store_ok(self, tmp_path):
        # a function that stores its compiled program into a kernel
        # cache (name contains 'cache'/'program') IS registered — the
        # store reports to the program registry
        got = _lint_src(tmp_path, """
            import jax
            _programs = {}
            def build(key):
                fn = jax.jit(lambda x: x)
                _programs[key] = fn
                return fn
        """)
        assert got == []

    def test_jit_registered_via_cached_builder_ok(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax
            from bodo_tpu.utils.kernel_cache import cached_builder

            @cached_builder("streaming")
            def build(key):
                return jax.jit(lambda x: x)
        """)
        assert got == []

    def test_unregistered_jit_suppression(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax
            def build(spec):
                # shardcheck: ignore[unregistered-jit]
                return jax.jit(lambda x: x + 1)
        """)
        assert got == []

    def test_rank_divergent_rng_seed(self, tmp_path):
        # seeding an RNG from rank identity silently diverges
        # replicated state across the gang
        got = _lint_src(tmp_path, """
            import os
            import numpy as np
            import jax

            def f(rank):
                rng = np.random.default_rng(rank)
                key = jax.random.PRNGKey(jax.process_index())
                np.random.seed(int(os.environ["BODO_TPU_PROC_ID"]))
                return rng, key
        """)
        assert sorted(f.rule for f in got) == \
            ["rank-divergent-rng-seed"] * 3
        assert all(f.func == "f" for f in got)

    def test_rank_invariant_seed_ok(self, tmp_path):
        # the sanctioned pattern: rank-invariant seed, explicit fold
        got = _lint_src(tmp_path, """
            import numpy as np
            import jax

            def f(seed, rank):
                rng = np.random.default_rng(seed)
                key = jax.random.fold_in(jax.random.PRNGKey(seed), rank)
                return rng, key
        """)
        assert got == []

    def test_divergent_host_sync(self, tmp_path):
        got = _lint_src(tmp_path, """
            import jax

            def f(x, rank):
                if rank == 0:
                    return jax.device_get(x)
                x.block_until_ready()
                return None
        """)
        assert [f.rule for f in got] == ["divergent-host-sync"]
        assert got[0].func == "f"

    def test_host_sync_outside_divergence_ok(self, tmp_path):
        # data-dependent control flow is every rank's same decision
        got = _lint_src(tmp_path, """
            import jax

            def f(x, n):
                if n > 0:
                    return jax.device_get(x)
                return None
        """)
        assert got == []

    def _lint_streaming_src(self, tmp_path, source: str):
        d = tmp_path / "plan"
        d.mkdir(exist_ok=True)
        p = d / "streaming_fixture.py"
        p.write_text(textwrap.dedent(source))
        return lint.lint_file(str(p), root=str(tmp_path))

    def test_stream_sync_unannotated(self, tmp_path):
        got = self._lint_streaming_src(tmp_path, """
            import jax

            def push(self, batch):
                n = int(jax.device_get(batch))
                n += 1
                n += 2
                batch.block_until_ready()
                return n
        """)
        assert [f.rule for f in got] == ["stream-sync-unannotated"] * 2
        assert {f.func for f in got} == {"push"}

    def test_stream_sync_annotated_ok(self, tmp_path):
        # annotation on the call line, on an adjacent line, and after
        # the closing paren of a multi-line call all count
        got = self._lint_streaming_src(tmp_path, """
            import jax

            def finish(self):
                n = int(jax.device_get(self._n))  # dispatch-boundary
                m = int(jax.device_get(
                    self._m))  # dispatch-boundary
                return n + m
        """)
        assert got == []

    def test_stream_sync_rule_scoped_to_streaming_modules(self, tmp_path):
        # the same unannotated sync outside plan/streaming*.py is fine
        got = _lint_src(tmp_path, """
            import jax

            def push(self, batch):
                return int(jax.device_get(batch))
        """)
        assert got == []

    def test_stream_sync_rule_covers_fusion_join(self, tmp_path):
        # plan/fusion_join.py is whole-module in scope: every
        # unannotated sync is a finding regardless of function name
        d = tmp_path / "plan"
        d.mkdir()
        p = d / "fusion_join.py"
        p.write_text(textwrap.dedent("""
            import jax

            def anything_at_all(x):
                return int(jax.device_get(x))
        """))
        got = lint.lint_file(str(p), root=str(tmp_path))
        assert [f.rule for f in got] == ["stream-sync-unannotated"]

    def test_stream_sync_rule_covers_views_maintenance(self, tmp_path):
        # runtime/views.py is scoped: only step/maintenance/refresh/
        # materialize-named bodies are in scope; other functions are not
        d = tmp_path / "runtime"
        d.mkdir()
        p = d / "views.py"
        p.write_text(textwrap.dedent("""
            import jax

            def maintenance_tick(sched):
                return int(jax.device_get(sched))

            def _materialize(v):
                v.block_until_ready()
                return v

            def unrelated_helper(x):
                return int(jax.device_get(x))
        """))
        got = lint.lint_file(str(p), root=str(tmp_path))
        assert sorted((f.rule, f.func) for f in got) == [
            ("stream-sync-unannotated", "_materialize"),
            ("stream-sync-unannotated", "maintenance_tick")]

    def test_baseline_roundtrip(self, tmp_path, monkeypatch, capsys):
        mod = tmp_path / "legacy.py"
        mod.write_text(textwrap.dedent("""
            def f(x, rank):
                if rank == 1:
                    return dist_sum(x)
                return x
        """))
        monkeypatch.chdir(tmp_path)
        base = str(tmp_path / "base.json")
        # fresh finding -> exit 1
        assert lint.main(["legacy.py", "--baseline", base]) == 1
        # grandfather it, then the same finding is baselined -> exit 0
        assert lint.main(["legacy.py", "--baseline", base,
                          "--write-baseline"]) == 0
        assert lint.main(["legacy.py", "--baseline", base]) == 0
        # baseline matching is line-number-insensitive: shifting the
        # finding down must not resurrect it
        mod.write_text("# a new leading comment\n" + mod.read_text())
        assert lint.main(["legacy.py", "--baseline", base]) == 0
        # --no-baseline reports it again
        assert lint.main(["legacy.py", "--baseline", base,
                          "--no-baseline"]) == 1
        capsys.readouterr()

    def test_package_lints_clean(self, capsys):
        """The CI gate: the bodo_tpu package itself has no findings
        beyond inline suppressions + the checked-in baseline."""
        assert lint.main([]) == 0
        out = capsys.readouterr().out
        assert "0 new" in out

    def test_dead_baseline_entry_fails_and_prunes(self, tmp_path,
                                                  capsys):
        """A baseline entry no current finding matches fails the
        full-package gate; --prune-baseline removes it and the gate
        goes green again."""
        import json as _json
        base = str(tmp_path / "base.json")
        with open(base, "w") as fh:
            _json.dump([{"rule": "rank-divergent-collective",
                         "file": "bodo_tpu/no_such_module.py",
                         "func": "f", "text": "psum(x)"}], fh)
        assert lint.main(["--baseline", base]) == 1
        out = capsys.readouterr().out
        assert "DEAD baseline entry" in out
        assert "1 dead baseline entries" in out
        assert lint.main(["--baseline", base,
                          "--prune-baseline"]) == 0
        assert "pruned 1 dead" in capsys.readouterr().out
        assert lint.main(["--baseline", base]) == 0
        capsys.readouterr()

    def test_prune_baseline_requires_full_package_run(self, tmp_path,
                                                      capsys):
        """Partial-path prune would read unscanned files' entries as
        falsely dead and delete them — refused."""
        mod = tmp_path / "ok.py"
        mod.write_text("x = 1\n")
        base = str(tmp_path / "base.json")
        assert lint.main([str(mod), "--baseline", base,
                          "--prune-baseline"]) == 1
        assert "full-package" in capsys.readouterr().out

    def test_dead_gate_skipped_for_partial_paths(self, tmp_path,
                                                 capsys):
        """Entries for unscanned files must not read as dead on a
        partial-path run."""
        import json as _json
        mod = tmp_path / "ok.py"
        mod.write_text("x = 1\n")
        base = str(tmp_path / "base.json")
        with open(base, "w") as fh:
            _json.dump([{"rule": "rank-divergent-collective",
                         "file": "bodo_tpu/other.py",
                         "func": "f", "text": "psum(x)"}], fh)
        assert lint.main([str(mod), "--baseline", base]) == 0
        assert "DEAD" not in capsys.readouterr().out


# ---------------------------------------------------------------------------
# layer 3: runtime lockstep checker
# ---------------------------------------------------------------------------

@pytest.fixture
def lockstep_reset():
    lockstep.reset()
    yield
    lockstep.reset()


class TestLockstep:
    def test_divergence_detected_fast(self, tmp_path, monkeypatch,
                                      lockstep_reset):
        """Two ranks issuing DIFFERENT collectives at the same sequence
        number both raise a structured LockstepError naming ranks and
        call sites — in well under 5 seconds."""
        monkeypatch.setattr(config, "lockstep_timeout_s", 5.0)
        c0 = lockstep.Checker(str(tmp_path), 0, 2)
        c1 = lockstep.Checker(str(tmp_path), 1, 2)
        errs = {}

        def run(c, op, site):
            try:
                c.check(op, site)
            except LockstepError as e:
                errs[c.rank] = e

        t0 = time.monotonic()
        th = threading.Thread(
            target=run, args=(c0, "groupby_agg", "query.py:10"))
        th.start()
        run(c1, "sort_table", "query.py:20")
        th.join()
        dt = time.monotonic() - t0
        assert dt < 5.0, f"divergence detection took {dt:.1f}s"
        assert sorted(errs) == [0, 1]  # both sides notice
        e = errs[1]
        assert e.seq == 1 and e.peer == 0
        assert e.site == "sort_table@query.py:20"
        assert e.peer_site == "groupby_agg@query.py:10"
        msg = str(e)
        assert "rank 1" in msg and "rank 0" in msg
        assert "divergence" in msg
        assert lockstep.stats()["mismatches"] >= 1
        c0.close(), c1.close()

    def test_lagging_rank_timeout(self, tmp_path, monkeypatch,
                                  lockstep_reset):
        """A peer that never reaches the dispatch is reported with its
        last-seen dispatch after lockstep_timeout_s — not the 180s gang
        timeout."""
        monkeypatch.setattr(config, "lockstep_timeout_s", 0.6)
        c0 = lockstep.Checker(str(tmp_path), 0, 2)
        t0 = time.monotonic()
        with pytest.raises(LockstepError) as ei:
            c0.check("join_tables", "query.py:33")
        dt = time.monotonic() - t0
        assert dt < 5.0
        e = ei.value
        assert e.peer == 1 and e.seq == 1
        assert "did not reach" in str(e)
        assert "no collective dispatched yet" in str(e)
        assert lockstep.stats()["timeouts"] == 1
        c0.close()

    def test_matching_streams_pass(self, tmp_path, monkeypatch,
                                   lockstep_reset):
        monkeypatch.setattr(config, "lockstep_timeout_s", 5.0)
        c0 = lockstep.Checker(str(tmp_path), 0, 2)
        c1 = lockstep.Checker(str(tmp_path), 1, 2)
        for seq in range(3):
            th = threading.Thread(
                target=c0.check, args=("groupby_agg", "q.py:1"))
            th.start()
            c1.check("groupby_agg", "q.py:1")
            th.join()
        assert lockstep.stats()["mismatches"] == 0
        assert lockstep.stats()["collectives"] == 6
        c0.close(), c1.close()

    def test_single_process_records_and_profiles(self, mesh8,
                                                 monkeypatch,
                                                 lockstep_reset):
        """Single-process mode: dispatches are fingerprinted and counted with no peers to poll,
        through the REAL relational dispatch path, and surface as the
        profile's lockstep:check row."""
        from bodo_tpu import relational
        from bodo_tpu.plan import physical
        from bodo_tpu.table.table import Table
        from bodo_tpu.utils import tracing
        monkeypatch.setattr(config, "lockstep", True)
        monkeypatch.setattr(config, "lockstep_dir", "")
        monkeypatch.setattr(config, "shard_min_rows", 0)
        monkeypatch.delenv("BODO_TPU_NPROCS", raising=False)
        t = physical._maybe_shard(Table.from_pandas(pd.DataFrame({
            "k": np.arange(64, dtype=np.int64) % 8,
            "v": np.arange(64, dtype=np.float64)})))
        assert t.distribution == "1D"
        relational.shuffle_by_key(t, ["k"])
        relational.sort_table(t, ["k"])
        st = lockstep.stats()
        assert st["collectives"] >= 2
        assert st["mismatches"] == 0 and st["timeouts"] == 0
        prof = tracing.profile()
        assert prof["lockstep:check"]["count"] == st["collectives"]

    def test_disabled_is_noop(self, lockstep_reset):
        assert not config.lockstep  # off by default
        lockstep.pre_collective("groupby_agg")
        assert lockstep.stats()["collectives"] == 0


@pytest.mark.slow_spawn
def test_lockstep_divergence_across_real_processes(monkeypatch):
    """Acceptance: a rank that takes a different control-flow path into
    a collective dies with a structured LockstepError (named rank + call
    site) and the gang is torn down — instead of both ranks wedging in
    the collective until the 180s gang timeout."""
    from bodo_tpu.spawn import SpawnError, run_spmd
    monkeypatch.setenv("BODO_TPU_LOCKSTEP", "1")
    monkeypatch.setenv("BODO_TPU_LOCKSTEP_TIMEOUT", "8")

    def worker(rank):
        import numpy as np
        import pandas as pd

        import bodo_tpu
        from bodo_tpu import relational
        from bodo_tpu.config import set_config
        from bodo_tpu.plan import physical
        from bodo_tpu.table.table import Table
        bodo_tpu.set_mesh(bodo_tpu.make_mesh())
        set_config(shard_min_rows=0)
        t = physical._maybe_shard(Table.from_pandas(pd.DataFrame({
            "k": np.arange(64, dtype=np.int64) % 8,
            "v": np.arange(64, dtype=np.float64)})))
        if rank == 0:
            # divergent path: rank 0 sorts while rank 1 shuffles — the
            # lockstep check fires BEFORE either kernel dispatches, so
            # neither rank ever enters a real collective
            relational.sort_table(t, ["k"])
        else:
            relational.shuffle_by_key(t, ["k"])
        return rank

    t0 = time.monotonic()
    with pytest.raises(SpawnError) as ei:
        run_spmd(worker, 2, timeout=120)
    dt = time.monotonic() - t0
    assert dt < 90.0, f"divergence surfaced after {dt:.1f}s"
    e = ei.value
    assert e.reason == "worker death"  # structured death, not a hang
    s = str(e)
    assert "LockstepError" in s
    assert "divergence" in s
    assert not e.transient  # a correctness bug is never gang-retried


# ---------------------------------------------------------------------------
# satellite regressions: race-lint fixes + resilience exclusions
# ---------------------------------------------------------------------------

class TestRaceFixes:
    def test_threaded_runtime_modules_race_clean(self):
        """The race-lint triage result for the worker-thread modules,
        pinned: runtime/io_pool.py and runtime/stats_store.py keep all
        module-global mutation under their locks, and runtime/pool.py
        does after the default_pool fix. A new unlocked write in any of
        them fails here (and the CI lint gate) with the rule name."""
        import bodo_tpu.runtime as rt
        root = rt.__path__[0]
        import os
        findings = lint.lint_paths(
            [os.path.join(root, f) for f in
             ("io_pool.py", "stats_store.py", "pool.py")],
            root=os.path.dirname(os.path.dirname(root)))
        races = [f for f in findings if f.rule == "unlocked-shared-state"]
        assert races == [], "\n".join(f.render() for f in races)

    def test_default_pool_single_instance_under_threads(self, monkeypatch):
        """runtime/pool.default_pool: two racing first calls must not
        each build (and leak) a native pool + spill dir — the
        unlocked-shared-state true positive fixed by double-checked
        locking."""
        from bodo_tpu.runtime import pool
        built = []

        class _SlowDummyPool:
            def __init__(self):
                built.append(self)
                time.sleep(0.05)  # widen the init race window

        monkeypatch.setattr(pool, "HostBufferPool", _SlowDummyPool)
        monkeypatch.setattr(pool, "_default", None)
        barrier = threading.Barrier(8)
        got = []

        def grab():
            barrier.wait()
            got.append(pool.default_pool())

        threads = [threading.Thread(target=grab) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(built) == 1, f"{len(built)} pools built under race"
        assert len({id(p) for p in got}) == 1

    def test_estimate_injector_locked_set(self):
        """plan/adaptive.set_estimate_injector now follows the module's
        lock discipline; concurrent install/uninstall against counter
        traffic must neither deadlock nor corrupt the final state."""
        from bodo_tpu.plan import adaptive
        stop = threading.Event()

        def hammer_counts():
            while not stop.is_set():
                adaptive.count("shardcheck_test")

        def hammer_injector():
            for _ in range(200):
                adaptive.set_estimate_injector(lambda node: 7.0)
                adaptive.set_estimate_injector(None)

        counters = threading.Thread(target=hammer_counts)
        counters.start()
        try:
            ths = [threading.Thread(target=hammer_injector)
                   for _ in range(4)]
            for t in ths:
                t.start()
            for t in ths:
                t.join(timeout=30)
                assert not t.is_alive(), "set_estimate_injector deadlock"
        finally:
            stop.set()
            counters.join()
            adaptive.set_estimate_injector(None)
        assert adaptive._injector is None


class TestResilienceExclusions:
    def test_lockstep_error_never_transient(self):
        from bodo_tpu.runtime import resilience
        e = LockstepError(
            "SPMD lockstep divergence at dispatch #3: rank 1 did not "
            "reach dispatch #3 within 1.0s; its last dispatch was "
            "nothing (no collective dispatched yet)")
        assert resilience.classify_transient(e) is None
        assert not resilience.is_degradable(e)

    def test_plan_invariant_error_never_transient(self):
        from bodo_tpu.runtime import resilience
        e = PlanInvariantError("collective typing violation",
                               rule="kernel-result-dist")
        assert resilience.classify_transient(e) is None
        assert not resilience.is_degradable(e)

    def test_exclusion_is_by_class_not_message(self):
        """The same 'collective' wording in a plain RuntimeError STILL
        degrades — proving the analysis errors are excluded by class
        name, not by a message pattern that could drift."""
        from bodo_tpu.runtime import resilience
        assert resilience.is_degradable(
            RuntimeError("INTERNAL: collective permute failed"))
        assert not resilience.is_degradable(
            LockstepError("INTERNAL: collective permute failed"))
