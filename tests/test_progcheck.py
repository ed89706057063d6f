"""progcheck: jaxpr-level SPMD program verification
(bodo_tpu/analysis/progcheck.py).

The static counterpart of the runtime lockstep checker: every program
the compile observatory registers is traced and walked BEFORE first
dispatch — ordered collective manifests with axis/shape/dtype facets,
rank-invariance (no collective under axis_index-derived control flow),
a donation/aliasing audit (read-after-donation, forbidden donation on
cached-output families), and a donation-aware liveness sweep yielding
a static HBM peak estimate consumed by the memory governor and the
serve admission controller.

Seeded-mutation coverage per the acceptance bar: a collective under
rank-derived control flow and a read-after-donation must BOTH be
rejected with a typed ProgramInvariantError naming program and eqn.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

from bodo_tpu.analysis import progcheck
from bodo_tpu.analysis.progcheck import ProgramInvariantError
from bodo_tpu.config import set_config


@pytest.fixture
def pc_reset():
    progcheck.reset()
    set_config(progcheck=1, progcheck_enforce=0)
    yield
    progcheck.reset()
    set_config(progcheck=1, progcheck_enforce=0)


def _shard_mapped(body, mesh8, n_in=1):
    # mesh8 guarantees the 8-device env; build a local mesh so the
    # bodies' literal axis name "x" is independent of config.data_axis
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), axis_names=("x",))
    specs = tuple(P("x") for _ in range(n_in))
    return jax.jit(jax.shard_map(  # shardcheck: ignore[unregistered-jit]
        body, mesh=mesh, in_specs=specs, out_specs=P("x"),
        check_vma=False))


# ---------------------------------------------------------------------------
# pass 1: static lockstep — manifests + rank invariance
# ---------------------------------------------------------------------------

class TestCollectiveManifest:
    def test_manifest_order_and_facets(self, mesh8, pc_reset):
        def body(x):
            g = jax.lax.all_gather(x, "x", tiled=True)
            s = jax.lax.psum(x, "x")
            return g[: x.shape[0]] + s

        fn = _shard_mapped(body, mesh8)
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),),
            program="t:manifest", subsystem="test")
        prims = [c["prim"] for c in rep["collectives"]]
        assert prims == ["all_gather", "psum"]  # dispatch order
        for c in rep["collectives"]:
            assert "x" in c["axis"]
            assert c["shape"] is not None and c["dtype"] is not None
            assert c["eqn"]  # eqn path present
        assert rep["rank_invariant"]
        assert rep["violations"] == []
        assert progcheck.manifest_for("t:manifest") is not None

    def test_seeded_rank_divergent_collective_rejected(self, mesh8,
                                                       pc_reset):
        """THE seeded mutation: a collective under control flow whose
        predicate derives from axis_index must be rejected with a typed
        error naming program and eqn."""
        def body(x):
            r = jax.lax.axis_index("x")
            return jax.lax.cond(
                r == 0,
                lambda v: jax.lax.psum(v, "x"),
                lambda v: v,
                x)

        fn = _shard_mapped(body, mesh8)
        with pytest.raises(ProgramInvariantError) as ei:
            progcheck.check_jit(
                fn, (jnp.arange(16, dtype=jnp.float32),),
                program="t:divergent", subsystem="test", enforce=True)
        e = ei.value
        assert e.rule == "rank-divergent-collective"
        assert e.program == "t:divergent"
        assert "psum" in e.eqn_path and "eqns[" in e.eqn_path
        assert "t:divergent" in str(e)

    def test_warn_mode_records_without_raising(self, mesh8, pc_reset):
        def body(x):
            r = jax.lax.axis_index("x")
            return jax.lax.cond(
                r == 0, lambda v: jax.lax.psum(v, "x"), lambda v: v, x)

        fn = _shard_mapped(body, mesh8)
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),),
            program="t:warned", subsystem="test")  # default: warn
        assert not rep["rank_invariant"]
        assert any(v["rule"] == "rank-divergent-collective"
                   for v in rep["violations"])
        assert progcheck.stats()["rank_variant_programs"] == 1

    def test_data_dependent_cond_is_fine(self, mesh8, pc_reset):
        def body(x):
            return jax.lax.cond(
                x[0] > 0,  # data-dependent, not rank-derived
                lambda v: jax.lax.psum(v, "x"), lambda v: v, x)

        fn = _shard_mapped(body, mesh8)
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),),
            program="t:datacond", subsystem="test", enforce=True)
        assert rep["rank_invariant"]
        assert [c["prim"] for c in rep["collectives"]] == ["psum"]

    def test_declared_subset_checked(self, mesh8, pc_reset):
        def body(x):
            return jax.lax.psum(x, "x")

        fn = _shard_mapped(body, mesh8)
        # declaring a collective the program doesn't contain is a lie
        with pytest.raises(ProgramInvariantError) as ei:
            progcheck.check_jit(
                fn, (jnp.arange(16, dtype=jnp.float32),),
                program="t:declared", subsystem="test",
                declared_collectives=("all_to_all",), enforce=True)
        assert ei.value.rule == "manifest-mismatch"
        progcheck.reset()
        # incidental extras beyond the declaration are allowed (subset)
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),),
            program="t:declared2", subsystem="test",
            declared_collectives=(), enforce=True)
        assert rep["violations"] == []

    def test_manifest_registered_with_lockstep(self, mesh8, pc_reset):
        from bodo_tpu.analysis import lockstep

        def body(x):
            return jax.lax.psum(x, "x")

        fn = _shard_mapped(body, mesh8)
        progcheck.check_jit(fn, (jnp.arange(16, dtype=jnp.float32),),
                            program="t:lockstep", subsystem="test")
        m = lockstep.program_manifests().get("t:lockstep")
        assert m is not None
        assert tuple(m["collectives"]) == ("psum",)
        assert m["rank_invariant"]


# ---------------------------------------------------------------------------
# pass 2: donation / aliasing audit
# ---------------------------------------------------------------------------

class TestDonationAudit:
    def test_seeded_read_after_donation_rejected(self, pc_reset):
        """THE seeded mutation: a donated input reaching an output
        through an alias-only chain is use-after-free for any caller
        holding the buffer."""
        fn = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x, y: (x.reshape(4, 4), y + 1),
            donate_argnums=(0,))
        with pytest.raises(ProgramInvariantError) as ei:
            progcheck.check_jit(
                fn, (jnp.arange(16, dtype=jnp.float32),
                     jnp.arange(4, dtype=jnp.float32)),
                program="t:raf", subsystem="test", enforce=True)
        e = ei.value
        assert e.rule == "read-after-donation"
        assert e.program == "t:raf"
        assert "invars[0]" in e.eqn_path and "outvars" in e.eqn_path

    def test_consuming_donation_is_fine(self, pc_reset):
        fn = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x: jnp.cumsum(x) * 2, donate_argnums=(0,))
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),),
            program="t:donate_ok", subsystem="test", enforce=True)
        assert rep["donated"] == 1
        assert rep["violations"] == []

    def test_forbidden_donation_contract(self, pc_reset):
        """Join-build family: outputs are cached across dispatches, so
        donation of ANY input is a checked contract violation."""
        fn = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x: jnp.cumsum(x), donate_argnums=(0,))
        with pytest.raises(ProgramInvariantError) as ei:
            progcheck.check_jit(
                fn, (jnp.arange(16, dtype=jnp.float32),),
                program="t:lut", subsystem="test",
                forbid_donation=True, enforce=True)
        assert ei.value.rule == "forbidden-donation"
        progcheck.reset()
        # the same family without donation passes
        fn2 = jax.jit(lambda x: jnp.cumsum(x))  # shardcheck: ignore[unregistered-jit]
        rep = progcheck.check_jit(
            fn2, (jnp.arange(16, dtype=jnp.float32),),
            program="t:lut2", subsystem="test",
            forbid_donation=True, enforce=True)
        assert rep["violations"] == []

    def test_unused_donation_flagged(self, pc_reset):
        fn = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x, y: y + 1.0, donate_argnums=(0,))
        rep = progcheck.check_jit(
            fn, (jnp.arange(16, dtype=jnp.float32),
                 jnp.arange(4, dtype=jnp.float32)),
            program="t:unused", subsystem="test")
        assert any(v["rule"] == "unused-donation"
                   for v in rep["violations"])


# ---------------------------------------------------------------------------
# pass 3: static HBM peak estimation
# ---------------------------------------------------------------------------

class TestHbmEstimate:
    def test_estimate_scales_with_temporaries(self, pc_reset):
        small = jax.jit(lambda x: x + 1.0)  # shardcheck: ignore[unregistered-jit]
        big = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x: (jnp.tile(x, 64).sum() + x).sum())
        x = jnp.arange(1024, dtype=jnp.float32)
        r1 = progcheck.check_jit(small, (x,), program="t:small",
                                 subsystem="test")
        r2 = progcheck.check_jit(big, (x,), program="t:big",
                                 subsystem="test")
        assert r1["hbm_bytes"] >= x.size * 4  # input lives throughout
        assert r2["hbm_bytes"] > r1["hbm_bytes"]
        assert progcheck.hbm_estimate("t:big") == r2["hbm_bytes"]
        assert progcheck.max_hbm_estimate() == r2["hbm_bytes"]

    def test_donation_lowers_estimate(self, pc_reset):
        f_plain = jax.jit(lambda x: jnp.flip(jnp.cumsum(x)))  # shardcheck: ignore[unregistered-jit]
        f_donated = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x: jnp.flip(jnp.cumsum(x)), donate_argnums=(0,))
        x = jnp.arange(4096, dtype=jnp.float32)
        r_plain = progcheck.check_jit(f_plain, (x,), program="t:plain",
                                      subsystem="test")
        r_don = progcheck.check_jit(f_donated, (x,), program="t:don",
                                    subsystem="test")
        assert r_don["hbm_bytes"] < r_plain["hbm_bytes"]

    def test_estimate_within_2x_of_ledger_on_join(self, mesh8,
                                                  pc_reset):
        """Acceptance bar: on a real join workload the static estimate
        for the verified programs lands within 2x of the device-buffer
        ledger's observed peak for the same dispatch set."""
        import bodo_tpu.pandas_api as bpd
        from bodo_tpu.runtime import xla_observatory as obs

        n = 4096
        right = pd.DataFrame({"k": np.arange(256),
                              "w": np.arange(256.0)})
        obs.reset()
        obs.set_enabled(True)
        rt = bpd.from_pandas(right)
        # from_pandas bypasses the arrow-ingest boundary where source
        # tables enter the ledger (io/arrow_bridge.arrow_to_table) —
        # register the inputs at the same boundary so the observed peak
        # is comparable to the estimate, and hold them live like a real
        # scan would across the query
        obs.track_table(rt._plan.table, "arrow_ingest")
        keep = [rt]
        # two distinct queries with the same schema: the first builds
        # the kernels (raw dispatch), the second misses the result
        # cache but hits the kernel cache — driving the verify proxy
        for seed in (11, 12):
            rng = np.random.default_rng(seed)
            cols = {"k": rng.integers(0, 256, n)}
            for j in range(6):
                cols[f"v{j}"] = rng.normal(size=n)
            lt = bpd.from_pandas(pd.DataFrame(cols))
            obs.track_table(lt._plan.table, "arrow_ingest")
            keep.append(lt)
            lt.merge(rt, on="k").to_pandas()
        est = progcheck.max_hbm_estimate()
        peak = int(obs.ledger_stats()["peak_live_bytes"])
        assert progcheck.stats()["programs"] > 0
        assert est > 0 and peak > 0
        # static liveness over-estimates are bounded; XLA fusion means
        # the sweep can only be an upper-bound style estimate
        assert est <= 2 * peak, (est, peak)
        del keep


# ---------------------------------------------------------------------------
# registration-point coverage
# ---------------------------------------------------------------------------

class TestCoverage:
    def test_relational_family_verified_via_cache_proxy(self, mesh8,
                                                        pc_reset):
        """The KernelCache wrap covers the ~40 relational dispatchers:
        running a groupby + join twice verifies their programs."""
        import bodo_tpu.pandas_api as bpd

        n = 2048
        # distinct data per run: identical queries would hit the result
        # cache and never re-dispatch; the proxy verifies on the first
        # kernel-cache-hit dispatch after the store
        for seed in (5, 6):
            rng = np.random.default_rng(seed)
            df = pd.DataFrame({"k": rng.integers(0, 16, n),
                               "v": rng.normal(size=n)})
            b = bpd.from_pandas(df)
            b.groupby("k", as_index=False).agg(s=("v", "sum")).to_pandas()
        progs = list(progcheck.reports())
        assert any(p.startswith("relational:") for p in progs), progs
        assert progcheck.stats()["violations"] == 0
        for rep in progcheck.reports().values():
            assert rep["rank_invariant"], rep["program"]

    def test_wrap_program_proxy_transparent(self, pc_reset):
        fn = jax.jit(lambda x: x * 3)  # shardcheck: ignore[unregistered-jit]
        w = progcheck.wrap_program(fn, program="t:wrap",
                                   subsystem="test")
        out = w(jnp.arange(4, dtype=jnp.float32))
        assert out[1] == 3.0
        assert "t:wrap" in progcheck.reports()
        # attribute fall-through and double-wrap guard
        assert hasattr(w, "trace")
        assert progcheck.wrap_program(w, program="t:wrap",
                                      subsystem="test") is w
        # second call doesn't re-verify
        n0 = progcheck.stats()["programs"]
        w(jnp.arange(4, dtype=jnp.float32))
        assert progcheck.stats()["programs"] == n0

    def test_mark_checked_dedups_handles(self, pc_reset):
        fn = jax.jit(lambda x: x + 1)  # shardcheck: ignore[unregistered-jit]
        progcheck.mark_checked(1234)
        rep = progcheck.check_jit(
            fn, (jnp.arange(4, dtype=jnp.float32),),
            program="t:dedup", subsystem="test", obs_handle=1234)
        assert rep is None  # handle already verified under another name

    def test_disabled_knob_skips(self, pc_reset):
        set_config(progcheck=0)
        fn = jax.jit(lambda x: x + 1)  # shardcheck: ignore[unregistered-jit]
        assert progcheck.check_jit(
            fn, (jnp.arange(4.0),), program="t:off",
            subsystem="test") is None
        assert progcheck.stats()["programs"] == 0

    def test_untraceable_counts_skipped_never_raises(self, pc_reset):
        fn = jax.jit(lambda x: x + 1)  # shardcheck: ignore[unregistered-jit]
        # wrong arity: the static trace fails, dispatch must not break
        assert progcheck.check_jit(fn, (1, 2, 3), program="t:bad",
                                   subsystem="test") is None
        assert progcheck.stats()["skipped"] == 1


# ---------------------------------------------------------------------------
# surfacing: governor, scheduler, metrics, profile, doctor, CLI
# ---------------------------------------------------------------------------

class TestSurfacing:
    def test_preadmission_charge_reserves(self, pc_reset):
        from bodo_tpu.runtime import memory_governor as mg
        big = jax.jit(  # shardcheck: ignore[unregistered-jit]
            lambda x: jnp.tile(x, 8).sum() + x.sum())
        x = jnp.zeros(8 * 1024 * 1024, dtype=jnp.float32)  # 32MB
        progcheck.check_jit(big, (x,), program="t:chargeme",
                            subsystem="test")
        est = progcheck.hbm_estimate("t:chargeme")
        assert est and est >= 32 * 1024 * 1024
        mg.reset_governor()
        try:
            with mg.preadmission_charge("t:chargeme") as g:
                assert g is not None
                assert g.granted >= mg._MIN_GRANT
                row = mg.governor().stats()["operators"][
                    "progcheck:t:chargeme"]
                assert row["peak"] >= est
        finally:
            mg.reset_governor()

    def test_preadmission_charge_null_for_unknown_or_tiny(self,
                                                          pc_reset):
        from bodo_tpu.runtime import memory_governor as mg
        with mg.preadmission_charge("t:neverchecked") as g:
            assert g is None  # nullcontext; nothing charged
        small = jax.jit(lambda x: x + 1)  # shardcheck: ignore[unregistered-jit]
        progcheck.check_jit(small, (jnp.arange(4.0),),
                            program="t:tiny", subsystem="test")
        est = progcheck.hbm_estimate("t:tiny")
        assert est is not None and est < mg._MIN_GRANT
        with mg.preadmission_charge("t:tiny") as g:
            assert g is None  # below _MIN_GRANT: no reservation

    def test_scheduler_sheds_on_hbm_headroom(self, pc_reset):
        from bodo_tpu.runtime.scheduler import (AdmissionController,
                                                AdmissionSignals)
        ctl = AdmissionController()
        sig = AdmissionSignals(
            governor_budget_bytes=100,
            governor_granted_bytes=90,
            progcheck_hbm_peak_bytes=50)
        d = ctl.decide(sig)
        assert d.action == "shed"
        assert "progcheck_hbm_estimate" in d.reason
        # enough headroom: not shed by this rule
        sig2 = AdmissionSignals(
            governor_budget_bytes=1000,
            governor_granted_bytes=0,
            progcheck_hbm_peak_bytes=50)
        assert ctl.decide(sig2).action == "admit"

    def test_metrics_and_profile_rows(self, pc_reset):
        from bodo_tpu.utils import metrics, tracing
        fn = jax.jit(lambda x: x * 2)  # shardcheck: ignore[unregistered-jit]
        progcheck.check_jit(fn, (jnp.arange(8.0),), program="t:metrics",
                            subsystem="test")
        text = metrics.expose_text()
        assert "bodo_tpu_progcheck_programs_total 1" in text
        assert "bodo_tpu_progcheck_hbm_peak_bytes_max" in text
        assert metrics.check_exposition(text) == []
        prof = tracing.profile()
        row = prof.get("progcheck:check")
        assert row and row["count"] == 1
        assert row["total_s"] >= 0.0

    def test_doctor_triage_from_bundle(self, pc_reset, tmp_path):
        from bodo_tpu import doctor
        d = str(tmp_path / "bundle_pc")
        os.makedirs(d)
        payload = {
            "stats": {"programs": 2, "violations": 1},
            "manifests": {
                "t:ok": {"collectives": [{"prim": "psum"}],
                         "rank_invariant": True,
                         "hbm_bytes": 4096},
                "t:bad": {"collectives": [],
                          "rank_invariant": False,
                          "hbm_bytes": 0},
            },
            "violations": [{
                "rule": "rank-divergent-collective",
                "program": "t:bad",
                "eqn": "eqns[3]:cond/branches[0]/eqns[0]:psum",
                "line": "x.py:9",
                "message": "collective under rank-derived control "
                           "flow"}],
        }
        with open(os.path.join(d, "progcheck.json"), "w") as f:
            json.dump(payload, f)
        t = doctor.triage(d)
        pc = t["progcheck"]
        assert pc is not None
        assert pc["programs"] == 2
        assert pc["rank_variant"] == ["t:bad"]
        assert pc["hbm_top"][0]["program"] == "t:ok"
        rep = doctor.render(t)
        assert "progcheck" in rep
        assert "rank-divergent-collective" in rep
        assert "t:bad" in rep
        assert "eqns[3]" in rep

    def test_cli_self_check(self, pc_reset, capsys):
        assert progcheck.main([]) == 0
        out = capsys.readouterr().out
        assert "selfcheck:collective" in out
        assert "psum" in out
        assert "0 violations" in out

    def test_reset_clears_everything(self, pc_reset):
        fn = jax.jit(lambda x: x + 1)  # shardcheck: ignore[unregistered-jit]
        progcheck.check_jit(fn, (jnp.arange(4.0),), program="t:r",
                            subsystem="test")
        assert progcheck.stats()["programs"] == 1
        progcheck.reset()
        s = progcheck.stats()
        assert s["programs"] == 0 and s["manifests"] == 0
        assert progcheck.reports() == {}
        assert progcheck.max_hbm_estimate() == 0
