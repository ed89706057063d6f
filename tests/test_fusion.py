"""Whole-stage fusion (plan/fusion.py).

The contract under test: grouping adjacent Filter/Projection/Aggregate
stages into one jitted program must be INVISIBLE except for speed —
bit-equal chain results across the distribution sweep, oracle-equal
aggregates, correct interplay with AQE, graceful degradation under
chaos faults, per-(schema, dictionary) program-cache keys, lockstep
manifests for the composite dispatch, and the Pallas dense-accumulate
kernel actually traced into fused bodies.
"""

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.config import config, set_config
from tests.utils import MODES, check_func, check_sql


@pytest.fixture(autouse=True)
def _fresh_fusion():
    from bodo_tpu.plan import fusion, physical
    physical._result_cache.clear()
    fusion.reset_stats()
    fusion.clear_programs()
    yield
    set_config(faults="")


def _chain_df(n=5000, seed=0):
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": r.integers(0, 40, n),
        "cat": r.choice(["aa", "bb", "cc", "dd"], n),
        "v": r.normal(size=n),
        "w": r.integers(0, 100, n).astype(np.int64),
    })


# ---------------------------------------------------------------------------
# equivalence: fused results across the distribution sweep
# ---------------------------------------------------------------------------


def test_chain_sweep_vs_pandas(mesh8):
    def fn(df):
        df = df[df["w"] % 3 != 0]
        df = df.assign(u=df["v"] * 2.0 + df["w"])
        return df[df["u"] > 0.0]

    check_func(fn, [_chain_df()])


def test_fused_agg_sweep_vs_pandas(mesh8):
    def fn(df):
        df = df[df["w"] < 80]
        df = df.assign(u=df["v"] + 1.0)
        return df.groupby("k", as_index=False).agg(
            s=("u", "sum"), c=("w", "count"), m=("v", "mean"))

    check_func(fn, [_chain_df()], rtol=1e-7)


def test_sql_q6_style_sweep(mesh8):
    lineitem = pd.DataFrame({
        "l_quantity": np.random.default_rng(1).integers(1, 50, 3000),
        "l_extendedprice": np.random.default_rng(2).uniform(
            100.0, 100000.0, 3000),
        "l_discount": np.random.default_rng(3).choice(
            [0.02, 0.05, 0.06, 0.07, 0.09], 3000),
    })
    check_sql(
        "select sum(l_extendedprice * l_discount) as revenue "
        "from lineitem "
        "where l_discount between 0.05 and 0.07 and l_quantity < 24",
        {"lineitem": lineitem}, rtol=1e-6)


def test_chain_bit_identical_fused_vs_unfused(mesh8):
    """Elementwise chains must be BIT-equal fused vs unfused: projection
    math is per-row, so evaluating before the (single) compaction
    instead of after each filter cannot change any value."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, physical

    def run():
        physical._result_cache.clear()
        bdf = bd.from_pandas(_chain_df())
        bdf = bdf[bdf["w"] % 3 != 0]
        bdf = bdf.assign(u=bdf["v"] * 2.0 + bdf["w"])
        return bdf[bdf["u"] > 0.5].to_pandas()

    fused = run()
    assert fusion.stats()["groups_executed"] > 0
    old = config.fusion
    set_config(fusion=False)
    try:
        plain = run()
    finally:
        set_config(fusion=old)
    pd.testing.assert_frame_equal(fused, plain)


def test_engagement_and_stats(mesh8):
    """The taxi-shaped hot path must actually fuse: groups planned and
    executed, programs compiled once and then cache-hit."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, physical

    def run():
        physical._result_cache.clear()
        bdf = bd.from_pandas(_chain_df())
        bdf = bdf[bdf["w"] < 90]
        bdf = bdf.assign(u=bdf["v"] + 1.0)
        return bdf.groupby("k", as_index=False).agg(
            s=("u", "sum")).to_pandas()

    run()
    s1 = fusion.stats()
    assert s1["groups_planned"] >= 1
    assert s1["groups_executed"] >= 1
    assert s1["compiles"] >= 1
    assert s1["fallbacks"] == 0
    run()
    s2 = fusion.stats()
    assert s2["groups_executed"] > s1["groups_executed"]
    assert s2["compiles"] == s1["compiles"]  # second run is a cache hit
    assert s2["hits"] > s1["hits"]


# ---------------------------------------------------------------------------
# group formation rules
# ---------------------------------------------------------------------------


def test_group_formation_and_shared_interior(mesh8):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion
    from bodo_tpu.plan.optimizer import optimize

    bdf = bd.from_pandas(_chain_df())
    filt = bdf[bdf["w"] % 2 == 0]
    out = filt.assign(u=filt["v"] + 1.0).groupby(
        "k", as_index=False).agg(s=("u", "sum"))
    root = optimize(out._plan)
    groups = fusion.plan_fusion_groups(root)
    assert len(groups) == 1
    assert groups[0].member_ops()[0] == "Aggregate"
    assert len(groups[0].members) >= 3

    # a shared interior (two consumers of the same filter) must never be
    # claimed into a group — its result is reused via the node cache
    a = filt.assign(u=filt["v"] + 1.0)
    b = filt.assign(t=filt["v"] - 1.0)
    joined = a.merge(b, on="k")
    shared_root = optimize(joined._plan)
    for g in fusion.plan_fusion_groups(shared_root):
        assert all(m is not filt._plan for m in g.members)


def test_fusion_config_toggle(mesh8):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion
    from bodo_tpu.plan.optimizer import optimize

    bdf = bd.from_pandas(_chain_df())
    f = bdf[bdf["w"] % 2 == 0]
    root = optimize(f.assign(u=f["v"] + 1.0)._plan)
    assert fusion.plan_fusion_groups(root)
    old = config.fusion
    set_config(fusion=False)
    try:
        assert fusion.plan_fusion_groups(root) == []
        # stale annotations from the fused pass must have been cleared
        assert all(getattr(n, "_fusion_group", None) is None
                   for n in _walk(root))
    finally:
        set_config(fusion=old)


def _walk(node):
    out, stack = [], [node]
    while stack:
        n = stack.pop()
        out.append(n)
        stack.extend(n.children)
    return out


# ---------------------------------------------------------------------------
# program-cache keys: same steps, different schema/dictionary
# ---------------------------------------------------------------------------


def test_cache_keys_distinguish_dictionaries(mesh8):
    """Two frames with identical structure but different string
    dictionaries run the same chain shape; each result must reflect its
    own dictionary (a collision would decode wrong strings)."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import physical

    def run(df):
        physical._result_cache.clear()
        bdf = bd.from_pandas(df)
        bdf = bdf[bdf["w"] % 2 == 0]
        bdf = bdf.assign(u=bdf["v"] + 1.0)
        return bdf.to_pandas().reset_index(drop=True)

    d1 = _chain_df(seed=1)
    d2 = _chain_df(seed=2)
    d2["cat"] = np.random.default_rng(9).choice(
        ["xx", "yy", "zz", "qq", "rr"], len(d2))
    for df in (d1, d2):
        got = run(df)
        exp = df[df["w"] % 2 == 0].assign(u=df["v"] + 1.0) \
            .reset_index(drop=True)
        assert got["cat"].tolist() == exp["cat"].tolist()
        np.testing.assert_allclose(got["u"], exp["u"])


# ---------------------------------------------------------------------------
# resilience: chaos fault inside the fused dispatch, degraded re-run
# ---------------------------------------------------------------------------


def test_collective_fault_degrades_fused_group(mesh8, monkeypatch):
    """An injected collective fault at the fused ONED dispatch must
    reach the degradation envelope (NOT the unfused fallback) and the
    replicated re-run must still produce correct results."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, physical
    from bodo_tpu.runtime import resilience

    monkeypatch.setattr(config, "shard_min_rows", 100)
    df = _chain_df(5000, seed=3)
    exp = df[df["w"] % 3 != 0].assign(u=df["v"] * 2.0)
    set_config(faults="collective=raise:Internal:1:1")
    physical._result_cache.clear()
    bdf = bd.from_pandas(df)
    bdf = bdf[bdf["w"] % 3 != 0]
    got = bdf.assign(u=bdf["v"] * 2.0).to_pandas().reset_index(drop=True)
    set_config(faults="")
    np.testing.assert_allclose(got["u"].to_numpy(),
                               exp["u"].to_numpy())
    s = resilience.stats()
    assert s["faults_fired"].get("collective", 0) >= 1
    assert sum(s["degraded_stages"].values()) >= 1, s
    # the fault must NOT have been swallowed as a fusion fallback
    assert fusion.stats()["fallbacks"] == 0


# ---------------------------------------------------------------------------
# AQE interplay: fusion re-planned per execution round
# ---------------------------------------------------------------------------


def test_aqe_replan_with_fusion(mesh8, monkeypatch):
    """AQE re-optimization executes leaves and re-plans the remainder;
    every round must re-run fusion planning on the rewritten tree and
    stay correct."""
    monkeypatch.setattr(config, "shard_min_rows", 100)
    r = np.random.default_rng(4)
    left = pd.DataFrame({"k": r.integers(0, 40, 4000),
                         "v": r.normal(size=4000)})
    right = pd.DataFrame({"k": np.arange(40), "w": np.arange(40.0)})

    def fn(a, b):
        a = a[a["v"] > -1.0]
        a = a.assign(u=a["v"] + 2.0)
        m = a.merge(b, on="k")
        return m.groupby("k", as_index=False).agg(s=("u", "sum"),
                                                  t=("w", "max"))

    check_func(fn, [left, right], modes=["1d8"], rtol=1e-7)


# ---------------------------------------------------------------------------
# streaming: per-batch fused bodies
# ---------------------------------------------------------------------------


def test_streaming_fused_batches(mesh8):
    import jax

    import bodo_tpu
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion

    old_mesh = bodo_tpu.parallel.mesh.get_mesh()
    bodo_tpu.set_mesh(bodo_tpu.make_mesh(jax.devices()[:1]))
    old = (config.stream_exec, config.streaming_batch_size)
    set_config(stream_exec=True, streaming_batch_size=1000)
    try:
        df = _chain_df(5000, seed=5)
        bdf = bd.from_pandas(df)
        f = bdf[bdf["w"] % 3 != 0]
        got = (f.assign(u=f["v"] * 2.0)
               .groupby("k", as_index=False).agg(s=("u", "sum"),
                                                 c=("w", "count"))
               .to_pandas().sort_values("k").reset_index(drop=True))
        pf = df[df["w"] % 3 != 0].assign(u=lambda d: d["v"] * 2.0)
        exp = (pf.groupby("k", as_index=False)
               .agg(s=("u", "sum"), c=("w", "count"))
               .sort_values("k").reset_index(drop=True))
        assert got["k"].tolist() == exp["k"].tolist()
        assert got["c"].tolist() == exp["c"].tolist()
        np.testing.assert_allclose(got["s"].to_numpy(),
                                   exp["s"].to_numpy(), rtol=1e-12)
        assert fusion.stats()["stream_chains"] >= 1
    finally:
        set_config(stream_exec=old[0], streaming_batch_size=old[1])
        bodo_tpu.set_mesh(old_mesh)


# ---------------------------------------------------------------------------
# lockstep: composite-dispatch manifest
# ---------------------------------------------------------------------------


def test_lockstep_fusion_manifest(mesh8, monkeypatch):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.analysis import lockstep
    from bodo_tpu.plan import physical

    monkeypatch.setattr(config, "shard_min_rows", 100)
    lockstep.reset()
    physical._result_cache.clear()
    df = _chain_df(5000, seed=6)
    bdf = bd.from_pandas(df)
    bdf = bdf[bdf["w"] % 3 != 0]
    bdf.assign(u=bdf["v"] * 2.0).to_pandas()
    mans = lockstep.fusion_manifests()
    assert mans, "fused sharded dispatch must register a manifest"
    # manifests outlive lockstep.reset(), so whatever ran earlier in
    # this worker may have registered others first: find this chain's
    mine = [(fp, m) for fp, m in mans.items()
            if "filter" in m["ops"] and "project" in m["ops"]]
    assert mine, f"no filter+project manifest among {list(mans.values())}"
    fp, man = mine[0]
    assert lockstep.fusion_manifest(fp) == man


# ---------------------------------------------------------------------------
# Pallas: dense_accumulate traced into the fused body
# ---------------------------------------------------------------------------


def test_pallas_traced_into_fused_agg(mesh8):
    """With FORCE_INTERPRET armed (the kernel runs through the pallas
    interpreter on CPU), a small fused dense aggregation must bump
    trace_count — proof the MXU one-hot matmul kernel is dispatched
    INSIDE the fused program, not beside it."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.ops import pallas_kernels as PK
    from bodo_tpu.plan import fusion, physical

    r = np.random.default_rng(7)
    df = pd.DataFrame({
        "k": r.integers(0, 16, 4000),
        "x": r.normal(size=4000).astype(np.float32),
        "y": r.integers(0, 100, 4000),
    })

    def run():
        physical._result_cache.clear()
        bdf = bd.from_pandas(df)
        bdf = bdf[bdf["y"] % 3 != 0]
        bdf = bdf.assign(z=bdf["x"] + bdf["x"])
        return bdf.groupby("k", as_index=False).agg(
            s=("z", "sum"), c=("y", "count")) \
            .to_pandas().sort_values("k").reset_index(drop=True)

    prev = PK.FORCE_INTERPRET
    PK.FORCE_INTERPRET = True
    try:
        before = PK.trace_count
        fused = run()
        assert PK.trace_count > before
        assert fusion.stats()["groups_executed"] >= 1
    finally:
        PK.FORCE_INTERPRET = prev
    pdf = df[df["y"] % 3 != 0].assign(z=lambda d: d["x"] + d["x"])
    exp = pdf.groupby("k", as_index=False).agg(s=("z", "sum"),
                                               c=("y", "count"))
    assert fused["k"].tolist() == exp["k"].tolist()
    assert fused["c"].tolist() == exp["c"].tolist()
    np.testing.assert_allclose(fused["s"], exp["s"], rtol=1e-5)


# ---------------------------------------------------------------------------
# observability: EXPLAIN / profile annotations
# ---------------------------------------------------------------------------


def test_profile_and_explain_fusion_rows(mesh8):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import explain, physical
    from bodo_tpu.utils import tracing

    set_config(tracing_level=1)
    try:
        physical._result_cache.clear()
        with tracing.query_span() as qid:
            bdf = bd.from_pandas(_chain_df(seed=8))
            bdf = bdf[bdf["w"] % 2 == 0]
            bdf.assign(u=bdf["v"] + 1.0).groupby(
                "k", as_index=False).agg(s=("u", "sum")).to_pandas()
        prof = tracing.profile()
        assert any(k.startswith("fusion:") for k in prof), \
            sorted(prof)[:20]
        tree = explain.explain_analyze(qid)
        assert "fused" in tree
    finally:
        set_config(tracing_level=0)


# ---------------------------------------------------------------------------
# lint: no host sync inside @fusion_stage bodies
# ---------------------------------------------------------------------------


def _lint_src(tmp_path, source):
    from bodo_tpu.analysis import lint
    p = tmp_path / "mod.py"
    p.write_text(source)
    return lint.lint_file(str(p), root=str(tmp_path))


def test_lint_fusion_host_call(tmp_path):
    got = _lint_src(tmp_path, """
from bodo_tpu.plan.fusion import fusion_stage
import jax

@fusion_stage
def body(tree, count):
    jax.device_get(count)
    return tree
""")
    assert any(f.rule == "fusion-host-call" for f in got), got


def test_lint_host_call_outside_fusion_ok(tmp_path):
    got = _lint_src(tmp_path, """
import jax

def helper(count):
    jax.device_get(count)
    return count
""")
    assert not any(f.rule == "fusion-host-call" for f in got), got


# ---------------------------------------------------------------------------
# donation bookkeeping
# ---------------------------------------------------------------------------


def test_no_donation_on_cpu_and_frompandas(mesh8):
    """On the CPU backend donation must stay off (buffer aliasing is a
    TPU/GPU win), and a FromPandas input must never be donate-eligible —
    its arrays back the user's live frame."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, physical
    from bodo_tpu.plan.optimizer import optimize

    bdf = bd.from_pandas(_chain_df(seed=9))
    f = bdf[bdf["w"] % 3 != 0]
    out = f.assign(u=f["v"] + 1.0)
    root = optimize(out._plan)
    groups = fusion.plan_fusion_groups(root)
    assert groups and all(not g.donate_ok for g in groups)
    physical._result_cache.clear()
    out.to_pandas()
    assert fusion.stats()["donated"] == 0


# ---------------------------------------------------------------------------
# process-wide compile budget
# ---------------------------------------------------------------------------


def test_compile_budget_falls_back_unfused(mesh8, monkeypatch):
    """Once the process-wide compile budget is spent, new fusion
    signatures must run unfused (correct, just not fused) instead of
    pinning more XLA executables; clear_programs() returns the budget
    with the cache."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, physical

    df = _chain_df(seed=11)

    def run():
        physical._result_cache.clear()
        bdf = bd.from_pandas(df)
        f = bdf[bdf["w"] % 4 != 0]
        return f.assign(u=f["v"] * 3.0).to_pandas()

    expect = run()
    monkeypatch.setattr(fusion, "_max_compiles", 0)
    fusion.clear_programs()  # drops cached programs, resets the budget
    monkeypatch.setattr(fusion, "_n_compiles", 0)
    fusion.reset_stats()
    got = run()
    pd.testing.assert_frame_equal(
        got.reset_index(drop=True), expect.reset_index(drop=True))
    assert fusion.stats()["budget_spent"] >= 1
    assert fusion.stats()["compiles"] == 0
