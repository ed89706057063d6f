"""Fused join groups (plan/fusion_join.py).

The contract under test: compiling [chain -> hash-join probe -> chain
-> decomposable agg] into one program — with the build-side hash table
device-resident and the partial-agg bucket shuffle traced in-program —
must be INVISIBLE except for speed. Sweep + sqlite-oracle equivalence,
bit-identity fused vs unfused for inner/left and dict/int keys,
build-table reuse proven through the LRU counters and the device-buffer
ledger, bucket-overflow regrowth, chaos degradation to a replicated
re-run (never a silent fallback), and lockstep/comm attribution of the
in-program all_to_all.
"""

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.config import config, set_config
from tests.utils import check_func, check_sql


@pytest.fixture(autouse=True)
def _fresh_fused_join():
    from bodo_tpu.plan import fusion, fusion_join, physical
    physical._result_cache.clear()
    fusion.reset_stats()
    fusion.clear_programs()
    fusion_join.reset_stats()
    fusion_join.clear_build_cache()
    yield
    set_config(faults="")


def _probe_df(n=4000, seed=0, nkeys=50):
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": r.integers(0, nkeys, n),
        "v": r.normal(size=n),
        "w": r.integers(0, 100, n).astype(np.int64),
    })


def _dim_df(nkeys=50, seed=1):
    r = np.random.default_rng(seed)
    return pd.DataFrame({
        "k": np.arange(nkeys),
        "g": r.integers(0, 7, nkeys),
        "dim": r.normal(size=nkeys),
    })


# ---------------------------------------------------------------------------
# equivalence: distribution sweep + sqlite oracle
# ---------------------------------------------------------------------------


def test_fused_join_chain_sweep_vs_pandas(mesh8):
    def fn(df, dim):
        df = df[df["w"] % 3 != 0]
        j = df.merge(dim, on="k", how="inner")
        j = j.assign(u=j["v"] * j["dim"])
        return j[j["u"] > -10.0]

    check_func(fn, [_probe_df(), _dim_df()])


def test_fused_left_join_sweep_vs_pandas(mesh8):
    def fn(df, dim):
        df = df[df["w"] < 90]
        return df.merge(dim, on="k", how="left")

    # dim covers only half the probe key space: real unmatched rows
    check_func(fn, [_probe_df(nkeys=50), _dim_df(nkeys=25)])


def test_fused_join_agg_sweep_vs_pandas(mesh8):
    """The taxi-shaped hot path: chain -> join -> project -> groupby
    with decomposable aggs — in 1D modes the shuffle traces in-program."""
    def fn(df, dim):
        df = df[df["w"] % 3 != 0]
        j = df.merge(dim, on="k", how="inner")
        j = j.assign(u=j["v"] * j["dim"])
        return j.groupby("g", as_index=False).agg(
            s=("u", "sum"), c=("w", "count"), m=("v", "mean"))

    check_func(fn, [_probe_df(), _dim_df()], rtol=1e-7)


def test_fused_join_sqlite_oracle(mesh8):
    check_sql(
        "select d.g as g, sum(t.v * d.dim) as s, count(*) as c "
        "from trips t join dims d on t.k = d.k "
        "where t.w < 80 group by d.g",
        {"trips": _probe_df(seed=3), "dims": _dim_df(seed=4)},
        rtol=1e-6)


# ---------------------------------------------------------------------------
# bit identity: fused vs unfused
# ---------------------------------------------------------------------------


def _run_fused_unfused(run):
    from bodo_tpu.plan import physical
    physical._result_cache.clear()
    fused = run()
    old_f, old_j = config.fusion, config.fusion_join
    set_config(fusion=False, fusion_join=False)
    try:
        physical._result_cache.clear()
        plain = run()
    finally:
        set_config(fusion=old_f, fusion_join=old_j)
    return fused, plain


def _sorted(df):
    return df.sort_values(list(df.columns)).reset_index(drop=True)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_bit_identity_int_keys(mesh8, how):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join

    def run():
        bl = bd.from_pandas(_probe_df(nkeys=60))
        br = bd.from_pandas(_dim_df(nkeys=40))
        bl = bl[bl["w"] % 3 != 0]
        j = bl.merge(br, on="k", how=how)
        return j.assign(u=j["v"] + j["w"]).to_pandas()

    fused, plain = _run_fused_unfused(run)
    assert fusion_join.stats()["groups_executed"] >= 1
    assert fusion_join.stats()["fallbacks"] == 0
    pd.testing.assert_frame_equal(_sorted(fused), _sorted(plain))


def test_bit_identity_dict_keys_shared_dictionary(mesh8):
    """String keys fuse only when both sides carry the SAME dictionary
    object — derive the build side from the probe frame so they do."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join

    r = np.random.default_rng(5)
    df = pd.DataFrame({
        "cat": r.choice(["aa", "bb", "cc", "dd"], 3000),
        "v": r.normal(size=3000),
        "w": r.integers(0, 50, 3000),
    })

    def run():
        bdf = bd.from_pandas(df)
        dim = bdf.groupby("cat", as_index=False).agg(dv=("v", "mean"))
        probe = bdf[bdf["w"] % 2 == 0]
        j = probe.merge(dim, on="cat", how="inner")
        return j.assign(u=j["v"] - j["dv"]).to_pandas()

    fused, plain = _run_fused_unfused(run)
    pd.testing.assert_frame_equal(_sorted(fused), _sorted(plain))


def test_dict_keys_different_dictionaries_fall_back_correct(mesh8):
    """Two independently-encoded string columns have distinct
    dictionary objects: the fused body cannot compare codes, so the
    group must FALL BACK (per-node unifies) and stay correct."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join

    r = np.random.default_rng(6)
    lp = pd.DataFrame({"cat": r.choice(["aa", "bb", "cc"], 2000),
                       "v": r.normal(size=2000)})
    rp = pd.DataFrame({"cat": ["bb", "cc", "dd"],
                       "dv": [1.0, 2.0, 3.0]})

    def run():
        bl = bd.from_pandas(lp)
        br = bd.from_pandas(rp)
        bl = bl[bl["v"] > -10.0]
        j = bl.merge(br, on="cat", how="inner")
        return j.assign(u=j["v"] + j["dv"]).to_pandas()

    fused, plain = _run_fused_unfused(run)
    pd.testing.assert_frame_equal(_sorted(fused), _sorted(plain))
    exp = lp.merge(rp, on="cat").assign(u=lambda d: d["v"] + d["dv"])
    assert len(fused) == len(exp)


# ---------------------------------------------------------------------------
# device-resident build reuse
# ---------------------------------------------------------------------------


def test_build_reuse_across_probes_ledger_and_stats(mesh8):
    """Two queries probing the SAME build table must build once and hit
    the LRU on the second dispatch; the slot-owner LUT must be visible
    in the device-buffer ledger under op `join_build_lut`."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join, physical
    from bodo_tpu.runtime import xla_observatory as xobs

    bl = bd.from_pandas(_probe_df(seed=7))
    br = bd.from_pandas(_dim_df(seed=8))

    def q(pred):
        physical._result_cache.clear()
        probe = bl[bl["w"] % pred != 0]
        j = probe.merge(br, on="k", how="inner")
        return j.assign(u=j["v"] * j["dim"]).to_pandas()

    q(3)
    s1 = fusion_join.build_cache_stats()
    assert s1["builds"] == 1 and s1["size"] == 1
    led = xobs.ledger_stats()["by_op"]
    assert "join_build_lut" in led, sorted(led)
    q(2)  # different probe shape, SAME build buffers
    s2 = fusion_join.build_cache_stats()
    assert s2["builds"] == 1, "second probe must not rebuild"
    assert s2["hits"] >= 1
    assert fusion_join.stats()["groups_executed"] >= 2


def test_per_node_hash_join_shares_build_cache(mesh8):
    """relational._join_hash_try must draw from the same LRU: an
    unfusable probe (no chain around the join) still reuses the build."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join, physical

    # sparse int64 keys defeat the dense-LUT fast path, forcing both
    # the per-node join and the fused probe onto the hash build
    r = np.random.default_rng(9)
    keys = np.unique(r.integers(0, 1 << 40, 80).astype(np.int64))
    lp = pd.DataFrame({"k": r.choice(keys, 4000),
                       "v": r.normal(size=4000),
                       "w": r.integers(0, 100, 4000).astype(np.int64)})
    rp = pd.DataFrame({"k": keys, "dim": r.normal(size=len(keys))})
    bl = bd.from_pandas(lp)
    br = bd.from_pandas(rp)

    physical._result_cache.clear()
    bl.merge(br, on="k", how="inner").to_pandas()   # bare join: per-node
    s1 = fusion_join.build_cache_stats()
    assert s1["builds"] == 1
    physical._result_cache.clear()
    probe = bl[bl["w"] < 90]
    j = probe.merge(br, on="k", how="inner")
    j.assign(u=j["v"] + 1.0).to_pandas()            # fused group
    s2 = fusion_join.build_cache_stats()
    assert s2["builds"] == 1, "fused probe must reuse the per-node build"
    assert s2["hits"] >= 1


def test_duplicate_build_keys_negative_cached(mesh8):
    """Duplicate build keys are a sort-join case: the fused group falls
    back, and the verdict is cached so the second run skips the build."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join, physical

    # three rows in a key range of seven: nothing says they repeat before
    # the build has run (`relational.keys_must_repeat` is false)
    dup = pd.DataFrame({"k": [1, 1, 7], "dim": [0.1, 0.2, 0.3]})
    bl = bd.from_pandas(_probe_df(nkeys=3))
    br = bd.from_pandas(dup)

    def run():
        physical._result_cache.clear()
        probe = bl[bl["w"] < 90]
        j = probe.merge(br, on="k", how="inner")
        return j.assign(u=j["v"] + j["dim"]).to_pandas()

    out = run()
    s = fusion_join.stats()
    assert s["fallbacks"] >= 1
    assert s["build_cache"]["negative"] == 1
    run()
    assert fusion_join.build_cache_stats()["negative_hits"] >= 1
    # correctness vs pandas despite the fallback
    pdf = _probe_df(nkeys=3)
    exp = pdf[pdf["w"] < 90].merge(dup, on="k").assign(
        u=lambda d: d["v"] + d["dim"])
    assert len(out) == len(exp)


# ---------------------------------------------------------------------------
# in-program shuffle: manifest, comm attribution, overflow regrowth
# ---------------------------------------------------------------------------


def _sharded_join_agg(bd, lp, rp):
    bl = bd.from_pandas(lp)
    br = bd.from_pandas(rp)
    bl = bl[bl["w"] % 3 != 0]
    j = bl.merge(br, on="k", how="inner")
    j = j.assign(u=j["v"] * j["dim"])
    return j.groupby("g", as_index=False).agg(s=("u", "sum"))


def test_manifest_declares_in_program_all_to_all(mesh8, monkeypatch):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.analysis import lockstep
    from bodo_tpu.parallel import comm
    from bodo_tpu.plan import fusion_join

    monkeypatch.setattr(config, "shard_min_rows", 100)
    monkeypatch.setattr(config, "comm_accounting", True)
    comm.reset()
    _sharded_join_agg(bd, _probe_df(seed=11), _dim_df(seed=12)) \
        .to_pandas()
    assert fusion_join.stats()["agg_inprogram"] >= 1
    mans = {fp: m for fp, m in lockstep.fusion_manifests().items()
            if "join" in m["ops"] and "shuffle" in m["ops"]}
    assert mans, "fused join+shuffle dispatch must register a manifest"
    assert all("aggregate" in m["ops"] for m in mans.values())
    assert all("all_to_all" in m["in_program"] for m in mans.values())
    # the comm observatory attributes the in-program collective at the
    # group's fused site even though no host dispatch hook ever saw it
    # (manifests persist process-wide, so match any registered group fp)
    sites = comm.stats()["sites"]
    assert any(f"all_to_all@fused[{fp}]" in sites for fp in mans), \
        (sorted(mans), sorted(sites))


def test_bucket_overflow_regrows_and_stays_correct(mesh8, monkeypatch):
    """Skewed keys + a tiny skew factor force the fixed-capacity bucket
    shuffle to overflow: the host must regrow capacity and recompile
    (shuffle_retries > 0), and the result must match pandas."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join

    monkeypatch.setattr(config, "shard_min_rows", 100)
    monkeypatch.setattr(config, "shuffle_skew_factor", 1.0)
    r = np.random.default_rng(13)
    lp = pd.DataFrame({
        "k": np.where(r.random(4000) < 0.95, 0,
                      r.integers(0, 50, 4000)).astype(np.int64),
        "v": r.normal(size=4000),
        "w": r.integers(0, 100, 4000).astype(np.int64),
    })
    rp = _dim_df(seed=14)
    out = _sharded_join_agg(bd, lp, rp).to_pandas()
    s = fusion_join.stats()
    if s["agg_inprogram"]:
        assert s["shuffle_retries"] >= 1 or s["fallbacks"] == 0
    pdf = lp[lp["w"] % 3 != 0].merge(rp, on="k")
    exp = pdf.assign(u=pdf["v"] * pdf["dim"]).groupby(
        "g", as_index=False).agg(s=("u", "sum"))
    got = out.sort_values("g").reset_index(drop=True)
    exp = exp.sort_values("g").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, rtol=1e-7,
                                  check_dtype=False)


# ---------------------------------------------------------------------------
# chaos: collective fault in the fused group degrades, never silently
# ---------------------------------------------------------------------------


def test_chaos_collective_fault_degrades_fused_join(mesh8, monkeypatch):
    """An armed collective fault at the fused-join dispatch must
    propagate to the resilience envelope (degraded replicated re-run of
    the whole group), NOT be swallowed as a FusionFallback."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join, physical
    from bodo_tpu.runtime import resilience

    monkeypatch.setattr(config, "shard_min_rows", 100)
    lp, rp = _probe_df(seed=15), _dim_df(seed=16)
    set_config(faults="collective=raise:Internal:1:1")
    physical._result_cache.clear()
    out = _sharded_join_agg(bd, lp, rp).to_pandas()
    set_config(faults="")
    s = resilience.stats()
    assert s["faults_fired"].get("collective", 0) >= 1
    assert sum(s["degraded_stages"].values()) >= 1, s
    assert fusion_join.stats()["fallbacks"] == 0
    pdf = lp[lp["w"] % 3 != 0].merge(rp, on="k")
    exp = pdf.assign(u=pdf["v"] * pdf["dim"]).groupby(
        "g", as_index=False).agg(s=("u", "sum"))
    got = out.sort_values("g").reset_index(drop=True)
    exp = exp.sort_values("g").reset_index(drop=True)
    pd.testing.assert_frame_equal(got, exp, rtol=1e-7,
                                  check_dtype=False)


# ---------------------------------------------------------------------------
# observability: EXPLAIN shows the absorbed Join/Shuffle members
# ---------------------------------------------------------------------------


def test_explain_shows_fused_join_members(mesh8, monkeypatch):
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import explain, fusion_join, physical
    from bodo_tpu.utils import tracing

    monkeypatch.setattr(config, "shard_min_rows", 100)
    set_config(tracing_level=1)
    try:
        physical._result_cache.clear()
        with tracing.query_span() as qid:
            _sharded_join_agg(bd, _probe_df(seed=17), _dim_df(seed=18)) \
                .to_pandas()
        assert fusion_join.stats()["groups_executed"] >= 1
        tree = explain.explain_analyze(qid)
        assert "fused" in tree
        assert "Join" in tree
    finally:
        set_config(tracing_level=0)


def test_fusion_join_config_toggle(mesh8):
    """fusion_join=False must keep plain chain fusion working and
    never form join groups."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion, fusion_join, physical
    from bodo_tpu.plan.optimizer import optimize

    bl = bd.from_pandas(_probe_df(seed=19))
    br = bd.from_pandas(_dim_df(seed=20))
    probe = bl[bl["w"] < 90]
    j = probe.merge(br, on="k", how="inner")
    plan = optimize(j.assign(u=j["v"] + 1.0)._plan)
    groups = fusion.plan_fusion_groups(plan)
    assert any(isinstance(g, fusion_join.JoinGroup) for g in groups)
    old = config.fusion_join
    set_config(fusion_join=False)
    try:
        groups = fusion.plan_fusion_groups(plan)
        assert not any(isinstance(g, fusion_join.JoinGroup)
                       for g in groups)
    finally:
        set_config(fusion_join=old)
