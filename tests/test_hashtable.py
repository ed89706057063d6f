"""Scatter-claim hash table (ops/hashtable.py): sort-free group ids and
join LUTs at arbitrary key cardinality.

Reference analogue: the serial-chaining hash tables of
bodo/libs/_hash_join.cpp and bodo/libs/groupby/_groupby.cpp, realized
as parallel scatter-min claim rounds (TPU-friendly dense ops)."""

import numpy as np
import pandas as pd
import pytest


def _mk(df):
    from bodo_tpu import Table
    return Table.from_pandas(df)


def test_claim_slots_basic(mesh8):
    import jax.numpy as jnp

    from bodo_tpu.ops import hashtable as HT

    r = np.random.default_rng(0)
    n = 4096
    k = r.integers(-10**18, 10**18, 300)[r.integers(0, 300, n)]
    codes, _ = HT.encode_columns([(jnp.asarray(k), None)])
    T = HT.table_size(n)
    slot, owner, rounds, unres = HT.claim_slots(codes, jnp.ones(n, bool), T)
    assert not bool(unres)
    s = np.asarray(slot)
    by_key = {}
    for i in range(n):
        by_key.setdefault(int(k[i]), set()).add(int(s[i]))
    # equal keys share one slot; distinct keys get distinct slots
    assert all(len(v) == 1 for v in by_key.values())
    slots = [next(iter(v)) for v in by_key.values()]
    assert len(set(slots)) == len(by_key)


@pytest.mark.parametrize("nb,m,max_rounds,unresolved", [
    (70, 60_000, 64, False),    # a few keys, long tails of misses
    (1000, 40_000, 64, False),  # load near 0.5
    (1000, 40_000, 2, True),    # rounds run out with most rows walking
    (1000, 40_000, 7, True),    # rounds run out inside the tail
    (3, 5, 64, False),          # a probe smaller than one tail row
], ids=["sparse_build", "half_load", "out_dense", "out_in_tail", "tiny"])
def test_probe_slots_tail_walk(mesh8, nb, m, max_rounds, unresolved):
    """`probe_slots` hands the last walkers to a compacted walk: every
    live probe row still finds its build row (or -1), and rows still
    walking when the rounds run out, in either walk, say unresolved."""
    import jax.numpy as jnp

    from bodo_tpu.ops import hashtable as HT

    r = np.random.default_rng(nb + m + max_rounds)
    bk = r.choice(10**9, nb, replace=False).astype(np.int64)
    pk = np.where(r.random(m) < 0.2, bk[r.integers(0, nb, m)],
                  r.integers(10**9, 2 * 10**9, m))
    ok = r.random(m) < 0.95
    bcodes, _ = HT.encode_columns([(jnp.asarray(bk), None)])
    pcodes, _ = HT.encode_columns([(jnp.asarray(pk), None)])
    T = HT.table_size(nb)
    _slot, owner, _r, bunres = HT.claim_slots(bcodes, jnp.ones(nb, bool), T)
    assert not bool(bunres)
    idx, unres = HT.probe_slots(bcodes, owner, pcodes, jnp.asarray(ok), T,
                                max_rounds=max_rounds)
    assert bool(unres) == unresolved
    if unresolved:
        return
    row = {int(k): i for i, k in enumerate(bk)}
    want = np.array([row.get(int(k), -1) if o else -1
                     for k, o in zip(pk, ok)])
    np.testing.assert_array_equal(np.asarray(idx), want)


def test_group_ids_matches_pandas_ngroups(mesh8):
    import jax.numpy as jnp

    from bodo_tpu.ops import hashtable as HT

    r = np.random.default_rng(1)
    n = 5000
    a = r.integers(-10**15, 10**15, n) % 211
    b = r.integers(0, 13, n)
    seg, grow, ng, unres = HT.group_ids(
        [(jnp.asarray(a), None), (jnp.asarray(b), None)],
        jnp.ones(n, bool))
    exp = pd.DataFrame({"a": a, "b": b}).groupby(["a", "b"]).ngroups
    assert int(ng) == exp and not bool(unres)


def test_hash_groupby_wide_keys_vs_pandas(mesh8):
    """Wide-range int64 keys: dense and packed gates both fail, the
    hash path must produce pandas-exact results."""
    import bodo_tpu.relational as R

    r = np.random.default_rng(2)
    n = 20_000
    keys = r.integers(-10**18, 10**18, 3000)
    df = pd.DataFrame({"k": keys[r.integers(0, 3000, n)],
                       "v": r.normal(size=n),
                       "w": r.integers(0, 100, n)})
    df.loc[::11, "v"] = np.nan
    exp = df.groupby("k", as_index=False).agg(
        s=("v", "sum"), m=("v", "mean"), mn=("w", "min"),
        mx=("w", "max"), c=("v", "count"), sz=("v", "size"),
        sd=("v", "std"), f=("w", "first"), l=("w", "last"))
    got = R.groupby_agg(_mk(df), ["k"], [
        ("v", "sum", "s"), ("v", "mean", "m"), ("w", "min", "mn"),
        ("w", "max", "mx"), ("v", "count", "c"), ("v", "size", "sz"),
        ("v", "std", "sd"), ("w", "first", "f"), ("w", "last", "l"),
    ]).to_pandas()
    assert got["k"].tolist() == exp["k"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(got["m"], exp["m"], rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(got["sd"], exp["sd"], rtol=1e-9)
    for c in ("mn", "mx", "c", "sz", "f", "l"):
        assert got[c].tolist() == exp[c].tolist(), c


def test_hash_groupby_null_keys_dropped(mesh8):
    """pandas dropna=True: float-NaN keys form no group on the hash path."""
    import bodo_tpu.relational as R

    r = np.random.default_rng(3)
    n = 3000
    k = r.integers(0, 50, n).astype(np.float64) * 1e12
    k[::9] = np.nan
    df = pd.DataFrame({"k": k, "v": r.normal(size=n)})
    exp = df.groupby("k", as_index=False).agg(s=("v", "sum"))
    got = R.groupby_agg(_mk(df), ["k"], [("v", "sum", "s")]).to_pandas()
    assert got["k"].tolist() == exp["k"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-12)


def test_hash_groupby_matches_sort_path(mesh8):
    """Differential: hash on/off must agree exactly."""
    import bodo_tpu.relational as R
    from bodo_tpu.config import set_config

    r = np.random.default_rng(4)
    n = 8000
    df = pd.DataFrame({
        "k1": r.integers(-10**17, 10**17, 500)[r.integers(0, 500, n)],
        "k2": r.choice(["x", "y", "z", "w"], n),
        "v": r.normal(size=n)})
    outs = {}
    for flag in (True, False):
        set_config(hash_groupby=flag)
        try:
            outs[flag] = R.groupby_agg(
                _mk(df), ["k1", "k2"],
                [("v", "sum", "s"), ("v", "var", "vv")]).to_pandas()
        finally:
            set_config(hash_groupby=True)
    pd.testing.assert_frame_equal(outs[True], outs[False])


def test_hash_join_wide_unique_build(mesh8):
    """Unique wide-range build keys: dense LUT can't fire; hash LUT
    must match pandas for inner and left joins."""
    import bodo_tpu.relational as R

    r = np.random.default_rng(5)
    n, u = 30_000, 2000
    bk = np.unique(r.integers(-10**18, 10**18, u))
    left = pd.DataFrame({"k": bk[r.integers(0, len(bk), n)],
                         "x": r.normal(size=n)})
    # drop some build keys so probes miss
    right = pd.DataFrame({"k": bk[: len(bk) // 2],
                          "y": r.normal(size=len(bk) // 2)})
    for how in ("inner", "left"):
        exp = left.merge(right, on="k", how=how).sort_values(
            ["k", "x"]).reset_index(drop=True)
        got = R.join_tables(_mk(left), _mk(right), ["k"], ["k"], how,
                            ("_x", "_y")).to_pandas().sort_values(
            ["k", "x"]).reset_index(drop=True)
        assert len(got) == len(exp), how
        np.testing.assert_allclose(got["y"], exp["y"], rtol=1e-12)


def test_hash_join_matches_sort_join(mesh8):
    """Differential vs the sort join, multi-key with one nullable side."""
    import bodo_tpu.relational as R
    from bodo_tpu.config import set_config

    r = np.random.default_rng(6)
    n = 10_000
    bk1 = np.unique(r.integers(-10**17, 10**17, 800))
    bk2 = r.integers(0, 5, len(bk1))
    left = pd.DataFrame({
        "a": bk1[r.integers(0, len(bk1), n)],
        "b": r.integers(0, 5, n), "x": r.normal(size=n)})
    right = pd.DataFrame({"a": bk1, "b": bk2,
                          "y": r.normal(size=len(bk1))})
    outs = {}
    for flag in (True, False):
        set_config(hash_join=flag)
        try:
            outs[flag] = R.join_tables(
                _mk(left), _mk(right), ["a", "b"], ["a", "b"], "inner",
                ("_x", "_y")).to_pandas().sort_values(
                ["a", "b", "x"]).reset_index(drop=True)
        finally:
            set_config(hash_join=True)
    pd.testing.assert_frame_equal(outs[True], outs[False])


def test_hash_join_duplicate_build_falls_back(mesh8):
    """Duplicate build keys: hash LUT declines, sort join answers."""
    import bodo_tpu.relational as R

    r = np.random.default_rng(7)
    left = pd.DataFrame({"k": r.integers(-10**17, 10**17, 50)[
        r.integers(0, 50, 500)], "x": np.arange(500.0)})
    right = pd.DataFrame({"k": np.repeat(left["k"].unique()[:20], 3),
                          "y": np.arange(60.0)})
    exp = left.merge(right, on="k", how="inner").sort_values(
        ["k", "x", "y"]).reset_index(drop=True)
    got = R.join_tables(_mk(left), _mk(right), ["k"], ["k"], "inner",
                        ("_x", "_y")).to_pandas().sort_values(
        ["k", "x", "y"]).reset_index(drop=True)
    assert len(got) == len(exp)
    np.testing.assert_allclose(got["y"], exp["y"], rtol=1e-12)


def test_hashed_groupby_mxu_route_interpret(mesh8):
    """The Pallas MXU one-hot accumulate engages after hash
    densification when the group count fits (interpret mode on CPU)."""
    import bodo_tpu.relational as R
    from bodo_tpu.ops import pallas_kernels as PK

    r = np.random.default_rng(8)
    n = 5000
    keys = r.integers(-10**18, 10**18, 300)
    df = pd.DataFrame({"k": keys[r.integers(0, 300, n)],
                       "v": r.normal(size=n).astype(np.float32)})
    exp = df.groupby("k", as_index=False).agg(
        s=("v", "sum"), m=("v", "mean"), c=("v", "count"),
        z=("v", "size"))
    PK.FORCE_INTERPRET = True
    try:
        got = R.groupby_agg(_mk(df), ["k"], [
            ("v", "sum", "s"), ("v", "mean", "m"), ("v", "count", "c"),
            ("v", "size", "z")]).to_pandas()
    finally:
        PK.FORCE_INTERPRET = False
    assert got["k"].tolist() == exp["k"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got["m"], exp["m"], rtol=1e-4, atol=1e-4)
    assert got["c"].tolist() == exp["c"].tolist()
    assert got["z"].tolist() == exp["z"].tolist()
