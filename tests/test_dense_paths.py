"""Dense (sort-free) groupby and dense-LUT join fast paths
(relational._groupby_agg_dense / _join_dense_try): they must fire on
eligible shapes and agree exactly with the sort-based paths and pandas.

Reference analogue: the specialized hash-table fast paths of
bodo/libs/groupby/_groupby.cpp and _hash_join.cpp."""

import numpy as np
import pandas as pd
import pytest

import bodo_tpu.relational as R
from bodo_tpu import Table
from bodo_tpu.config import config, set_config


def _df(n=5000, seed=0):
    r = np.random.default_rng(seed)
    df = pd.DataFrame({
        "a": r.integers(0, 12, n),
        "b": r.choice(["x", "yy", "z"], n),
        "flag": r.integers(0, 2, n).astype(bool),
        "v": r.normal(size=n),
        "w": r.integers(-50, 50, n).astype(np.int32),
    })
    df.loc[r.random(n) < 0.07, "v"] = np.nan
    return df


def test_dense_groupby_fires_and_matches(one_dev, monkeypatch):
    df = _df()
    fired = []
    orig = R._groupby_agg_dense

    def spy(*a, **k):
        fired.append(1)
        return orig(*a, **k)
    monkeypatch.setattr(R, "_groupby_agg_dense", spy)

    aggs = [("v", "sum", "s"), ("v", "mean", "m"), ("v", "std", "sd"),
            ("v", "count", "c"), ("w", "min", "lo"), ("w", "max", "hi"),
            ("b", "first", "fb")]
    got = R.groupby_agg(Table.from_pandas(df), ["a", "b", "flag"], aggs
                        ).to_pandas()
    assert fired, "dense groupby did not fire on a small key space"
    exp = df.groupby(["a", "b", "flag"], as_index=False).agg(
        s=("v", "sum"), m=("v", "mean"), sd=("v", "std"), c=("v", "count"),
        lo=("w", "min"), hi=("w", "max"), fb=("b", "first"))
    got = got.sort_values(["a", "b", "flag"]).reset_index(drop=True)
    exp = exp.sort_values(["a", "b", "flag"]).reset_index(drop=True)
    assert got["a"].tolist() == exp["a"].tolist()
    assert got["b"].tolist() == exp["b"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-9)
    np.testing.assert_allclose(got["sd"].fillna(-1), exp["sd"].fillna(-1),
                               rtol=1e-9)
    assert got["c"].tolist() == exp["c"].tolist()
    assert got["lo"].tolist() == exp["lo"].tolist()
    assert got["fb"].tolist() == exp["fb"].tolist()


def test_dense_groupby_matches_sort_path(one_dev):
    df = _df(seed=1)
    t = Table.from_pandas(df)
    aggs = [("v", "sum", "s"), ("v", "var", "vv")]
    dense = R.groupby_agg(t, ["a", "flag"], aggs).to_pandas()
    old = config.dense_groupby_max_slots
    set_config(dense_groupby_max_slots=0)
    try:
        sortp = R.groupby_agg(t, ["a", "flag"], aggs).to_pandas()
    finally:
        set_config(dense_groupby_max_slots=old)
    d = dense.sort_values(["a", "flag"]).reset_index(drop=True)
    s = sortp.sort_values(["a", "flag"]).reset_index(drop=True)
    assert d["a"].tolist() == s["a"].tolist()
    np.testing.assert_allclose(d["s"], s["s"], rtol=1e-12)
    np.testing.assert_allclose(d["vv"], s["vv"], rtol=1e-12)


def test_dense_join_fires_and_matches(one_dev, monkeypatch):
    r = np.random.default_rng(2)
    n = 4000
    left = pd.DataFrame({"k": r.integers(0, 100, n),
                         "v": r.normal(size=n)})
    right = pd.DataFrame({"k": np.arange(100),
                          "name": [f"n{i}" for i in range(100)],
                          "z": np.arange(100) * 1.5})
    fired = []
    orig = R._join_dense_try

    def spy(*a, **k):
        out = orig(*a, **k)
        if out is not None:
            fired.append(1)
        return out
    monkeypatch.setattr(R, "_join_dense_try", spy)

    for how in ("inner", "left"):
        got = R.join_tables(Table.from_pandas(left),
                            Table.from_pandas(right.iloc[:80]),
                            ["k"], ["k"], how).to_pandas()
        exp = left.merge(right.iloc[:80], on="k", how=how)
        assert len(got) == len(exp), how
        g = got.sort_values(["k", "v"]).reset_index(drop=True)
        e = exp.sort_values(["k", "v"]).reset_index(drop=True)
        assert g["k"].tolist() == e["k"].tolist()
        np.testing.assert_allclose(g["v"], e["v"], rtol=1e-12)
        if how == "inner":
            assert g["name"].tolist() == e["name"].tolist()
        else:
            assert g["name"].fillna("<NA>").tolist() == \
                e["name"].fillna("<NA>").tolist()
    assert len(fired) == 2


def test_dense_join_duplicate_build_keys_falls_back(one_dev):
    left = pd.DataFrame({"k": [1, 2, 3, 2], "v": [1.0, 2.0, 3.0, 4.0]})
    right = pd.DataFrame({"k": [2, 2, 3], "w": [10.0, 20.0, 30.0]})
    got = R.join_tables(Table.from_pandas(left), Table.from_pandas(right),
                        ["k"], ["k"], "inner").to_pandas()
    exp = left.merge(right, on="k", how="inner")
    assert len(got) == len(exp)
    assert sorted(got["w"].tolist()) == sorted(exp["w"].tolist())


def test_dense_join_multikey_and_null_keys(one_dev):
    r = np.random.default_rng(3)
    left = pd.DataFrame({
        "a": r.integers(0, 10, 500),
        "b": r.integers(0, 5, 500),
        "v": np.arange(500.0),
    })
    right = pd.DataFrame([(a, b, a * 10 + b)
                          for a in range(10) for b in range(5)],
                         columns=["a", "b", "code"])
    got = R.join_tables(Table.from_pandas(left), Table.from_pandas(right),
                        ["a", "b"], ["a", "b"], "inner").to_pandas()
    exp = left.merge(right, on=["a", "b"], how="inner")
    assert len(got) == len(exp)
    g = got.sort_values("v").reset_index(drop=True)
    e = exp.sort_values("v").reset_index(drop=True)
    assert g["code"].tolist() == e["code"].tolist()


def test_mxu_matmul_groupby_interpret(one_dev):
    """The pallas one-hot MXU accumulate (interpret mode) must agree with
    the scatter path for sum/count/mean/size."""
    from bodo_tpu.ops import pallas_kernels as PK
    r = np.random.default_rng(5)
    n = 6000
    df = pd.DataFrame({
        "a": r.integers(0, 9, n), "b": r.integers(0, 7, n),
        "v": r.normal(size=n).astype(np.float32),
        "c": r.integers(0, 100, n).astype(np.int32),
    })
    df.loc[r.random(n) < 0.1, "v"] = np.nan
    aggs = [("v", "sum", "s"), ("v", "mean", "m"), ("v", "count", "cnt"),
            ("c", "size", "sz")]
    old = PK.FORCE_INTERPRET
    PK.FORCE_INTERPRET = True
    try:
        got = R.groupby_agg(Table.from_pandas(df), ["a", "b"], aggs
                            ).to_pandas()
    finally:
        PK.FORCE_INTERPRET = old
    exp = df.groupby(["a", "b"], as_index=False).agg(
        s=("v", "sum"), m=("v", "mean"), cnt=("v", "count"),
        sz=("c", "size"))
    g = got.sort_values(["a", "b"]).reset_index(drop=True)
    e = exp.sort_values(["a", "b"]).reset_index(drop=True)
    assert g["a"].tolist() == e["a"].tolist()
    np.testing.assert_allclose(g["s"], e["s"], rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(g["m"], e["m"], rtol=1e-3, atol=1e-4)
    assert g["cnt"].tolist() == e["cnt"].tolist()
    assert g["sz"].tolist() == e["sz"].tolist()


def test_pallas_dense_accumulate_unit():
    import jax.numpy as jnp

    from bodo_tpu.ops.pallas_kernels import dense_accumulate
    r = np.random.default_rng(6)
    n, K = 3000, 250
    codes = jnp.asarray(r.integers(0, K, n).astype(np.int32))
    v = jnp.asarray(r.normal(size=n).astype(np.float32))
    ok = jnp.asarray(r.random(n) > 0.2)
    out = dense_accumulate(codes, [v], [ok], K, interpret=True)[0]
    exp = np.zeros(K)
    np.add.at(exp, np.asarray(codes)[np.asarray(ok)],
              np.asarray(v)[np.asarray(ok)])
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# dense_agg_tail's reduce route against its scatter route, same inputs
# ---------------------------------------------------------------------------

REDUCE_OPS = ("sum", "sumnull", "sum64", "mean", "count", "size", "min",
              "max")
SIZES, LOS = (3, 2), (5, 0)       # six slots, as TPC-H Q1 has
_tails = {}                       # (route, op, dtype, nullable) -> jitted tail


def _tail(route, op, dtype, nullable, monkeypatch):
    """`dense_agg_tail` jitted with the route steered the way only a
    test may: by the module constant, while the program traces."""
    import jax
    key = (route, op, dtype, nullable)
    monkeypatch.setattr(R, "DENSE_REDUCE_MAX_SLOTS",
                        6 if route == "reduce" else 0)
    assert R.dense_route(6, (op,), False) == route
    if key not in _tails:
        def body(tree, live):
            return R.dense_agg_tail(tree, live, ["a", "b"], ["v"], (op,),
                                    SIZES, LOS, 6, False)
        _tails[key] = jax.jit(body)
    return _tails[key]


def _tail_inputs(dtype, case):
    r = np.random.default_rng(11)
    n = 4096
    a = r.integers(0, 3, n).astype(np.int64)
    b = r.integers(0, 2, n).astype(np.int32)
    if np.dtype(dtype).kind == "f":
        v = r.normal(scale=1e3, size=n).astype(dtype)
    else:
        v = r.integers(-10_000, 10_000, n).astype(dtype)
    valid, live = None, np.ones(n, bool)
    if case != "no_nulls":
        valid = r.random(n) > 0.2
        if np.dtype(dtype).kind == "f":
            v[r.random(n) < 0.05] = np.nan
    if case == "live_mask":
        live = r.random(n) < 0.6
    elif case == "all_null_group":
        valid &= ~((a == 2) & (b == 0))
    elif case == "empty_slot":
        b[a == 1] = 1             # slot (1, 0) has no row
    return {"a": (a + LOS[0], None), "b": (b, None), "v": (v, valid)}, live


@pytest.mark.parametrize("case", ["no_nulls", "nulls", "live_mask",
                                  "all_null_group", "empty_slot"])
@pytest.mark.parametrize("dtype", ["float64", "int64", "int32"])
@pytest.mark.parametrize("op", REDUCE_OPS)
def test_reduce_route_matches_scatter_route(op, dtype, case, monkeypatch):
    tree, live = _tail_inputs(dtype, case)
    nullable = tree["v"][1] is not None
    got = {}
    for route in ("reduce", "scatter"):
        keys, ((data, valid),), ng = _tail(route, op, dtype, nullable,
                                           monkeypatch)(tree, live)
        ng = int(ng)
        got[route] = ([np.asarray(k)[:ng] for k in keys],
                      np.asarray(data)[:ng],
                      None if valid is None else np.asarray(valid)[:ng])
    (rk, rd, rv), (sk, sd, sv) = got["reduce"], got["scatter"]
    assert len(rd) == (5 if case == "empty_slot" else 6)
    for x, y in zip(rk, sk):
        assert x.tolist() == y.tolist()
    assert rd.dtype == sd.dtype
    assert (rv is None) == (sv is None)
    if rv is not None:
        assert rv.tolist() == sv.tolist()
        if case == "all_null_group":
            assert not rv.all()
    if rd.dtype.kind == "f" and op not in ("min", "max"):
        # a tree against a chain: same dtype, another order of additions
        np.testing.assert_allclose(rd, sd, rtol=1e-12, atol=0,
                                   equal_nan=True)
        if op == "mean" and case == "all_null_group":
            assert np.isnan(rd).sum() == 1
    else:
        assert rd.tolist() == sd.tolist()


def _route_of(t, keys, aggs):
    """(answer, dense_route as the groupby_agg span carried it)."""
    import json

    from bodo_tpu.utils import tracing
    old = config.tracing_level
    set_config(tracing_level=1)
    tracing.reset()
    try:
        out = R.groupby_agg(t, keys, aggs).to_pandas()
        routes = [e["args"].get("dense_route")
                  for e in json.loads(tracing.dump())["traceEvents"]
                  if e["name"] == "groupby_agg"]
    finally:
        set_config(tracing_level=old)
        tracing.reset()
    assert len(routes) == 1
    return out, routes[0]


@pytest.mark.parametrize("n_slots,route", [
    (R.DENSE_REDUCE_MAX_SLOTS, "reduce"),
    (R.DENSE_REDUCE_MAX_SLOTS + 1, "scatter")])
def test_route_flips_above_the_constant(one_dev, n_slots, route):
    r = np.random.default_rng(4)
    n = 20 * n_slots
    df = pd.DataFrame({"k": np.arange(n) % n_slots,
                       "v": r.normal(size=n),
                       "w": r.integers(-9, 9, n)})
    got, span_route = _route_of(
        Table.from_pandas(df), ["k"],
        [("v", "sum", "s"), ("w", "max", "hi"), ("v", "size", "n")])
    assert span_route == route
    exp = df.groupby("k", as_index=False).agg(
        s=("v", "sum"), hi=("w", "max"), n=("v", "size"))
    assert got["k"].tolist() == exp["k"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-12)
    assert got["hi"].tolist() == exp["hi"].tolist()
    assert got["n"].tolist() == exp["n"].tolist()


def test_spec_outside_the_set_keeps_the_scatter_route(one_dev):
    df = _df(seed=7)
    got, span_route = _route_of(Table.from_pandas(df), ["flag"],
                                [("v", "sum", "s"), ("v", "var", "vv")])
    assert span_route == "scatter"
    assert R.dense_route(2, ("sum", "var"), False) == "scatter"
    assert R.dense_route(2, ("sum", "mean"), False) == "reduce"
    assert R.dense_route(2, ("sum", "mean"), True) == "mxu"
    exp = df.groupby("flag", as_index=False).agg(s=("v", "sum"),
                                                 vv=("v", "var"))
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-12)
    np.testing.assert_allclose(got["vv"], exp["vv"], rtol=1e-12)


def test_fused_aggregate_carries_its_route(one_dev):
    """SQL group-by over a filter: the fused stage takes the dense tail,
    its span says which route, and `fusion.stats()` counts it."""
    import json

    import bodo_tpu
    from bodo_tpu.plan import fusion
    from bodo_tpu.utils import tracing
    df = _df(seed=8)
    ctx = bodo_tpu.sql.BodoSQLContext({"t": df})
    old = (config.tracing_level, config.result_cache)
    set_config(tracing_level=1, result_cache=False)
    tracing.reset()
    before = fusion.stats()["dense_reduce"]
    try:
        got = ctx.sql("select b, sum(v) as s, count(*) as n from t "
                      "where w > -40 group by b order by b").to_pandas()
        events = json.loads(tracing.dump())["traceEvents"]
    finally:
        set_config(tracing_level=old[0], result_cache=old[1])
        tracing.reset()
    routes = [e["args"].get("dense_route") for e in events
              if e["name"] == "fused_group"]
    assert routes == ["reduce"]
    assert fusion.stats()["dense_reduce"] == before + 1
    exp = df[df.w > -40].groupby("b", as_index=False).agg(
        s=("v", "sum"), n=("v", "size")).sort_values("b")
    assert got["b"].tolist() == exp["b"].tolist()
    np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-12)
    assert got["n"].tolist() == exp["n"].tolist()


# ---------------------------------------------------------------------------
# the LUT probes emit at the size of their result (PR 34): an inner
# join's probe touches no column; `_join_emit` gathers them once the
# host knows the count, or skips the compaction when every row hit
# ---------------------------------------------------------------------------

def _emit_tables(route, case):
    """(left, right, how) whose join takes `route`: the hash LUT's keys
    are the dense LUT's spread too thin for a dense slot space."""
    r = np.random.default_rng(11)
    n, nb = 4000, 300
    spread = 1 if route == "dense" else 1_000_003
    right = pd.DataFrame({"k": np.arange(nb) * spread,
                          "name": [f"n{i}" for i in range(nb)],
                          "z": np.arange(nb) * 1.5})
    how = "inner"
    if case == "all_hit":
        k = r.integers(0, nb, n)
    elif case == "none_hit":
        k = r.integers(nb, 2 * nb, n)
    elif case in ("five_pct", "most_hit"):
        share = 0.05 if case == "five_pct" else 0.7
        k = np.where(r.random(n) < share, r.integers(0, nb, n),
                     r.integers(nb, 2 * nb, n))
    else:                       # null_keys, left
        k = r.integers(0, 2 * nb, n)
        how = "left" if case == "left" else "inner"
    left = pd.DataFrame({"k": k * spread, "v": r.normal(size=n),
                         "w": r.integers(-9, 9, n).astype(np.int32)})
    if case == "null_keys":
        left["k"] = left["k"].astype("Int64").mask(r.random(n) < 0.2)
    return left, right, how


@pytest.mark.parametrize("case", ["all_hit", "none_hit", "five_pct",
                                  "most_hit", "null_keys", "left"])
@pytest.mark.parametrize("route", ["dense", "hash"])
def test_lut_join_emits_at_result_size(one_dev, route, case):
    from bodo_tpu.plan import fusion
    from bodo_tpu.table.table import round_capacity
    left, right, how = _emit_tables(route, case)
    tl, tr = Table.from_pandas(left), Table.from_pandas(right)
    before = fusion.stats()
    out = R.join_tables(tl, tr, ["k"], ["k"], how, null_equal=False)
    after = fusion.stats()
    delta = {k: after[k] - before[k] for k in
             ("join_dense", "join_hash", "join_sort", "join_emit",
              "join_emit_skipped")}
    want_route = {"join_dense": 0, "join_hash": 0, "join_sort": 0,
                  "join_" + route: 1}
    assert {k: delta[k] for k in want_route} == want_route
    exp = left.merge(right, on="k", how=how)
    if case == "all_hit":
        # every probe row found its key: nothing is compacted
        assert (delta["join_emit"], delta["join_emit_skipped"]) == (0, 1)
        assert out.capacity == tl.capacity
    elif case == "left":
        # a left join keeps every probe row: no emit either way
        assert (delta["join_emit"], delta["join_emit_skipped"]) == (0, 0)
        assert out.capacity == tl.capacity
    else:
        # the result is born at the capacity `rebucket` would leave it:
        # the probe table's while most rows hit (one program shape
        # whatever the count), that of its rows below the threshold
        assert (delta["join_emit"], delta["join_emit_skipped"]) == (1, 0)
        assert out.capacity == R.rebucket_capacity(len(exp), tl.capacity)
        assert out.capacity == (
            tl.capacity if case == "most_hit"
            else round_capacity(max(len(exp), 1)))
    assert out.nrows == len(exp)
    if case == "none_hit":
        assert out.nrows == 0
    got = out.to_pandas()
    assert list(got.columns) == list(exp.columns)
    # row for row: the compaction is stable, as pandas' merge is
    assert got["k"].astype("float64").tolist() == \
        exp["k"].astype("float64").tolist()
    np.testing.assert_array_equal(got["v"].to_numpy(), exp["v"].to_numpy())
    assert got["w"].tolist() == exp["w"].tolist()
    assert got["name"].fillna("<NA>").tolist() == \
        exp["name"].fillna("<NA>").tolist()
    np.testing.assert_array_equal(got["z"].to_numpy(dtype="float64"),
                                  exp["z"].to_numpy(dtype="float64"))
