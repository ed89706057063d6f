"""Which side of a replicated inner join the LUT is built on
(relational.join_tables, keys_must_repeat; PR 36): a right side whose
keys must repeat is never built on, the left is when it can be, the
answer is pandas' whichever side was built, and the route span says so.
"""

import os
import sys

import numpy as np
import pandas as pd
import pytest

import bodo_tpu.relational as R
from bodo_tpu import Table
from bodo_tpu.config import config, set_config
from bodo_tpu.plan import fusion
from bodo_tpu.table import dtypes as dt
from bodo_tpu.table.table import Column

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import compare, spec  # noqa: E402

COUNTED = ("join_dense", "join_hash", "join_sort", "join_fused",
           "join_build_left", "join_build_skipped")


@pytest.fixture(autouse=True)
def _no_result_cache():
    # a repeated query has to run its joins again to be counted
    old = config.result_cache
    set_config(result_cache=False)
    yield
    set_config(result_cache=old)


def counted(before):
    after = fusion.stats()
    return {k: after[k] - before[k] for k in COUNTED if after[k] != before[k]}


def same_rows(got: pd.DataFrame, exp: pd.DataFrame):
    """Equal to pandas' merge after sorting: an inner join's rows come
    in the probe side's order, which is no longer always the left's."""
    assert list(got.columns) == list(exp.columns)
    assert len(got) == len(exp)
    if not len(exp):
        return
    # nulls sort last and compare equal to each other, column by column
    def norm(df):
        df = df.astype({c: "float64" for c in df.columns
                        if df[c].dtype.kind in "iuf" or
                        str(df[c].dtype) in ("Int64", "Float64")})
        return df.sort_values(list(df.columns)).reset_index(drop=True)
    pd.testing.assert_frame_equal(norm(got), norm(exp), check_dtype=False)


# (left, right, left_on, right_on, suffixes, null_equal, route, skipped):
# a left side of unique keys, a right side that repeats them; `skipped`
# counts the build sides `keys_must_repeat` refuses before any program
def swap_case(case):
    r = np.random.default_rng(36)
    n, nl = 5000, 50
    sfx, null_equal, on = ("_x", "_y"), False, (["k"], ["k"])
    lk = np.arange(nl) * 3
    rk = r.integers(0, 200, n)
    route, skipped = "dense", 1
    if case == "hash":
        # keys spread too thin for a dense slot space on either side, so
        # the right side's range proves nothing: its builds find the
        # duplicates, and the left's LUT is the hash table
        lk, rk, route, skipped = lk * 1_000_003, rk * 1_000_003, "hash", 0
    elif case == "empty":
        lk = lk + 1000            # no key of the right is in the left
        rk = np.concatenate([rk, [1200]])   # ... but its range holds them
    elif case == "all_hit":
        lk = np.arange(200)
    left = pd.DataFrame({"k": lk, "a": np.arange(len(lk)) * 0.5,
                         "v": np.arange(len(lk))})
    right = pd.DataFrame({"k": rk, "v": r.normal(size=len(rk)),
                          "w": r.integers(-9, 9, len(rk)).astype(np.int32)})
    if case == "pair":
        left["k2"] = np.arange(nl) % 4
        right["k2"] = r.integers(0, 4, n)
        on = (["k", "k2"], ["k", "k2"])
    elif case == "other_names":
        # the right's key is named as a left column that is no key
        left = left.rename(columns={"k": "lk", "a": "rk"})
        right = right.rename(columns={"k": "rk"})
        on = (["lk"], ["rk"])
    elif case == "string":
        left["k"] = [f"s{i:03d}" for i in lk]
        right["k"] = [f"s{i:03d}" for i in rk]
    elif case == "suffixes":
        sfx = ("_l", "_r")
    elif case in ("nullable_sql", "nullable_pandas"):
        # one null on the left (a key like any other under pandas'
        # semantics), many on the right
        left["k"] = left["k"].astype("Int64")
        left.loc[7, "k"] = pd.NA
        right["k"] = right["k"].astype("Int64").mask(r.random(n) < 0.1)
        null_equal = case == "nullable_pandas"
        # a `valid` mask proves nothing; under pandas' semantics two
        # nullable sides make no dense LUT either way round
        route, skipped = ("hash" if null_equal else "dense"), 0
    return left, right, on, sfx, null_equal, route, skipped


@pytest.mark.parametrize("case", [
    "single", "hash", "pair", "other_names", "string", "suffixes",
    "nullable_sql", "nullable_pandas", "empty", "all_hit"])
def test_inner_join_builds_on_the_unique_left(one_dev, case):
    left, right, (lon, ron), sfx, null_equal, route, skipped = \
        swap_case(case)
    tl, tr = Table.from_pandas(left), Table.from_pandas(right)
    before = fusion.stats()
    out = R.join_tables(tl, tr, lon, ron, "inner", sfx,
                        null_equal=null_equal)
    want = {"join_" + route: 1, "join_build_left": 1}
    if skipped:
        want["join_build_skipped"] = skipped
    assert counted(before) == want
    l, r = left, right
    if not null_equal and case.startswith("nullable"):
        l, r = left.dropna(subset=["k"]), right.dropna(subset=["k"])
    exp = l.merge(r, left_on=lon, right_on=ron, how="inner", suffixes=sfx)
    if case == "empty":
        assert out.nrows == 0
    elif case == "all_hit":
        assert out.nrows == len(right)
    elif case == "nullable_pandas":
        assert exp["k"].isna().sum() == right["k"].isna().sum() > 0
    same_rows(out.to_pandas(), exp)


@pytest.mark.parametrize("case", ["many_to_many", "many_to_many_built",
                                  "left_join", "larger_left"])
def test_joins_that_are_not_swapped(one_dev, case):
    r = np.random.default_rng(37)
    left = pd.DataFrame({"k": np.arange(50) * 3, "a": np.arange(50) * 0.5})
    right = pd.DataFrame({"k": r.integers(0, 200, 5000),
                          "w": r.normal(size=5000)})
    how, skipped = "inner", 1
    if case == "many_to_many":
        # the left repeats as well, 200 rows in a range of 148: both
        # sides are refused unbuilt
        left = pd.concat([left] * 4, ignore_index=True)
        skipped = 2
    elif case == "many_to_many_built":
        # 100 rows in that range: the left's build finds the duplicates
        left = pd.concat([left] * 2, ignore_index=True)
    elif case == "left_join":
        how = "left"
    else:
        # a unique left of more rows than the right is not built on
        left = pd.DataFrame({"k": np.arange(6000), "a": np.arange(6000.0)})
    before = fusion.stats()
    out = R.join_tables(Table.from_pandas(left), Table.from_pandas(right),
                        ["k"], ["k"], how, null_equal=False)
    assert counted(before) == {"join_sort": 1, "join_build_skipped": skipped}
    same_rows(out.to_pandas(), left.merge(right, on="k", how=how))


def test_keys_must_repeat(one_dev):
    n = 100
    t = Table.from_pandas(pd.DataFrame({
        "k": np.arange(n) % 10, "b": np.arange(n) % 2 == 0,
        "s": [f"s{i % 7}" for i in range(n)],
        "f": np.arange(n) * 0.5}))
    tn = Table.from_pandas(pd.DataFrame({
        "n": pd.array([1, None] * 50, dtype="Int64")}))
    assert t.column("k").vrange is None and t.column("k").valid is None
    # without a bound the host knows nothing of an integer column
    assert not R.keys_must_repeat(t, ["k"])
    assert not R.keys_must_repeat(t, [])
    # the pigeonhole: more rows than values, and not at rows == range
    assert R.keys_must_repeat(t, ["k"], [10])
    assert R.keys_must_repeat(t, ["k"], [n - 1])
    assert not R.keys_must_repeat(t, ["k"], [n])
    assert not R.keys_must_repeat(t, ["k"], [n + 1])
    # what the host has: a dictionary's size, a bool's two, a `vrange`
    assert R.keys_must_repeat(t, ["s"]) and R.keys_must_repeat(t, ["b"])
    assert R.keys_must_repeat(t, ["s", "b"])            # 14 < 100
    assert not R.keys_must_repeat(t, ["s", "b", "k"], [None, None, 8])
    assert R.keys_must_repeat(t, ["s", "b", "k"], [None, None, 7])
    assert not R.keys_must_repeat(t, ["s", "k"])         # one has none
    c = t.column("k")
    tv = t.with_columns({"k": Column(c.data, None, c.dtype, None, (0, 9))})
    assert R.keys_must_repeat(tv, ["k"])
    assert not R.keys_must_repeat(tv, ["k"], [1000])     # the caller's
    # a float has no bound, a `valid` mask ends the argument
    assert not R.keys_must_repeat(t, ["f"])
    assert tn.column("n").valid is not None
    assert not R.keys_must_repeat(tn, ["n"], [2])
    cv = Column(c.data, c.data >= 0, dt.INT64, None, (0, 9))
    assert not R.keys_must_repeat(t.with_columns({"k": cv}), ["k"], [10])


def _dim_fact():
    r = np.random.default_rng(38)
    dim = pd.DataFrame({"k": np.arange(50), "g": r.integers(0, 7, 50),
                        "dim": r.normal(size=50)})
    fact = pd.DataFrame({"k": r.integers(0, 50, 4000),
                         "v": r.normal(size=4000)})
    return dim, fact


def test_fused_group_skips_a_build_side_that_repeats(one_dev, tmp_path):
    """[Filter -> Join -> Projection] with the fact table on the right:
    the group asks the planner's bound before `build_hash_table`, falls
    back having built nothing, and the per-node join builds on the
    left."""
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import fusion_join
    dim, fact = _dim_fact()
    bl, br = bd.from_pandas(dim), bd.from_pandas(fact)

    def run():
        j = bl[bl["g"] < 5].merge(br, on="k", how="inner")
        return j.assign(u=j["v"] + j["dim"]).to_pandas()

    exp = dim[dim["g"] < 5].merge(fact, on="k").assign(
        u=lambda d: d["v"] + d["dim"])
    same_rows(run(), exp)            # compiles; the bound is reduced once
    before, jbefore = fusion.stats(), fusion_join.stats()
    out = []
    spans, modules = profiled(tmp_path, lambda: out.append(run()))
    jafter = fusion_join.stats()
    same_rows(out[0], exp)
    # once in the group, once in the per-node join's dense try
    assert counted(before) == {"join_dense": 1, "join_build_left": 1,
                               "join_build_skipped": 2}
    assert jafter["fallbacks"] - jbefore["fallbacks"] == 1
    assert jafter["build_cache"] == jbefore["build_cache"]
    assert "jit_join_build_fused" not in modules
    assert "jit_join_build_dense" in modules
    # exactly one route span, the join's own sides, the build side named
    routes = [s for s in spans if s[1].startswith("bodo:join.")]
    assert [s[1] for s in routes] == ["bodo:join.dense"]
    args = routes[0][4]
    assert args["build"] == "left"
    assert (args["rows_left"], args["rows_right"]) == \
        (int((dim["g"] < 5).sum()), len(fact))
    assert args["rows_out"] == len(exp)


@pytest.mark.parametrize("route", ["dense", "hash"])
def test_one_route_span_a_join_says_which_side_was_built(
        one_dev, tmp_path, route):
    left, right, (lon, ron), sfx, null_equal, want, _ = swap_case(
        "single" if route == "dense" else "hash")
    assert want == route
    tl, tr = Table.from_pandas(left), Table.from_pandas(right)
    # the right built on, as ever: the same join the other way round
    for i, (a, b, built) in enumerate([(tl, tr, "left"), (tr, tl, None)]):
        spans, _ = profiled(tmp_path / str(i), lambda: R.join_tables(
            a, b, lon, ron, "inner", sfx, null_equal=null_equal))
        routes = [s for s in spans if s[1].startswith("bodo:join.")]
        assert [s[1] for s in routes] == ["bodo:join." + route]
        args = routes[0][4]
        assert args.get("build") == built
        assert (args["rows_left"], args["rows_right"]) == (a.nrows, b.nrows)
        (call,) = [s for s in spans if s[1] == "bodo:join_tables"]
        assert call[2] <= routes[0][2] and routes[0][3] <= call[3]


# the three join cells at a rehearsal's scale: Q9 and Q18 where their cell
# tests run them, Q5 at the smallest round scale whose join order is the
# cell's (test_stats_ordering: below it suppliers meet customers on
# `nationkey`, a many-to-many join that has to sort)
@pytest.mark.parametrize("name,generator,orders", [
    ("tpch_q5", "tpch", 150000), ("tpch_q9", "tpch_parts", 30000),
    ("tpch_q18", "tpch_volume", 60000)])
def test_the_join_cells_sort_no_table(one_dev, name, generator, orders):
    from bodo_tpu.sql import BodoSQLContext
    query = spec.Query(name)
    inputs = spec.load_module("gen", generator).generate(
        {"orders": orders, "structure_seed": 20260926}, 2147483777)
    ref = query.reference().answer(inputs)
    assert len(ref)
    ctx = BodoSQLContext(inputs["frames"])
    for _ in range(2):          # the first query and a warm one
        before = fusion.stats()
        got = ctx.sql(query.text).to_pandas()
        took = counted(before)
        assert "join_sort" not in took
        assert took["join_build_left"] >= 1
        assert took["join_build_skipped"] >= took["join_build_left"]
        gap = compare.answer_gap(got, ref)
        assert gap["columns_differ"] == gap["rows_differ"] == 0
        assert gap["exact_cells_differ"] == 0
        assert gap["float_rel_gap"] <= query.limits["float_rel_gap"]
