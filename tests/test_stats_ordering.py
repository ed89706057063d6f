"""Cardinality estimation + greedy join ordering (plan/stats.py,
sql/planner._plan_from_where) and the runtime broadcast decision.

Replaces the role of the reference's vendored-DuckDB cost model
(bodo/pandas/plan.py get_plan_cardinality)."""

import numpy as np
import pandas as pd
import pytest

from bodo_tpu.plan import logical as L
from bodo_tpu.plan.stats import estimate, join_estimate, selectivity


def _q5_ctx(seed=0, n=20_000):
    from bodo_tpu.sql import BodoSQLContext
    r = np.random.default_rng(seed)
    fact = pd.DataFrame({"ck": r.integers(0, 2000, n),
                         "amt": r.random(n)})
    cust = pd.DataFrame({"ck": np.arange(2000),
                         "cnk": r.integers(0, 25, 2000)})
    nation = pd.DataFrame({"nk": np.arange(25), "rk": np.arange(25) % 5,
                           "nname": [f"n{i}" for i in range(25)]})
    region = pd.DataFrame({"rk": np.arange(5),
                           "rname": ["ASIA", "EUROPE", "AFRICA",
                                     "AMERICA", "MIDEAST"]})
    return BodoSQLContext({"fact": fact, "cust": cust, "nation": nation,
                           "region": region}), fact, cust, nation, region


_Q5 = """
select nname, sum(amt) as rev from fact, cust, nation, region
where fact.ck = cust.ck and cust.cnk = nation.nk
  and nation.rk = region.rk and rname = 'ASIA'
group by nname order by rev desc
"""


def test_estimates_basic(mesh8):
    t = L.FromPandas(pd.DataFrame({"a": np.arange(1000)}))
    est, raw = estimate(t)
    assert est == raw == 1000
    from bodo_tpu.plan.expr import BinOp, ColRef, Lit
    f = L.Filter(t, BinOp("==", ColRef("a"), Lit(5)))
    est_f, raw_f = estimate(f)
    assert est_f == 100 and raw_f == 1000  # eq selectivity 0.1
    assert selectivity(BinOp("<", ColRef("a"), Lit(5))) == 0.3
    # FK join: fact(10k) x dim(100) on dim's PK ≈ fact size
    assert join_estimate(10_000, 10_000, 100, 100) == 10_000
    # selective dim (filtered to 10 of 100) cuts the fact proportionally
    assert join_estimate(10_000, 10_000, 10, 100) == 1_000


def test_q5_join_order_puts_selective_dims_first(mesh8):
    ctx, *_ = _q5_ctx()
    plan = ctx.generate_plan(_Q5)

    # walk to the innermost join: its left subtree must contain the
    # filtered region/nation dims, not the fact table
    node = plan
    joins = []
    while node.children:
        if isinstance(node, L.Join):
            joins.append(node)
        node = node.children[0]
    assert joins, "no joins in plan"
    innermost = joins[-1]

    def leaf_cols(n, acc):
        if isinstance(n, L.FromPandas):
            acc.update(n.schema)
        for c in n.children:
            leaf_cols(c, acc)
        return acc

    left_cols = leaf_cols(innermost.left, set())
    assert "rname" in left_cols, "region not joined first"
    assert "amt" not in left_cols, "fact table joined too early"

    def has_filter(n):
        if isinstance(n, L.Filter):
            return True
        return any(has_filter(c) for c in n.children)
    assert has_filter(innermost.left), "region filter not pushed pre-join"


def test_q5_results_correct(mesh8):
    ctx, fact, cust, nation, region = _q5_ctx()
    got = ctx.sql(_Q5).to_pandas().reset_index(drop=True)
    exp = (fact.merge(cust, on="ck")
           .merge(nation, left_on="cnk", right_on="nk")
           .merge(region, on="rk").query("rname == 'ASIA'")
           .groupby("nname", as_index=False).agg(rev=("amt", "sum"))
           .sort_values("rev", ascending=False).reset_index(drop=True))
    assert got["nname"].tolist() == exp["nname"].tolist()
    np.testing.assert_allclose(got["rev"], exp["rev"], rtol=1e-9)


def test_runtime_broadcast_of_tiny_sharded_side(mesh8):
    """A 1D x 1D join where one side is tiny must take the broadcast
    path (small side gathered) instead of shuffling the big side."""
    import bodo_tpu.relational as R
    from bodo_tpu import Table
    r = np.random.default_rng(1)
    big = pd.DataFrame({"k": r.integers(0, 40, 20_000),
                        "v": r.random(20_000)})
    tiny = pd.DataFrame({"k": np.arange(40), "w": np.arange(40) * 2.0})
    calls = []
    orig = R.shuffle_by_key

    def spy(t, cols):
        calls.append(t.nrows)
        return orig(t, cols)
    R.shuffle_by_key = spy
    try:
        out = R.join_tables(Table.from_pandas(big).shard(),
                            Table.from_pandas(tiny).shard(),
                            ["k"], ["k"], "inner")
        got = out.to_pandas()
    finally:
        R.shuffle_by_key = orig
    exp = big.merge(tiny, on="k")
    assert len(got) == len(exp)
    # broadcast path: the 20k-row probe side was never hash-shuffled
    assert not any(n >= 20_000 for n in calls), calls


def test_runtime_broadcast_tiny_left_swaps(mesh8):
    import bodo_tpu.relational as R
    from bodo_tpu import Table
    r = np.random.default_rng(2)
    tiny = pd.DataFrame({"k": np.arange(40), "w": np.arange(40) * 2.0})
    big = pd.DataFrame({"k": r.integers(0, 40, 20_000),
                        "v": r.random(20_000), "w": r.random(20_000)})
    out = R.join_tables(Table.from_pandas(tiny).shard(),
                        Table.from_pandas(big).shard(),
                        ["k"], ["k"], "inner").to_pandas()
    exp = tiny.merge(big, on="k")
    assert list(out.columns) == list(exp.columns)
    assert len(out) == len(exp)
    g = out.sort_values(["k", "v"]).reset_index(drop=True)
    e = exp.sort_values(["k", "v"]).reset_index(drop=True)
    np.testing.assert_allclose(g["w_x"], e["w_x"], rtol=1e-12)


def test_select_star_keeps_from_order(mesh8):
    from bodo_tpu.sql import BodoSQLContext
    r = np.random.default_rng(3)
    fact = pd.DataFrame({"k": r.integers(0, 40, 5000),
                         "v": r.random(5000)})
    dim = pd.DataFrame({"k2": np.arange(40), "w": np.arange(40) * 1.0})
    ctx = BodoSQLContext({"fact": fact, "dim": dim})
    got = ctx.sql("select * from fact, dim where fact.k = dim.k2"
                  ).to_pandas()
    assert list(got.columns) == ["k", "v", "k2", "w"]


def test_frame_merge_chain_reorders(mesh8, tmp_path):
    """A 3-table pandas merge chain reorders by estimated cardinality:
    the big fact table joins the SMALLER filtered dimension first
    (VERDICT: the frame path used to run merges in user order)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import logical as L
    from bodo_tpu.plan.optimizer import optimize

    r = np.random.default_rng(0)
    fact = pd.DataFrame({"k1": r.integers(0, 50, 5000),
                         "k2": r.integers(0, 5, 5000),
                         "v": r.normal(size=5000)})
    dim_big = pd.DataFrame({"k1": np.arange(50),
                            "a": r.normal(size=50)})
    dim_small = pd.DataFrame({"k2": np.arange(5),
                              "b": r.normal(size=5)})
    pf, pb, ps = (str(tmp_path / f"{n}.pq")
                  for n in ("fact", "big", "small"))
    pq.write_table(pa.Table.from_pandas(fact), pf)
    pq.write_table(pa.Table.from_pandas(dim_big), pb)
    pq.write_table(pa.Table.from_pandas(dim_small), ps)

    f = (bd.read_parquet(pf)
         .merge(bd.read_parquet(pb), on="k1")
         .merge(bd.read_parquet(ps), on="k2"))
    opt = optimize(f._plan)

    joins = []

    def walk(n):
        if isinstance(n, L.Join):
            joins.append(n)
        for c in n.children:
            walk(c)
    walk(opt)
    assert len(joins) == 2
    # the innermost (first-executed) join must involve the small dim
    inner = joins[-1]
    schemas = [set(inner.left.schema), set(inner.right.schema)]
    assert any("b" in s for s in schemas), \
        "expected the 5-row dimension joined first"
    # and the result still matches pandas
    got = f.to_pandas().sort_values(["k1", "k2", "v"]) \
        .reset_index(drop=True)
    exp = (fact.merge(dim_big, on="k1").merge(dim_small, on="k2")
           .sort_values(["k1", "k2", "v"]).reset_index(drop=True))
    pd.testing.assert_frame_equal(got[exp.columns], exp,
                                  check_dtype=False)


def test_frame_merge_chain_suffix_guard(mesh8):
    """Chains where suffixes fire must NOT reorder (column meaning would
    change) — result must equal pandas user-order semantics."""
    import bodo_tpu.pandas_api as bd
    r = np.random.default_rng(1)
    a = pd.DataFrame({"k": np.arange(20), "v": r.normal(size=20)})
    b = pd.DataFrame({"k": np.arange(20), "v": r.normal(size=20)})
    c = pd.DataFrame({"k": np.arange(3), "w": r.normal(size=3)})
    f = (bd.from_pandas(a).merge(bd.from_pandas(b), on="k")
         .merge(bd.from_pandas(c), on="k"))
    got = f.to_pandas().sort_values("k").reset_index(drop=True)
    exp = (a.merge(b, on="k").merge(c, on="k")
           .sort_values("k").reset_index(drop=True))
    pd.testing.assert_frame_equal(got[exp.columns], exp,
                                  check_dtype=False)


def test_four_table_chain_reorders_as_one_unit(mesh8, tmp_path):
    """4-relation merge chains must reorder as a whole (review finding:
    bottom-up recursion used to hide the inner chain behind a
    projection)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    import bodo_tpu.pandas_api as bd
    from bodo_tpu.plan import logical as L
    from bodo_tpu.plan.optimizer import optimize

    r = np.random.default_rng(3)
    fact = pd.DataFrame({"k1": r.integers(0, 40, 4000),
                         "k2": r.integers(0, 30, 4000),
                         "k3": r.integers(0, 4, 4000),
                         "v": r.normal(size=4000)})
    d1 = pd.DataFrame({"k1": np.arange(40), "a": np.arange(40) * 1.0})
    d2 = pd.DataFrame({"k2": np.arange(30), "b": np.arange(30) * 1.0})
    d3 = pd.DataFrame({"k3": np.arange(4), "c": np.arange(4) * 1.0})
    paths = {}
    for name, df in (("fact", fact), ("d1", d1), ("d2", d2), ("d3", d3)):
        p = str(tmp_path / f"{name}.pq")
        pq.write_table(pa.Table.from_pandas(df), p)
        paths[name] = p
    f = (bd.read_parquet(paths["fact"])
         .merge(bd.read_parquet(paths["d1"]), on="k1")
         .merge(bd.read_parquet(paths["d2"]), on="k2")
         .merge(bd.read_parquet(paths["d3"]), on="k3"))
    opt = optimize(f._plan)

    joins = []

    def walk(n):
        if isinstance(n, L.Join):
            joins.append(n)
        for c in n.children:
            walk(c)
    walk(opt)
    assert len(joins) == 3
    # innermost join (executed first) must involve the 4-row dimension
    inner = joins[-1]
    assert any("c" in set(s.schema)
               for s in (inner.left, inner.right)), \
        "4-row dim should join first in the reordered chain"
    got = f.to_pandas()
    exp = (fact.merge(d1, on="k1").merge(d2, on="k2").merge(d3, on="k3"))
    assert len(got) == len(exp)


# ---------------------------------------------------------------------------
# join keys' distinct-value bounds (plan/stats.key_ndv_bound) and the one
# greedy loop (plan/stats.greedy_join_order)
# ---------------------------------------------------------------------------

def _bench_gen(name):
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from harness import spec
    return spec, spec.load_module("gen", name)


def _chain(plan):
    """The left-deep join chain under `plan`: (start relation, [Join...])
    in execution order."""
    node = plan
    while not isinstance(node, L.Join):
        node = node.children[0]
    joins = []
    while isinstance(node, L.Join):
        joins.append(node)
        node = node.left
    return node, joins[::-1]


def _table_of(rel):
    """Which source table a chain relation reads: its columns' prefix
    (`c_`, `l_`, ...) after the planner's flat `tN__` names."""
    return next(iter(rel.schema)).split("__")[-1].split("_")[0]


def test_q5_chain_never_joins_two_sides_that_both_repeat_their_keys(mesh8):
    """Suppliers and customers share 25 nation keys: priced by row counts
    alone the planner met them on `nationkey` (757 x 37,500 -> 1,142,129
    rows at the cell's scale); with the keys' bounds lineitem is joined
    on `l_suppkey` first and every join is foreign key to primary key."""
    from bodo_tpu.plan.physical import execute
    from bodo_tpu.sql import BodoSQLContext
    spec, gen = _bench_gen("tpch")
    frames = gen.generate({"orders": 150000, "structure_seed": 20260926},
                          1)["frames"]
    plan = BodoSQLContext(frames).generate_plan(spec.Query("tpch_q5").text)
    start, joins = _chain(plan)
    assert len(joins) == 5
    order = [_table_of(start)] + [_table_of(j.right) for j in joins]
    assert order.index("l") < order.index("c")
    assert {k.split("__")[-1] for k in
            joins[order.index("l") - 1].right_on} <= {"l_suppkey",
                                                      "l_orderkey"}
    # every relation as the engine filters it; the joins by pandas
    acc = execute(start).to_pandas()
    for j in joins:
        right = execute(j.right).to_pandas()
        assert not (acc.duplicated(j.left_on).any()
                    and right.duplicated(j.right_on).any()), \
            (j.left_on, j.right_on)
        acc = acc.merge(right, left_on=j.left_on, right_on=j.right_on)
        assert len(acc) <= len(frames["lineitem"])


def test_q9_keeps_its_order_and_runs_each_join_once(mesh8):
    """Every join of Q9 is foreign key to primary key, so the bounds
    change no price: the order is PERF.md section 5's, and the static
    and the run-time pass agree, so an execution realises five joins
    (nine when the two passes disagreed about `part` and `partsupp`)."""
    from bodo_tpu.config import config, set_config
    from bodo_tpu.plan import fusion
    from bodo_tpu.sql import BodoSQLContext
    spec, gen = _bench_gen("tpch_parts")
    frames = gen.generate({"orders": 30000, "structure_seed": 20260926},
                          1)["frames"]
    text = spec.Query("tpch_q9").text
    ctx = BodoSQLContext(frames)
    start, joins = _chain(ctx.generate_plan(text))
    assert [_table_of(start)] + [_table_of(j.right) for j in joins] == \
        ["n", "s", "l", "p", "ps", "o"]
    old = config.result_cache
    set_config(result_cache=False)
    try:
        for _ in range(2):  # the first run and a warm one
            before = fusion.stats()
            got = ctx.sql(text).to_pandas()
            after = fusion.stats()
            assert sum(after[k] - before[k] for k in (
                "join_dense", "join_hash", "join_sort", "join_fused")) == 5
    finally:
        set_config(result_cache=old)
    assert 0 < len(got) <= 175



@pytest.mark.parametrize("a,b,key_ndv,want", [
    # nationkey: 757 suppliers x 37,500 customers over 25 values
    ((757, 3750), (37500, 37500), 25, 757 * 37500 / 25),
    # a primary key: the range is the row count, nothing changes
    ((757, 3750), (1499994, 1499994), 3750, 757 * 1499994 / 3750),
    # a range wider than the table (sparse keys): the row count stays
    ((1000, 1000), (50000, 50000), 10**9, 50000),
    # a pair (partkey, suppkey): parts x suppliers exceeds both tables
    ((164276, 2999997), (400000, 400000), 100000 * 7500, 164276),
    # no bound at all
    ((164276, 2999997), (400000, 400000), None, 164276),
])
def test_join_estimate_caps_ndv_by_rows_and_by_key_bound(a, b, key_ndv,
                                                        want):
    got = join_estimate(*a, *b, key_ndv)
    assert got == pytest.approx(want)
    today = join_estimate(*a, *b)
    # never divides by more than min(raw rows): never under today's price
    assert got >= today
    if key_ndv is None or key_ndv >= min(a[1], b[1]):
        assert got == today


def test_key_without_a_bound_gives_todays_estimate(mesh8):
    """Float keys and computed keys have no bound: the join is priced by
    row counts alone, to the digit."""
    from bodo_tpu.plan.expr import BinOp, ColRef, Lit
    from bodo_tpu.plan.stats import join_key_ndv, key_ndv_bound
    r = np.random.default_rng(5)
    a = L.FromPandas(pd.DataFrame({"k": r.integers(0, 25, 700),
                                   "f": r.integers(0, 25, 700) * 1.0}))
    b = L.FromPandas(pd.DataFrame({"k2": r.integers(0, 25, 9000),
                                   "f2": r.integers(0, 25, 9000) * 1.0}))
    comp = L.Projection(b, [("k2", BinOp("+", ColRef("k2"), Lit(1)))])
    assert key_ndv_bound(a, "k") == 25
    assert key_ndv_bound(L.Filter(a, BinOp("<", ColRef("k"), Lit(3))),
                         "k") == 25  # of the source, whatever is observed
    assert key_ndv_bound(L.Projection(b, [("x", ColRef("k2"))]), "x") == 25
    assert key_ndv_bound(a, "f") is None
    assert key_ndv_bound(comp, "k2") is None
    assert join_key_ndv([(a, "k", comp, "k2")]) is None
    for left_on, right, right_on in ((["f"], b, ["f2"]),
                                     (["k"], comp, ["k2"])):
        j = L.Join(a, right, left_on, right_on, "inner")
        assert estimate(j)[0] == join_estimate(700, 700, 9000, 9000) \
            == 9000.0
    # the same join on the bounded keys: 25 values, not 700
    keyed = L.Join(a, b, ["k"], ["k2"], "inner")
    assert estimate(keyed)[0] == 700 * 9000 / 25
    # strings: the dictionary's length
    s = L.FromPandas(pd.DataFrame({"s": [f"n{i % 7}" for i in range(100)]}))
    assert key_ndv_bound(s, "s") == 7


def test_parquet_key_bound_comes_from_the_footer(mesh8, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    from bodo_tpu.plan.stats import key_ndv_bound
    r = np.random.default_rng(6)
    df = pd.DataFrame({"k": r.integers(10, 35, 5000),
                       "v": r.random(5000)})
    df.loc[0, "k"], df.loc[1, "k"] = 10, 34
    path = str(tmp_path / "t.pq")
    pq.write_table(pa.Table.from_pandas(df), path, row_group_size=1000)
    scan = L.ReadParquet(path)
    assert key_ndv_bound(scan, "k") == 25
    assert key_ndv_bound(scan, "v") is None


def test_resident_key_range_is_reduced_once(mesh8):
    """The join order reads a resident key column's range through one
    min/max reduction in the column's life: the first query on a context
    reduces each key column once, a second reduces none of them."""
    import bodo_tpu.relational as R
    from bodo_tpu.config import config, set_config
    from bodo_tpu.plan import adaptive
    from bodo_tpu.sql import BodoSQLContext
    r = np.random.default_rng(4)
    n = 20_000
    fact = pd.DataFrame({"fk": r.integers(0, 2000, n),
                         "fs": r.integers(0, 200, n), "amt": r.random(n)})
    cust = pd.DataFrame({"ck": np.arange(2000),
                         "cn": r.integers(0, 25, 2000)})
    supp = pd.DataFrame({"sk": np.arange(200),
                         "sn": r.integers(0, 25, 200)})
    ctx = BodoSQLContext({"fact": fact, "cust": cust, "supp": supp})
    keys = {id(ctx._tables[t].table.columns[c].data): c
            for t, cs in (("fact", ("fk", "fs")), ("cust", ("ck", "cn")),
                          ("supp", ("sk", "sn"))) for c in cs}
    reduced = []
    orig = R.reduce_table

    def spy(t, aggs):
        reduced.extend(keys[id(c.data)] for c in t.columns.values()
                       if id(c.data) in keys)
        return orig(t, aggs)
    q = ("select cn, sum(amt) as s from fact, cust, supp "
         "where fk = ck and fs = sk and cn = sn group by cn")
    old = config.result_cache
    set_config(result_cache=False)
    R.reduce_table = spy
    try:
        adaptive.reset()
        first = ctx.sql(q).to_pandas()
        assert sorted(reduced) == sorted(keys.values())
        st = adaptive.stats()
        assert st["join_est_keyed"] > 0 and st["join_est_unkeyed"] == 0
        del reduced[:]
        second = ctx.sql(q).to_pandas()
        assert reduced == []
    finally:
        R.reduce_table = orig
        set_config(result_cache=old)
    exp = (fact.merge(cust, left_on="fk", right_on="ck")
           .merge(supp, left_on=["fs", "cn"], right_on=["sk", "sn"])
           .groupby("cn", as_index=False).agg(s=("amt", "sum")))
    for got in (first, second):
        got = got.sort_values("cn").reset_index(drop=True)
        assert got["cn"].tolist() == exp["cn"].tolist()
        np.testing.assert_allclose(got["s"], exp["s"], rtol=1e-9)
