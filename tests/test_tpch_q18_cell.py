"""TPC-H Q18 as the benchmark's cell `tpch_q18` runs it, small, on the
CPU: the engine against the cell's plain reference on the cell's
generator, the generator's shape (the same sizes for every seed, other
large orders), the `bodo:groupby.<route>` spans the cell's reader reads,
and a float64 group key against pandas.
"""

import os
import sys

import jax
import numpy as np
import pandas as pd
import pytest

import bodo_tpu
from bodo_tpu import Table
from bodo_tpu import relational as R
from bodo_tpu.config import config, set_config
from bodo_tpu.ops import pallas_kernels as PK
from bodo_tpu.parallel import mesh as mesh_mod
from bodo_tpu.plan import fusion
from bodo_tpu.sql import BodoSQLContext
from bodo_tpu.utils import tracing

from test_profiler_spans import profiled

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
sys.path.insert(0, BENCH)
from harness import compare, spec  # noqa: E402

SEEDS = [1, 2147483777, 2200000001]
STRUCTURE = {"structure_seed": 20260926}
# the smallest round scale whose answer has rows (dbgen's share of large
# orders is about 1 in 26,000)
ORDERS = 60000
ROUTES = ("groupby_dense", "groupby_packed", "groupby_hashed",
          "groupby_sort", "groupby_fused")


@pytest.fixture(scope="module")
def gen():
    return spec.load_module("gen", "tpch_volume")


@pytest.fixture(scope="module")
def query():
    return spec.Query("tpch_q18")


@pytest.fixture(autouse=True)
def _no_result_cache():
    old = config.result_cache
    set_config(result_cache=False)
    yield
    set_config(result_cache=old)


def large_orders(frames):
    qty = frames["lineitem"].groupby("l_orderkey")["l_quantity"].sum()
    return set(qty.index[qty > 300])


def routes_taken(before):
    after = fusion.stats()
    return {k: after[k] - before[k] for k in ROUTES if after[k] != before[k]}


# ------------------------------------------------- engine against reference
@pytest.mark.parametrize("seed", SEEDS)
def test_engine_gives_the_reference_answer(gen, query, seed, mesh8):
    inputs = gen.generate({"orders": ORDERS, **STRUCTURE}, seed)
    got = BodoSQLContext(inputs["frames"]).sql(query.text).to_pandas()
    ref = query.reference().answer(inputs)
    assert 0 < len(ref) == len(large_orders(inputs["frames"])) <= 100
    assert list(ref.columns) == ["c_name", "c_custkey", "o_orderkey",
                                 "o_orderdate", "o_totalprice", "sum_qty"]
    gap = compare.answer_gap(got, ref)
    assert gap["columns_differ"] == gap["rows_differ"] == 0
    # every key cell, in the reference's order: price down, then date
    assert gap["exact_cells_differ"] == 0
    assert gap["float_rel_gap"] == 0.0
    assert ref["o_totalprice"].is_monotonic_decreasing
    assert (ref["sum_qty"] > 300).all()


# ---------------------------------------------------- the generator's shape
def test_sizes_do_not_depend_on_the_seed_and_the_large_orders_do(gen):
    p = {"orders": ORDERS, **STRUCTURE}
    runs = [gen.generate(p, s) for s in SEEDS]
    frames = [r["frames"] for r in runs]
    assert runs[0]["rows"] == runs[1]["rows"] == runs[2]["rows"]
    assert set(frames[0]) == {"customer", "orders", "lineitem"}
    large = [large_orders(f) for f in frames]
    assert len(large[0]) == len(large[1]) == len(large[2]) > 0
    assert large[0] != large[1] != large[2]
    a, b = frames[0], frames[1]
    # keys, names and dates are structure
    assert a["customer"].equals(b["customer"])
    for c in ("o_orderkey", "o_custkey", "o_orderdate"):
        assert a["orders"][c].equals(b["orders"][c])
    assert a["lineitem"]["l_orderkey"].equals(b["lineitem"]["l_orderkey"])
    # prices are the seed's; quantities the same multiset of per-order
    # vectors, dealt to other orders of the same number of lines
    assert not a["orders"]["o_totalprice"].equals(b["orders"]["o_totalprice"])
    assert not a["lineitem"]["l_quantity"].equals(b["lineitem"]["l_quantity"])

    def vectors(f):
        return sorted(f["lineitem"].groupby("l_orderkey")["l_quantity"]
                      .agg(tuple))
    assert vectors(a) == vectors(b)
    # only an order of seven lines can pass 300
    lines = a["lineitem"].groupby("l_orderkey").size()
    assert (lines[sorted(large[0])] == 7).all()
    assert gen.generate(p, SEEDS[0])["frames"]["lineitem"].equals(
        a["lineitem"])
    other = gen.generate({"orders": ORDERS, "structure_seed": 7}, SEEDS[0])
    assert vectors(other["frames"]) != vectors(a)


def test_quantities_are_dbgen_s_and_so_is_the_share_of_large_orders(gen):
    f = gen.generate({"orders": 300000, **STRUCTURE}, SEEDS[0])["frames"]
    qty = f["lineitem"]["l_quantity"]
    assert qty.dtype == np.float64 and qty.min() == 1 and qty.max() == 50
    assert (qty == qty.round()).all() and abs(qty.mean() - 25.5) < 0.1
    lines = f["lineitem"].groupby("l_orderkey").size()
    assert lines.min() == 1 and lines.max() == 7
    # the share that seven draws of 1..50 pass 300, by convolution
    one = np.full(50, 1 / 50)
    dist = one
    for _ in range(6):
        dist = np.convolve(dist, one)
    share = dist[300 - 7 + 1:].sum() / 7      # index 0 is a sum of 7
    assert 50 < share * 1_500_000 < 65        # the specification: 57
    expected = share * 300000
    assert expected / 3 < len(large_orders(f)) < expected * 3


def test_totalprice_is_the_rounded_sum_over_the_lines_and_name_the_key(gen):
    p = {"orders": 7500, **STRUCTURE}
    ours = gen.generate(p, SEEDS[1])["frames"]
    li = spec.load_module("gen", "tpch").generate(p, SEEDS[1])["frames"][
        "lineitem"]
    charged = li["l_extendedprice"] * (1 + li["l_tax"]) * (1 - li["l_discount"])
    want = charged.groupby(li["l_orderkey"]).sum()
    price = ours["orders"]["o_totalprice"]
    assert np.abs(price.to_numpy() - want.to_numpy()).max() <= 0.0051
    assert (price == price.round(2)).all()
    cust = ours["customer"]
    assert cust["c_name"].tolist() == [
        "Customer#%09d" % k for k in cust["c_custkey"]]
    assert cust["c_name"].nunique() == len(cust)


@pytest.mark.parametrize("seed", SEEDS)
def test_shared_columns_are_gen_tpch_s_own_but_l_quantity(gen, seed):
    p = {"orders": 7500, **STRUCTURE}
    ours = gen.generate(p, seed)["frames"]
    theirs = spec.load_module("gen", "tpch").generate(p, seed)["frames"]
    shared = 0
    for t in ours:
        for c in set(ours[t].columns) & set(theirs[t].columns):
            if c == "l_quantity":
                continue
            assert ours[t][c].equals(theirs[t][c]), (t, c)
            shared += 1
    assert shared == 5
    # the columns Q18's file lists, and no others
    reads = spec.Query("tpch_q18").reads
    assert {t: sorted(df.columns) for t, df in ours.items()} \
        == {t: sorted(cols) for t, cols in reads.items()}


# ------------------------------------------------------------------- spans
def test_groupby_routes_reach_the_profiler_and_the_counters(
        gen, query, tmp_path, mesh8):
    inputs = gen.generate({"orders": ORDERS, **STRUCTURE}, SEEDS[0])
    assert config.tracing_level == 0
    tracing.reset()
    # one chip, as the cell: the spans are a replicated table's
    with mesh_mod.use_mesh(bodo_tpu.make_mesh(jax.devices()[:1])):
        ctx = BodoSQLContext(inputs["frames"])
        before = fusion.stats()
        spans, _ = profiled(tmp_path,
                            lambda: ctx.sql(query.text).to_pandas())
    routes = [s for s in spans if s[1].startswith("bodo:groupby.")]
    # the subquery's aggregate (one slot an order), DISTINCT over the
    # orders the HAVING kept, the five-key group-by with its float64 key
    assert sorted(s[1] for s in routes) == [
        "bodo:groupby.fused", "bodo:groupby.hashed", "bodo:groupby.sort"]
    by_name = {s[1]: s[4] for s in routes}
    fused = by_name["bodo:groupby.fused"]
    assert fused["keys"] == 1 and fused["slots"] == ORDERS
    assert fused["rows_in"] == len(inputs["frames"]["lineitem"])
    assert fused["dense_route"] == "scatter"
    large = len(large_orders(inputs["frames"]))
    assert by_name["bodo:groupby.hashed"]["rows_in"] == large
    last = by_name["bodo:groupby.sort"]
    assert last["keys"] == 5 and last["rows_in"] == 7 * large
    assert routes_taken(before) == {"groupby_fused": 1, "groupby_hashed": 1,
                                    "groupby_sort": 1}
    # with tracing off the spans went to the profiler alone
    assert not tracing.has_events()


# ------------------------------------------------------ float64 group keys
KEY = np.array([0.0, -0.0, 1.5, np.nan, 1.5, -2.25, 1e300, -0.0, np.nan,
                1.5, 1e-300, 1e300])


@pytest.mark.parametrize("keys", [["p"], ["k", "p"], ["p", "k"]],
                         ids=["alone", "after_int64", "before_int64"])
def test_float64_group_key_equals_pandas_on_the_sort_route(keys, mesh8):
    df = pd.DataFrame({"p": KEY, "k": np.arange(len(KEY), dtype=np.int64) % 2,
                       "v": np.arange(len(KEY), dtype=np.float64)})
    before = fusion.stats()
    got = R.groupby_agg(Table.from_pandas(df), keys,
                        [("v", "sum", "s"), ("v", "size", "n")]).to_pandas()
    assert routes_taken(before) == {"groupby_sort": 1}
    # pandas: NaN keys dropped, -0.0 and 0.0 one group, keys ascending
    want = df.groupby(keys, as_index=False).agg(s=("v", "sum"),
                                                n=("v", "size"))
    assert len(got) == len(want)
    for c in keys + ["s", "n"]:
        assert got[c].tolist() == want[c].tolist(), c
    assert not got["p"].isna().any()
    assert (got["p"] == 0.0).sum() == (2 if "k" in keys else 1)


def test_other_keys_stay_on_the_hashed_route(mesh8):
    """A wide int64 key (no slot space, nothing to pack) hashes as before;
    so does a float32 one, whose bits the TPU compiler does give."""
    wide = np.array([7, 1 << 40, -(1 << 50), 7, 0, 1 << 40], dtype=np.int64)
    for key in (wide, np.array([0.5, 1.5, 0.5, 2.0, 1.5, -1.0], np.float32)):
        df = pd.DataFrame({"k": key, "v": np.arange(6.0)})
        before = fusion.stats()
        got = R.groupby_agg(Table.from_pandas(df), ["k"],
                            [("v", "sum", "s")]).to_pandas()
        assert routes_taken(before) == {"groupby_hashed": 1}
        want = df.groupby("k", as_index=False).agg(s=("v", "sum"))
        assert got["k"].tolist() == want["k"].tolist()
        assert got["s"].tolist() == want["s"].tolist()


def test_key_only_group_by_with_pallas_on(mesh8):
    """Q18's DISTINCT is a group-by with no aggregate; with Pallas on (the
    chip, or interpret mode here) its few groups met the MXU gate, which
    had nothing to stack."""
    df = pd.DataFrame({"k": np.array([7, 1 << 40, 7, 0, 1 << 40],
                                     dtype=np.int64)})
    old = PK.FORCE_INTERPRET
    PK.FORCE_INTERPRET = True
    try:
        got = R.groupby_agg(Table.from_pandas(df), ["k"], []).to_pandas()
    finally:
        PK.FORCE_INTERPRET = old
    assert got["k"].tolist() == [0, 7, 1 << 40]
