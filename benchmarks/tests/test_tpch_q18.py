"""Cell `tpch_q18` (PR 35): the float32 control of Q18's reference, the
planted faults on the cell, its generator's sizes at two seeds, and the
reader `groupby_sort_routes` on made-up intervals. The cell's two
rehearsals and its configuration's case are `test_selftests.py`'s, which
finds new cells and configurations by itself.
Run by hand: `JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q`.
"""

import json
import os
import subprocess
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
from harness import compare, spec  # noqa: E402
from test_spans import made_up  # noqa: E402

# dbgen's share of large orders is about 1 in 26,000: the smallest round
# scale whose answer has rows
ORDERS = 60000


def large_orders(frames):
    qty = frames["lineitem"].groupby("l_orderkey")["l_quantity"].sum()
    return set(qty.index[qty > 300])


@pytest.mark.parametrize("seed", [1, 2147483777, 2200000001])
def test_float32_control_fails(seed):
    q = spec.Query("tpch_q18")
    inputs = spec.load_module("gen", q.meta["generator"]).generate(
        {"orders": ORDERS, "structure_seed": 7}, seed)
    ref = q.reference().answer(inputs)
    assert 0 < len(ref) <= 100
    control = q.reference().answer(inputs, precision="float32")
    ok, compared = compare.judge([compare.answer_gap(ref, ref)], 0, q.limits)
    assert ok, compared
    ok, compared = compare.judge([compare.answer_gap(control, ref)], 0,
                                 q.limits)
    assert not ok, compared
    gap = {c["name"]: c for c in compared}["float_rel_gap"]
    assert gap["value"] >= 3 * gap["limit"], gap
    # float32 fails by the float limit alone: the sums of quantities are
    # whole numbers under 351 and survive, so the same orders pass the
    # HAVING, and the rows, keys and order are the same
    assert all(c["value"] == 0 for c in compared
               if c["name"] != "float_rel_gap"), compared
    assert (control["sum_qty"] == ref["sum_qty"]).all()


def test_sizes_do_not_depend_on_the_seed():
    gen = spec.load_module("gen", "tpch_volume")
    p = {"orders": ORDERS, "structure_seed": 3}
    a, b = gen.generate(p, 1), gen.generate(p, 2147483659)
    assert a["rows"] == b["rows"]
    assert {t: df.shape for t, df in a["frames"].items()} \
        == {t: df.shape for t, df in b["frames"].items()}
    la, lb = large_orders(a["frames"]), large_orders(b["frames"])
    assert len(la) == len(lb) > 0 and la != lb
    ref = spec.load_module("reference", "tpch_q18")
    ra, rb = ref.answer(a), ref.answer(b)
    assert len(ra) == len(rb) == len(la)
    assert ra["o_orderkey"].tolist() != rb["o_orderkey"].tolist()
    assert sorted(ra["sum_qty"]) == sorted(rb["sum_qty"])


@pytest.mark.parametrize("fault,correct", [("none", True), ("answer", False),
                                           ("count", False),
                                           ("half", False)])
def test_fault_reads_not_correct(fault, correct):
    # 60,000 orders whatever step of the ladder the cell stands on
    orders = spec.Cell("tpch_q18").config["orders"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault,
         "tpch_q18", repr(ORDERS / orders)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert "rehearsal" in last
    assert last["correct"] is correct, last["compared"]


def run_of(names, starts, ends, queries=2):
    return types.SimpleNamespace(
        trace=made_up(names, starts, ends, (0.0, 10e9)),
        traced_queries=queries)


@pytest.fixture(scope="module")
def reader():
    return spec.load_module("layer_metrics", "groupby_sort_routes")


def test_reader_reads_nothing_from_a_program_without_the_spans(reader):
    run = run_of(["bodo:query", "bodo:groupby_agg", "bodo:Aggregate",
                  "bodo:join.sort", "bodo:groupby_sharded",
                  "PjitFunction(groupby_local)"],
                 [0, 1e9, 1e9, 2e9, 3e9, 4e9],
                 [9e9, 3e9, 3e9, 2.5e9, 3.5e9, 4.5e9])
    assert reader.read(run) is None
    assert reader.read(run_of([], [], [])) is None


def test_reader_reads_zero_where_no_group_by_sorts(reader):
    run = run_of(["bodo:query", "bodo:groupby.dense", "bodo:groupby.fused",
                  "bodo:groupby.packed", "bodo:groupby.hashed"],
                 [0, 1e9, 2e9, 3e9, 3.1e9], [9e9, 1.5e9, 2.5e9, 4e9, 3.9e9])
    assert reader.read(run) == 0.0


def test_reader_counts_the_sort_routes_that_start_in_the_window(reader):
    run = run_of(
        ["bodo:groupby.sort", "bodo:groupby.fused", "bodo:groupby.sort",
         "bodo:groupby.sort", "bodo:groupby.sort", "bodo:groupby.hashed",
         "bodo:join.sort"],
        # the fourth sort starts after the window's end and the fifth
        # before its start; a join's sort is not a group-by's
        [1e9, 2e9, 3e9, 11e9, -1e9, 4e9, 5e9],
        [2e9, 3e9, 4e9, 12e9, 0.5e9, 5e9, 6e9])
    assert reader.read(run) == pytest.approx(2 / 2)
    assert reader.read(run_of(["bodo:groupby.sort"], [1e9], [2e9],
                              queries=4)) == pytest.approx(0.25)
    assert reader.read(run_of(["bodo:groupby.sort"], [1e9], [2e9],
                              queries=0)) is None
