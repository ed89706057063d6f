"""Cell `tpch_q9` (PR 31): the float32 control of Q9's reference, the
planted faults on the cell, and its two readers (`join_sort_routes`,
`strpred_lut_ms`) on made-up intervals. The cell's two rehearsals and its
configuration's case are `test_selftests.py`'s, which finds new cells
and configurations by itself.
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


@pytest.mark.parametrize("seed", [1, 2147483777, 2200000001])
def test_float32_control_fails(seed):
    q = spec.Query("tpch_q9")
    inputs = spec.load_module("gen", q.meta["generator"]).generate(
        {"orders": 30000, "structure_seed": 7}, seed)
    ref = q.reference().answer(inputs)
    assert 0 < len(ref) <= 175
    control = q.reference().answer(inputs, precision="float32")
    ok, compared = compare.judge([compare.answer_gap(ref, ref)], 0, q.limits)
    assert ok, compared
    ok, compared = compare.judge([compare.answer_gap(control, ref)], 0,
                                 q.limits)
    assert not ok, compared
    gap = {c["name"]: c for c in compared}["float_rel_gap"]
    assert gap["value"] >= 3 * gap["limit"], gap
    # float32 fails by the float limit alone: the same rows, keys and order
    assert all(c["value"] == 0 for c in compared
               if c["name"] != "float_rel_gap"), compared


@pytest.mark.parametrize("fault,correct", [("none", True), ("answer", False),
                                           ("half", False)])
def test_fault_reads_not_correct(fault, correct):
    # 7,500 orders whatever step of the ladder the cell stands on
    orders = spec.Cell("tpch_q9").config["orders"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "fault_driver.py"), fault,
         "tpch_q9", repr(7500 / orders)],
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
def readers():
    return (spec.load_module("layer_metrics", "join_sort_routes"),
            spec.load_module("layer_metrics", "strpred_lut_ms"))


def test_readers_read_nothing_from_a_program_without_the_spans(readers):
    routes, lut = readers
    run = run_of(["bodo:query", "bodo:join_tables", "bodo:Join",
                  "PjitFunction(join_local)"], [0, 1e9, 1e9, 2e9],
                 [9e9, 3e9, 3e9, 2.5e9])
    assert routes.read(run) is None
    assert lut.read(run) is None
    assert routes.read(run_of([], [], [])) is None
    assert lut.read(run_of([], [], [])) is None


def test_readers_read_zero_where_the_engine_writes_spans_and_none_of_theirs(
        readers):
    routes, lut = readers
    run = run_of(["bodo:query", "bodo:join.dense", "bodo:join.hash",
                  "bodo:join.fused"], [0, 1e9, 2e9, 3e9],
                 [9e9, 1.5e9, 2.5e9, 3.5e9])
    assert routes.read(run) == 0.0
    assert lut.read(run) == 0.0
    # the LUT's span alone says the program writes them
    run = run_of(["bodo:strpred.lut"], [1e9], [1.5e9])
    assert routes.read(run) is None
    assert lut.read(run) == pytest.approx(250.0)


def test_readers_count_sort_routes_and_unite_lut_spans(readers):
    routes, lut = readers
    run = run_of(
        ["bodo:join.sort", "bodo:join.dense", "bodo:join.sort",
         "bodo:join.sort", "bodo:join.sort", "bodo:strpred.lut",
         "bodo:strpred.lut", "bodo:strpred.lut"],
        # the fourth sort starts after the window's end and the fifth
        # before its start; two LUT spans overlap, one ends outside
        [1e9, 2e9, 3e9, 11e9, -1e9, 4e9, 4.5e9, 9.5e9],
        [2e9, 3e9, 4e9, 12e9, 0.5e9, 5e9, 6e9, 12e9])
    assert routes.read(run) == pytest.approx(2 / 2)
    # [4, 6] + [9.5, 10] = 2.5 s over two queries
    assert lut.read(run) == pytest.approx(1250.0)
    assert routes.read(run_of(["bodo:join.sort"], [1e9], [2e9],
                              queries=0)) is None
