"""The control: the plain reference computed in float32, the nearest
precision below the float64 the configurations state, put in the
program's place. It has to come out as not correct under each query's own
limit, and the float64 reference against itself as correct. Host only
(pandas and numpy); the chip readings of the same control at the cells'
own sizes are in PERF.md, section 2.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from harness import compare, spec  # noqa: E402

CASES = [("tpch_q1", {"orders": 30000}), ("tpch_q5", {"orders": 30000}),
         ("taxi_weather", {"rows": 100000})]


@pytest.mark.parametrize("seed", [1, 2147483777, 2200000001])
@pytest.mark.parametrize("query,size", CASES)
def test_float32_control_fails(query, size, seed, tmp_path):
    q = spec.Query(query)
    gen = spec.load_module("gen", q.meta["generator"])
    inputs = gen.generate(dict(size, structure_seed=7), seed, str(tmp_path))
    ref = q.reference().answer(inputs)
    control = q.reference().answer(inputs, precision="float32")
    ok, compared = compare.judge([compare.answer_gap(ref, ref)], 0, q.limits)
    assert ok, compared
    ok, compared = compare.judge([compare.answer_gap(control, ref)], 0,
                                 q.limits)
    assert not ok, compared
    gap = {c["name"]: c for c in compared}["float_rel_gap"]
    # with room: the control reads at least three times the limit
    assert gap["value"] >= 3 * gap["limit"], gap
